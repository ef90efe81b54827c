"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

What a reader of the trace gets:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* ``window_s``: the length of the window (the host span named ``window``);
* ``module_s``: device seconds per jitted module (``jit_seed_reads``,
  ``jit__linear_stage_impl``, ...), from the device's module line, with any
  ``(id)`` suffix dropped;
* ``device_ops``: the ten ``module/op`` names with the most device time;
* ``idle_gaps``: the ten longest intervals of the window in which no
  operation ran, each named by the host span (``ingest``, ``mapper``,
  ``pairing``, ``sam_emit``) that overlapped it most, or ``none``.

Device and host events of one trace share its clock.  The interval
arithmetic takes plain lists, so it is checked apart from the file format.
"""
from __future__ import annotations

import glob
import os
import re

HOST_SPANS = ("ingest", "mapper", "pairing", "sam_emit")
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """``while.39`` from an op event named by its HLO text
    (``%while.39 = (s32[], ...) while(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(busy, lo, hi):
    """The parts of [lo, hi] that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, spans) -> str:
    """Name of the host span overlapping ``gap`` most, or ``none``."""
    best, best_len = "none", 0.0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_len:
            best, best_len = name, ov
    return best


def reduce_events(devices, spans, window):
    """``devices``: per device, a dict with ``ops`` [(start, end, name)]
    and ``modules`` [(start, end, name)]; ``spans``: host spans [(name,
    start, end)]; ``window``: (start, end).  Times in seconds."""
    lo, hi = window
    busy_each, module_s, op_s, idle = [], {}, {}, []
    for dev in devices:
        ops = [(s, e) for s, e, _ in dev["ops"]]
        busy_each.append(union_length(clip(ops, lo, hi)))
        for s, e, name in dev["modules"]:
            c = clip([(s, e)], lo, hi)
            if c:
                key = module_name(name)
                module_s[key] = module_s.get(key, 0.0) + c[0][1] - c[0][0]
        for s, e, name in dev["ops"]:
            c = clip([(s, e)], lo, hi)
            if c:
                op_s[name] = op_s.get(name, 0.0) + c[0][1] - c[0][0]
        idle += [(label(g, spans), g[1] - g[0]) for g in gaps(ops, lo, hi)]
    n = max(len(devices), 1)
    return dict(
        busy_s=sum(busy_each) / n,
        window_s=hi - lo,
        module_s=module_s,
        device_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:10],
        idle_gaps=sorted(idle, key=lambda kv: -kv[1])[:10])


def _device_events(plane):
    """(ops, modules) of one device plane: its ``XLA Ops`` line named
    ``module/op`` by the enclosing module event, and its ``XLA Modules``
    line."""
    lines = {ln.name: ln for ln in plane.lines}
    modules = [(e.start_ns * 1e-9, e.end_ns * 1e-9, module_name(e.name))
               for e in (lines["XLA Modules"].events
                         if "XLA Modules" in lines else [])]
    mods = sorted(modules)
    ops, k = [], 0
    for e in sorted(lines["XLA Ops"].events, key=lambda e: e.start_ns):
        s, t = e.start_ns * 1e-9, e.end_ns * 1e-9
        while k + 1 < len(mods) and mods[k + 1][0] <= s:
            k += 1
        owner = mods[k][2] if mods and mods[k][0] <= s <= mods[k][1] else "?"
        ops.append((s, t, f"{owner}/{op_name(e.name)}"))
    return ops, modules


def load(path: str):
    """Device events and host spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(path))


def events(pd):
    """(devices, host spans, window) of a ``ProfileData``."""
    devices, spans, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and any(
                ln.name == "XLA Ops" for ln in plane.lines):
            ops, modules = _device_events(plane)
            devices.append(dict(ops=ops, modules=modules))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9))
                    elif e.name == "window":
                        window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
    return devices, spans, window


def find(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def reduce_file(path: str) -> dict | None:
    """The reduction of one trace, or None where it holds no device
    operation or no ``window`` span."""
    devices, spans, window = load(path)
    return reduce_or_none(devices, spans, window)


def reduce_or_none(devices, spans, window) -> dict | None:
    if not devices or window is None:
        return None
    return reduce_events(devices, spans, window)
