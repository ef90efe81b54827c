"""Device idle split by the program's own layer spans.

The program marks each layer's work with a ``jax.profiler.TraceAnnotation``
whose name starts with ``repro/`` (``repro/fastq``, ``repro/map`` and its
``prep``/``dispatch``/``sync``/``fetch`` children, ``repro/pairing``,
``repro/pairing/rescue``, ``repro/sam``), with its counts as event stats.
They land on the host plane of the same trace as the device ops, on the
same clock.  ``reduce_program`` gives, for the traced window:

* ``span_s``: host seconds by span name, clipped to the window;
* ``idle_s``: by name, the device-idle seconds of the window that fall
  inside that span on the loop's thread (the line holding ``window``); an
  idle gap is split across the spans it overlaps, the innermost span taking
  each instant;
* ``idle_outside_s``: the window's device-idle seconds under no such span
  on that thread;
* ``stats``: each span name's numeric stats (``chunk`` aside), summed;
* ``count``: spans by name.

Idle is averaged over the devices, as ``devtrace``'s ``busy_s`` is.  A
trace without ``repro/`` spans (a program that has none) reduces to
nothing.  The metric readers get the reduction from ``for_run``, which
finds the run's trace file under ``.bench_work`` and checks it is the one
``run.trace`` was reduced from.
"""
from __future__ import annotations

import glob
import os
import sys
import time

import devtrace

PREFIX = "repro/"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CACHE: dict = {}


def segments(spans, lo: float, hi: float):
    """[lo, hi] cut into disjoint (start, end, name) pieces, each named by
    the innermost of ``spans`` [(name, start, end)] covering it (the one
    that started last), or None where none does."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, -e, name) for name, s, e in spans if s <= a and e >= b]
        name = max(cover)[2] if cover else None
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split_idle(idle, segs) -> dict:
    """Seconds of the sorted, disjoint ``idle`` intervals inside each
    segment's name (None: under no span)."""
    out, k = {}, 0
    for s, e in idle:
        while k < len(segs) and segs[k][1] <= s:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < e:
            a, b, name = segs[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            j += 1
    return out


def reduce_program(devices, spans, window, loop_line):
    """``devices``: per device, its op intervals [(start, end)];
    ``spans``: the ``repro/`` host spans [(name, line, start, end,
    stats)]; ``window``: (start, end); ``loop_line``: the line of the
    loop's thread.  Times in seconds.  None without such a span."""
    if not spans or not devices or window is None:
        return None
    lo, hi = window
    span_s, stats, count = {}, {}, {}
    for name, _, s, e, st in spans:
        if not lo <= s < hi:
            continue
        span_s[name] = span_s.get(name, 0.0) + max(min(e, hi) - s, 0.0)
        count[name] = count.get(name, 0) + 1
        acc = stats.setdefault(name, {})
        for k, v in st.items():
            if k != "chunk" and isinstance(v, (int, float)):
                acc[k] = acc.get(k, 0) + v
    segs = segments([(n, s, e) for n, line, s, e, _ in spans
                     if line == loop_line and e > lo and s < hi], lo, hi)
    idle = {}
    for ops in devices:
        for name, secs in split_idle(devtrace.gaps(ops, lo, hi),
                                     segs).items():
            idle[name] = idle.get(name, 0.0) + secs / len(devices)
    outside = idle.pop(None, 0.0)
    return dict(span_s=span_s, idle_s=idle, idle_outside_s=outside,
                stats=stats, count=count)


def _stat(value):
    return value if isinstance(value, (int, float, str)) else str(value)


def events(pd):
    """(devices, spans, window, loop_line) of a ``ProfileData``: each
    device's op intervals, the ``repro/`` host spans with their line
    (plane and index: every Python thread's line is named ``python``) and
    stats, the ``window`` span and its line."""
    devices, spans, window, loop_line = [], [], None, None
    for p, plane in enumerate(pd.planes):
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    devices.append([(e.start_ns * 1e-9, e.end_ns * 1e-9)
                                    for e in ln.events])
        elif plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for e in ln.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        spans.append((name, (p, i), e.start_ns * 1e-9,
                                      e.end_ns * 1e-9,
                                      {k: _stat(v) for k, v in e.stats}))
                    elif name == "window":
                        window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                        loop_line = (p, i)
    return devices, spans, window, loop_line


def reduce_file(path: str) -> dict | None:
    from jax.profiler import ProfileData
    devices, spans, window, loop_line = events(ProfileData.from_file(path))
    out = reduce_program(devices, spans, window, loop_line)
    if out is not None:
        out["window_s"] = window[1] - window[0]
    return out


def for_run(run, work: str | None = None) -> dict | None:
    """The program reduction of ``run``'s trace: the newest trace under
    ``work`` (default ``<checkout>/.bench_work``) whose window is the one
    ``run.trace`` holds; None when there is none, or it has no program
    spans.  Read once per file."""
    if run.trace is None:
        return None
    pattern = os.path.join(work or os.path.join(ROOT, ".bench_work"), "*",
                           "trace", "**", "*.xplane.pb")
    files = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)
    if not files:
        return None
    path = files[-1]
    if path not in _CACHE:
        t0 = time.perf_counter()
        try:
            _CACHE[path] = reduce_file(path)
        except Exception as exc:   # a reader returns nothing, never raises
            print(f"progtrace: {path}: {exc!r}", file=sys.stderr)
            _CACHE[path] = None
        out = _CACHE[path]
        print(f"progtrace: program spans read in "
              f"{time.perf_counter() - t0:.3f} s: "
              + ("none" if out is None else
                 f"idle_s {out['idle_s']}, idle_outside_s "
                 f"{out['idle_outside_s']}, span_s {out['span_s']}, "
                 f"count {out['count']}, stats {out['stats']}"),
              file=sys.stderr, flush=True)
    out = _CACHE[path]
    if out is None or out["window_s"] != run.trace["window_s"]:
        return None
    return out
