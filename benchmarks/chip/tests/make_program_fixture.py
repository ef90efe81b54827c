"""Cut a text-proto excerpt with the program's spans out of a chip trace.

    python3 benchmarks/chip/tests/make_program_fixture.py TRACE.xplane.pb \
        OUT.pbtxt [--ms 1500] [--max-ops 20000]

Like ``make_trace_fixture.py``, from the first ``--ms`` milliseconds after
the ``window`` span opens, but for the program reduction
(``progtrace.py``): every host line that holds a ``repro/`` span, a
harness layer span or the ``window`` keeps those events whole, with their
stats, on a line of its own (the loop's thread and the fetch thread), and
a ``window`` span over the slice goes on the loop's line.  The device's
``XLA Ops`` line becomes the union of its op intervals (busy time and idle
gaps as recorded); where that union has more than ``--max-ops`` intervals,
gaps shorter than a threshold, doubled from 0.1 us until it fits, are
closed, and the threshold is written at the top of the file.
``jax.profiler.ProfileData.from_text_proto`` reads the result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402
from make_trace_fixture import _quote  # noqa: E402


def _stat_text(mid: int, value) -> str:
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        text = _quote(str(value))
        return f"stats {{ metadata_id: {mid} str_value: {text} }}"
    if isinstance(value, int):
        return f"stats {{ metadata_id: {mid} int64_value: {value} }}"
    return f"stats {{ metadata_id: {mid} double_value: {value!r} }}"


def plane_text(pid: int, name: str, lines: list) -> str:
    """One plane: ``lines`` [(line name, [(event name, start, end,
    stats)])], times in seconds."""
    meta, stat_meta = {}, {}
    out = [f"planes {{\n  id: {pid}\n  name: {_quote(name)}"]
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(lname)}\n"
                   f"    timestamp_ns: 0")
        for ename, s, e, stats in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            body = " ".join(
                _stat_text(stat_meta.setdefault(k, len(stat_meta) + 1), v)
                for k, v in stats.items())
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{round(s * 1e12)} duration_ps: "
                       f"{round((e - s) * 1e12)} {body} }}")
        out.append("  }")
    for ename, mid in meta.items():
        out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: {_quote(ename)} }} }}")
    for sname, sid in stat_meta.items():
        out.append(f"  stat_metadata {{ key: {sid} value {{ id: {sid} "
                   f"name: {_quote(sname)} }} }}")
    out.append("}")
    return "\n".join(out)


def close_gaps(busy, max_ops: int):
    """``busy`` (sorted, disjoint) with gaps shorter than a threshold
    closed, the threshold doubled from 0.1 us until at most ``max_ops``
    intervals remain; returns (intervals, threshold in seconds)."""
    thr = 0.0
    while len(busy) > max_ops:
        thr = 1e-7 if thr == 0.0 else 2 * thr
        out = [list(busy[0])]
        for s, e in busy[1:]:
            if s - out[-1][1] < thr:
                out[-1][1] = e
            else:
                out.append([s, e])
        busy = [(s, e) for s, e in out]
    return busy, thr


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=1500.0)
    ap.add_argument("--max-ops", type=int, default=20000)
    a = ap.parse_args(argv)
    pd = ProfileData.from_file(a.trace)
    window = None
    host = []                     # [(line events)] of the kept host lines
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            kept = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                     {k: v for k, v in e.stats})
                    for e in ln.events
                    if e.name.startswith("repro/") or e.name == "window"
                    or e.name in devtrace.HOST_SPANS]
            if kept:
                host.append(kept)
            for name, s, e, _ in kept:
                if name == "window":
                    window = (s, e)
    lo = window[0]
    hi = min(lo + a.ms * 1e-3, window[1])
    t0 = lo - 1e-3
    planes, thr = [], 0.0
    for i, (ops, modules) in enumerate(
            devtrace._device_events(p) for p in pd.planes
            if p.name.startswith("/device:")
            and any(ln.name == "XLA Ops" for ln in p.lines)):
        busy, thr_i = close_gaps(
            devtrace.merge(devtrace.clip([(s, e) for s, e, _ in ops],
                                         lo, hi)), a.max_ops)
        thr = max(thr, thr_i)
        planes.append(plane_text(i + 1, f"/device:TPU:{i}", [
            ("XLA Modules", [(n, s - t0, e - t0, {}) for s, e, n in modules
                             if s >= lo and e <= hi]),
            ("XLA Ops", [("busy", s - t0, e - t0, {}) for s, e in busy])]))
    lines = []
    for kept in host:
        evs = [(n, s - t0, e - t0, st) for n, s, e, st in kept
               if n != "window" and s >= lo and e <= hi]
        if any(n == "window" for n, *_ in kept):
            evs.append(("window", lo - t0, hi - t0, {}))
        if evs:
            lines.append(("python", evs))
    planes.append(plane_text(len(planes) + 1, "/host:CPU", lines))
    head = (f"# device idle gaps under {thr * 1e6:g} us closed\n" if thr
            else "")
    Path(a.out).write_text(head + "\n".join(planes) + "\n")
    print(f"{a.out}: {sum(len(e) for _, e in lines)} host events; "
          + head.strip("# \n"), file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
