"""Cut a small text-proto excerpt out of a recorded chip trace.

    python3 benchmarks/chip/tests/make_trace_fixture.py TRACE.xplane.pb \
        OUT.pbtxt [--ms 400]

Keeps, from the first ``--ms`` milliseconds after the ``window`` span opens,
the device planes' ``XLA Modules`` events, their ``XLA Ops`` events of at
least ``--min-us`` microseconds (the loops of the jnp stages emit an event
per iteration, far too many to keep), the host's layer spans, with their
names and times, and a ``window`` span over that slice.
``jax.profiler.ProfileData.from_text_proto`` reads the result, so the
reducer's tests run on real event names and timings without the recorded
file's size.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import devtrace as trace  # noqa: E402


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _plane(pid: int, name: str, lines: dict) -> str:
    meta, out = {}, [f"planes {{\n  id: {pid}\n  name: {_quote(name)}"]
    for lid, (lname, events) in enumerate(lines.items(), 1):
        out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(lname)}\n"
                   f"    timestamp_ns: 0")
        for ename, s, e in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{round(s * 1e12)} duration_ps: "
                       f"{round((e - s) * 1e12)} }}")
        out.append("  }")
    for ename, mid in meta.items():
        out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: {_quote(ename)} }} }}")
    out.append("}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=400.0)
    ap.add_argument("--min-us", type=float, default=20.0)
    a = ap.parse_args(argv)
    devices, spans, window = trace.load(a.trace)
    lo = window[0]
    hi = lo + a.ms * 1e-3
    t0 = lo - 1e-3

    def cut(events):
        return [(n, s - t0, e - t0) for s, e, n in events
                if s >= lo and e <= hi]
    planes = []
    for i, dev in enumerate(devices):
        planes.append(_plane(i + 1, f"/device:TPU:{i}", {
            "XLA Modules": cut(dev["modules"]),
            "XLA Ops": [(n.split("/", 1)[1], s, e)
                        for n, s, e in cut(dev["ops"])
                        if e - s >= a.min_us * 1e-6]}))
    host = [(n, s - t0, e - t0) for n, s, e in spans if s >= lo and e <= hi]
    host.append(("window", lo - t0, hi - t0))
    planes.append(_plane(len(devices) + 1, "/host:CPU", {"python3": host}))
    Path(a.out).write_text("\n".join(planes) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
