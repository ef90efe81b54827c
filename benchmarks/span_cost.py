"""What the program's layer spans (``repro.obs.tracing.span``) cost.

    PYTHONPATH=src python3 benchmarks/span_cost.py [--calls 200000] \
        [--se-spans 12] [--pe-spans 24]

Times one span as the layers open it (two counts at entry, one more set at
exit) with no sink recording, and again while a ``jax.profiler`` trace
records, and prints the cost per span and per 1,024-read chunk: a chunk of
single-end reads passes ``--se-spans`` span sites (``repro/fastq``,
``repro/map`` with one ``prep``, five ``dispatch``, two ``sync`` and one
``fetch``, ``repro/sam``), a chunk of 1,024 pairs ``--pe-spans`` (the
mapper's sites twice, ``repro/pairing`` and two ``repro/pairing/rescue``).
The last line of stdout is the result's JSON, with the device it ran on.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time


def per_span_us(calls: int) -> float:
    from repro.obs.tracing import span
    t0 = time.perf_counter()
    for _ in range(calls):
        with span("repro/map", reads=1024, lin_cap=4096) as sp:
            sp.set_metadata(syncs=12)
    return 1e6 * (time.perf_counter() - t0) / calls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=200_000)
    ap.add_argument("--se-spans", type=int, default=12)
    ap.add_argument("--pe-spans", type=int, default=24)
    a = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    per_span_us(1000)                                   # warm
    off = min(per_span_us(a.calls) for _ in range(3))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = min(per_span_us(a.calls // 10) for _ in range(3))
        finally:
            jax.profiler.stop_trace()
    out = dict(platform=dev.platform, device_kind=dev.device_kind,
               off_us_per_span=off, on_us_per_span=on,
               off_us_per_chunk=dict(se=off * a.se_spans,
                                     pe=off * a.pe_spans),
               on_us_per_chunk=dict(se=on * a.se_spans, pe=on * a.pe_spans))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
