"""Build a persistent sharded genome index from a FASTA, out of core.

    PYTHONPATH=src python -m repro.launch.build_index ref.fa -o ref.idx \
        --partitions 8 --tile-bp 1048576
    PYTHONPATH=src python -m repro.launch.map_fastq --index-dir ref.idx \
        reads.fq -o out.sam

One pass over the FASTA in ``--tile-bp`` tiles (peak memory is bounded
by the tile, not the genome), partitioned by the crossbar rule
``hash32(kmer) % partitions``; the output directory holds a versioned
JSON manifest, per-partition memmap CSR files with 2-bit packed
segments, and the 2-bit packed reference — everything ``map_fastq
--index-dir`` needs, on both topologies (``--partitions`` must equal
the mesh device count for ``--topology mesh``).
"""
from __future__ import annotations

import argparse
import sys
import time


def run(args) -> int:
    from repro.index import build_sharded_index, verify_index
    from repro.launch.map_fastq import _metrics_snapshot
    from repro.obs import logjson
    from repro.obs import registry as _metrics
    from repro.obs import tracing as _tracing

    trace_out = getattr(args, "trace_out", None)
    metrics_out = getattr(args, "metrics_out", None)
    log_on = getattr(args, "log_json", False) and not logjson.enabled()
    metrics_on = metrics_out is not None and _metrics.ACTIVE is None
    tracing_on = trace_out is not None and _tracing.ACTIVE is None
    if log_on:
        logjson.enable("build_index")
    if metrics_on:
        _metrics.enable_metrics()
    if tracing_on:
        _tracing.enable_tracing()
    try:
        t0 = time.perf_counter()
        say = (lambda msg: logjson.say(f"build_index: {msg}",
                                       event="progress"))
        idx = build_sharded_index(
            args.reference, args.output, num_partitions=args.partitions,
            tile_bp=args.tile_bp, read_len=args.read_len, k=args.k,
            w=args.w, eth=args.eth, max_pls_per_minimizer=args.max_pls,
            overwrite=args.force, origin=args.origin, progress=say)
        if args.verify:
            verify_index(args.output)
            say("full integrity check passed")
        stor = idx.storage_bytes()
        bstats = (idx.manifest or {}).get("build", {})
        dt = time.perf_counter() - t0
        logjson.say(
            f"build_index: {args.output}: {idx.num_partitions} "
            f"partitions, {len(idx.contigs)} contig(s), {idx.ref_len} "
            f"bases, {idx.n_occurrences} occurrences, "
            f"{stor['total_bytes']} B on disk ({stor['blowup']:.1f}x "
            f"segment blowup), {bstats.get('spill_bytes', 0)} spill B "
            f"in {dt:.1f}s",
            event="done", partitions=idx.num_partitions,
            ref_len=idx.ref_len, occurrences=idx.n_occurrences,
            bytes_on_disk=stor["total_bytes"],
            spill_bytes=bstats.get("spill_bytes", 0), wall_s=round(dt, 3))
        return 0
    finally:
        if metrics_out is not None and _metrics.ACTIVE is not None:
            open(metrics_out, "w").close()
            _metrics_snapshot(metrics_out, seq=0)
        if trace_out is not None and _tracing.ACTIVE is not None:
            _tracing.ACTIVE.export(trace_out)
        if tracing_on:
            _tracing.disable_tracing()
        if metrics_on:
            _metrics.disable_metrics()
        if log_on:
            logjson.disable()


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="repro.launch.build_index",
        description="Build a sharded on-disk genome index from a FASTA "
                    "(streamed; bounded memory).")
    ap.add_argument("reference", help="FASTA reference (multi-contig ok; "
                                      "N -> never-matching sentinel)")
    ap.add_argument("-o", "--output", required=True,
                    help="output index directory")
    ap.add_argument("--partitions", type=int, default=4,
                    help="partition count (power of two; use the mesh "
                         "device count for --topology mesh mapping)")
    ap.add_argument("--tile-bp", type=int, default=1 << 20,
                    help="scan tile size in bases — the peak-memory knob")
    ap.add_argument("--read-len", type=int, default=150,
                    help="read length the segment geometry is sized for")
    ap.add_argument("--k", type=int, default=12)
    ap.add_argument("--w", type=int, default=30)
    ap.add_argument("--eth", type=int, default=6)
    ap.add_argument("--max-pls", type=int, default=256,
                    help="occurrence cap per hyper-repetitive minimizer")
    ap.add_argument("--origin", type=int, default=0,
                    help="global position of the reference's first base "
                         "(format v2): occurrence positions are recorded "
                         "at origin + offset, so multi-host builds can "
                         "split one coordinate space")
    ap.add_argument("--force", action="store_true",
                    help="rebuild over an existing index directory")
    ap.add_argument("--verify", action="store_true",
                    help="re-read and digest-check every file after the "
                         "build")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the build as Chrome trace-event JSON "
                         "(scan + per-partition finalize spans)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a final JSONL metrics snapshot (schema: "
                         "schemas/metrics_snapshot.schema.json)")
    ap.add_argument("--log-json", action="store_true",
                    help="structured one-object-per-line JSON progress "
                         "on stderr")
    args = ap.parse_args(argv)
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    return run(args)


if __name__ == "__main__":
    raise SystemExit(main())
