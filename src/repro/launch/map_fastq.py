"""FASTA + FASTQ -> SAM, end-to-end over the ``Mapper`` session.

    PYTHONPATH=src python -m repro.launch.map_fastq ref.fa reads.fq \
        -o out.sam
    PYTHONPATH=src python -m repro.launch.map_fastq ref.fa \
        --r1 reads_R1.fastq.gz --r2 reads_R2.fastq.gz -o out.sam
    PYTHONPATH=src python -m repro.launch.map_fastq ref.fa pairs.fq \
        --interleaved -o out.sam --topology mesh --shards 4

The real-data boundary of the reproduction: a (multi-contig) FASTA
reference is indexed, FASTQ reads stream through the session in
``--chunk-reads`` batches — each chunk mapped on **both strands**
(forward + reverse complement; ``--single-strand`` disables) — and
spec-valid SAM comes out.  Plain and ``.gz`` FASTQ parse identically.

Single-end input (one positional FASTQ) emits FLAG 0x4/0x10 records
with MAPQ 255 (no quality model on this path — unchanged output).
Paired-end input (``--r1``/``--r2`` or ``--interleaved``) maps both
mates of every pair in one stacked batch, resolves proper pairs
host-side (FR orientation, insert window from a running median, mate
rescue — see ``repro.core.pairing``) and emits the full pairing FLAGs
(0x1/0x2/0x8/0x20/0x40/0x80), RNEXT/PNEXT/TLEN, and calibrated MAPQ.
``--topology mesh`` routes chunks onto the distributed all_to_all
mapper; its stage B computes distances/positions only, so mesh records
carry CIGAR ``*`` (strand/POS/NM/pairing still present).

Progress and the closing unified-stats lines go to stderr, so ``-o -``
pipes clean SAM to stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _open_stream(args, injector=None):
    """Build the FASTQ stream per input layout -> (stream, paired)."""
    from repro.io.fastq import FastqStream, PairedFastqStream

    kw = dict(read_len=args.read_len, chunk_reads=args.chunk_reads,
              on_error=args.on_error, rejects=args.rejects,
              injector=injector)
    if args.r2 is not None and args.r1 is None:
        raise SystemExit("map_fastq: --r2 needs --r1")
    if args.r1 is not None:
        if args.reads is not None:
            raise SystemExit("map_fastq: pass either a positional FASTQ or "
                             "--r1/--r2, not both")
        if args.r2 is None:
            raise SystemExit("map_fastq: --r1 needs --r2 (or use "
                             "--interleaved with a single file)")
        if args.interleaved:
            raise SystemExit("map_fastq: --interleaved takes a single "
                             "positional FASTQ, not --r1/--r2")
        return PairedFastqStream(args.r1, args.r2, **kw), True
    if args.reads is None:
        raise SystemExit("map_fastq: no reads given (positional FASTQ or "
                         "--r1/--r2)")
    if args.interleaved:
        return PairedFastqStream(args.reads, interleaved=True, **kw), True
    return FastqStream(args.reads, **kw), False


def _metrics_snapshot(path, seq: int) -> None:
    """Append one registry snapshot line to the ``--metrics-out`` JSONL
    (schema: ``schemas/metrics_snapshot.schema.json``)."""
    from repro.obs import registry as _metrics
    reg = _metrics.ACTIVE
    if path is None or reg is None:
        return
    rec = dict(kind="metrics_snapshot", seq=seq, ts_unix_s=time.time())
    rec.update(reg.snapshot())
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def run(args) -> int:
    """Entry point: arms the ``--log-json``/``--metrics-out``/
    ``--trace-out`` surfaces around the mapping run and always tears
    them down — the trace is exported even when the run fails, so a
    crash still leaves an inspectable timeline."""
    from repro.obs import logjson
    from repro.obs import registry as _metrics
    from repro.obs import tracing as _tracing

    trace_out = getattr(args, "trace_out", None)
    metrics_out = getattr(args, "metrics_out", None)
    log_on = getattr(args, "log_json", False) and not logjson.enabled()
    metrics_on = metrics_out is not None and _metrics.ACTIVE is None
    tracing_on = trace_out is not None and _tracing.ACTIVE is None
    if log_on:
        logjson.enable("map_fastq")
    if metrics_on:
        _metrics.enable_metrics()
    if tracing_on:
        _tracing.enable_tracing()
    # the closing stats are re-derived from the registry only when this
    # run owns a fresh one (counters from an inherited registry would
    # include earlier runs)
    args.obs_fresh_registry = metrics_on
    if metrics_out is not None:
        open(metrics_out, "w").close()   # truncate; snapshots append
    try:
        return _run(args)
    finally:
        if trace_out is not None and _tracing.ACTIVE is not None:
            _tracing.ACTIVE.export(trace_out)
        if tracing_on:
            _tracing.disable_tracing()
        if metrics_on:
            _metrics.disable_metrics()
        if log_on:
            logjson.disable()


def _run(args) -> int:
    from repro.core.index import build_index
    from repro.core.mapper import (Mapper, accumulate_partition_stats,
                                   accumulate_stats, totals_from_registry)
    from repro.obs import logjson
    from repro.core.pairing import InsertSizeTracker, resolve_pairs
    from repro.core.pipeline import MapperConfig
    from repro.core.resilience import FaultInjector, ResilientMapper
    from repro.io.fasta import ReferenceMap, load_reference

    from repro.io.sam import (emit_alignments, emit_paired_alignments,
                              sam_header)
    from repro.launch.runtime import device_line

    t0 = time.perf_counter()
    injector = (FaultInjector.from_spec(args.inject)
                if args.inject is not None else None)
    sharded = None
    if args.index_dir is not None:
        from repro.index import open_index
        sharded = open_index(args.index_dir)
        if args.read_len is not None and args.read_len != sharded.read_len:
            raise SystemExit(
                f"map_fastq: --read-len {args.read_len} conflicts with the "
                f"index's read_len={sharded.read_len} — segment geometry "
                f"is fixed at build time; rebuild with "
                f"repro.launch.build_index --read-len {args.read_len}")
        args.read_len = sharded.read_len
        for name in ("k", "w", "eth"):
            if getattr(args, name) != getattr(sharded, name):
                print(f"map_fastq: --{name} {getattr(args, name)} ignored; "
                      f"index manifest has {name}="
                      f"{getattr(sharded, name)}", file=sys.stderr)
                setattr(args, name, getattr(sharded, name))
    stream, paired = _open_stream(args, injector)
    rl = stream.read_len
    if sharded is not None:
        contigs = sharded.contigs
        refmap = sharded.reference_map()
        # only the paired-end mate-rescue scan needs the genome itself;
        # single-end runs stay on the mmap'd packed reference
        ref = sharded.reference_codes() if paired else None
        n_indexed = sharded.ref_len
        idx = sharded
    else:
        # spacer >= one alignment window: no read can map across a boundary
        rejected_contigs: list = []
        ref, contigs = load_reference(args.reference,
                                      spacer=rl + 2 * args.eth,
                                      on_error=args.on_error,
                                      rejected=rejected_contigs)
        for cname, why in rejected_contigs:
            print(f"map_fastq: skipped contig {cname!r}: {why}",
                  file=sys.stderr)
        refmap = ReferenceMap(contigs)
        n_indexed = len(ref)
        idx = build_index(ref, read_len=rl, k=args.k, w=args.w,
                          eth=args.eth)
    cfg = MapperConfig.from_index(
        idx, engine=args.engine, wf_backend=args.wf_backend,
        chunk_reads=args.chunk_reads, stream=not args.no_stream,
        both_strands=not args.single_strand,
        # --trace-out needs per-stage times on the streamed path: spans
        # are emitted from the same perf_counter reads that build
        # stage_times_s, so the trace and the stats agree by construction
        profile=getattr(args, "trace_out", None) is not None)
    budget = (int(args.index_budget_mb * (1 << 20))
              if args.index_budget_mb is not None else None)
    if args.prefetch and (sharded is None or args.topology != "single"):
        raise SystemExit(
            "map_fastq: --prefetch needs --index-dir with --topology "
            "single — only the shard-routed arena path has per-chunk "
            "partition uploads to overlap")
    mapper = Mapper(idx, cfg, topology=args.topology, n_shards=args.shards,
                    injector=injector, watchdog_s=args.watchdog,
                    memory_budget_bytes=budget, prefetch=args.prefetch)
    # fault containment (retry/bisect/degrade) is armed alongside the
    # injector or a permissive run; a plain strict run keeps today's
    # fail-fast behaviour with zero wrapping
    resilient = (ResilientMapper(mapper, injector=injector)
                 if injector is not None or args.on_error == "permissive"
                 else None)
    src = (f"index {args.index_dir} ({sharded.num_partitions} partitions)"
           if sharded is not None else "in-memory index")
    logjson.say(
        f"map_fastq: {len(contigs)} contig(s), {n_indexed} indexed bases "
        f"({src}), read_len={rl}, topology={mapper.topology}, "
        f"paired={paired}, both_strands={cfg.both_strands}, "
        f"engine={cfg.engine}, wf_backend={cfg.wf_backend}, "
        f"{device_line()}",
        event="start", contigs=len(contigs), indexed_bases=n_indexed,
        read_len=rl, topology=mapper.topology, paired=paired,
        engine=cfg.engine, wf_backend=cfg.wf_backend)

    # resume-safe atomic output: SAM accumulates in a .partial segment
    # and lands on the final path in one os.replace only after a clean
    # finish — an interrupted run can never leave a truncated file that
    # looks complete
    partial = None if args.output == "-" else args.output + ".partial"
    out = sys.stdout if partial is None else open(partial, "w")
    totals = dict(reads=0, mapped=0, reverse_best=0, survivors=0,
                  affine_instances=0, padded_affine_instances=0,
                  dropped_send=0, dropped_affine=0,
                  pairs=0, proper=0, rescued=0)
    saw_stats = False
    tracker = InsertSizeTracker()
    contig_starts = [c.offset for c in contigs]
    try:
        for line in sam_header(contigs,
                               command_line=" ".join(sys.argv)):
            out.write(line + "\n")
        t_map = time.perf_counter()
        n_chunks = 0
        for i, chunk in enumerate(stream):
            n_chunks = i + 1
            if paired:
                c1, c2 = chunk
                if resilient is not None:
                    res1, res2, _ = resilient.map_pairs(c1.reads, c2.reads)
                    if res1 is None:  # every block failed after retries
                        print(f"chunk {i}: all {2 * len(c1)} reads failed "
                              f"after retries; chunk quarantined",
                              file=sys.stderr)
                        totals["reads"] += 2 * len(c1)
                        continue
                else:
                    res1, res2 = mapper.map_pairs(c1.reads, c2.reads)
                pr = resolve_pairs(res1, res2, cfg=cfg, tracker=tracker,
                                   ref=ref, reads1=c1.reads,
                                   reads2=c2.reads,
                                   contig_starts=contig_starts)
                for rec in emit_paired_alignments(
                        pr, c1.names, c1.reads, c1.quals, c2.reads,
                        c2.quals, refmap, seqs1=c1.seqs, seqs2=c2.seqs):
                    out.write(rec + "\n")
                n_new = 2 * len(c1)
                n_mapped = int(pr.res1.mapped.sum() + pr.res2.mapped.sum())
                res = res1  # stats object is shared by both halves
                for r in (pr.res1, pr.res2):
                    if r.strand is not None:
                        totals["reverse_best"] += int((r.strand
                                                       & r.mapped).sum())
                totals["pairs"] += pr.stats["n_pairs"]
                totals["proper"] += pr.stats["n_proper"]
                totals["rescued"] += pr.stats["n_rescued"]
                extra = (f", proper {pr.stats['n_proper']}/"
                         f"{pr.stats['n_pairs']} "
                         f"(insert median {pr.stats['insert_median']})")
            else:
                if resilient is not None:
                    res, mask, _ = resilient.map(chunk.reads)
                    if res is None:  # every block failed after retries
                        print(f"chunk {i}: all {len(chunk)} reads failed "
                              f"after retries; chunk quarantined",
                              file=sys.stderr)
                        totals["reads"] += len(chunk)
                        continue
                else:
                    res = mapper.map(chunk.reads)
                for rec in emit_alignments(res, chunk.names, chunk.reads,
                                           chunk.quals, refmap,
                                           seqs=chunk.seqs):
                    out.write(rec + "\n")
                n_new = len(chunk)
                n_mapped = int(res.mapped.sum())
                if res.strand is not None:  # from the result, not stats:
                    #                         the padded engine has stats=None
                    totals["reverse_best"] += int((res.strand
                                                   & res.mapped).sum())
                extra = ""
            totals["reads"] += n_new
            totals["mapped"] += n_mapped
            if res.stats is not None:
                saw_stats = True
                accumulate_stats(totals, res.stats, fields=(
                    "survivors", "affine_instances",
                    "padded_affine_instances", "dropped_send",
                    "dropped_affine"))
                accumulate_partition_stats(totals, res.stats)
            out.flush()  # each chunk's records land in the .partial segment
            _metrics_snapshot(getattr(args, "metrics_out", None), seq=i)
            rate = totals["reads"] / max(time.perf_counter() - t_map, 1e-9)
            logjson.say(
                f"chunk {i}: {n_new} reads, "
                f"mapped {n_mapped / max(n_new, 1):.3f} "
                f"(cumulative {totals['reads']} reads, {rate:.0f} reads/s)"
                f"{extra}",
                event="chunk", chunk=i, reads=n_new, mapped=n_mapped,
                cumulative_reads=totals["reads"],
                reads_per_s=round(rate, 1))
        complete = True
    except BaseException:
        complete = False
        raise
    finally:
        if out is not sys.stdout:
            out.close()
        if partial is not None:
            if complete:  # atomic landing: complete output or none
                os.replace(partial, args.output)
            else:
                print(f"map_fastq: run did not complete; partial SAM "
                      f"left at {partial}", file=sys.stderr)

    dt = time.perf_counter() - t0
    skipped = (f", skipped {stream.n_skipped} short" if stream.n_skipped
               else "") + (f", truncated {stream.n_truncated} long"
                           if stream.n_truncated else "")
    logjson.say(
        f"done: {totals['reads']} reads in {dt:.1f}s "
        f"({totals['reads']/max(dt, 1e-9):.0f} reads/s incl. index build), "
        f"mapped {totals['mapped']} "
        f"({totals['reverse_best']} reverse-strand){skipped}",
        event="done", reads=totals["reads"], mapped=totals["mapped"],
        wall_s=round(dt, 3))
    if stream.n_rejected:
        reasons = dict(getattr(stream, "reject_reasons", {}))
        subs = {id(s): s for s in (getattr(stream, "_s1", None),
                                   getattr(stream, "_s2", None))
                if s is not None}
        for s in subs.values():  # paired: fold in both mates' counts once
            for k, v in s.reject_reasons.items():
                reasons[k] = reasons.get(k, 0) + v
        where = f" -> {args.rejects}" if args.rejects else ""
        print(f"quarantined: {stream.n_rejected} malformed record(s) "
              f"{reasons}{where}", file=sys.stderr)
    if resilient is not None:
        rc = resilient.counters
        if any(rc.values()) or resilient.ladder.degraded:
            print(f"resilience: {rc['retries']} retries, "
                  f"{rc['failed_reads']} quarantined reads in "
                  f"{rc['failed_blocks']} block(s), engine ladder "
                  f"{resilient.ladder.describe()}", file=sys.stderr)
    if paired:
        lo, hi = tracker.window()
        print(f"pairing: {totals['proper']}/{totals['pairs']} proper, "
              f"{totals['rescued']} rescued, insert median "
              f"{tracker.median} window [{lo}, {hi}]", file=sys.stderr)
    if saw_stats:
        if getattr(args, "obs_fresh_registry", False):
            # re-derive the engine counters from the metrics registry so
            # the closing lines and the exported snapshots can never
            # disagree (the registry counts every engine run)
            derived = totals_from_registry(mapper.topology)
            if derived is not None:
                for k in ("survivors", "affine_instances",
                          "padded_affine_instances", "dropped_send",
                          "dropped_affine"):
                    totals[k] = derived[k]
        from repro.launch.serve import _print_mapper_stats
        _print_mapper_stats(mapper, totals, file=sys.stderr)
    else:  # padded reference engine: no instance accounting to report
        print(f"plan cache: {mapper.plan_cache_hits} hits / "
              f"{mapper.plan_cache_misses} misses", file=sys.stderr)
    _metrics_snapshot(getattr(args, "metrics_out", None), seq=n_chunks)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="repro.launch.map_fastq",
        description="Map a FASTQ read set against a FASTA reference; "
                    "emit SAM.")
    ap.add_argument("reference", nargs="?", default=None,
                    help="FASTA reference (multi-contig ok; N -> "
                         "never-matching sentinel); omit when mapping "
                         "against a prebuilt --index-dir")
    ap.add_argument("reads", nargs="?", default=None,
                    help="FASTQ reads (4-line records; .gz ok) — "
                         "single-end, or interleaved pairs with "
                         "--interleaved")
    ap.add_argument("--index-dir", default=None, metavar="DIR",
                    help="prebuilt sharded index directory "
                         "(repro.launch.build_index) instead of indexing "
                         "a FASTA at startup; geometry comes from the "
                         "manifest")
    ap.add_argument("--index-budget-mb", type=float, default=None,
                    metavar="MB",
                    help="--index-dir + single topology: device budget "
                         "for the partition arena; partitions load "
                         "lazily and LRU-evict under this bound")
    ap.add_argument("--prefetch", action="store_true",
                    help="--index-dir + single topology: stage the next "
                         "chunk's partition uploads on a background "
                         "worker while the current chunk computes "
                         "(bit-identical results)")
    ap.add_argument("--r1", default=None,
                    help="paired-end R1 FASTQ (.gz ok); requires --r2")
    ap.add_argument("--r2", default=None,
                    help="paired-end R2 FASTQ (.gz ok)")
    ap.add_argument("--interleaved", action="store_true",
                    help="the positional FASTQ holds interleaved R1/R2 "
                         "records")
    ap.add_argument("-o", "--output", default="-",
                    help="output SAM path ('-' = stdout; progress goes to "
                         "stderr either way)")
    ap.add_argument("--topology", default="single",
                    choices=("single", "mesh"))
    ap.add_argument("--shards", type=int, default=None,
                    help="mesh topology: shard count (default: all devices)")
    ap.add_argument("--chunk-reads", type=int, default=1024,
                    help="FASTQ batch size == engine streaming chunk")
    ap.add_argument("--read-len", type=int, default=None,
                    help="fixed read length (default: first FASTQ record)")
    ap.add_argument("--single-strand", action="store_true",
                    help="forward strand only (reverse-strand reads will "
                         "not map)")
    ap.add_argument("--engine", default="compacted",
                    choices=("compacted", "fused", "padded"))
    ap.add_argument("--wf-backend", default="jnp", choices=("jnp", "pallas"))
    ap.add_argument("--no-stream", action="store_true",
                    help="synchronous debug path (per-stage timings)")
    ap.add_argument("--on-error", default="strict",
                    choices=("strict", "permissive"),
                    help="malformed-input policy: strict raises with "
                         "file:line context; permissive quarantines bad "
                         "records (counted; see --rejects) and keeps "
                         "mapping")
    ap.add_argument("--rejects", default=None,
                    help="permissive mode: write quarantined raw FASTQ "
                         "records to this file (.gz ok)")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'bucket=0.125,record=0.005,seed=3' (sites: "
                         "bucket, record, stall, error, flush; plus "
                         "seed=, stall_s=, poison=r1;r2, "
                         "engines=fused;pallas) — chaos testing")
    ap.add_argument("--watchdog", type=float, default=None, metavar="S",
                    help="streaming fetch watchdog seconds: a stalled "
                         "chunk fetch fails (and is retried/quarantined) "
                         "instead of hanging the run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the run as Chrome trace-event JSON "
                         "(loadable in Perfetto / chrome://tracing); "
                         "implies per-stage profiling, so the span "
                         "durations equal stage_times_s")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write JSONL metrics snapshots (one per chunk "
                         "plus a final one; schema: "
                         "schemas/metrics_snapshot.schema.json)")
    ap.add_argument("--log-json", action="store_true",
                    help="structured one-object-per-line JSON progress "
                         "on stderr instead of human-readable lines")
    ap.add_argument("--k", type=int, default=12)
    ap.add_argument("--w", type=int, default=30)
    ap.add_argument("--eth", type=int, default=6)
    args = ap.parse_args(argv)
    if args.index_dir is not None:
        if args.reference is not None and args.reads is None:
            # `map_fastq --index-dir DIR reads.fq`: the sole positional
            # is the FASTQ — no FASTA on this path
            args.reference, args.reads = None, args.reference
        if args.reference is not None:
            raise SystemExit("map_fastq: pass either a FASTA reference or "
                             "--index-dir, not both")
    elif args.reference is None:
        raise SystemExit("map_fastq: a FASTA reference (positional) or "
                         "--index-dir is required")
    from repro.launch.runtime import emulate_host_devices, \
        enable_compile_cache
    if args.topology == "mesh":
        emulate_host_devices(args.shards)
    enable_compile_cache()
    try:
        return run(args)
    except BrokenPipeError:
        # `map_fastq ... -o - | head` closing the pipe is not an error;
        # detach stdout so interpreter shutdown doesn't re-raise
        devnull = os.open(os.devnull, os.O_WRONLY)
        os.dup2(devnull, sys.stdout.fileno())
        return 141  # 128 + SIGPIPE, the conventional exit status


if __name__ == "__main__":
    raise SystemExit(main())
