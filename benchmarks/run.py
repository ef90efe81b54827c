"""Benchmark harness: one module per paper table/figure.

Default mode prints ``name,value,derived`` CSV (value is us_per_call for
timed rows, the modelled/papers' metric otherwise).

``--pipeline-json [PATH]`` instead runs the end-to-end engine comparison
(padded reference vs candidate-compacted, jnp vs Pallas backends,
synchronous vs streamed chunk execution) at ``--reads`` / ``--chunk-reads``
and writes the result to PATH (default BENCH_pipeline.json), so the perf
trajectory is tracked across PRs.  ``--check-against BASELINE.json`` then
compares the fresh run to a committed baseline and exits non-zero when
the streamed Pallas engine's reads/s regressed more than ``--tolerance``
(the CI perf-trend gate).
"""
import argparse
import json
import sys
import time

REGRESSION_ENGINE = "compacted_pallas"
REGRESSION_METRIC = "reads_per_s"
# synchronous runs carry per-stage wall times; each stage is gated
# independently so a regression hiding inside an improved total still fails
STAGE_ENGINES = ("compacted_pallas_sync", "fused_pallas_sync")
STAGE_NOISE_FLOOR_S = 0.005  # sub-5ms stages are runner noise, not signal
# armed-but-idle fault-tolerance tax ceiling: the resilience stack
# (injector in the fetch thread + watchdog + retry wrapper) may cost at
# most this fraction of the plain streamed engine's reads/s.  The metric
# is self-relative (armed vs plain in the *same* fresh run), so it needs
# no hardware-variance tolerance on top.
RESILIENCE_OVERHEAD_MAX = 0.05
# armed-but-idle observability tax ceiling: a live metrics registry +
# span tracer may cost at most this fraction of the disarmed streamed
# engine's reads/s.  Self-relative like the resilience gate.
OBS_OVERHEAD_MAX = 0.05


def emit_pipeline_json(path: str, reads: int, chunk_reads: int | None,
                       include_padded: bool) -> dict:
    from benchmarks.pipeline_bench import bench_pipeline
    bench = bench_pipeline(R=reads, chunk_reads=chunk_reads,
                           include_padded=include_padded)
    with open(path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    for name, e in bench["engines"].items():
        if "error" in e:
            print(f"{name}: ERROR {e['error']}")
        else:
            extra = ""
            if "survivors" in e:
                extra = (f" affine={e['affine_dist_instances']}"
                         f"/{e['padded_affine_instances']}padded"
                         f" survivors={e['survivors']}"
                         f" pruning={e['pruning_ratio']:.3f}")
            if "speedup_vs_sync" in e:
                extra += f" stream_speedup={e['speedup_vs_sync']}x"
            print(f"{name}: {e['wall_s']:.3f}s "
                  f"{e['per_read_us']:.1f}us/read "
                  f"speedup={e.get('speedup_vs_padded', 1.0)}x{extra}")
    fp = bench.get("fastq_path")
    if fp:
        print(f"fastq_path (dual-strand): "
              f"{fp['fastq_sam_reads_per_s']:.1f} reads/s through "
              f"FASTQ->SAM vs {fp['in_memory_reads_per_s']:.1f} in-memory "
              f"(I/O overhead {fp['io_overhead_frac']:.1%})")
    pp = bench.get("paired_path")
    if pp:
        print(f"paired_path (gzip R1/R2 -> paired SAM): "
              f"{pp['reads_per_s']:.1f} reads/s "
              f"({pp['pairs_per_s']:.1f} pairs/s, proper "
              f"{pp['proper_frac']:.1%}, {pp['rescued']} rescued)")
    ib = bench.get("index_build")
    if ib:
        if "error" in ib:
            print(f"index_build: ERROR {ib['error']}")
        else:
            print(f"index_build (out-of-core sharded build -> mmap "
                  f"reload -> routed mapping): "
                  f"{ib['build_bases_per_s']:.0f} bases/s build "
                  f"({ib.get('spill_bytes', 0)} spill B), "
                  f"{ib['reload_ms']:.1f}ms reload, "
                  f"{ib['routed_reads_per_s']:.1f} routed vs "
                  f"{ib['flat_reads_per_s']:.1f} flat reads/s "
                  f"({ib['routed_overhead_frac']:.1%} overhead)")
            pf = ib.get("routed_prefetch_reads_per_s")
            if pf is not None:
                print(f"index_build prefetch: {pf:.1f} prefetch-on vs "
                      f"{ib['routed_reads_per_s']:.1f} prefetch-off "
                      f"routed reads/s "
                      f"({ib.get('prefetch_overhead_frac', 0):.1%} vs "
                      f"flat)")
    ro = bench.get("resilience_overhead")
    if ro:
        if "error" in ro:
            print(f"resilience_overhead: ERROR {ro['error']}")
        else:
            print(f"resilience_overhead (armed-but-idle injector + "
                  f"watchdog + retry wrapper): "
                  f"{ro['armed_reads_per_s']:.1f} vs "
                  f"{ro['plain_reads_per_s']:.1f} plain reads/s "
                  f"({ro['overhead_frac']:.1%} overhead)")
    oo = bench.get("obs_overhead")
    if oo:
        if "error" in oo:
            print(f"obs_overhead: ERROR {oo['error']}")
        else:
            print(f"obs_overhead (armed-but-idle metrics registry + "
                  f"span tracer): {oo['armed_reads_per_s']:.1f} vs "
                  f"{oo['plain_reads_per_s']:.1f} plain reads/s "
                  f"({oo['overhead_frac']:.1%} overhead, "
                  f"{oo['spans_recorded']} spans, "
                  f"{oo['counter_series']} counter series)")
    print(f"wrote {path}")
    return bench


def _gate_metric(name: str, fresh_val, base_val, tolerance: float,
                 missing_reason: str | None = None) -> int:
    if fresh_val is None:
        why = f": {missing_reason}" if missing_reason else ""
        print(f"perf-trend: FAIL — fresh run has no {name}{why}")
        return 1
    floor = (1.0 - tolerance) * base_val
    verdict = "OK" if fresh_val >= floor else "FAIL"
    print(f"perf-trend: {verdict} — {name} "
          f"fresh={fresh_val:.1f} baseline={base_val:.1f} "
          f"floor={floor:.1f} (tolerance {tolerance:.0%})")
    return 0 if fresh_val >= floor else 1


def _gate_stages(fresh: dict, base: dict, engine: str,
                 tolerance: float) -> int:
    """Per-stage gate: any stage of ``engine``'s synchronous breakdown
    that takes > (1 + tolerance) x its baseline wall time fails, even
    when the total improved — that is what catches a stage-level
    regression smuggled in under a bigger win elsewhere."""
    bst = base.get("engines", {}).get(engine, {}).get("stage_times_s")
    if not bst:
        print(f"perf-trend: baseline lacks {engine}.stage_times_s; "
              f"skipping per-stage check")
        return 0
    fe = fresh.get("engines", {}).get(engine, {})
    fst = fe.get("stage_times_s")
    if not fst:
        why = fe.get("error", "engine missing from fresh run")
        print(f"perf-trend: FAIL — fresh run has no "
              f"{engine}.stage_times_s ({why})")
        return 1
    rc = 0
    for stage, bval in sorted(bst.items()):
        fval = fst.get(stage)
        if fval is None or bval < STAGE_NOISE_FLOOR_S:
            continue
        ceil = (1.0 + tolerance) * bval
        verdict = "OK" if fval <= ceil else "FAIL"
        print(f"perf-trend: {verdict} — {engine}.{stage} "
              f"fresh={fval:.4f}s baseline={bval:.4f}s "
              f"ceiling={ceil:.4f}s (tolerance {tolerance:.0%})")
        rc |= fval > ceil
    return rc


def check_regression(fresh: dict, baseline_path: str, tolerance: float,
                     stage_tolerance: float = 0.25) -> int:
    """Non-zero when the streamed Pallas engine — or the paired-end
    path's reads/s — regressed > tolerance vs the committed baseline,
    or any synchronous per-stage wall time grew > stage_tolerance
    (the CI perf-trend gate).  Metrics the baseline lacks are skipped,
    so the gate never blocks the PR that introduces a new section."""
    with open(baseline_path) as f:
        base = json.load(f)
    rc = 0
    try:
        b = base["engines"][REGRESSION_ENGINE][REGRESSION_METRIC]
    except KeyError:
        print(f"perf-trend: baseline {baseline_path} lacks "
              f"{REGRESSION_ENGINE}.{REGRESSION_METRIC}; skipping check")
        b = None
    if b is not None:
        e = fresh["engines"].get(REGRESSION_ENGINE, {})
        fresh_val = (None if "error" in e else e.get(REGRESSION_METRIC))
        rc |= _gate_metric(f"{REGRESSION_ENGINE}.{REGRESSION_METRIC}",
                           fresh_val, b, tolerance,
                           missing_reason=e.get("error"))
    bp = base.get("paired_path", {}).get("reads_per_s")
    if bp is None:
        print(f"perf-trend: baseline {baseline_path} lacks "
              f"paired_path.reads_per_s; skipping check")
    else:
        rc |= _gate_metric("paired_path.reads_per_s",
                           fresh.get("paired_path", {}).get("reads_per_s"),
                           bp, tolerance)
    ro = fresh.get("resilience_overhead")
    if base.get("resilience_overhead") is None:
        print(f"perf-trend: baseline {baseline_path} lacks "
              f"resilience_overhead; skipping check")
    elif ro is None or "error" in (ro or {}):
        why = (ro or {}).get("error", "section missing from fresh run")
        print(f"perf-trend: FAIL — fresh run has no resilience_overhead "
              f"({why})")
        rc |= 1
    else:
        of = ro["overhead_frac"]
        verdict = "OK" if of <= RESILIENCE_OVERHEAD_MAX else "FAIL"
        print(f"perf-trend: {verdict} — resilience_overhead "
              f"armed={ro['armed_reads_per_s']:.1f} "
              f"plain={ro['plain_reads_per_s']:.1f} reads/s "
              f"overhead={of:.1%} "
              f"(ceiling {RESILIENCE_OVERHEAD_MAX:.0%})")
        rc |= of > RESILIENCE_OVERHEAD_MAX
    oo = fresh.get("obs_overhead")
    if base.get("obs_overhead") is None:
        print(f"perf-trend: baseline {baseline_path} lacks "
              f"obs_overhead; skipping check")
    elif oo is None or "error" in (oo or {}):
        why = (oo or {}).get("error", "section missing from fresh run")
        print(f"perf-trend: FAIL — fresh run has no obs_overhead ({why})")
        rc |= 1
    else:
        of = oo["overhead_frac"]
        verdict = "OK" if of <= OBS_OVERHEAD_MAX else "FAIL"
        print(f"perf-trend: {verdict} — obs_overhead "
              f"armed={oo['armed_reads_per_s']:.1f} "
              f"plain={oo['plain_reads_per_s']:.1f} reads/s "
              f"overhead={of:.1%} (ceiling {OBS_OVERHEAD_MAX:.0%})")
        rc |= of > OBS_OVERHEAD_MAX
    bi = base.get("index_build", {})
    if bi.get("build_bases_per_s") is None:
        print(f"perf-trend: baseline {baseline_path} lacks "
              f"index_build.build_bases_per_s; skipping check")
    else:
        fi = fresh.get("index_build") or {}
        fresh_val = (None if "error" in fi
                     else fi.get("build_bases_per_s"))
        rc |= _gate_metric("index_build.build_bases_per_s", fresh_val,
                           bi["build_bases_per_s"], tolerance,
                           missing_reason=fi.get("error"))
    # routed-mapping throughput, prefetch off and on — each skipped
    # until a baseline records it, so the introducing run stays green
    for key in ("routed_reads_per_s", "routed_prefetch_reads_per_s"):
        if bi.get(key) is None:
            print(f"perf-trend: baseline {baseline_path} lacks "
                  f"index_build.{key}; skipping check")
            continue
        fi = fresh.get("index_build") or {}
        fresh_val = None if "error" in fi else fi.get(key)
        rc |= _gate_metric(f"index_build.{key}", fresh_val, bi[key],
                           tolerance, missing_reason=fi.get("error"))
    for engine in STAGE_ENGINES:
        rc |= _gate_stages(fresh, base, engine, stage_tolerance)
    return rc


def run_csv() -> None:
    from benchmarks import (accuracy, area, costmodel_tables, energy,
                            pipeline_bench, roofline_report, throughput,
                            wf_kernel_bench, wf_roofline)
    modules = [
        ("costmodel_tables", costmodel_tables),
        ("throughput", throughput),
        ("energy", energy),
        ("area", area),
        ("accuracy", accuracy),
        ("wf_kernel_bench", wf_kernel_bench),
        ("wf_roofline", wf_roofline),
        ("pipeline_bench", pipeline_bench),
        ("roofline", roofline_report),
    ]
    print("name,value,derived")
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            for row in mod.rows():
                n, v, d = row
                print(f"{n},{v},{str(d).replace(',', ';')}")
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        sys.stdout.flush()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pipeline-json", nargs="?", const="BENCH_pipeline.json",
                    default=None, metavar="PATH",
                    help="write the end-to-end engine comparison JSON "
                         "instead of the CSV sweep")
    ap.add_argument("--reads", type=int, default=4096,
                    help="batch size for --pipeline-json (default 4096)")
    ap.add_argument("--chunk-reads", type=int, default=1024,
                    help="streaming chunk size (0 = unchunked; default 1024)")
    ap.add_argument("--no-padded", action="store_true",
                    help="skip the slow padded-jnp reference (CI perf job)")
    ap.add_argument("--check-against", metavar="BASELINE", default=None,
                    help="compare the fresh --pipeline-json run to this "
                         "baseline JSON; exit 1 on >tolerance regression")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed reads/s regression fraction (default .15)")
    ap.add_argument("--stage-tolerance", type=float, default=0.25,
                    help="allowed per-stage wall-time growth fraction for "
                         "the synchronous engines (default .25)")
    args = ap.parse_args()
    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()
    if args.check_against and not args.pipeline_json:
        ap.error("--check-against requires --pipeline-json (the gate "
                 "compares a fresh pipeline run)")
    if args.pipeline_json:
        bench = emit_pipeline_json(args.pipeline_json, args.reads,
                                   args.chunk_reads or None,
                                   include_padded=not args.no_padded)
        if args.check_against:
            raise SystemExit(check_regression(bench, args.check_against,
                                              args.tolerance,
                                              args.stage_tolerance))
    else:
        run_csv()


if __name__ == "__main__":
    main()
