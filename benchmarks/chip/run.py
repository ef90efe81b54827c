#!/usr/bin/env python3
"""One run of one benchmark cell on the chip: FASTQ.gz in, SAM on disk.

    python3 benchmarks/chip/run.py --workload chr21.se150 --seed 7 \
        --seconds 30 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration (the
file its entry in ``configs`` gives) and a traffic mix
(``traffic/<name>.json``).  Its per-layer metrics are the ``per_layer``
entries whose ``workloads`` list it, each read by ``metrics/<name>.py``;
its limits for ``correct`` are in ``limits/<cell>.json``.  A new cell is new
files and entries, with no edit to this one.

Set-up (``setup_s``): make the reference (from the configuration's fixed
``reference_seed``) and the reads (from ``--seed``), write the
FASTQ.gz, build the index with ``repro.core.index.build_index``, open the
``Mapper`` as ``map_fastq`` does, and run one untimed pass over the whole
read set through the chunk loop (``loop.py``), which compiles every
capacity bucket the window meets.  Window: replay the same set, pass after
pass, until ``--seconds`` have passed; stop at the first chunk boundary
after that.  ``reads_per_s`` is every read whose SAM record was written over
the window's wall time.  Compiles inside the window are counted.  After the
window the device's peak memory is read, the session freed, and a sample of
the window's records judged against the plain reference
(``reference.py``).

``--trace 1`` traces a shorter window, which ends at the first chunk
boundary after ``TRACE_WINDOW_S`` (or ``--seconds``, if that is shorter),
and reports the per-layer metrics of that window alone (host spans, reads,
counters and device time all of the same chunks) with
``device.busy_s``/``window_s`` and a ``breakdown``; ``--trace 0`` reports
the end-to-end metrics.  The last line of stdout is
the result's JSON; the last lines of stderr give each number compared with
its limit.  Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
WORK = ROOT / ".bench_work"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a --trace 1 run's window ends at the first chunk boundary after this many
# seconds: the TPU trace holds an event for every iteration of the jnp
# stages' loops (some 1.4M events a second), and stopping the profiler takes
# about 40 s per second traced, which the run's time limit has to hold
TRACE_WINDOW_S = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Cells, found by name
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list          # per-layer entries of BENCHMARK.json
    end_to_end: list       # end-to-end entries this cell reports
    limits: dict


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read
    from ``here`` (this directory)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return Cell(
        name=name, chips=wl["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (here / "traffic" / f"{wl['traffic']}.json").read_text()),
        metrics=[m for m in bench["per_layer"] if _applies(m, name)],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        limits=json.loads((here / "limits" / f"{name}.json").read_text()))


def reader(metric: str, here: Path = HERE):
    """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
    path = here / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# What the metric readers see
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    n_reads: int
    paired: bool
    read_len: int
    band: int
    spans: dict             # layer -> host seconds in the window
    stats: dict             # summed MapperStats counters of the window
    compiles: int
    peak_bytes: int | None
    trace: dict | None      # devtrace.reduce_events output

    def per_read_us(self, seconds: float) -> float | None:
        return 1e6 * seconds / self.n_reads if self.n_reads else None

    def module_seconds(self, modules) -> float | None:
        if self.trace is None:
            return None
        secs = sum(self.trace["module_s"].get(m, 0.0) for m in modules)
        return secs or None


STAT_KEYS = ("candidates", "survivors", "linear_instances",
             "affine_dist_instances", "affine_dirs_instances")


def _add_stats(acc: dict, stats) -> None:
    acc["candidates"] += stats.candidates
    acc["survivors"] += stats.survivors
    for k in STAT_KEYS[2:]:
        acc[k] += int(stats.get(k, 0))


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------

def check_device(chips: int, require_chip: bool) -> dict:
    """Platform, device kind and count, with the kind's entry of the peak
    table (``peaks.json``) under ``peaks``; exits 2 without a TPU, with
    fewer chips than the cell asks for, or on a device kind the table does
    not list."""
    import jax
    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    log(f"device: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']}")
    if not require_chip:
        return dev
    if dev["platform"] != "tpu" or dev["count"] < chips:
        log(f"run.py: needs {chips} TPU chip(s), JAX found {dev['count']} "
            f"{dev['platform']} device(s); no result")
        raise SystemExit(2)
    peaks = json.loads((HERE / "peaks.json").read_text())
    if dev["kind"] not in peaks:
        log(f"run.py: device kind {dev['kind']!r} is not in peaks.json")
        raise SystemExit(2)
    return dict(dev, peaks=peaks[dev["kind"]])


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``, a fixed path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, work: Path | None = None,
             mapper_options: dict | None = None, fault=None) -> dict:
    """One run; returns the result dict printed as the last line.
    ``mapper_options`` (the control) and ``fault`` (a wrapper around the
    mapping call, for the fault tests) exist for the tests only."""
    t_start = time.perf_counter()
    import jax

    import gen
    import loop
    import reference
    import devtrace as trace_mod

    dev = check_device(cell.chips, require_chip)
    if require_chip:
        enable_cache()
    compiles = [0]
    cache = dict(hits=0, misses=0, compile_s=0.0)

    def on_compile(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1
            cache["compile_s"] += secs

    def on_cache(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_cache)

    from repro.core.index import build_index
    from repro.core.mapper import Mapper
    from repro.core.pipeline import MapperConfig
    from repro.io.fasta import Contig, ReferenceMap
    from repro.io.fastq import DEFAULT_CHUNK_READS
    from repro.io.sam import sam_header

    conf, traffic = cell.config, cell.traffic
    work = work or WORK / cell.name
    work.mkdir(parents=True, exist_ok=True)
    paired = traffic["layout"] == "paired"
    rl = traffic["read_len"]

    # ---- data
    t0 = time.perf_counter()
    ref = gen.make_reference(conf)
    reads = gen.sample(ref, traffic, seed)
    n_set = len(reads.reads1)
    if paired:
        files = [str(work / "r1.fastq.gz"), str(work / "r2.fastq.gz")]
        gen.write_fastq_gz(files[0], reads.reads1, reads.quals1,
                           gen.read_names(n_set, 1))
        gen.write_fastq_gz(files[1], reads.reads2, reads.quals2,
                           gen.read_names(n_set, 2))
    else:
        files = [str(work / "reads.fastq.gz")]
        gen.write_fastq_gz(files[0], reads.reads1, reads.quals1,
                           gen.read_names(n_set))
    t_data = time.perf_counter() - t0

    # ---- index and session, as map_fastq opens them (single FASTA contig)
    t0 = time.perf_counter()
    idx = build_index(ref, read_len=rl, k=conf["k"], w=conf["w"],
                      eth=conf["eth"])
    t_index = time.perf_counter() - t0
    contigs = [Contig(name=conf["contig"], length=len(ref), offset=0)]
    refmap = ReferenceMap(contigs)
    options = dict(chunk_reads=DEFAULT_CHUNK_READS, stream=True,
                   both_strands=True, profile=False)
    cfg = MapperConfig.from_index(idx, **{**options,
                                          **(mapper_options or {})})
    mapper = Mapper(idx, cfg, topology="single")
    if fault is not None:
        mapper = fault(mapper)
    contig_starts = [c.offset for c in contigs]
    log(f"cell {cell.name}: {len(ref)} bases, {reads.n_reads} reads a pass, "
        f"engine={cfg.engine} wf_backend={cfg.wf_backend} "
        f"chunk_reads={cfg.chunk_reads} cigar_mode={cfg.cigar_mode}")

    # ---- warm pass over the whole replay set (untimed, set-up)
    t0 = time.perf_counter()
    hist = dict(linear_instances={}, affine_dist_instances={})
    acc = dict(good=0, total=0)
    warm_stats = {k: 0 for k in STAT_KEYS}

    def on_warm(n_new, res, pr, tracker):
        first = res[0] if pr is not None else res
        ex = first.stats.extra if first.stats is not None else {}
        for k in hist:
            v = int(ex.get(k, 0))
            hist[k][v] = hist[k].get(v, 0) + 1
        if first.stats is not None:
            _add_stats(warm_stats, first.stats)
        _accuracy(acc, reads, first, pr, on_warm.done, conf["eth"])
        on_warm.done += n_new // (2 if paired else 1)
    on_warm.done = 0
    with open(work / "warm.sam", "w") as out:
        loop.map_pass(files, paired, cfg.chunk_reads, mapper, cfg, ref,
                      refmap, contig_starts, out, loop.Spans(),
                      on_chunk=on_warm)
    os.remove(work / "warm.sam")
    t_warm = time.perf_counter() - t0
    compiles_setup = compiles[0]
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s (data {t_data:.3f}, index {t_index:.3f}, "
        f"warm pass {t_warm:.3f}); compiles in set-up {compiles_setup} "
        f"({cache['compile_s']:.3f} s; persistent cache {cache['hits']} "
        f"hits, {cache['misses']} misses)")
    for k, h in hist.items():
        log(f"warm-pass {k} histogram: {dict(sorted(h.items()))}")
    log(f"accuracy vs truth (warm pass): {acc['good']}/{acc['total']} = "
        f"{acc['good'] / max(acc['total'], 1):.4f}")
    log(f"warm pass per read: {warm_stats['candidates'] / reads.n_reads:.3f} "
        f"candidates, {warm_stats['survivors'] / reads.n_reads:.3f} "
        f"survivors")

    # ---- window
    spans = loop.Spans(annotate=trace)
    stats = {k: 0 for k in STAT_KEYS}
    chunk_walls = []
    last = [0.0]
    sam_path = work / "window.sam"
    out = open(sam_path, "w")
    for line in sam_header(contigs, command_line="benchmarks/chip/run.py"):
        out.write(line + "\n")
    trace_dir = work / "trace"
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, TRACE_WINDOW_S)

    def on_chunk(n_new, res, pr, tracker):
        first = res[0] if pr is not None else res
        if first.stats is not None:
            _add_stats(stats, first.stats)
        now = time.perf_counter()
        chunk_walls.append(now - last[0])
        last[0] = now

    compiles[0] = 0
    written = 0
    passes = 0
    if trace:
        jax.profiler.start_trace(str(trace_dir))
        ann = jax.profiler.TraceAnnotation("window")
        ann.__enter__()
    cpu0, load0 = time.process_time(), os.getloadavg()[0]
    t_w0 = time.perf_counter()
    deadline = t_w0 + seconds
    last[0] = t_w0
    stop = lambda: time.perf_counter() >= deadline  # noqa: E731
    while not stop():
        written += loop.map_pass(files, paired, cfg.chunk_reads, mapper,
                                 cfg, ref, refmap, contig_starts, out,
                                 spans, on_chunk=on_chunk, stop=stop)
        passes += 1
    t_w1 = time.perf_counter()
    cpu_s, load1 = time.process_time() - cpu0, os.getloadavg()[0]
    compiles_window = compiles[0]
    if trace:
        ann.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"profiler stopped in {time.perf_counter() - t0:.3f} s")
    out.close()
    window_s = t_w1 - t_w0
    reads_per_s = written / window_s
    reduced = None
    if trace:
        t0 = time.perf_counter()
        path = trace_mod.find(str(trace_dir))
        reduced = trace_mod.reduce_file(path) if path else None
        log(f"trace: {os.path.getsize(path) if path else 0} bytes, read in "
            f"{time.perf_counter() - t0:.3f} s")
    ms = jax.devices()[0].memory_stats() or {}
    peak = ms.get("peak_bytes_in_use")
    log(f"window: {written} reads in {window_s:.3f} s over {passes} "
        f"pass(es), {len(chunk_walls)} chunks; reads/s {reads_per_s:.1f}"
        + (" (traced)" if trace else ""))
    log(f"host in window: process cpu {cpu_s:.3f} s over "
        f"{window_s:.3f} s wall; load average {load0:.2f} -> {load1:.2f} "
        f"on {os.cpu_count()} cpus")
    log(f"chunk wall s: p50 {statistics.median(chunk_walls):.4f} max "
        f"{max(chunk_walls):.4f}")
    log(f"compiles in window: {compiles_window}")
    log(f"host spans s: " + ", ".join(f"{k} {v:.3f}"
                                       for k, v in spans.seconds.items()))
    if peak is not None and "peaks" in dev:
        log(f"peak_bytes_in_use: {peak} "
            f"({100 * peak / dev['peaks']['hbm_bytes']:.2f}% of the chip)")
    else:
        log(f"peak_bytes_in_use: {peak}")

    # ---- free the session, then judge the window's records
    if hasattr(mapper, "close"):
        mapper.close()
    del mapper, idx
    jax.clear_caches()
    t0 = time.perf_counter()
    genome = reference.Genome(ref, rl, conf["k"], conf["w"], conf["eth"])
    judge = dict(conf)
    if paired:
        # an FR pair whose footprint lies within four sd of the fragment
        # mean is inside any window the running-median tracker can hold
        m, sd = traffic["fragment_mean"], traffic["fragment_sd"]
        judge["sure_proper"] = (m - 4 * sd, m + 4 * sd)
    verdict = reference.compare(str(sam_path), reads, genome, judge,
                                written, traffic["sample"], seed)
    log(f"reference: {verdict['checked']} sampled, {verdict['wrong']} wrong "
        f"{verdict['reasons']}, {verdict['records']} records in the SAM, "
        f"{time.perf_counter() - t0:.3f} s")
    for ex in verdict["examples"]:
        log(f"reference disagrees: {json.dumps(ex)}")
    checks = {"wrong": dict(value=verdict["wrong"],
                            limit=cell.limits["wrong"]["limit"])}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and verdict["records"] == written)
    os.remove(sam_path)

    run = RunData(n_reads=written, paired=paired, read_len=rl,
                  band=2 * conf["eth"] + 1, spans=spans.seconds,
                  stats=stats, compiles=compiles_window, peak_bytes=peak,
                  trace=reduced)
    metrics = {}
    if trace:
        for m in cell.metrics:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        values = dict(reads_per_s=reads_per_s, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = dict(value=values[m["name"]],
                                      unit=m["unit"])
    device = dict({k: v for k, v in dev.items() if k != "peaks"},
                  memory_peak_bytes=peak)
    result = dict(correct=bool(correct), attempted=written, failed=0,
                  metrics=metrics, device=device)
    if trace and reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = dict(
            device_ops=[[n, s] for n, s in reduced["device_ops"]],
            idle_gaps=[[n, s] for n, s in reduced["idle_gaps"]])
        for k, v in sorted(reduced["module_s"].items(), key=lambda kv: -kv[1]):
            log(f"device module s: {k} {v:.6f}")
    result["check"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def _accuracy(acc, reads, res, pr, first, eth) -> None:
    """Reads placed within eth of their true start, on the true strand."""
    import numpy as np
    if pr is not None:
        results = ((pr.res1, reads.pos1, reads.strand1),
                   (pr.res2, reads.pos2, reads.strand2))
    else:
        results = ((res, reads.pos1, reads.strand1),)
    for r, pos, strand in results:
        sl = slice(first, first + len(r.position))
        ok = (r.mapped & (np.abs(r.position - pos[sl]) <= eth)
              & (r.strand == strand[sl]))
        acc["good"] += int(ok.sum())
        acc["total"] += len(r.position)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
