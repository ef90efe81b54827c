"""Genomics mapping service launcher (the paper's system kind).

    PYTHONPATH=src python -m repro.launch.serve --shards 8 --reads 256
    PYTHONPATH=src python -m repro.launch.serve --service --batches 16
    PYTHONPATH=src python -m repro.launch.serve --service --topology mesh \
        --shards 4 --batches 16

Both modes drive the unified ``repro.core.mapper.Mapper`` session API:

  * distributed (default) — ``Mapper(topology="mesh")`` batch loop: one
    process per host on a real pod (mesh from the TPU environment);
    under ``JAX_PLATFORMS=cpu``, ``--shards N`` runs over N virtual
    devices.  Stage B runs affine WF only on compacted filter survivors;
    the unified ``MapperStats`` reports the instance accounting.
  * ``--service`` — the request-batching path: variable-sized request
    batches are coalesced by the pow-2 ``ReadBatcher`` into static bucket
    shapes (``repro.core.serving``).  ``--topology single`` (default)
    streams buckets through the async double-buffered engine;
    ``--topology mesh`` routes every bucket onto the distributed mapper,
    where repeated same-size buckets hit the session plan cache (the
    compiled shard_map program) with zero recompiles after warm-up —
    watch the plan-cache counters in the closing stats lines.
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time


@contextlib.contextmanager
def _obs(args, component: str):
    """Arm the observability surfaces a launcher asked for and always
    tear them down: ``--log-json`` structured logging, ``--metrics-out``
    (final JSONL snapshot), ``--trace-out`` (Chrome trace export, even
    on failure), ``--metrics-port`` (Prometheus exposition thread) and
    ``--profiler-port`` (jax profiler server for on-demand device
    timelines)."""
    from repro.launch.map_fastq import _metrics_snapshot
    from repro.obs import logjson
    from repro.obs import registry as _metrics
    from repro.obs import server as obs_server
    from repro.obs import tracing as _tracing

    trace_out = getattr(args, "trace_out", None)
    metrics_out = getattr(args, "metrics_out", None)
    metrics_port = getattr(args, "metrics_port", None)
    profiler_port = getattr(args, "profiler_port", None)
    log_on = getattr(args, "log_json", False) and not logjson.enabled()
    need_metrics = metrics_out is not None or metrics_port is not None
    metrics_on = need_metrics and _metrics.ACTIVE is None
    tracing_on = trace_out is not None and _tracing.ACTIVE is None
    if log_on:
        logjson.enable(component)
    if metrics_on:
        _metrics.enable_metrics()
    if tracing_on:
        _tracing.enable_tracing()
    srv = None
    if metrics_port is not None and _metrics.ACTIVE is not None:
        srv = obs_server.start_metrics_server(_metrics.ACTIVE,
                                              port=metrics_port)
        logjson.say(f"serve: metrics exposition on "
                    f"http://{srv.host}:{srv.port}/metrics",
                    event="metrics_server", port=srv.port)
    if profiler_port is not None:
        prof = obs_server.start_profiler_server(profiler_port)
        if prof is not None:
            logjson.say(f"serve: jax profiler server on port "
                        f"{profiler_port}", event="profiler_server",
                        port=profiler_port)
        else:
            logjson.say("serve: jax profiler server unavailable on this "
                        "jax build; continuing without it",
                        event="profiler_server", port=None)
    try:
        yield
    finally:
        if srv is not None:
            srv.stop()
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


def _print_mapper_stats(mapper, totals: dict, file=None) -> None:
    """Closing stats lines shared by every launcher (``map_fastq`` uses
    it too, with ``file=sys.stderr``): the unified MapperStats accounting
    and the session plan-cache counters.  The counter label names the
    stage that actually ran them: the mesh topology's stage B (filter +
    compacted affine on the index-owner shards) vs the single topology's
    filter/affine stages — so `--topology mesh` output is comparable
    across modes without guessing which path produced it."""
    label = ("stage B [mesh]" if mapper.topology == "mesh"
             else "filter/affine [single]")
    print(f"{label}: {totals['survivors']} "
          f"survivors -> {totals['affine_instances']} affine instances "
          f"(of {totals['padded_affine_instances']} padded), dropped "
          f"send={totals['dropped_send']} affine={totals['dropped_affine']}",
          file=file)
    print(f"plan cache: {mapper.plan_cache_hits} hits / "
          f"{mapper.plan_cache_misses} misses "
          f"(same-size batches reuse compiled executables after warm-up)",
          file=file)
    part = totals.get("partitions")
    if part:
        if "minis_routed_per_partition" in part:  # shard-routed single
            print(f"partitions: routed "
                  f"{part['minis_routed_per_partition']} minimizers "
                  f"(found {part['minis_found_per_partition']}) over "
                  f"{part['chunks_routed']} chunk(s); arena "
                  f"{part['arena_bytes']} B, {part['partition_loads']} "
                  f"load(s), {part['partition_evictions']} eviction(s), "
                  f"{part['h2d_bytes']} B h2d", file=file)
        else:  # mesh: partition i on shard i
            print(f"partitions: {part['num_partitions']} mesh-placed, "
                  f"occurrences {part['occurrences_per_partition']}, "
                  f"stage-B survivors {part['survivors_per_partition']}",
                  file=file)
    stor = mapper.index_storage()
    if stor is not None:
        per = stor.get("per_partition")
        breakdown = (" (" + ", ".join(
            f"p{d['partition']}: "
            f"{d['hash_table_bytes'] + d['segments_bytes']}"
            for d in per) + ")" if per else "")
        print(f"index storage: {stor['total_bytes']} B "
              f"(hash {stor['hash_table_bytes']} B + segments "
              f"{stor['materialized_segments_bytes']} B, blowup "
              f"{stor['blowup']:.1f}x){breakdown}", file=file)


def run_service(args) -> int:
    import numpy as np

    from repro.core.index import build_index
    from repro.core.mapper import Mapper
    from repro.core.pipeline import MapperConfig
    from repro.core.resilience import MappingError
    from repro.core.serving import BatcherConfig
    from repro.data.genome import make_reference, sample_reads
    from repro.launch.runtime import device_line
    from repro.obs import logjson

    ref = make_reference(args.genome, seed=0, repeat_frac=0.02)
    idx = build_index(ref)
    cfg = MapperConfig.from_index(idx, wf_backend=args.wf_backend,
                                  stream=not args.no_stream)
    mapper = Mapper(idx, cfg, topology=args.topology, n_shards=args.shards)
    svc = mapper.serve(BatcherConfig(bucket_min=args.bucket_min,
                                     bucket_max=args.bucket_max))
    rng = np.random.default_rng(7)
    logjson.say(f"service: genome {len(ref)} bases, buckets "
                f"[{args.bucket_min}..{args.bucket_max}], "
                f"topology={mapper.topology}, stream={cfg.stream}, "
                f"wf_backend={cfg.wf_backend}, {device_line()}",
                event="start", file=sys.stdout,
                genome=len(ref), topology=mapper.topology)
    total = correct = failed = 0
    t0 = time.perf_counter()
    truth = {}
    for b in range(args.batches):
        # a burst of variable-sized client requests, then one flush
        for _ in range(int(rng.integers(1, 5))):
            n = int(rng.integers(1, args.reads + 1))
            rs = sample_reads(ref, n, seed=int(rng.integers(1 << 30)))
            truth[svc.submit(rs.reads)] = rs.true_pos
        for rid, res in svc.flush().items():
            true_pos = truth.pop(rid)
            if isinstance(res, MappingError):
                failed += 1
                print(f"request {rid}: {res.error_type}: {res.message}",
                      file=sys.stderr)
                continue
            total += len(res.position)
            correct += int((np.abs(res.position - true_pos) <= 6).sum())
    dt = time.perf_counter() - t0
    st = svc.batcher.stats
    waste = st["padded_reads"] / max(st["padded_reads"] + st["reads"], 1)
    logjson.say(f"{total} reads / {st['requests']} requests in {dt:.1f}s "
                f"({total/dt:.0f} reads/s), accuracy "
                f"{correct/max(total,1):.4f}",
                event="done", file=sys.stdout, reads=total,
                requests=st["requests"], wall_s=round(dt, 3),
                accuracy=round(correct / max(total, 1), 4))
    print(f"bucket hist {st['bucket_hist']}, lane padding waste {waste:.3f}")
    _print_mapper_stats(mapper, svc.totals)
    rc = svc.resilient.counters
    print(f"resilience: {rc['retries']} retries, {rc['failed_reads']} "
          f"quarantined reads, {failed} failed request(s), engine ladder "
          f"{svc.resilient.ladder.describe()}")
    return 1 if failed else 0


def run_distributed(args) -> int:
    import numpy as np

    from repro.core.index import build_index
    from repro.core.mapper import Mapper, accumulate_stats
    from repro.core.pipeline import MapperConfig
    from repro.data.genome import make_reference, sample_reads
    from repro.launch.mesh import make_genomics_mesh
    from repro.launch.runtime import device_line

    mesh = make_genomics_mesh(args.shards)
    n_shards = mesh.devices.size
    ref = make_reference(args.genome, seed=0, repeat_frac=0.02)
    idx = build_index(ref)
    cfg = MapperConfig.from_index(idx, wf_backend=args.wf_backend)
    mapper = Mapper(idx, cfg, topology="mesh", mesh=mesh,
                    send_cap=args.send_cap)
    print(f"serving: {n_shards} shards, {len(idx.uniq_kmers)} minimizers, "
          f"{len(ref)} bases, {device_line()}")
    totals = dict(survivors=0, affine_instances=0,
                  padded_affine_instances=0, dropped_send=0,
                  dropped_affine=0, reverse_best=0)
    total = correct = 0
    t0 = time.perf_counter()
    for b in range(args.batches):
        rs = sample_reads(ref, args.reads, seed=1000 + b)
        res = mapper.map(rs.reads)
        total += len(res.position)
        correct += int((np.abs(res.position - rs.true_pos) <= 6).sum())
        accumulate_stats(totals, res.stats)
    dt = time.perf_counter() - t0
    print(f"{total} reads in {dt:.1f}s ({total/dt:.0f} reads/s), "
          f"accuracy {correct/total:.4f}, dropped {totals['dropped_send']}")
    _print_mapper_stats(mapper, totals)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--service", action="store_true",
                    help="request batcher + Mapper session service mode")
    ap.add_argument("--topology", default="single",
                    choices=("single", "mesh"),
                    help="service mode only: execute buckets on the "
                         "single-shard streaming engine or route them onto "
                         "the distributed mesh mapper")
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--genome", type=int, default=50_000)
    ap.add_argument("--reads", type=int, default=128,
                    help="reads per batch (distributed) / max request size "
                         "(service)")
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--send-cap", type=int, default=None)
    ap.add_argument("--bucket-min", type=int, default=64)
    ap.add_argument("--bucket-max", type=int, default=1024)
    ap.add_argument("--wf-backend", default="jnp",
                    choices=("jnp", "pallas"))
    ap.add_argument("--no-stream", action="store_true",
                    help="service mode only: synchronous debug path "
                         "(per-stage timings)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the run as Chrome trace-event JSON "
                         "(Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a final JSONL metrics snapshot (schema: "
                         "schemas/metrics_snapshot.schema.json)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="expose the live metrics registry over HTTP "
                         "(Prometheus text on /metrics, JSON on "
                         "/metrics.json; 0 = ephemeral port)")
    ap.add_argument("--profiler-port", type=int, default=None,
                    metavar="PORT",
                    help="start the jax profiler server so TensorBoard / "
                         "jax.profiler.trace clients can capture device "
                         "timelines from the live process")
    ap.add_argument("--log-json", action="store_true",
                    help="structured one-object-per-line JSON progress "
                         "on stderr")
    args, _ = ap.parse_known_args(argv)
    from repro.launch.runtime import emulate_host_devices, \
        enable_compile_cache
    emulate_host_devices(args.shards)
    enable_compile_cache()
    fn = run_service if args.service else run_distributed
    with _obs(args, "serve"):
        return fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
