"""``repro.obs``: metrics registry + span tracing units, the
timed()-level agreement between spans / counters / ``stage_times_s``,
the accumulation properties behind the launchers' closing stats, and
the registry-derived closing-stats byte-match on both topologies."""
import io
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import streaming
from repro.core.mapper import (_METRIC_RUN_FIELDS, Mapper, MapperStats,
                               accumulate_partition_stats, accumulate_stats,
                               totals_from_registry)
from repro.core.pipeline import MapperConfig
from repro.obs import registry as obs_registry
from repro.obs import tracing as obs_tracing
from repro.obs.registry import MAX_LABEL_SETS, MetricsRegistry
from repro.obs.tracing import Tracer
from repro.obs.validate import validate_chrome_trace, validate_json


@pytest.fixture(autouse=True)
def _clean_obs():
    """Obs state is process-global; never leak an armed registry/tracer
    (or a stale thread-local span context) into another test."""
    yield
    obs_tracing.disable_tracing()
    obs_registry.disable_metrics()
    obs_tracing.clear_ctx()


@pytest.fixture(scope="module")
def world():
    from repro.core.index import build_index
    from repro.data.genome import make_reference, sample_reads
    ref = make_reference(8_000, seed=11, repeat_frac=0.03)
    idx = build_index(ref)
    rs = sample_reads(ref, 48, seed=13)
    return idx, rs.reads


# ---------------------------------------------------------------- registry

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    reg.counter("c_total").inc()
    reg.counter("c_total").inc(4)
    assert reg.counter("c_total").value == 5
    reg.gauge("g", shard="0").set(7)
    reg.gauge("g", shard="0").dec(2)
    assert reg.gauge("g", shard="0").value == 5
    snap = reg.snapshot()
    assert snap["counters"]["c_total"] == 5
    assert snap["gauges"]['g{shard="0"}'] == 5


def test_registry_rejects_kind_mixing():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError):
        reg.gauge("m")


def test_label_cardinality_is_bounded():
    reg = MetricsRegistry()
    for i in range(MAX_LABEL_SETS * 3):
        reg.counter("hot_total", tenant=f"t{i}").inc()
    snap = reg.snapshot()["counters"]
    series = [k for k in snap if k.startswith("hot_total")]
    assert len(series) <= MAX_LABEL_SETS + 1
    assert 'hot_total{other="true"}' in snap  # overflow series absorbs


def test_histogram_quantiles_and_bounded_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    for v in (0.001, 0.002, 0.004, 0.1, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(2.107)
    assert sum(snap["buckets"].values()) == 5
    p50, p95, p99 = h.quantile(0.5), h.quantile(0.95), h.quantile(0.99)
    assert 0.002 <= p50 <= 0.01          # bucket upper edges
    assert p50 <= p95 <= p99
    h.observe(1e9)                       # beyond the last edge
    assert "+Inf" in h.snapshot()["buckets"]
    # memory is fixed: the bucket layout never grows with observations
    assert len(h.counts) == len(h.edges) + 1


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req_total", code="200").inc(3)
    reg.histogram("lat_seconds").observe(0.5)
    text = reg.to_prometheus()
    assert '# TYPE req_total counter' in text
    assert 'req_total{code="200"} 3' in text
    assert '# TYPE lat_seconds histogram' in text
    assert 'lat_seconds_count 1' in text
    assert 'lat_seconds_sum 0.5' in text
    assert 'le="+Inf"' in text


# ----------------------------------------------------------------- tracing

def test_tracer_spans_ctx_and_chrome_export():
    tr = obs_tracing.enable_tracing(tracer_=Tracer())
    obs_tracing.set_ctx(chunk=3)
    with obs_tracing.span("work", shard=1):
        pass
    obs_tracing.clear_ctx()
    assert len(tr) == 1
    trace = tr.chrome()
    assert validate_chrome_trace(trace) == []
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs[0]["name"] == "work"
    assert xs[0]["args"] == {"chunk": 3, "shard": 1}
    assert all(k in xs[0] for k in ("pid", "tid", "ts", "dur"))


def test_tracer_bounds_memory():
    tr = Tracer(max_events=4)
    for _ in range(10):
        tr.add("e", 0.0, 1.0)
    assert len(tr) == 4 and tr.dropped == 6
    assert tr.chrome()["dropped_events"] == 6


def test_trace_validator_catches_malformed():
    assert validate_chrome_trace({"traceEvents": []}) != []  # empty
    bad = {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 0,
                            "ts": 0.0}]}          # missing dur
    assert any("dur" in e for e in validate_chrome_trace(bad))
    unbalanced = {"traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 0, "ts": 0.0}]}
    assert validate_chrome_trace(unbalanced) != []


def test_mini_schema_validator():
    schema = {"type": "object", "required": ["n"],
              "properties": {"n": {"type": "integer", "minimum": 0}}}
    assert validate_json({"n": 3}, schema) == []
    assert validate_json({"n": -1}, schema) != []
    assert validate_json({}, schema) != []


# ------------------------------------------------- timed()-level agreement

def test_timed_feeds_times_span_and_counter_from_same_clock_reads():
    reg = obs_registry.enable_metrics(MetricsRegistry())
    tr = obs_tracing.enable_tracing(tracer_=Tracer())
    times = {}
    t0 = time.perf_counter()
    t1 = streaming.timed(times, "stage_x", t0)
    assert t1 >= t0
    assert tr.stage_totals()["stage_x"] == times["stage_x"]
    assert reg.counter("repro_stage_seconds_total",
                       stage="stage_x").value == times["stage_x"]
    # times=None (profiling off) emits nothing: the disabled path stays
    # a pure clock read
    n = len(tr)
    streaming.timed(None, "stage_y", t0)
    assert len(tr) == n
    assert "stage_y" not in tr.stage_totals()


def test_trace_durations_equal_stage_times(world):
    """The acceptance property: a traced run's summed span durations are
    the ``stage_times_s`` dict — same clock reads, so equality is exact,
    not approximate."""
    idx, reads = world
    tr = obs_tracing.enable_tracing(tracer_=Tracer())
    cfg = MapperConfig.from_index(idx, chunk_reads=16, profile=True)
    res = Mapper(idx, cfg).map(reads[:32])
    st = res.stats["stage_times_s"]
    totals = tr.stage_totals()
    assert set(st) <= set(totals)
    for k, v in st.items():
        assert totals[k] == pytest.approx(v, rel=1e-9), k
    # full precision survives in the stats dict (no 4-decimal rounding
    # at collection)
    assert any(v != round(v, 4) for v in st.values() if v)


def test_mesh_profile_records_stage_times(world):
    from repro.core.distributed import shard_index
    from repro.core.mapper import _flat_mesh
    idx, reads = world
    cfg = MapperConfig.from_index(idx, profile=True)
    res = Mapper(shard_index(idx, 1), cfg, topology="mesh",
                 mesh=_flat_mesh(1)).map(reads[:16])
    assert set(res.stats["stage_times_s"]) == {"dispatch", "d2h"}


# ------------------------------------------- accumulation properties

def _mk_stats(vals):
    return MapperStats(topology="single", engine="compacted",
                       reads=vals[0], candidates=vals[1],
                       survivors=vals[2], affine_instances=vals[3],
                       padded_affine_instances=vals[4],
                       dropped_send=vals[5], dropped_affine=vals[6],
                       reverse_best=vals[7])


@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(st.integers(min_value=0, max_value=10**6),
                         min_size=8, max_size=8),
                min_size=1, max_size=6))
def test_accumulate_stats_split_equals_one_shot(chunks):
    """Accumulating per-chunk stats equals accumulating the one-shot sum
    — the property that makes chunked launcher totals trustworthy."""
    split = {f: 0 for f in _METRIC_RUN_FIELDS}
    for vals in chunks:
        accumulate_stats(split, _mk_stats(vals), fields=_METRIC_RUN_FIELDS)
    merged = _mk_stats([sum(v[i] for v in chunks) for i in range(8)])
    one_shot = {f: 0 for f in _METRIC_RUN_FIELDS}
    accumulate_stats(one_shot, merged, fields=_METRIC_RUN_FIELDS)
    assert split == one_shot


@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(st.integers(min_value=0, max_value=10**6),
                         min_size=5, max_size=5),
                min_size=1, max_size=6))
def test_accumulate_partition_stats_split_equals_one_shot(runs):
    """Per-partition counters and count vectors sum across runs; static
    descriptors take the latest run's value."""
    def mk(v):
        s = _mk_stats([0] * 8)
        s.extra["partitions"] = {
            "partition_loads": v[0], "h2d_bytes": v[1],
            "minis_routed_per_partition": [v[2], v[3]],
            "arena_rows": v[4],          # static: latest wins
        }
        return s
    split = {}
    for v in runs:
        accumulate_partition_stats(split, mk(v))
    part = split["partitions"]
    assert part["partition_loads"] == sum(v[0] for v in runs)
    assert part["h2d_bytes"] == sum(v[1] for v in runs)
    assert part["minis_routed_per_partition"] == [
        sum(v[2] for v in runs), sum(v[3] for v in runs)]
    assert part["arena_rows"] == runs[-1][4]


# -------------------------------- registry-derived closing stats

def _closing_lines(mapper, totals) -> str:
    from repro.launch.serve import _print_mapper_stats
    buf = io.StringIO()
    _print_mapper_stats(mapper, totals, file=buf)
    return buf.getvalue()


def _run_chunked(mapper, reads, step):
    totals = {f: 0 for f in _METRIC_RUN_FIELDS}
    for lo in range(0, len(reads), step):
        res = mapper.map(reads[lo:lo + step])
        accumulate_stats(totals, res.stats, fields=_METRIC_RUN_FIELDS)
    return totals


@pytest.mark.parametrize("topology", ["single", "mesh"])
def test_registry_closing_stats_byte_match(world, topology):
    """Totals re-derived from the metrics registry render the exact same
    closing-stats bytes as the legacy accumulate_stats path, on both
    topologies — the numbers can never disagree."""
    idx, reads = world
    if topology == "mesh":
        from repro.core.distributed import shard_index
        from repro.core.mapper import _flat_mesh
        mapper = Mapper(shard_index(idx, 1), MapperConfig.from_index(idx),
                        topology="mesh", mesh=_flat_mesh(1))
    else:
        mapper = Mapper(idx, MapperConfig.from_index(idx, chunk_reads=16))
    reg = obs_registry.enable_metrics(MetricsRegistry())
    totals = _run_chunked(mapper, reads, 24)
    derived = totals_from_registry(topology, reg)
    assert derived == totals
    assert (_closing_lines(mapper, dict(totals))
            == _closing_lines(mapper, dict(derived)))


def test_totals_from_registry_none_when_disabled():
    assert totals_from_registry("single") is None


# ------------------------------------------------- service-level metrics

def test_service_latency_metrics_and_tenant_bound(world):
    from repro.core.serving import _MAX_TENANTS, BatcherConfig
    idx, reads = world
    reg = obs_registry.enable_metrics(MetricsRegistry())
    svc = Mapper(idx, MapperConfig.from_index(idx)).serve(
        BatcherConfig(bucket_min=64, bucket_max=256))
    for i in range(_MAX_TENANTS + 8):
        svc.submit(reads[i % len(reads)][None], tenant=f"tenant{i}")
    assert len(svc._tenant_pending) <= _MAX_TENANTS + 1
    assert svc.tenant_queue_depth["_other"] == 8
    out = svc.flush()
    assert len(out) == _MAX_TENANTS + 8
    assert all(d == 0 for d in svc._tenant_pending.values())
    assert not svc._submit_ts and not svc._tenants  # drained with the rids
    snap = reg.snapshot()
    assert snap["histograms"]["repro_flush_seconds"]["count"] == 1
    assert (snap["histograms"]["repro_request_queue_wait_seconds"]["count"]
            == _MAX_TENANTS + 8)
    assert snap["histograms"]["repro_bucket_execute_seconds"]["count"] >= 1
    tenant_series = [k for k in snap["counters"]
                     if k.startswith("repro_requests_total")]
    assert 0 < len(tenant_series) <= MAX_LABEL_SETS + 1


def test_batcher_bucket_hist_is_bounded(world):
    """The audit satellite: ``bucket_hist`` keys are pow-2 sizes within
    [bucket_min, bucket_max], so long-lived serving cannot grow it."""
    import math

    from repro.core.serving import BatcherConfig, ReadBatcher
    cfg = BatcherConfig(bucket_min=64, bucket_max=1024)
    b = ReadBatcher(150, cfg)
    rng = np.random.default_rng(0)
    for _ in range(40):
        b.submit(np.zeros((int(rng.integers(1, 900)), 150), np.uint8))
        b.drain()
    max_keys = int(math.log2(cfg.bucket_max // cfg.bucket_min)) + 1
    hist = b.stats["bucket_hist"]
    assert len(hist) <= max_keys
    assert all(cfg.bucket_min <= k <= cfg.bucket_max and (k & (k - 1)) == 0
               for k in hist)


def test_metrics_server_round_trip():
    import json
    import urllib.request

    from repro.obs.server import start_metrics_server
    reg = MetricsRegistry()
    reg.counter("up_total").inc()
    srv = start_metrics_server(reg, port=0)
    try:
        base = f"http://{srv.host}:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "up_total 1" in text
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read())
        assert snap["counters"]["up_total"] == 1
    finally:
        srv.stop()


# ------------------------------------------- layer spans in a profiler trace

SPAN_NAMES = ("repro/fastq", "repro/map", "repro/map/prep",
              "repro/map/dispatch", "repro/map/sync", "repro/map/fetch",
              "repro/pairing", "repro/pairing/rescue", "repro/sam")


@pytest.fixture(scope="module")
def paired_world(tmp_path_factory):
    """Two chunks of 12 pairs in R1/R2 FASTQ files, a quarter of the R2
    mates random so that rescue sweeps run."""
    from repro.core.index import build_index
    from repro.data.genome import (make_reference, sample_pairs,
                                   write_fasta, write_fastq_pair)
    d = tmp_path_factory.mktemp("obs_spans")
    ref = make_reference(8_000, seed=11, repeat_frac=0.03)
    ps = sample_pairs(ref, 24, insert_mean=300, insert_sd=20,
                      unmappable_frac=0.25, seed=5)
    write_fastq_pair(d / "r1.fq", d / "r2.fq", ps)
    write_fasta(d / "ref.fa", [("chr1", ref)])
    return d, ref, build_index(ref)


def _map_pairs_and_emit(d, ref, idx):
    """Map, pair and emit every chunk of the world's FASTQ pair."""
    from repro.core.pairing import InsertSizeTracker, resolve_pairs
    from repro.io.fasta import Contig, ReferenceMap
    from repro.io.fastq import PairedFastqStream
    from repro.io.sam import emit_paired_alignments
    cfg = MapperConfig.from_index(idx, chunk_reads=16, both_strands=True)
    mapper = Mapper(idx, cfg)
    refmap = ReferenceMap([Contig(name="chr1", length=len(ref), offset=0)])
    tracker = InsertSizeTracker(min_samples=4)
    records = 0
    for c1, c2 in PairedFastqStream(str(d / "r1.fq"), str(d / "r2.fq"),
                                    chunk_reads=12):
        res1, res2 = mapper.map_pairs(c1.reads, c2.reads)
        pr = resolve_pairs(res1, res2, cfg=cfg, tracker=tracker, ref=ref,
                           reads1=c1.reads, reads2=c2.reads)
        records += sum(1 for _ in emit_paired_alignments(
            pr, c1.names, c1.reads, c1.quals, c2.reads, c2.quals, refmap))
    return records


def _profiled(trace_dir, fn):
    """Run ``fn`` under ``jax.profiler``; returns its result and the
    trace's ``repro/*`` host events as (name, line, start_ns, end_ns,
    stats), ``line`` the index of the event's thread line (every Python
    thread's line is named ``python``)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events += [(e.name, i, e.start_ns, e.end_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("repro/")]
    return out, events


def test_profiler_trace_holds_the_layer_spans(paired_world, tmp_path,
                                              monkeypatch):
    """Under a ``jax.profiler`` trace, with no ``Tracer`` armed, every
    layer's span is on the host plane, nested under its parent, with its
    chunk and counts; ``syncs`` equals the blocking reads made."""
    import threading

    import jax
    from jax._src.array import ArrayImpl

    from repro.core import pipeline
    d, ref, idx = paired_world
    _map_pairs_and_emit(d, ref, idx)          # compile outside the trace
    reads_back = []                           # from both threads
    to_int = ArrayImpl.__int__

    def counting_int(x):
        reads_back.append(threading.get_ident())
        return to_int(x)

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **kw):
            if isinstance(x, jax.Array):
                reads_back.append(threading.get_ident())
            return np.asarray(x, *a, **kw)

    monkeypatch.setattr(ArrayImpl, "__int__", counting_int)
    monkeypatch.setattr(pipeline, "np", CountingNumpy())
    records, events = _profiled(tmp_path, lambda: _map_pairs_and_emit(
        d, ref, idx))
    assert records == 2 * 24
    by = {n: [e for e in events if e[0] == n] for n in SPAN_NAMES}
    assert all(by[n] for n in SPAN_NAMES), str(
        [(n, len(v)) for n, v in by.items()])

    def within(child, parents, same_line=True):
        return [p for p in parents if p[2] <= child[2] and child[3] <= p[3]
                and (p[1] == child[1] or not same_line)]

    for child, parent, same_line in (
            ("repro/map/prep", "repro/map", True),
            ("repro/map/dispatch", "repro/map", True),
            ("repro/map/sync", "repro/map", True),
            ("repro/map/fetch", "repro/map", False),
            ("repro/pairing/rescue", "repro/pairing", True)):
        for e in by[child]:
            enclosing = within(e, by[parent], same_line)
            assert len(enclosing) == 1, (child, e)
            assert enclosing[0][4]["chunk"] == e[4]["chunk"]
    assert {e[1] for e in by["repro/map/fetch"]} != {
        e[1] for e in by["repro/map"]}      # the fetch thread's own line
    fastq = [e for e in by["repro/fastq"] if e[4]["reads"]]
    assert [e[4]["chunk"] for e in fastq] == [0, 1]
    assert [e[4]["reads"] for e in fastq] == [24, 24]
    for name in ("repro/map", "repro/pairing", "repro/sam"):
        assert sorted(e[4]["chunk"] for e in by[name]) == [0, 1], name
    assert [e[4]["records"] for e in by["repro/sam"]] == [24, 24]
    assert [e[4]["pairs"] for e in by["repro/pairing"]] == [12, 12]
    stages = {e[4]["stage"] for e in by["repro/map/dispatch"]}
    assert stages == {"seed", "linear", "affine", "strand", "traceback"}
    rows = [(e[4]["rows"], e[4]["bucket"]) for e in by["repro/pairing/rescue"]]
    assert any(r for r, _ in rows)
    assert all(r <= b for r, b in rows)
    assert sum(e[4]["syncs"] for e in by["repro/map"]) == len(reads_back)
    assert len(set(reads_back)) == 2        # the dispatch and fetch threads


def test_spans_cost_no_annotation_with_the_profiler_off(paired_world,
                                                        monkeypatch):
    from jax.profiler import TraceAnnotation
    made = []

    class Counting(TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs_tracing, "TraceAnnotation", Counting)
    d, ref, idx = paired_world
    assert _map_pairs_and_emit(d, ref, idx) == 2 * 24
    assert made == []


def _map_fastq_argv(d, out, *extra):
    return [str(d / "ref.fa"), "--r1", str(d / "r1.fq"), "--r2",
            str(d / "r2.fq"), "-o", str(out), "--chunk-reads", "12", *extra]


def test_map_fastq_profiler_trace_without_trace_out(paired_world, tmp_path):
    """A plain ``jax.profiler`` trace of ``map_fastq`` (no ``--trace-out``,
    no ``Tracer``) holds the program's layer spans."""
    from repro.launch.map_fastq import main
    d, _, _ = paired_world
    rc, events = _profiled(tmp_path / "trace", lambda: main(
        _map_fastq_argv(d, tmp_path / "out.sam")))
    assert rc == 0
    assert obs_tracing.tracer() is None
    assert {e[0] for e in events} == set(SPAN_NAMES)


def test_trace_out_spans_carry_the_stream_chunk(paired_world, tmp_path):
    """``--trace-out``: each chunk's spans carry its ordinal in the FASTQ
    stream, and the streaming engine leaves the caller's context as it
    found it."""
    import json

    from repro.launch.map_fastq import main
    d, _, _ = paired_world
    path = tmp_path / "trace.json"
    assert main(_map_fastq_argv(d, tmp_path / "out.sam", "--trace-out",
                                str(path))) == 0
    trace = json.loads(path.read_text())
    assert validate_chrome_trace(trace) == []
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    for name in ("repro/sam", "repro/map", "repro/pairing"):
        assert sorted(e["args"]["chunk"] for e in spans
                      if e["name"] == name) == [0, 1], name
    fetch = [e["args"]["chunk"] for e in spans
             if e["name"] == "repro/map/fetch"]
    assert sorted(set(fetch)) == [0, 1]


def test_stream_map_restores_the_callers_context():
    seen = []

    def fetch(outs, times):
        seen.append(obs_tracing.get_ctx()["chunk"])
        return outs

    obs_tracing.set_ctx(chunk=7)
    assert streaming.stream_map([1, 2, 3], lambda x: x, lambda s: s,
                                fetch) == [1, 2, 3]
    assert obs_tracing.get_ctx() == {"chunk": 7}
    assert seen == [7, 7, 7]
    obs_tracing.clear_ctx()
    streaming.stream_map([1, 2, 3], lambda x: x, lambda s: s, fetch)
    assert seen[3:] == [0, 1, 2]
    assert not obs_tracing.get_ctx()


def test_span_records_to_the_tracer_with_late_counts():
    tr = obs_tracing.enable_tracing(tracer_=Tracer())
    obs_tracing.set_ctx(chunk=2)
    with obs_tracing.span("repro/x", rows=3) as sp:
        sp.set_metadata(rescued=1)
    (ev,) = [e for e in tr.chrome()["traceEvents"] if e["ph"] == "X"]
    assert ev["name"] == "repro/x"
    assert ev["args"] == {"chunk": 2, "rows": 3, "rescued": 1}
