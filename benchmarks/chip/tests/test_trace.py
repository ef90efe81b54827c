"""The trace reducer: interval arithmetic on hand-made events, and the
whole reduction on an excerpt of a trace recorded on a TPU v5e
(``data/trace_se150.pbtxt``, cut by ``make_trace_fixture.py`` from a
``chr21.se150`` run with ``--trace 1``)."""
from pathlib import Path

import pytest

import devtrace as trace

FIXTURE = Path(__file__).parent / "data" / "trace_se150.pbtxt"


def test_union_merges_overlaps_and_nesting():
    iv = [(0.0, 1.0), (0.5, 2.0), (0.6, 0.7), (3.0, 4.0)]
    assert trace.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.union_length(iv) == pytest.approx(3.0)


def test_gaps_are_the_uncovered_window():
    busy = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (7.5, 9.0)]
    assert trace.gaps(busy, 0.0, 8.0) == [(0.0, 1.0), (3.0, 5.0),
                                          (6.0, 7.5)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_gap_named_by_the_span_overlapping_it_most():
    spans = [("mapper", 0.0, 1.2), ("sam_emit", 1.2, 3.0)]
    assert trace.label((1.0, 2.0), spans) == "sam_emit"
    assert trace.label((0.2, 1.3), spans) == "mapper"
    assert trace.label((5.0, 6.0), spans) == "none"


def test_reduce_events_by_module_and_window():
    dev = dict(modules=[(0.0, 1.0, "jit_a(12)"), (2.0, 4.0, "jit_b(7)"),
                        (9.0, 11.0, "jit_a(12)")],
               ops=[(0.0, 1.0, "jit_a/while.1"), (2.0, 3.0, "jit_b/fusion"),
                    (2.5, 4.0, "jit_b/copy"), (9.0, 11.0, "jit_a/while.1")])
    spans = [("ingest", 1.0, 2.0), ("sam_emit", 4.0, 9.0)]
    out = trace.reduce_events([dev], spans, (0.0, 10.0))
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(1.0 + 2.0 + 1.0)
    assert out["module_s"] == pytest.approx({"jit_a": 2.0, "jit_b": 2.0})
    assert out["device_ops"][0] == ("jit_a/while.1", pytest.approx(2.0))
    assert out["idle_gaps"] == [("sam_emit", pytest.approx(5.0)),
                                ("ingest", pytest.approx(1.0))]


def test_busy_is_averaged_over_devices():
    a = dict(modules=[], ops=[(0.0, 4.0, "m/x")])
    b = dict(modules=[], ops=[(0.0, 2.0, "m/x")])
    out = trace.reduce_events([a, b], [], (0.0, 4.0))
    assert out["busy_s"] == pytest.approx(3.0)


def test_op_names_are_cut_from_hlo_text():
    assert trace.op_name("%while.39 = (s32[]{:T(128)}) while(...)") == \
        "while.39"
    assert trace.op_name("fusion.2") == "fusion.2"
    assert trace.module_name("jit__linear_stage_impl(1234)") == \
        "jit__linear_stage_impl"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(FIXTURE.read_text())
    return trace.events(pd)


def test_recorded_trace_reduces(recorded):
    devices, spans, window = recorded
    out = trace.reduce_or_none(devices, spans, window)
    assert out is not None
    # busy time equals the union worked out on a 1 us grid
    lo, hi = window
    step = 1e-6
    covered = set()
    for s, e, _ in devices[0]["ops"]:
        a, b = max(s, lo), min(e, hi)
        covered.update(range(round((a - lo) / step), round((b - lo) / step)))
    assert out["busy_s"] == pytest.approx(len(covered) * step, rel=1e-3)
    assert 0 < out["busy_s"] <= out["window_s"]
    # the mapping stages are there, and no module outlasts the busy time
    for m in ("jit_seed_reads", "jit__linear_stage_impl",
              "jit__affine_stage_impl", "jit__winner_traceback"):
        assert out["module_s"].get(m, 0) > 0, m
    assert max(out["module_s"].values()) <= out["busy_s"] * (1 + 1e-9)
    # every op is named module/op, and gaps by the harness's layers
    assert all("/" in n and " = " not in n for n, _ in out["device_ops"])
    assert {n for n, _ in out["idle_gaps"]} <= {
        "ingest", "mapper", "pairing", "sam_emit", "none"}
    gap_total = sum(g[1] - g[0] for g in trace.gaps(
        [(s, e) for s, e, _ in devices[0]["ops"]], lo, hi))
    assert gap_total + out["busy_s"] == pytest.approx(out["window_s"])


def test_no_device_or_no_window_reads_nothing():
    assert trace.reduce_or_none([], [], (0.0, 1.0)) is None
    assert trace.reduce_or_none([dict(modules=[], ops=[])], [], None) is None
