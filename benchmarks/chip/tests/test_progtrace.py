"""The program reduction (``progtrace.py``): device idle split by the
program's ``repro/`` spans on hand-made events, its readers, and the whole
reduction on an excerpt of a ``chr21.pe150 --trace 1`` run recorded on a
TPU v5e (``data/trace_pe150_program.pbtxt``, cut by
``make_program_fixture.py``)."""
import os
from pathlib import Path

import pytest

import devtrace
import progtrace
import run

DATA = Path(__file__).parent / "data"
LOOP, FETCH = (0, 0), (0, 1)


def _span(name, s, e, line=LOOP, **stats):
    return (name, line, s, e, stats)


def test_a_gap_is_split_across_the_spans_it_overlaps():
    # device busy [0, 1) and [4, 10): one idle gap [1, 4)
    ops = [(0.0, 1.0), (4.0, 10.0)]
    spans = [_span("repro/sam", 0.5, 2.0, records=8),
             _span("repro/fastq", 2.0, 3.5, reads=8)]
    out = progtrace.reduce_program([ops], spans, (0.0, 10.0), LOOP)
    assert out["idle_s"] == pytest.approx({"repro/sam": 1.0,
                                           "repro/fastq": 1.5})
    assert out["idle_outside_s"] == pytest.approx(0.5)
    assert out["span_s"] == pytest.approx({"repro/sam": 1.5,
                                           "repro/fastq": 1.5})
    assert out["stats"] == {"repro/sam": {"records": 8},
                            "repro/fastq": {"reads": 8}}
    assert out["count"] == {"repro/sam": 1, "repro/fastq": 1}


def test_the_innermost_span_takes_each_instant():
    ops = [(0.0, 1.0), (9.0, 10.0)]
    spans = [_span("repro/map", 1.0, 8.0, reads=4, syncs=12),
             _span("repro/map/sync", 2.0, 3.0),
             _span("repro/map/dispatch", 5.0, 6.0, stage="linear",
                   lin_cap=256)]
    out = progtrace.reduce_program([ops], spans, (0.0, 10.0), LOOP)
    assert out["idle_s"] == pytest.approx({
        "repro/map": 5.0, "repro/map/sync": 1.0, "repro/map/dispatch": 1.0})
    assert out["idle_outside_s"] == pytest.approx(1.0)
    # a string stat is not summed
    assert out["stats"]["repro/map/dispatch"] == {"lin_cap": 256}


def test_spans_on_another_thread_take_no_idle():
    ops = [(0.0, 1.0), (3.0, 4.0)]
    spans = [_span("repro/map", 0.0, 4.0, syncs=12),
             _span("repro/map/fetch", 1.0, 3.0, line=FETCH, arrays=10)]
    out = progtrace.reduce_program([ops], spans, (0.0, 4.0), LOOP)
    assert out["idle_s"] == pytest.approx({"repro/map": 2.0})
    assert out["span_s"]["repro/map/fetch"] == pytest.approx(2.0)
    assert out["stats"]["repro/map/fetch"] == {"arrays": 10}


def test_spans_are_clipped_to_the_window_and_idle_averaged_over_devices():
    a = [(0.0, 2.0)]
    b = [(0.0, 4.0)]
    spans = [_span("repro/sam", 1.0, 6.0, records=2),
             _span("repro/fastq", 5.0, 6.0, reads=2)]   # after the window
    out = progtrace.reduce_program([a, b], spans, (0.0, 4.0), LOOP)
    assert out["idle_s"] == pytest.approx({"repro/sam": 1.0})
    assert out["span_s"] == pytest.approx({"repro/sam": 3.0})
    assert "repro/fastq" not in out["count"]


def test_segments_are_disjoint_and_named_by_the_innermost_span():
    segs = progtrace.segments([("a", 1.0, 5.0), ("b", 2.0, 3.0)], 0.0, 6.0)
    assert segs == [(0.0, 1.0, None), (1.0, 2.0, "a"), (2.0, 3.0, "b"),
                    (3.0, 5.0, "a"), (5.0, 6.0, None)]


def test_a_trace_without_program_spans_reads_nothing():
    """The parent program has no ``repro/`` spans: the reduction, and so
    every reader, is empty; ``devtrace``'s reduction of the same trace is
    untouched."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        (DATA / "trace_se150.pbtxt").read_text())
    devices, spans, window, loop = progtrace.events(pd)
    assert devices and window is not None and spans == []
    assert progtrace.reduce_program(devices, spans, window, loop) is None
    assert devtrace.reduce_or_none(*devtrace.events(pd)) is not None


def _run_data(**kw):
    base = dict(n_reads=2048, paired=True, read_len=150, band=13,
                spans={}, stats={}, compiles=0, peak_bytes=None,
                trace=dict(busy_s=1.0, window_s=2.0, module_s={}))
    base.update(kw)
    return run.RunData(**base)


READERS = ("fastq_idle_us_per_read", "sam_idle_us_per_read",
           "mapper_bubble_us_per_read", "mapper_syncs_per_chunk",
           "rescue_fill_pct")


def test_readers_arithmetic(monkeypatch):
    prog = dict(idle_s={"repro/fastq": 0.02048, "repro/sam": 0.2048,
                        "repro/map": 0.002048, "repro/map/sync": 0.002048},
                idle_outside_s=0.0, span_s={},
                stats={"repro/map": {"reads": 4096, "syncs": 48},
                       "repro/pairing/rescue": {"rows": 300,
                                                "bucket": 512}},
                count={"repro/fastq": 2, "repro/sam": 2, "repro/map": 2,
                       "repro/pairing/rescue": 4})
    monkeypatch.setattr(progtrace, "for_run", lambda r: prog)
    r = _run_data()
    got = {m: run.reader(m)(r) for m in READERS}
    assert got == pytest.approx({
        "fastq_idle_us_per_read": 10.0, "sam_idle_us_per_read": 100.0,
        "mapper_bubble_us_per_read": 2.0, "mapper_syncs_per_chunk": 24.0,
        "rescue_fill_pct": 100 * 300 / 512})


def test_readers_are_silent_without_program_spans(monkeypatch):
    monkeypatch.setattr(progtrace, "for_run", lambda r: None)
    assert all(run.reader(m)(_run_data()) is None for m in READERS)


def test_for_run_reads_the_runs_own_trace(tmp_path, monkeypatch):
    """The newest trace under the work directory, read once, and only
    where its window is the one the run's ``devtrace`` reduction holds."""
    for cell, stamp in (("a", 1.0), ("b", 2.0)):
        f = tmp_path / cell / "trace" / "plugins" / "x.xplane.pb"
        f.parent.mkdir(parents=True)
        f.write_bytes(b"")
        os.utime(f, (stamp, stamp))
    read = []

    def fake_reduce(path):
        read.append(Path(path).parts[-4])
        return dict(window_s=2.0, idle_s={}, idle_outside_s=0.0,
                    span_s={}, stats={}, count={})
    monkeypatch.setattr(progtrace, "reduce_file", fake_reduce)
    monkeypatch.setattr(progtrace, "_CACHE", {})
    assert progtrace.for_run(_run_data(), str(tmp_path)) is not None
    assert progtrace.for_run(_run_data(), str(tmp_path)) is not None
    assert read == ["b"]
    other = _run_data(trace=dict(busy_s=1.0, window_s=3.0, module_s={}))
    assert progtrace.for_run(other, str(tmp_path)) is None
    assert progtrace.for_run(_run_data(trace=None), str(tmp_path)) is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(
        (DATA / "trace_pe150_program.pbtxt").read_text())
    return pd, progtrace.reduce_program(*progtrace.events(pd))


def test_recorded_trace_splits_all_of_the_idle(recorded):
    pd, prog = recorded
    dev = devtrace.reduce_or_none(*devtrace.events(pd))
    idle = dev["window_s"] - dev["busy_s"]
    split = sum(prog["idle_s"].values()) + prog["idle_outside_s"]
    assert split == pytest.approx(idle, rel=1e-9)
    assert prog["idle_outside_s"] <= 0.1 * idle
    # the loop's thread only: the fetch thread's spans take no idle
    assert "repro/map/fetch" not in prog["idle_s"]
    assert prog["span_s"]["repro/map/fetch"] > 0


def test_recorded_trace_reads_the_five_metrics(recorded, monkeypatch):
    _, prog = recorded
    monkeypatch.setattr(progtrace, "for_run", lambda r: prog)
    r = _run_data(n_reads=prog["stats"]["repro/sam"]["records"])
    got = {m: run.reader(m)(r) for m in READERS}
    assert 20 < got["fastq_idle_us_per_read"] < 60
    assert 80 < got["sam_idle_us_per_read"] < 160
    assert 1 < got["mapper_bubble_us_per_read"] < 20
    # two 1,024-read engine chunks a 1,024-pair chunk, 12 reads back each
    assert got["mapper_syncs_per_chunk"] == 24
    assert 50 < got["rescue_fill_pct"] <= 100
