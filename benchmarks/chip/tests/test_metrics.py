"""Per-layer metric readers: their arithmetic on known counts, and silence
where there is nothing to read."""
import pytest

import run


def _run(**kw):
    base = dict(n_reads=1000, paired=False, read_len=150, band=13,
                spans=dict(ingest=0.01, mapper=0.5, pairing=0.0,
                           sam_emit=0.1),
                stats=dict(candidates=40_000, survivors=9_000,
                           linear_instances=65_536,
                           affine_dist_instances=16_384,
                           affine_dirs_instances=1_000),
                compiles=0, peak_bytes=980_000_000,
                trace=dict(busy_s=0.4, window_s=0.8,
                           module_s={"jit__linear_stage_impl": 0.2,
                                     "jit__affine_stage_impl": 0.05,
                                     "jit__strand_stage": 0.001,
                                     "jit__winner_traceback": 0.019}))
    base.update(kw)
    return run.RunData(**base)


def read(name, r):
    return run.reader(name)(r)


def test_linear_gcups_counts_valid_candidates_not_buckets():
    # 40,000 candidates x 150 rows x 13 cells in 0.2 s
    assert read("linear_wf_gcups", _run()) == pytest.approx(
        40_000 * 150 * 13 / 0.2 / 1e9)


def test_affine_gcups_counts_survivors_and_winners():
    # (9,000 survivors + 1,000 winners) x 150 x 13 in 0.07 s
    assert read("affine_wf_gcups", _run()) == pytest.approx(
        10_000 * 150 * 13 / 0.07 / 1e9)


def test_kernel_metrics_silent_without_their_stage():
    r = _run(trace=dict(busy_s=0.4, window_s=0.8,
                        module_s={"jit__fused_stage_impl": 0.3}))
    assert read("linear_wf_gcups", r) is None
    assert read("affine_wf_gcups", r) is None
    assert read("linear_wf_gcups", _run(trace=None)) is None


def test_device_and_span_metrics():
    r = _run()
    assert read("device_busy_us_per_read", r) == pytest.approx(400.0)
    assert read("device_idle_pct", r) == pytest.approx(50.0)
    assert read("peak_hbm_gb", r) == pytest.approx(0.98)
    assert read("mapper_us_per_read", r) == pytest.approx(500.0)
    assert read("sam_emit_us_per_read", r) == pytest.approx(100.0)
    assert read("ingest_us_per_read", r) == pytest.approx(10.0)
    assert read("compiles_in_window", r) == 0.0
    assert read("pairing_us_per_read", r) is None
    assert read("pairing_us_per_read", _run(paired=True)) == 0.0
    assert read("device_busy_us_per_read", _run(trace=None)) is None
    # busy time is per read of the traced window
    assert read("device_busy_us_per_read",
                _run(n_reads=4000)) == pytest.approx(100.0)
