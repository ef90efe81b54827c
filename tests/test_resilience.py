"""Fault-tolerance policy layer (tier-1 units): config validation with
recoverable ValueErrors, deterministic fault injection, the degradation
ladder state machine, admission control / deadlines at the service, the
transactional flush, and retry/bisection quarantine on a real (small)
mapping world."""
import dataclasses
import time

import numpy as np
import pytest

from repro.core.mapper import Mapper
from repro.core.pipeline import MapperConfig, map_reads
from repro.core.resilience import (AdmissionConfig, DegradeLadder,
                                   FaultInjector, InjectedFault,
                                   MappingError, ResilientMapper,
                                   RetryPolicy, ShedError)
from repro.core.serving import BatcherConfig, MappingService, ReadBatcher

# a no-wait policy for tests: failures must not sleep the suite
FAST = RetryPolicy(max_attempts=2, backoff_s=0.0, bisect_min=4,
                   degrade_after=1)


@pytest.fixture(scope="module")
def world():
    from repro.core.index import build_index
    from repro.data.genome import make_reference, sample_reads
    ref = make_reference(8_000, seed=11, repeat_frac=0.03)
    idx = build_index(ref)
    rs = sample_reads(ref, 64, seed=13)
    return idx, rs.reads


# ------------------------------------------------------ config validation

def test_batcher_config_rejects_non_pow2():
    with pytest.raises(ValueError, match=r"bucket_min=48.*power"):
        BatcherConfig(bucket_min=48)
    with pytest.raises(ValueError, match=r"bucket_max=0.*power"):
        BatcherConfig(bucket_max=0)
    with pytest.raises(ValueError, match=r"bucket_min=128 must be <= "
                                         r"bucket_max=64"):
        BatcherConfig(bucket_min=128, bucket_max=64)


def test_read_batcher_submit_rejects_bad_shapes():
    bat = ReadBatcher(150)
    with pytest.raises(ValueError, match=r"expected \(n, 150\) reads, "
                                         r"got \(3, 100\)"):
        bat.submit(np.zeros((3, 100), np.uint8))
    with pytest.raises(ValueError, match=r"expected \(n, 150\)"):
        bat.submit(np.zeros(150, np.uint8))        # 1-D
    with pytest.raises(ValueError, match="empty read batch"):
        bat.submit(np.zeros((0, 150), np.uint8))
    assert bat.pending_reads == 0                  # nothing was enqueued


def test_policy_configs_validate():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="bisect_min"):
        RetryPolicy(bisect_min=0)
    with pytest.raises(ValueError, match="backoff_mult"):
        RetryPolicy(backoff_mult=0.5)
    with pytest.raises(ValueError, match="unknown admission policy"):
        AdmissionConfig(policy="drop")
    with pytest.raises(ValueError, match="max_pending_reads"):
        AdmissionConfig(max_pending_reads=0)
    with pytest.raises(ValueError, match="deadline_s"):
        AdmissionConfig(deadline_s=0.0)


# -------------------------------------------------------- fault injector

def test_injector_deterministic_per_site():
    a = FaultInjector(seed=7, rates={"bucket": 0.5, "fastq_record": 0.5})
    b = FaultInjector(seed=7, rates={"bucket": 0.5, "fastq_record": 0.5})
    seq_a = [a.fire("bucket") for _ in range(64)]
    # interleave another site: streams are independent, so "bucket"
    # must not be perturbed by "fastq_record" draws
    seq_b = []
    for _ in range(64):
        b.fire("fastq_record")
        seq_b.append(b.fire("bucket"))
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)
    assert a.fired["bucket"] == sum(seq_a)
    assert [FaultInjector(seed=8, rates={"bucket": 0.5}).fire("bucket")
            for _ in range(64)] != seq_a            # seed actually matters


def test_injector_from_spec():
    inj = FaultInjector.from_spec(
        "bucket=0.125,record=0.01,stall=1,stall_s=0.5,seed=3,"
        "poison=5;9,engines=fused;pallas")
    assert inj.seed == 3 and inj.stall_s == 0.5
    assert inj.rates == {"bucket": 0.125, "fastq_record": 0.01,
                         "fetch_stall": 1.0}
    assert inj.poison_rows == {5, 9}
    assert inj.fail_engines == {"fused", "pallas"}
    assert inj.armed
    assert not FaultInjector.from_spec("seed=3").armed
    with pytest.raises(ValueError, match="key=value"):
        FaultInjector.from_spec("bucket")


def test_injector_block_checks():
    inj = FaultInjector(poison_rows=[5])
    inj.check_block(6, 10, engine="compacted", backend="jnp")  # clean
    with pytest.raises(InjectedFault, match=r"poisoned read\(s\) \[5\]"):
        inj.check_block(0, 8, engine="compacted", backend="jnp")
    eng = FaultInjector(fail_engines=["fused"])
    eng.check_block(0, 8, engine="compacted", backend="jnp")   # clean
    with pytest.raises(InjectedFault, match="'fused' is marked failing"):
        eng.check_block(0, 8, engine="fused", backend="jnp")


# ------------------------------------------------------- degrade ladder

def test_degrade_ladder_rungs_and_stickiness():
    lad = DegradeLadder(MapperConfig(engine="fused", wf_backend="pallas"),
                        degrade_after=2)
    assert [(c.engine, c.wf_backend) for c in lad.rungs] == [
        ("fused", "pallas"), ("compacted", "pallas"), ("compacted", "jnp")]
    assert not lad.fail()                   # streak 1 < degrade_after
    lad.ok()                                # success resets the streak
    assert not lad.fail() and lad.fail()    # two consecutive -> degrade
    assert lad.level == 1 and lad.degraded
    lad.ok()
    assert lad.level == 1                   # sticky: ok() never climbs
    assert lad.fail() is False and lad.fail() is True
    assert lad.level == 2 and not lad.fail()  # bottom rung: nowhere to go
    assert lad.steps == 2
    assert "compacted/jnp" in lad.describe()


def test_degrade_ladder_trivial_for_base_config():
    lad = DegradeLadder(MapperConfig(engine="compacted", wf_backend="jnp"))
    assert len(lad.rungs) == 1
    assert not lad.fail() and not lad.degraded


# ---------------------------------------------- retry/bisect on a mapper

def test_resilient_map_clean_matches_plain(world):
    idx, reads = world
    cfg = MapperConfig(engine="compacted")
    res, mask, counters = ResilientMapper(Mapper(idx, cfg), FAST).map(reads)
    assert not mask.any() and res.failed is None
    base = map_reads(idx, reads, cfg)
    np.testing.assert_array_equal(res.position, base.position)
    np.testing.assert_array_equal(res.distance, base.distance)
    assert counters == dict(retries=0, failed_reads=0, failed_blocks=0,
                            degraded_steps=0)


def test_poisoned_row_quarantined_by_bisection(world):
    idx, reads = world
    cfg = MapperConfig(engine="compacted")
    inj = FaultInjector(poison_rows=[5])
    rm = ResilientMapper(Mapper(idx, cfg, injector=inj), FAST, injector=inj)
    res, mask, counters = rm.map(reads)
    # bisection narrows the failure to the bisect_min-sized block
    # holding row 5 (64 -> 32 -> 16 -> 8 -> rows [4, 8)), not the batch
    assert mask.sum() == FAST.bisect_min
    np.testing.assert_array_equal(np.flatnonzero(mask), np.arange(4, 8))
    assert res.failed is not None
    np.testing.assert_array_equal(res.failed, mask)
    # quarantined rows come back unmapped; healthy rows match plain
    base = map_reads(idx, reads, cfg)
    assert not res.mapped[mask].any()
    assert (res.position[mask] == -1).all()
    np.testing.assert_array_equal(res.position[~mask],
                                  base.position[~mask])
    np.testing.assert_array_equal(res.ops[~mask], base.ops[~mask])
    assert counters["failed_reads"] == FAST.bisect_min
    assert counters["failed_blocks"] == 1 and counters["retries"] > 0
    assert res.stats.failed_reads == FAST.bisect_min
    assert res.stats.extra["resilience"] == counters


def test_transient_fault_retried_away(world):
    idx, reads = world
    # rate 1.0 on the first draw only: fail once, then clean forever
    class OneShot(FaultInjector):
        def __init__(self):
            super().__init__(rates={"bucket": 1.0})
            self._shots = 1

        def fire(self, site):
            if site == "bucket" and self._shots > 0:
                self._shots -= 1
                return True
            return False

    rm = ResilientMapper(Mapper(idx, MapperConfig(engine="compacted")),
                         RetryPolicy(max_attempts=3, backoff_s=0.0),
                         injector=OneShot())
    res, mask, counters = rm.map(reads)
    assert not mask.any() and counters["retries"] == 1
    assert counters["failed_reads"] == 0


# ------------------------------------------------- service-level policy

def _service(idx, **kw):
    return MappingService(idx, MapperConfig(engine="compacted"),
                          BatcherConfig(bucket_min=8, bucket_max=32), **kw)


def test_admission_shed(world):
    idx, reads = world
    svc = _service(idx, admission=AdmissionConfig(max_pending_reads=16,
                                                  policy="shed"))
    svc.submit(reads[:10])
    with pytest.raises(ShedError, match="resubmit after a flush"):
        svc.submit(reads[10:20])
    assert svc.totals["shed_requests"] == 1
    # a single oversize request against an empty queue is still accepted
    svc.flush()
    rid = svc.submit(reads[:32])
    assert isinstance(svc.flush()[rid].position, np.ndarray)


def test_admission_block_drains_and_delivers_later(world):
    idx, reads = world
    svc = _service(idx, admission=AdmissionConfig(max_pending_reads=16,
                                                  policy="block"))
    r0 = svc.submit(reads[:10])
    r1 = svc.submit(reads[10:20])   # overflow -> synchronous drain of r0
    assert svc.batcher.pending_reads == 10
    out = svc.flush()               # delivers r0 (held) and r1 together
    assert set(out) == {r0, r1}
    assert svc.totals["shed_requests"] == 0


def test_deadline_expiry_resolves_to_error(world):
    idx, reads = world
    svc = _service(idx)
    r0 = svc.submit(reads[:8], deadline_s=0.01)
    r1 = svc.submit(reads[8:20])
    time.sleep(0.03)
    out = svc.flush()
    assert isinstance(out[r0], MappingError)
    assert out[r0].error_type == "deadline" and out[r0].n_reads == 8
    assert not out[r0].ok
    assert svc.totals["deadline_misses"] == 1
    # the live request still mapped, against the rebuilt batch
    np.testing.assert_array_equal(
        out[r1].position,
        map_reads(idx, reads[8:20], MapperConfig(engine="compacted"))
        .position)


def test_flush_transactional_on_internal_failure(world):
    idx, reads = world
    inj = FaultInjector(rates={"flush": 1.0})
    svc = _service(idx, injector=inj)
    rids = [svc.submit(reads[:10]), svc.submit(reads[10:20])]
    out = svc.flush()
    # every drained rid resolves exactly once, to a structured error
    assert sorted(out) == sorted(rids)
    for rid in rids:
        assert isinstance(out[rid], MappingError)
        assert out[rid].error_type == "internal"
        assert "InjectedFault" in out[rid].message
    assert svc.totals["failed_requests"] == 2
    assert svc.flush() == {}        # nothing stranded in pending state


def test_flush_partial_quarantine_per_request(world):
    idx, reads = world
    # poison one row of the first request; the second must be untouched
    inj = FaultInjector(poison_rows=[2])
    svc = _service(idx, retry=FAST, injector=inj)
    r0 = svc.submit(reads[:8])
    r1 = svc.submit(reads[8:20])
    out = svc.flush()
    assert out[r0].failed is not None and out[r0].failed.sum() > 0
    assert not out[r0].mapped[out[r0].failed].any()
    assert out[r1].failed is None or not out[r1].failed.any()
    np.testing.assert_array_equal(
        out[r1].position,
        map_reads(idx, reads[8:20], MapperConfig(engine="compacted"))
        .position)
    assert svc.totals["failed_reads"] > 0


def test_mapping_error_shape():
    e = MappingError("execution", "boom", n_reads=8, attempts=2)
    assert not e.ok and e.error_type == "execution"
    assert dataclasses.asdict(e)["n_reads"] == 8


@pytest.mark.parametrize("fault", ["lowering", "injected"])
def test_compile_errors_propagate_injected_faults_degrade(world,
                                                          monkeypatch,
                                                          fault):
    """A kernel the compiler refuses must surface, not retry or step the
    ladder down to the jnp rung (which would hand back reference results
    and exit 0); an injected fault on the same rung still degrades."""
    import jax

    from repro.kernels import ops
    idx, reads = world
    cfg = MapperConfig(engine="compacted", wf_backend="pallas")
    inj = None
    if fault == "lowering":
        def refuse(*a, **k):
            raise NotImplementedError("Reductions over int8 not implemented")
        monkeypatch.setattr(ops, "linear_wf", refuse)
        jax.clear_caches()      # the stage jit must re-trace the kernel
    else:
        inj = FaultInjector(fail_engines=["pallas"])
    rm = ResilientMapper(Mapper(idx, cfg, injector=inj), FAST, injector=inj)
    try:
        if fault == "lowering":
            with pytest.raises(NotImplementedError, match="int8"):
                rm.map(reads)
            assert rm.counters["retries"] == 0
            assert rm.counters["degraded_steps"] == 0
            assert not rm.ladder.degraded
        else:
            res, mask, counters = rm.map(reads)
            assert counters["degraded_steps"] == 1
            assert rm.cfg.wf_backend == "jnp"
            assert not mask.any() and res.mapped.all()
    finally:
        jax.clear_caches()
