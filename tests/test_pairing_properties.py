"""Property-based paired-end conformance: for simulated paired sets and
for adversarial synthetic mate results, every emitted pair satisfies the
FLAG algebra (0x40 xor 0x80, mate bits mirror each other, 0x2 implies
both mapped), TLEN(R1) == -TLEN(R2), and CIGAR query-lengths re-sum to
the read length; plus a byte-exact golden-file SAM conformance test
(tests/golden/) and unit coverage of the MAPQ model, the insert-size
tracker, mate rescue, and the paired serving path."""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.index import build_index
from repro.core.mapper import Mapper, split_result
from repro.core.pairing import (MAPQ_MAX, InsertSizeTracker, compute_mapq,
                                resolve_pairs)
from repro.core.pipeline import MapperConfig, MappingResult
from repro.data.genome import make_reference, sample_pairs
from repro.io.cigar import cigar_query_len
from repro.io.fasta import Contig, ReferenceMap
from repro.io.sam import (FLAG_MATE_REVERSE, FLAG_MATE_UNMAPPED,
                          FLAG_PAIRED, FLAG_PROPER, FLAG_READ1, FLAG_READ2,
                          FLAG_REVERSE, FLAG_UNMAPPED,
                          emit_paired_alignments, sam_header, validate_sam)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
READ_LEN = 100
N_PAIRS = 16

_WORLD = None


def _world():
    """Module-cached mapping world (plain function, not a fixture, so the
    hypothesis @given tests can reach it too — the vendored stub cannot
    inject pytest fixtures)."""
    global _WORLD
    if _WORLD is None:
        ref = make_reference(12_000, seed=40, repeat_frac=0.0)
        idx = build_index(ref, read_len=READ_LEN)
        cfg = MapperConfig.from_index(idx, both_strands=True)
        _WORLD = ref, idx, cfg, Mapper(idx, cfg)
    return _WORLD


@pytest.fixture(scope="module")
def world():
    return _world()


def _contigs(ref):
    return [Contig("chrT", len(ref), 0)]


def _paired_sam(world, seed: int, n_pairs: int = N_PAIRS):
    """Simulate -> map (one stacked batch) -> resolve -> emit; returns
    (sam_text, PairResolution, PairedReadSet)."""
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, n_pairs, read_len=READ_LEN, insert_mean=300,
                      insert_sd=30, seed=seed, unmappable_frac=0.15)
    res1, res2 = mapper.map_pairs(ps.reads1, ps.reads2)
    pr = resolve_pairs(res1, res2, cfg=cfg, ref=ref,
                       reads1=ps.reads1, reads2=ps.reads2)
    names = [f"p{seed}_{i}" for i in range(n_pairs)]
    recs = list(emit_paired_alignments(
        pr, names, ps.reads1, ps.quals1, ps.reads2, ps.quals2,
        ReferenceMap(_contigs(ref))))
    text = "\n".join(sam_header(_contigs(ref)) + recs) + "\n"
    return text, pr, ps


def _flag_algebra(records):
    """The pair-FLAG invariants, asserted record-by-record (independent
    of validate_sam, which is itself under test here)."""
    by_name: dict[str, list] = {}
    for ln in records:
        f = ln.split("\t")
        by_name.setdefault(f[0], []).append(f)
    for qname, pair in by_name.items():
        assert len(pair) == 2, qname
        fl = [int(f[1]) for f in pair]
        assert all(x & FLAG_PAIRED for x in fl)
        # exactly one R1 and one R2, each with exactly one of 0x40/0x80
        assert all(bool(x & FLAG_READ1) != bool(x & FLAG_READ2) for x in fl)
        assert bool(fl[0] & FLAG_READ1) != bool(fl[1] & FLAG_READ1)
        for me, other in ((0, 1), (1, 0)):
            # mate bits mirror the mate's own state
            assert bool(fl[me] & FLAG_MATE_UNMAPPED) == \
                bool(fl[other] & FLAG_UNMAPPED)
            if not fl[other] & FLAG_UNMAPPED:
                assert bool(fl[me] & FLAG_MATE_REVERSE) == \
                    bool(fl[other] & FLAG_REVERSE)
        # 0x2 implies both mapped, and is set on both or neither
        assert bool(fl[0] & FLAG_PROPER) == bool(fl[1] & FLAG_PROPER)
        if fl[0] & FLAG_PROPER:
            assert not any(x & FLAG_UNMAPPED for x in fl)
            assert not any(x & FLAG_MATE_UNMAPPED for x in fl)
        # TLEN symmetry
        assert int(pair[0][8]) == -int(pair[1][8]), qname
        # CIGAR query length re-sums to the read length
        for f in pair:
            assert len(f[9]) == READ_LEN
            if f[5] != "*":
                assert cigar_query_len(f[5]) == READ_LEN


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_property_simulated_pairs_conform(seed):
    text, _, _ = _paired_sam(_world(), seed)
    records = [ln for ln in text.splitlines() if not ln.startswith("@")]
    assert len(records) == 2 * N_PAIRS
    _flag_algebra(records)
    stats = validate_sam(text, expect_reads=2 * N_PAIRS, require_mapq=True)
    assert stats["n_paired"] == 2 * N_PAIRS


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 15), min_size=1, max_size=12))
def test_property_synthetic_states_conform(states):
    """Adversarial host-side states the simulator rarely produces: every
    combination of (mate1 mapped, mate2 mapped, per-mate strands)
    including both-unmapped, same-strand discordant, and far-apart
    loci."""
    n = len(states)
    rng = np.random.default_rng(sum(states) + 7 * n)
    sat = 32

    def mk(mapped, strand):
        pos = np.where(mapped, rng.integers(0, 900, n), -1).astype(np.int64)
        return MappingResult(
            position=pos,
            distance=np.where(mapped, rng.integers(0, 6, n), sat),
            distance2=np.full(n, sat, dtype=np.int64),
            mapped=np.asarray(mapped, bool),
            strand=np.asarray(strand, np.int8))

    m1 = np.array([bool(s & 1) for s in states])
    m2 = np.array([bool(s & 2) for s in states])
    s1 = np.array([int(bool(s & 4)) for s in states], np.int8)
    s2 = np.array([int(bool(s & 8)) for s in states], np.int8)
    res1, res2 = mk(m1, s1), mk(m2, s2)
    cfg = MapperConfig(read_len=20)
    pr = resolve_pairs(res1, res2, cfg=cfg)
    rm = ReferenceMap([Contig("c", 1000, 0)])
    reads = np.zeros((n, 20), np.uint8)
    quals = np.full((n, 20), ord("I"), np.uint8)
    names = [f"s{i}" for i in range(n)]
    recs = list(emit_paired_alignments(pr, names, reads, quals, reads,
                                       quals, rm))
    assert len(recs) == 2 * n

    def check(records):
        by = {}
        for ln in records:
            f = ln.split("\t")
            by.setdefault(f[0], []).append(f)
        for pair in by.values():
            fl = [int(f[1]) for f in pair]
            assert all(x & FLAG_PAIRED for x in fl)
            assert bool(fl[0] & FLAG_READ1) != bool(fl[1] & FLAG_READ1)
            assert int(pair[0][8]) == -int(pair[1][8])
            for me, other in ((0, 1), (1, 0)):
                assert bool(fl[me] & FLAG_MATE_UNMAPPED) == \
                    bool(fl[other] & FLAG_UNMAPPED)
    check(recs)
    text = "\n".join(sam_header([Contig("c", 1000, 0)]) + recs) + "\n"
    validate_sam(text, expect_reads=2 * n, require_mapq=True)


# ------------------------------------------------------------- golden file

def test_golden_paired_sam_conformance(world):
    """Byte-exact conformance against the checked-in golden SAM.  If a
    deliberate behavior change moves the output, regenerate with:
    PYTHONPATH=src python tests/make_golden.py"""
    text, _, _ = _paired_sam(world, seed=779)
    golden_path = os.path.join(GOLDEN_DIR, "paired_small.sam")
    with open(golden_path) as f:
        golden = f.read()
    assert text == golden, (
        "paired SAM output drifted from tests/golden/paired_small.sam; "
        "if intentional, regenerate via tests/make_golden.py")


# ----------------------------------------------------- accuracy + rescue

def test_proper_pair_accuracy_vs_ground_truth(world):
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, 64, read_len=READ_LEN, insert_mean=300,
                      insert_sd=30, seed=51)
    res1, res2 = mapper.map_pairs(ps.reads1, ps.reads2)
    pr = resolve_pairs(res1, res2, cfg=cfg, ref=ref,
                       reads1=ps.reads1, reads2=ps.reads2)
    ok = ((np.abs(pr.res1.position - ps.pos1) <= 6)
          & (np.abs(pr.res2.position - ps.pos2) <= 6)
          & (pr.res1.strand == ps.strand1)
          & (pr.res2.strand == ps.strand2) & pr.proper)
    assert ok.mean() >= 0.97, pr.stats
    # observed fragment length recovers the simulator's ground truth
    close = np.abs(pr.insert[pr.proper]
                   - ps.isize[pr.proper]) <= 6
    assert close.mean() >= 0.9


def test_mate_rescue_recovers_killed_mate(world):
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, 32, read_len=READ_LEN, insert_mean=300,
                      insert_sd=30, seed=52)
    res1, res2 = mapper.map_pairs(ps.reads1, ps.reads2)
    kill = np.flatnonzero(res2.mapped)[:6]
    res2.mapped[kill] = False
    res2.position[kill] = -1
    pr = resolve_pairs(res1, res2, cfg=cfg, ref=ref,
                       reads1=ps.reads1, reads2=ps.reads2)
    assert pr.stats["n_rescued"] == len(kill)
    assert pr.rescued2[kill].all()
    np.testing.assert_array_equal(pr.res2.strand[kill], ps.strand2[kill])
    assert (np.abs(pr.res2.position[kill] - ps.pos2[kill]) <= 2).all()
    # rescued mates are capped: never more confident than their anchor
    assert (pr.mapq2[kill] <= np.minimum(pr.mapq1[kill], 17)).all()


def test_rescue_rejects_junk_mate(world):
    """A genuinely unmappable mate (random sequence) must NOT be rescued
    into a fake placement."""
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, 16, read_len=READ_LEN, insert_mean=300,
                      insert_sd=30, seed=53, unmappable_frac=1.0)
    res1, res2 = mapper.map_pairs(ps.reads1, ps.reads2)
    assert res2.mapped.sum() == 0
    pr = resolve_pairs(res1, res2, cfg=cfg, ref=ref,
                       reads1=ps.reads1, reads2=ps.reads2)
    assert pr.stats["n_rescued"] == 0
    assert not pr.res2.mapped.any() and not pr.proper.any()
    assert (pr.mapq2 == 0).all()


# ------------------------------------------------------------- unit layer

def test_cross_contig_mates_never_proper():
    """Regression (review-found): in flat concatenated coordinates, R1 at
    the end of one contig and R2 at the start of the next sit a
    spacer-width apart — inside any permissive insert window — but a
    chimeric pair must never earn 0x2 nor feed the insert tracker."""
    sat = 32
    # contigs: [0, 1000) and [1200, 2200) with a 200-base spacer
    contig_starts = [0, 1200]
    res1 = MappingResult(position=np.array([950]),
                         distance=np.array([0]),
                         distance2=np.array([sat]),
                         mapped=np.array([True]),
                         strand=np.array([0], np.int8))
    res2 = MappingResult(position=np.array([1210]),
                         distance=np.array([0]),
                         distance2=np.array([sat]),
                         mapped=np.array([True]),
                         strand=np.array([1], np.int8))
    cfg = MapperConfig(read_len=100)
    tr = InsertSizeTracker()
    pr = resolve_pairs(res1, res2, cfg=cfg, tracker=tr,
                       contig_starts=contig_starts)
    assert not pr.proper[0]
    assert tr.n_observed == 0  # the pseudo-insert never enters the median
    # same geometry on a single contig IS concordant (sanity check)
    pr2 = resolve_pairs(res1, res2, cfg=cfg, contig_starts=[0])
    assert pr2.proper[0]
    # and the emitted records carry no 0x2 but still point at the mate
    rm = ReferenceMap([Contig("cA", 1000, 0), Contig("cB", 1000, 1200)])
    reads = np.zeros((1, 100), np.uint8)
    quals = np.full((1, 100), ord("I"), np.uint8)
    r1, r2 = list(emit_paired_alignments(pr, ["x"], reads, quals, reads,
                                         quals, rm))
    f1, f2 = r1.split("\t"), r2.split("\t")
    assert not int(f1[1]) & FLAG_PROPER and not int(f2[1]) & FLAG_PROPER
    assert f1[2] == "cA" and f1[6] == "cB" and int(f1[8]) == 0
    assert f2[2] == "cB" and f2[6] == "cA" and int(f2[8]) == 0


def test_exact_repeat_read_gets_zero_gap_distance2():
    """Regression (review-found): a read from an exact two-copy repeat
    shares ALL its minimizers between the copies, so the per-minimizer
    argmin collapse hides the second copy from the affine survey — the
    linear-stage co-optimality fold must still report distance2 ==
    distance (no gap, MAPQ ~0) instead of claiming uniqueness."""
    rng = np.random.default_rng(9)
    ref = rng.integers(0, 4, 6000).astype(np.uint8)
    ref[4000:4400] = ref[1000:1400]  # exact 400-base duplicate
    idx = build_index(ref, read_len=120)
    read = ref[1100:1220][None, :]  # read wholly inside the repeat
    uniq = ref[300:420][None, :]    # control: unique locus
    for engine in ("compacted", "padded"):
        cfg = MapperConfig.from_index(idx, engine=engine)
        res = Mapper(idx, cfg).map(np.concatenate([read, uniq]))
        assert res.mapped.all()
        assert res.distance2[0] == res.distance[0], engine  # ambiguous
        assert res.distance2[1] == cfg.sat_affine, engine   # unique
        q = compute_mapq(res.distance, res.distance2, res.mapped,
                         sat=cfg.sat_affine)
        assert q[0] == 0 and q[1] == MAPQ_MAX, engine
    # mesh path (1-shard in-process mesh): same calibration
    from repro.core.mapper import make_auto_mesh
    from repro.core.distributed import AXIS
    mesh = make_auto_mesh((1,), (AXIS,))
    mres = Mapper(idx, MapperConfig.from_index(idx), topology="mesh",
                  mesh=mesh).map(np.concatenate([read, uniq]))
    assert mres.mapped.all()
    assert mres.distance2[0] == mres.distance[0]
    assert mres.distance2[1] == MapperConfig.from_index(idx).sat_affine


def test_insert_tracker_window():
    tr = InsertSizeTracker(min_samples=8)
    assert tr.window() == tr.default_window  # bootstrap: permissive
    rng = np.random.default_rng(0)
    tr.update(rng.normal(350, 30, 256).astype(int))
    lo, hi = tr.window()
    assert lo < 350 < hi and 330 < tr.median < 370
    assert hi - lo < 2 * 350  # and it actually narrowed
    tr2 = InsertSizeTracker(max_samples=64)
    tr2.update(np.full(200, 100))
    assert len(tr2._samples) == 64 and tr2.n_observed == 200
    lo2, hi2 = tr2.window()
    assert lo2 < 100 < hi2  # zero-MAD library keeps a floored window


def test_compute_mapq_calibration():
    sat = 32
    d1 = np.array([0, 0, 0, 3, 0])
    d2 = np.array([sat, 0, 2, sat, sat])
    mapped = np.array([True, True, True, True, False])
    proper = np.array([True, False, False, False, False])
    mate = np.array([True, True, True, False, True])
    q = compute_mapq(d1, d2, mapped, sat=sat, proper=proper,
                     mate_mapped=mate)
    assert q[0] == MAPQ_MAX                   # unique + proper: top score
    assert q[1] == 0                          # exact co-optimal: no trust
    assert 0 < q[2] < q[0]                    # small gap, discordant: mid
    assert q[3] > 0                           # lone mate keeps solo score
    assert q[4] == 0                          # unmapped: always 0
    assert (q <= MAPQ_MAX).all() and (q >= 0).all()


def test_split_result_roundtrip(world):
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, 8, read_len=READ_LEN, seed=54)
    stacked = mapper.map(np.concatenate([ps.reads1, ps.reads2]))
    r1, r2 = split_result(stacked, 8)
    np.testing.assert_array_equal(r1.position, stacked.position[:8])
    np.testing.assert_array_equal(r2.position, stacked.position[8:])
    np.testing.assert_array_equal(r2.distance2, stacked.distance2[8:])
    assert r1.stats is stacked.stats and r2.stats is stacked.stats
    # and map_pairs is exactly this stack+split
    m1, m2 = mapper.map_pairs(ps.reads1, ps.reads2)
    np.testing.assert_array_equal(m1.position, r1.position)
    np.testing.assert_array_equal(m2.position, r2.position)


def test_service_submit_paired(world):
    ref, idx, cfg, mapper = world
    ps = sample_pairs(ref, 9, read_len=READ_LEN, seed=55)
    svc = mapper.serve()
    rid_single = svc.submit(ps.reads1[:3])
    rid_pair = svc.submit_paired(ps.reads1, ps.reads2)
    out = svc.flush()
    assert isinstance(out[rid_pair], tuple)
    r1, r2 = out[rid_pair]
    assert len(r1.position) == len(r2.position) == 9
    direct1, direct2 = mapper.map_pairs(ps.reads1, ps.reads2)
    np.testing.assert_array_equal(r1.position, direct1.position)
    np.testing.assert_array_equal(r2.position, direct2.position)
    np.testing.assert_array_equal(r2.distance2, direct2.distance2)
    assert not isinstance(out[rid_single], tuple)  # single stays single
