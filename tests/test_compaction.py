"""Compacted execution engine: compaction primitives, Pallas-kernel parity
against the jnp references and the numpy band oracles, and compacted-vs-
padded pipeline equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.affine_wf import (banded_affine, banded_affine_dist,
                                  banded_affine_numpy)
from repro.core.compaction import (bucket_capacity, compact_indices,
                                   scatter_to)
from repro.core.filtering import gather_windows
from repro.core.linear_wf import banded_wf, banded_wf_numpy
from repro.kernels import ops

rng = np.random.default_rng(23)


# ---------------------------------------------------------------- primitives

def test_bucket_capacity_properties():
    for count in (0, 1, 2, 127, 128, 129, 1000, 5000):
        cap = bucket_capacity(count, align=128, cap_max=8192)
        assert cap & (cap - 1) == 0            # power of two
        assert cap % 128 == 0                  # lane-aligned
        assert cap >= min(max(count, 1), 8192)
    # ceiling: never exceeds next_pow2(cap_max)
    assert bucket_capacity(10 ** 9, align=128, cap_max=6144) == 8192
    # floor: never below align
    assert bucket_capacity(1, align=512, cap_max=8192) == 512


@pytest.mark.parametrize("read_len,k,eth,win_eth,lead", [
    (150, 12, 6, 6, (1000,)),
    (40, 8, 3, 3, (10, 4, 5)),
    (150, 12, 6, 4, (7, 9)),
], ids=["flat", "rmp", "narrow"])
def test_gather_windows_matches_dynamic_slice(read_len, k, eth, win_eth,
                                              lead):
    """The barrel-shift gather picks the windows a per-candidate
    ``dynamic_slice`` picks, start wrapping and clamping included
    (minimizer offsets past either end of the read), for a flat candidate
    axis, (R, M, P) rows with (R, M, 1) offsets, and a narrower window."""
    seg_len = 2 * (read_len + eth) - k
    wlen = read_len + 2 * win_eth
    segments = rng.integers(0, 5, (300, seg_len)).astype(np.uint8)
    occ = rng.integers(0, 300, lead).astype(np.int32)
    mpos = rng.integers(-4, read_len - k + 5,
                        lead[:-1] + (1,) if len(lead) == 3 else lead)
    mpos = mpos.astype(np.int32)
    got = gather_windows(jnp.asarray(segments), jnp.asarray(occ),
                         jnp.asarray(mpos), read_len=read_len, k=k, eth=eth,
                         win_eth=win_eth)
    starts = np.broadcast_to(read_len + eth - k - mpos - win_eth, lead)
    want = jax.vmap(lambda row, start: jax.lax.dynamic_slice_in_dim(
        jnp.asarray(segments)[row], start, wlen))(
            jnp.asarray(occ.reshape(-1)), jnp.asarray(starts.reshape(-1)))
    assert got.shape == lead + (wlen,)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1, wlen),
                                  np.asarray(want))


@given(st.integers(0, 10 ** 6), st.integers(1, 300))
@settings(max_examples=25, deadline=None)
def test_compact_scatter_roundtrip(seed, n):
    r = np.random.default_rng(seed)
    valid = jnp.asarray(r.random(n) < r.random())
    count = int(valid.sum())
    cap = bucket_capacity(count, align=8, cap_max=n)
    slots, slot_ok = compact_indices(valid, cap)
    # occupied slots list exactly the valid indices, original order kept
    want = np.flatnonzero(np.asarray(valid))[:cap]
    got = np.asarray(slots)[np.asarray(slot_ok)]
    np.testing.assert_array_equal(got, want)
    assert int(slot_ok.sum()) == min(count, cap)
    # scatter_to inverts the compaction
    vals = jnp.arange(cap, dtype=jnp.int32) + 100
    back = np.asarray(scatter_to(n, slots, slot_ok, vals, jnp.int32(-1)))
    assert (back[~np.asarray(valid)] == -1).all()
    for s, f in zip(got, range(len(got))):
        assert back[s] == 100 + f


# ------------------------------------------------- kernels vs numpy oracles

def _rand_pairs(R, n, eth, seed=0):
    r = np.random.default_rng(seed)
    s1 = r.integers(0, 4, (R, n)).astype(np.uint8)
    s2 = r.integers(0, 4, (R, n + 2 * eth)).astype(np.uint8)
    # half the instances hold a lightly-edited copy on the centre diagonal
    s2[: R // 2, eth : eth + n] = s1[: R // 2]
    for i in range(R // 2):
        for _ in range(int(r.integers(0, 4))):
            s2[i, eth + int(r.integers(0, n))] = r.integers(0, 4)
    return s1, s2


@pytest.mark.parametrize("R,n,eth", [(16, 24, 6), (24, 40, 4)])
def test_linear_pallas_matches_numpy_oracle(R, n, eth):
    s1, s2 = _rand_pairs(R, n, eth, seed=3)
    de, dm = ops.linear_wf(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                           block_r=8)
    je, jm = banded_wf(jnp.asarray(s1), jnp.asarray(s2), eth=eth)
    np.testing.assert_array_equal(np.asarray(de), np.asarray(je))
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(jm))
    for i in range(R):
        B, d_np = banded_wf_numpy(s1[i], s2[i], eth)
        assert int(de[i]) == d_np
        assert int(dm[i]) == int(B[n].min())


@pytest.mark.parametrize("R,n,eth,sat", [(12, 24, 6, 32), (16, 30, 4, 16)])
def test_affine_pallas_matches_numpy_oracle(R, n, eth, sat):
    s1, s2 = _rand_pairs(R, n, eth, seed=5)
    de, dm, dirs = ops.affine_wf(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                                 sat=sat, block_r=4)
    je, jm, jdirs = banded_affine(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                                  sat=sat)
    np.testing.assert_array_equal(np.asarray(de), np.asarray(je))
    np.testing.assert_array_equal(np.asarray(dirs), np.asarray(jdirs))
    for i in range(R):
        D, dirs_np, dist = banded_affine_numpy(s1[i], s2[i], eth=eth, sat=sat)
        assert int(de[i]) == dist
        assert int(dm[i]) == int(D.min())
        np.testing.assert_array_equal(np.asarray(dirs[i]), dirs_np)


@pytest.mark.parametrize("R,n,eth,sat", [(16, 24, 6, 32), (12, 36, 4, 16)])
def test_affine_dist_variants_match_dirs_variant(R, n, eth, sat):
    """banded_affine_dist (jnp) and affine_wf_dist (Pallas) return exactly
    the distances of the dirs-emitting reference."""
    s1, s2 = _rand_pairs(R, n, eth, seed=7)
    je, jm, _ = banded_affine(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                              sat=sat)
    de, dm = banded_affine_dist(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                                sat=sat)
    np.testing.assert_array_equal(np.asarray(de), np.asarray(je))
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(jm))
    ke, km = ops.affine_wf_dist(jnp.asarray(s1), jnp.asarray(s2), eth=eth,
                                sat=sat, block_r=8)
    np.testing.assert_array_equal(np.asarray(ke), np.asarray(je))
    np.testing.assert_array_equal(np.asarray(km), np.asarray(jm))


# --------------------------------------------- pipeline engine equivalence

@pytest.fixture(scope="module")
def small_world():
    from repro.core.index import build_index
    from repro.data.genome import make_reference, sample_reads
    ref = make_reference(8_000, seed=11, repeat_frac=0.03)
    idx = build_index(ref)
    rs = sample_reads(ref, 40, seed=13)
    junk = np.random.default_rng(15).integers(0, 4, (8, 150)).astype(np.uint8)
    reads = np.concatenate([rs.reads, junk])  # include unmapped reads
    return idx, reads


def _assert_same_mapping(a, b):
    for f in ("position", "distance", "mapped", "ops", "op_count",
              "linear_dist", "n_candidates"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)


def test_compacted_equals_padded_jnp(small_world):
    from repro.core.pipeline import MapperConfig, map_reads
    idx, reads = small_world
    a = map_reads(idx, reads, MapperConfig(engine="padded"))
    b = map_reads(idx, reads, MapperConfig(engine="compacted"))
    _assert_same_mapping(a, b)
    assert b.stats is not None
    assert b.stats["survivors"] <= b.stats["candidates_valid"]
    assert b.stats["linear_instances"] < b.stats["padded_linear_instances"]


def test_compacted_equals_padded_chunked(small_world):
    from repro.core.pipeline import MapperConfig, map_reads
    idx, reads = small_world
    a = map_reads(idx, reads, MapperConfig(engine="padded"))
    b = map_reads(idx, reads, MapperConfig(engine="compacted",
                                           chunk_reads=14))
    _assert_same_mapping(a, b)
    assert b.stats["n_chunks"] == 4


def test_pallas_backend_equals_jnp_reference(small_world):
    """map_reads with wf_backend="pallas" (interpret mode on CPU) produces
    identical positions/distances to the jnp reference."""
    from repro.core.pipeline import MapperConfig, map_reads
    idx, reads = small_world
    a = map_reads(idx, reads, MapperConfig(engine="padded",
                                           wf_backend="jnp"))
    b = map_reads(idx, reads, MapperConfig(engine="compacted",
                                           wf_backend="pallas",
                                           lin_block_r=128, aff_block_r=64))
    _assert_same_mapping(a, b)


def test_unknown_engine_and_backend_raise(small_world):
    from repro.core.pipeline import MapperConfig, map_reads
    from repro.core import wf_backend as wfb
    idx, reads = small_world
    with pytest.raises(ValueError):
        map_reads(idx, reads[:4], MapperConfig(engine="nope"))
    with pytest.raises(ValueError):
        wfb.linear_wf_dist(jnp.zeros((2, 10), jnp.uint8),
                           jnp.zeros((2, 22), jnp.uint8), eth=6,
                           backend="cuda")
