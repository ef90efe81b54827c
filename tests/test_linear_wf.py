import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.linear_wf import banded_wf, banded_wf_numpy, full_wf_numpy


def _make_pair(r, n, eth, n_edits):
    """Random read + window holding an edited copy on the centre diagonal."""
    s1 = r.integers(0, 4, n).astype(np.uint8)
    lst = list(np.concatenate([r.integers(0, 4, eth), s1,
                               r.integers(0, 4, eth)]))
    for _ in range(n_edits):
        p = int(r.integers(eth, eth + n - 2))
        t = int(r.integers(0, 3))
        if t == 0:
            lst[p] = int(r.integers(0, 4))
        elif t == 1:
            lst.insert(p, int(r.integers(0, 4)))
        else:
            del lst[p]
    win = np.array((lst + [0] * (n + 2 * eth))[: n + 2 * eth], dtype=np.uint8)
    return s1, win


@given(st.integers(0, 10 ** 6), st.integers(10, 60), st.integers(0, 5))
@settings(max_examples=40, deadline=None)
def test_jnp_matches_numpy_oracle(seed, n, edits):
    r = np.random.default_rng(seed)
    eth = 6
    s1, win = _make_pair(r, n, eth, edits)
    _, d_np = banded_wf_numpy(s1, win, eth)
    d_end, d_min = banded_wf(jnp.array(s1), jnp.array(win), eth=eth)
    assert int(d_end) == d_np
    assert int(d_min) <= d_np


@given(st.integers(0, 10 ** 6), st.integers(10, 50))
@settings(max_examples=30, deadline=None)
def test_band_equals_full_when_within_eth(seed, n):
    """Ukkonen band correctness: if the true distance <= eth, the banded
    result is exact."""
    r = np.random.default_rng(seed)
    eth = 6
    s1, win = _make_pair(r, n, eth, int(r.integers(0, 4)))
    _, d_band = banded_wf_numpy(s1, win, eth)
    d_full = full_wf_numpy(s1, win[eth : eth + n])[n, n]
    if d_full <= eth:
        assert d_band == d_full
    else:
        assert d_band >= min(d_full, eth + 1) or d_band == eth + 1


@given(st.integers(0, 10 ** 6), st.integers(12, 40))
@settings(max_examples=20, deadline=None)
def test_identity_and_saturation(seed, n):
    r = np.random.default_rng(seed)
    eth = 6
    s1 = r.integers(0, 4, n).astype(np.uint8)
    win = np.concatenate([r.integers(0, 4, eth), s1,
                          r.integers(0, 4, eth)]).astype(np.uint8)
    d_end, _ = banded_wf(jnp.array(s1), jnp.array(win), eth=eth)
    assert int(d_end) == 0  # exact copy -> distance 0
    # a window of sentinel bases (never equal to any read base) saturates:
    # every path must pay >= n > eth edits
    s2w = np.full(len(win), 4, dtype=np.uint8)
    d_sat, _ = banded_wf(jnp.array(s1), jnp.array(s2w), eth=eth)
    assert int(d_sat) == eth + 1


def test_distance_bounded_by_edit_count():
    """Edit-distance upper bound: d <= number of substitutions applied."""
    r = np.random.default_rng(7)
    eth = 6
    for _ in range(20):
        n = int(r.integers(20, 80))
        s1 = r.integers(0, 4, n).astype(np.uint8)
        win = np.concatenate([r.integers(0, 4, eth), s1.copy(),
                              r.integers(0, 4, eth)]).astype(np.uint8)
        k = int(r.integers(0, 6))
        pos = r.choice(n, size=k, replace=False) if k else []
        for p in pos:
            win[eth + p] = (win[eth + p] + int(r.integers(1, 4))) % 4
        d_end, _ = banded_wf(jnp.array(s1), jnp.array(win), eth=eth)
        assert int(d_end) <= k


def test_batched_shapes():
    r = np.random.default_rng(3)
    eth = 6
    S1 = r.integers(0, 4, (4, 3, 25)).astype(np.uint8)
    S2 = r.integers(0, 4, (4, 3, 25 + 2 * eth)).astype(np.uint8)
    de, dm = banded_wf(jnp.array(S1), jnp.array(S2), eth=eth)
    assert de.shape == (4, 3) and dm.shape == (4, 3)
    for i in range(4):
        for j in range(3):
            _, dn = banded_wf_numpy(S1[i, j], S2[i, j], eth)
            assert int(de[i, j]) == dn


@pytest.mark.parametrize("lead", [(130,), (2, 3, 5), ()],
                         ids=["n130", "rmp", "single"])
@pytest.mark.parametrize("n,eth", [(150, 1), (150, 2), (150, 3), (150, 6),
                                   (150, 8), (24, 6), (40, 6), (50, 4),
                                   (30, 8)])
def test_lane_dense_matches_numpy_oracle(n, eth, lead):
    """The lane-dense ``banded_wf`` equals the cell-by-cell oracle on
    150 bp reads at band widths 3-17 (prefix scans of 1-4 steps), and at
    the read lengths and bands of the Pallas kernel sweeps, which compare
    the kernel against ``banded_wf``; for an instance count off the
    128-lane tiling, several leading dims and one unbatched pair.  A
    quarter of the windows (all of them for the single pair's second
    call) are sentinel bases that must saturate."""
    r = np.random.default_rng(1000 * n + eth)
    count = int(np.prod(lead, dtype=np.int64))
    s1 = np.empty((count, n), np.uint8)
    win = np.empty((count, n + 2 * eth), np.uint8)
    for k in range(count):
        edits = int(r.integers(0, eth + 3)) if k < count - 1 else 0
        s1[k], win[k] = _make_pair(r, n, eth, edits)
    win[: count // 4] = 4
    want_end = np.empty(count, np.int64)
    want_min = np.empty(count, np.int64)
    for k in range(count):
        B, want_end[k] = banded_wf_numpy(s1[k], win[k], eth)
        want_min[k] = B[n].min()
    de, dm = banded_wf(jnp.asarray(s1.reshape(lead + (n,))),
                       jnp.asarray(win.reshape(lead + (n + 2 * eth,))),
                       eth=eth)
    assert de.shape == lead and dm.shape == lead
    assert de.dtype == jnp.int32 and dm.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(de).reshape(-1), want_end)
    np.testing.assert_array_equal(np.asarray(dm).reshape(-1), want_min)
    assert (want_end[: count // 4] == eth + 1).all()
    assert (want_end <= eth).any()
    if not lead:
        sentinel = np.full(n + 2 * eth, 4, np.uint8)
        d_sat, m_sat = banded_wf(jnp.asarray(s1[0]), jnp.asarray(sentinel),
                                 eth=eth)
        assert int(d_sat) == int(m_sat) == eth + 1
