"""On-device banded traceback: the batched ``affine_wf.traceback`` walk
and the fused affine+traceback Pallas kernel must match the
``traceback_numpy`` oracle — including band-edge walks, all-match reads,
adjacent insertion/deletion runs, and the ``max_ops`` truncation wrap."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import wf_backend as wfb
from repro.core.affine_wf import (OP_DEL, OP_INS, OP_MATCH, OP_NONE,
                                  banded_affine, banded_affine_numpy,
                                  traceback, traceback_numpy)

ETH, SAT = 6, 32


def _make_pair(r, n, n_edits):
    """Read + window with ``n_edits`` random substitutions/indels (the
    same generator as test_affine_wf): enough edits pushes the walk to
    the band edges and produces adjacent gap runs."""
    s1 = r.integers(0, 4, n).astype(np.uint8)
    lst = list(np.concatenate([r.integers(0, 4, ETH), s1,
                               r.integers(0, 4, ETH)]))
    for _ in range(n_edits):
        p = int(r.integers(ETH, ETH + n - 2))
        t = int(r.integers(0, 3))
        if t == 0:
            lst[p] = int(r.integers(0, 4))
        elif t == 1:
            lst.insert(p, int(r.integers(0, 4)))
        else:
            del lst[p]
    win = np.array((lst + [0] * (n + 2 * ETH))[: n + 2 * ETH],
                   dtype=np.uint8)
    return s1, win


def _oracle_rows(dirs_list, n, max_ops):
    """END-aligned op rows + counts the way the oracle defines them: op k
    (counting from the end of the walk) lands at ``(max_ops - 1 - k) %
    max_ops``, later walk steps overwriting on wrap — the truncation
    semantics the device walk must reproduce bit-for-bit."""
    rows = np.full((len(dirs_list), max_ops), OP_NONE, np.int32)
    cnts = np.zeros(len(dirs_list), np.int32)
    for i, dirs in enumerate(dirs_list):
        ops = traceback_numpy(dirs, ETH, n)
        cnts[i] = len(ops)
        for k, op in enumerate(reversed(ops)):
            rows[i, (max_ops - 1 - k) % max_ops] = op
    return rows, cnts


@given(st.integers(0, 10 ** 6), st.integers(10, 50), st.integers(0, 6))
@settings(max_examples=30, deadline=None)
def test_device_walk_matches_oracle(seed, n, edits):
    r = np.random.default_rng(seed)
    s1, win = _make_pair(r, n, edits)
    _, dirs_np, _ = banded_affine_numpy(s1, win, ETH, SAT)
    max_ops = 2 * n + 2
    exp_rows, exp_cnt = _oracle_rows([dirs_np], n, max_ops)
    ops, cnt = traceback(jnp.array(dirs_np)[None], ETH, max_ops)
    np.testing.assert_array_equal(np.asarray(ops), exp_rows)
    np.testing.assert_array_equal(np.asarray(cnt), exp_cnt)


@given(st.integers(0, 10 ** 6), st.integers(12, 40),
       st.integers(1, 2 * 40))
@settings(max_examples=25, deadline=None)
def test_max_ops_truncation_wrap(seed, n, max_ops):
    """A ``max_ops`` buffer smaller than the walk must hold exactly the
    oracle's wrapped tail (the SAM layer then reports those as '*')."""
    r = np.random.default_rng(seed)
    s1, win = _make_pair(r, n, int(r.integers(0, 5)))
    _, dirs_np, _ = banded_affine_numpy(s1, win, ETH, SAT)
    exp_rows, exp_cnt = _oracle_rows([dirs_np], n, max_ops)
    ops, cnt = traceback(jnp.array(dirs_np)[None], ETH, max_ops)
    np.testing.assert_array_equal(np.asarray(ops), exp_rows)
    np.testing.assert_array_equal(np.asarray(cnt), exp_cnt)


def _batch(r, n, count, edit_pool):
    s1s, wins = zip(*(_make_pair(r, n, edit_pool[i % len(edit_pool)])
                      for i in range(count)))
    return np.stack(s1s), np.stack(wins)


def test_fused_kernel_matches_jnp_backend():
    """`wf_backend.affine_traceback`: the Pallas fused kernel (dirs in
    VMEM scratch) against the jnp reference, distances included."""
    r = np.random.default_rng(7)
    n = 24
    s1, win = _batch(r, n, 12, edit_pool=(0, 1, 2, 3, 4, 5))
    outs = {}
    for backend in ("jnp", "pallas"):
        outs[backend] = wfb.affine_traceback(
            jnp.asarray(s1), jnp.asarray(win), eth=ETH, sat=SAT,
            max_ops=2 * n + 2, backend=backend, block_r=8)
    for a, b, name in zip(outs["jnp"], outs["pallas"],
                          ("dist_end", "dist_min", "ops", "op_count")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # and the jnp side against the oracle, so both chains are anchored
    dirs = [banded_affine_numpy(s1[i], win[i], ETH, SAT)[1]
            for i in range(len(s1))]
    exp_rows, exp_cnt = _oracle_rows(dirs, n, 2 * n + 2)
    np.testing.assert_array_equal(np.asarray(outs["jnp"][2]), exp_rows)
    np.testing.assert_array_equal(np.asarray(outs["jnp"][3]), exp_cnt)


def test_all_match_and_gap_runs_both_backends():
    """Degenerate shapes: an exact-match read (walk = straight diagonal)
    and the affine gap-run pair (adjacent 2-insertion + 2-deletion runs)
    in one batch, on both backends."""
    n = 12
    origin = np.array([0, 1, 2, 3] * 5, dtype=np.uint8)
    exact = origin[:n]
    win_exact = np.concatenate([np.full(ETH, 4, np.uint8), exact,
                                np.full(ETH, 4, np.uint8)])
    gap_read = np.concatenate([origin[:4], [3, 3],
                               origin[4:10]]).astype(np.uint8)
    win_gap = np.concatenate([np.full(ETH, 4, np.uint8), origin[:n],
                              np.full(ETH, 4, np.uint8)])
    s1 = np.stack([exact, gap_read])
    win = np.stack([win_exact, win_gap])
    max_ops = 2 * n + 2
    for backend in ("jnp", "pallas"):
        de, _, ops, cnt = wfb.affine_traceback(
            jnp.asarray(s1), jnp.asarray(win), eth=ETH, sat=SAT,
            max_ops=max_ops, backend=backend, block_r=8)
        ops, cnt = np.asarray(ops), np.asarray(cnt)
        assert int(de[0]) == 0 and int(cnt[0]) == n
        assert (ops[0, -n:] == OP_MATCH).all()
        assert (ops[0, :-n] == OP_NONE).all()
        walk = [int(o) for o in ops[1] if o != OP_NONE]
        assert int(de[1]) == 6 and len(walk) == int(cnt[1])
        runs = {OP_INS: [], OP_DEL: []}
        prev = None
        for o in walk:
            if o in runs:
                if o == prev:
                    runs[o][-1] += 1
                else:
                    runs[o].append(1)
            prev = o
        assert 2 in runs[OP_INS] and 2 in runs[OP_DEL], backend


def test_traceback_matches_banded_affine_plus_walk():
    """The one-dispatch ``wfb.affine_traceback`` must equal running the
    staged pair (dirs-emitting affine, then the batched walk)."""
    r = np.random.default_rng(11)
    n = 30
    s1, win = _batch(r, n, 6, edit_pool=(0, 2, 4))
    de_s, dm_s, dirs = banded_affine(jnp.asarray(s1), jnp.asarray(win),
                                     eth=ETH, sat=SAT)
    ops_s, cnt_s = traceback(dirs, ETH, 2 * n + 2)
    de_f, dm_f, ops_f, cnt_f = wfb.affine_traceback(
        jnp.asarray(s1), jnp.asarray(win), eth=ETH, sat=SAT,
        max_ops=2 * n + 2, backend="jnp")
    np.testing.assert_array_equal(np.asarray(de_s), np.asarray(de_f))
    np.testing.assert_array_equal(np.asarray(dm_s), np.asarray(dm_f))
    np.testing.assert_array_equal(np.asarray(ops_s), np.asarray(ops_f))
    np.testing.assert_array_equal(np.asarray(cnt_s), np.asarray(cnt_f))


def test_saturated_walks_match_jnp_backend():
    """Unrelated read/window pairs saturate, and their walks leave the
    band: the kernel's row-by-row walk must still read the same cells
    as the reference's flat ``take_along_axis`` (one wrap below 0, the
    fill value past the end).  The pipeline masks these unmapped lanes,
    but the kernel output is defined for them too."""
    r = np.random.default_rng(23)
    n, count = 48, 16
    s1 = r.integers(0, 4, (count, n)).astype(np.uint8)
    win = r.integers(0, 4, (count, n + 2 * ETH)).astype(np.uint8)
    outs = {}
    for backend in ("jnp", "pallas"):
        outs[backend] = wfb.affine_traceback(
            jnp.asarray(s1), jnp.asarray(win), eth=ETH, sat=SAT,
            max_ops=2 * n + 2, backend=backend, block_r=16)
    assert (np.asarray(outs["jnp"][0]) >= SAT).sum() >= count // 2
    for a, b, name in zip(outs["jnp"], outs["pallas"],
                          ("dist_end", "dist_min", "ops", "op_count")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
