"""Linear Wagner-Fischer: banded edit distance (paper Sec. III-A, Alg. 2).

The band has half-width ``eth`` (paper: 6); all values are saturated at
``eth + 1``.  Only ``2*eth + 1`` cells are live per row — DART-PIM keeps them
in one crossbar row per instance.  ``banded_wf`` keeps them lane-dense, as
the Pallas kernel in ``repro.kernels.linear_wf`` does: instances on the
128-wide lane axis, the band cells on sublanes, one (band, N) state swept
over the read length, and the in-row left propagation as a log-step prefix
scan.  Both implement the identical recurrence with bit-identical results;
``banded_wf_numpy`` is the cell-by-cell oracle.

Band coordinates: cell (i, j) of the (n+1) x (m+1) WF matrix is stored at
``d = j - i + eth`` (valid for |i - j| <= eth).  Row ``i`` of the band needs
reference chars ``s2_window[i-1 : i-1 + 2*eth+1]`` — a contiguous slice,
where ``s2_window`` has length ``n + 2*eth`` and position ``p`` holds the
reference base at (expected read start - eth + p).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def full_wf_numpy(s1: np.ndarray, s2: np.ndarray,
                  w_del: int = 1, w_ins: int = 1, w_sub: int = 1) -> np.ndarray:
    """Unbanded Wagner-Fischer distance matrix (oracle). O(n*m) numpy."""
    n, m = len(s1), len(s2)
    D = np.zeros((n + 1, m + 1), dtype=np.int32)
    D[1:, 0] = np.cumsum(np.full(n, w_del))
    D[0, 1:] = np.cumsum(np.full(m, w_ins))
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            if s1[i - 1] == s2[j - 1]:
                D[i, j] = D[i - 1, j - 1]
            else:
                D[i, j] = min(D[i - 1, j] + w_del,
                              D[i, j - 1] + w_ins,
                              D[i - 1, j - 1] + w_sub)
    return D


def banded_wf_numpy(s1: np.ndarray, s2_window: np.ndarray, eth: int = 6):
    """Band-only oracle with saturation, mirroring paper Algorithm 2 exactly.

    ``s2_window`` must have length len(s1) + 2*eth.  Returns the full band
    history (n+1, 2*eth+1) and the final distance D[n][n] (= band[n, eth]).
    """
    n = len(s1)
    assert len(s2_window) == n + 2 * eth
    sat = eth + 1
    B = np.full((n + 1, 2 * eth + 1), sat, dtype=np.int32)
    for d in range(eth, 2 * eth + 1):
        B[0, d] = min(d - eth, sat)
    for i in range(1, n + 1):
        for d in range(2 * eth + 1):
            j = i + d - eth
            if j < 0:
                continue  # stays saturated
            diag = B[i - 1, d]
            up = B[i - 1, d + 1] if d + 1 <= 2 * eth else sat
            left = B[i, d - 1] if d >= 1 else sat
            if j == 0:
                B[i, d] = min(up + 1, sat)
                continue
            sub = int(s1[i - 1] != s2_window[i + d - 1])
            B[i, d] = min(diag + sub, up + 1, left + 1, sat)
    return B, int(B[n, eth])


@partial(jax.jit, static_argnames=("eth",))
def banded_wf(s1: jnp.ndarray, s2_window: jnp.ndarray, eth: int = 6):
    """Batched banded WF distance. s1: (..., n), s2_window: (..., n+2*eth).

    Returns (dist_end, dist_min) int32 of the broadcast leading shape: the
    paper-faithful D[n][n] and the semi-global min over the last band row,
    saturated at eth+1 (paper: 3-bit cells for eth=6).

    The leading dims are flattened to one instance axis N and transposed
    onto lanes: reads (n, N), windows (n + 2*eth, N), and a (2*eth+1, N)
    int32 band state with the band cells on sublanes.  Each row reads its
    base and its band of window chars with a slice along the major axis.
    The left propagation new[d] = min(sat, min over d' <= d of
    x[d'] + d - d') is a log-step prefix scan, x = min(x, x shifted down
    by s, + s); steps with s > eth are skipped, since they add at least
    eth + 1 = sat and every cell is already at most sat.
    """
    lead = jnp.broadcast_shapes(s1.shape[:-1], s2_window.shape[:-1])
    n, wlen = s1.shape[-1], s2_window.shape[-1]
    s1T = jnp.broadcast_to(s1, lead + (n,)).reshape(-1, n).T
    s2T = jnp.broadcast_to(s2_window, lead + (wlen,)).reshape(-1, wlen).T
    N = s1T.shape[1]
    band = 2 * eth + 1
    sat = eth + 1
    d_col = jnp.arange(band, dtype=jnp.int32)[:, None]

    b0 = jnp.where(d_col < eth, sat, jnp.minimum(d_col - eth, sat))
    b0 = jnp.broadcast_to(b0, (band, N))

    def row(i, B):
        base = jax.lax.dynamic_slice_in_dim(s1T, i - 1, 1, axis=0)
        chars = jax.lax.dynamic_slice_in_dim(s2T, i - 1, band, axis=0)
        sub = (chars != base).astype(jnp.int32)
        j = i + d_col - eth
        diag = jnp.where(j >= 1, B + sub, sat)
        up_src = jnp.concatenate([B[1:], jnp.full((1, N), sat, jnp.int32)],
                                 axis=0)
        up = jnp.where(j >= 0, jnp.minimum(up_src + 1, sat), sat)
        x = jnp.minimum(diag, up)
        s = 1
        while s <= eth:
            sh = jnp.concatenate([jnp.full((s, N), sat, jnp.int32),
                                  x[:band - s]], axis=0)
            x = jnp.minimum(x, sh + s)
            s *= 2
        return jnp.where(j >= 0, jnp.minimum(x, sat), sat)

    last = jax.lax.fori_loop(1, n + 1, row, b0)
    return last[eth].reshape(lead), jnp.min(last, axis=0).reshape(lead)
