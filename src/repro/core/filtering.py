"""Pre-alignment filtering (paper Sec. V-D) + the base-count baseline.

The paper replaces the popular base-count heuristic with an exact banded
linear WF distance (Sec. III-A).  Both are provided: ``base_count_filter``
is the baseline the paper cites (eliminates ~68% of PLs on average at some
accuracy cost); ``linear_wf_filter`` is DART-PIM's mechanism.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import wf_backend as wfb
from .linear_wf import banded_wf  # noqa: F401 — re-exported for callers


def gather_windows(segments: jnp.ndarray, occ_idx: jnp.ndarray,
                   mini_pos: jnp.ndarray, *, read_len: int, k: int,
                   eth: int, win_eth: int | None = None) -> jnp.ndarray:
    """Slice per-candidate reference windows out of materialized segments.

    segments: (P_total, seg_len); occ_idx: (..., ) rows; mini_pos: (...,)
    minimizer offset within the read (broadcast-compatible with occ_idx).
    Returns windows (..., read_len + 2*win_eth) where window position p holds
    the reference base at (expected read start - win_eth + p).

    Segment row for occurrence at reference pos q spans
    ref[q - pad : q - pad + seg_len], pad = read_len + eth - k.  The read's
    expected start is (q - o) for minimizer offset o, i.e. segment-local
    index (pad - o); the WF window begins win_eth earlier.

    A per-candidate dynamic slice becomes a loop over the candidates on a
    TPU, and an element-wise gather is little faster, so this gathers whole
    segment rows and moves each window to column 0 with a barrel shifter:
    one select between two static slices per bit of the start.  Starts
    wrap and clamp as ``dynamic_slice`` does it.
    """
    win_eth = eth if win_eth is None else win_eth
    assert win_eth <= eth, "segment slack only covers the indexing eth"
    seg_len = segments.shape[-1]
    pad = read_len + eth - k
    wlen = read_len + 2 * win_eth
    starts = pad - mini_pos - win_eth  # (...,) >= eth - win_eth >= 0
    starts = jnp.broadcast_to(starts, occ_idx.shape).reshape(-1)
    starts = jnp.where(starts < 0, starts + seg_len, starts)
    starts = jnp.clip(starts, 0, seg_len - wlen)
    nbits = max(seg_len - wlen, 1).bit_length()
    x = segments[occ_idx.reshape(-1)]                     # (N, seg_len)
    x = jnp.pad(x, ((0, 0), (0, wlen + (1 << nbits) - 1 - seg_len)))
    for b in reversed(range(nbits)):
        s = 1 << b
        width = wlen + s - 1
        take = ((starts >> b) & 1).astype(bool)[:, None]
        x = jnp.where(take, x[:, s:s + width], x[:, :width])
    return x.reshape(occ_idx.shape + (wlen,))


@partial(jax.jit, static_argnames=("eth", "backend", "block_r"))
def linear_wf_filter(reads: jnp.ndarray, windows: jnp.ndarray,
                     occ_valid: jnp.ndarray, eth: int = 6,
                     backend: str = "jnp", block_r: int = 512):
    """Banded linear WF distance per candidate; invalid -> saturated.

    reads: (R, rl); windows: (R, M, P, rl + 2*eth); occ_valid: (R, M, P).
    ``backend`` selects the jnp reference or the Pallas kernel (see
    ``repro.core.wf_backend``; ``block_r`` is the kernel lane-block size).
    Returns distances (R, M, P) int32 in [0, eth+1].
    """
    R, M, P, _ = windows.shape
    s1 = jnp.broadcast_to(reads[:, None, None, :], (R, M, P, reads.shape[-1]))
    dist_end, dist_min = wfb.linear_wf_dist(s1, windows, eth=eth,
                                            backend=backend, block_r=block_r)
    sat = eth + 1
    return jnp.where(occ_valid, dist_end, sat), jnp.where(occ_valid, dist_min,
                                                          sat)


def collapse_candidates(lin_end: jnp.ndarray, threshold: int):
    """(4) min extraction + filter: collapse the PL axis to the best
    candidate per (read, minimizer) and apply the filter threshold.

    lin_end (..., P) int32 (invalid slots hold the linear sat value) ->
    (best_pl (...,), best_lin (...,), pass_filter (...,)).  Shared by the
    padded reference, both compacted engines and the distributed stage B
    so the winner/filter semantics cannot drift between paths.
    """
    best_pl = jnp.argmin(lin_end, axis=-1)
    best_lin = jnp.take_along_axis(lin_end, best_pl[..., None],
                                   -1)[..., 0]
    return best_pl, best_lin, best_lin <= threshold


@jax.jit
def base_count_filter(reads: jnp.ndarray, windows: jnp.ndarray,
                      occ_valid: jnp.ndarray, threshold: int = 6):
    """Base-count histogram filter [Alser et al.] — the cited baseline.

    Compares per-base counts of the read vs. the aligned reference window
    (central read_len slice); L1/2 histogram distance lower-bounds the edit
    distance restricted to substitutions+indels, so ``hist > threshold``
    safely discards.
    Returns (keep (R,M,P) bool, hist_dist (R,M,P) int32).
    """
    rl = reads.shape[-1]
    wlen = windows.shape[-1]
    off = (wlen - rl) // 2
    centre = windows[..., off : off + rl]
    dists = []
    for b in range(4):
        h1 = jnp.sum(reads == b, axis=-1).astype(jnp.int32)
        h2 = jnp.sum(centre == b, axis=-1).astype(jnp.int32)
        dists.append(jnp.abs(h1[:, None, None] - h2))
    hist = sum(dists) // 2
    keep = (hist <= threshold) & occ_valid
    return keep, hist
