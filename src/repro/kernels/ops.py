"""jit'd public wrappers for the Pallas kernels.

``on_tpu()`` is the one place that decides how a kernel runs: compiled
on a TPU; elsewhere in ``interpret=True`` mode, where the kernel body
runs as traced JAX ops (correctness of the exact code that compiles for
the TPU, not its speed).  The ``*_pallas`` kernels take ``interpret``
with no default, so a direct caller must choose.

Wrappers handle the (instances-last) transposes and padding to the block
size so callers keep the natural (R, n) layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .affine_wf import affine_wf_dist_pallas, affine_wf_pallas
from .linear_wf import linear_wf_pallas
from .minimizer import minimizer_pallas
from .traceback import affine_traceback_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_r(x, block_r):
    R = x.shape[-1]
    pad = (-R) % block_r
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return x, R


@functools.partial(jax.jit, static_argnames=("eth", "block_r"))
def linear_wf(s1: jnp.ndarray, s2_window: jnp.ndarray, *, eth: int = 6,
              block_r: int = 512):
    """Batched banded linear WF via the Pallas kernel.

    s1 (R, n) uint8, s2_window (R, n+2*eth) uint8 ->
    (dist_end (R,), dist_min (R,)) int32.
    """
    s1T, R = _pad_r(s1.astype(jnp.int8).T, block_r)
    s2T, _ = _pad_r(s2_window.astype(jnp.int8).T, block_r)
    out = linear_wf_pallas(s1T, s2T, eth=eth, block_r=block_r,
                           interpret=not on_tpu())
    return out[0, :R], out[1, :R]


@functools.partial(jax.jit, static_argnames=("eth", "sat", "block_r"))
def affine_wf(s1: jnp.ndarray, s2_window: jnp.ndarray, *, eth: int = 6,
              sat: int = 32, block_r: int = 256):
    """Batched banded affine WF via the Pallas kernel.

    s1 (R, n), s2_window (R, n+2*eth) uint8 ->
    (dist_end (R,), dist_min (R,), dirs (R, n, band) uint8).
    """
    n = s1.shape[-1]
    band = 2 * eth + 1
    s1T, R = _pad_r(s1.astype(jnp.int8).T, block_r)
    s2T, _ = _pad_r(s2_window.astype(jnp.int8).T, block_r)
    dists, dirsT = affine_wf_pallas(s1T, s2T, eth=eth, sat=sat,
                                    block_r=block_r, interpret=not on_tpu())
    dirs = dirsT[:, :R].T.reshape(R, n, band)
    return dists[0, :R], dists[1, :R], dirs


@functools.partial(jax.jit,
                   static_argnames=("eth", "sat", "max_ops", "block_r"))
def affine_traceback(s1: jnp.ndarray, s2_window: jnp.ndarray, *,
                     eth: int = 6, sat: int = 32, max_ops: int,
                     block_r: int = 256):
    """Fused banded affine WF + on-device traceback via the Pallas kernel
    (direction planes stay in VMEM scratch — see ``kernels.traceback``).

    s1 (R, n), s2_window (R, n+2*eth) uint8 ->
    (dist_end (R,), dist_min (R,), ops (R, max_ops) int32 END-aligned,
    op_count (R,) int32).
    """
    s1T, R = _pad_r(s1.astype(jnp.int8).T, block_r)
    s2T, _ = _pad_r(s2_window.astype(jnp.int8).T, block_r)
    dists, opsT, cnt = affine_traceback_pallas(
        s1T, s2T, eth=eth, sat=sat, max_ops=max_ops, block_r=block_r,
        interpret=not on_tpu())
    return dists[0, :R], dists[1, :R], opsT[:, :R].T, cnt[0, :R]


@functools.partial(jax.jit, static_argnames=("eth", "sat", "block_r"))
def affine_wf_dist(s1: jnp.ndarray, s2_window: jnp.ndarray, *, eth: int = 6,
                   sat: int = 32, block_r: int = 256):
    """Distance-only banded affine WF via the Pallas kernel (no direction
    planes — the compacted pipeline's survivor pass).

    s1 (R, n), s2_window (R, n+2*eth) uint8 ->
    (dist_end (R,), dist_min (R,)) int32.
    """
    s1T, R = _pad_r(s1.astype(jnp.int8).T, block_r)
    s2T, _ = _pad_r(s2_window.astype(jnp.int8).T, block_r)
    out = affine_wf_dist_pallas(s1T, s2T, eth=eth, sat=sat, block_r=block_r,
                                interpret=not on_tpu())
    return out[0, :R], out[1, :R]


@functools.partial(jax.jit, static_argnames=("k", "w", "block_r"))
def minimizer_scan(seqs: jnp.ndarray, *, k: int = 12, w: int = 30,
                   block_r: int = 512):
    """Batched minimizer extraction via the Pallas kernel.

    seqs (R, L) uint8 -> (hashes (R, n_win) uint32, positions (R, n_win)).
    """
    seqT, R = _pad_r(seqs.T, block_r)
    mh, mp = minimizer_pallas(seqT, k=k, w=w, block_r=block_r,
                              interpret=not on_tpu())
    return mh[:, :R].T, mp[:, :R].T


@functools.partial(jax.jit, static_argnames=("causal", "q_chunk", "kv_chunk"))
def flash_attention(q, k, v, *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512):
    """Flash attention via the Pallas kernel (layers layout).

    q (B, S, H, hd); k/v (B, S, KV, hd) -> (B, S, H, hd)."""
    from .flash_attention import flash_attention_pallas
    qT = jnp.transpose(q, (0, 2, 1, 3))
    kT = jnp.transpose(k, (0, 2, 1, 3))
    vT = jnp.transpose(v, (0, 2, 1, 3))
    o = flash_attention_pallas(qT, kT, vT, causal=causal, q_chunk=q_chunk,
                               kv_chunk=kv_chunk, interpret=not on_tpu())
    return jnp.transpose(o, (0, 2, 1, 3))
