"""Pallas TPU kernel: k-mer hashing + sliding-window minimizer extraction.

Seeding front-end (paper Sec. V-C).  Reads sit along lanes; the sequence
axis along sublanes.  The kernel fuses three stages that would otherwise
round-trip HBM:
  1. 2-bit k-mer code assembly  (k unrolled shift-or steps)
  2. 32-bit invertible hash     (mul/xor lane ops)
  3. sliding-window argmin      (log2(w) doubling steps on (value, idx) pairs)

Output is (n_windows, R) minimizer positions + hashes; the unique-ification
(variable-length) stays in plain JAX — it is O(windows) scalar work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(seq_ref, hash_ref, pos_ref, *, k: int, w: int, n_win: int):
    L, block_r = seq_ref.shape
    n_kmers = L - k + 1
    seq = seq_ref[...].astype(jnp.uint32)
    acc = jnp.zeros((n_kmers, block_r), jnp.uint32)
    for j in range(k):
        acc = acc | (seq[j : j + n_kmers] << (2 * (k - 1 - j)))
    # hash32 (invertible mix)
    x = acc
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # sliding argmin via (value, index) doubling
    idx = jax.lax.broadcasted_iota(jnp.int32, (n_kmers, block_r), 0)
    val, pos = x, idx
    span = 1
    while span < w:
        step = min(span, w - span)
        a_v, a_p = val[: val.shape[0] - step], pos[: pos.shape[0] - step]
        b_v, b_p = val[step:], pos[step:]
        take_b = (b_v < a_v) | ((b_v == a_v) & (b_p < a_p))
        val = jnp.where(take_b, b_v, a_v)
        pos = jnp.where(take_b, b_p, a_p)
        span += step
    hash_ref[...] = val[:n_win]
    pos_ref[...] = pos[:n_win]


@functools.partial(jax.jit, static_argnames=("k", "w", "block_r", "interpret"))
def minimizer_pallas(seqT: jnp.ndarray, *, k: int = 12, w: int = 30,
                     block_r: int = 512, interpret: bool):
    """seqT (L, R) uint8 base codes -> (hashes (n_win, R) uint32,
    positions (n_win, R) int32), n_win = L - (w + k - 1) + 1."""
    L, R = seqT.shape
    n_win = L - (w + k - 1) + 1
    assert R % block_r == 0
    grid = (R // block_r,)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, w=w, n_win=n_win),
        grid=grid,
        in_specs=[pl.BlockSpec((L, block_r), lambda r: (0, r))],
        out_specs=[
            pl.BlockSpec((n_win, block_r), lambda r: (0, r)),
            pl.BlockSpec((n_win, block_r), lambda r: (0, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_win, R), jnp.uint32),
            jax.ShapeDtypeStruct((n_win, R), jnp.int32),
        ],
        interpret=interpret,
    )(seqT)
