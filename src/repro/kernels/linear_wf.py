"""Pallas TPU kernel: banded linear Wagner-Fischer (paper Alg. 2).

TPU mapping of the crossbar-row parallelism: each WF *instance* occupies one
VPU **lane**; the 2*eth+1 band cells live along **sublanes**.  A block of
``block_r`` instances is resident in VMEM; the kernel sweeps the read length
with a fori_loop, updating the (band, block_r) band in registers — the in-row
dataflow of DART-PIM's Fig. 3, with MAGIC NOR ops replaced by 8x128-lane
min/add/select.  The v5e VPU has no int8 arithmetic, so the band is int32;
values saturate at eth + 1 exactly as in the int8 jnp reference.  The in-row
left propagation, a sequential (min, +1) scan in the reference, is a
log2(band)-step prefix scan here (same result: see ``_kernel``).

Inputs are pre-transposed to (seq, instances) so the instance axis is the
(128-wide, contiguous) lane axis, and widened to int32 by ``pad_rows8``:
  s1T  (n,          R)  reads
  s2T  (n + 2*eth,  R)  reference windows
  out  (2,          R)  int32  row 0 = D[n][n] (paper), row 1 = min last row
Each row is read from its sublane-aligned 8-row group and rotated into place
(``load_row``): Mosaic refuses a dynamic load at an unaligned sublane offset.

VMEM per block (block_r = 512, n = 150, eth = 6), inputs double-buffered:
  s1 2 x 304 KiB + s2 2 x 336 KiB + out 2 x 4 KiB  ~= 1.3 MiB, inside the
  default scoped VMEM limit, so no ``vmem_limit_bytes`` is set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def load_row(s1_ref, s2_ref, i, band: int):
    """Row ``i``'s read base (1, block_r) and its ``band`` window chars
    (band, block_r), read from the sublane-aligned 8-row group holding
    row ``i - 1`` and rotated into place: Mosaic refuses a ref load at an
    unaligned dynamic sublane offset (see ``pad_rows8``)."""
    base = pl.multiple_of((i - 1) // 8 * 8, 8)
    off = (i - 1) % 8
    win = s2_ref.shape[0] - s1_ref.shape[0] + 8
    s1b = pltpu.roll(s1_ref[pl.ds(base, 8), :], (8 - off) % 8, 0)
    s2b = pltpu.roll(s2_ref[pl.ds(base, win), :], (win - off) % win, 0)
    return s1b[0:1], s2b[:band]


def _kernel(s1_ref, s2_ref, out_ref, *, eth: int, n: int):
    band = 2 * eth + 1
    block_r = s1_ref.shape[1]
    sat = eth + 1
    d_col = jax.lax.broadcasted_iota(jnp.int32, (band, 1), 0)

    b0 = jnp.where(d_col < eth, sat, jnp.minimum(d_col - eth, sat))
    b0 = jnp.broadcast_to(b0, (band, block_r))

    def row(i, B):
        s1c, chars = load_row(s1_ref, s2_ref, i, band)
        sub = (chars != s1c).astype(jnp.int32)
        j = i + d_col - eth
        diag = jnp.where(j >= 1, B + sub, sat)
        up_src = jnp.concatenate(
            [B[1:], jnp.full((1, block_r), sat, jnp.int32)], axis=0)
        up = jnp.where(j >= 0, jnp.minimum(up_src + 1, sat), sat)
        x = jnp.minimum(diag, up)
        # left propagation: new[d] = min(sat, min_{d' <= d} x[d'] + d - d'),
        # which the reference's clamped running scan also computes (x <= sat)
        s = 1
        while s < band:
            sh = jnp.concatenate(
                [jnp.full((s, block_r), sat, jnp.int32), x[:band - s]], axis=0)
            x = jnp.minimum(x, sh + s)
            s *= 2
        return jnp.where(j >= 0, jnp.minimum(x, sat), sat)

    B = jax.lax.fori_loop(1, n + 1, row, b0)
    out_ref[0:1, :] = B[eth:eth + 1, :]
    out_ref[1:2, :] = jnp.min(B, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eth", "block_r", "interpret"))
def linear_wf_pallas(s1T: jnp.ndarray, s2T: jnp.ndarray, *, eth: int,
                     block_r: int, interpret: bool):
    """s1T (n, R) int8, s2T (n+2*eth, R) int8; R divisible by block_r.

    Returns (2, R) int32: [dist_end; dist_min].
    """
    n, R = s1T.shape
    assert s2T.shape == (n + 2 * eth, R)
    assert R % block_r == 0
    s1p, s2p = pad_rows8(s1T, s2T, eth)
    return pl.pallas_call(
        functools.partial(_kernel, eth=eth, n=n),
        grid=(R // block_r,),
        in_specs=in_specs(s1p, s2p, block_r),
        out_specs=pl.BlockSpec((2, block_r), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((2, R), jnp.int32),
        interpret=interpret,
    )(s1p, s2p)


def pad_rows8(s1T, s2T, eth: int):
    """Widen reads/windows to int32 and pad their rows so every row's
    ``load_row`` stays in bounds: s1T to ``n8 = ceil(n / 8) * 8`` rows,
    s2T to ``n8 - 8 + win`` rows, where ``win`` (a multiple of 8) covers
    the ``7 + band`` window rows an aligned 8-row group can need.  The
    padding is never part of a row's result."""
    n = s1T.shape[0]
    n8 = -(-n // 8) * 8
    win = -(-(8 + 2 * eth) // 8) * 8
    s1p = jnp.pad(s1T.astype(jnp.int32), ((0, n8 - n), (0, 0)))
    s2p = jnp.pad(s2T.astype(jnp.int32),
                  ((0, n8 - 8 + win - s2T.shape[0]), (0, 0)))
    return s1p, s2p


def in_specs(s1p, s2p, block_r: int):
    """Lane-block specs of the padded (rows, R) reads and windows."""
    return [pl.BlockSpec((s1p.shape[0], block_r), lambda r: (0, r)),
            pl.BlockSpec((s2p.shape[0], block_r), lambda r: (0, r))]
