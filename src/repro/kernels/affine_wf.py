"""Pallas TPU kernel: banded affine Wagner-Fischer + direction emission.

Same lane/sublane mapping as the linear kernel (instances on lanes, band on
sublanes, rows loaded through ``linear_wf.load_row``); three live int32 bands (D,
M1, M2) and a per-row packed-direction write-out.  The direction planes are
the only O(n * band) output — exactly DART-PIM's traceback rows (4
bits/cell; we emit one uint8 per cell, packed dD | dM1<<2 | dM2<<3).  Each
row's bytes go to plane ``[i - 1]`` of an (n8, band, R) array, a dynamic
index on the untiled leading axis, so traceback runs without re-computing
values.

Two entry points share the row recurrence:
  ``affine_wf_pallas``      — distances + direction planes (traceback pass,
                              runs on the one winner per read);
  ``affine_wf_dist_pallas`` — distances only.  No (n * band, R) plane is
                              allocated or written; this is the kernel the
                              compacted pipeline runs on every filter
                              survivor.

VMEM per block (block_r = 256, n = 150, eth = 6), double-buffered: inputs
~0.6 MiB, dirs block 152 x 13 x 256 uint8 (sublane-padded to 32) ~2.4 MiB —
inside the default scoped VMEM limit (and absent entirely in the dist-only
variant), so no ``vmem_limit_bytes`` is set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .linear_wf import in_specs, load_row, pad_rows8


def _row_step(Dp, M1p, M2p, chars, s1c, d_col, i, *, eth: int, sat: int,
              block_r: int, emit_dirs: bool):
    """One band-row update shared by every affine kernel.

    All values are int32 (the chip's VPU has no int8 arithmetic); every
    raw candidate stays below ``sat + 43``, so the results equal the int8
    jnp reference bit for bit.  Returns (Dn, M1n, M2n, bytes_or_None);
    direction-byte work is only emitted when ``emit_dirs``.
    """
    band = 2 * eth + 1
    big = sat + 40
    match = chars == s1c                                  # (band, R)
    j = i + d_col - eth                                   # (band, 1)

    shift = lambda a: jnp.concatenate(
        [a[1:], jnp.full((1, block_r), big, jnp.int32)], axis=0)
    m1_ext = shift(M1p) + 1                               # raw
    m1_open = shift(Dp) + 2                               # raw
    M1n = jnp.minimum(jnp.minimum(m1_ext, m1_open), sat)
    dM1 = (m1_open < m1_ext).astype(jnp.int32) if emit_dirs else None
    M1n = jnp.where(j >= 0, M1n, sat)

    d_left = jnp.full((1, block_r), big, jnp.int32)
    m2_left = d_left
    D_rows, M2_rows, B_rows = [], [], []
    for dd in range(band):
        jj = i + dd - eth
        m2_ext = m2_left + 1
        m2_open = d_left + 2
        m2n = jnp.minimum(jnp.minimum(m2_ext, m2_open), sat)
        m2n = jnp.where(jj <= 0, sat, m2n)
        dp, m1n, mt = Dp[dd:dd + 1], M1n[dd:dd + 1], match[dd:dd + 1]
        sub_raw = dp + 1
        dmin = jnp.minimum(jnp.minimum(sub_raw, m1n), m2n)
        dval = jnp.where(mt, dp, jnp.minimum(dmin, sat))
        dval = jnp.where(jj == 0, m1n, dval)
        dval = jnp.where(jj < 0, sat, dval)
        if emit_dirs:
            dm2 = (m2_open < m2_ext).astype(jnp.int32)
            ddir = jnp.where(mt, 0, jnp.where(dmin == sub_raw, 1,
                                              jnp.where(dmin == m1n, 2, 3)))
            ddir = jnp.where(jj == 0, 2, ddir)
            byte = ddir | (dM1[dd:dd + 1] << 2) | (dm2 << 3)
            B_rows.append(jnp.where(jj < 0, 0, byte))
        D_rows.append(dval)
        M2_rows.append(m2n)
        d_left, m2_left = dval, m2n
    Dn = jnp.concatenate(D_rows, axis=0)
    M2n = jnp.concatenate(M2_rows, axis=0)
    bytes_ = jnp.concatenate(B_rows, axis=0) if emit_dirs else None
    return Dn, M1n, M2n, bytes_


def _init_bands(eth: int, sat: int, block_r: int):
    band = 2 * eth + 1
    d_col = jax.lax.broadcasted_iota(jnp.int32, (band, 1), 0)
    j0 = d_col - eth
    D0 = jnp.where(j0 < 0, sat, jnp.minimum(jnp.where(j0 == 0, 0, 1 + j0),
                                            sat))
    M20 = jnp.where(j0 > 0, D0, sat)
    return (d_col, jnp.broadcast_to(D0, (band, block_r)),
            jnp.full((band, block_r), sat, jnp.int32),
            jnp.broadcast_to(M20, (band, block_r)))


def sweep(s1_ref, s2_ref, *, eth: int, n: int, sat: int, dirs_ref=None):
    """Run the affine band recurrence over rows 1..n of one lane block and
    return the last D band row (band, block_r) int32.  With ``dirs_ref``
    (n8, band, block_r), row i's packed direction bytes are stored at
    ``dirs_ref[i - 1]``."""
    band = 2 * eth + 1
    block_r = s1_ref.shape[1]
    d_col, D0, M10, M20 = _init_bands(eth, sat, block_r)

    def row(i, carry):
        s1c, chars = load_row(s1_ref, s2_ref, i, band)
        Dn, M1n, M2n, bytes_ = _row_step(
            *carry, chars, s1c, d_col, i, eth=eth, sat=sat, block_r=block_r,
            emit_dirs=dirs_ref is not None)
        if dirs_ref is not None:
            dirs_ref[i - 1] = bytes_.astype(dirs_ref.dtype)
        return Dn, M1n, M2n

    D, _, _ = jax.lax.fori_loop(1, n + 1, row, (D0, M10, M20))
    return D


def store_dists(out_ref, D, eth: int):
    out_ref[0:1, :] = D[eth:eth + 1, :]
    out_ref[1:2, :] = jnp.min(D, axis=0, keepdims=True)


def _kernel(s1_ref, s2_ref, out_ref, dirs_ref, *, eth: int, n: int,
            sat: int):
    store_dists(out_ref, sweep(s1_ref, s2_ref, eth=eth, n=n, sat=sat,
                               dirs_ref=dirs_ref), eth)


def _kernel_dist(s1_ref, s2_ref, out_ref, *, eth: int, n: int, sat: int):
    store_dists(out_ref, sweep(s1_ref, s2_ref, eth=eth, n=n, sat=sat), eth)


@functools.partial(jax.jit,
                   static_argnames=("eth", "sat", "block_r", "interpret"))
def affine_wf_pallas(s1T: jnp.ndarray, s2T: jnp.ndarray, *, eth: int,
                     sat: int, block_r: int, interpret: bool):
    """s1T (n, R), s2T (n+2*eth, R) int8; returns (dists (2, R) int32,
    dirs (n*band, R) uint8)."""
    n, R = s1T.shape
    band = 2 * eth + 1
    assert s2T.shape == (n + 2 * eth, R)
    assert R % block_r == 0
    s1p, s2p = pad_rows8(s1T, s2T, eth)
    n8 = s1p.shape[0]
    dists, dirs = pl.pallas_call(
        functools.partial(_kernel, eth=eth, n=n, sat=sat),
        grid=(R // block_r,),
        in_specs=in_specs(s1p, s2p, block_r),
        out_specs=[
            pl.BlockSpec((2, block_r), lambda r: (0, r)),
            pl.BlockSpec((n8, band, block_r), lambda r: (0, 0, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((2, R), jnp.int32),
            jax.ShapeDtypeStruct((n8, band, R), jnp.uint8),
        ],
        interpret=interpret,
    )(s1p, s2p)
    return dists, dirs[:n].reshape(n * band, R)


@functools.partial(jax.jit,
                   static_argnames=("eth", "sat", "block_r", "interpret"))
def affine_wf_dist_pallas(s1T: jnp.ndarray, s2T: jnp.ndarray, *, eth: int,
                          sat: int, block_r: int, interpret: bool):
    """Distance-only variant: s1T (n, R), s2T (n+2*eth, R) int8 ->
    (2, R) int32 [dist_end; dist_min].  No direction plane is produced."""
    n, R = s1T.shape
    assert s2T.shape == (n + 2 * eth, R)
    assert R % block_r == 0
    s1p, s2p = pad_rows8(s1T, s2T, eth)
    return pl.pallas_call(
        functools.partial(_kernel_dist, eth=eth, n=n, sat=sat),
        grid=(R // block_r,),
        in_specs=in_specs(s1p, s2p, block_r),
        out_specs=pl.BlockSpec((2, block_r), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((2, R), jnp.int32),
        interpret=interpret,
    )(s1p, s2p)
