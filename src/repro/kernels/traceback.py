"""Pallas TPU kernel: fused banded affine WF + on-device traceback.

The staged pipeline materializes the (n * band, R) packed-direction planes
in HBM (``affine_wf_pallas``), fetches nothing, and then runs a separate
traceback program over them — the planes round-trip through HBM purely to
connect two kernels.  This kernel fuses the two: the forward pass writes
its direction bytes into a VMEM *scratch* buffer, and the traceback walk
consumes them in-place, so the only O(n * band) array never leaves the
core.  Outputs are the END-aligned op rows + per-lane op count + the two
distance rows — exactly the arrays the host needs, nothing else crosses
D2H.  This is DART-PIM's traceback dataflow (Sec. IV-B: direction bits
live in auxiliary crossbar rows next to the values and are walked there)
rather than the paper's CPU-side reconstruction.

Walk layout: the fused-transition step (``repro.core.affine_wf
.traceback_step``) emits exactly one op per active lane per step, so lane
``x``'s k-th op lands in output row ``(max_ops - 1 - k) % max_ops`` — the
same END-aligned rows as the reference walk.  Mosaic has no per-lane
gather over the planes, so the walk runs row by row: every lane visits
rows n..0 in order, and the lanes on row r step (a one-hot pick over the
13 band cells of plane r - 1, and a one-hot row update of the op block)
until none is left there.  A walk that leaves the band (only saturated,
unmapped alignments do) reads the cell the reference's flat indexing
reads, through a slower pick over all planes.

VMEM per block (block_r = 256, n = 150, eth = 6, max_ops = 302): inputs
~0.6 MiB double-buffered, dirs scratch 152 x 13 x 256 uint8 (sublane-padded
to 32) ~1.2 MiB, ops block 2 x 302 KiB — inside the default scoped VMEM
limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.affine_wf import OP_NONE, traceback_step

from .affine_wf import store_dists, sweep
from .linear_wf import in_specs, pad_rows8


def _any(mask):
    return jnp.max(mask.astype(jnp.int32)) > 0


def _kernel(s1_ref, s2_ref, out_ref, ops_ref, cnt_ref, dirs_ref, walk_ref,
            *, eth: int, n: int, sat: int, max_ops: int):
    band = 2 * eth + 1
    block_r = s1_ref.shape[1]

    # ---- forward pass: affine band recurrence, dirs -> VMEM scratch
    store_dists(out_ref, sweep(s1_ref, s2_ref, eth=eth, n=n, sat=sat,
                               dirs_ref=dirs_ref), eth)

    # ---- traceback walk over the scratch planes (never leave VMEM).
    # Every lane visits rows n..0 in order and steps while on row r, so a
    # step on row r reads only plane r - 1.  The lane state (i, d, state,
    # k) lives in the ``walk_ref`` scratch rather than in loop carries,
    # whose layouts Mosaic cannot always reconcile.
    d_col = jax.lax.broadcasted_iota(jnp.int32, (band, 1), 0)
    op_row = jax.lax.broadcasted_iota(jnp.int32, (max_ops, 1), 0)
    I, D, S, K, ACC = (pl.ds(x, 1) for x in range(5))

    def pick(plane, col):
        """plane (band, block_r), col (1, block_r) -> plane[col, lane]."""
        return jnp.max(jnp.where(d_col == col, plane, 0), axis=0,
                       keepdims=True)

    def flat_byte(cell):
        """Byte at flat cell ``cell`` of the (n * band) planes, with the
        reference walk's ``take_along_axis`` semantics: one wrap for a
        negative cell, and 0 past either end (the reference's fill value
        carries no direction bits).  Only band-edge walks of saturated
        alignments get here."""
        cell = jnp.where(cell < 0, cell + n * band, cell)
        q = jnp.where(cell >= 0, cell // band, -1)
        col = cell - q * band
        walk_ref[ACC, :] = jnp.zeros_like(cell)

        @pl.loop(0, n)
        def _(r):
            walk_ref[ACC, :] = jnp.where(
                q == r, pick(dirs_ref[r].astype(jnp.int32), col),
                walk_ref[ACC, :])

        return walk_ref[ACC, :]

    def body(c):
        r, _ = c
        i, d, state = walk_ref[I, :], walk_ref[D, :], walk_ref[S, :]
        plane = dirs_ref[jnp.maximum(r - 1, 0)].astype(jnp.int32)
        on_row = i == r
        byte = jax.lax.cond(
            _any(on_row & ((d < 0) | (d >= band))),
            lambda: flat_byte((i - 1) * band + d),
            lambda: pick(plane, d))
        op, ni, nd, ns, active = traceback_step(i, d, state, byte, eth)
        active = active & on_row
        k = walk_ref[K, :]
        slot = jnp.remainder(max_ops - 1 - k, max_ops)
        ops_ref[...] = jnp.where(active & (op_row == slot), op, ops_ref[...])
        i, d = jnp.where(active, ni, i), jnp.where(active, nd, d)
        walk_ref[I, :] = i
        walk_ref[D, :] = d
        walk_ref[S, :] = jnp.where(active, ns, state)
        walk_ref[K, :] = k + active.astype(jnp.int32)
        stay = _any((i == r) & ((i > 0) | (i + d - eth > 0)))
        r = jnp.where(stay, r, r - 1)
        return r, r >= 0

    ops_ref[...] = jnp.full((max_ops, block_r), OP_NONE, jnp.int32)
    walk_ref[I, :] = jnp.full((1, block_r), n, jnp.int32)
    walk_ref[D, :] = jnp.full((1, block_r), eth, jnp.int32)
    walk_ref[S, :] = jnp.zeros((1, block_r), jnp.int32)
    walk_ref[K, :] = jnp.zeros((1, block_r), jnp.int32)
    jax.lax.while_loop(lambda c: c[1], body, (jnp.int32(n), True))
    cnt_ref[...] = walk_ref[K, :]


@functools.partial(jax.jit, static_argnames=("eth", "sat", "max_ops",
                                             "block_r", "interpret"))
def affine_traceback_pallas(s1T: jnp.ndarray, s2T: jnp.ndarray, *,
                            eth: int, sat: int, max_ops: int, block_r: int,
                            interpret: bool):
    """s1T (n, R), s2T (n+2*eth, R) int8 -> (dists (2, R) int32,
    ops (max_ops, R) int32 END-aligned, count (1, R) int32).

    The direction planes live only in VMEM scratch — nothing O(n * band)
    is allocated in HBM or crosses D2H.
    """
    n, R = s1T.shape
    band = 2 * eth + 1
    assert s2T.shape == (n + 2 * eth, R)
    assert R % block_r == 0
    s1p, s2p = pad_rows8(s1T, s2T, eth)
    return pl.pallas_call(
        functools.partial(_kernel, eth=eth, n=n, sat=sat, max_ops=max_ops),
        grid=(R // block_r,),
        in_specs=in_specs(s1p, s2p, block_r),
        out_specs=[
            pl.BlockSpec((2, block_r), lambda r: (0, r)),
            pl.BlockSpec((max_ops, block_r), lambda r: (0, r)),
            pl.BlockSpec((1, block_r), lambda r: (0, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((2, R), jnp.int32),
            jax.ShapeDtypeStruct((max_ops, R), jnp.int32),
            jax.ShapeDtypeStruct((1, R), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((s1p.shape[0], band, block_r),
                                   jnp.uint8),
                        pltpu.VMEM((8, block_r), jnp.int32)],
        interpret=interpret,
    )(s1p, s2p)
