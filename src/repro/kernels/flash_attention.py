"""Pallas TPU kernel: causal/bidirectional flash attention with GQA.

The LM-side perf-critical layer (prefill/train attention).  Grid is
(batch, q_heads, q_blocks); each program holds one (qc, hd) query tile and
its kv-head's full (S, hd) K/V panels in VMEM, sweeping kv chunks with an
online-softmax accumulator — the classic flash schedule, with the GQA
q-head -> kv-head mapping folded into the BlockSpec index_map (no KV
replication in HBM or VMEM).

VMEM per program (S = 8192, hd = 128, qc = 512, bf16):
  K + V panels 2x2 MiB + q/out tiles ~0.25 MiB + f32 stats — fits the
  16 MiB VMEM budget up to S ~ 24k; beyond that the jnp chunked oracle
  (layers._sdpa_chunked) streams from HBM instead (documented fallback).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_chunk: int, causal: bool,
            scale: float):
    qc, hd = q_ref.shape[2], q_ref.shape[3]
    S = k_ref.shape[2]
    nk = S // kv_chunk
    iq = pl.program_id(2)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (qc, hd)

    def body(ik, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(ik * kv_chunk, kv_chunk), :]  # (kc, hd)
        v = v_ref[0, 0, pl.ds(ik * kv_chunk, kv_chunk), :]
        s = jnp.dot(q, k.astype(jnp.float32).T)             # (qc, kc)
        if causal:
            qpos = iq * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, 1), 0)
            kpos = ik * kv_chunk + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv_chunk), 1)
            s = jnp.where(qpos >= kpos, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.dot(p.astype(v.dtype),
                                       v).astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((qc, 1), NEG, jnp.float32)
    l0 = jnp.zeros((qc, 1), jnp.float32)
    a0 = jnp.zeros((qc, hd), jnp.float32)
    if causal:
        # only kv chunks up to the diagonal contribute; masked-out chunks
        # are skipped entirely (no wasted rectangles, unlike the jnp path)
        nk_eff = jnp.minimum(((iq + 1) * qc + kv_chunk - 1) // kv_chunk, nk)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_chunk", "kv_chunk",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           q_chunk: int = 512, kv_chunk: int = 512,
                           interpret: bool):
    """q (B, H, S, hd); k/v (B, KV, S, hd) -> (B, H, S, hd).

    H must be a multiple of KV (GQA); S divisible by the chunk sizes.
    """
    B, H, S, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    qc = min(q_chunk, S)
    kc = min(kv_chunk, S)
    assert S % qc == 0 and S % kc == 0
    grid = (B, H, S // qc)
    scale = 1.0 / math.sqrt(hd)
    return pl.pallas_call(
        functools.partial(_kernel, kv_chunk=kc, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qc, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qc, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
