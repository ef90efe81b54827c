"""Validate the analytic FLOPs model against XLA cost_analysis on configs
whose loops are fully unrolled (the documented methodology — see
repro/launch/roofline.py: cost_analysis counts while bodies once)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ShapeCell
from repro.launch import roofline as rl
from repro.models import lm, transformer


def _hlo_flops_unrolled(cfg, B, S):
    """Compile an eval step with scan replaced by an unrolled loop."""
    from repro.models import transformer as tr

    params = tr.abstract_params(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.input_kind == "embeds":
        batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                jnp.bfloat16),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def fwd_unrolled(p, b):
        x = (tr.compute_dtype(p["embed"])[b["tokens"]]
             if cfg.input_kind == "tokens"
             else b["embeds"].astype(jnp.bfloat16))
        from repro.models.transformer import _block_fwd
        for i in range(cfg.n_layers):
            pl = jax.tree.map(lambda a: a[i], p["blocks"])
            x, _ = _block_fwd(x, pl, cfg, tr.layers.NO_SHARD)
        from repro.models import layers
        x = layers.apply_norm(x, p["final_norm"], cfg.norm)
        logits = x @ tr.compute_dtype(p["lm_head"])
        return jnp.sum(logits.astype(jnp.float32))

    c = jax.jit(fwd_unrolled).lower(params, batch).compile()
    return c.cost_analysis()["flops"]


@pytest.mark.parametrize("arch", ["smollm-135m", "olmo-1b"])
def test_analytic_flops_vs_hlo_dense(arch):
    cfg = dataclasses.replace(reduced(ARCHS[arch]), remat=False, n_layers=3)
    B, S = 2, 256
    hlo = _hlo_flops_unrolled(cfg, B, S)
    model = rl.forward_flops(cfg, B * S, s_ctx=S)
    # HLO counts causal-masked full rectangles too (we pass s_ctx=S);
    # small ops (norms, rope, softmax) are not in the analytic model.
    assert model == pytest.approx(hlo, rel=0.15), (model, hlo)


def test_analytic_flops_vs_hlo_moe():
    cfg = dataclasses.replace(reduced(ARCHS["moonshot-v1-16b-a3b"]),
                              remat=False, n_layers=2)
    B, S = 2, 256
    hlo = _hlo_flops_unrolled(cfg, B, S)
    model = rl.forward_flops(cfg, B * S, s_ctx=S)
    assert model == pytest.approx(hlo, rel=0.25), (model, hlo)


def test_roofline_terms_reasonable():
    cfg = ARCHS["qwen2-vl-72b"]
    shape = ShapeCell("train_4k", 4096, 256, "train")
    r = rl.cell_roofline(cfg, shape, {"data": 16, "model": 16}, n_micro=16)
    # 72B x 1M tokens / 256 chips at 197 TF/s: seconds-scale step
    assert 1.0 < r.compute_s < 60.0
    assert r.dominant in ("compute", "memory", "collective")
    assert 0 < r.useful_ratio <= 1.0
    assert 0 < r.roofline_fraction <= 1.0


def test_decode_is_memory_bound():
    cfg = ARCHS["olmo-1b"]
    shape = ShapeCell("decode_32k", 32768, 128, "decode")
    r = rl.cell_roofline(cfg, shape, {"data": 16, "model": 16})
    assert r.dominant in ("memory", "collective")  # classic decode regime


def test_useful_ratio_definitions():
    cfg = ARCHS["qwen3-moe-235b-a22b"]
    shape = ShapeCell("train_4k", 4096, 256, "train")
    fl = rl.cell_flops(cfg, shape)
    # MoE useful flops use ACTIVE params
    assert fl["useful"] == 6 * cfg.active_params() * 256 * 4096
    assert fl["useful"] < fl["hlo_like_total"]
