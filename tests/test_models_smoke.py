"""Per-arch smoke tests: reduced same-family config, one forward/train step
on CPU, shape + finiteness assertions; decode where the family supports it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, cell_applicable, input_specs, reduced
from repro.models import lm, transformer
from repro.train.optimizer import adamw

KEY = jax.random.key(0)


def _batch(cfg, B=2, S=32):
    if cfg.input_kind == "embeds":
        return {"embeds": jax.random.normal(KEY, (B, S, cfg.d_model),
                                            jnp.bfloat16) * 0.1,
                "labels": jnp.ones((B, S), jnp.int32)}
    return {"tokens": jnp.zeros((B, S), jnp.int32) + 3,
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_and_train_step(arch):
    cfg = reduced(ARCHS[arch])
    params = transformer.init_params(cfg, KEY)
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    logits, aux = transformer.forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    opt = adamw(warmup=0, total_steps=4)
    step = jax.jit(lm.make_train_step(cfg, opt))
    state = (params, opt.init(params), jnp.int32(0))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    delta = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state[0], params)
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_step(arch):
    cfg = reduced(ARCHS[arch])
    if not cfg.supports_decode:
        pytest.skip("encoder-only: no decode step by design")
    params = transformer.init_params(cfg, KEY)
    B, S = 2, 16
    cache = transformer.init_cache(cfg, B, S)
    serve = jax.jit(lm.make_serve_step(cfg))
    tok = (jax.random.normal(KEY, (B, 1, cfg.d_model), jnp.bfloat16) * 0.1
           if cfg.input_kind == "embeds"
           else jnp.ones((B, 1), jnp.int32))
    lg, cache = serve(params, cache, tok, jnp.int32(0))
    lg, cache = serve(params, cache, tok, jnp.int32(1))
    assert lg.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg, np.float32)).all()


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-0.6b", "falcon-mamba-7b",
                                  "zamba2-2.7b"])
def test_decode_matches_forward(arch):
    """Step-by-step decode reproduces the full forward logits (token archs;
    MoE excluded — train-path capacity dropping differs by design)."""
    cfg = reduced(ARCHS[arch])
    params = transformer.init_params(cfg, KEY)
    B, T = 2, 6
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    full, _ = transformer.forward(params, {"tokens": toks}, cfg)
    serve = jax.jit(lm.make_serve_step(cfg))
    cache = transformer.init_cache(cfg, B, 8)
    for t in range(T):
        lg, cache = serve(params, cache, toks[:, t : t + 1], jnp.int32(t))
    tol = 0.05 if cfg.family in ("hybrid", "ssm") else 1e-3
    np.testing.assert_allclose(np.asarray(full[:, -1], np.float32),
                               np.asarray(lg, np.float32), atol=tol, rtol=tol)


def test_cell_applicability_matrix():
    """The 40-cell accounting: every cell is either runnable or has a
    documented skip reason."""
    n_run = n_skip = 0
    for arch, cfg in ARCHS.items():
        for shape in SHAPES.values():
            ok, reason = cell_applicable(cfg, shape)
            if ok:
                n_run += 1
            else:
                n_skip += 1
                assert reason
    assert n_run + n_skip == 40
    # encoder skips 2 decode cells; 8 full-attention archs skip long_500k
    assert n_skip == 2 + 7  # hubert(decode_32k+long), 7 others long_500k


def test_input_specs_are_abstract():
    for arch, cfg in ARCHS.items():
        for shape in SHAPES.values():
            specs = input_specs(cfg, shape)
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)


def test_param_count_sanity():
    # full configs should be in the advertised ballpark
    assert 2.0e9 < ARCHS["zamba2-2.7b"].n_params() < 3.6e9
    assert 0.9e9 < ARCHS["olmo-1b"].n_params() < 1.6e9
    assert 60e9 < ARCHS["qwen2-vl-72b"].n_params() < 85e9
    assert 6e9 < ARCHS["falcon-mamba-7b"].n_params() < 9e9
    assert 150e9 < ARCHS["qwen3-moe-235b-a22b"].n_params() < 300e9
    a22 = ARCHS["qwen3-moe-235b-a22b"].active_params()
    assert 15e9 < a22 < 30e9
