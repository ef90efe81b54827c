"""Training substrate: optimizer, checkpoint atomicity, fault tolerance,
microbatch equivalence, deterministic data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.data.tokens import batch_for_step
from repro.models import lm, transformer
from repro.train import checkpoint as ckpt
from repro.train.optimizer import adamw, cosine_schedule
from repro.train.trainer import FaultInjector, Trainer, TrainerConfig

CFG = reduced(ARCHS["smollm-135m"])


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(100)) == pytest.approx(0, abs=1e-9)
    assert float(lr(55)) < float(lr(20))


def test_adamw_converges_quadratic():
    opt = adamw(lr=0.05, weight_decay=0.0, warmup=0, total_steps=200)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for step in range(150):
        grads = {"w": 2 * (params["w"] - 1.0)}
        upd, state = opt.update(grads, state, params, jnp.int32(step))
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0], atol=0.05)


def test_microbatch_equivalence():
    key = jax.random.key(0)
    params = transformer.init_params(CFG, key)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, CFG.vocab_size),
             "labels": jax.random.randint(key, (8, 32), 0, CFG.vocab_size)}
    opt = adamw(warmup=0, total_steps=4)
    s1 = (params, opt.init(params), jnp.int32(0))
    s2 = (params, opt.init(params), jnp.int32(0))
    t1 = jax.jit(lm.make_train_step(CFG, opt, num_microbatches=1))
    t4 = jax.jit(lm.make_train_step(CFG, opt, num_microbatches=4))
    (_, m1) = t1(s1, batch)[1], None
    s1n, m1 = t1(s1, batch)
    s4n, m4 = t4(s2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=2e-2)
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                     s1n[0], s4n[0])
    assert max(jax.tree.leaves(d)) < 5e-2  # bf16 grads: small tolerance


def test_data_pipeline_deterministic_and_sharded():
    a1, b1 = batch_for_step(7, global_batch=8, seq_len=16, vocab_size=100,
                            seed=3)
    a2, b2 = batch_for_step(7, global_batch=8, seq_len=16, vocab_size=100,
                            seed=3)
    assert (a1 == a2).all() and (b1 == b2).all()
    # shards partition the global batch deterministically
    s0 = batch_for_step(7, global_batch=8, seq_len=16, vocab_size=100,
                        seed=3, shard_index=0, num_shards=2)[0]
    s1 = batch_for_step(7, global_batch=8, seq_len=16, vocab_size=100,
                        seed=3, shard_index=1, num_shards=2)[0]
    assert s0.shape == (4, 16)
    assert not (s0 == s1).all()


def test_checkpoint_roundtrip_and_retention(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    for step in (1, 2, 3, 4):
        ckpt.save(str(tmp_path), step, tree, extra={"next_step": step},
                  keep=2)
    assert ckpt.all_steps(str(tmp_path)) == [3, 4]
    restored, extra = ckpt.restore(str(tmp_path), 4, tree)
    assert extra["next_step"] == 4
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    tree = {"a": jnp.ones((2, 3))}
    ckpt.save(str(tmp_path), 1, tree)
    with pytest.raises(AssertionError):
        ckpt.restore(str(tmp_path), 1, {"a": jnp.ones((3, 3))})


def test_trainer_fault_injection_and_resume(tmp_path):
    tc = TrainerConfig(total_steps=8, global_batch=4, seq_len=32,
                       ckpt_dir=str(tmp_path), ckpt_every=4, log_every=2,
                       seed=5)
    t = Trainer(CFG, tc, fault_injector=FaultInjector(fail_steps=(2, 5)))
    state = t.run()
    assert int(state[2]) == 8
    assert len(t.metrics_log) >= 2

    # uninterrupted run from scratch must produce the identical final loss
    t2 = Trainer(CFG, TrainerConfig(total_steps=8, global_batch=4, seq_len=32,
                                    log_every=2, seed=5))
    state2 = t2.run()
    l1 = [m["loss"] for m in t.metrics_log]
    l2 = [m["loss"] for m in t2.metrics_log]
    np.testing.assert_allclose(l1, l2, rtol=1e-5)

    # resume from checkpoint continues at the right step
    t3 = Trainer(CFG, TrainerConfig(total_steps=10, global_batch=4,
                                    seq_len=32, ckpt_dir=str(tmp_path),
                                    log_every=1, seed=5))
    t3.run()
    assert t3.metrics_log[0]["step"] == 8  # resumed, not restarted


def test_trainer_exhausted_retries_raises():
    tc = TrainerConfig(total_steps=4, global_batch=4, seq_len=32,
                       max_retries=1)
    fi = FaultInjector(fail_steps=(1,))
    fi.tripped = None  # force check to raise every attempt

    class AlwaysFail(FaultInjector):
        def check(self, step):
            if step == 1:
                raise RuntimeError("persistent failure")

    t = Trainer(CFG, tc, fault_injector=AlwaysFail())
    with pytest.raises(RuntimeError):
        t.run()


def test_adafactor_converges_and_state_small():
    from repro.train.optimizer import adafactor, adafactor_state_specs
    from jax.sharding import PartitionSpec as P
    opt = adafactor(lr=0.3, warmup=0, total_steps=300)
    params = {"w": jnp.full((8, 4), 3.0), "b": jnp.array([2.0])}
    state = opt.init(params)
    # factored state is O(rows+cols), not O(rows*cols)
    n_state = sum(x.size for x in jax.tree.leaves(state))
    assert n_state == (8 + 4) + (1 + 1)
    for step in range(200):
        grads = jax.tree.map(lambda p: 2 * (p - 1.0), params)
        upd, state = opt.update(grads, state, params, jnp.int32(step))
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    np.testing.assert_allclose(np.asarray(params["w"]), 1.0, atol=0.05)
    # spec mapping drops the right axes
    specs = adafactor_state_specs({"w": P("data", "model"), "b": P(None)})
    assert specs["w"]["vr"] == P("data")
    assert specs["w"]["vc"] == P("model")


def test_train_step_with_adafactor():
    from repro.train.optimizer import adafactor
    opt = adafactor(warmup=0, total_steps=4)
    key = jax.random.key(0)
    params = transformer.init_params(CFG, key)
    batch = {"tokens": jax.random.randint(key, (4, 32), 0, CFG.vocab_size),
             "labels": jax.random.randint(key, (4, 32), 0, CFG.vocab_size)}
    ts = jax.jit(lm.make_train_step(CFG, opt, num_microbatches=2))
    state = (params, opt.init(params), jnp.int32(0))
    state, m = ts(state, batch)
    assert np.isfinite(float(m["loss"]))
