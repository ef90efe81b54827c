"""Distributed read mapper + sharded LM steps on 8 virtual devices.

jax locks the device count at first init, so multi-device tests run in a
subprocess with XLA_FLAGS set (the dry-run itself uses 512 — see
repro/launch/dryrun.py; here 8 keeps test time sane).
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_MAPPER_SCRIPT = r"""
import jax, numpy as np
from repro.launch.mesh import make_genomics_mesh
mesh = make_genomics_mesh(8)
from repro.data.genome import make_reference, sample_reads
from repro.core.index import build_index
from repro.core.distributed import shard_index, distributed_map_reads
from repro.core.pipeline import MapperConfig, map_reads

ref = make_reference(20000, seed=0, repeat_frac=0.02)
idx = build_index(ref)
sidx = shard_index(idx, 8)
rs = sample_reads(ref, 64, seed=3)
cfg = MapperConfig(read_len=sidx.read_len, k=sidx.k, w=sidx.w, eth=sidx.eth,
                   aff_block_r=64)
pos, dist, dropped, stats = distributed_map_reads(mesh, sidx, rs.reads,
                                                  cfg=cfg, with_stats=True)
res = map_reads(idx, rs.reads)
assert (pos == res.position).all(), "distributed != single-shard positions"
assert (dist == res.distance).all()
assert dropped.sum() == 0
acc = (np.abs(pos - rs.true_pos) <= 6).mean()
assert acc > 0.95, acc

# stage B ran affine WF only on compacted filter survivors
assert stats["stage_b_affine_instances"] < stats["stage_b_entries"], stats
assert stats["stage_b_survivors"] <= stats["stage_b_affine_instances"]
assert stats["stage_b_affine_dropped"] == 0

# send-capacity overflow drops entries but never corrupts results
pos2, dist2, dropped2 = distributed_map_reads(mesh, sidx, rs.reads,
                                              send_cap=2)
assert dropped2.sum() > 0
mapped2 = pos2 >= 0
assert (np.abs(pos2[mapped2] - rs.true_pos[mapped2]) <= 6).mean() > 0.9

# survivor-capacity overflow: bounded affine work, sane subset results
cfg3 = MapperConfig(read_len=sidx.read_len, k=sidx.k, w=sidx.w, eth=sidx.eth,
                    stage_b_survivor_frac=0.001, aff_block_r=8)
pos3, dist3, drop3, st3 = distributed_map_reads(mesh, sidx, rs.reads,
                                                cfg=cfg3, with_stats=True)
assert st3["stage_b_affine_dropped"] > 0, st3
m3 = pos3 >= 0
assert m3.any()
assert (np.abs(pos3[m3] - rs.true_pos[m3]) <= 6).mean() > 0.9

# unified session API, mesh topology: bit-identical to the free function
from repro.core.mapper import Mapper
mapper = Mapper(sidx, cfg, topology="mesh", mesh=mesh)
mres = mapper.map(rs.reads)
assert (mres.position == pos).all() and (mres.distance == dist).all()
assert mres.stats["stage_b_survivors"] == stats["stage_b_survivors"]

# MappingService routed onto the mesh: repeated same-size buckets are
# pure plan-cache hits (no new executables after warm-up)
from repro.core.serving import BatcherConfig, MappingService
svc = MappingService(mapper, batcher=BatcherConfig(bucket_min=16,
                                                   bucket_max=32))
for _ in range(2):
    rids = [svc.submit(rs.reads[:40]), svc.submit(rs.reads[40:])]
    out = svc.flush()
    for rid, (lo, hi) in zip(rids, ((0, 40), (40, 64))):
        assert (np.abs(out[rid].position - res.position[lo:hi]) <= 0).all()
warm = mapper.plan_cache_misses
rids = [svc.submit(rs.reads[:40]), svc.submit(rs.reads[40:])]
svc.flush()
assert mapper.plan_cache_misses == warm, "same-size buckets recompiled"
assert mapper.plan_cache_hits > 0
print("DISTRIBUTED_MAPPER_OK")
"""

_LM_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
from repro.configs import ARCHS, reduced
from repro.models import lm, transformer
from repro.models.layers import Shardings
from repro.train.optimizer import adamw
import dataclasses

cfg = dataclasses.replace(reduced(ARCHS["olmo-1b"]), remat=True)
sh = Shardings(batch=("data",), model=("model",), fsdp=("data",),
               model_size=4)
key = jax.random.key(0)
params = transformer.init_params(cfg, key)
pspecs = transformer.param_specs(cfg, sh)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
params_sharded = jax.device_put(params, ns(pspecs))
opt = adamw(total_steps=4)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
with mesh:
    step = jax.jit(lm.make_train_step(cfg, opt, sh, num_microbatches=2))
    state = (params_sharded, opt.init(params_sharded), jnp.int32(0))
    state, metrics = step(state, batch)
    sharded_loss = float(metrics["loss"])

# unsharded single-device reference
step1 = jax.jit(lm.make_train_step(cfg, opt, num_microbatches=2))
state1 = (params, opt.init(params), jnp.int32(0))
state1, metrics1 = step1(state1, batch)
assert abs(sharded_loss - float(metrics1["loss"])) < 2e-2, (
    sharded_loss, float(metrics1["loss"]))
print("DISTRIBUTED_LM_OK")
"""


def _run(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_distributed_mapper_8dev():
    assert "DISTRIBUTED_MAPPER_OK" in _run(_MAPPER_SCRIPT)


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded():
    assert "DISTRIBUTED_LM_OK" in _run(_LM_SCRIPT)


_ELASTIC_SCRIPT = r"""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import ARCHS, reduced
from repro.models import lm, transformer
from repro.models.layers import Shardings
from repro.train import checkpoint as ckpt
from repro.train.optimizer import adamw

cfg = reduced(ARCHS["olmo-1b"])
key = jax.random.key(0)
opt = adamw(warmup=0, total_steps=6)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}

def make(mesh_shape, axes):
    mesh = jax.make_mesh(mesh_shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
    sh = Shardings(batch=("data",), model=("model",), fsdp=("data",),
                   model_size=mesh.shape["model"])
    pspecs = transformer.param_specs(cfg, sh)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    return mesh, sh, pspecs, ns

with tempfile.TemporaryDirectory() as d:
    # train 2 steps on a (2, 4) mesh, checkpoint
    mesh, sh, pspecs, ns = make((2, 4), ("data", "model"))
    params = jax.device_put(transformer.init_params(cfg, key), ns(pspecs))
    state = (params, opt.init(params), jnp.int32(0))
    with mesh:
        step = jax.jit(lm.make_train_step(cfg, opt, sh))
        for _ in range(2):
            state, m = step(state, batch)
    ckpt.save(d, 2, state, extra={"next_step": 2})
    loss_a = None
    with mesh:
        state_a, m_a = step(state, batch)
        loss_a = float(m_a["loss"])

    # restart on a DIFFERENT mesh shape (node loss: 8 -> same 8 devices,
    # reshaped (4, 2)), restore, take the same step
    mesh2, sh2, pspecs2, ns2 = make((4, 2), ("data", "model"))
    params2 = jax.device_put(transformer.init_params(cfg, key), ns2(pspecs2))
    like = (params2, opt.init(params2), jnp.int32(0))
    shard_tree = (ns2(pspecs2), {"m": ns2(pspecs2), "v": ns2(pspecs2)},
                  NamedSharding(mesh2, P()))
    restored, extra = ckpt.restore(d, 2, like, sharding_tree=shard_tree)
    assert extra["next_step"] == 2
    with mesh2:
        step2 = jax.jit(lm.make_train_step(cfg, opt, sh2))
        state_b, m_b = step2(restored, batch)
    loss_b = float(m_b["loss"])
    assert abs(loss_a - loss_b) < 1e-3, (loss_a, loss_b)
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_restore_across_mesh_shapes():
    """Checkpoint on a (2,4) mesh, restore + continue on (4,2): the step
    after restart produces the same loss as the uninterrupted run."""
    assert "ELASTIC_OK" in _run(_ELASTIC_SCRIPT)


_LONGCTX_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
from repro.configs import ARCHS, reduced
from repro.models import lm, transformer
from repro.models.layers import Shardings

# zamba-like reduced hybrid, batch=1, cache sequence sharded over data
cfg = reduced(ARCHS["zamba2-2.7b"])
sh = Shardings(batch=(), model=("model",), fsdp=(), model_size=2)
key = jax.random.key(0)
params = transformer.init_params(cfg, key)
pspecs = transformer.param_specs(cfg, sh)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
params_s = jax.device_put(params, ns(pspecs))
S = 64
cache = transformer.init_cache(cfg, 1, S)
cspecs = transformer.cache_specs(cfg, sh, seq_shard_axes=("data",))
cache_s = jax.device_put(cache, ns(cspecs))
toks = jax.random.randint(key, (1, 6), 0, cfg.vocab_size)
with mesh:
    serve = jax.jit(lm.make_serve_step(cfg, sh))
    c = cache_s
    for t in range(6):
        lg, c = serve(params_s, c, toks[:, t:t+1], jnp.int32(t))
# reference: unsharded decode
serve0 = jax.jit(lm.make_serve_step(cfg))
c0 = transformer.init_cache(cfg, 1, S)
for t in range(6):
    lg0, c0 = serve0(params, c0, toks[:, t:t+1], jnp.int32(t))
d = float(jnp.max(jnp.abs(lg.astype(jnp.float32) - lg0.astype(jnp.float32))))
assert d < 0.05, d
print("LONGCTX_OK")
"""


@pytest.mark.slow
def test_seq_sharded_decode_matches_unsharded():
    """batch=1 decode with the KV cache sequence sharded over the data axis
    (the long_500k configuration) matches unsharded decode."""
    assert "LONGCTX_OK" in _run(_LONGCTX_SCRIPT)
