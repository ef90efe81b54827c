"""The mapping path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies as plain
JAX ops and cannot see what the chip's compiler refuses: int8 vector
arithmetic, loads at unaligned dynamic sublane offsets, layouts Mosaic
cannot reconcile.  These tests compile, with ``interpret=False``, for a
v5e that is described rather than attached, at the reference size: read
length 150, eth 6, 4096 instances.  Nothing runs.  The topology is built
inside a fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU compiler.

``minimizer_pallas`` is left out on purpose: it is off the mapping path,
and its compile aborts the process (which would take the test worker and
every test it holds down with it).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, ETH, R = 150, 6, 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_call(name):
    from repro.kernels import affine_wf, linear_wf, traceback
    return {
        "linear_wf": lambda a, b: linear_wf.linear_wf_pallas(
            a, b, eth=ETH, block_r=512, interpret=False),
        "affine_wf_dist": lambda a, b: affine_wf.affine_wf_dist_pallas(
            a, b, eth=ETH, sat=32, block_r=256, interpret=False),
        "affine_wf": lambda a, b: affine_wf.affine_wf_pallas(
            a, b, eth=ETH, sat=32, block_r=256, interpret=False),
        "affine_traceback": lambda a, b: traceback.affine_traceback_pallas(
            a, b, eth=ETH, sat=32, max_ops=2 * N + 2, block_r=256,
            interpret=False),
    }[name]


@pytest.mark.parametrize("kernel", ["linear_wf", "affine_wf_dist",
                                    "affine_wf", "affine_traceback"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    s1 = jax.ShapeDtypeStruct((N, R), jnp.int8, sharding=one_chip)
    s2 = jax.ShapeDtypeStruct((N + 2 * ETH, R), jnp.int8, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(_kernel_call(kernel), s1, s2)


def test_fused_chunk_program_compiles_for_v5e(one_chip, monkeypatch):
    """The fused engine's whole per-chunk program — seeding output in,
    linear filter, affine survivors, strand fold and winner traceback —
    for one 1024-read dual-strand chunk against a 48 Mb reference's index
    (about 3.1M occurrence rows), with the pallas backend in compiled
    mode.  ``ops.on_tpu`` is what the backend asks; this sandbox's answer
    is patched, and JAX's caches are cleared around the trace so no
    interpret-mode trace is reused or left behind."""
    from repro.core import pipeline
    from repro.kernels import ops

    cfg = pipeline.MapperConfig(engine="fused", wf_backend="pallas",
                                both_strands=True, chunk_reads=1024)
    rows, M, P = 2 * 1024, cfg.max_minis, cfg.max_pls
    n_occ, seg_len = 3_100_000, 2 * (cfg.read_len + cfg.eth) - cfg.k
    n_valid = 40_000                    # ~20 candidates per read row
    lin_cap = pipeline.bucket_capacity(n_valid, align=cfg.lin_block_r,
                                       cap_max=rows * M * P)
    aff_cap = pipeline.fused_affine_capacity(n_valid, rows, cfg)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((n_occ, seg_len), jnp.uint8), sds((n_occ,), jnp.int32),
            sds((rows, cfg.read_len), jnp.uint8),
            sds((rows, M, P), jnp.int32), sds((rows, M, P), jnp.bool_),
            sds((rows, M), jnp.int32), sds((), jnp.int32))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    jax.clear_caches()
    try:
        text = pipeline._fused_stage.lower(
            *args, cfg=cfg, lin_cap=lin_cap, aff_cap=aff_cap
        ).compile().as_text()
    finally:
        jax.clear_caches()
    assert text.count("tpu_custom_call") >= 3   # linear, affine, traceback
