"""Mesh construction (production + genomics service).

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.mapper import make_auto_mesh as _make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_genomics_mesh(n_shards: int | None = None):
    """Flat shard mesh for the distributed read mapper (one axis)."""
    n = n_shards or len(jax.devices())
    return _make_mesh((n,), ("shards",))


def batch_axes(mesh) -> tuple:
    """Mesh axes carrying data parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def named(mesh, spec_tree):
    """Map a PartitionSpec pytree to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
