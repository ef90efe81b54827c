"""Process set-up shared by the command-line entry points.

Called from each launcher's ``main`` (and ``chip_smoke.py``), never on
library import:

  * ``enable_compile_cache`` — JAX's persistent compilation cache, so a
    second run of the same programs skips the TPU compiles;
  * ``emulate_host_devices`` — virtual XLA:CPU devices for a mesh run, and
    only on a run pinned to the CPU, so a chip run whose JAX fell back to
    the CPU cannot pass for a mesh run;
  * ``device_line`` — the platform / device kind / device count each
    launcher prints on its start line.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because it is part of every cache
# key — a directory that moves between runs never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to ``CACHE_DIR``.  Every compile
    is cached, the second-long kernel compiles included.  A run pinned to
    the CPU (``JAX_PLATFORMS=cpu``: the tests, rehearsals) sets no
    directory: its compiles are cheap, and XLA:CPU warns on reloading its
    own cache entries."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            return "off (CPU run)"
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def emulate_host_devices(n: int | None) -> None:
    """Ask XLA:CPU for ``n`` virtual devices when the run is pinned to the
    CPU (``JAX_PLATFORMS=cpu``) and ``XLA_FLAGS`` is not already set.  Must
    run before JAX initialises its backends."""
    if n and os.environ.get("JAX_PLATFORMS") == "cpu" \
            and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n}"


def device_line() -> str:
    """``platform=tpu device_kind=TPU v5 lite devices=1`` for the start
    line: what JAX actually runs on, not what was asked for."""
    import jax
    devs = jax.devices()
    return (f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
            f"devices={len(devs)}")
