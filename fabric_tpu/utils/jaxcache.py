"""Persistent XLA compilation cache setup, shared by
tests/conftest.py, __graft_entry__.py, TPUProvider and the serve
registry.

A first compile of the ECDSA verify kernel costs minutes (XLA:CPU and
the TPU compiler alike); a cached one costs seconds.  Where the cache
lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets NO directory in code, so the operator (or the chip tool) places
  the cache.  The directory is part of nothing's key, but a cache that
  moves between runs is simply cold — keep the path fixed.
- unset: ``<checkout>/.jax_cache`` (gitignored), so repeated runs from
  one checkout are warm without any set-up.

Either way every entry is kept, however small or quick to compile.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pin_cpu_mesh(n_devices: int = 8) -> None:
    """Pin jax to the host-CPU platform with >= n_devices virtual devices.

    Must run before ANY backend/array initialisation, so sharding checks
    run on a hermetic CPU mesh and never touch an accelerator client.
    If XLA_FLAGS already forces a host device count (conftest, driver),
    that wins; otherwise use the dynamic config key.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    if "xla_force_host_platform_device_count" in os.environ.get(
        "XLA_FLAGS", ""
    ):
        return
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except Exception:
        # Backend already initialised (called twice in-process): an
        # in-process no-op by design — callers assert on the resulting
        # device count.
        pass
