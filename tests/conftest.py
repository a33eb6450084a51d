"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths
(jax.sharding.Mesh over 8 devices) are exercised without TPU hardware.

The platform is pinned through jax.config.update (a plugin or an env
var set before pytest started must not move the tests onto a device);
XLA_FLAGS is honored because no backend is initialized until the first
jax use inside the tests.

The big ECDSA verify kernel costs minutes of XLA:CPU compile time the
first run; the persistent compilation cache (utils/jaxcache.py:
JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) makes every later
run fast. Keep that directory out of git but on disk.
"""

import importlib.util
import os

import pytest

# Shared marker: tests needing X.509 / TLS material skip cleanly in
# minimal environments (test modules `from conftest import requires_crypto`).
requires_crypto = pytest.mark.skipif(
    importlib.util.find_spec("cryptography") is None,
    reason="needs the cryptography package (X.509 / TLS material)",
)

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from fabric_tpu.utils.jaxcache import enable_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
enable_compile_cache()

# Opt-in persistent-cache forensics: FABRIC_TPU_CACHE_DEBUG=1 logs every
# compilation-cache hit/miss/write with its key (the env-var route is
# too late here for the same reason as above).
if os.environ.get("FABRIC_TPU_CACHE_DEBUG") == "1":
    jax.config.update(
        "jax_debug_log_modules",
        "jax._src.compiler,jax._src.compilation_cache",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow')",
    )
