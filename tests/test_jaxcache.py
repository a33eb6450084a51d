"""Where the persistent compile cache lives (utils/jaxcache.py, PR 22):
JAX_COMPILATION_CACHE_DIR when it is set — the code then sets no
directory — else <checkout>/.jax_cache; never a temp name, pid or time."""

import os
import subprocess
import sys

import pytest

from fabric_tpu.utils import jaxcache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "from fabric_tpu.utils.jaxcache import enable_compile_cache\n"
    "enable_compile_cache()\n"
    "import jax\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n"
)


def _run_probe(env):
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("placed", ["env", "default"])
def test_cache_directory_follows_the_environment(tmp_path, placed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "moved")
        assert _run_probe(env) == str(tmp_path / "moved")
        # every entry of the run landed there (min size/time thresholds 0)
        assert any(
            name.endswith("-cache") for name in os.listdir(tmp_path / "moved")
        )
    else:
        assert _run_probe(env) == jaxcache.CACHE_DIR
        assert jaxcache.CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")


def test_no_directory_is_set_in_code_when_the_variable_is_set(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    jaxcache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
