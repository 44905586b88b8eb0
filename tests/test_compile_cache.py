"""The persistent compilation cache goes where ``use_compile_cache`` says.

Each case runs in a child process on the CPU backend, because the helper
changes process-wide JAX config and JAX reads ``JAX_COMPILATION_CACHE_DIR``
only when it is imported.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(REPO_CACHE_DIR)
if {compile}:
    jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)))
"""


def _run(env_dir, compile_):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(compile=compile_)],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


@pytest.mark.parametrize("placed", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_location(tmp_path, placed):
    """With the variable set the cache is configured to, and written in, that
    directory only; without it the cache is ``<repo>/.jax_cache``."""
    if placed:
        where = str(tmp_path / "cache")
        used, cfg, repo_dir = _run(where, compile_=True)
        assert used == cfg == where
        assert repo_dir == os.path.join(ROOT, ".jax_cache")
        assert os.listdir(where), "nothing was written to the cache"
    else:
        used, cfg, repo_dir = _run(None, compile_=False)
        assert used == cfg == repo_dir == os.path.join(ROOT, ".jax_cache")
