"""The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
set, else one fixed directory inside the checkout.  Each case runs in
a fresh process, since the cache binds to its directory at the
process's first compile."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
"""


def _run(env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(compile_cache.ENV, None)
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_default_dir_is_fixed_inside_the_checkout():
    assert compile_cache.default_dir() == str(ROOT / ".jax_cache")


def test_env_dir_is_used_and_written(tmp_path):
    enabled, configured = _run(tmp_path)
    assert enabled == configured == str(tmp_path)
    assert any(tmp_path.iterdir())          # the compile was cached there


def test_default_dir_without_env():
    enabled, configured = _run()
    assert enabled == configured == compile_cache.default_dir()
