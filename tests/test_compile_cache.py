"""The persistent compilation cache: one place decides where it lives."""

import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "write":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.cos(x) * 3)(jnp.ones(16)).block_until_ready()
"""


def _run(env_dir, mode="write"):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", SCRIPT, mode], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_env_var_dir_is_used_and_nothing_else(tmp_path):
    before = (set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.is_dir()
              else set())
    chosen, configured = _run(tmp_path)
    assert chosen == configured == str(tmp_path)
    assert os.listdir(tmp_path)                  # the entry landed there
    after = (set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.is_dir()
             else set())
    assert after == before


def test_default_dir_is_fixed_in_the_checkout_and_ignored():
    assert DEFAULT_DIR == Path(ROOT) / ".jax_cache"
    assert _run(None, mode="config") == [str(DEFAULT_DIR)] * 2
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
