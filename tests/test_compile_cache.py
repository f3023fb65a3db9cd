"""`use_compile_cache`: JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise to one fixed directory
of the checkout. Each case runs in a fresh interpreter, so no test
process turns the cache on for the tests that follow it."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = r"""
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
print("returned", use_compile_cache())
print("config", jax.config.jax_compilation_cache_dir)
if "--compile" in sys.argv:
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
"""


def _run(env_dir, *args):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CODE, *args], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_defaults_to_fixed_checkout_dir():
    out = _run(None)
    want = os.path.join(ROOT, ".jax_cache")
    assert out["returned"] == out["config"] == want


def test_cache_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    out = _run(tmp_path, "--compile")
    assert out["returned"] == out["config"] == str(tmp_path)
    assert os.listdir(tmp_path), "no cache entry was written there"


def test_checkout_cache_dir_is_git_ignored():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
