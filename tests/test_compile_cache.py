"""Where the launchers' persistent compilation cache lands.

Each case runs in a fresh CPU process, since a process fixes its cache
directory at its first compile.
"""
import os
import subprocess
import sys
import time

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compilation_cache
where = enable_compilation_cache()
salt = float(sys.argv[1])  # a new program, so a new cache entry, per run
jax.jit(lambda x: x * 3 + salt)(jnp.arange(7.0)).block_until_ready()
print(where)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(cache_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               # cache even the probe's millisecond compile
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, repr(time.time())], env=env, cwd=ROOT,
        check=True, capture_output=True, text=True, timeout=120,
    )
    return out.stdout.split()


def _entries_since(directory, t0):
    return [
        f for f in os.listdir(directory)
        if os.path.getmtime(os.path.join(directory, f)) >= t0
    ]


def test_env_cache_dir_is_used_as_given(tmp_path):
    target = str(tmp_path / "cache")
    assert _probe(target) == [target, target]
    assert os.listdir(target)


def test_default_cache_is_fixed_inside_the_checkout():
    assert CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    t0 = time.time() - 1.0
    assert _probe(None) == [CHECKOUT_CACHE_DIR, CHECKOUT_CACHE_DIR]
    assert _entries_since(CHECKOUT_CACHE_DIR, t0)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
