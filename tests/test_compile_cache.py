"""The persistent compilation cache goes where the environment says, else
to the checkout's fixed ``.jax_cache`` — never anywhere else."""
import os
import subprocess
import sys

from repro.launch.compile_cache import CHECKOUT_CACHE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = """
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.tanh(x) * 5 - 2)(jnp.ones(13)).block_until_ready()
"""


def _run(env_extra, drop=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"), **env_extra)
    for k in drop:
        env.pop(k, None)
    r = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cache_follows_environment(tmp_path):
    before = _entries(CHECKOUT_CACHE)
    where = _run({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert where == str(tmp_path)
    assert _entries(tmp_path)
    assert _entries(CHECKOUT_CACHE) == before


def test_cache_defaults_to_checkout():
    where = _run({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert where == str(CHECKOUT_CACHE)
    assert os.path.dirname(where) == ROOT
    assert _entries(CHECKOUT_CACHE)
