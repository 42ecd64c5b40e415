"""The persistent compile cache lands where the environment says, and
otherwise in the one fixed directory of the checkout."""
import os
import subprocess
import sys
import uuid

from repro.compile_cache import DEFAULT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compiles a function no earlier run can have cached (a fresh constant),
# so the cache must gain an entry for it
PROG = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda a: a * float(sys.argv[1]) + 1)(jnp.ones(8)).block_until_ready()
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    const = str(1 + uuid.uuid4().int % 10**6 / 7.0)
    out = subprocess.run([sys.executable, "-c", PROG, const], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()[-1]


def _entries(d):
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def test_env_dir_is_used_and_nothing_else(tmp_path):
    target = str(tmp_path / "cache")
    before = _entries(DEFAULT_DIR)
    assert _run(target) == target
    assert any(e.startswith("jit__lambda") for e in _entries(target))
    assert not {e for e in _entries(DEFAULT_DIR) - before
                if e.startswith("jit__lambda")}


def test_default_dir_is_fixed_in_the_checkout():
    assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    before = _entries(DEFAULT_DIR)
    assert _run(None) == DEFAULT_DIR
    assert any(e.startswith("jit__lambda")
               for e in _entries(DEFAULT_DIR) - before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
