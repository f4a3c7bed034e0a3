"""utils/compile_cache.py: where the persistent XLA compile cache goes.

Each case runs in a fresh interpreter (the cache is process-global JAX
state, and the suite keeps it off — tests/conftest.py)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE = """
import os, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from shadow_tpu.utils import compile_cache
compile_cache.CHECKOUT_CACHE = {fallback!r}
print(compile_cache.setup_compile_cache())
jax.jit(lambda x: jnp.cumsum(x) * 3)(jnp.arange(64)).block_until_ready()
"""


def _run(tmp_path, env_dir):
    fallback = str(tmp_path / "checkout-cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)   # conftest's off switch
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c",
                        COMPILE.format(repo=REPO, fallback=fallback)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1], fallback


def test_env_var_wins_and_programs_land_there(tmp_path):
    chosen = str(tmp_path / "from-env")
    used, fallback = _run(tmp_path, chosen)
    assert used == chosen
    assert os.listdir(chosen), "no compiled program landed in the env dir"
    assert not os.path.exists(fallback)


def test_unset_env_var_uses_the_fixed_checkout_dir(tmp_path):
    used, fallback = _run(tmp_path, None)
    assert used == fallback
    assert os.listdir(fallback)


def test_child_env_points_children_at_the_same_dir(monkeypatch):
    from shadow_tpu.fuzz.runner import child_env
    from shadow_tpu.utils.compile_cache import CHECKOUT_CACHE

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert child_env()["JAX_COMPILATION_CACHE_DIR"] == CHECKOUT_CACHE
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    env = child_env()
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"
    # the child keeps the parent's platform choice: never re-pinned
    assert env.get("JAX_PLATFORMS") == os.environ.get("JAX_PLATFORMS")
