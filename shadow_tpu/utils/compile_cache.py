"""Where JAX keeps its persistent compilation cache — one rule for every
entry point (cli, tools/mkscenario, bench.py, chip_smoke.py, and the
fuzz children's env).

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
sets nothing.  Unset: the cache goes to ``.jax_cache/`` at the root of
the checkout (git-ignored).  The path is part of a cache entry's key, so
a fixed path is what lets a second run on the same machine hit.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    return os.environ.get(ENV_VAR) or CHECKOUT_CACHE


def setup_compile_cache(env: Optional[Dict[str, str]] = None) -> str:
    """Place the cache and return its directory.  With ``env`` (a child
    process's environment) the child is pointed at the same directory
    through the variable; without it this process is configured."""
    path = cache_dir()
    if env is not None:
        env.setdefault(ENV_VAR, path)
        return path
    if ENV_VAR not in os.environ:
        os.makedirs(path, exist_ok=True)
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
