"""The one rule for JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no path in code.  Otherwise the cache is ``.jax_cache`` at the
root of the checkout (git-ignored): a FIXED path, because the path is part
of the cache key — a directory built from a temp name, a pid or a
timestamp never hits.

Every entry point that compiles the big programs (``chip_smoke.py``,
``bench.py``, the examples, ``tests/conftest.py``) calls
:func:`configure` once, before its first jit.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-tree default — used only when the environment names no cache
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Apply the rule; returns the directory the cache lives in."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
