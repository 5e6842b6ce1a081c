"""Where the persistent XLA compilation cache lives.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set this
module sets nothing and the cache lands there alone.  Otherwise the cache
goes to ``.jax_cache/`` at the checkout root: a fixed path, because a
later run finds an entry only under the directory that wrote it.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/api_server.py``, ``launch/train.py``) call
``enable_compile_cache()`` before their first compile.  Library modules
and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
