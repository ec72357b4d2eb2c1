"""Where every entry point keeps JAX's persistent compilation cache.

One rule for the CLI, ``chip_smoke.py``, ``bench.py``/``soak_tpu.py``/
``tune_tpu.py`` and ``tests/conftest.py``: a ``JAX_COMPILATION_CACHE_DIR``
set from outside wins and no other directory is set in code; otherwise the
cache lives at the fixed ``<checkout>/.jax_cache``. The path is part of
the cache key, so it never carries a pid, a timestamp or a temp dir.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the compile cache; returns the directory in use.

    Never imports JAX itself (the drill CLI parents stay JAX-free): an
    unset variable is exported so a later ``import jax`` — here or in a
    child process — reads it, and a JAX that is already imported gets the
    same value through its config.
    """
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.environ[CACHE_DIR_ENV] = str(DEFAULT_CACHE_DIR)
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
