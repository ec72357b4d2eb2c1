"""Where every entry point keeps JAX's persistent compilation cache.

One rule for the CLI, ``chip_smoke.py``, ``benchmarks/run.py`` and
``tests/conftest.py``: a ``JAX_COMPILATION_CACHE_DIR``
set from outside wins and no other directory is set in code; otherwise the
cache lives at the fixed ``<checkout>/.jax_cache``. The path is part of
the cache key, so it never carries a pid, a timestamp or a temp dir.

The key also covers each program's metadata (``op_name``, source lines).
JAX's default strips it from the key, and a program loaded from the cache
then carries the metadata of whichever source compiled it first: on the
v5e the parent of PR 23, which has no named scope, showed PR 23's scopes
in its trace because PR 23 had filled the cache, and the other order
shows none (my chip run, PR 23). The device program is read by those
names (``obs/scopes.py``), so they have to be this source's. The price is
a compile wherever a file on a jitted call's stack moved or changed its
line numbers. ``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY`` set from
outside wins here too (``tests/conftest.py`` keeps JAX's default).
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
METADATA_IN_KEY_ENV = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
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
    in_key = os.environ.setdefault(METADATA_IN_KEY_ENV, "1")
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          in_key.lower() in ("1", "true"))
    return path
