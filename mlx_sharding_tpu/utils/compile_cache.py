"""Where JAX's persistent compilation cache lives.

One rule for every entry point (the server, the CLI generator, the shard
tool, ``chip_smoke.py``, the benchmark's launcher): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here sets another path; otherwise the
cache goes to one fixed, git-ignored directory inside the checkout. The
path is part of the cache key, so it must never move between processes —
no ``tempfile``, no pid, no timestamp. Processes started one after another
from the same checkout then find each other's compiled programs.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the fixed fallback, ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns the directory.
    Call first thing in ``main()``, before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
