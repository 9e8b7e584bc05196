"""Where JAX's persistent compilation cache lives.

One rule for every entry point (the server, the CLI generator, the shard
tool, ``chip_smoke.py``, the benchmark's launcher): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here sets another path; otherwise the
cache goes to one fixed, git-ignored directory inside the checkout. The
path is part of the cache key, so it must never move between processes —
no ``tempfile``, no pid, no timestamp. Processes started one after another
from the same checkout then find each other's compiled programs.

Under a size bound (``JAX_COMPILATION_CACHE_MAX_SIZE``) JAX keeps a
``<key>-atime`` file beside every ``<key>-cache`` and, to choose what to
evict, reads them ALL before every write: one entry whose ``-atime`` is gone
(a directory pruned by size from outside loses files one at a time) fails
every later write with ``FileNotFoundError``, and every program that is new
compiles again in every process — seen on the chip tool's machine at PR 57:
100 s of set-up a run for 68. :func:`mend_compile_cache` writes the missing
stamps back, and :func:`enable_compile_cache` calls it.
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
    if not placed:
        import jax

        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    where = placed or str(DEFAULT_CACHE_DIR)
    mend_compile_cache(where)
    return where


def mend_compile_cache(where: str) -> int:
    """Give every ``<key>-cache`` of a size-bounded cache directory the
    ``<key>-atime`` stamp JAX expects beside it (its own modification time,
    8 bytes little-endian, as JAX writes them); returns how many were
    missing. Nothing to do without a bound: JAX keeps no stamps then."""
    if os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE", "-1") == "-1":
        return 0
    mended = 0
    for entry in Path(where).glob("*-cache"):
        stamp = entry.with_name(entry.name[: -len("-cache")] + "-atime")
        try:
            if not stamp.exists():
                stamp.write_bytes(entry.stat().st_mtime_ns.to_bytes(8, "little"))
                mended += 1
        except OSError:  # another process took the entry away, or a read-only cache
            pass
    return mended
