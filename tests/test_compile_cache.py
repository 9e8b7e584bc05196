"""``utils/compile_cache.py``: where the persistent compile cache lives, and
the stamps a size-bounded one needs beside its entries."""

import jax
import pytest

from mlx_sharding_tpu.utils import compile_cache


@pytest.fixture
def bounded(tmp_path, monkeypatch):
    """A cache directory under a size bound, as JAX leaves it: every
    ``-cache`` with its ``-atime`` — but for one entry whose stamp was pruned
    away from outside."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_MAX_SIZE", str(1 << 20))
    for key in ("jit_a-1", "jit_b-2", "jit__rows-3"):
        (tmp_path / f"{key}-cache").write_bytes(b"x" * 100)
        (tmp_path / f"{key}-atime").write_bytes((1).to_bytes(8, "little"))
    (tmp_path / "jit__rows-3-atime").unlink()
    return tmp_path


def test_jax_s_bounded_cache_refuses_every_write_beside_an_entry_without_its_stamp(bounded):
    """The fault this module mends, in JAX's own class: one missing stamp and
    ``put`` raises for every new key."""
    from jax._src.lru_cache import LRUCache

    with pytest.raises(FileNotFoundError, match="jit__rows-3-atime"):
        LRUCache(str(bounded), max_size=1 << 20).put("jit_new-4", b"y" * 10)


def test_enable_mends_the_stamps_and_writes_go_through_again(bounded):
    from jax._src.lru_cache import LRUCache

    assert compile_cache.enable_compile_cache() == str(bounded)
    stamp = bounded / "jit__rows-3-atime"
    assert int.from_bytes(stamp.read_bytes(), "little") == (
        bounded / "jit__rows-3-cache").stat().st_mtime_ns
    assert (bounded / "jit_a-1-atime").read_bytes() == (1).to_bytes(8, "little")  # left alone
    cache = LRUCache(str(bounded), max_size=1 << 20)
    cache.put("jit_new-4", b"y" * 10)
    assert cache.get("jit_new-4") == b"y" * 10
    assert compile_cache.mend_compile_cache(str(bounded)) == 0


@pytest.mark.parametrize("bound", [None, "-1"])
def test_without_a_bound_no_stamp_is_written(tmp_path, monkeypatch, bound):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    if bound is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_MAX_SIZE", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_MAX_SIZE", bound)
    (tmp_path / "jit_a-1-cache").write_bytes(b"x")
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert sorted(p.name for p in tmp_path.iterdir()) == ["jit_a-1-cache"]


def test_the_fallback_is_one_fixed_directory_of_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_MAX_SIZE", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(compile_cache.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(compile_cache.DEFAULT_CACHE_DIR)
        assert compile_cache.DEFAULT_CACHE_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
