"""The page pool's host-side accounting (``mlx_sharding_tpu/page_pool.py``):
the invariants a whole batcher used to be needed to reach. No engine, no
device: plain Python over a pool of a few pages."""

import ast
import random
from pathlib import Path

import numpy as np
import pytest

from mlx_sharding_tpu import page_pool
from mlx_sharding_tpu.analysis import runtime as mst_runtime
from mlx_sharding_tpu.page_pool import PagePool

pytestmark = pytest.mark.quick


def check(pool: PagePool):
    """Every page is on the free list or has a count >= 1, never both."""
    free = pool._free
    assert len(set(free)) == len(free)
    assert set(free).isdisjoint(pool._refs)
    assert set(free) | set(pool._refs) == set(range(pool.total))
    assert all(r >= 1 for r in pool._refs.values())
    assert pool.free + pool.in_use == pool.total
    assert pool.in_use == len(pool._refs)
    for pages in pool._of.values():
        assert all(pool.refs(p) >= 1 for p in pages)


def test_the_module_imports_no_jax():
    tree = ast.parse(Path(page_pool.__file__).read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] in ("jax", "jaxlib")]


def test_take_is_all_or_nothing_when_the_list_is_short():
    pool = PagePool(4, 6)
    held = pool.take(3)
    before = list(pool._free)
    with pytest.raises(RuntimeError, match="pool exhausted: need 2 pages, 1"):
        pool.take(2)
    assert pool._free == before and pool.free == 1
    assert {p: pool.refs(p) for p in range(4)} == {**dict.fromkeys(held, 1),
                                                   before[0]: 0}
    assert pool.take(0) == []
    assert pool.take(1) == before
    check(pool)


def test_pages_come_off_in_the_order_a_fresh_batcher_gave_them():
    """Page 0 first: every slot gets the page it got before the pool was an
    object — and gets it again after ``reset``."""
    pool = PagePool(5, 5)
    assert pool._free == list(range(4, -1, -1))
    assert pool.take(2) == [0, 1]
    pool.bind(0, pool.take(1))
    pool.share([0])
    pool.row([0, 1])
    pool.reset()
    assert pool._free == list(range(4, -1, -1))
    assert pool.free == pool.total == 5 and pool.in_use == 0
    assert pool.pages(0) == [] and pool.refs(0) == 0
    assert pool.high_water == 3  # a mark of the pool's life, not of a run
    assert pool.take(5) == [0, 1, 2, 3, 4]


def test_unref_frees_at_one_holder_and_not_at_two():
    pool = PagePool(3, 3)
    (p,) = pool.take(1)
    pool.share([p])
    assert pool.refs(p) == 2
    pool.unref([p])
    assert pool.refs(p) == 1 and pool.free == 2
    pool.unref([p])
    assert pool.refs(p) == 0 and pool.free == 3
    assert pool._free[-1] == p  # and is the next page taken
    check(pool)


def test_a_page_nobody_holds_is_neither_shared_nor_given_back_twice():
    pool = PagePool(3, 3)
    (p,) = pool.take(1)
    pool.unref([p])
    with pytest.raises(KeyError):
        pool.unref([p])
    with pytest.raises(KeyError):
        pool.share([p])
    check(pool)


def test_a_slots_mapping_is_bound_read_extended_and_released():
    pool = PagePool(6, 4)
    assert pool.pages(2) == []
    pool.bind(2, pool.take(2))
    assert pool.pages(2) == [0, 1]
    pool.extend(2, pool.take(1))
    assert pool.pages(2) == [0, 1, 2]
    pool.share([0])  # an index entry's claim outlives the slot
    pool.release(2)
    assert pool.pages(2) == [] and pool.in_use == 1 and pool.refs(0) == 1
    pool.release(2)  # a slot that maps nothing gives nothing back
    assert pool.in_use == 1
    check(pool)


def test_a_row_is_padded_with_the_scratch_page_and_the_mark_never_falls():
    pool = PagePool(8, 5)
    pages = pool.take(3)
    row = pool.row(pages)
    assert row.dtype == np.int32 and row.tolist() == [0, 1, 2, 8, 8]
    assert pool.row([]).tolist() == [8] * 5
    assert pool.high_water == 3
    more = pool.take(4)
    assert pool.high_water == 3  # the mark is read when a row is made
    pool.row(pages + more[:2])
    assert pool.high_water == 7
    pool.unref(pages + more)
    pool.row([])
    assert pool.high_water == 7 and pool.in_use == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_random_walk_keeps_every_page_free_or_held_and_never_both(seed):
    rng = random.Random(seed)
    pool = PagePool(24, 8)
    loose: list[list[int]] = []  # claims no slot's mapping holds
    high = 0
    for _ in range(3000):
        op = rng.choice(("take", "bind", "extend", "share", "unref",
                         "release", "row"))
        slot = rng.randrange(6)
        if op == "take":
            n = rng.randrange(0, 5)
            if n > pool.free:
                free = list(pool._free)
                with pytest.raises(RuntimeError):
                    pool.take(n)
                assert pool._free == free
            else:
                loose.append(pool.take(n))
        elif op == "bind" and not pool.pages(slot):
            pool.bind(slot, pool.take(min(rng.randrange(1, 4), pool.free)))
        elif op == "extend" and pool.pages(slot) and pool.free:
            pool.extend(slot, pool.take(1))
        elif op == "share" and pool.pages(slot):
            loose.append(rng.sample(pool.pages(slot), 1))
            pool.share(loose[-1])
        elif op == "unref" and loose:
            pool.unref(loose.pop(rng.randrange(len(loose))))
        elif op == "release":
            pool.release(slot)
        elif op == "row":
            row = pool.row(pool.pages(slot)[:8])
            assert (row[len(pool.pages(slot)):] == pool.total).all()
        check(pool)
        assert pool.high_water >= high
        high = pool.high_water
    for slot in range(6):
        pool.release(slot)
    for pages in loose:
        pool.unref(pages)
    assert pool.free == pool.total and not pool._refs
    assert 0 < high <= pool.total


def test_the_leak_ledger_balances():
    ledger = mst_runtime.instrument_resources()
    try:
        pool, other = PagePool(6, 6), PagePool(6, 6)
        kept = other.take(2)
        pool.bind(0, pool.take(3))
        shared = pool.pages(0)[:1]
        pool.share(shared)
        assert len(ledger.live()) == 5
        pool.release(0)
        # the shared page is still held once: out of the free list, live
        assert [k for k in ledger.live() if k[1][0] == id(pool)] == [
            ("scheduler.page", (id(pool), shared[0]))]
        pool.unref(shared)
        assert len(ledger.live()) == 2
        pool.take(4)
        pool.reset()  # whoever held them: the pool's pages are forgotten,
        assert sorted(k[1] for k in ledger.live()) == [  # nobody else's
            (id(other), p) for p in kept]
        other.forget()
        assert not ledger.live()
        assert ledger.counts()["scheduler.page"] == (9, 9)
    finally:
        mst_runtime.deinstrument_resources()
