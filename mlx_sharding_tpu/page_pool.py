"""Host-side accounting of a paged KV pool.

One object knows the pool's data format — a free list, a claim count per
page, the pages mapped to each slot, the device table's row with its
scratch-page padding, the high-water mark and the leak ledger's shadow
(``scheduler.page``). ``ContinuousBatcher`` asks it and decides only policy:
how many pages a request needs, which index-only pages to evict and when.
Writing a row to the device stays with the scheduler (that is a program).

A page is on the free list or has a count >= 1, never both. A count is the
number of holders: each slot that maps the page, plus one for a prefix-index
or prefix-store entry that registered it.
"""

from __future__ import annotations

import numpy as np

from mlx_sharding_tpu.analysis import runtime as mst_runtime


class PagePool:
    def __init__(self, total: int, row_width: int):
        self.total = total  # pages; index ``total`` is the scratch page
        self.row_width = row_width  # entries of a slot's device-table row
        self.high_water = 0
        self.reset()

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    def _note(self, pages, *, acquired: bool):
        """Leak-ledger shadow of a batch of takes or returns. One global
        read when the ledger is off — the per-page loop only runs under
        instrument_resources()."""
        led = mst_runtime._RESOURCES
        if led is None:
            return
        note = led.note_acquire if acquired else led.note_release
        for p in pages:
            note("scheduler.page", (id(self), p))

    def take(self, n: int) -> list[int]:
        """``n`` fresh pages, each held once by the caller — all or none."""
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: need {n} pages, "
                f"{len(self._free)} free"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self._note(pages, acquired=True)
        return pages

    def share(self, pages):
        """One more holder of each of ``pages`` (all held already)."""
        for p in pages:
            self._refs[p] += 1

    def unref(self, pages):
        """One holder fewer; a page nobody holds goes back on the list."""
        for p in pages:
            r = self._refs[p] - 1
            if r:
                self._refs[p] = r
            else:
                del self._refs[p]
                self._free.append(p)
                self._note((p,), acquired=False)

    def refs(self, p: int) -> int:
        return self._refs.get(p, 0)

    def bind(self, slot: int, pages: list[int]):
        self._of[slot] = pages

    def pages(self, slot: int) -> list[int]:
        return self._of.get(slot, [])

    def extend(self, slot: int, fresh: list[int]):
        self._of[slot].extend(fresh)

    def release(self, slot: int):
        """Unmap ``slot`` and drop its claim on every page it mapped."""
        self.unref(self._of.pop(slot, ()))

    def row(self, pages: list[int]) -> np.ndarray:
        """A mapping as the device table's row, and the high-water mark
        bumped. Unmapped tail entries stay at the scratch page: overshoot
        writes past the mapping land there harmlessly."""
        row = np.full((self.row_width,), self.total, np.int32)
        row[: len(pages)] = pages
        self.high_water = max(self.high_water, self.in_use)
        return row

    def reset(self):
        """Every page free and unmapped (page 0 is taken first), whoever
        held it; the ledger forgets this pool's pages."""
        self._free = list(range(self.total - 1, -1, -1))
        self._refs: dict[int, int] = {}  # page -> holders (absent: free)
        self._of: dict[int, list[int]] = {}  # slot -> mapped pages
        self.forget()

    def forget(self):
        """Retire this pool's pages from the leak ledger: the pool dies
        with its engine, index-resident prefix pages included."""
        oid = id(self)
        mst_runtime.note_reset("scheduler.page", lambda k: k[0] == oid)
