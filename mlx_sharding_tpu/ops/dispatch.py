"""Which path a dispatcher of ops/ chose, counted where it chooses.

A jitted program asks its dispatcher while it is traced, so one call is one
count per traced program: a compiled program's steps add nothing and the
count costs the device nothing. ``utils/observability.py`` shows each
counter on ``/metrics`` as ``mst_<op>_dispatch_total{path}``.
"""

from __future__ import annotations

import threading


class DispatchCounter:
    def __init__(self, *paths: str):
        self._counts = dict.fromkeys(paths, 0)
        self._lock = threading.Lock()

    def count(self, path: str) -> None:
        with self._lock:
            self._counts[path] += 1

    def counts(self) -> dict[str, int]:
        """Lifetime count of calls dispatched to each path."""
        with self._lock:
            return dict(self._counts)
