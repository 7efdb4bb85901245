"""guarded-by fixture: every annotated write is under its lock."""

import threading


class Good:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0  # guarded-by: _lock
        self._items = []  # guarded-by: _lock

    def bump(self, n):
        with self._lock:
            self._count += n
            self._items.append(n)

    def reset_waived(self):
        # single-writer teardown path, other threads already joined
        self._count = 0  # apexlint: unguarded(teardown, threads joined)

    def reinit(self):
        with self._lock:
            self._items = []
            self._items[0:0] = [1]

    def _hold(self, who):  # apexlint: holds(_lock)
        return self._lock

    def bump_through_holder(self, n):
        with self._hold("bump"):
            self._count += n
