"""Random-order tracking dict for anonymized object downloads.

Semantics of the reference's RandomTrackingDict
(src/randomtrackingdict.py:13-132): dict-like storage whose
``random_keys(count)`` returns up to ``count`` randomly-chosen keys,
excluding keys already handed out within the last ``pending_timeout``
seconds, and none at all while ``max_pending`` or more are still out —
so download order never betrays receive order while requests aren't
duplicated.  Deleting a key (object arrived) takes it out of the
window.  As in the reference the window gates a hand-out and does not
size it: below ``max_pending`` a poll hands out its whole ``count``.
Sized by the window, a poll a second fetched ten objects a second from
a peer, which a sender on four chips outruns (PERF.md section 6, PR 37).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Generic, Iterator, TypeVar

K = TypeVar("K")
V = TypeVar("V")


class RandomTrackingDict(Generic[K, V]):
    #: keys out at which no more are handed out (reference maxPending = 10)
    max_pending = 10
    #: seconds before a handed-out key becomes eligible again
    pending_timeout = 60

    def __init__(self) -> None:
        self._dict: dict[K, V] = {}
        self._pending: dict[K, float] = {}  # key -> expiry time
        self._lock = threading.RLock()

    def __len__(self) -> int:
        return len(self._dict)

    def __contains__(self, key: K) -> bool:
        return key in self._dict

    def __getitem__(self, key: K) -> V:
        return self._dict[key]

    def __setitem__(self, key: K, value: V) -> None:
        with self._lock:
            self._dict[key] = value

    def __delitem__(self, key: K) -> None:
        with self._lock:
            del self._dict[key]
            self._pending.pop(key, None)

    def pop(self, key: K, *default):
        with self._lock:
            self._pending.pop(key, None)
            return self._dict.pop(key, *default)

    def keys(self) -> list[K]:
        with self._lock:
            return list(self._dict)

    def __iter__(self) -> Iterator[K]:
        return iter(self.keys())

    def random_keys(self, count: int = 1) -> list[K]:
        """Up to ``count`` random keys outside the pending window; none
        while the window holds ``max_pending`` keys or more."""
        with self._lock:
            now = time.time()
            for k in [k for k, exp in self._pending.items() if exp <= now]:
                del self._pending[k]
            if len(self._pending) >= self.max_pending:
                return []
            eligible = [k for k in self._dict if k not in self._pending]
            if not eligible:
                return []
            chosen = random.sample(eligible, min(count, len(eligible)))
            expiry = now + self.pending_timeout
            for k in chosen:
                self._pending[k] = expiry
            return chosen
