"""In-memory transactional backend (tests + fakers; FakeKVStorage analog)."""

from __future__ import annotations

import threading
from typing import Iterator

from .entry import Entry
from .interfaces import (
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
    count_prepared,
    staged_rows,
)


class MemoryStorage(TransactionalStorage):
    def __init__(self) -> None:
        self._data: dict[tuple[str, bytes], Entry] = {}
        self._pending: dict[int, dict[tuple[str, bytes], Entry]] = {}
        self._lock = threading.RLock()

    def get_row(self, table: str, key: bytes) -> Entry | None:
        with self._lock:
            e = self._data.get((table, bytes(key)))
            return None if e is None or e.deleted else e.copy()

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        with self._lock:
            self._data[(table, bytes(key))] = entry.copy()

    def get_primary_keys(self, table: str) -> list[bytes]:
        with self._lock:
            return sorted(
                k for (t, k), e in self._data.items() if t == table and not e.deleted
            )

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        with self._lock:
            items = list(self._data.items())
        for (t, k), e in items:
            yield t, k, e.copy()

    # -- 2PC ------------------------------------------------------------

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> dict[str, int]:
        """Stage writes for `number`. PER-KEY MERGE, not slot replacement:
        a Max-form block is prepared by several executor participants, each
        staging its own (disjoint) dirty set into the same number — TiKV's
        multi-participant prewrite semantics. Re-preparing the same key
        (block re-execution after a term switch) overwrites per key.

        The slot KEEPS the Entry objects it is given (borrow_rows' contract:
        nobody mutates a stored Entry, and this store copies in and out
        like the overlays do), so a row changes hands and is not copied."""
        rows, mode = staged_rows(writes)
        with self._lock:
            self._pending.setdefault(params.number, {}).update(rows)
        return count_prepared(mode, len(rows))

    def commit(self, params: TwoPCParams) -> None:
        with self._lock:
            self._data.update(self._pending.pop(params.number, {}))

    def rollback(self, params: TwoPCParams) -> None:
        with self._lock:
            self._pending.pop(params.number, None)

    def pending_numbers(self) -> list[int]:
        with self._lock:
            return sorted(self._pending)
