"""LRU read-cache over a transactional backend.

Reference: bcos-table/src/CacheStorageFactory.cpp + the LRU cache layer the
reference stacks over RocksDB (StateStorageFactory with cache enabled).
Write-through: set_row updates backend then cache; 2PC commits invalidate
the written keys (the staged write-set goes to the backend, so cached
pre-images must drop).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Iterator

from .entry import Entry
from .interfaces import (
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
    staged_rows,
)


class CacheStorage(TransactionalStorage):
    def __init__(self, inner: TransactionalStorage, capacity: int = 64 * 1024):
        self.inner = inner
        self.capacity = capacity
        self._cache: OrderedDict[tuple[str, bytes], Entry | None] = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self._gen = 0  # bumped on every commit (miss-fill TOCTOU guard)
        # staged write-sets by 2PC batch, to invalidate on commit
        self._staged_keys: dict[int, list[tuple[str, bytes]]] = {}

    # -- reads ----------------------------------------------------------------

    def get_row(self, table: str, key: bytes) -> Entry | None:
        k = (table, bytes(key))
        with self._lock:
            if k in self._cache:
                self.hits += 1
                self._cache.move_to_end(k)
                e = self._cache[k]
                if e is None:
                    return None
                return e.copy()
            self.misses += 1
            gen = self._gen
        e = self.inner.get_row(table, key)
        with self._lock:
            # TOCTOU guard: a commit() invalidation between the backend read
            # and this fill means `e` may be a pre-commit value — caching it
            # would serve stale state indefinitely. The generation counter
            # bumps on every commit; only same-generation reads may fill.
            if gen == self._gen:
                self._cache[k] = None if e is None else e.copy()
                while len(self._cache) > self.capacity:
                    self._cache.popitem(last=False)
        return e

    def get_primary_keys(self, table: str) -> list[bytes]:
        return self.inner.get_primary_keys(table)

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        traverse = getattr(self.inner, "traverse", None)
        if traverse is None:
            return iter(())
        return traverse()

    # -- writes (write-through) ------------------------------------------------

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.inner.set_row(table, key, entry)
        self._fill(table, key, entry)

    def set_rows(self, table: str, items) -> None:
        self.inner.set_rows(table, items)  # one backend transaction
        for key, entry in items:
            self._fill(table, key, entry)

    def _fill(self, table: str, key: bytes, entry: Entry) -> None:
        k = (table, bytes(key))
        with self._lock:
            self._cache[k] = None if entry.deleted else entry.copy()
            self._cache.move_to_end(k)
            while len(self._cache) > self.capacity:
                self._cache.popitem(last=False)

    # -- 2PC -------------------------------------------------------------------

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> dict[str, int] | None:
        staged = list(staged_rows(writes)[0])
        with self._lock:
            # per number, every participant's keys (a Max block is prepared
            # by several executors): commit must drop them all
            self._staged_keys.setdefault(params.number, []).extend(staged)
        return self.inner.prepare(params, writes)

    def commit(self, params: TwoPCParams) -> None:
        self.inner.commit(params)
        with self._lock:
            self._gen += 1
            for k in self._staged_keys.pop(params.number, []):
                self._cache.pop(k, None)

    def rollback(self, params: TwoPCParams) -> None:
        self.inner.rollback(params)
        with self._lock:
            self._staged_keys.pop(params.number, None)

    def pending_numbers(self) -> list[int]:
        return self.inner.pending_numbers()

    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()
