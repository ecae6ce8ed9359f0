"""Durable KV backend on sqlite3 (stdlib) with two-phase commit.

Plays the role of bcos-storage's RocksDBStorage.cpp (574 lines: asyncPrepare
stages a WriteBatch, asyncCommit writes it atomically, asyncRollback drops
it). Sqlite gives us the same contract — single-writer atomic batches with
WAL journaling — without a non-baked-in rocksdb dependency; the storage seam
(interfaces.TransactionalStorage) is what the rest of the stack codes
against, so swapping in a native engine later is a constructor change.
"""

from __future__ import annotations

import sqlite3
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


class SQLiteStorage(TransactionalStorage):
    def __init__(self, path: str = ":memory:") -> None:
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.RLock()
        with self._lock:
            self._conn.execute("PRAGMA journal_mode=WAL")
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS kv ("
                " tbl TEXT NOT NULL, k BLOB NOT NULL, v BLOB NOT NULL,"
                " PRIMARY KEY (tbl, k))"
            )
            # prepared-but-uncommitted 2PC slots are DURABLE (TiKV persists
            # prewrite locks): a participant that crashes between prepare
            # and commit must still roll FORWARD after restart when the
            # coordinator's primary commit witness exists
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS pending_2pc ("
                " num INTEGER NOT NULL, tbl TEXT NOT NULL, k BLOB NOT NULL,"
                " v BLOB NOT NULL, PRIMARY KEY (num, tbl, k))"
            )
            self._conn.commit()

    def get_row(self, table: str, key: bytes) -> Entry | None:
        with self._lock:
            row = self._conn.execute(
                "SELECT v FROM kv WHERE tbl=? AND k=?", (table, bytes(key))
            ).fetchone()
        if row is None:
            return None
        e = Entry.decode(row[0])
        return None if e.deleted else e

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        with self._lock:
            self._conn.execute(
                "INSERT OR REPLACE INTO kv (tbl, k, v) VALUES (?, ?, ?)",
                (table, bytes(key), entry.encode()),
            )
            self._conn.commit()

    def set_rows(self, table: str, items) -> None:
        with self._lock:
            self._conn.executemany(
                "INSERT OR REPLACE INTO kv (tbl, k, v) VALUES (?, ?, ?)",
                [(table, bytes(k), e.encode()) for k, e in items],
            )
            self._conn.commit()

    def get_primary_keys(self, table: str) -> list[bytes]:
        with self._lock:
            rows = self._conn.execute(
                "SELECT k, v FROM kv WHERE tbl=? ORDER BY k", (table,)
            ).fetchall()
        return [bytes(k) for k, v in rows if not Entry.decode(v).deleted]

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        with self._lock:
            rows = self._conn.execute("SELECT tbl, k, v FROM kv").fetchall()
        for t, k, v in rows:
            yield t, bytes(k), Entry.decode(v)

    # -- 2PC ------------------------------------------------------------

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> dict[str, int]:
        """Durably stage writes for `number`. Per-key merge, not slot
        replacement (multi-participant 2PC: several Max executors prepare
        the same block; see MemoryStorage.prepare). The rows are only
        encoded here, so they are read where they lie (staged_rows)."""
        lent, mode = staged_rows(writes)
        with self._lock:
            rows = [(params.number, t, k, e.encode()) for (t, k), e in lent.items()]
            self._conn.executemany(
                "INSERT OR REPLACE INTO pending_2pc (num, tbl, k, v)"
                " VALUES (?, ?, ?, ?)",
                rows,
            )
            self._conn.commit()
        return count_prepared(mode, len(rows))

    def commit(self, params: TwoPCParams) -> None:
        with self._lock:
            # apply + clear the slot in ONE sqlite transaction: a crash
            # mid-commit leaves either the staged slot (re-commit resolves)
            # or the applied state, never half of each
            self._conn.execute(
                "INSERT OR REPLACE INTO kv (tbl, k, v)"
                " SELECT tbl, k, v FROM pending_2pc WHERE num=?",
                (params.number,),
            )
            self._conn.execute(
                "DELETE FROM pending_2pc WHERE num=?", (params.number,)
            )
            self._conn.commit()

    def rollback(self, params: TwoPCParams) -> None:
        with self._lock:
            self._conn.execute(
                "DELETE FROM pending_2pc WHERE num=?", (params.number,)
            )
            self._conn.commit()

    def pending_numbers(self) -> list[int]:
        """Block numbers with a durable prepared-but-unresolved slot
        (the recovery scan's input — TiKV's leftover prewrite locks)."""
        with self._lock:
            rows = self._conn.execute(
                "SELECT DISTINCT num FROM pending_2pc ORDER BY num"
            ).fetchall()
        return [int(r[0]) for r in rows]

    def close(self) -> None:
        with self._lock:
            self._conn.close()
