"""KeyPageStorage — page-packed key layout over a KV backend.

Reference: bcos-table/src/KeyPageStorage.cpp (1,051 lines): instead of one
backend row per (table, key), rows are packed into pages holding up to
`page_size` sorted keys; a per-table meta row tracks page split points.
Point reads fetch one page instead of one row (amortizing backend seeks),
range scans fetch contiguous pages, and small values share pages — the
reference's biggest storage win for state tables with many tiny entries.

Layout in the inner storage:
    table "__kp_meta__",  key <table>           -> sorted list of page-start keys
    table "__kp_page__",  key <table>\\x00<start> -> serialized page (sorted items)

Pages split at `page_size` entries.  2PC: `prepare` repacks the row-level
write-set into page-level writes and forwards to the inner backend, so the
atomic-commit contract is preserved.
"""

from __future__ import annotations

import bisect
import threading
from typing import Iterator

from ..codec.flat import FlatReader, FlatWriter
from .entry import Entry, EntryStatus
from .interfaces import (
    RowsView,
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
    staged_rows,
)

META_TABLE = "__kp_meta__"
PAGE_TABLE = "__kp_page__"


def _encode_page(items: list[tuple[bytes, Entry]]) -> bytes:
    w = FlatWriter()
    w.seq(items, lambda w2, kv: (w2.bytes_(kv[0]), w2.bytes_(kv[1].encode())))
    return w.out()


def _decode_page(buf: bytes) -> list[tuple[bytes, Entry]]:
    r = FlatReader(buf)
    out = r.seq(lambda r2: (r2.bytes_(), Entry.decode(r2.bytes_())))
    r.done()
    return out


def _encode_meta(starts: list[bytes]) -> bytes:
    w = FlatWriter()
    w.seq(starts, lambda w2, s: w2.bytes_(s))
    return w.out()


def _decode_meta(buf: bytes) -> list[bytes]:
    r = FlatReader(buf)
    out = r.seq(lambda r2: r2.bytes_())
    r.done()
    return out


class KeyPageStorage(TransactionalStorage):
    # decoded-page cache bound: ~page_size entries per page, so 1024 pages
    # ≈ 256k cached rows — cleared wholesale when exceeded (reads repopulate)
    _CACHE_MAX_PAGES = 1024

    def __init__(self, inner: TransactionalStorage, page_size: int = 256):
        self.inner = inner
        self.page_size = page_size
        self._lock = threading.RLock()
        # decoded caches (the reference's KeyPageStorage likewise keeps
        # decoded PageData in memory; re-decoding a 256-entry page per row
        # read is what the page layout exists to avoid)
        self._page_cache: dict[tuple[str, bytes], list[tuple[bytes, Entry]]] = {}
        self._meta_cache: dict[str, list[bytes]] = {}

    # -- page plumbing --------------------------------------------------------

    def _meta_locked(self, table: str) -> list[bytes]:
        cached = self._meta_cache.get(table)
        if cached is not None:
            return list(cached)
        e = self.inner.get_row(META_TABLE, table.encode())
        starts = _decode_meta(e.get()) if e is not None else []
        if len(self._meta_cache) >= self._CACHE_MAX_PAGES:
            self._meta_cache.clear()
        self._meta_cache[table] = list(starts)
        return starts

    def _save_meta_locked(self, table: str, starts: list[bytes]) -> None:
        self._meta_cache[table] = list(starts)
        self.inner.set_row(META_TABLE, table.encode(), Entry({"value": _encode_meta(starts)}))

    @staticmethod
    def _page_key(table: str, start: bytes) -> bytes:
        return table.encode() + b"\x00" + start

    def _load_page_locked(self, table: str, start: bytes) -> list[tuple[bytes, Entry]]:
        pk = (table, start)
        cached = self._page_cache.get(pk)
        if cached is not None:
            return list(cached)  # shallow copy: callers mutate the list
        e = self.inner.get_row(PAGE_TABLE, self._page_key(table, start))
        items = _decode_page(e.get()) if e is not None and not e.deleted else []
        if len(self._page_cache) >= self._CACHE_MAX_PAGES:
            self._page_cache.clear()
        self._page_cache[pk] = list(items)
        return items

    def _save_page_locked(self, table: str, start: bytes, items: list[tuple[bytes, Entry]]) -> None:
        if len(self._page_cache) >= self._CACHE_MAX_PAGES:
            self._page_cache.clear()
        self._page_cache[(table, start)] = list(items)
        self.inner.set_row(
            PAGE_TABLE, self._page_key(table, start), Entry({"value": _encode_page(items)})
        )

    def _page_for(self, starts: list[bytes], key: bytes) -> int | None:
        """Index of the page whose range contains `key` (None if no pages)."""
        if not starts:
            return None
        i = bisect.bisect_right(starts, key) - 1
        return max(i, 0)

    def _delete_page_row_locked(self, table: str, start: bytes) -> None:
        self._page_cache.pop((table, start), None)
        self.inner.set_row(
            PAGE_TABLE,
            self._page_key(table, start),
            Entry(status=EntryStatus.DELETED),
        )

    def _chunk_page(
        self,
        start: bytes,
        merged: list[tuple[bytes, Entry]],
        starts: list[bytes],
    ) -> tuple[list[tuple[bytes, list[tuple[bytes, Entry]] | None]], bool]:
        """Split the merged (sorted) content of the page registered at
        ``start`` into page_size chunks and assign each its registration
        key. Returns (ops, meta_dirty): ops is [(cstart, items)] with
        items=None meaning "tombstone the page row at cstart".

        Invariant maintained: every registered start ≤ its page's min key.
        Only the table-head page can accumulate keys below its registered
        start (reads clamp to page 0) — splitting such a page without
        rekeying would register later chunks at starts that sort BELOW the
        head page's own key, sending reads of the head page's rows to the
        wrong page (rows silently unreadable). The head page is therefore
        rekeyed to its true min key before chunk registration."""
        ops: list[tuple[bytes, list[tuple[bytes, Entry]] | None]] = []
        dirty = False
        head = start
        if merged and merged[0][0] < start:
            ops.append((start, None))  # tombstone the old page row
            starts.remove(start)
            head = merged[0][0]
            bisect.insort(starts, head)
            dirty = True
        chunks = [
            merged[i : i + self.page_size]
            for i in range(0, len(merged), self.page_size)
        ] or [[]]
        for chunk in chunks:
            cstart = head if chunk is chunks[0] else chunk[0][0]
            ops.append((cstart, chunk))
            if cstart not in starts:
                bisect.insort(starts, cstart)
                dirty = True
        return ops, dirty

    # -- StorageInterface -----------------------------------------------------

    def get_row(self, table: str, key: bytes) -> Entry | None:
        key = bytes(key)
        with self._lock:
            starts = self._meta_locked(table)
            idx = self._page_for(starts, key)
            if idx is None:
                return None
            for k, e in self._load_page_locked(table, starts[idx]):
                if k == key:
                    return None if e.deleted else e.copy()
        return None

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.set_rows(table, [(key, entry)])

    def set_rows(self, table: str, items) -> None:
        """Bulk write with one decode/encode per TOUCHED page (the same
        page-grouping the 2PC prepare path uses) — a per-row path would
        re-codec a whole page per row, ~1000x slower for bulk loads."""
        with self._lock:
            starts = self._meta_locked(table)
            meta_dirty = False
            # per-page pending writes as a dict (last write wins), merged
            # into the decoded page ONCE at write-out — per-item list
            # surgery on a deferred-split page would be quadratic
            staged: dict[bytes, dict[bytes, Entry]] = {}
            for key, entry in items:
                key = bytes(key)
                if not starts:
                    starts.append(key)
                    meta_dirty = True
                start = starts[self._page_for(starts, key)]
                staged.setdefault(start, {})[key] = entry.copy()
            for start, pending in staged.items():
                merged = {k: e for k, e in self._load_page_locked(table, start)}
                merged.update(pending)
                ops, dirty = self._chunk_page(start, sorted(merged.items()), starts)
                meta_dirty |= dirty
                for cstart, chunk in ops:
                    if chunk is None:
                        self._delete_page_row_locked(table, cstart)
                    else:
                        self._save_page_locked(table, cstart, chunk)
            if meta_dirty:
                self._save_meta_locked(table, starts)

    def get_primary_keys(self, table: str) -> list[bytes]:
        out: list[bytes] = []
        with self._lock:
            for start in self._meta_locked(table):
                out.extend(
                    k for k, e in self._load_page_locked(table, start) if not e.deleted
                )
        return out

    def traverse(self) -> Iterator[tuple[str, bytes, Entry]]:
        traverse = getattr(self.inner, "traverse", None)
        if traverse is None:
            return
        for t, k, e in traverse():
            if t == PAGE_TABLE:
                if e.deleted:
                    continue  # tombstoned page row (rekeyed head page)
                table, _, _start = k.partition(b"\x00")
                for key, entry in _decode_page(e.get()):
                    yield table.decode(), key, entry
            elif t != META_TABLE:
                yield t, k, e

    # -- 2PC: repack the row write-set into page writes ------------------------

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> dict[str, int] | None:
        with self._lock:
            staged: dict[tuple[str, bytes], dict[bytes, Entry]] = {}
            metas: dict[str, list[bytes]] = {}
            for (table, key), entry in staged_rows(writes)[0].items():
                if table not in metas:  # setdefault would re-copy per row
                    metas[table] = self._meta_locked(table)
                starts = metas[table]
                idx = self._page_for(starts, key)
                if idx is None:
                    starts.append(key)
                    idx = 0
                start = starts[idx]
                # pending writes as a dict (last wins), merged into the
                # decoded page once — per-item list surgery is quadratic
                # on a 2000-row block write-set. The row is only read: the
                # merged page is encoded below and nothing of it is kept
                # (commit drops the decoded caches), so no copy
                staged.setdefault((table, start), {})[key] = entry
            rows: list[tuple[str, bytes, Entry]] = []
            for (table, start), pending in staged.items():
                starts = metas[table]
                merged = {k: e for k, e in self._load_page_locked(table, start)}
                merged.update(pending)
                ops, _dirty = self._chunk_page(start, sorted(merged.items()), starts)
                for cstart, chunk in ops:
                    if chunk is None:
                        rows.append(
                            (
                                PAGE_TABLE,
                                self._page_key(table, cstart),
                                Entry(status=EntryStatus.DELETED),
                            )
                        )
                    else:
                        rows.append(
                            (
                                PAGE_TABLE,
                                self._page_key(table, cstart),
                                Entry({"value": _encode_page(chunk)}),
                            )
                        )
            for table, starts in metas.items():
                rows.append(
                    (
                        META_TABLE,
                        table.encode(),
                        Entry({"value": _encode_meta(starts)}),
                    )
                )
            return self.inner.prepare(params, RowsView(rows))

    def commit(self, params: TwoPCParams) -> None:
        # the 2PC write-set lands through inner.prepare/commit, bypassing
        # _save_page — drop decoded caches so reads see the committed pages.
        # The lock spans inner.commit so no reader can serve a stale cached
        # page in the window after the data is durable but before the clear.
        with self._lock:
            self.inner.commit(params)
            self._page_cache.clear()
            self._meta_cache.clear()

    def rollback(self, params: TwoPCParams) -> None:
        self.inner.rollback(params)

    def pending_numbers(self) -> list[int]:
        return self.inner.pending_numbers()


    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()
