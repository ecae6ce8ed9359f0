"""Storage interfaces.

Reference: bcos-framework/storage/StorageInterface.h — read/write interface
plus the transactional (2PC) extension implemented by the durable backends
(RocksDBStorage.cpp asyncPrepare/asyncCommit/asyncRollback) and driven by the
scheduler's commit (TwoPCParams). Python methods are synchronous; async
orchestration happens at the node layer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Iterator

from ..utils.metrics import REGISTRY
from .entry import Entry

Row = tuple[str, bytes, Entry]
RowMap = dict[tuple[str, bytes], Entry]


@dataclass
class TwoPCParams:
    """bcos-framework/storage/StorageInterface.h TwoPCParams analog."""

    number: int = 0
    primary_key: str = ""
    timestamp: int = 0


class StorageInterface:
    def get_row(self, table: str, key: bytes) -> Entry | None:
        raise NotImplementedError

    def get_rows(self, table: str, keys: Iterable[bytes]) -> list[Entry | None]:
        return [self.get_row(table, k) for k in keys]

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        raise NotImplementedError

    def set_rows(self, table: str, items: list[tuple[bytes, Entry]]) -> None:
        """Bulk write; durable backends commit all rows in one transaction
        (hot paths like pool persistence write thousands of rows per block)."""
        for key, entry in items:
            self.set_row(table, key, entry)

    def get_primary_keys(self, table: str) -> list[bytes]:
        raise NotImplementedError


class TraversableStorage(StorageInterface):
    def traverse(self) -> Iterator[Row]:
        """Yield (table, key, entry) for every locally-held row. The entries
        are the consumer's own: a store yields copies."""
        raise NotImplementedError

    def borrow_rows(self) -> RowMap:
        """``traverse()`` for the 2PC: a snapshot ``{(table, key): entry}`` of
        the same rows, and where the layer can lend them (StateStorage, the
        executor's chain of overlays, RowsView) its OWN Entry objects and
        key tuples, with no copy. The caller may read them and may keep
        them; it must never mutate an entry. Sound because a stored Entry
        is never mutated in place and never handed out (reads copy out,
        writes copy in), so two stores of one process may hold the same
        object. The default copies."""
        return {(t, bytes(k)): e for t, k, e in self.traverse()}


class RowsView(TraversableStorage):
    """A write-set that is a list of rows built for one ``prepare`` (decoded
    off the wire, repacked into pages, split by shard): nobody else holds
    them, so it lends them."""

    def __init__(self, rows: list[Row]):
        self._rows = rows

    def traverse(self) -> Iterator[Row]:
        return iter(self._rows)

    def borrow_rows(self) -> RowMap:
        return {(t, bytes(k)): e for t, k, e in self._rows}


def staged_rows(writes) -> tuple[RowMap, str]:
    """What a backend's ``prepare`` reads its write-set through: the rows
    (per key the last one wins), and how they came. "moved": the layer lent
    its objects (``borrow_rows()``); "copied": it has only ``traverse()``."""
    lend = getattr(type(writes), "borrow_rows", None)
    if lend is None or lend is TraversableStorage.borrow_rows:
        return TraversableStorage.borrow_rows(writes), "copied"
    return writes.borrow_rows(), "moved"


def count_prepared(mode: str, n: int) -> dict[str, int]:
    """The backend that staged ``n`` rows says so, once a ``prepare`` call;
    what it returns is what that ``prepare`` returns (the scheduler's
    ``prepare`` stage mark carries it)."""
    REGISTRY.counter_add(
        f'fisco_storage_prepare_rows_total{{mode="{mode}"}}',
        n,
        help="rows a backend's 2PC prepare staged: moved (the write-set lent "
        "its Entry objects and none was copied on the way into the slot) or "
        "copied (the write-set has only traverse(), one copy a row)",
    )
    return {"moved": 0, "copied": 0, mode: n}


class TransactionalStorage(StorageInterface):
    """Durable backend with two-phase commit."""

    def prepare(
        self, params: TwoPCParams, writes: TraversableStorage
    ) -> dict[str, int] | None:
        """Stage ``writes`` for ``params.number``, read through
        ``staged_rows(writes)``. Returns ``count_prepared``'s tally where
        this backend staged the rows itself, a wrapper what its inner
        backend returned, None where nobody says (a remote store)."""
        raise NotImplementedError

    def commit(self, params: TwoPCParams) -> None:
        raise NotImplementedError

    def rollback(self, params: TwoPCParams) -> None:
        raise NotImplementedError

    def pending_numbers(self) -> list[int]:
        """Block numbers with a prepared-but-unresolved 2PC slot.

        Part of the interface because the distributed recovery plane
        (DistributedStorage.recover_in_flight) DEPENDS on every backend
        answering truthfully — a backend silently reporting [] would make
        recovery skip its stuck slots forever."""
        raise NotImplementedError
