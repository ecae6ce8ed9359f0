"""A block's rows change hands through the 2PC (PR 37): the prewrite hands its
Entry objects to the overlay (``StateStorage.adopt_row``), the prepare leg
borrows what the overlays hold (``borrow_rows``) and ``MemoryStorage`` keeps
those objects. Nothing may show: the stores stay independent of each other
whatever backend stages the rows, and every row's bytes stay what they were."""

import logging

import pytest

from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor.executor import _StagedWrites
from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig, Ledger
from fisco_bcos_tpu.protocol import Block, BlockHeader, ParentInfo, TransactionReceipt
from fisco_bcos_tpu.protocol.transaction import TransactionFactory
from fisco_bcos_tpu.storage import Entry, MemoryStorage, SQLiteStorage, StateStorage
from fisco_bcos_tpu.storage.cache import CacheStorage
from fisco_bcos_tpu.storage.interfaces import (
    RowsView,
    StorageInterface,
    TraversableStorage,
    TwoPCParams,
    staged_rows,
)
from fisco_bcos_tpu.storage.keypage import KeyPageStorage
from fisco_bcos_tpu.utils.metrics import REGISTRY

SUITE = ecdsa_suite()
STORES = {
    "memory": MemoryStorage,
    "sqlite": lambda: SQLiteStorage(":memory:"),
    "keypage": lambda: KeyPageStorage(MemoryStorage(), page_size=4),
    "cache": lambda: CacheStorage(MemoryStorage()),
}


@pytest.fixture(params=list(STORES))
def backend(request):
    return STORES[request.param]()


def two_pc(store, number, writes):
    store.prepare(TwoPCParams(number=number), writes)
    store.commit(TwoPCParams(number=number))


def row_bytes(store, table, key):
    e = store.get_row(table, key)
    return None if e is None else e.encode()


def test_a_committed_row_and_the_overlays_row_are_independent(backend):
    overlay = StateStorage(backend)
    overlay.set_row("t", b"a", Entry().set(b"a1"))
    overlay.set_row("t", b"b", Entry({"value": b"b1", "other": b"x"}))
    overlay.adopt_row("t", b"c", Entry().set(b"c1"))
    two_pc(backend, 1, overlay)
    # the overlay lives on (it parents the next block's speculation): what
    # it is told afterwards stays its own
    overlay.set_row("t", b"a", Entry().set(b"a2"))
    overlay.remove_row("t", b"b")
    overlay.adopt_row("t", b"c", Entry().set(b"c2"))
    assert [backend.get_row("t", k).get() for k in (b"a", b"b", b"c")] == [b"a1", b"b1", b"c1"]
    assert backend.get_row("t", b"b").get("other") == b"x"
    assert overlay.get_row("t", b"a").get() == b"a2" and overlay.get_row("t", b"b") is None
    # and a write to the backend does not reach into the overlay
    backend.set_row("t", b"a", Entry().set(b"a3"))
    assert overlay.get_row("t", b"a").get() == b"a2"


def test_an_entry_read_from_either_side_is_the_readers_own(backend):
    overlay = StateStorage(backend)
    overlay.set_row("t", b"k", Entry().set(b"v"))
    overlay.adopt_row("t", b"j", Entry().set(b"w"))
    two_pc(backend, 1, overlay)
    for side in (overlay, backend):
        for key in (b"k", b"j"):
            got = side.get_row("t", key)
            got.set(b"scribbled").set("extra", b"field")
    assert [row_bytes(backend, "t", k) for k in (b"k", b"j")] == [
        Entry().set(b"v").encode(), Entry().set(b"w").encode()]
    assert [row_bytes(overlay, "t", k) for k in (b"k", b"j")] == [
        Entry().set(b"v").encode(), Entry().set(b"w").encode()]
    # the copying traversal still hands out copies
    for _t, _k, e in overlay.traverse():
        e.set(b"scribbled")
    assert row_bytes(overlay, "t", b"k") == Entry().set(b"v").encode()


def test_rollback_then_prepare_of_the_reexecuted_block_stages_the_new_rows(backend):
    first = StateStorage(backend)
    first.set_row("t", b"kept", Entry().set(b"old"))
    first.set_row("t", b"only_in_first", Entry().set(b"gone"))
    backend.prepare(TwoPCParams(number=3), first)
    assert backend.pending_numbers() == [3]
    backend.rollback(TwoPCParams(number=3))
    assert backend.pending_numbers() == [] and backend.get_row("t", b"kept") is None
    again = StateStorage(backend)  # the block executed again after a term switch
    again.set_row("t", b"kept", Entry().set(b"new"))
    two_pc(backend, 3, again)
    assert backend.get_row("t", b"kept").get() == b"new"
    assert backend.get_row("t", b"only_in_first") is None
    # a re-prepare WITHOUT a rollback overwrites per key
    a, b = StateStorage(backend), StateStorage(backend)
    a.set_row("t", b"x", Entry().set(b"x1"))
    a.set_row("u", b"y", Entry().set(b"y1"))
    b.set_row("t", b"x", Entry().set(b"x2"))
    backend.prepare(TwoPCParams(number=4), a)
    two_pc(backend, 4, b)
    assert backend.get_row("t", b"x").get() == b"x2" and backend.get_row("u", b"y").get() == b"y1"


def participants(backend, tables):
    parts = [StateStorage(backend), StateStorage(backend)]
    for i in range(12):
        parts[i % 2].set_row(tables[i % 2], b"k%02d" % i, Entry().set(b"v%d" % i))
    for part in parts:
        backend.prepare(TwoPCParams(number=7), part)
    assert backend.pending_numbers() == [7]
    assert backend.get_row(tables[0], b"k00") is None  # a miss a cache may remember
    backend.commit(TwoPCParams(number=7))
    return [row_bytes(backend, tables[i % 2], b"k%02d" % i) for i in range(12)]


def test_two_participants_of_one_number_merge_per_key(backend):
    """A Max block's executors each prepare their own contracts' tables."""
    assert participants(backend, ("t_a", "t_b")) == [
        Entry().set(b"v%d" % i).encode() for i in range(12)]


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, reason="KeyPage stages whole pages built from the committed page and "
        "ONE participant's rows: two participants whose rows share a page overwrite each "
        "other's page row (as before PR 37; PERF.md, section 7)"))
    if name == "keypage" else name
    for name in STORES
])
def test_two_participants_sharing_one_table_merge_per_key(name):
    assert participants(STORES[name](), ("t", "t")) == [
        Entry().set(b"v%d" % i).encode() for i in range(12)]


def test_pipelined_blocks_over_a_prepared_slot_end_at_a_plain_replay(backend):
    """Block N + 1 executes on overlay N while N's slot is prepared and not
    committed; ten blocks on, the backend holds what a dict replay of the
    rows' bytes holds, row for row."""
    replay: dict[tuple[str, bytes], bytes | None] = {}
    keys = [b"acct%02d" % i for i in range(9)]

    def execute(number, overlay):
        ledger_rows = StateStorage()
        for j in range(4):
            key = keys[(number * 3 + j) % len(keys)]
            below = overlay.get_row("s", key)  # through N, then the backend
            count = int(below.get()) if below is not None else 0
            assert (Entry().set(b"%d" % count).encode() if count else None) == replay.get(("s", key))
            if (number + j) % 5 == 4 and count:
                overlay.remove_row("s", key)
                replay[("s", key)] = None
            else:
                overlay.set_row("s", key, Entry().set(b"%d" % (count + number)))
                replay[("s", key)] = Entry().set(b"%d" % (count + number)).encode()
        head = Entry().set(b"h%d" % number)
        ledger_rows.adopt_row("chain", b"%d" % number, head)
        ledger_rows.adopt_row("chain", b"current", Entry().set(b"%d" % number))
        replay[("chain", b"%d" % number)] = head.encode()
        replay[("chain", b"current")] = Entry().set(b"%d" % number).encode()
        return _StagedWrites(overlay, ledger_rows)

    prev = backend
    in_flight = None
    for number in range(1, 11):
        overlay = StateStorage(prev)
        writes = execute(number, overlay)  # reads N's values under N's open slot
        if in_flight is not None:
            backend.commit(TwoPCParams(number=in_flight))
        backend.prepare(TwoPCParams(number=number), writes)
        assert backend.pending_numbers() == [number]
        in_flight, prev = number, overlay
    backend.commit(TwoPCParams(number=in_flight))
    assert backend.pending_numbers() == []
    assert {tk: row_bytes(backend, *tk) for tk in replay} == replay
    assert backend.get_primary_keys("s") == sorted(
        k for (t, k), v in replay.items() if t == "s" and v is not None)


# -- the prewrite, byte for byte ----------------------------------------------


class CopyingStore(StorageInterface):
    """A plain StorageInterface as a service passes for ``out``: copy in,
    copy out, no ``adopt_row``."""

    def __init__(self):
        self.rows: dict[tuple[str, bytes], Entry] = {}

    def get_row(self, table, key):
        e = self.rows.get((table, bytes(key)))
        return None if e is None else e.copy()

    def set_row(self, table, key, entry):
        self.rows[(table, bytes(key))] = entry.copy()


def _ledger():
    store = MemoryStorage()
    ledger = Ledger(store, SUITE)
    ledger.build_genesis(GenesisConfig(consensus_nodes=[ConsensusNode(b"\x01" * 64)]))
    return ledger


def _block(ledger, statuses):
    fac = TransactionFactory(SUITE)
    kp = SUITE.signature_impl.generate_keypair(secret=37)
    txs = [
        fac.create_signed(kp, chain_id="c", group_id="g", block_limit=100, nonce=f"n{i}")
        for i in range(len(statuses))
    ]
    parent = ledger.header_by_number(0)
    blk = Block(
        header=BlockHeader(number=1, parent_info=[ParentInfo(0, parent.hash(SUITE))],
                           timestamp=37),
        transactions=txs,
    )
    blk.receipts = [
        TransactionReceipt(gas_used=21000 + i, block_number=1, status=s)
        for i, s in enumerate(statuses)
    ]
    return blk


@pytest.mark.parametrize("statuses", [[0, 16, 0, 0, 12, 0], []], ids=["failed_receipts", "empty"])
def test_prewrite_into_an_adopting_overlay_and_into_a_copying_store_agree(statuses):
    ledger = _ledger()
    blk = _block(ledger, statuses)
    adopting, copying = StateStorage(), CopyingStore()
    ledger.prewrite_block(blk, adopting)
    ledger.prewrite_block(blk, copying)
    adopted = {(t, k, e.encode()) for (t, k), e in adopting.borrow_rows().items()}
    assert adopted == {(t, k, e.encode()) for (t, k), e in copying.rows.items()}
    assert adopted == {(t, k, e.encode()) for t, k, e in adopting.traverse()}
    tables = sorted(t for t, _k, _b in adopted)
    assert tables.count("s_hash_2_tx") == tables.count("s_hash_2_receipt") == len(statuses)
    failed = adopting.get_row("s_current_state", b"total_failed_transaction_count")
    assert (failed.get() if failed else None) == (b"2" if statuses else None)
    # the totals' read-back goes through the overlay: a second block staged
    # on the same overlay sees the first one's increment
    ledger.prewrite_block(blk, adopting)
    assert adopting.get_row("s_current_state", b"total_transaction_count").get() == \
        b"%d" % (2 * len(statuses))


# -- the counter and the stage mark -------------------------------------------


class TraverseOnly(TraversableStorage):
    def __init__(self, rows):
        self._rows = rows

    def traverse(self):
        for t, k, e in self._rows:
            yield t, k, e.copy()


def prepared_rows():
    rows = REGISTRY.counters_matching("fisco_storage_prepare_rows_total")
    return tuple(rows.get(f'fisco_storage_prepare_rows_total{{mode="{m}"}}', 0.0)
                 for m in ("moved", "copied"))


def test_one_prepare_of_3000_rows_counts_them_once_by_how_they_came():
    state, ledger_rows = StateStorage(), StateStorage()
    for i in range(1000):
        state.set_row("s", b"k%04d" % i, Entry().set(b"v"))
    for i in range(2000):
        ledger_rows.adopt_row("l", b"k%04d" % i, Entry().set(b"w"))
    store = MemoryStorage()
    moved0, copied0 = prepared_rows()
    assert store.prepare(TwoPCParams(number=1), _StagedWrites(state, ledger_rows)) == {
        "moved": 3000, "copied": 0}
    assert prepared_rows() == (moved0 + 3000, copied0)
    # no copy on the way: the slot holds the overlays' own objects
    lent = _StagedWrites(state, ledger_rows).borrow_rows()
    assert all(store._pending[1][tk] is e for tk, e in lent.items()) and len(lent) == 3000
    store.commit(TwoPCParams(number=1))
    assert all(store._data[tk] is e for tk, e in lent.items())

    rows = [(t, k, e) for (t, k), e in lent.items()]
    assert MemoryStorage().prepare(TwoPCParams(number=1), TraverseOnly(rows)) == {
        "moved": 0, "copied": 3000}
    assert prepared_rows() == (moved0 + 3000, copied0 + 3000)
    # sqlite only encodes what it reads: the same count, by the same rule
    assert SQLiteStorage(":memory:").prepare(TwoPCParams(number=1), RowsView(rows)) == {
        "moved": 3000, "copied": 0}
    assert prepared_rows() == (moved0 + 6000, copied0 + 3000)


def test_staged_rows_lends_only_where_the_layer_says_it_can():
    overlay = StateStorage()
    overlay.set_row("t", b"k", Entry().set(b"v"))
    rows, mode = staged_rows(overlay)
    assert mode == "moved" and rows[("t", b"k")] is overlay._data[("t", b"k")]
    assert rows is not overlay._data  # a snapshot: the overlay goes on taking writes
    mine = Entry().set(b"v")
    rows, mode = staged_rows(TraverseOnly([("t", bytearray(b"k"), mine)]))
    assert mode == "copied" and rows == {("t", b"k"): mine} and rows[("t", b"k")] is not mine
    # the interface's default is the copying traversal
    assert TraverseOnly([("t", b"k", mine)]).borrow_rows()[("t", b"k")] is not mine
    assert RowsView([("t", b"k", mine)]).borrow_rows()[("t", b"k")] is mine

    class Duck:  # no TraversableStorage at all, and a `rows` of its own
        rows = [("t", b"k", Entry().set(b"v"))]

        def traverse(self):
            yield from self.rows

    assert staged_rows(Duck())[1] == "copied"
    # a chain lends layer by layer and copies from a layer that cannot
    below = MemoryStorage()
    below.set_row("t", b"j", Entry().set(b"w"))
    chained = _StagedWrites(overlay, below).borrow_rows()
    assert chained == {("t", b"k"): Entry().set(b"v"), ("t", b"j"): Entry().set(b"w")}
    assert chained[("t", b"k")] is overlay._data[("t", b"k")]
    assert chained[("t", b"j")] is not below._data[("t", b"j")]
    # later layers win per key, as in every backend's per-key merge
    above = StateStorage()
    above.set_row("t", b"k", Entry().set(b"later"))
    assert _StagedWrites(overlay, above).borrow_rows()[("t", b"k")].get() == b"later"


def test_the_commit_spans_prepare_mark_carries_the_two_counts():
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from test_executor import Env

    lines = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = lambda record: lines.append(record.getMessage())
    logging.getLogger("scheduler").addHandler(handler)
    try:
        env = Env()
        moved0, copied0 = prepared_rows()
        env.run_block([
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", f"u{i}", 10)
            for i in range(3)
        ])
    finally:
        logging.getLogger("scheduler").removeHandler(handler)
    (line,) = [ln for ln in lines if ln.startswith("[CommitBlock.1.") and "|prepare|" in ln]
    moved, copied = prepared_rows()
    assert copied == copied0 and moved > moved0
    # three state rows, three transactions, three receipts and the block's own rows
    assert moved - moved0 >= 9
    assert line.endswith(f"|moved={int(moved - moved0)}|copied=0")
