"""Storage layers (overlay, 2PC backends) + ledger schema."""

import threading

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig, Ledger
from fisco_bcos_tpu.ops.merkle import MerkleTree
from fisco_bcos_tpu.protocol import Block, BlockHeader, ParentInfo, TransactionReceipt
from fisco_bcos_tpu.protocol.transaction import TransactionFactory
from fisco_bcos_tpu.storage import (
    Entry,
    MemoryStorage,
    SQLiteStorage,
    StateStorage,
)
from fisco_bcos_tpu.storage.interfaces import TwoPCParams
from fisco_bcos_tpu.storage.table import create_table, open_table

SUITE = ecdsa_suite()


def test_entry_roundtrip():
    e = Entry({"value": b"abc", "other": b"\x00\xff"})
    assert Entry.decode(e.encode()) == e
    e2 = Entry().set(b"just-value")
    assert e2.get() == b"just-value"


def test_state_storage_overlay_and_root():
    base = MemoryStorage()
    base.set_row("t", b"k1", Entry().set(b"base1"))
    s1 = StateStorage(base)
    assert s1.get_row("t", b"k1").get() == b"base1"
    s1.set_row("t", b"k2", Entry().set(b"local2"))
    s1.remove_row("t", b"k1")
    assert s1.get_row("t", b"k1") is None
    assert s1.get_primary_keys("t") == [b"k2"]

    # root is order-independent and matches a hand XOR
    root = s1.hash(SUITE)
    s2 = StateStorage(base)
    s2.remove_row("t", b"k1")
    s2.set_row("t", b"k2", Entry().set(b"local2"))
    assert s2.hash(SUITE) == root
    assert root != b"\x00" * 32

    # merge pushes writes down
    s1.merge_into_prev()
    assert base.get_row("t", b"k1") is None
    assert base.get_row("t", b"k2").get() == b"local2"
    assert s1.dirty_count() == 0


def test_a_blocks_rows_take_no_lock_but_the_overlays_own(monkeypatch):
    """A 1,000-row block through the overlay (write, read back, traverse and
    encode for the root's preimages) acquires no lock of the process but
    the StateStorage's own: nothing process-wide sits under a row."""
    from fisco_bcos_tpu.analysis import lockorder

    if not lockorder._installed:
        pytest.skip("lockorder factory not installed (FISCO_LOCKORDER=0)")
    base = MemoryStorage()
    state = StateStorage(base)
    assert isinstance(state._lock, lockorder.InstrumentedRLock)
    rows = [(b"k%04d" % i, Entry().set(b"v%04d" % i)) for i in range(1000)]
    me = threading.get_ident()
    taken: list[str] = []
    real = lockorder.RECORDER.on_acquire

    def on_acquire(site):
        if threading.get_ident() == me:
            taken.append(site)
        real(site)

    monkeypatch.setattr(lockorder.RECORDER, "on_acquire", on_acquire)
    for k, e in rows:
        state.set_row("t", k, e)
    for k, e in rows:
        assert state.get_row("t", k) == e
    n = sum(len(e.encode()) for _t, _k, e in state.traverse())
    monkeypatch.undo()
    assert n > 0 and state.dirty_count() == len(rows)
    own = state._lock._site
    assert own.startswith("fisco_bcos_tpu/storage/state_storage.py:")
    assert taken, "the recorder saw the overlay's own lock"
    assert set(taken) == {own}, sorted(set(taken) - {own})


def test_two_pc_backends(tmp_path):
    for store in (MemoryStorage(), SQLiteStorage(str(tmp_path / "kv.db"))):
        writes = StateStorage()
        writes.set_row("t", b"a", Entry().set(b"1"))
        writes.set_row("t", b"b", Entry().set(b"2"))
        p = TwoPCParams(number=5)
        store.prepare(p, writes)
        assert store.get_row("t", b"a") is None  # not visible before commit
        store.commit(p)
        assert store.get_row("t", b"a").get() == b"1"
        # rollback discards
        w2 = StateStorage()
        w2.set_row("t", b"a", Entry().set(b"overwritten"))
        p2 = TwoPCParams(number=6)
        store.prepare(p2, w2)
        store.rollback(p2)
        assert store.get_row("t", b"a").get() == b"1"


def test_sqlite_persistence(tmp_path):
    path = str(tmp_path / "kv.db")
    s = SQLiteStorage(path)
    s.set_row("t", b"k", Entry().set(b"v"))
    s.close()
    s2 = SQLiteStorage(path)
    assert s2.get_row("t", b"k").get() == b"v"
    s2.close()


def test_tables():
    store = MemoryStorage()
    t = create_table(store, "u_accounts", "key", ("balance",))
    t.set_row(b"alice", Entry().set("balance", b"100"))
    t2 = open_table(store, "u_accounts")
    assert t2.info.value_fields == ("balance",)
    assert t2.get_row(b"alice").get("balance") == b"100"
    assert open_table(store, "missing") is None


def _ledger():
    store = MemoryStorage()
    ledger = Ledger(store, SUITE)
    nodes = [ConsensusNode(node_id=bytes([i]) * 64, weight=1) for i in range(4)]
    ledger.build_genesis(GenesisConfig(consensus_nodes=nodes))
    return ledger, store


def test_genesis_and_config():
    ledger, _ = _ledger()
    assert ledger.block_number() == 0
    cfg = ledger.ledger_config()
    assert cfg.tx_count_limit == 1000 and cfg.leader_period == 1
    assert len(cfg.consensus_nodes) == 4
    g = ledger.header_by_number(0)
    assert ledger.block_hash_by_number(0) == g.hash(SUITE)
    # idempotent
    ledger.build_genesis(GenesisConfig())
    assert len(ledger.consensus_nodes()) == 4


def test_block_commit_and_proofs():
    ledger, store = _ledger()
    fac = TransactionFactory(SUITE)
    kp = SUITE.signature_impl.generate_keypair(secret=42)
    txs = [
        fac.create_signed(kp, chain_id="c", group_id="g", block_limit=100, nonce=str(i))
        for i in range(5)
    ]
    parent = ledger.header_by_number(0)
    blk = Block(
        header=BlockHeader(
            number=1,
            parent_info=[ParentInfo(0, parent.hash(SUITE))],
            timestamp=123,
        ),
        transactions=txs,
    )
    blk.receipts = [
        TransactionReceipt(gas_used=21000, block_number=1, status=0) for _ in txs
    ]
    blk.header.txs_root = blk.calculate_txs_root(SUITE)
    blk.header.receipts_root = blk.calculate_receipts_root(SUITE)

    overlay = StateStorage(store)
    ledger.prewrite_block(blk, overlay)
    store.prepare(TwoPCParams(number=1), overlay)
    store.commit(TwoPCParams(number=1))

    assert ledger.block_number() == 1
    assert ledger.total_transaction_count() == 5
    th = txs[2].hash(SUITE)
    assert ledger.tx_by_hash(th).nonce == "2"
    assert ledger.receipt_by_hash(th).gas_used == 21000
    got = ledger.block_by_number(1, with_txs=True, with_receipts=True)
    assert len(got.transactions) == 5 and len(got.receipts) == 5
    assert ledger.nonces_by_number(1) == [str(i) for i in range(5)]

    proof, idx, n = ledger.tx_proof(th)
    assert MerkleTree.verify_proof(
        th, idx, n, proof, blk.header.txs_root, hasher="keccak256"
    )
    rproof, ridx, rn = ledger.receipt_proof(th)
    rc_hash = blk.receipts[2].hash(SUITE)
    assert MerkleTree.verify_proof(
        rc_hash, ridx, rn, rproof, blk.header.receipts_root, hasher="keccak256"
    )
