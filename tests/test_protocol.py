"""CryptoSuite + protocol objects: roundtrips, hashing, signing, roots."""

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
from fisco_bcos_tpu.ops.merkle import MerkleTree
from fisco_bcos_tpu.protocol import (
    Block,
    BlockHeader,
    LogEntry,
    ParentInfo,
    SignatureTuple,
    Transaction,
    TransactionFactory,
    TransactionReceipt,
)
from fisco_bcos_tpu.protocol.transaction import hash_transactions_batch
from receipt_ref import flat_receipt

SUITES = [ecdsa_suite(), sm_suite()]


@pytest.mark.parametrize("suite", SUITES, ids=["ecdsa", "sm"])
def test_suite_sign_verify_recover(suite):
    kp = suite.signature_impl.generate_keypair(secret=0x1234567)
    h = suite.hash(b"hello consensus")
    sig = suite.signature_impl.sign(kp, h)
    assert suite.signature_impl.verify(kp.pub, h, sig)
    pub = suite.signature_impl.recover(h, sig)
    assert pub == kp.pub
    assert suite.calculate_address(pub) == suite.calculate_address(kp.pub)
    # recover binds signer to message: a different message either hard-fails
    # (SM2 — carried pubkey no longer verifies) or yields a different key
    try:
        other = suite.signature_impl.recover(suite.hash(b"other message"), sig)
        assert other != kp.pub
    except ValueError:
        pass


@pytest.mark.parametrize("suite", SUITES, ids=["ecdsa", "sm"])
def test_suite_batch_matches_single(suite):
    kps = [suite.signature_impl.generate_keypair(secret=1000 + i) for i in range(4)]
    hashes = [suite.hash(b"msg %d" % i) for i in range(4)]
    sigs = [suite.signature_impl.sign(kp, h) for kp, h in zip(kps, hashes)]
    hs = np.frombuffer(b"".join(hashes), dtype=np.uint8).reshape(-1, 32)
    pubs = np.frombuffer(b"".join(k.pub for k in kps), dtype=np.uint8).reshape(-1, 64)
    ss = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(len(sigs), -1)
    ok = suite.signature_impl.batch_verify(hs, pubs, ss)
    assert ok.all()
    rec, ok2 = suite.signature_impl.batch_recover(hs, ss)
    assert ok2.all()
    for i, kp in enumerate(kps):
        assert bytes(rec[i]) == kp.pub


def test_transaction_roundtrip_and_verify():
    suite = ecdsa_suite()
    fac = TransactionFactory(suite)
    kp = suite.signature_impl.generate_keypair(secret=0xABCDEF)
    tx = fac.create_signed(
        kp,
        chain_id="chain0",
        group_id="group0",
        block_limit=600,
        nonce="n-123",
        to=b"\x11" * 20,
        input=b"transfer(alice,bob,5)",
        abi="",
    )
    buf = tx.encode()
    tx2 = fac.decode(buf)
    assert tx2.encode() == buf
    assert tx2.hash(suite) == tx.hash(suite)
    assert tx2.verify(suite)
    assert tx2.sender == tx.sender == suite.calculate_address(kp.pub)
    # tampered payload must change the hash and recover a different sender
    tx3 = fac.decode(buf)
    tx3.input = b"transfer(alice,eve,500)"
    tx3.invalidate_caches()
    assert tx3.hash(suite) != tx.hash(suite)
    assert (not tx3.verify(suite)) or tx3.sender != tx.sender


def test_batch_tx_hashing_matches_single():
    suite = ecdsa_suite()
    fac = TransactionFactory(suite)
    txs = [
        fac.create(
            chain_id="c", group_id="g", block_limit=10, nonce=str(i), input=b"x" * i
        )
        for i in range(5)
    ]
    expected = [suite.hash(t.encode_data()) for t in txs]
    got = hash_transactions_batch(txs, suite)
    assert got == expected


def test_receipt_and_header_roundtrip():
    rc = TransactionReceipt(
        version=1,
        gas_used=21000,
        contract_address=b"\x22" * 20,
        status=0,
        output=b"\x01",
        log_entries=[LogEntry(b"\x22" * 20, [b"\xaa" * 32], b"payload")],
        block_number=7,
    )
    assert TransactionReceipt.decode(rc.encode()).encode() == rc.encode()

    # decode seeds the wire-form cache; a mutation WITHOUT invalidation would
    # silently re-serialize the stale pre-mutation bytes into the receipts
    # root — invalidate_caches is the one correct idiom (mirrors Transaction)
    rc2 = TransactionReceipt.decode(rc.encode())
    rc2.block_number = 8
    rc2.invalidate_caches()
    assert TransactionReceipt.decode(rc2.encode()).block_number == 8
    assert rc2.encode() != rc.encode()

    suite = ecdsa_suite()
    h = BlockHeader(
        version=3,
        parent_info=[ParentInfo(6, b"\x07" * 32)],
        txs_root=b"\x01" * 32,
        receipts_root=b"\x02" * 32,
        state_root=b"\x03" * 32,
        number=7,
        gas_used=12345,
        timestamp=1700000000000,
        sealer=2,
        sealer_list=[b"\x40" * 64, b"\x41" * 64],
        consensus_weights=[1, 1],
        signature_list=[SignatureTuple(0, b"\x55" * 65)],
    )
    h2 = BlockHeader.decode(h.encode())
    assert h2.encode() == h.encode()
    # hash excludes the signature list (QC signs the hash)
    h3 = BlockHeader.decode(h.encode())
    h3.signature_list = []
    assert h3.hash(suite) == h.hash(suite)


def test_block_roots_match_merkle():
    suite = ecdsa_suite()
    fac = TransactionFactory(suite)
    kp = suite.signature_impl.generate_keypair(secret=99)
    txs = [
        fac.create_signed(
            kp, chain_id="c", group_id="g", block_limit=100, nonce=str(i)
        )
        for i in range(7)
    ]
    blk = Block(transactions=txs)
    blk.receipts = [
        TransactionReceipt(gas_used=i, block_number=1) for i in range(7)
    ]
    buf = blk.encode()
    blk2 = Block.decode(buf)
    assert blk2.encode() == buf

    hashes = blk.tx_hashes(suite)
    leaves = np.frombuffer(b"".join(hashes), dtype=np.uint8).reshape(-1, 32)
    tree = MerkleTree(leaves, hasher="keccak256")
    assert blk.calculate_txs_root(suite) == tree.root
    # metadata-only block (proposal form) yields the same root
    prop = Block(tx_metadata=hashes)
    assert prop.calculate_txs_root(suite) == tree.root


# -- receipts: the wire form packed directly, a block's digests as one batch --

RECEIPTS = {
    "default": TransactionReceipt(),
    "precompiled": TransactionReceipt(gas_used=16_000, output=b"\x00" * 32, block_number=41),
    "fault": TransactionReceipt(version=2, gas_used=3_000_000_000, status=15,
                                output=bytearray(b"precompile fault: x"), block_number=1 << 40),
    "deploy_with_logs": TransactionReceipt(
        version=1, gas_used=21_000, contract_address=b"\x22" * 20, output=b"\x01",
        log_entries=[LogEntry(b"\x22" * 20, [b"\xaa" * 32, b"\xbb" * 32], b"payload"), LogEntry()],
        block_number=7, effective_gas_price="0x12a05f200"),
    "negative_height": TransactionReceipt(block_number=-1, effective_gas_price="é"),
}


@pytest.mark.parametrize("name", sorted(RECEIPTS))
def test_receipt_wire_form_is_the_flat_codecs(name):
    rc = RECEIPTS[name]
    rc.invalidate_caches()
    assert rc.encode() == flat_receipt(rc)
    again = TransactionReceipt.decode(rc.encode())
    assert (again.version, again.gas_used, again.contract_address, again.status,
            bytes(again.output), again.block_number, again.effective_gas_price) == (
        rc.version, rc.gas_used, rc.contract_address, rc.status,
        bytes(rc.output), rc.block_number, rc.effective_gas_price)
    assert again.log_entries == rc.log_entries


@pytest.mark.parametrize("suite", SUITES, ids=["ecdsa", "sm"])
def test_receipts_are_hashed_as_one_batch(suite, monkeypatch):
    from fisco_bcos_tpu.protocol.receipt import hash_receipts

    receipts = [TransactionReceipt(gas_used=16_000 + i, output=bytes([i]) * (i % 40),
                                   block_number=3) for i in range(70)]
    receipts[5].log_entries = [LogEntry(b"\x01" * 20, [b"\x02" * 32], b"d")]
    already = receipts[9].hash(suite)  # hashed before the batch: left alone
    calls = []
    real = type(suite.hash_impl).hash_each
    monkeypatch.setattr(type(suite.hash_impl), "hash_each",
                        lambda self, msgs: calls.append(len(msgs)) or real(self, msgs))
    want = [suite.hash(flat_receipt(rc)) for rc in receipts]
    assert hash_receipts(receipts, suite) == want
    assert calls == [69] and receipts[9]._hash is already
    assert all(rc._hash == w and rc._enc == flat_receipt(rc) for rc, w in zip(receipts, want))
    # the root and a later hash() find them: nothing is hashed again
    monkeypatch.setattr(type(suite.hash_impl), "hash", lambda self, data: 1 / 0)
    leaves = np.frombuffer(b"".join(want), dtype=np.uint8).reshape(-1, 32)
    assert Block(receipts=receipts).calculate_receipts_root(suite) == MerkleTree(
        leaves, hasher=suite.hash_impl.name).root
    assert [rc.hash(suite) for rc in receipts] == want and calls == [69]
    assert hash_receipts([], suite) == []


@pytest.mark.parametrize("suite", SUITES, ids=["ecdsa", "sm"])
def test_hash_each_is_hash_of_each(suite, monkeypatch):
    from fisco_bcos_tpu import native_bind

    msgs = [b"", b"abc", b"\xff" * 135, b"\x00" * 136, b"x" * 137, b"\x80" * 55, b"\x80" * 56,
            bytes(range(256)) * 5] + [bytes([i]) * 72 for i in range(40)]
    want = [suite.hash(m) for m in msgs]
    assert suite.hash_each(msgs) == want and suite.hash_each([]) == []
    # no native core: the per-message loop
    monkeypatch.setattr(native_bind, "load", lambda: None)
    assert suite.hash_each(msgs) == want
