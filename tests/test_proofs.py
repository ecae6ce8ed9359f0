"""ProofPlane (ISSUE 7): frozen-tree cache bit-identity vs the direct
ledger path, per-height build coalescing, invalidation on rollback
re-drive / failover / identity drift, the batch RPC + lightnode surfaces,
and the commit-time warm path.

The synthetic-ledger tests stage chain rows directly (no signing, no
consensus) so ragged leaf counts across the bucket-ladder boundaries stay
cheap; the live tests ride the standard 4-node in-proc chain.
"""

import hashlib
import sys
import threading

sys.path.insert(0, "tests")

import pytest  # noqa: E402
from test_pbft import leader_of, make_chain, submit_txs  # noqa: E402

from fisco_bcos_tpu.crypto.suite import ecdsa_suite  # noqa: E402
from fisco_bcos_tpu.ledger import Ledger  # noqa: E402
from fisco_bcos_tpu.ledger.ledger import (  # noqa: E402
    SYS_HASH_2_RECEIPT,
    SYS_NUMBER_2_HASH,
    SYS_NUMBER_2_TXS,
    _encode_hash_list,
)
from fisco_bcos_tpu.ops.merkle import MerkleProofItem, MerkleTree  # noqa: E402
from fisco_bcos_tpu.proofs import ProofPlane  # noqa: E402
from fisco_bcos_tpu.protocol.receipt import TransactionReceipt  # noqa: E402
from fisco_bcos_tpu.storage import MemoryStorage  # noqa: E402
from fisco_bcos_tpu.storage.entry import Entry  # noqa: E402

SUITE = ecdsa_suite()


def _stage_block(storage, number: int, k: int, tag: bytes = b""):
    """Write a synthetic committed block's proof-relevant rows: k fake tx
    hashes, their receipts, and the number->hash identity row."""
    hashes = [
        hashlib.sha256(b"%s-%d-%d" % (tag, number, i)).digest() for i in range(k)
    ]
    storage.set_row(
        SYS_NUMBER_2_TXS, str(number).encode(), Entry().set(_encode_hash_list(hashes))
    )
    for i, h in enumerate(hashes):
        rc = TransactionReceipt(block_number=number, gas_used=i)
        storage.set_row(SYS_HASH_2_RECEIPT, h, Entry().set(rc.encode()))
    block_hash = hashlib.sha256(b"hdr-%s-%d" % (tag, number)).digest()
    storage.set_row(
        SYS_NUMBER_2_HASH, str(number).encode(), Entry().set(block_hash)
    )
    return hashes, block_hash


@pytest.fixture
def synthetic():
    storage = MemoryStorage()
    ledger = Ledger(storage, SUITE)
    plane = ProofPlane(ledger, SUITE)
    return storage, ledger, plane


# -- bit-identity ------------------------------------------------------------


def test_bit_identity_across_bucket_boundaries(synthetic):
    """ProofPlane proofs byte-equal the direct Ledger path for ragged leaf
    counts spanning the bucket-ladder boundaries (<=16 exact, then the
    5-bit-mantissa buckets: 17->32 pad, 33->48 pad, 48 exact, 49->64 pad),
    and verify_proof accepts both against the same root."""
    storage, ledger, plane = synthetic
    for number, k in enumerate((1, 2, 15, 16, 17, 32, 33, 48, 49), start=1):
        hashes, _bh = _stage_block(storage, number, k)
        for probe in {0, k // 2, k - 1}:
            h = hashes[probe]
            ledger.proof_plane = None
            direct_tx = ledger.tx_proof(h)
            direct_rc = ledger.receipt_proof(h)
            ledger.proof_plane = plane
            assert ledger.tx_proof(h) == direct_tx, (k, probe)
            assert ledger.receipt_proof(h) == direct_rc, (k, probe)
            items, idx, n = direct_tx
            assert (idx, n) == (probe, k)
            import numpy as np

            root = MerkleTree(
                np.frombuffer(b"".join(hashes), np.uint8).reshape(-1, 32),
                hasher=SUITE.hash_impl.name,
            ).root
            assert MerkleTree.verify_proof(
                h, idx, n, items, root, hasher=SUITE.hash_impl.name
            )


def test_unknown_hash_and_bad_kind(synthetic):
    _storage, _ledger, plane = synthetic
    assert plane.proof_batch([b"\x01" * 32], "tx") == [None]
    assert plane.tx_proof(b"\x02" * 32) is None
    with pytest.raises(ValueError, match="kind"):
        plane.proof_batch([], "bogus")


# -- cache mechanics ----------------------------------------------------------


def test_cache_hits_and_lru_eviction(synthetic):
    storage, _ledger, plane = synthetic
    plane.capacity = 4  # 2 heights x 2 kinds
    staged = {
        n: _stage_block(storage, n, 8)[0] for n in (1, 2, 3)
    }
    plane.proof_batch([staged[1][0]], "tx")
    assert plane.stats()["builds_lazy"] == 1
    plane.proof_batch([staged[1][1]], "tx")
    st = plane.stats()
    assert st["builds_lazy"] == 1 and st["hits"] == 1  # second serve = hit
    # filling heights 2 and 3 (tx+receipt each) overflows capacity 4
    for n in (2, 3):
        plane.proof_batch([staged[n][0]], "tx")
        plane.proof_batch([staged[n][0]], "receipt")
    st = plane.stats()
    assert st["entries"] <= 4
    assert st["evictions"].get("lru", 0) >= 1


def test_identity_drift_evicts_and_rebuilds(synthetic):
    """A cached tree whose height was re-driven to a DIFFERENT block must
    not serve: the stale entry is evicted and the proof comes from (and
    verifies against) the current root only."""
    storage, ledger, plane = synthetic
    ledger.proof_plane = plane
    hashes, _ = _stage_block(storage, 1, 9, tag=b"a")
    items_a, idx_a, n_a = ledger.tx_proof(hashes[2])
    # the height is re-driven: same number, different content + identity
    hashes_b, _ = _stage_block(storage, 1, 7, tag=b"b")
    res = plane.proof_batch([hashes_b[4]], "tx")
    assert res[0] is not None
    number, items, idx, n = res[0]
    assert (number, idx, n) == (1, 4, 7)
    assert plane.stats()["evictions"].get("identity", 0) >= 1
    # a proof for the DEAD block's tx is no longer servable
    assert ledger.tx_proof(hashes[2]) is None


def test_height_gone_serves_nothing(synthetic):
    storage, ledger, plane = synthetic
    ledger.proof_plane = plane
    hashes, _ = _stage_block(storage, 5, 6)
    assert ledger.tx_proof(hashes[0]) is not None
    # the identity row dies (rollback finished): nothing may serve
    from fisco_bcos_tpu.storage.entry import EntryStatus

    storage.set_row(
        SYS_NUMBER_2_HASH, b"5", Entry(status=EntryStatus.DELETED)
    )
    assert storage.get_row(SYS_NUMBER_2_HASH, b"5") is None
    assert ledger.tx_proof(hashes[0]) is None


def test_concurrent_misses_coalesce_to_one_build(synthetic):
    storage, _ledger, plane = synthetic
    hashes, _ = _stage_block(storage, 1, 64)
    barrier = threading.Barrier(8)
    errs = []

    def hammer(i):
        try:
            barrier.wait(10)
            res = plane.proof_batch([hashes[i * 7]], "tx")
            assert res[0] is not None
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    st = plane.stats()
    assert st["builds_lazy"] == 1  # singleflight: one build for the height
    assert st["hits"] + st["coalesced_builds"] >= 7


def test_stale_locator_memo_falls_back(synthetic):
    """The tx->height memo may go stale across a re-drive; membership in
    the identity-checked tree is the authority and the serve falls back to
    the receipt row."""
    storage, _ledger, plane = synthetic
    hashes, _ = _stage_block(storage, 1, 5, tag=b"a")
    h = hashes[3]
    assert plane.proof_batch([h], "tx")[0][0] == 1
    # the tx moves to height 2 (block 1 re-driven without it)
    keep = [x for i, x in enumerate(hashes) if i != 3]
    storage.set_row(SYS_NUMBER_2_TXS, b"1", Entry().set(_encode_hash_list(keep)))
    storage.set_row(
        SYS_NUMBER_2_HASH, b"1", Entry().set(hashlib.sha256(b"hdr2").digest())
    )
    h2s, _ = _stage_block(storage, 2, 3, tag=b"c")
    rc = TransactionReceipt(block_number=2, gas_used=9)
    storage.set_row(SYS_HASH_2_RECEIPT, h, Entry().set(rc.encode()))
    storage.set_row(
        SYS_NUMBER_2_TXS, b"2", Entry().set(_encode_hash_list(h2s + [h]))
    )
    res = plane.proof_batch([h], "tx")
    assert res[0] is not None and res[0][0] == 2  # relocated, not stale


# -- rollback / failover invalidation -----------------------------------------


def test_rollback_redrive_evicts_cached_height():
    """2PC rollback declaring a height dead fires the on_rollback hook on
    the initial drive AND the re-drive (deterministic via FaultPlan), and
    the plane evicts the height each time — a proof served mid-rollback can
    never certify against the dead root once the drive lands."""
    from fisco_bcos_tpu.resilience import (
        FaultPlan,
        clear_fault_plan,
        install_fault_plan,
    )
    from fisco_bcos_tpu.service import StorageService
    from fisco_bcos_tpu.storage.distributed import DistributedStorage
    from fisco_bcos_tpu.storage.interfaces import TwoPCParams

    backings = [MemoryStorage() for _ in range(3)]
    svcs = [StorageService(b) for b in backings]
    for s in svcs:
        s.start()
    clear_fault_plan()
    try:
        dist = DistributedStorage([(s.host, s.port) for s in svcs], timeout=3.0)
        ledger = Ledger(dist, SUITE)
        plane = ProofPlane(ledger, SUITE)
        ledger.proof_plane = plane
        dist.on_rollback.append(plane.on_rolled_back)

        hashes, block_hash = _stage_block(dist, 9, 12)
        proof = ledger.tx_proof(hashes[1])
        assert proof is not None and plane.stats()["entries"] == 1

        # rollback with shard 2's servant dead: the drive records a skip
        # set, but the hook fires and the cached height dies NOW
        install_fault_plan(
            FaultPlan(seed=7).rule("kill", "send", f"{svcs[2].port}/rollback")
        )
        dist.rollback(TwoPCParams(number=9))
        clear_fault_plan()
        assert plane.stats()["evictions"].get("rollback", 0) == 1
        assert plane.stats()["entries"] == 0
        assert dist.unresolved_rollbacks() == {9: {2}}

        # the re-drive (shard revived) fires the hook again — idempotent
        dist.recover_in_flight_if_needed()
        assert dist.unresolved_rollbacks() == {}
        # the dead height's identity row is retired with the block: once
        # gone, nothing serves for it
        from fisco_bcos_tpu.storage.entry import EntryStatus

        dist.set_row(SYS_NUMBER_2_HASH, b"9", Entry(status=EntryStatus.DELETED))
        assert ledger.tx_proof(hashes[1]) is None
    finally:
        clear_fault_plan()
        for s in svcs:
            s.stop()


def test_failover_clears_cache(synthetic):
    storage, _ledger, plane = synthetic
    hashes, _ = _stage_block(storage, 1, 4)
    _stage_block(storage, 2, 4)
    plane.proof_batch([hashes[0]], "tx")
    plane.proof_batch([hashes[0]], "receipt")
    assert plane.stats()["entries"] == 2
    plane.on_failover()
    st = plane.stats()
    assert st["entries"] == 0
    assert st["evictions"].get("failover", 0) == 2


# -- live chain: commit warm path, RPC + lightnode surfaces -------------------


@pytest.fixture
def live_chain():
    nodes, gw = make_chain(4)
    for height in (1, 2):
        leader = leader_of(nodes, height)
        submit_txs(leader, 3, start=height * 10)
        assert leader.sealer.seal_and_submit()
    # the commit-notify worker freezes each head's trees in the background:
    # the chain is ready for a test once its notifications have been delivered
    for nd in nodes:
        assert nd.scheduler.drain_commits(30.0)
        assert nd.scheduler.drain_notifications(30.0)
    return nodes, gw


def test_commit_builds_frozen_trees(live_chain):
    nodes, _gw = live_chain
    node = nodes[0]
    assert node.proof_plane is not None
    assert node.ledger.proof_plane is node.proof_plane
    st = node.proof_plane.stats()
    assert st["builds_commit"] >= 2  # tx + receipt trees for the head
    h = node.ledger.tx_hashes_by_number(2)[0]
    p = node.ledger.tx_proof(h)
    assert p is not None
    after = node.proof_plane.stats()
    assert after["builds_lazy"] == 0  # served from the commit-time build
    assert after["hits"] >= 1
    # ... and it certifies against the committed header's txs root
    items, idx, n = p
    header = node.ledger.header_by_number(2)
    assert MerkleTree.verify_proof(
        h, idx, n, items, header.txs_root, hasher=SUITE.hash_impl.name
    )
    from fisco_bcos_tpu.resilience import HEALTH

    assert HEALTH.status("proof-plane") == "ok"


def test_get_proof_batch_rpc(live_chain):
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl
    from fisco_bcos_tpu.utils.bytesutil import from_hex, to_hex

    nodes, _gw = live_chain
    node = nodes[0]
    rpc = JsonRpcImpl(node)
    hashes = node.ledger.tx_hashes_by_number(1) + node.ledger.tx_hashes_by_number(2)
    req = [to_hex(h) for h in hashes] + [to_hex(b"\xee" * 32)]
    out = rpc.handle(
        {
            "jsonrpc": "2.0",
            "id": 1,
            "method": "getProofBatch",
            "params": ["group0", "", req, "tx"],
        }
    )
    res = out["result"]
    assert res["kind"] == "tx"
    assert len(res["proofs"]) == len(hashes) + 1
    assert res["proofs"][-1] is None  # the unknown hash
    for h, doc in zip(hashes, res["proofs"]):
        header = node.ledger.header_by_number(doc["blockNumber"])
        # rebuild proof items from the JSON shape (in-group index is
        # derived from the leaf index, exactly as the verifier pins it)
        rebuilt = []
        idx = doc["index"]
        width = 16
        for grp in doc["path"]:
            g0 = (idx // width) * width
            rebuilt.append(
                MerkleProofItem(
                    group=tuple(from_hex(g) for g in grp), index=idx - g0
                )
            )
            idx //= width
        assert MerkleTree.verify_proof(
            h,
            doc["index"],
            doc["leaves"],
            rebuilt,
            header.txs_root,
            hasher=SUITE.hash_impl.name,
        )
    # receipt kind rides the same surface
    out = rpc.handle(
        {
            "jsonrpc": "2.0",
            "id": 2,
            "method": "getProofBatch",
            "params": ["group0", "", [to_hex(hashes[0])], "receipt"],
        }
    )
    assert out["result"]["proofs"][0] is not None
    # receipt proof now also rides getTransactionReceipt(proof=True)
    out = rpc.handle(
        {
            "jsonrpc": "2.0",
            "id": 3,
            "method": "getTransactionReceipt",
            "params": ["group0", "", to_hex(hashes[0]), True],
        }
    )
    assert "receiptProof" in out["result"]


def test_lightnode_proof_batch_frame(live_chain):
    from fisco_bcos_tpu.front import FrontService
    from fisco_bcos_tpu.lightnode import LightNode, LightNodeService

    nodes, gw = live_chain
    for n in nodes:
        LightNodeService(n)
    lkp = SUITE.signature_impl.generate_keypair(secret=0x22222)
    front = FrontService(lkp.pub)
    gw.connect(front)
    light = LightNode(front, SUITE, nodes[0].ledger.consensus_nodes())
    light.full_node = nodes[0].node_id
    assert light.sync_headers() == 2

    hashes = nodes[0].ledger.tx_hashes_by_number(1) + nodes[0].ledger.tx_hashes_by_number(2)
    got = light.get_proof_batch(hashes + [b"\xaa" * 32], kind="tx")
    assert set(got) == set(hashes)  # unknown hash simply absent
    assert {got[h][0] for h in hashes} == {1, 2}

    rgot = light.get_proof_batch(hashes[:2], kind="receipt")
    for h in hashes[:2]:
        number, rc = rgot[h]
        assert rc is not None and rc.block_number == number

    # a header the client has NOT synced taints the batch
    leader = leader_of(nodes, 3)
    submit_txs(leader, 2, start=50)
    assert leader.sealer.seal_and_submit()
    new_hash = nodes[0].ledger.tx_hashes_by_number(3)[0]
    with pytest.raises(ValueError, match="unsynced"):
        light.get_proof_batch([new_hash], kind="tx")


def test_proof_plane_disabled_env(monkeypatch):
    from fisco_bcos_tpu.ledger import GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig

    monkeypatch.setenv("FISCO_PROOF_PLANE", "0")
    kp = SUITE.signature_impl.generate_keypair(secret=0x9999)
    from fisco_bcos_tpu.ledger.ledger import ConsensusNode

    cfg = NodeConfig(
        genesis=GenesisConfig(consensus_nodes=[ConsensusNode(kp.pub, weight=1)])
    )
    node = Node(cfg, keypair=kp)
    assert node.proof_plane is None
    assert node.ledger.proof_plane is None  # the direct fallback path


def test_proof_lane_below_sync():
    from fisco_bcos_tpu.device.plane import LANES

    assert LANES["proof"] > LANES["sync"] > LANES["admission"] > LANES["consensus"]


def test_proof_storm_bench_small():
    """The bench harness end-to-end at toy scale: artifact shape, zero
    verification failures, every queued client served."""
    from fisco_bcos_tpu.scenario import run_proof_storm_bench

    doc = run_proof_storm_bench(
        seed=5, scale=0.02, workers=2, clients=96, deadline_s=180
    )
    assert doc["proofs_served"] == 96
    assert doc["verify_failures"] == 0
    assert doc["cache_hit_ratio"] > 0.5
    assert doc["proofs_per_s"] > 0 and doc["proofs_per_s_steady"] > 0
    assert doc["flood"]["solo_tps"] > 0
    assert "error" not in doc


def test_merkle_tree_seam_not_captured_by_first_suite():
    """The plane binds one executor per op NAME process-wide; the seam must
    key the op by hasher or a keccak group's executor would hash an SM
    group's trees (review finding). Order matters: keccak registers first."""
    import numpy as np

    from fisco_bcos_tpu.crypto.suite import sm_suite

    leaves = np.frombuffer(
        b"".join(hashlib.sha256(b"ms-%d" % i).digest() for i in range(40)),
        np.uint8,
    ).reshape(-1, 32)
    for suite in (SUITE, sm_suite()):
        tree = suite.merkle_tree(leaves)
        direct = MerkleTree(leaves, hasher=suite.hash_impl.name)
        assert tree.root == direct.root, suite.hash_impl.name
        assert tree.proof(7) == direct.proof(7)


def test_proof_batch_rpc_cap(live_chain):
    from fisco_bcos_tpu.proofs import MAX_PROOF_BATCH
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl

    nodes, _gw = live_chain
    rpc = JsonRpcImpl(nodes[0])
    out = rpc.handle(
        {
            "jsonrpc": "2.0", "id": 9, "method": "getProofBatch",
            "params": [
                "group0", "",
                ["0x" + "00" * 32] * (MAX_PROOF_BATCH + 1), "tx",
            ],
        }
    )
    assert out["error"]["code"] == -32602 and "over" in out["error"]["message"]
