"""What a national-crypto chain's spans and counters tell apart (PR 44), on a
two-block SM chain beside ``tests/test_sm_chain.py``'s: an SM3 batch of the
hash plane (``device.sm3``, ``fisco_device_items_total{op="sm3"}``: a block's
state root) from the SM3 levels of a merkle root (``device.merkle_root`` with
``hasher="sm3"`` on the record and on the items series), and the QC's batch
verification under SM2 (``qc.verify`` with ``suite="sm2"``; its leg is
``fisco_device_dispatch_path_total{op="sm2_verify",path}``) from admission
(``op="admission"``)."""

import time

import numpy as np
import pytest

from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.utils.metrics import REGISTRY

ITEMS = "fisco_device_items_total{"
PATHS = "fisco_device_dispatch_path_total{"
BLOCKS, BATCH = 2, 8


def _delta(before: dict, prefix: str) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in REGISTRY.counters_matching(prefix).items()
            if v - before.get(k, 0.0)}


@pytest.fixture(scope="module")
def chain():
    """Four ``sm_crypto=True`` nodes that committed two blocks of eight
    ``userAdd`` transactions through ``txpool.submit_batch`` (the native leg:
    nothing pins the device here) -> what the run left behind."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import sm_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
        suite = sm_suite()
        sign, codec, fac = suite.signature_impl, ABICodec(suite.hash), TransactionFactory(suite)
        keys = [sign.generate_keypair(secret=0x44C41B + i) for i in range(4)]
        committee = [ConsensusNode(kp.pub, weight=1) for kp in keys]
        gw = InprocGateway(auto=True)
        nodes = []
        for kp in keys:
            node = Node(NodeConfig(sm_crypto=True, genesis=GenesisConfig(
                consensus_nodes=list(committee), tx_count_limit=1000)), keypair=kp)
            gw.connect(node.front)
            nodes.append(node)
        sender = sign.generate_keypair(secret=0x5EED44)
        items0 = REGISTRY.counters_matching(ITEMS)
        paths0 = REGISTRY.counters_matching(PATHS)
        t0 = time.perf_counter()
        try:
            for k in range(BLOCKS):
                head = max(nd.engine.consensus_head()[0] for nd in nodes)
                cfg = nodes[0].pbft_config
                leader_id = cfg.nodes[cfg.leader_index(head + 1, 0)].node_id
                leader = next(nd for nd in nodes if nd.node_id == leader_id)
                batch = [
                    fac.decode(fac.create_signed(
                        sender, chain_id="chain0", group_id="group0", block_limit=500,
                        nonce=f"l44-{k}-{i}", to=DAG_TRANSFER_ADDRESS,
                        input=codec.encode_call("userAdd(string,uint256)", f"l44-{k}-{i}", 1 + i),
                    ).encode())
                    for i in range(BATCH)
                ]
                assert all(r.status == 0 for r in leader.txpool.submit_batch(batch))
                leader.tx_sync.maintain()
                deadline = time.monotonic() + 60.0
                while leader.txpool.pending_count() > 0 or any(
                        nd.block_number() < head + 1 for nd in nodes):
                    assert time.monotonic() < deadline, "the chain stalled"
                    if not leader.sealer.seal_and_submit():
                        time.sleep(0.002)
                for nd in nodes:
                    nd.scheduler.drain_commits(60.0)
            t1 = time.perf_counter()
            yield {
                "nodes": nodes, "committee": committee, "suite": suite,
                "items": _delta(items0, ITEMS), "paths": _delta(paths0, PATHS),
                "records": [r for r in TRACER.spans() if t0 <= r.ts < t1],
            }
        finally:
            for nd in nodes:
                nd.stop()


def test_a_merkle_root_under_sm3_says_its_hasher_on_the_record_and_the_series(chain):
    roots = [r for r in chain["records"] if r.name == "device.merkle_root"]
    # a transactions root and a receipts root a block and replica, at the least
    assert len(roots) >= BLOCKS * 4 * 2
    assert {r.attrs.get("hasher") for r in roots} == {"sm3"}
    assert chain["items"]['fisco_device_items_total{op="merkle_root",hasher="sm3"}'] >= (
        BLOCKS * 4 * 2 * BATCH)
    assert not any(name.startswith('fisco_device_items_total{op="merkle_root"')
                   and "sm3" not in name for name in chain["items"])


def test_a_hash_plane_batch_under_sm3_is_another_op_than_the_merkle_levels(chain):
    hashed = [r for r in chain["records"] if r.name == "device.sm3"]
    assert hashed and all("hasher" not in r.attrs for r in hashed)  # the op names it
    assert chain["items"]['fisco_device_items_total{op="sm3"}'] == sum(
        r.attrs["batch"] for r in hashed)
    # the merkle levels' hashes run inside the merkle span and count there, not here
    assert chain["items"]['fisco_device_items_total{op="sm3"}'] != chain["items"][
        'fisco_device_items_total{op="merkle_root",hasher="sm3"}']


@pytest.mark.parametrize("hasher", ["keccak256", "sm3"])
def test_the_two_suites_roots_count_apart_on_one_host(hasher):
    from fisco_bcos_tpu.ops.merkle import merkle_root

    leaves = np.arange(5 * 32, dtype=np.uint8).reshape(5, 32)
    before = REGISTRY.counters_matching(ITEMS)
    t0 = time.perf_counter()
    merkle_root(leaves, hasher=hasher)
    assert _delta(before, ITEMS) == {
        f'fisco_device_items_total{{op="merkle_root",hasher="{hasher}"}}': 5.0}
    (record,) = [r for r in TRACER.spans() if r.name == "device.merkle_root" and r.ts >= t0]
    assert record.attrs["hasher"] == hasher and record.attrs["batch"] == 5


def test_a_proof_tree_says_its_hasher_too(chain):
    leaves = np.arange(3 * 32, dtype=np.uint8).reshape(3, 32)
    before = REGISTRY.counters_matching(ITEMS)
    t0 = time.perf_counter()
    tree = chain["suite"].merkle_tree(leaves)
    assert tree.hasher == "sm3"
    assert _delta(before, ITEMS) == {
        'fisco_device_items_total{op="merkle_tree",hasher="sm3"}': 3.0}
    (record,) = [r for r in TRACER.spans() if r.name == "device.merkle_tree" and r.ts >= t0]
    assert record.attrs["hasher"] == "sm3"


def test_the_qcs_batch_verification_under_sm2_says_its_suite_and_has_a_leg_of_its_own(chain):
    node = chain["nodes"][0]
    header = node.ledger.header_by_number(node.block_number())
    assert len(header.signature_list) >= 3 and not header.qc  # the signature list, 128 bytes each
    assert all(len(s.signature) == 128 for s in header.signature_list)
    before = REGISTRY.counters_matching(PATHS)
    t0 = time.perf_counter()
    assert node.block_validator.check_block(header, chain["committee"]) is True
    (record,) = [r for r in TRACER.spans() if r.name == "qc.verify" and r.ts >= t0]
    assert record.attrs["suite"] == "sm2" and record.attrs["scheme"] == "signature_list"
    assert record.attrs["n"] == len(header.signature_list)
    # three or four signatures ride the native loop, under the curve's own op:
    # admission's leg counter does not move (a proof tree of the last block,
    # built in the background, may still count its form in the same series)
    legs = {k: v for k, v in _delta(before, PATHS).items() if 'op="merkle_' not in k}
    assert legs == {
        'fisco_device_dispatch_path_total{op="sm2_verify",path="native"}': 1.0}


def test_in_the_block_path_admission_is_the_only_batch_seam_that_ran(chain):
    """The served block path verifies its PBFT packets and checkpoint
    signatures one by one on the host (no ``qc.verify`` batch: that is block
    sync's), so over the two blocks the only dispatches by leg are
    admission's: the entry node's and the three replicas', a block. The
    merkle programs count in the same series which form a tree took (PR 45):
    eight leaves are far under the fused tree's 256, so every root (two an
    execution, the sealer's and four checks of the proposal's: 13 a block) and
    every proof tree went level by level."""
    merkle = {k: v for k, v in chain["paths"].items() if 'op="merkle_' in k}
    assert {k: v for k, v in chain["paths"].items() if k not in merkle} == {
        'fisco_device_dispatch_path_total{op="admission",path="native"}': BLOCKS * 4.0}
    assert merkle.pop(
        'fisco_device_dispatch_path_total{op="merkle_root",path="levels"}') == BLOCKS * 13.0
    assert list(merkle) == ['fisco_device_dispatch_path_total{op="merkle_tree",path="levels"}']
    assert not [r for r in chain["records"] if r.name == "qc.verify"]
