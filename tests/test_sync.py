"""Block sync + tx gossip across the in-process gateway."""

import sys

sys.path.insert(0, "tests")

from test_pbft import leader_of, make_chain, submit_txs  # noqa: E402

from fisco_bcos_tpu.crypto.suite import ecdsa_suite  # noqa: E402
from fisco_bcos_tpu.front import InprocGateway  # noqa: E402
from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig  # noqa: E402
from fisco_bcos_tpu.node import Node, NodeConfig  # noqa: E402

SUITE = ecdsa_suite()


def test_lagging_node_catches_up():
    nodes, gw = make_chain(4)
    # node 3 goes offline; chain advances 3 blocks without it
    laggard = nodes[3]
    gw.disconnect(laggard.node_id)
    for height in (1, 2, 3):
        leader = leader_of(nodes, height)
        if leader is laggard:
            continue
        submit_txs(leader, 3, start=height * 10)
        assert leader.sealer.seal_and_submit()
    alive_height = nodes[0].block_number()
    assert alive_height >= 2
    assert laggard.block_number() == 0

    # reconnect and sync
    gw.connect(laggard.front)
    nodes[0].block_sync.broadcast_status()
    laggard.block_sync.maintain()
    assert laggard.block_number() == alive_height
    assert (
        laggard.ledger.header_by_number(alive_height).state_root
        == nodes[0].ledger.header_by_number(alive_height).state_root
    )
    # consensus state fast-forwarded
    assert laggard.engine.committed_number == alive_height
    # and the laggard can now participate in the next block
    leader = leader_of(nodes, alive_height + 1)
    submit_txs(leader, 2, start=500)
    assert leader.sealer.seal_and_submit()
    assert laggard.block_number() == alive_height + 1


def test_sync_rejects_forged_blocks():
    nodes, gw = make_chain(4)
    leader = leader_of(nodes, 1)
    submit_txs(leader, 2)
    assert leader.sealer.seal_and_submit()

    # a fifth node with the same genesis but outside the committee forges a block
    outsider_kp = SUITE.signature_impl.generate_keypair(secret=66666)
    # same genesis (same committee order) as make_chain built
    committee = [ConsensusNode(n.node_id, weight=1) for n in nodes]
    cfg = NodeConfig(genesis=GenesisConfig(consensus_nodes=committee))
    outsider = Node(cfg, keypair=outsider_kp)
    gw.connect(outsider.front)

    blk = nodes[0].ledger.block_by_number(1, with_txs=True)
    blk.header.signature_list = blk.header.signature_list[:1]  # below quorum
    assert not outsider.block_sync._apply_block(blk)
    assert outsider.block_number() == 0

    # the genuine block applies cleanly
    genuine = nodes[0].ledger.block_by_number(1, with_txs=True)
    assert outsider.block_sync._apply_block(genuine)
    assert outsider.block_number() == 1


def test_tx_gossip_spreads_to_peers():
    nodes, gw = make_chain(4)
    leader = leader_of(nodes, 1)
    submit_txs(leader, 4)  # submit_txs gossips via tx_sync.maintain()
    for n in nodes:
        assert n.txpool.pending_count() == 4
    # gossip is idempotent
    leader.tx_sync.maintain()
    for n in nodes:
        assert n.txpool.pending_count() == 4


def test_fetch_missing_txs():
    nodes, _ = make_chain(2)
    holder, asker = nodes[0], nodes[1]
    txs = submit_txs(holder, 3)
    hashes = [t.hash(SUITE) for t in txs]
    got = asker.tx_sync.fetch_missing(hashes, holder.node_id)
    assert all(g is not None for g in got)
    assert [g.hash(SUITE) for g in got] == hashes


# -- what a replica commits through sync it has checked itself (PR 30) --------
#
# Every case on both suites (secp256k1 + keccak256, SM2 + SM3), at the suite's
# 32-lane bucket; the plain reference is benchmark/refsync.py.

from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

from benchmark import refsm, refsync  # noqa: E402
from fisco_bcos_tpu.codec.abi import ABICodec  # noqa: E402
from fisco_bcos_tpu.crypto.suite import sm_suite  # noqa: E402
from fisco_bcos_tpu.executor.precompiled import (  # noqa: E402
    ACCOUNT_MGR_ADDRESS,
    DAG_TRANSFER_ADDRESS,
)
from fisco_bcos_tpu.front.front import FrontService, ModuleID  # noqa: E402
from fisco_bcos_tpu.protocol.block import Block  # noqa: E402
from fisco_bcos_tpu.protocol.transaction import TransactionFactory  # noqa: E402
from fisco_bcos_tpu.sync import block_sync  # noqa: E402
from fisco_bcos_tpu.utils.metrics import REGISTRY  # noqa: E402

SUITES = {
    "secp": SimpleNamespace(sm=False, suite=SUITE, ref=refsync.Secp, order=refsync.refcrypto.N),
    "sm": SimpleNamespace(sm=True, suite=sm_suite(), ref=refsync.Sm, order=refsm.N),
}
both_suites = pytest.mark.parametrize("which", list(SUITES))


def chain_of(which, **genesis):
    """Four nodes of one committee over an in-process gateway."""
    s = SUITES[which]
    keys = [s.suite.signature_impl.generate_keypair(secret=10_000 + i) for i in range(4)]
    committee = [ConsensusNode(kp.pub, weight=1) for kp in keys]
    gw = InprocGateway(auto=True)
    nodes = []
    for kp in keys:
        cfg = NodeConfig(
            sm_crypto=s.sm,
            genesis=GenesisConfig(consensus_nodes=list(committee), **genesis),
        )
        node = Node(cfg, keypair=kp)
        gw.connect(node.front)
        nodes.append(node)
    return nodes, gw


def signed(which, secret, nonce, to, call, *args):
    s = SUITES[which]
    return TransactionFactory(s.suite).create_signed(
        s.suite.signature_impl.generate_keypair(secret=secret),
        chain_id="chain0", group_id="group0", block_limit=500, nonce=nonce, to=to,
        input=ABICodec(s.suite.hash).encode_call(call, *args),
    )


def commit_block(nodes, txs):
    """One block of ``txs`` sealed by whoever of the live ``nodes`` leads; a
    turn of the node that is away is timed out into the next view."""
    height = max(n.block_number() for n in nodes) + 1
    cfg = nodes[0].pbft_config
    while True:
        target = cfg.nodes[cfg.leader_index(height, nodes[0].engine.view)].node_id
        leader = next((n for n in nodes if n.node_id == target), None)
        if leader is not None:
            break
        for n in nodes:
            n.engine.on_timeout()
    assert all(r.status == 0 for r in leader.txpool.submit_batch(txs))
    leader.tx_sync.maintain()
    assert leader.sealer.seal_and_submit()
    assert leader.block_number() == height


def backlog_of(which, blocks, txs_per_block=4):
    """A chain whose node 3 was away while the other three committed
    ``blocks`` blocks of fresh ``userAdd``s from three senders."""
    nodes, gw = chain_of(which)
    laggard = nodes[3]
    gw.disconnect(laggard.node_id)
    k = 0
    while nodes[0].block_number() < blocks:
        commit_block(nodes[:3], [
            signed(which, 700 + (k + i) % 3, f"n{k + i}", DAG_TRANSFER_ADDRESS,
                   "userAdd(string,uint256)", f"u{k + i}", 100 + k + i)
            for i in range(txs_per_block)
        ])
        k += txs_per_block
    return nodes, gw, laggard


def give_up_at_once(node):
    """The next tick abandons whatever block request is outstanding."""
    node.block_sync.request_timeout = node.block_sync.request_timeout_floor = 0.0


def counter(name):
    return sum(REGISTRY.counters_matching(name).values())


class Peer:
    """A peer of the driver's own on the gateway: says it stands at
    ``number`` and answers every block request with the next of ``answers``
    (a list of encoded blocks each)."""

    def __init__(self, gw, genesis_hash, number, answers):
        self.kp = SUITE.signature_impl.generate_keypair(secret=424242)
        self.front = FrontService(self.kp.pub)
        self.answers, self.asked = list(answers), []
        self.front.register_module(ModuleID.BLOCK_SYNC, self._on_message)
        gw.connect(self.front)
        self.status = block_sync._encode_status(
            block_sync.SyncStatus(number, b"\x11" * 32, genesis_hash))

    @property
    def node_id(self):
        return self.kp.pub

    def announce(self):
        self.front.broadcast(ModuleID.BLOCK_SYNC, self.status)

    def _on_message(self, src, payload):
        if payload[0] != int(block_sync.SyncPacket.REQUEST):
            return
        self.asked.append(src)
        if self.answers:
            self.front.send_message(
                ModuleID.BLOCK_SYNC, src, block_sync._encode_response(self.answers.pop(0)))


def broken(which, raw_block, lane, how):
    """The block re-encoded with one transaction's signature broken."""
    s = SUITES[which]
    blk = Block.decode(raw_block)
    tx = blk.transactions[lane]
    sig = bytes(tx.signature)
    zero, order = bytes(32), s.order.to_bytes(32, "big")
    tx.signature = {
        "r=0": zero + sig[32:], "s=0": sig[:32] + zero + sig[64:],
        "r=n": order + sig[32:], "s=n": sig[:32] + order + sig[64:],
        "short": sig[:64],
    }[how]
    tx._wire = None
    return blk.encode()


def encoded(node, lo, hi):
    return [node.ledger.block_by_number(n, with_txs=True).encode() for n in range(lo, hi + 1)]


@both_suites
def test_synced_blocks_agree_with_the_plain_reference(which):
    """Senders, hashes, per-block verdicts, balances and state roots of a
    caught-up replica are what benchmark/refsync.py works out from the
    served bytes; the whole backlog went through one admission call."""
    nodes, gw, laggard = backlog_of(which, blocks=3)
    raw = encoded(nodes[0], 1, 3)
    want = refsync.judge(raw, [n.node_id for n in nodes], SUITES[which].ref)
    assert [b["applied"] for b in want["blocks"]] == [True] * 3 and want["height"] == 3
    calls, lanes = counter("fisco_sync_verify_calls_total"), counter("fisco_sync_verify_lanes_total")
    gw.connect(laggard.front)
    nodes[0].block_sync.broadcast_status()
    assert laggard.block_number() == 3
    assert counter("fisco_sync_verify_calls_total") - calls == 1
    assert counter("fisco_sync_verify_lanes_total") - lanes == 12
    codec = ABICodec(SUITES[which].suite.hash)
    for row in want["blocks"]:
        n = row["number"]
        assert laggard.ledger.header_by_number(n).state_root == row["state_root"]
        stored = laggard.ledger.block_by_number(n, with_txs=True).transactions
        assert [t.hash(SUITES[which].suite) for t in stored] == row["hashes"]
        assert laggard.ledger.tx_hashes_by_number(n) == row["hashes"]
    assert not laggard.block_sync._queue
    for user, amount in want["balances"].items():
        call = TransactionFactory(SUITES[which].suite).create(
            chain_id="chain0", group_id="group0", block_limit=0, nonce="",
            to=DAG_TRANSFER_ADDRESS, input=codec.encode_call("userBalance(string)", user))
        code, got = codec.decode_output(["uint256", "uint256"], laggard.scheduler.call(call).output)
        assert (code, got) == (0, amount)
    assert len(want["balances"]) == 12


@both_suites
def test_admission_fills_senders_and_hashes_from_the_answer(which):
    """What _apply_gather hands to execution: every transaction carries the
    sender and hash the reference gives for its bytes, nothing left empty."""
    nodes, _gw, laggard = backlog_of(which, blocks=2)
    blocks = [Block.decode(raw) for raw in encoded(nodes[0], 1, 2)]
    assert all(t.sender == b"" and t._hash is None for b in blocks for t in b.transactions)
    assert laggard.block_sync._apply_gather([(b, b"") for b in blocks]) == 2
    want = refsync.judge(encoded(nodes[0], 1, 2), [n.node_id for n in nodes], SUITES[which].ref)
    for blk, row in zip(blocks, want["blocks"]):
        assert [t.sender for t in blk.transactions] == row["senders"]
        assert [t._hash for t in blk.transactions] == row["hashes"]
        assert all(len(s) == 20 for s in row["senders"])


@both_suites
@pytest.mark.parametrize("how", ["r=0", "s=0", "r=n", "s=n", "short"])
def test_a_block_with_a_broken_signature_is_refused_then_the_genuine_one_applies(which, how):
    """The hole of the parent's sync: a genuine block whose second
    transaction's signature is broken was applied with an empty sender.
    Now: refused, height unchanged, nothing stored, the peer struck; the
    genuine block served next applies."""
    nodes, gw, laggard = backlog_of(which, blocks=1)
    genuine = encoded(nodes[0], 1, 1)[0]
    bad = broken(which, genuine, 1, how)
    committee = [n.node_id for n in nodes]
    want_bad = refsync.judge([bad], committee, SUITES[which].ref)["blocks"][0]
    want = refsync.judge([genuine], committee, SUITES[which].ref)["blocks"][0]
    assert (want["qc"], want["admits"], want["applied"]) == (True, True, True)
    assert (want_bad["qc"], want_bad["admits"], want_bad["applied"]) == (True, False, False)
    assert [i for i, s in enumerate(want_bad["senders"]) if not s] == [1]
    refused = counter('fisco_sync_blocks_refused_total{reason="signature"}')
    for n in nodes[:3]:
        gw.disconnect(n.node_id)
    gw.connect(laggard.front)
    peer = Peer(gw, laggard.ledger.block_hash_by_number(0), 1, [[bad], [genuine]])
    peer.announce()
    # the first answer was refused, the range asked for again, the second applied
    assert peer.asked == [laggard.node_id] * 2 and not peer.answers
    assert counter('fisco_sync_blocks_refused_total{reason="signature"}') - refused == 1
    assert laggard.block_number() == 1
    assert laggard.ledger.header_by_number(1).state_root == nodes[0].ledger.header_by_number(1).state_root
    stored = laggard.ledger.block_by_number(1, with_txs=True).transactions
    assert [bytes(t.signature) for t in stored] == [
        bytes(t.signature) for t in Block.decode(genuine).transactions]


@both_suites
def test_a_refused_block_strikes_its_peer_and_stores_nothing(which):
    nodes, gw, laggard = backlog_of(which, blocks=1)
    bad = broken(which, encoded(nodes[0], 1, 1)[0], 0, "r=0")
    for n in nodes[:3]:
        gw.disconnect(n.node_id)
    gw.connect(laggard.front)
    peer = Peer(gw, laggard.ledger.block_hash_by_number(0), 1, [[bad]])
    peer.announce()
    assert laggard.block_number() == 0
    assert laggard.ledger.block_by_number(1, with_txs=True) is None
    assert laggard.ledger.total_transaction_count() == 0
    assert laggard.block_sync._strikes == {peer.node_id: 1}


@both_suites
def test_a_broken_block_in_the_middle_keeps_what_came_before_it(which, monkeypatch):
    """Gathers of three blocks (the lanes' limit cut to fit): six blocks
    served with the fifth broken leave four applied, the fifth and sixth
    not, in two admission calls; the range is asked for again."""
    monkeypatch.setattr(block_sync, "VERIFY_LANES_MAX", 12)
    nodes, gw, laggard = backlog_of(which, blocks=6)
    raw = encoded(nodes[0], 1, 6)
    served = list(raw)
    served[4] = broken(which, raw[4], 2, "s=0")
    want = refsync.judge(served, [n.node_id for n in nodes], SUITES[which].ref)
    assert [b["applied"] for b in want["blocks"]] == [True] * 4 + [False] * 2
    for n in nodes[:3]:
        gw.disconnect(n.node_id)
    gw.connect(laggard.front)
    calls = counter("fisco_sync_verify_calls_total")
    peer = Peer(gw, laggard.ledger.block_hash_by_number(0), 6, [served])
    peer.announce()
    assert laggard.block_number() == want["height"] == 4
    assert counter("fisco_sync_verify_calls_total") - calls == 2
    assert laggard.block_sync._strikes == {peer.node_id: 1} and not laggard.block_sync._queue
    assert len(peer.asked) == 2  # [1, 6], then [5, 6] again
    # the live replicas serve the rest, once the request to the peer is given up
    give_up_at_once(laggard)
    for n in nodes[:3]:
        gw.connect(n.front)
    nodes[0].block_sync.broadcast_status()
    assert laggard.block_number() == 6
    assert laggard.ledger.header_by_number(6).state_root == nodes[0].ledger.header_by_number(6).state_root


@both_suites
def test_gathers_stay_full_across_responses(which, monkeypatch):
    """The download queue gathers across responses: with two blocks to a
    request and three to a gather, seven blocks go 3 + 3 + 1 (the tail), not
    2 + 2 + 2 + 1."""
    monkeypatch.setattr(block_sync, "MAX_BLOCKS_PER_REQUEST", 2)
    monkeypatch.setattr(block_sync, "VERIFY_LANES_MAX", 12)
    nodes, gw, laggard = backlog_of(which, blocks=7)
    calls, lanes = counter("fisco_sync_verify_calls_total"), counter("fisco_sync_verify_lanes_total")
    gw.connect(laggard.front)
    nodes[0].block_sync.broadcast_status()
    assert laggard.block_number() == 7
    assert counter("fisco_sync_verify_calls_total") - calls == 3
    assert counter("fisco_sync_verify_lanes_total") - lanes == 28
    assert laggard.ledger.header_by_number(7).state_root == nodes[0].ledger.header_by_number(7).state_root


@both_suites
def test_a_block_that_reads_its_sender_syncs_to_the_replicas_state_root(which):
    """An account frozen by the governor: the governor's call and the frozen
    sender's both read ``tx.sender``. The parent's sync executed with empty
    senders and forked (``root mismatch on verify``)."""
    s = SUITES[which]
    governor = s.suite.calculate_address(
        s.suite.signature_impl.generate_keypair(secret=900).pub)
    alice = s.suite.calculate_address(
        s.suite.signature_impl.generate_keypair(secret=901).pub)
    nodes, gw = chain_of(which, governors=["0x" + governor.hex()])
    laggard = nodes[3]
    gw.disconnect(laggard.node_id)
    plan = [
        [signed(which, 900, "g0", ACCOUNT_MGR_ADDRESS, "setAccountStatus(address,uint8)",
                "0x" + alice.hex(), 1)],
        [signed(which, 901, "a0", DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "alice", 5),
         signed(which, 902, "b0", DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "bob", 7)],
    ]
    for txs in plan:
        commit_block(nodes[:3], txs)
    head = nodes[0].block_number()
    gw.connect(laggard.front)
    nodes[0].block_sync.broadcast_status()
    assert laggard.block_number() == head
    for n in range(1, head + 1):
        assert laggard.ledger.header_by_number(n).state_root == nodes[0].ledger.header_by_number(n).state_root
        assert laggard.ledger.header_by_number(n).receipts_root == nodes[0].ledger.header_by_number(n).receipts_root
    # the freeze took: alice's userAdd left no balance, bob's did
    codec = ABICodec(s.suite.hash)
    for user, want in (("alice", None), ("bob", 7)):
        call = TransactionFactory(s.suite).create(
            chain_id="chain0", group_id="group0", block_limit=0, nonce="",
            to=DAG_TRANSFER_ADDRESS, input=codec.encode_call("userBalance(string)", user))
        code, got = codec.decode_output(["uint256", "uint256"], laggard.scheduler.call(call).output)
        assert (got if code == 0 else None) == want


@both_suites
def test_an_undecodable_block_is_counted_and_strikes_the_peer(which):
    nodes, gw, laggard = backlog_of(which, blocks=2)
    raw = encoded(nodes[0], 1, 2)
    for n in nodes[:3]:
        gw.disconnect(n.node_id)
    gw.connect(laggard.front)
    refused = counter('fisco_sync_blocks_refused_total{reason="decode"}')
    peer = Peer(gw, laggard.ledger.block_hash_by_number(0), 2, [[raw[0], raw[1][:-3]]])
    peer.announce()
    assert counter('fisco_sync_blocks_refused_total{reason="decode"}') - refused == 1
    assert laggard.block_sync._strikes == {peer.node_id: 1}
    # what decoded before it waits for the rest of its gather, asked for
    # again; when that request is given up, it applies as the tail
    assert laggard.block_number() == 0 and len(peer.asked) == 2
    give_up_at_once(laggard)
    laggard.block_sync.maintain()
    assert laggard.block_number() == 1


@both_suites
def test_a_gather_checks_its_headers_signatures_as_one_batch(which, monkeypatch):
    nodes, gw, laggard = backlog_of(which, blocks=3)
    impl = type(laggard.suite.signature_impl)
    seen = []
    real = impl.batch_verify

    def spy(self, hashes, pubs, sigs):
        seen.append(len(hashes))
        return real(self, hashes, pubs, sigs)

    monkeypatch.setattr(impl, "batch_verify", spy)
    gw.connect(laggard.front)
    nodes[0].block_sync.broadcast_status()
    assert laggard.block_number() == 3
    assert seen == [sum(len(laggard.ledger.header_by_number(n).signature_list) for n in (1, 2, 3))]
    # a forged certificate in the middle refuses its block and what follows
    blocks = [Block.decode(raw) for raw in encoded(nodes[0], 1, 3)]
    blocks[1].header.signature_list = blocks[1].header.signature_list[:1]
    verdicts = laggard.block_validator.check_blocks(
        [b.header for b in blocks], laggard.ledger.consensus_nodes())
    assert verdicts == [True, False, True]
