"""Byzantine consensus-message scenarios — one adversary inside a live
f=1-tolerant committee (ISSUE 15 tentpole).

The :class:`ByzantineReplica` owns a REAL committee member's keypair and
front (it is indistinguishable from an honest replica on the wire) and
drives the real PBFT engine handlers of its peers over the scenario
runner's mesh topology — per-host :class:`~fisco_bcos_tpu.gateway.group.
GroupGateway` muxes on one :class:`~fisco_bcos_tpu.front.InprocGateway`
transport, queued (``auto=False``) so each attack's frame interleaving is
seed-deterministic. The catalog covers the cheap attacks 2302.00418's
committee-vote model and ByzCoin's equivocation analysis name:

- ``equivocation`` — two signed pre-prepares at one (number, view);
- ``stale_view_replay`` — the adversary's recorded frames re-injected
  after the committee moved to a newer view;
- ``vote_conflict`` — two different PREPARE votes from one signer;
- ``fabricated_prepared_cert`` — a view-change carrying a prepared claim
  whose "proof" is one self-signed PREPARE (no quorum);
- ``forged_qc_vote`` — a vote with a garbage QC signature under the
  adversary's own identity PLUS a vote forged under a victim's index.

Every attack must be *detected* (``fisco_consensus_evidence_total{kind}``
and the :data:`~fisco_bcos_tpu.consensus.audit.EVIDENCE` board), the
attacker demoted through the existing strike/quota board, and the honest
committee must keep committing — the
:func:`~fisco_bcos_tpu.consensus.audit.audit_chain` safety auditor is the
final gate of every run.
"""

from __future__ import annotations

import time

from ..consensus.audit import EVIDENCE, audit_chain, validator_source
from ..consensus.messages import PacketType, PBFTMessage, ViewChangePayload
from ..front.front import ModuleID
from ..protocol.block import Block
from ..protocol.block_header import BlockHeader, ParentInfo
from .base import WorkloadContext

ATTACK_NAMES = (
    "equivocation",
    "stale_view_replay",
    "vote_conflict",
    "fabricated_prepared_cert",
    "forged_qc_vote",
)

# attack -> evidence kinds its detection must raise
ATTACK_EVIDENCE = {
    "equivocation": ("equivocation",),
    "stale_view_replay": ("stale_view_replay",),
    "vote_conflict": ("vote_conflict",),
    "fabricated_prepared_cert": ("fabricated_prepared_cert",),
    "forged_qc_vote": ("bad_qc_vote", "forged_qc_vote"),
}


class ByzantineReplica:
    """The adversary: a legitimate committee member that crafts and signs
    arbitrary consensus frames. Everything it sends authenticates — that
    is the threat model; garbage from a non-member dies at the signature
    check and needs no catalog."""

    def __init__(self, node):
        self.node = node
        self.cfg = node.pbft_config
        self.suite = node.suite
        self.keypair = node.keypair
        self.recorded: list[bytes] = []  # frames kept for replay attacks

    @property
    def index(self) -> int:
        return self.cfg.my_index

    def sign(self, msg: PBFTMessage) -> PBFTMessage:
        msg.generated_from = self.index
        msg.sign(self.suite, self.keypair)
        return msg

    def broadcast(self, msg_or_frame, record: bool = False) -> None:
        frame = (
            msg_or_frame
            if isinstance(msg_or_frame, (bytes, bytearray))
            else msg_or_frame.encode()
        )
        if record:
            self.recorded.append(bytes(frame))
        self.node.front.broadcast(ModuleID.PBFT, bytes(frame))

    def craft_block(self, number: int, parent_hash: bytes, salt: int) -> Block:
        """A well-formed empty proposal at `number` — passes every
        verification gate (no txs to check), distinct per ``salt``."""
        header = BlockHeader(
            version=1,
            number=number,
            parent_info=[ParentInfo(number - 1, parent_hash)],
            timestamp=1_700_000_000_000 + salt,  # deterministic, distinct
            sealer=self.index,
            sealer_list=[n.node_id for n in self.cfg.nodes],
            consensus_weights=[n.weight for n in self.cfg.nodes],
        )
        return Block(header=header, tx_metadata=[])


class ByzantineHarness:
    """One n-host committee on the queued in-proc mesh, one adversary.

    The drive loop is the scenario runner's: submit at the leader, gossip,
    seal, drain the queue — every delivery explicit so attack frames can
    be interleaved at exact points.
    """

    def __init__(
        self,
        seed: int = 0,
        hosts: int = 4,
        with_qc: bool = True,
        block_cap: int = 2000,
        group: str = "group0",
    ):
        from ..crypto.suite import ecdsa_suite
        from ..front import InprocGateway
        from ..gateway.group import GroupGateway
        from ..ledger import ConsensusNode, GenesisConfig
        from ..node import Node, NodeConfig

        self.seed = int(seed)
        self.group = group
        suite = ecdsa_suite()
        secrets = [0xB12A_0000 + seed * 101 + i for i in range(hosts)]
        keypairs = [
            suite.signature_impl.generate_keypair(secret=s) for s in secrets
        ]
        committee = []
        for i, kp in enumerate(keypairs):
            qc_pub = b""
            if with_qc:
                from ..consensus.qc import qc_pub_for

                qc_pub = qc_pub_for(secrets[i])
            committee.append(ConsensusNode(kp.pub, weight=1, qc_pub=qc_pub))
        self.transport = InprocGateway(auto=False)
        self.nodes = []
        self._muxes: dict[bytes, GroupGateway] = {}
        for kp in keypairs:
            mux = GroupGateway(kp.pub)
            self.transport.connect(mux)
            self._muxes[kp.pub] = mux
            cfg = NodeConfig(
                group_id=group,
                genesis=GenesisConfig(
                    group_id=group,
                    consensus_nodes=list(committee),
                    tx_count_limit=block_cap,
                ),
            )
            self.nodes.append(Node(cfg, keypair=kp, front=mux.register_group(group)))
        # the adversary: committee index seed % n — stable under the
        # sorted-committee reordering because we select BY index
        self.adv_index = self.seed % hosts
        self.adversary = ByzantineReplica(self._node_at(self.adv_index))
        self.honest = [n for n in self.nodes if n is not self.adversary.node]
        self.ctx = WorkloadContext(suite=suite)
        self._nonce = 0

    # -- plumbing -------------------------------------------------------------

    def _node_at(self, index: int):
        return next(
            n for n in self.nodes if n.pbft_config.my_index == index
        )

    def silence(self, node) -> None:
        """Cut one node off the mesh (its GroupGateway mux, NOT its group
        front — reconnecting the front would replace the group facade and
        wedge the node's framing)."""
        self.transport.disconnect(node.node_id)

    def rejoin(self, node) -> None:
        """Undo :meth:`silence` — reconnects the node's original mux so
        the group envelope wiring survives the round trip."""
        self.transport.connect(self._muxes[node.node_id])

    def deliver(self) -> int:
        return self.transport.deliver_all(max_rounds=200)

    def reconcile(self) -> None:
        """Bring stragglers back before the next honest round. The
        adversary's own node is the usual laggard — it never receives the
        broadcasts it sends, so an attack at its own leader height leaves
        it behind by a block and (after view changes) behind in view;
        block sync recovers the chain, the recover round recovers the
        view (a lagging node rejects NEW_VIEW from what it computes as a
        wrong leader — by design — and must ask the committee instead)."""
        for _ in range(5):
            if len({n.block_number() for n in self.nodes}) == 1:
                break
            for n in self.nodes:
                n.block_sync.maintain()
            self.deliver()
        top_view = max(n.engine.view for n in self.honest)
        for n in self.nodes:
            if n.engine.view < top_view:
                n.engine.request_recover()
        self.deliver()

    def view(self) -> int:
        return self.honest[0].engine.view

    def height(self) -> int:
        return max(n.block_number() for n in self.honest)

    def leader_for(self, number: int):
        idx = self.honest[0].pbft_config.leader_index(number, self.view())
        return self._node_at(idx)

    def mint_txs(self, n: int) -> list:
        txs = []
        for _ in range(n):
            self._nonce += 1
            txs.append(
                self.ctx.signed_tx(
                    0xFEED + (self._nonce % 7),
                    self.group,
                    f"byz-{self.seed}-{self._nonce}",
                    b"\x10" * 20,
                    b"",
                )
            )
        return txs

    def commit_block(self, n_txs: int = 4) -> bool:
        """One honest round: submit at the leader, gossip, seal, drain."""
        self.reconcile()
        number = self.height() + 1
        leader = self.leader_for(number)
        txs = self.mint_txs(n_txs)
        results = leader.txpool.submit_batch(txs)
        if any(r.status != 0 for r in results):
            return False
        leader.tx_sync.maintain()
        self.deliver()  # gossip lands before the proposal references it
        ok = leader.sealer.seal_and_submit()
        self.deliver()
        return ok and self.height() >= number

    def commit_until_leader(self, index: int, max_blocks: int = 8) -> int:
        """Advance the chain until `index` leads the next height."""
        cfg = self.honest[0].pbft_config
        for _ in range(max_blocks):
            number = self.height() + 1
            if cfg.leader_index(number, self.view()) == index:
                return number
            if not self.commit_block():
                break
        number = self.height() + 1
        if cfg.leader_index(number, self.view()) != index:
            raise RuntimeError(f"could not rotate leadership to {index}")
        return number

    def in_flight_proposal(self) -> tuple[int, bytes]:
        """Seal (but do not drain) the next honest proposal; returns
        (number, proposal_hash) with the pre-prepare still queued —
        the window vote attacks inject into."""
        self.reconcile()
        number = self.height() + 1
        leader = self.leader_for(number)
        txs = self.mint_txs(3)
        results = leader.txpool.submit_batch(txs)
        assert all(r.status == 0 for r in results)
        leader.tx_sync.maintain()
        self.deliver()
        assert leader.sealer.seal_and_submit()
        cache = leader.engine._caches.get(number)
        assert cache is not None and cache.pre_prepare is not None
        return number, cache.pre_prepare.proposal_hash

    # -- the attack catalog ---------------------------------------------------

    def attack_equivocation(self) -> None:
        """Two signed pre-prepares at one (number, view), as the leader."""
        adv = self.adversary
        number = self.commit_until_leader(adv.index)
        parent = self.honest[0].ledger.block_hash_by_number(number - 1) or b""
        view = self.view()
        frames = []
        for salt in (1, 2):
            block = adv.craft_block(number, parent, salt)
            msg = PBFTMessage(
                packet_type=PacketType.PRE_PREPARE,
                view=view,
                number=number,
                proposal_hash=block.header.hash(adv.suite),
                proposal_data=block.encode(),
            )
            frames.append(adv.sign(msg))
        adv.broadcast(frames[0])  # the one the committee will commit
        adv.broadcast(frames[1])  # the equivocation
        self.deliver()

    def attack_stale_view_replay(self) -> None:
        """Record frames at the current view, force a view change, replay
        them — the replayer (transport peer) is charged, not the frames'
        signer."""
        adv = self.adversary
        number = self.height() + 1
        view = self.view()
        vote = PBFTMessage(
            packet_type=PacketType.PREPARE,
            view=view,
            number=number,
            proposal_hash=b"\x5a" * 32,
        )
        adv.sign(vote)
        adv.broadcast(vote, record=True)
        self.deliver()
        # the committee times out and moves on (quorum of honest VCs)
        for n in self.honest:
            n.engine.on_timeout()
        self.deliver()
        assert self.view() > view, "view change did not complete"
        # re-inject the recorded pre-view-change frames
        for frame in self.adversary.recorded:
            adv.broadcast(frame)
        self.deliver()

    def attack_vote_conflict(self) -> None:
        """Vote twice — different hashes — at one (number, view): the
        fake vote lands first, the genuine one (same signer) conflicts
        with it at every honest receiver."""
        adv = self.adversary
        number, real_hash = self.in_flight_proposal()
        view = self.view()
        fake = adv.sign(
            PBFTMessage(
                packet_type=PacketType.PREPARE,
                view=view,
                number=number,
                proposal_hash=b"\xfa" * 32,
            )
        )
        genuine = adv.sign(
            PBFTMessage(
                packet_type=PacketType.PREPARE,
                view=view,
                number=number,
                proposal_hash=real_hash,
            )
        )
        adv.broadcast(fake)
        adv.broadcast(genuine)
        self.deliver()

    def attack_fabricated_prepared_cert(self) -> None:
        """Claim a prepared proposal in view change with a one-vote
        'proof' — steering the new view onto an unprepared block."""
        adv = self.adversary
        # the fabricated VC is judged by the NEW view's leader (and then
        # by every replica via its NEW_VIEW proof set); a node never
        # receives its own broadcasts, so advance the chain until that
        # leader is honest
        cfg = self.honest[0].pbft_config
        while cfg.leader_index(self.height() + 1, self.view() + 1) == adv.index:
            assert self.commit_block()
        number = self.height() + 1
        view = self.view()
        parent = self.honest[0].ledger.block_hash_by_number(number - 1) or b""
        fake_block = adv.craft_block(number, parent, 77)
        fake_hash = fake_block.header.hash(adv.suite)
        lone_prepare = adv.sign(
            PBFTMessage(
                packet_type=PacketType.PREPARE,
                view=view,
                number=number,
                proposal_hash=fake_hash,
            )
        )
        vc = PBFTMessage(
            packet_type=PacketType.VIEW_CHANGE,
            view=view + 1,
            number=self.honest[0].engine.committed_number,
            payload=ViewChangePayload(
                committed_number=self.honest[0].engine.committed_number,
                prepared_view=view,
                prepared_proposal=fake_block.encode(),
                prepare_proof=[lone_prepare.encode()],
            ).encode(),
        )
        adv.sign(vc)
        adv.broadcast(vc)  # queued ahead of the honest view changes
        for n in self.honest:
            n.engine.on_timeout()
        self.deliver()
        assert self.view() > view, "view change did not complete"

    def attack_forged_qc_vote(self) -> None:
        """Two QC-vote abuses while a proposal is mid-vote: a garbage QC
        signature under the adversary's own (authenticated) identity, and
        a vote forged under a victim's index. The first must strike the
        adversary; the second must be dropped WITHOUT striking the
        victim."""
        adv = self.adversary
        number, real_hash = self.in_flight_proposal()
        view = self.view()
        bad = PBFTMessage(
            packet_type=PacketType.PREPARE,
            view=view,
            number=number,
            proposal_hash=real_hash,
        )
        adv.sign(bad)
        bad.qc_sig = b"\x66" * 64  # authenticated packet, garbage QC vote
        adv.broadcast(bad)
        victim_idx = next(
            i
            for i in range(len(adv.cfg.nodes))
            if i != adv.index
        )
        forged = PBFTMessage(
            packet_type=PacketType.PREPARE,
            view=view,
            number=number,
            proposal_hash=real_hash,
        )
        forged.generated_from = victim_idx
        forged.signature = b"\x13" * adv.suite.signature_impl.sig_len
        forged.qc_sig = b"\x37" * 64
        adv.broadcast(forged)
        self.deliver()

    def run_attack(self, name: str) -> dict:
        """Execute one cataloged attack; returns the detection delta."""
        if name not in ATTACK_NAMES:
            raise ValueError(f"unknown attack {name!r} (known: {ATTACK_NAMES})")
        before = EVIDENCE.counts()
        getattr(self, f"attack_{name}")()
        after = EVIDENCE.counts()
        delta = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in ATTACK_EVIDENCE[name]
        }
        return {
            "attack": name,
            "evidence_delta": delta,
            "detected": all(v > 0 for v in delta.values()),
        }

    # -- verdicts -------------------------------------------------------------

    def adversary_source(self) -> str:
        return validator_source(self.adversary.node.node_id)

    def adversary_demoted(self) -> bool:
        from ..consensus.audit import EVIDENCE_GROUP
        from ..txpool.quota import get_quotas

        return get_quotas().demoted(EVIDENCE_GROUP, self.adversary_source())

    def audit(self, prior_views=None) -> dict:
        # the adversary's NODE runs honest engine code — its committed
        # chain is audited too (it may simply be shorter)
        return audit_chain(self.nodes, prior_views=prior_views)

    def catch_up(self) -> None:
        """Final convergence before the audit (alias of reconcile)."""
        self.reconcile()


def run_byzantine_scenario(
    seed: int = 0,
    scale: float = 1.0,
    attacks=ATTACK_NAMES,
    hosts: int = 4,
    deadline_s: float | None = None,
) -> dict:
    """The full catalog against one committee, honest blocks interleaved
    between attacks; returns the artifact dict (per-attack detection,
    evidence counts, demotion, audit report, liveness)."""
    from ..resilience import HEALTH
    from ..txpool.quota import get_quotas

    get_quotas().reset()
    HEALTH.reset()
    EVIDENCE.reset()
    deadline = (
        time.perf_counter() + deadline_s if deadline_s is not None else None
    )
    h = ByzantineHarness(seed=seed, hosts=hosts)
    # a couple of clean blocks first: evidence must start at zero on a
    # healthy chain (the byzantine-off passthrough the criteria pin)
    for _ in range(2):
        h.commit_block(max(1, int(4 * scale)))
    assert EVIDENCE.count() == 0, "clean blocks raised evidence"
    results = []
    t0 = time.perf_counter()
    h0 = h.height()
    for name in attacks:
        results.append(h.run_attack(name))
        h.commit_block(max(1, int(4 * scale)))  # honest progress after each
        if deadline is not None and time.perf_counter() > deadline:
            break
    dt = time.perf_counter() - t0
    h.catch_up()
    audit = h.audit()
    quotas = get_quotas()
    doc = {
        "scenario": "byzantine",
        "seed": seed,
        "scale": scale,
        "adversary_index": h.adv_index,
        "attacks": results,
        # a deadline-truncated run must not claim the whole requested
        # catalog passed
        "all_detected": (
            len(results) == len(attacks)
            and all(r["detected"] for r in results)
        ),
        "evidence_counts": EVIDENCE.counts(),
        "evidence": EVIDENCE.snapshot()[-32:],
        "adversary_demoted": h.adversary_demoted(),
        "quotas": quotas.snapshot(),
        "honest_height": h.height(),
        "blocks_during_attacks": h.height() - h0,
        "attack_window_s": round(dt, 3),
        "audit": audit,
    }
    return doc
