"""Real-wire chaos mesh — the byzantine catalog over TCP (ISSUE 17).

:class:`WireHarness` boots an n-node committee on REAL sockets: one
:class:`~fisco_bcos_tpu.gateway.tcp.TcpGateway` per node, each bound to
its own loopback address (``127.0.0.<i+1>``) so host identity is a
first-class property of every link — exactly what the partition fault
family (:meth:`~fisco_bcos_tpu.resilience.faults.FaultPlan.partition`)
cuts on. The attack semantics are inherited wholesale from
:class:`~.byzantine.ByzantineHarness`; only the transport changes: the
in-proc queue's explicit ``deliver_all`` becomes a quiescence wait
(reader threads deliver asynchronously, so "drained" means the fleet's
observable state stopped moving).

Beyond the catalog, the wire plane adds what only a real transport can
exercise:

- **partition/heal** — a seeded bidirectional cut between host sets; the
  majority side keeps committing (view-changing over isolated leaders),
  the minority stalls, and on heal the laggards block-sync back while
  severed links re-establish through the gateway's
  :class:`~fisco_bcos_tpu.resilience.retry.RetryPolicy` redial;
- **evidence-gossip convergence** — each node runs its own
  :class:`~fisco_bcos_tpu.consensus.gossip.EvidenceGossip`, and
  :meth:`WireHarness.await_convergence` measures (in settle rounds) how
  long a detection made anywhere takes to reach every honest node;
- **colluding adversaries** — :func:`run_wire_colluders` drives TWO
  cooperating byzantine members (equivocation + forged QC votes) inside
  an n=7 committee and gates on agreement, double demotion, and quorum
  membership surviving the demotions.

Every run ends at the same gate as the in-proc catalog: the
:func:`~fisco_bcos_tpu.consensus.audit.audit_chain` safety auditor over
ALL nodes after heal/catch-up.
"""

from __future__ import annotations

import time

from ..consensus.audit import EVIDENCE, EVIDENCE_GROUP, validator_source
from ..consensus.messages import PacketType, PBFTMessage, ViewChangePayload
from ..gateway.tcp import TcpGateway
from ..resilience import HEALTH
from ..resilience.faults import (
    FaultPlan,
    clear_fault_plan,
    install_fault_plan,
)
from ..txpool.quota import get_quotas
from .base import WorkloadContext
from .byzantine import ATTACK_NAMES, ByzantineHarness, ByzantineReplica

# attacks whose evidence family gossips (stale_view_replay is
# indistinguishable from lag and never gossips; forged_qc_vote's FORGED
# frame convicts nobody, but its garbage-own-signature half raises
# bad_qc_vote which does)
GOSSIPED_ATTACKS = (
    "equivocation",
    "vote_conflict",
    "fabricated_prepared_cert",
    "forged_qc_vote",
)


class WireHarness(ByzantineHarness):
    """The :class:`ByzantineHarness` contract over real TCP sockets.

    Node i binds ``127.0.0.<i+1>`` (the whole 127/8 block routes on
    loopback), dials a full mesh, and runs live reader threads — attack
    frames, votes, gossip and block sync all ride genuine sockets.
    """

    def __init__(
        self,
        seed: int = 0,
        hosts: int = 4,
        with_qc: bool = True,
        block_cap: int = 2000,
        group: str = "group0",
        tick_s: float = 0.015,
        quiet_ticks: int = 3,
        settle_timeout_s: float = 8.0,
    ):
        from ..crypto.suite import ecdsa_suite
        from ..ledger import ConsensusNode, GenesisConfig
        from ..node import Node, NodeConfig

        self.seed = int(seed)
        self.group = group
        self.tick_s = tick_s
        self.quiet_ticks = quiet_ticks
        self.settle_timeout_s = settle_timeout_s
        suite = ecdsa_suite()
        secrets = [0xA17E_0000 + seed * 131 + i for i in range(hosts)]
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
        self.transport = None  # no in-proc queue on the wire
        self.nodes = []
        self.gateways: list[TcpGateway] = []
        for i, kp in enumerate(keypairs):
            gw = TcpGateway(
                kp.pub, host=f"127.0.0.{i + 1}", port=0, heartbeat_interval=0
            )
            cfg = NodeConfig(
                group_id=group,
                genesis=GenesisConfig(
                    group_id=group,
                    consensus_nodes=list(committee),
                    tx_count_limit=block_cap,
                ),
            )
            node = Node(cfg, keypair=kp)
            gw.connect(node.front)
            gw.start()
            self.nodes.append(node)
            self.gateways.append(gw)
        for i, gw in enumerate(self.gateways):
            for other in self.gateways[i + 1 :]:
                if not gw.connect_peer(other.host, other.port):
                    raise RuntimeError(
                        f"dial {gw.host} -> {other.host}:{other.port} failed"
                    )
        self.await_mesh()
        self.adv_index = self.seed % hosts
        self.adversary = ByzantineReplica(self._node_at(self.adv_index))
        self.honest = [n for n in self.nodes if n is not self.adversary.node]
        self.ctx = WorkloadContext(suite=suite)
        self._nonce = 0

    # -- wire plumbing --------------------------------------------------------

    def gateway_of(self, node) -> TcpGateway:
        return self.gateways[self.nodes.index(node)]

    def host_of(self, node) -> str:
        return self.gateway_of(node).host

    def await_mesh(
        self, expect: "dict | None" = None, timeout_s: float = 10.0
    ) -> None:
        """Block until every gateway sees its expected peer count
        (default: the full mesh, n-1 each)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            short = [
                gw
                for gw in self.gateways
                if len(gw.peers())
                < (expect or {}).get(gw.host, len(self.gateways) - 1)
            ]
            if not short:
                return
            time.sleep(0.01)
        raise TimeoutError(
            "mesh incomplete: "
            + ", ".join(
                f"{gw.host} sees {len(gw.peers())}" for gw in self.gateways
            )
        )

    def _fingerprint(self) -> tuple:
        """Everything externally observable that in-flight frames still
        change — two identical consecutive reads mean the wire is quiet.
        Vote-cache sizes and the off-lock verify queue are included so a
        round mid-quorum (or parked in a slow aggregate check) never
        reads as idle."""
        rows = []
        for n in self.nodes:
            g = n.engine.gossip
            try:
                votes = sum(
                    len(c.prepares) + len(c.commits) + len(c.checkpoints)
                    for c in list(n.engine._caches.values())
                )
            except RuntimeError:  # cache dict mutated underfoot: not quiet
                votes = -1
            rows.append(
                (
                    n.block_number(),
                    n.engine.view,
                    n.engine.committed_number,
                    len(n.engine._caches),
                    votes,
                    len(n.engine._verify_keys),
                    n.txpool.pending_count(),
                    sum(g.stats.values()) if g is not None else 0,
                )
            )
        return (tuple(rows), EVIDENCE.count())

    def deliver(self) -> int:
        """The wire's ``deliver_all``: wait until the fleet's observable
        state holds still for ``quiet_ticks`` consecutive ticks. Reader
        threads deliver for real; this only decides when they're done."""
        last, stable = None, 0
        deadline = time.monotonic() + self.settle_timeout_s
        while time.monotonic() < deadline:
            time.sleep(self.tick_s)
            cur = self._fingerprint()
            if cur == last:
                stable += 1
                if stable >= self.quiet_ticks:
                    return 0
            else:
                stable = 0
                last = cur
        return 0  # noisy but alive: callers assert on state, not on quiet

    def await_height(self, number: int, among=None, timeout_s=None) -> bool:
        """Condition-wait for the chain to reach `number` on every node in
        `among` (default: the honest set). The quiet-wait in
        :meth:`deliver` can close mid-round (a slow QC aggregate check has
        no externally visible progress), so round completion is awaited on
        the CONDITION, never inferred from wire silence."""
        among = among if among is not None else self.honest
        deadline = time.monotonic() + (timeout_s or self.settle_timeout_s)
        while time.monotonic() < deadline:
            # EVERY node in the set, durably: attack code reads parent
            # hashes out of honest ledgers right after a commit round
            if min(n.block_number() for n in among) >= number:
                return True
            time.sleep(self.tick_s)
        return False

    def commit_block(self, n_txs: int = 4, max_view_changes: int = 2) -> bool:
        """One honest round over the wire: submit at the leader, gossip,
        seal, then WAIT for the height (not for wire silence). A stalled
        round — e.g. the round an attack just equivocated or vote-split,
        whose leader may even be the demoted adversary — is rotated past
        with a view change and retried, exactly as live PBFT recovers."""
        self.reconcile()
        number = self.height() + 1
        for _ in range(1 + max_view_changes):
            leader = self.leader_for(number)
            txs = self.mint_txs(n_txs)
            results = leader.txpool.submit_batch(txs)
            if any(r.status != 0 for r in results):
                return False
            leader.tx_sync.maintain()
            self.deliver()  # gossip lands before the proposal references it
            sealed = leader.sealer.seal_and_submit()
            if self.await_height(
                number, timeout_s=None if sealed else self.settle_timeout_s / 2
            ):
                return True
            try:
                self.force_view_change()
            except TimeoutError:
                return False
            self.reconcile()
            # the stalled round may have completed during the view change
            number = self.height() + 1
        return self.await_height(number)

    def force_view_change(self, timeout_s=None) -> int:
        """The committee times out and converges on a strictly higher
        view with nobody left mid-view-change. Over a real wire the
        sequential on_timeout loop races the completing quorum: a node
        that already accepted NEW_VIEW(v+1) gets timed out AGAIN toward
        v+2 and strands itself (timeout_state forever, quorum for v+2
        never forms). Stragglers are rescued the way live PBFT would —
        the rest of the committee times out onto the straggler's target
        view until everyone re-converges."""
        start = self.view()
        for n in self.honest:
            n.engine.on_timeout()
        deadline = time.monotonic() + (timeout_s or self.settle_timeout_s)
        while time.monotonic() < deadline:
            self.deliver()
            views = {n.engine.view for n in self.honest}
            stuck = [n for n in self.honest if n.engine.timeout_state]
            if not stuck and len(views) == 1 and min(views) > start:
                return min(views)
            if stuck:
                target = max(n.engine.to_view for n in stuck)
                for n in self.honest:
                    if not n.engine.timeout_state and n.engine.view < target:
                        n.engine.on_timeout()
        raise TimeoutError(
            "view change did not converge from "
            f"{start}: views={[n.engine.view for n in self.honest]} "
            f"timed_out={[n.engine.timeout_state for n in self.honest]}"
        )

    def attack_stale_view_replay(self) -> None:
        """Wire variant: identical frames and detection semantics, but the
        committee's timeout is driven through :meth:`force_view_change`
        (the parent's bare on_timeout loop strands stragglers on a live
        wire — see there)."""
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
        assert self.force_view_change() > view
        for frame in adv.recorded:
            adv.broadcast(frame)
        self.deliver()

    def attack_fabricated_prepared_cert(self) -> None:
        """Wire variant: the fabricated view change must be ON the honest
        nodes' VC cache BEFORE the committee times out — async delivery
        can otherwise complete the honest quorum first and the forged
        cert is never judged (the queued transport ordered this for
        free)."""
        adv = self.adversary
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
        adv.broadcast(vc)
        self.deliver()  # the fabricated VC lands on every cache first
        assert self.force_view_change() > view

    def silence(self, node) -> None:  # pragma: no cover - guard rail
        raise RuntimeError("wire mesh: use cut()/heal(), not silence()")

    def rejoin(self, node) -> None:  # pragma: no cover - guard rail
        raise RuntimeError("wire mesh: use cut()/heal(), not rejoin()")

    def stop(self) -> None:
        try:
            # quiesce first: a reader thread torn down mid-QC-aggregate
            # would linger past the gateway joins and die inside native
            # code at interpreter exit
            self.deliver()
        except Exception:  # analysis: allow(except-hygiene, best-effort quiesce on teardown — nodes may already be crash-halted)
            pass
        for n in self.nodes:
            n.stop()
        for gw in self.gateways:
            gw.stop()

    # -- partition family -----------------------------------------------------

    def cut(self, minority, heal_ms: float = 0.0) -> FaultPlan:
        """Partition `minority` (nodes) off the rest of the committee:
        installs a seeded :class:`FaultPlan` whose ``partition`` rule
        refuses every dial/send/recv across the cut (timed heal when
        ``heal_ms`` > 0, else :meth:`heal` on demand)."""
        minority_hosts = [self.host_of(n) for n in minority]
        majority_hosts = [
            gw.host for gw in self.gateways if gw.host not in minority_hosts
        ]
        plan = FaultPlan(seed=self.seed).partition(
            majority_hosts, minority_hosts, heal_ms=heal_ms
        )
        install_fault_plan(plan)
        return plan

    def heal(self, plan: FaultPlan) -> None:
        """Heal the cut and re-establish the full mesh. The gateways'
        RetryPolicy redials recover links the partition dropped while
        their attempt budgets last; anything they gave up on is re-dialed
        here (the operator's 'plug the cable back in')."""
        plan.heal_partitions()
        for i, gw in enumerate(self.gateways):
            have = set(gw.peers())
            for j, other in enumerate(self.gateways):
                if i != j and other.node_id not in have:
                    gw.connect_peer(other.host, other.port)
        self.await_mesh()

    def commit_block_among(
        self, alive, n_txs: int = 3, max_view_changes: int = 8
    ) -> bool:
        """One committed block using only the `alive` side of a cut,
        view-changing past leaders stranded on the other side."""
        for _ in range(max_view_changes):
            number = max(n.block_number() for n in alive) + 1
            view = max(n.engine.view for n in alive)
            cfg = alive[0].pbft_config
            idx = cfg.leader_index(number, view)
            leader = next(
                (n for n in alive if n.pbft_config.my_index == idx), None
            )
            if leader is None:
                # the scheduled leader is across the cut: rotate the view
                for n in alive:
                    n.engine.on_timeout()
                self.deliver()
                continue
            txs = self.mint_txs(n_txs)
            results = leader.txpool.submit_batch(txs)
            if any(r.status != 0 for r in results):
                return False
            leader.tx_sync.maintain()
            self.deliver()
            if leader.sealer.seal_and_submit() and self.await_height(
                number, among=alive
            ):
                return True
        return False

    # -- wire-adapted vote attacks --------------------------------------------
    #
    # The queued in-proc harness holds a round open: `in_flight_proposal`
    # seals a proposal whose frames sit in the queue while the attack
    # injects votes "mid-round". Real reader threads race the round to
    # completion in milliseconds, so the window must be CREATED, not held:
    # the adversary rotates itself into leadership, crafts its own
    # proposal (knowing the hash before the committee does), and plants
    # its conflicting/bad votes on the wire AHEAD of the pre-prepare —
    # per-link FIFO guarantees every receiver caches the attack votes at
    # (number, view) before the round can possibly finish.

    def _leader_window(self) -> tuple[int, int, PBFTMessage, bytes]:
        """Rotate the adversary into leadership and seal ITS proposal
        locally; returns (number, view, signed pre-prepare, hash) with
        nothing on the wire yet."""
        adv = self.adversary
        number = self.commit_until_leader(adv.index)
        parent = self.honest[0].ledger.block_hash_by_number(number - 1) or b""
        block = adv.craft_block(number, parent, 9)
        view = self.view()
        pp = adv.sign(
            PBFTMessage(
                packet_type=PacketType.PRE_PREPARE,
                view=view,
                number=number,
                proposal_hash=block.header.hash(adv.suite),
                proposal_data=block.encode(),
            )
        )
        return number, view, pp, pp.proposal_hash

    def attack_vote_conflict(self) -> None:
        """Wire variant: fake and genuine PREPAREs land back-to-back
        BEFORE the proposal they vote on — the conflict is cached at every
        honest receiver before the round starts."""
        adv = self.adversary
        number, view, pp, real_hash = self._leader_window()
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
        adv.broadcast(pp)  # the committee commits this one
        self.deliver()

    def attack_forged_qc_vote(self) -> None:
        """Wire variant of the two QC-vote abuses: the garbage-own-sig
        vote and the forged-victim vote are planted ahead of the
        adversary's own proposal, so the off-lock aggregate check finds
        the bad share in its first quorum snapshot."""
        adv = self.adversary
        number, view, pp, real_hash = self._leader_window()
        bad = PBFTMessage(
            packet_type=PacketType.PREPARE,
            view=view,
            number=number,
            proposal_hash=real_hash,
        )
        adv.sign(bad)
        bad.qc_sig = b"\x66" * 64  # authenticated packet, garbage QC vote
        victim_idx = next(
            i for i in range(len(adv.cfg.nodes)) if i != adv.index
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
        adv.broadcast(bad)
        adv.broadcast(forged)
        adv.broadcast(pp)
        self.deliver()

    # -- evidence-gossip convergence ------------------------------------------

    def gossip_convergence(self, offender_id: bytes | None = None, among=None) -> dict:
        """Which honest nodes have locally confirmed the offender (their
        own detection or a re-verified gossip record)."""
        offender = (offender_id or self.adversary.node.node_id).hex()
        rows = {}
        for n in among if among is not None else self.honest:
            g = n.engine.gossip
            rows[n.engine.crash_scope or n.node_id.hex()[:8]] = bool(
                g is not None and offender in g.confirmed_offenders
            )
        return {"offender": offender, "confirmed": rows, "all": all(rows.values())}

    def await_convergence(
        self,
        offender_id: bytes | None = None,
        among=None,
        timeout_s: float = 5.0,
    ) -> int:
        """Settle rounds until EVERY honest node confirms the offender;
        -1 on timeout. The bounded-rounds claim of the gossip design is
        measured here, not assumed."""
        rounds = 0
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.gossip_convergence(offender_id, among)["all"]:
                return rounds
            time.sleep(self.tick_s)
            rounds += 1
        return -1


def _reset_boards() -> None:
    get_quotas().reset()
    HEALTH.reset()
    EVIDENCE.reset()
    clear_fault_plan()


def run_wire_catalog(
    seed: int = 0,
    hosts: int = 4,
    attacks=ATTACK_NAMES,
    deadline_s: float | None = None,
) -> dict:
    """The full byzantine catalog over real TCP: every attack detected,
    the offender's demotion converged committee-wide through gossip
    (measured in rounds), the safety auditor green at the end."""
    _reset_boards()
    deadline = (
        time.perf_counter() + deadline_s if deadline_s is not None else None
    )
    h = WireHarness(seed=seed, hosts=hosts)
    try:
        for _ in range(2):
            if not h.commit_block(2):
                raise RuntimeError("clean wire round failed")
        assert EVIDENCE.count() == 0, "clean wire blocks raised evidence"
        results = []
        offender = h.adversary.node.node_id
        for name in attacks:
            r = h.run_attack(name)
            if name in GOSSIPED_ATTACKS:
                r["convergence_rounds"] = h.await_convergence(offender)
                r["gossip"] = h.gossip_convergence(offender)
            results.append(r)
            h.commit_block(2)
            if deadline is not None and time.perf_counter() > deadline:
                break
        h.catch_up()
        audit = h.audit()
        gossiped = [r for r in results if "gossip" in r]
        return {
            "scenario": "byzantine-wire",
            "seed": seed,
            "hosts": hosts,
            "adversary_index": h.adv_index,
            "attacks": results,
            "all_detected": (
                len(results) == len(attacks)
                and all(r["detected"] for r in results)
            ),
            "gossip_converged": (
                len(gossiped) > 0
                and all(
                    r["gossip"]["all"] and r["convergence_rounds"] >= 0
                    for r in gossiped
                )
            ),
            "convergence_rounds_max": max(
                (r["convergence_rounds"] for r in gossiped), default=-1
            ),
            "adversary_demoted": h.adversary_demoted(),
            "evidence_counts": EVIDENCE.counts(),
            "honest_height": h.height(),
            "audit": audit,
        }
    finally:
        h.stop()
        clear_fault_plan()


def run_wire_partition(
    seed: int = 0,
    hosts: int = 4,
    blocks_during: int = 2,
    heal_ms: float = 0.0,
) -> dict:
    """Partition/heal over real sockets: the minority host is cut off,
    the majority keeps committing (view-changing past stranded leaders),
    and on heal the laggard block-syncs back before the audit gate."""
    _reset_boards()
    h = WireHarness(seed=seed, hosts=hosts)
    try:
        if not h.commit_block(2):
            raise RuntimeError("pre-partition round failed")
        minority = [h._node_at((h.adv_index + 1) % hosts)]
        majority = [n for n in h.nodes if n not in minority]
        base = h.height()
        plan = h.cut(minority, heal_ms=heal_ms)
        committed = 0
        for _ in range(blocks_during):
            if h.commit_block_among(majority):
                committed += 1
        minority_height = minority[0].block_number()
        if heal_ms > 0:
            time.sleep(max(0.0, heal_ms / 1e3))
            h.heal(plan)  # re-dial abandoned links; the cut itself timed out
        else:
            h.heal(plan)
        # laggard recovery is block sync's job: drive maintain rounds
        # until the minority's durable chain catches the majority head
        deadline = time.monotonic() + h.settle_timeout_s
        while time.monotonic() < deadline:
            h.reconcile()
            if len({n.block_number() for n in h.nodes}) == 1:
                break
        heights = {n.block_number() for n in h.nodes}
        post = h.commit_block(2)
        audit = h.audit()
        return {
            "scenario": "wire-partition",
            "seed": seed,
            "hosts": hosts,
            "minority_host": h.host_of(minority[0]),
            "majority_committed": committed,
            "minority_stalled": minority_height <= base,
            "resynced": len(heights) == 1,
            "post_heal_commit": bool(post),
            "reconnects": plan.injected,
            "heights": sorted(n.block_number() for n in h.nodes),
            "audit": audit,
        }
    finally:
        h.stop()
        clear_fault_plan()


def run_wire_colluders(seed: int = 0, hosts: int = 7) -> dict:
    """Two colluding adversaries inside an n=7 committee: one
    equivocates as leader, the other forges QC votes — agreement must
    hold, BOTH must be demoted on every honest node, and the demotions
    must never cost quorum membership (demoted members' valid votes
    still count, so the 5 honest + 2 demoted committee keeps its 2f+1)."""
    _reset_boards()
    h = WireHarness(seed=seed, hosts=hosts)
    try:
        adv_a = h.adversary
        adv_b = ByzantineReplica(h._node_at((h.adv_index + 1) % hosts))
        h.honest = [
            n for n in h.nodes if n not in (adv_a.node, adv_b.node)
        ]
        if not h.commit_block(2):
            raise RuntimeError("clean n=7 round failed")
        r_a = h.run_attack("equivocation")
        conv_a = h.await_convergence(adv_a.node.node_id)
        h.adversary = adv_b  # the colluder takes the stage
        before = EVIDENCE.counts()
        h.attack_forged_qc_vote()
        after = EVIDENCE.counts()
        h.adversary = adv_a
        # the colluder's garbage-own-signature vote MUST always be caught
        # (bad_qc_vote): its slot is never re-voted, so the quorum
        # snapshot is guaranteed to judge it. The forged-victim frame is
        # dropped either way; its unattributable forged_qc_vote record
        # only lands when a quorum snapshot beats the victim's genuine
        # vote to the slot — deterministic at n=4 (pinned by the catalog),
        # a race at n=7's slower quorum, so it does not gate THIS run.
        delta = {
            k: after.get(k, 0) - before.get(k, 0)
            for k in ("bad_qc_vote", "forged_qc_vote")
        }
        r_b = {
            "attack": "forged_qc_vote",
            "evidence_delta": delta,
            "detected": delta["bad_qc_vote"] > 0,
        }
        conv_b = h.await_convergence(adv_b.node.node_id)
        # agreement + liveness with both adversaries demoted: the honest
        # majority (5 of 7) keeps committing and every node converges
        live = all(h.commit_block(2) for _ in range(2))
        h.catch_up()
        audit = h.audit()
        quotas = get_quotas()
        demoted = {
            "a": quotas.demoted(
                EVIDENCE_GROUP, validator_source(adv_a.node.node_id)
            ),
            "b": quotas.demoted(
                EVIDENCE_GROUP, validator_source(adv_b.node.node_id)
            ),
        }
        honest_undemoted = not any(
            quotas.demoted(EVIDENCE_GROUP, validator_source(n.node_id))
            for n in h.honest
        )
        return {
            "scenario": "wire-colluders",
            "seed": seed,
            "hosts": hosts,
            "attacks": [r_a, r_b],
            "all_detected": r_a["detected"] and r_b["detected"],
            "convergence_rounds": {"a": conv_a, "b": conv_b},
            "both_demoted": demoted["a"] and demoted["b"],
            "demoted": demoted,
            "honest_undemoted": honest_undemoted,
            "liveness_after_demotion": bool(live),
            "honest_height": h.height(),
            "audit": audit,
        }
    finally:
        h.stop()
        clear_fault_plan()
