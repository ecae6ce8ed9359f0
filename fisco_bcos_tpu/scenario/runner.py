"""Scenario runner — drive a generated workload against a live chain and
emit the per-group artifact.

Deployment shape: one host set (default 4, the BASELINE PBFT quorum), one
:class:`~fisco_bcos_tpu.gateway.group.GroupGateway` mux per host over one
in-proc transport, one :class:`~fisco_bcos_tpu.node.Node` per (host,
group) — the multi-group topology of tests/test_multigroup.py at bench
scale. Every group shares the process's ONE DevicePlane and ONE
:class:`~fisco_bcos_tpu.txpool.quota.AdmissionQuotas` policer, which is
the point: the scenarios exist to prove (or break) the isolation between
tenants of shared machinery.

Event driving: batches submit at the group's next-height leader (the
test_multigroup pattern — gossip via ``tx_sync.maintain()`` fills the
replicas), sealing interleaves with submission so pools never grow
unboundedly, and a final drain loop commits the tail. Per-group stats
count every admission verdict, per-tx commit latency (submit→commit wall
time) and committed TPS over the measured window.

The artifact is JSON-ready: per-group breakdowns, the quota policer's
shed/demotion snapshot, plane stats, the health registry, and the
determinism digest of everything submitted.
"""

from __future__ import annotations

import time

from ..utils.log import get_logger
from .base import Scenario, SubmitTxs, get_scenario

_log = get_logger("scenario")


class _GroupStats:
    __slots__ = (
        "submitted", "admitted", "rejected", "committed", "blocks",
        "latencies_ms", "t_submit",
    )

    def __init__(self):
        self.submitted = 0
        self.admitted = 0
        self.rejected: dict[str, int] = {}
        self.committed = 0
        self.blocks = 0
        self.latencies_ms: list[float] = []
        # admitted tx hash -> submit wall time (consumed at commit)
        self.t_submit: dict[bytes, float] = {}


def _pctl(xs: list[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


class ScenarioRunner:
    """One scenario run on a fresh chain. ``scale`` multiplies workload
    sizes; ``seal_every`` batches a seal pass between that many submit
    events (1 = seal eagerly, larger = deeper pools / bigger blocks)."""

    def __init__(
        self,
        scenario: Scenario | str,
        seed: int = 0,
        hosts: int = 4,
        scale: float = 1.0,
        seal_every: int = 4,
        block_cap: int = 2000,
        deadline_s: float | None = None,
    ):
        self.scenario = (
            get_scenario(scenario) if isinstance(scenario, str) else scenario
        )
        self.seed = int(seed)
        self.hosts = int(hosts)
        self.scale = float(scale)
        self.seal_every = max(1, int(seal_every))
        self.block_cap = int(block_cap)
        self.deadline_s = deadline_s
        self.error: str | None = None

    # -- chain construction ---------------------------------------------------

    def _build_chain(self):
        from ..front import InprocGateway
        from ..gateway.group import GroupGateway
        from ..ledger import ConsensusNode, GenesisConfig
        from ..node import Node, NodeConfig

        suite_secrets = [0x5CE9_0000 + i for i in range(self.hosts)]
        from ..crypto.suite import ecdsa_suite

        suite = ecdsa_suite()
        keypairs = [
            suite.signature_impl.generate_keypair(secret=s) for s in suite_secrets
        ]
        committee = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
        transport = InprocGateway(auto=True)
        hosts = []
        for kp in keypairs:
            mux = GroupGateway(kp.pub)
            transport.connect(mux)
            nodes = {}
            for g in self.scenario.groups:
                cfg = NodeConfig(
                    group_id=g,
                    admission_rate=self.scenario.quota_rate,
                    genesis=GenesisConfig(
                        group_id=g,
                        consensus_nodes=list(committee),
                        tx_count_limit=self.block_cap,
                    ),
                )
                nodes[g] = Node(cfg, keypair=kp, front=mux.register_group(g))
            hosts.append({"mux": mux, "nodes": nodes})
        return hosts

    def _leader(self, hosts, group: str):
        any_node = hosts[0]["nodes"][group]
        number = any_node.block_number() + 1
        idx = any_node.pbft_config.leader_index(number, 0)
        target = any_node.pbft_config.nodes[idx].node_id
        return next(
            h["nodes"][group]
            for h in hosts
            if h["nodes"][group].node_id == target
        )

    # -- driving --------------------------------------------------------------

    def _seal_group(self, hosts, group: str, stats: _GroupStats) -> bool:
        """One sealing attempt for the group's next height; on commit,
        settle latency for every tx that left the pool."""
        leader = self._leader(hosts, group)
        if leader.txpool.unsealed_count() == 0:
            return False
        before = leader.block_number()
        if not leader.sealer.seal_and_submit():
            return False
        after = leader.block_number()
        if after <= before:
            return False
        now = time.perf_counter()
        # settle committed txs from the LEDGER's record of the new blocks
        # (leaders rotate per height, so pool membership on any one node is
        # not a commit witness)
        for number in range(before + 1, after + 1):
            stats.blocks += 1
            for h in leader.ledger.tx_hashes_by_number(number):
                t0 = stats.t_submit.pop(h, None)
                if t0 is not None:
                    stats.latencies_ms.append((now - t0) * 1e3)
                    stats.committed += 1
        return True

    def run(self) -> dict:
        """Execute the scenario; returns the artifact dict."""
        import hashlib

        from ..resilience import HEALTH
        from ..resilience.faults import clear_fault_plan, install_fault_plan
        from ..txpool.quota import get_quotas

        t_wall = time.perf_counter()
        deadline = (
            t_wall + self.deadline_s if self.deadline_s is not None else None
        )
        hosts = self._build_chain()
        stats = {g: _GroupStats() for g in self.scenario.groups}
        digest = hashlib.sha256()
        plan = self.scenario.fault_plan(self.seed)
        if plan is not None:
            install_fault_plan(plan)
        try:
            t0 = time.perf_counter()
            n_events = 0
            for ev in self.scenario.events(self.seed, self.scale):
                self._apply(hosts, ev, stats[ev.group], digest)
                n_events += 1
                if n_events % self.seal_every == 0:
                    for g in self.scenario.groups:
                        self._seal_group(hosts, g, stats[g])
                if deadline is not None and time.perf_counter() > deadline:
                    self.error = "scenario stopped at wall-clock deadline"
                    break
            # drain: commit the tail (a stalled group must not hang the
            # run). Gate on EVERY host's pool, not host 0's replica — the
            # submissions landed at the rotating leader and gossip may lag
            # (sync-storm's delay plan), so an empty host-0 pool does not
            # mean the group is drained.
            for g in self.scenario.groups:
                stalls = 0
                while (
                    any(
                        h["nodes"][g].txpool.unsealed_count() > 0
                        for h in hosts
                    )
                    and stalls < 3
                ):
                    if deadline is not None and time.perf_counter() > deadline:
                        self.error = self.error or "drain hit deadline"
                        break
                    if not self._seal_group(hosts, g, stats[g]):
                        stalls += 1
            dt = time.perf_counter() - t0
        finally:
            if plan is not None:
                clear_fault_plan()
        quotas = get_quotas()
        groups_doc = {}
        for g, st in stats.items():
            tip = hosts[0]["nodes"][g].block_number()
            heights = {h["nodes"][g].block_number() for h in hosts}
            if len(heights) != 1:
                self.error = self.error or (
                    f"group {g} replicas diverged: heights {sorted(heights)}"
                )
            groups_doc[g] = {
                "abusive": g in self.scenario.abusive_groups,
                "submitted": st.submitted,
                "admitted": st.admitted,
                "rejected": dict(sorted(st.rejected.items())),
                "committed": st.committed,
                "blocks": st.blocks,
                "height": tip,
                "tps": round(st.committed / dt, 2) if dt > 0 else 0.0,
                "latency_ms_p50": round(_pctl(st.latencies_ms, 0.50), 2),
                "latency_ms_p95": round(_pctl(st.latencies_ms, 0.95), 2),
            }
        doc = {
            "scenario": self.scenario.name,
            "seed": self.seed,
            "scale": self.scale,
            "hosts": self.hosts,
            "wall_s": round(time.perf_counter() - t_wall, 3),
            "measured_s": round(dt, 3),
            "events": n_events,
            "groups": groups_doc,
            "quotas": quotas.snapshot(),
            "health": HEALTH.snapshot(),
            "faults_injected": plan.injected if plan is not None else 0,
            "determinism_digest": digest.hexdigest(),
        }
        from ..device.plane import get_plane

        plane = get_plane()
        plane.drain(10.0)
        doc["device_plane"] = plane.stats()
        if self.error:
            doc["error"] = self.error
        return doc

    @staticmethod
    def _reset_shared_state() -> None:
        """Fresh policer/health state so back-to-back runs in one process
        (the isolation bench's solo + combined legs) don't bleed quota
        debt, demotions or degradations into each other."""
        from ..resilience import HEALTH
        from ..txpool.quota import get_quotas

        get_quotas().reset()
        HEALTH.reset()

    def _apply(self, hosts, ev: SubmitTxs, st: _GroupStats, digest) -> None:
        from ..txpool.txpool import _REJECT_REASON
        from ..utils.error import ErrorCode

        digest.update(ev.encode())
        node = self._leader(hosts, ev.group)
        t0 = time.perf_counter()
        results = node.txpool.submit_batch(ev.txs, lane=ev.lane, source=ev.source)
        st.submitted += len(ev.txs)
        for r in results:
            if r.status == ErrorCode.SUCCESS:
                st.admitted += 1
                st.t_submit[r.tx_hash] = t0
            else:
                reason = _REJECT_REASON.get(r.status, "static")
                st.rejected[reason] = st.rejected.get(reason, 0) + 1
        # gossip payloads so replicas can execute whatever gets sealed
        node.tx_sync.maintain()
