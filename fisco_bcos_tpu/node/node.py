"""Node — wires every subsystem into a running consensus participant.

Reference: libinitializer/Initializer.cpp:121-330 (storage → ledger → txpool
→ scheduler → executor → PBFT/sealer wiring) + ProtocolInitializer.cpp:51-99
(crypto suite selection: sm_crypto ? SM3+SM2 : Keccak256+Secp256k1 — the
seam where this framework's batch suites plug in).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..consensus import BlockValidator, PBFTConfig, PBFTEngine, Sealer
from ..consensus.storage import ConsensusStorage
from ..crypto.suite import CryptoSuite, KeyPair, ecdsa_suite, sm_suite
from ..executor import TransactionExecutor
from ..front import FrontService
from ..ledger import GenesisConfig, Ledger
from ..scheduler import Scheduler
from ..storage import MemoryStorage, SQLiteStorage
from ..sync import BlockSync, TransactionSync
from ..storage.interfaces import TransactionalStorage, TwoPCParams
from ..txpool import TxPool
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY

_log = get_logger("node")


@dataclass
class NodeConfig:
    """The config.ini/config.genesis analog (bcos-tool/NodeConfig.cpp)."""

    chain_id: str = "chain0"
    group_id: str = "group0"
    sm_crypto: bool = False
    db_path: str = ":memory:"  # sqlite path; ":memory:"/"" -> MemoryStorage
    # distributed backend (TiKVStorage analog): "host:port,host:port,..."
    # storage service endpoints; non-empty overrides db_path
    storage_endpoints: str = ""
    block_limit: int = 600
    pool_limit: int = 15000 * 9
    # storage_security (bcos-security DataEncryption): non-empty -> every
    # stored value is encrypted at rest with this key
    data_key: bytes = b""
    # external KeyCenter (KeyCenter.cpp): when set ("host:port" +
    # cipher_data_key), the node never holds its data key in config — it is
    # fetched and derived at boot, overriding data_key
    key_center: str = ""
    cipher_data_key: str = ""
    # Max topology (TarsRemoteExecutorManager): non-empty "host:port" hosts
    # an executor registry here and replaces the in-process executor with
    # the remote fleet (CompositeRemoteExecutor); port 0 picks a free port.
    # executor_min = executors to wait for at boot
    # (waitForExecutorConnection).
    executor_registry: str = ""
    executor_min: int = 1
    # multi-tenant admission quota for THIS group (txs/sec into the pool;
    # 0 = unlimited / env default FISCO_GROUP_ADMISSION_RATE). On a
    # multi-group host every group's pool shares one device plane — the
    # quota is what keeps an abusive group's flood from taxing the rest.
    admission_rate: float = 0.0
    admission_burst: float = 0.0  # 0 = 2x rate
    genesis: GenesisConfig = field(default_factory=GenesisConfig)


class Node:
    def __init__(
        self,
        config: NodeConfig,
        keypair: KeyPair | None = None,
        front: FrontService | None = None,
    ):
        self.config = config
        self.suite: CryptoSuite = sm_suite() if config.sm_crypto else ecdsa_suite()
        self.keypair = keypair or self.suite.signature_impl.generate_keypair()
        if config.storage_endpoints:
            from ..storage.distributed import DistributedStorage

            eps = []
            for hp in config.storage_endpoints.split(","):
                host, port = hp.strip().rsplit(":", 1)
                eps.append((host, int(port)))
            self.storage: TransactionalStorage = DistributedStorage(eps)
        else:
            self.storage = (
                MemoryStorage()
                if config.db_path in ("", ":memory:")
                else SQLiteStorage(config.db_path)
            )
        raw_storage = self.storage  # pre-encryption handle (failover seam)
        data_key = config.data_key
        if config.key_center:
            from ..security.key_center import KeyCenter

            host, port = config.key_center.rsplit(":", 1)
            data_key = KeyCenter(host, int(port)).get_data_key(
                config.cipher_data_key, config.sm_crypto
            )
        if data_key:
            from ..security import DataEncryption, EncryptedStorage

            self.storage = EncryptedStorage(
                self.storage, DataEncryption(data_key, config.sm_crypto)
            )
        config.genesis.chain_id = config.chain_id
        config.genesis.group_id = config.group_id
        self.ledger = Ledger(self.storage, self.suite)
        self.ledger.build_genesis(config.genesis)
        durable = config.db_path not in ("", ":memory:")
        self.txpool = TxPool(
            self.suite,
            self.ledger,
            chain_id=config.chain_id,
            group_id=config.group_id,
            pool_limit=config.pool_limit,
            block_limit=config.block_limit,
            persistent_store=self.storage if durable else None,
        )
        if config.admission_rate > 0:
            self.txpool.quotas.configure(
                config.group_id,
                config.admission_rate,
                config.admission_burst or None,
            )
        # degraded-mode registry: seed the components this node owns so
        # GET /health lists them from boot (unknown != ok for an operator)
        from ..resilience import HEALTH

        if config.storage_endpoints:
            HEALTH.ok("storage", "distributed backend mounted")
        self.executor_manager = None
        if config.executor_registry:
            # Max form: stateless executor fleet over the shared storage
            # service, discovered via the registry servant hosted here
            from ..service.remote_manager import (
                CompositeRemoteExecutor,
                RemoteExecutorManager,
            )

            host, port = config.executor_registry.rsplit(":", 1)
            self.executor_manager = RemoteExecutorManager(host, int(port))
            self.executor_manager.start()
            self.executor_manager.wait_for_executors(config.executor_min)
            self.executor = CompositeRemoteExecutor(self.executor_manager)
            # lifecycle tracing across the Max split: /trace/tx pulls the
            # executor processes' ring spans through the fleet. The source
            # holds the manager WEAKLY and removes itself once the manager
            # is gone — repeated Node constructions in one process must not
            # pin dead fleets or grow the source list without bound.
            import weakref

            from ..observability import critical_path

            mgr_ref = weakref.ref(self.executor_manager)

            def _fleet_spans(trace_ids, block):
                mgr = mgr_ref()
                if mgr is None:
                    try:
                        critical_path.SPAN_SOURCES.remove(_fleet_spans)
                    except ValueError:
                        pass
                    return []
                members = mgr.members()
                if not members:
                    return []
                from concurrent.futures import ThreadPoolExecutor

                from ..service.remote_manager import _guarded

                # _guarded marks an unreachable member dead (so the NEXT
                # /trace/tx request skips it instead of re-paying its
                # timeout); the parallel dial bounds this request to the
                # slowest member, not the sum over a half-dead fleet
                def one(m):
                    try:
                        return _guarded(
                            mgr, m, lambda: m.executor.trace_spans(trace_ids, block)
                        )
                    except Exception:
                        return []  # a dead executor must not kill the answer

                out = []
                with ThreadPoolExecutor(max_workers=min(8, len(members))) as pool:
                    for spans in pool.map(one, members):
                        out.extend(spans)
                return out

            critical_path.SPAN_SOURCES.append(_fleet_spans)
        else:
            self.executor = TransactionExecutor(
                self.storage,
                self.suite,
                is_wasm=config.genesis.is_wasm,
                wasm_gas_mode=config.genesis.wasm_gas_mode,
            )
        self.scheduler = Scheduler(
            self.executor, self.ledger, self.storage, self.suite, self.txpool
        )
        if self.executor_manager is not None:
            # fleet change mid-block = in-flight execution is suspect:
            # drop the term like a storage switch (asyncSwitchTerm analog)
            self.executor_manager.on_change.append(
                lambda _term: self.scheduler.switch_term()
            )
        # read-path proof plane (proofs/plane.py): frozen-tree cache warmed
        # at commit time, invalidated on rollback re-drive and failover;
        # ledger.tx_proof/receipt_proof delegate to it from here on.
        # FISCO_PROOF_PLANE=0 keeps the direct per-request rebuild path.
        from ..proofs import ProofPlane, proof_plane_enabled

        self.proof_plane = None
        if proof_plane_enabled():
            self.proof_plane = ProofPlane(self.ledger, self.suite)
            self.ledger.proof_plane = self.proof_plane
            self.scheduler.on_committed.append(self.proof_plane.on_committed)
            if hasattr(raw_storage, "on_rollback"):
                raw_storage.on_rollback.append(self.proof_plane.on_rolled_back)
            HEALTH.ok("proof-plane", "frozen-tree proof cache up")
        # succinct state plane (succinct/state_plane.py): incremental merkle
        # commitment over the whole KeyPage state, carried in the header and
        # served as membership proofs. FISCO_STATE_PROOF=0 (default) creates
        # nothing — headers stay byte-identical to the pre-succinct build.
        from ..succinct import state_proof_enabled

        self.state_plane = None
        if state_proof_enabled():
            from ..succinct import StatePlane

            self.state_plane = StatePlane(
                self.ledger, self.suite, backend=raw_storage
            )
            self.scheduler.state_plane = self.state_plane
            self.ledger.state_plane = self.state_plane
            if hasattr(raw_storage, "on_rollback"):
                raw_storage.on_rollback.append(self.state_plane.on_rolled_back)
            HEALTH.ok(
                "state-plane",
                f"state commitments up (hasher={self.state_plane.hasher}, "
                f"pages={self.state_plane.n_pages})",
            )
        # storage failover seam (Initializer.cpp:225-235): backend loss
        # drops the in-flight scheduler term instead of wedging consensus
        # (and clears the proof cache — the recovered backend may disagree
        # about any height the cache froze)
        if hasattr(raw_storage, "set_switch_handler"):

            def _on_storage_switch() -> None:
                self.scheduler.switch_term()
                if self.proof_plane is not None:
                    self.proof_plane.on_failover()
                if self.state_plane is not None:
                    self.state_plane.on_failover()

            raw_storage.set_switch_handler(_on_storage_switch)
        # injected front = multi-group hosting (gateway/group.py GroupGateway
        # hands each group its own front over one shared transport)
        self.front = front if front is not None else FrontService(self.keypair.pub)
        ledger_cfg = self.ledger.ledger_config()
        self.pbft_config = PBFTConfig(
            suite=self.suite,
            keypair=self.keypair,
            nodes=ledger_cfg.consensus_nodes,
            leader_period=ledger_cfg.leader_period,
            head=self.ledger.block_number(),
        )
        self.engine = PBFTEngine(
            self.pbft_config,
            self.scheduler,
            self.txpool,
            self.ledger,
            self.front,
            consensus_storage=ConsensusStorage(self.storage) if durable else None,
        )
        self.sealer = Sealer(self.pbft_config, self.txpool, self.ledger, self.engine)
        # crash-point scoping (resilience/crashpoints.py): tag this node's
        # consensus/commit seams so a multi-node process can kill exactly
        # one replica deterministically
        crash_scope = self.keypair.pub.hex()[:8]
        self.engine.crash_scope = crash_scope
        self.sealer.crash_scope = crash_scope
        self.scheduler.crash_scope = crash_scope
        # fleet observatory (ISSUE 16): per-node round ledger on the engine
        # + the ModuleID 4007 federation endpoint. FISCO_FLEET_OBS=0 leaves
        # the engine on the shared noop ledger and registers nothing.
        from ..observability.roundlog import RoundLedger, fleet_obs_enabled

        self.fleet = None
        if fleet_obs_enabled():
            self.engine.roundlog = RoundLedger(node_tag=crash_scope)
            from ..observability.fleet import FleetService

            self.fleet = FleetService(self)
        # evidence gossip (ISSUE 17): byzantine detections re-broadcast as
        # signed, self-attributing records on ModuleID 4008 so demotion
        # converges on every honest node. FISCO_EVIDENCE_GOSSIP=0 leaves
        # engine.gossip unwired (detections stay local, as before).
        if os.environ.get("FISCO_EVIDENCE_GOSSIP", "1") != "0":
            from ..consensus.gossip import EvidenceGossip

            self.engine.gossip = EvidenceGossip(
                self.engine, self.front, self.keypair
            )
        # one injected crash anywhere kills the WHOLE node: a commit-worker
        # death halts the engine (no zombie quorum votes), and block sync
        # reads the engine's halt state (no durable writes after death)
        self.scheduler.on_fatal = self._halt_injected
        self.block_validator = BlockValidator(self.suite)
        self.block_sync = BlockSync(
            self.ledger,
            self.scheduler,
            self.front,
            consensus=self.engine,
            validator=self.block_validator,
            group_id=config.group_id,
        )
        self.tx_sync = TransactionSync(self.txpool, self.front)
        # proposal straggler fetch (asyncVerifyBlock's fetch-missing hook)
        self.engine.fetch_missing_fn = self.tx_sync.fetch_missing
        # AMOP topic routing (bcos-gateway/libamop); ws sessions attach later
        from ..gateway.amop import AMOPService

        self.amop = AMOPService(self.front)
        # shared device-verification plane: spin the worker (and its queue
        # gauges) up BEFORE consensus traffic so the first proposal never
        # races the thread start
        from ..device.plane import get_plane
        from ..utils.jaxenv import device_identity

        get_plane()
        ident = device_identity()
        HEALTH.ok(
            "device-plane",
            f"coalescing scheduler up on platform={ident['platform']} "
            f"device_kind={ident['device_kind']} count={ident['count']}",
        )
        # pipeline observatory (ISSUE 9): backpressure watermark probes at
        # every inter-stage boundary, sampled by one background thread into
        # bounded timelines (GET /pipeline + Chrome-trace counter events).
        # First registration wins — in a multi-node test process the entry
        # node's queues are the observed ones. FISCO_PIPELINE_OBS=0 skips
        # registration entirely (add_probe refuses, sampler never starts).
        from ..observability.pipeline import PIPELINE

        if PIPELINE.enabled:
            PIPELINE.add_probe("txpool.pending", self.txpool.pending_count)
            PIPELINE.add_probe("sealer.backlog", self.txpool.unsealed_count)
            PIPELINE.add_probe(
                "scheduler.inflight_2pc", self.scheduler.in_flight_commits
            )
            PIPELINE.add_probe(
                "scheduler.notify_queue", self.scheduler.notify_depth
            )
            PIPELINE.add_probe(
                "scheduler.commit_queue", self.scheduler.commit_depth
            )
            PIPELINE.add_probe("device_plane", get_plane().lane_depths)
            if self.proof_plane is not None:
                PIPELINE.add_probe(
                    "proof_plane.pending", self.proof_plane.pending_builds
                )
            PIPELINE.ensure_sampler()
        # device observatory (ISSUE 13): jax compile/cache hooks feeding
        # the compile ledger + the per-device live-buffer watermark probe.
        # FISCO_DEVICE_OBS=0 refuses the whole installation (noop layer).
        from ..observability.device import install_observatory

        install_observatory()
        if durable:
            # restart path, order matters: resolve any 2PC slot a crash
            # stranded BEFORE the pool re-imports (a rolled-back block's
            # txs must come back as pending), then re-admit durably-stored
            # pool txs (signatures re-verified on device;
            # Initializer.cpp:188-195 analog)
            self._reconcile_pending_2pc(raw_storage)
            self.txpool.reload_persisted()

    def _reconcile_pending_2pc(self, raw_storage) -> None:
        """Boot-time 2PC reconciliation for single-backend local storage.

        A node killed between ``prepare`` and ``commit`` leaves a durable
        prepared-but-unresolved slot (sqlite ``pending_2pc``). Without a
        separate commit witness the slot must be ROLLED BACK, never rolled
        forward: committing writes consensus never acknowledged could fork
        the chain, while rolling back merely re-runs work — the prepared
        proposal survives in ConsensusStorage for the view-change re-offer
        and block sync re-drives whatever the committee committed without
        us. Rolling back also kills a subtler poison: a later, *different*
        proposal at the same height would otherwise 2PC-merge into the
        stale slot and commit the dead proposal's rows alongside its own.
        Distributed backends keep their witness-based recovery
        (``recover_in_flight``) and are excluded here.
        """
        if hasattr(raw_storage, "recover_in_flight"):
            return
        pending = getattr(self.storage, "pending_numbers", None)
        if pending is None:
            return
        stale = pending()
        if not stale:
            return
        for n in stale:
            self.storage.rollback(TwoPCParams(number=n))
        REGISTRY.counter_add(
            "fisco_2pc_boot_rollbacks_total",
            float(len(stale)),
            help="prepared-but-unresolved 2PC slots rolled back at node "
            "boot (crash recovery)",
        )
        _log.warning(
            "boot recovery: rolled back %d stranded 2PC slot(s) %s "
            "(ledger at %d; consensus re-drives)",
            len(stale),
            stale,
            self.ledger.block_number(),
        )

    def _halt_injected(self) -> None:
        """Whole-node halt on an injected crash outside the engine's own
        message boundary (the commit worker, the runtime drive loop): the
        engine goes silent, and block sync's dead-node check follows it —
        a process-death emulation must not leave a zombie that votes or
        durably commits."""
        self.engine._crashed = True
        # black box: the whole-node halt is a death door — flush the flight
        # ring (the crash point's own flush may predate the halt reason)
        from ..observability.flight import FLIGHT, flush_node

        FLIGHT.record(
            "halt", "fatal_injected", scope=self.engine.crash_scope
        )
        flush_node(self, "fatal_halt")
        _log.error(
            "injected crash — node %s halted (reboot to recover)",
            self.node_id.hex()[:8],
        )

    def stop(self, timeout: float = 30.0, close_storage: bool = True) -> bool:
        """Clean shutdown: quiesce consensus, DRAIN the commit-2pc worker
        — every queued/in-flight async 2PC lands durably — then stop the
        scheduler workers and tear down storage. The drain-before-teardown
        order is the point: stopping storage under a half-prepared 2PC
        would strand a slot that previously only the crash path could
        produce. Returns False if the drain timed out (the stop still
        completes — an operator kill must not hang forever)."""
        from ..observability.flight import FLIGHT, flush_node

        FLIGHT.record("halt", "stop", scope=self.engine.crash_scope)
        flush_node(self, "stop")
        self.engine.stop_worker()
        if self.engine._crashed:
            # an injected crash halted this node — possibly by killing the
            # commit-2pc worker mid-flight, after which queued commits can
            # never drain. Don't block the full drain timeout on a node
            # that is dead by design: boot recovery owns its stranded slots.
            drained = False
        else:
            drained = self.scheduler.drain_commits(timeout)
            if not drained:
                _log.error(
                    "stop: commit worker did not drain within %.0fs — a 2PC "
                    "may be stranded (boot recovery will resolve it)",
                    timeout,
                )
        self.scheduler.stop()
        if close_storage:
            close = getattr(self.storage, "close", None)
            if close is not None:
                close()
        return drained

    def warmup(self, batch_sizes: tuple[int, ...] = (8,)) -> None:
        """Pre-compile (or load from the persistent cache) the batch
        admission program for the ladder bucket each batch size implies, so
        the first live proposal doesn't pay XLA compile latency inside the
        consensus timeout window. The warm batch takes the same route a
        live batch of that size takes; where that route is the native host
        loop (below the cutover, or any size on a CPU backend) there is no
        program to compile, and the log says so instead of claiming warm
        kernels. Payloads are padded into the two-block keccak bucket that
        precompiled calls land in — the shape key is (bucket, blocks).

        Besides the sizes asked for, the one block sync re-verifies in a
        call when this node has to catch up: whole blocks of the chain's
        ``tx_count_limit`` up to ``VERIFY_LANES_MAX`` lanes (ten 1,000-tx
        blocks: the 10,240 bucket)."""
        from ..device.dispatch import use_native_batch
        from ..ops.hash_common import bucket_batch
        from ..protocol.transaction import Transaction
        from ..sync.block_sync import VERIFY_LANES_MAX
        from ..txpool.validator import batch_admit

        limit = max(1, self.ledger.ledger_config().tx_count_limit)
        gather = max(limit, VERIFY_LANES_MAX // limit * limit)
        warmed: set[int] = set()
        for b in (*batch_sizes, gather):
            if bucket_batch(b) in warmed:
                continue
            warmed.add(bucket_batch(b))
            if use_native_batch(b, "admission"):
                _log.info(
                    "warmup: a batch of %d rides the native host loop on "
                    "this backend — no device program to compile", b
                )
                continue
            bucket = bucket_batch(b)
            txs = []
            for i in range(bucket):
                tx = Transaction(
                    chain_id=self.config.chain_id,
                    nonce=f"warm{i}",
                    input=b"\x00" * 132,
                )
                tx.signature = b"\x01" * self.suite.signature_impl.sig_len
                txs.append(tx)
            batch_admit(txs, self.suite)  # validity is irrelevant; shapes compile
            _log.info("warmup: admission program ready for bucket %d", bucket)

    @property
    def node_id(self) -> bytes:
        return self.keypair.pub

    def block_number(self) -> int:
        return self.ledger.block_number()

    def is_sealer(self) -> bool:
        return self.pbft_config.my_index is not None
