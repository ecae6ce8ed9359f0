"""Scheduler — block-level execution + the commit 2PC.

Reference: bcos-scheduler/src/SchedulerImpl.cpp (executeBlock:150,
commitBlock:390, call:621) and BlockExecutive.cpp (fill txs from pool
:301-357, DAG/DMC dispatch :378-996, state root into the header :998-1061).
One executor here (the Air form); the DMC multi-executor sharding rides the
same interface and arrives with the multi-executor manager.

executeBlock splits a proposal into DAG-annotated txs (conflict-parallel,
Transaction::Attribute::DAG — Transaction.h:45-51) and serial txs, executes,
then fills the header with stateRoot (device XOR root), receiptsRoot and
txsRoot (device merkle), and gasUsed. commitBlock stages ledger rows +
executed state into one 2PC against the durable backend.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass

from ..crypto.suite import CryptoSuite
from ..executor.executor import TransactionExecutor
from ..ledger import Ledger
from ..observability import TRACER
from ..observability.flight import FLIGHT
from ..observability.pipeline import PIPELINE
from ..protocol.block import Block
from ..protocol.block_header import BlockHeader
from ..protocol.transaction import TransactionAttribute
from ..resilience.crashpoints import (
    InjectedCrash,
    crashpoint,
    ensure_env_crash_plan,
)
from ..storage.interfaces import TransactionalStorage, TwoPCParams
from ..storage.state_storage import StateStorage
from ..utils.error import ErrorCode
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY
from ..utils.worker import Worker

_log = get_logger("scheduler")

ensure_env_crash_plan()  # arm FISCO_CRASH_PLAN seams once per process


def pipeline_on() -> bool:
    """The throughput-campaign switch: ``FISCO_PIPELINE=0`` restores the
    lock-step block loop (execute force-syncs its roots, the checkpoint
    handler drives the 2PC inline, the sealer chains on the durable
    ledger head) as a byte-identical passthrough. Read per call so tests
    can flip it."""
    return os.environ.get("FISCO_PIPELINE", "1") != "0"


class SchedulerError(Exception):
    def __init__(self, code: ErrorCode, msg: str):
        super().__init__(msg)
        self.code = code


def _run_notify(cb, number: int, block) -> None:
    """One commit-notify delivery on the notify worker, accounted as the
    pipeline's notify stage (ws push / proof-plane warm build / sync hooks
    all ride this thread — its saturation is a real backpressure signal)."""
    with PIPELINE.busy("notify"):
        cb(number, block)


def _is_executor_loss(e: Exception) -> bool:
    """An RPC failure against a remote executor (Max form) — retryable
    after the fleet drops the dead member."""
    from ..service.rpc import ServiceRemoteError

    return isinstance(e, (ServiceRemoteError, ConnectionError, OSError))


@dataclass
class ExecutedBlock:
    header: BlockHeader
    block: Block
    tx_hashes: tuple[bytes, ...]  # proposal identity (same number ≠ same block)
    post_state: object = None  # StateStorage chained onto by block N+1's
    # speculative pre-execution (ref SchedulerInterface.h:76 preExecuteBlock)
    # pipeline mode: the three un-synced root resolvers (state, txs,
    # receipts) of a lazily-executed block — the device programs were
    # dispatched during execution, the sync is paid at quorum time
    # (_resolve_roots_locked), overlapping the consensus round-trip
    pending_roots: tuple | None = None


class Scheduler:
    def __init__(
        self,
        executor: TransactionExecutor,
        ledger: Ledger,
        backend: TransactionalStorage,
        suite: CryptoSuite,
        txpool=None,
        notify_worker=None,
        commit_worker=None,
    ):
        self.executor = executor
        self.ledger = ledger
        self.backend = backend
        self.suite = suite
        self.txpool = txpool
        self._executed: dict[int, ExecutedBlock] = {}
        # node tag for crash-point scoping (Node sets the pubkey prefix),
        # and the whole-node halt hook an injected crash on the commit
        # worker fires before killing the thread (Node wires it)
        self.crash_scope = ""
        self.on_fatal = None
        # storage-failover term (SchedulerManager.cpp schedulerTerm analog):
        # bumped by switch_term when the storage backend connection is lost
        self.term = 0
        # block-commit listeners: cb(number, committed Block-with-receipts)
        self.on_committed: list = []
        # succinct state plane (Node wires it when FISCO_STATE_PROOF=1):
        # execute-time previews feed header.state_commitment, commit-time
        # promotes freeze the height for proof serving
        self.state_plane = None
        self._lock = threading.RLock()
        # heights whose 2PC is in flight lock-free (see commit_block);
        # the cv serializes committers without holding the lock across IO.
        # The owning thread is tracked so switch_term — which the storage
        # layer invokes synchronously on the thread whose IO just failed —
        # can recognize its own in-flight commit and not wait on itself
        self._committing: set[int] = set()
        self._committing_thread: threading.Thread | None = None
        self._commit_done = threading.Condition(self._lock)
        # listeners drain on a dedicated thread: commit_block is called by the
        # PBFT engine under ITS lock, and a listener doing network I/O (ws
        # block notify to a stalled client) must never stall consensus.
        # Started here — commit_block has two concurrent callers (engine,
        # block sync) and Worker.start is not thread-safe. `notify_worker`
        # is the injection seam for deterministic tests (the interleave
        # scheduler harness posts inline: no unmanaged thread may race a
        # seeded schedule).
        self._notify = (
            notify_worker if notify_worker is not None else Worker("commit-notify")
        )
        self._notify.start()
        # pipeline mode: the 2PC legs run on this dedicated worker
        # (commit_block_async) so the engine thread and the sealer never
        # idle behind prepare/commit round-trips. `commit_worker` is the
        # same determinism seam as `notify_worker` (harnesses post inline).
        self._commits_queued = 0  # guarded by self._lock
        self._commit_worker = (
            commit_worker if commit_worker is not None else Worker("commit-2pc")
        )
        self._commit_worker.start()

    def stop(self) -> None:
        """Drain + stop the commit and notify workers (queued 2PCs land and
        their notifications deliver first — Worker.stop posts a sentinel
        and joins)."""
        self._commit_worker.stop()
        self._notify.stop()

    # -- pipeline-observatory probes (observability/pipeline.py) -------------

    def in_flight_commits(self) -> int:
        """Heights whose 2PC is currently in flight (0 or 1 by the commit
        serialization) — a backpressure watermark and the sealer's
        blocked-on discriminator. Deliberately LOCK-FREE: execute_block
        holds self._lock for the whole block execution, and this is polled
        by the sealer tick and the 25 ms watermark sampler — parking them
        there would make the observatory perturb the pipeline it measures.
        A stale read only shifts one tick's attribution."""
        return len(self._committing)

    def notify_depth(self) -> int:
        """Queued-but-undelivered commit notifications."""
        try:
            return self._notify._queue.qsize()
        except (AttributeError, NotImplementedError):
            return 0

    def commit_depth(self) -> int:
        """Async commits accepted but not yet durable (queued on the commit
        worker or mid-2PC) plus any sync commit in flight — the commit
        stage's backpressure watermark. Lock-free for the same reason as
        in_flight_commits."""
        return max(self._commits_queued, len(self._committing))

    def drain_commits(self, timeout: float = 30.0) -> bool:
        """Block until every queued/in-flight commit has landed (bench and
        test boundary: the ledger height is only meaningful once the
        pipelined 2PCs drain). Returns False on timeout."""
        deadline = time.monotonic() + timeout
        with self._lock:
            while self._commits_queued or self._committing:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._commit_done.wait(min(remaining, 0.5))
        return True

    def drain_notifications(self, timeout: float = 30.0) -> bool:
        """Block until every commit notification posted so far has been
        delivered (the proof plane's commit-time tree build rides that
        worker, beside the next block). ``drain_commits`` does not wait for
        it, on purpose: listeners are off the block path. Returns False on
        timeout."""
        done = threading.Event()
        self._notify.post(done.set)  # one FIFO worker: a barrier
        return done.wait(timeout)

    def staged_state(self, number: int):
        """Post-state overlay of a block whose commit has not landed yet —
        lets the engine read block-derived state (committee membership)
        at optimistic-advance time instead of waiting out the 2PC. None
        once the commit has booked (the durable ledger is current then)."""
        with self._lock:
            eb = self._executed.get(number)
            return eb.post_state if eb is not None else None

    # -- storage failover (SchedulerManager.cpp asyncSwitchTerm) -------------

    def switch_term(self) -> None:
        """Drop the in-flight execution term after a storage-backend loss.

        Reference: TiKVStorage's connection-loss handler triggers
        SchedulerManager::triggerSwitch, which abandons the current
        scheduler instance (its half-executed blocks reference state that
        may not have been durably staged) and starts term+1. Here the same
        reset clears the executed-block cache so consensus re-executes its
        proposals against the recovered backend instead of committing
        headers derived from writes the backend may have lost.
        """
        with self._lock:
            # an in-flight 2PC references _executed state and the backend
            # this switch is abandoning — wait it out (bounded by the RPC
            # timeout of the failing leg), exactly as the pre-r10 lock hold
            # serialized term switches behind the commit in progress.
            # UNLESS this thread IS the committer: the storage backend
            # invokes its switch handler synchronously on the thread whose
            # commit IO just failed, and waiting for our own marker (whose
            # cleanup only runs after this handler returns) would
            # self-deadlock — the pre-r10 RLock hold let this same-thread
            # call reenter and proceed, so keep that semantics
            while (
                self._committing
                and self._committing_thread is not threading.current_thread()
            ):
                self._commit_done.wait()
            self.term += 1
            dropped = sorted(self._executed)
            self._executed.clear()
            discard = getattr(self.executor, "discard_blocks_above", None)
            if discard is not None:
                discard(self.ledger.block_number())
        _log.warning(
            "storage switch: term -> %d, dropped in-flight blocks %s",
            self.term,
            dropped,
        )

    # -- executeBlock:150 ----------------------------------------------------

    def execute_block(
        self, block: Block, verify: bool = False, lazy_roots: bool = False
    ) -> BlockHeader:
        """Execute a proposal; returns the filled header. `verify` asserts
        the proposal's declared roots match execution (sync path).
        `lazy_roots` (pipeline mode, speculative pre-execution) returns a
        header whose roots are still pending device futures — dispatched,
        not synced — resolved under the lock when the commit-quorum
        execution hits the cache (or at the commit gate), so the dominant
        execute-stage device wait overlaps the consensus round-trip."""
        number = block.header.number
        proposal_ident = tuple(block.tx_hashes(self.suite))
        # the lock covers the whole execution: the executor's block context is
        # shared state, and two interleaved same-height executions would
        # corrupt each other's state layer
        # the stage marks (sp.stage) are the block's BlockTrace lines, its
        # record's `stages` and fisco_span_stage_seconds_total, all at once
        with TRACER.span(
            "scheduler.execute_block",
            block=number,
            stage_log=(_log, f"ExecuteBlock.{number}"),
        ) as sp, PIPELINE.busy("execute"):
            with self._lock:
                cached = self._executed.get(number)
                if (
                    cached is not None
                    and cached.tx_hashes == proposal_ident
                    and not verify
                ):
                    # same proposal re-executed (preExecute cache)
                    sp.set(cache="hit")
                    REGISTRY.counter_add(
                        "fisco_scheduler_preexec_hits_total",
                        help="commit-quorum executions served by the "
                        "pre-execution cache",
                    )
                    if lazy_roots:
                        return cached.header
                    sp.stage("cached")
                    return self._resolve_roots_locked(cached, sp)
                t0 = time.perf_counter()
                header = self._execute_block_locked(
                    block, verify, number, proposal_ident, lazy_roots, sp
                )
                from ..observability.tracer import trace_hex

                REGISTRY.observe(
                    "fisco_block_execute_latency_ms",
                    (time.perf_counter() - t0) * 1e3,
                    help="block execution wall latency (mtail block-exec "
                    "buckets)",
                    exemplar=trace_hex(sp.ctx),
                )
                sp.set(txs=len(block.transactions))
                return header

    def _execute_block_locked(
        self, block: Block, verify: bool, number: int, proposal_ident,
        lazy_roots: bool, sp,
    ) -> BlockHeader:
        # An in-flight lock-free 2PC (commit_block) used to mutate the
        # committing block's post-state overlay (ledger prewrite merge) —
        # a torn read for anything executing through it, so executions
        # drained the commit first. The staging is non-mutating now
        # (executor.prepare chains the ledger rows as a traverse view),
        # which makes ONE overlap sound: a speculative execution chained
        # strictly ABOVE every in-flight commit reads only through
        # overlays the 2PC never writes, so in pipeline mode it proceeds
        # while the commit worker round-trips — consensus on N+1 overlaps
        # the commit of N. Re-execution at or below a committing height
        # (a different proposal would wipe the committing cache entry)
        # still drains, exactly as the old whole-commit lock hold did.
        if self._committing:
            overlap = (
                pipeline_on()
                and number > max(self._committing)
                and self._executed.get(number - 1) is not None
                and getattr(self.executor, "supports_preexec", False)
            )
            if not overlap:
                with PIPELINE.blocked("2pc_commit"):
                    while self._committing:
                        self._commit_done.wait()

        # Height gate with block pipelining (preExecuteBlock,
        # SchedulerInterface.h:76 / StateMachine.cpp:47 asyncPreApply): the
        # next uncommitted height executes against the durable backend; any
        # height one past a contiguous executed-but-uncommitted chain
        # executes SPECULATIVELY against the previous block's post-state
        # overlay, so proposal N+1 runs while N's commit quorum round-trips.
        expected = self.ledger.block_number() + 1
        base = None
        if number != expected:
            prev = self._executed.get(number - 1)
            chain_ok = prev is not None and all(
                k in self._executed for k in range(expected, number)
            )
            if (
                not chain_ok
                or prev.post_state is None
                or not getattr(self.executor, "supports_preexec", False)
            ):
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK,
                    f"execute out of order: got {number}, expect {expected}",
                )
            base = prev.post_state

        txs = block.transactions
        if not txs and block.tx_metadata:
            if self.txpool is None:
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK, "no txpool to fill proposal"
                )
            fetched = self.txpool.fetch_txs(block.tx_metadata)
            if any(t is None for t in fetched):
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK, "proposal references unknown txs"
                )
            txs = fetched
            block.transactions = txs

        dag_idx = [
            i for i, t in enumerate(txs) if t.attribute & TransactionAttribute.DAG
        ]
        serial_idx = [
            i for i, t in enumerate(txs) if not (t.attribute & TransactionAttribute.DAG)
        ]
        # the gates, the fill and the DAG / serial split; `execute` is run_block
        sp.stage("fillBlock", txs=len(txs))

        def run_block():
            if base is not None:
                self.executor.next_block_header(block.header, base=base)
            else:
                self.executor.next_block_header(block.header)
            receipts = [None] * len(txs)
            if dag_idx:
                dag_rcs = self.executor.dag_execute_transactions(
                    [txs[i] for i in dag_idx]
                )
                for i, rc in zip(dag_idx, dag_rcs):
                    receipts[i] = rc
            if serial_idx:
                ser_rcs = self.executor.execute_transactions(
                    [txs[i] for i in serial_idx]
                )
                for i, rc in zip(serial_idx, ser_rcs):
                    receipts[i] = rc
            return receipts

        try:
            receipts = run_block()
        except Exception as e:
            # Max form: an executor died mid-block. The composite executor
            # already dropped it from the fleet (term bump); stateless
            # executors over shared storage make whole-block re-execution
            # sound — the SchedulerManager term-switch-and-retry
            # (TarsRemoteExecutorManager executor loss -> asyncSwitchTerm).
            if not _is_executor_loss(e) or not hasattr(
                self.executor, "replay_block_header"
            ):
                raise
            _log.warning(
                "executor fleet changed mid-block %d (%s): re-executing on "
                "the survivors", number, e,
            )
            receipts = run_block()
        block.receipts = receipts  # type: ignore[assignment]
        sp.stage("execute", dag=len(dag_idx), serial=len(serial_idx))

        header = block.header
        header.gas_used = sum(rc.gas_used for rc in block.receipts)
        # dispatch all three root programs before syncing any — each forced
        # sync parks the host until the device answers, and the three
        # computations are independent
        get_hash_async = getattr(self.executor, "get_hash_async", None)
        state_f = (
            get_hash_async() if get_hash_async else (lambda: self.executor.get_hash())
        )
        sp.stage("stateRoot")
        txs_f = block.calculate_txs_root_async(self.suite)
        sp.stage("txsRoot")
        receipts_f = block.calculate_receipts_root_async(self.suite)
        sp.stage("receiptsRoot")
        # pipeline mode, speculative pre-execution: all three programs are
        # dispatched (above), the sync is deferred to quorum time — the
        # device computes the roots while the prepare/commit votes
        # round-trip, instead of parking this thread (the observatory's
        # headline `execute blocked_on=device_plane` edge)
        lazy = lazy_roots and not verify and pipeline_on()
        pending = (state_f, txs_f, receipts_f) if lazy else None
        if not lazy:
            state_root = state_f()
            txs_root = txs_f()
            receipts_root = receipts_f()
            if verify and (
                (header.state_root != state_root)
                or (header.txs_root != txs_root)
                or (header.receipts_root != receipts_root)
            ):
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK,
                    f"block {number} root mismatch on verify",
                )
            header.state_root = state_root
            header.txs_root = txs_root
            header.receipts_root = receipts_root
            header.clear_hash_cache()
            sp.stage("roots", state_root=state_root.hex()[:16])
        else:
            REGISTRY.counter_add(
                "fisco_scheduler_lazy_roots_total",
                help="speculative executions returning pending (dispatched, "
                "un-synced) root futures",
            )
            sp.stage("roots", dispatched="lazy")

        if self.state_plane is not None:
            # incremental commitment update from THIS block's write set
            # (delta over touched pages — never a full state recompute).
            # Independent of the root futures, so the lazy path computes it
            # here too: the commitment is part of the hash preimage and must
            # be in place before anyone hashes the header.
            post = getattr(self.executor, "block_state", lambda n: None)(number)
            if post is not None:
                commitment = self.state_plane.preview(
                    number, list(post.traverse())
                )
                if verify:
                    # only judge proposals that CARRY a commitment — a peer
                    # with the plane off seals none, and inventing one here
                    # would change the header hash out from under its QC
                    if (
                        header.state_commitment
                        and header.state_commitment != commitment
                    ):
                        raise SchedulerError(
                            ErrorCode.SCHEDULER_INVALID_BLOCK,
                            f"block {number} state commitment mismatch on "
                            "verify",
                        )
                else:
                    header.state_commitment = commitment
                    header.clear_hash_cache()
                sp.stage("stateCommit")

        with self._lock:
            # anything executed ABOVE this height was chained on the state
            # this execution just replaced — drop those speculations
            for k in [k for k in self._executed if k > number]:
                self._executed.pop(k)
            discard = getattr(self.executor, "discard_blocks_above", None)
            if discard is not None:
                discard(number)
            self._executed[number] = ExecutedBlock(
                header,
                block,
                proposal_ident,
                post_state=getattr(self.executor, "block_state", lambda n: None)(
                    number
                ),
                pending_roots=pending,
            )
        sp.stage("store")
        return header

    def _resolve_roots_locked(self, eb: ExecutedBlock, sp=None) -> BlockHeader:
        """Sync a lazily-executed block's pending root futures into its
        header (runs under self._lock — single resolver). The wait is a
        device sync, attributed as such for the observatory, and the
        `roots` stage of the span `sp` it happens under (the caller marks
        what came before it)."""
        pend = eb.pending_roots
        if pend is not None:
            state_f, txs_f, receipts_f = pend
            header = eb.header
            with PIPELINE.blocked("device_plane"):
                header.state_root = state_f()
                header.txs_root = txs_f()
                header.receipts_root = receipts_f()
            header.clear_hash_cache()
            eb.pending_roots = None
            if sp is not None:
                sp.stage("roots")
        return eb.header

    # -- commitBlock:390 -----------------------------------------------------

    def commit_block(self, header: BlockHeader) -> None:
        number = header.number
        with TRACER.span(
            "scheduler.commit_block",
            block=number,
            stage_log=(_log, f"CommitBlock.{number}"),
        ) as sp, PIPELINE.busy("commit"):
            t0 = time.perf_counter()
            with self._lock:
                # committers serialize HERE, before the gate, exactly as the
                # old whole-commit lock did (so a pipelined N+1 committer
                # blocks until N is fully booked, keeping gate semantics and
                # notify order intact) — cv.wait releases the lock, so
                # execute_block callers are not starved while we queue
                if self._committing:
                    with PIPELINE.blocked("prior_commit"):
                        while self._committing:
                            self._commit_done.wait()
                cached = self._gate_commit_locked(header, sp)
            sp.stage("gate")
            # The prewrite reads and the 2PC legs run OUTSIDE the scheduler
            # lock: on the Pro/Max splits they round-trip to remote
            # executor/storage services, and holding self._lock across that
            # IO would serialize execute_block callers behind remote
            # latency (the runtime lock-order recorder flags it). The
            # in-flight marker keeps commits strictly serialized anyway.
            try:
                ledger_writes = StateStorage()
                self.ledger.prewrite_block(cached.block, ledger_writes)
                sp.stage("prewrite")
                params = TwoPCParams(number=number)
                # the 2PC legs as spans: on a remote executor/storage
                # split these parent the service-side svc.*.prepare/
                # commit spans
                FLIGHT.record(
                    "2pc", "prepare", scope=self.crash_scope,
                    height=number,
                )
                with TRACER.span(
                    "scheduler.2pc_prepare", block=number
                ), PIPELINE.blocked("2pc_prepare"):
                    staged = self.executor.prepare(
                        params, extra_writes=ledger_writes
                    )
                # rows the backend kept as they were / copied on the way
                # in (count_prepared; nothing from a remote executor)
                sp.stage("prepare", **(staged or {}))
                # crash window: the 2PC slot is durably staged, the
                # commit has not run — a reboot finds the prepared-but-
                # unresolved slot and must re-drive or roll it back
                # (Node's boot scan)
                crashpoint("scheduler.mid_2pc", self.crash_scope)
                FLIGHT.record(
                    "2pc", "commit", scope=self.crash_scope,
                    height=number,
                )
                with TRACER.span(
                    "scheduler.2pc_commit", block=number
                ), PIPELINE.blocked("2pc_commit"):
                    self.executor.commit(params)
                sp.stage("commit")
                FLIGHT.record(
                    "2pc", "booked", scope=self.crash_scope, height=number
                )
            except BaseException:
                # failed commit: clear the marker so recovery can re-drive
                with self._lock:
                    self._committing.discard(number)
                    self._committing_thread = None
                    self._commit_done.notify_all()
                raise
            with self._lock:
                self._committing.discard(number)
                self._committing_thread = None
                self._commit_done.notify_all()
                self._executed.pop(number, None)
                for n in [n for n in self._executed if n <= number]:
                    self._executed.pop(n)
                if self.txpool is not None:
                    # the proposal identity IS the block's tx-hash list —
                    # re-hashing every tx under the scheduler lock here was
                    # pure waste (the admission-time digests are in hand)
                    self.txpool.on_block_committed(
                        number, list(cached.tx_hashes)
                    )
                if self.state_plane is not None:
                    # the height's preview becomes the new base + a served
                    # height (cheap dict swaps; promote never throws)
                    self.state_plane.promote(
                        number, cached.block.header.hash(self.suite)
                    )
                # listeners run on the notify worker, never on the caller's
                # thread: the caller is the PBFT engine holding its own
                # RLock, so a blocking sendall to a stalled ws client here
                # would freeze consensus. Posting stays inside the lock
                # (post never blocks) so enqueue order matches commit order.
                block = cached.block
                for cb in list(self.on_committed):
                    self._notify.post(
                        lambda cb=cb: _run_notify(cb, number, block)
                    )
            sp.stage("booked")
            from ..observability.tracer import trace_hex

            REGISTRY.observe(
                "fisco_block_commit_latency_ms",
                (time.perf_counter() - t0) * 1e3,
                help="block commit wall latency (mtail block-commit buckets)",
                exemplar=trace_hex(sp.ctx),
            )

    def _gate_commit_locked(self, header: BlockHeader, sp) -> "ExecutedBlock":
        """Height-order gate + in-flight marker (runs under self._lock);
        returns the cached execution whose 2PC the caller drives lock-free.
        Roots still pending are synced here, as the `roots` stage of the
        commit span `sp` between two parts of its `gate`."""
        number = header.number
        # commits must land in height order: with the block pipeline, a
        # SPECULATIVE block N+1 is executed (and preparable) while N is
        # uncommitted — committing it first would stage only N+1's overlay
        # deltas, skip N's writes entirely, and advance current_number past
        # a hole. The execute gate can't enforce this; the commit gate must.
        expected = self.ledger.block_number() + 1
        if number != expected:
            raise SchedulerError(
                ErrorCode.SCHEDULER_INVALID_BLOCK,
                f"commit out of order: got {number}, expect {expected}",
            )
        # _committing is empty here: every committer drains it on the cv
        # before calling this gate, so a duplicate commit of an in-flight
        # height waits, then fails the height check above once N is booked
        cached = self._executed.get(number)
        if cached is None:
            raise SchedulerError(
                ErrorCode.SCHEDULER_INVALID_BLOCK, f"commit of unexecuted block {number}"
            )
        if cached.pending_roots is not None:
            sp.stage("gate")
            self._resolve_roots_locked(cached, sp)
        if cached.header.hash(self.suite) != header.hash(self.suite):
            raise SchedulerError(
                ErrorCode.SCHEDULER_INVALID_BLOCK,
                f"commit header mismatch for block {number}",
            )
        # carry QC signatures into the stored header
        cached.block.header = header
        self._committing.add(number)
        self._committing_thread = threading.current_thread()
        return cached

    # -- async commit (pipeline mode) ----------------------------------------

    def commit_block_async(self, header: BlockHeader, on_done=None) -> None:
        """Hand the 2PC to the dedicated commit worker and return — the
        engine advances its head optimistically while prepare/commit
        round-trip. Validates proposal identity NOW (same SchedulerError
        contract as commit_block for an unknown/mismatched header);
        height-order gating and the in-flight marker run on the worker,
        where the prior commit has already landed (FIFO). ``on_done(number,
        exc_or_None)`` reports the terminal outcome — a failure means the
        optimistic head must roll back to the durable ledger."""
        number = header.number
        with self._lock:
            cached = self._executed.get(number)
            if cached is None:
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK,
                    f"commit of unexecuted block {number}",
                )
            self._resolve_roots_locked(cached)
            if cached.header.hash(self.suite) != header.hash(self.suite):
                raise SchedulerError(
                    ErrorCode.SCHEDULER_INVALID_BLOCK,
                    f"commit header mismatch for block {number}",
                )
            self._commits_queued += 1
        REGISTRY.counter_add(
            "fisco_async_commits_total",
            help="block commits handed to the 2PC commit worker",
        )
        self._commit_worker.post(lambda: self._run_commit(header, on_done))

    def _run_commit(self, header: BlockHeader, on_done) -> None:
        """One queued 2PC on the commit worker. Exceptions are reported via
        ``on_done`` (never kill the worker); the marker/cv cleanup inside
        commit_block already ran on the failure path, so recovery
        (block sync, storage-failover re-drive) sees a clean scheduler."""
        exc = None
        try:
            self.commit_block(header)
        except InjectedCrash:
            # a planted crash on the commit worker IS process death for
            # this node: let it kill the worker thread (no on_done, no
            # rollback bookkeeping) — only the durable 2PC slot survives,
            # exactly what the reboot harness must reconcile. The fatal
            # hook (Node wiring) halts the REST of the node first — the
            # engine must not keep voting as a zombie quorum member while
            # its commit path is dead.
            if self.on_fatal is not None:
                self.on_fatal()
            raise
        except BaseException as e:  # noqa: BLE001 — reported, not swallowed
            exc = e
            REGISTRY.counter_add(
                "fisco_async_commit_failures_total",
                help="async 2PCs that failed terminally on the commit worker",
            )
            _log.error("async commit of block %d failed: %s", header.number, e)
        finally:
            with self._lock:
                self._commits_queued -= 1
                self._commit_done.notify_all()
        if on_done is not None:
            on_done(header.number, exc)

    # -- call:621 ------------------------------------------------------------

    def call(self, tx) -> "TransactionReceipt":  # noqa: F821
        return self.executor.call(tx)
