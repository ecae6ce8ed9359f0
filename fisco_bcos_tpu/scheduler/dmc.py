"""DMC — Deterministic Multi-Contract scheduling across executor shards.

Reference: bcos-scheduler/src/BlockExecutive.cpp DMCExecute:832-996 (round
loop: per-contract DmcExecutor::go under tbb, join batch status, paused ⇒
next round), DmcExecutor.cpp (per-(executor, contract) message pools, status
ERROR/NEED_PREPARE/PAUSED/FINISHED, cross-contract calls migrating messages
via f_onSchedulerOut :239), GraphKeyLocks.{h,cpp} (wait-for graph, deadlock
revert), DmcStepRecorder.h:15-60 (per-round checksums of every message sent/
received — the cross-executor nondeterminism detector).

This is the "state sharded by contract address across executors" axis of the
reference's parallelism inventory (SURVEY.md §2.8). The live path:

- A tx starts an :class:`~fisco_bcos_tpu.executor.executor.Executive` on its
  contract's shard. When the contract calls a contract on ANOTHER shard, the
  executive **pauses** (generator parked) and a MESSAGE migrates to the
  target shard, where it runs as a sub-executive of the same context; its
  FINISHED/REVERT response migrates back and resumes the parked frames —
  the CoroutineTransactionExecutive suspend/resume protocol without native
  stacks.
- **Key locks**: every executive tracks the (table, key) rows it touched.
  Completion (and every pause) must acquire those locks in
  :class:`GraphKeyLocks`; a conflict means another in-flight context owns
  the row, so the executive's work is discarded and the whole context chain
  retries in a later round (optimistic execution + round-boundary lock
  validation — same observable protocol as the reference's in-execution
  acquisition, with the wait-for graph feeding the same deadlock detector).
- **Deadlock**: a wait-for cycle reverts one victim context
  (DmcExecutor::detectLockAndRevert analog): its executives are dropped
  everywhere, its locks released, and the tx gets a REVERT receipt.
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from enum import IntEnum

from ..codec.flat import FlatReader, FlatWriter
from ..executor.evm import EVMCall, EVMResult
from ..observability import BATCH_BUCKETS, TRACER
from ..protocol.receipt import LogEntry, TransactionReceipt, TransactionStatus
from ..protocol.transaction import Transaction
from ..storage.entry import Entry
from ..storage.state_storage import StateStorage
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY
from .key_locks import GraphKeyLocks

_log = get_logger("dmc")


class MsgType(IntEnum):
    TXHASH = 0
    MESSAGE = 1  # call request
    FINISHED = 2
    REVERT = 3


@dataclass
class ExecutionMessage:
    """Scheduler <-> executor unit (bcos-framework ExecutionMessage analog)."""

    type: MsgType = MsgType.MESSAGE
    context_id: int = 0  # tx index in the block
    seq: int = 0
    from_addr: bytes = b""
    to_addr: bytes = b""
    sender: bytes = b""  # frame sender (caller contract or tx origin)
    origin: bytes = b""  # tx origin
    data: bytes = b""
    static_call: bool = False
    create: bool = False
    kind: str = "call"  # call|delegatecall|callcode|staticcall (frame kind)
    storage_addr: bytes = b""  # storage context (≠ to_addr for delegatecall)
    value: int = 0
    abi: bytes = b""
    gas: int = 0
    status: int = 0
    gas_used: int = 0
    logs: list = field(default_factory=list)
    key_locks: list = field(default_factory=list)
    create_address: bytes = b""

    def encode_into(self, w: FlatWriter) -> None:
        """Wire form for cross-process DMC (the ExecutionMessage the
        reference ships over Tars — bcos-tars-protocol ExecutionMessage.tars)."""
        w.u8(int(self.type))
        w.u64(self.context_id)
        w.u64(self.seq)
        w.bytes_(self.from_addr)
        w.bytes_(self.to_addr)
        w.bytes_(self.sender)
        w.bytes_(self.origin)
        w.bytes_(self.data)
        w.u8(1 if self.static_call else 0)
        w.u8(1 if self.create else 0)
        w.str_(self.kind)
        w.bytes_(self.storage_addr)
        w.bytes_(self.value.to_bytes(32, "big"))
        w.bytes_(self.abi)
        w.u64(self.gas)
        w.i64(self.status)
        w.u64(self.gas_used)
        w.seq(self.logs, lambda w2, e: e.encode_into(w2))
        w.seq(
            self.key_locks,
            lambda w2, kl: (w2.str_(kl[0]), w2.bytes_(kl[1])),
        )
        w.bytes_(self.create_address)

    @classmethod
    def decode_from(cls, r: FlatReader) -> "ExecutionMessage":
        return cls(
            type=MsgType(r.u8()),
            context_id=r.u64(),
            seq=r.u64(),
            from_addr=r.bytes_(),
            to_addr=r.bytes_(),
            sender=r.bytes_(),
            origin=r.bytes_(),
            data=r.bytes_(),
            static_call=bool(r.u8()),
            create=bool(r.u8()),
            kind=r.str_(),
            storage_addr=r.bytes_(),
            value=int.from_bytes(r.bytes_(), "big"),
            abi=r.bytes_(),
            gas=r.u64(),
            status=r.i64(),
            gas_used=r.u64(),
            logs=r.seq(LogEntry.decode_from),
            key_locks=r.seq(lambda r2: (r2.str_(), r2.bytes_())),
            create_address=r.bytes_(),
        )


def encode_messages(msgs: list[ExecutionMessage]) -> bytes:
    w = FlatWriter()
    w.seq(msgs, lambda w2, m: m.encode_into(w2))
    return w.out()


def decode_messages(buf: bytes) -> list[ExecutionMessage]:
    r = FlatReader(buf)
    out = r.seq(ExecutionMessage.decode_from)
    r.done()
    return out


class DmcStepRecorder:
    """Running checksums of messages per DMC round (DmcStepRecorder.h).
    Divergent checksums across executors/replicas expose nondeterminism."""

    def __init__(self) -> None:
        self.round = 0
        self._send = hashlib.sha256()
        self._recv = hashlib.sha256()
        self.history: list[tuple[int, str, str]] = []

    @staticmethod
    def _digest_msg(m: ExecutionMessage) -> bytes:
        return b"|".join(
            [
                bytes([m.type]),
                m.context_id.to_bytes(8, "little"),
                m.seq.to_bytes(8, "little"),
                m.from_addr,
                m.to_addr,
                m.data,
                m.status.to_bytes(4, "little", signed=True),
            ]
        )

    def record_send(self, msgs: list[ExecutionMessage]) -> None:
        for m in msgs:
            self._send.update(self._digest_msg(m))

    def record_recv(self, msgs: list[ExecutionMessage]) -> None:
        for m in msgs:
            self._recv.update(self._digest_msg(m))

    def next_round(self) -> tuple[str, str]:
        send, recv = self._send.hexdigest()[:16], self._recv.hexdigest()[:16]
        self.history.append((self.round, send, recv))
        _log.debug("DMC round %d checksums send=%s recv=%s", self.round, send, recv)
        self.round += 1
        return send, recv


class TrackingStorage(StateStorage):
    """Overlay that records every (table, key) it touches — the executive's
    read/write set, which becomes its key-lock claim (the reference's
    HostContext acquires key locks during execution; DmcExecutor.cpp ships
    them on ExecutionMessages)."""

    def __init__(self, prev):
        super().__init__(prev)
        self.touched: set[tuple[str, bytes]] = set()

    def get_row(self, table: str, key: bytes):
        self.touched.add((table, bytes(key)))
        return super().get_row(table, key)

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.touched.add((table, bytes(key)))
        super().set_row(table, key, entry)

    def adopt_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.touched.add((table, bytes(key)))
        super().adopt_row(table, key, entry)


@dataclass
class _Parked:
    executive: object  # Executive
    storage: TrackingStorage
    start_msg: ExecutionMessage
    out_seq: int  # seq of the outbound request we wait on


class ExecutorShard:
    """One executor shard: runs executives for its contracts, parks them on
    cross-shard calls (ParallelTransactionExecutorInterface::
    dmcExecuteTransactions + CoroutineTransactionExecutive analog).

    All of a context's frames on this shard — the original executive and any
    sub-executives migrated in from other shards — share ONE context-scoped
    overlay (`_ctx_storage`), so the whole tx commits or vanishes atomically
    across shards when the scheduler settles the top-level result. Lock
    claims happen at every pause/completion boundary; a conflict aborts the
    WHOLE context, which the scheduler restarts from its original tx in a
    later round (optimistic execution + round-boundary lock validation; the
    wait-for graph feeds the same deadlock detector as the reference)."""

    def __init__(self, executor, name: str = "executor0", owns=None):
        self.executor = executor  # TransactionExecutor (owns block storage)
        self.name = name
        self.owns = owns if owns is not None else (lambda addr: True)
        self.parked: dict[tuple[int, int], _Parked] = {}
        self._next_seq: dict[int, int] = {}
        self._ctx_storage: dict[int, TrackingStorage] = {}

    def _alloc_seq(self, ctx: int) -> int:
        n = self._next_seq.get(ctx, 1)
        self._next_seq[ctx] = n + 1
        return n

    # context-id coordination (ChecksumAddress hashes the contextID, so ids
    # must be block-unique ACROSS shards; the scheduler aligns every
    # participant to one floor — serializable, unlike reaching into
    # `executor._block` directly, so RemoteShard can forward it)
    def ctx_floor(self) -> int:
        block = self.executor._block
        return block.next_ctx if block else 0

    def align(self, upto: int) -> None:
        self.executor.align_contexts(upto)

    def ctx_storage(self, ctx: int) -> TrackingStorage:
        st = self._ctx_storage.get(ctx)
        if st is None:
            block = self.executor._block
            assert block is not None
            st = TrackingStorage(block.storage)
            self._ctx_storage[ctx] = st
        return st

    def cancel_context(self, ctx: int) -> None:
        """Drop every trace of a context (retry restart or deadlock revert)."""
        for key in [k for k in self.parked if k[0] == ctx]:
            del self.parked[key]
        self._ctx_storage.pop(ctx, None)
        self._next_seq.pop(ctx, None)

    def reset(self) -> None:
        """Drop ALL per-block DMC state — called when a new block opens.

        Without this, a block abandoned mid-execution (Max form: an
        executor died, the scheduler re-executes on the survivors) leaves
        parked executives and context overlays layered on the DEAD block's
        storage; the re-execution would then reuse the same context ids,
        merge writes into the abandoned storage, and drop them from the
        new block's state root — silent state loss."""
        self.parked.clear()
        self._next_seq.clear()
        self._ctx_storage.clear()

    def commit_context(self, ctx: int) -> None:
        """Merge the context overlay into the block state (top-level OK)."""
        st = self._ctx_storage.pop(ctx, None)
        if st is not None and st.dirty_count():
            st.merge_into_prev()
        self._next_seq.pop(ctx, None)

    def execute(
        self, contract: bytes, msgs: list[ExecutionMessage]
    ) -> list[ExecutionMessage]:
        """Run/resume executives for `contract`. Outgoing messages carry the
        context's touched-row set in `key_locks`; the SCHEDULER claims them
        against its lock graph (the reference ships key locks on
        ExecutionMessages the same way — DmcExecutor.cpp; the shard itself
        never sees the graph, which is what lets it live in another
        process)."""
        out: list[ExecutionMessage] = []
        block = self.executor._block
        assert block is not None, "next_block_header first"
        for m in msgs:
            if m.type in (MsgType.FINISHED, MsgType.REVERT):
                parked = self.parked.pop((m.context_id, m.seq), None)
                if parked is None:
                    continue  # canceled context
                res = EVMResult(
                    status=m.status, output=m.data,
                    gas_left=max(parked.executive.block.gas_limit - m.gas_used, 0),
                    create_address=m.create_address,
                )
                res.logs = list(m.logs)
                state, payload = parked.executive.step(res)
                out.extend(
                    self._settle(
                        parked.start_msg, parked.storage, parked.executive,
                        state, payload,
                    )
                )
            else:
                is_top = m.from_addr == b"" and m.seq == 0
                if is_top and not m.create and not self.executor.known_callee(
                    m.to_addr, self.ctx_storage(m.context_id)
                ):
                    # same rejection the serial path performs (executor.py)
                    out.append(ExecutionMessage(
                        type=MsgType.REVERT, context_id=m.context_id,
                        seq=m.seq, from_addr=m.to_addr, to_addr=m.from_addr,
                        sender=m.sender, origin=m.origin,
                        data=b"unknown contract address",
                        status=int(TransactionStatus.CALL_ADDRESS_ERROR),
                    ))
                    continue
                storage = self.ctx_storage(m.context_id)
                call = EVMCall(
                    kind="create" if m.create else (m.kind or "call"),
                    sender=m.sender,
                    to=(m.storage_addr or m.to_addr) if not m.create else b"",
                    code_address=m.to_addr,
                    data=m.data,
                    # only top-level frames default to the block gas limit; a
                    # migrated sub-call keeps its forwarded gas (even 0)
                    gas=block.gas_limit if is_top else m.gas,
                    value=m.value,
                    static=m.static_call,
                )
                ex = self.executor.start_executive(
                    call, storage, block, m.origin or m.sender, m.context_id,
                    seq_start=m.seq, abi=m.abi, is_local=self.owns,
                )
                state, payload = ex.step(None)
                out.extend(self._settle(m, storage, ex, state, payload))
        return out

    def _settle(
        self, start: ExecutionMessage, storage: TrackingStorage, executive,
        state: str, payload,
    ) -> list[ExecutionMessage]:
        ctx = start.context_id
        if state == "external":
            req: EVMCall = payload
            seq = self._alloc_seq(ctx)
            self.parked[(ctx, seq)] = _Parked(executive, storage, start, seq)
            return [
                ExecutionMessage(
                    type=MsgType.MESSAGE,
                    context_id=ctx,
                    seq=seq,
                    from_addr=start.to_addr,
                    to_addr=req.code_address,
                    storage_addr=req.to,
                    kind=req.kind,
                    value=req.value,
                    sender=req.sender,
                    origin=start.origin or start.sender,
                    data=req.data,
                    static_call=req.static,
                    gas=req.gas,
                    key_locks=sorted(storage.touched),
                )
            ]
        # done (top-level or migrated sub-call); commit is the scheduler's
        # job once the TOP frame settles — nothing merges here. Successful
        # frames ship their touched-row claims for the scheduler to acquire.
        res: EVMResult = payload
        return [
            ExecutionMessage(
                type=MsgType.FINISHED if res.ok else MsgType.REVERT,
                context_id=ctx,
                seq=start.seq,
                from_addr=start.to_addr,
                to_addr=start.from_addr,
                sender=start.sender,
                origin=start.origin,
                data=res.output,
                status=res.status,
                gas_used=max(
                    (self.executor._block.gas_limit if self.executor._block else 0)
                    - res.gas_left,
                    0,
                ),
                logs=res.logs,
                key_locks=sorted(storage.touched) if res.ok else [],
                create_address=res.create_address,
            )
        ]


class DmcExecutor:
    """Per-contract message pool + round driver (DmcExecutor.cpp)."""

    def __init__(self, contract: bytes, shard: ExecutorShard):
        self.contract = contract
        self.shard = shard
        self.pool: list[ExecutionMessage] = []

    def schedule_in(self, msg: ExecutionMessage) -> None:
        self.pool.append(msg)

    def go(self, recorder: DmcStepRecorder) -> list[ExecutionMessage]:
        """Execute everything pending for this contract; returns results
        (FINISHED/REVERT) and migrated requests (MESSAGE), each carrying its
        context's key-lock claims for the scheduler to acquire."""
        msgs, self.pool = self.pool, []
        if not msgs:
            return []
        msgs.sort(key=lambda m: (m.context_id, m.seq))  # determinism
        recorder.record_send(msgs)
        results = self.shard.execute(self.contract, msgs)
        recorder.record_recv(results)
        return results


class DMCScheduler:
    """Round loop over per-contract DmcExecutors (BlockExecutive::DMCExecute).

    `shard_of(contract)` maps contracts to ExecutorShards — the Air form has
    one shard; Pro/Max register several (TarsRemoteExecutorManager analog is
    the ExecutorManager in scheduler/executor_manager.py).
    """

    def __init__(self, shard_of, max_rounds: int = 1000):
        self.shard_of = shard_of
        self.max_rounds = max_rounds
        self.recorder = DmcStepRecorder()
        self.key_locks = GraphKeyLocks()
        self._shards: set = set()

    def _cancel_everywhere(self, ctx: int, dmc: dict) -> None:
        for s in self._shards:
            s.cancel_context(ctx)
        for d in dmc.values():
            d.pool = [m for m in d.pool if m.context_id != ctx]

    def execute(self, txs: list[Transaction]) -> list[TransactionReceipt]:
        t_exec0 = time.perf_counter()
        start_round = self.recorder.round
        msg_total = 0
        dmc: dict[bytes, DmcExecutor] = {}

        def executor_for(contract: bytes) -> DmcExecutor:
            if contract not in dmc:
                shard = self.shard_of(contract)
                self._shards.add(shard)
                shard.align(getattr(self, "_ctx_end", 0))
                dmc[contract] = DmcExecutor(contract, shard)
            return dmc[contract]

        def start_message(i: int) -> ExecutionMessage:
            tx = txs[i]
            return ExecutionMessage(
                type=MsgType.MESSAGE,
                context_id=self._ctx_base + i,
                from_addr=b"",
                to_addr=tx.to,
                sender=tx.sender,
                origin=tx.sender,
                data=tx.input,
                create=not tx.to,
                abi=tx.abi.encode() if not tx.to else b"",
            )

        receipts: list[TransactionReceipt | None] = [None] * len(txs)
        reverted: set[int] = set()
        retry_ctxs: list[int] = []
        # every block executes on fresh lock/recorder state (the reference
        # builds per-BlockExecutive structures); leaked locks from a previous
        # block would alias context ids across blocks
        self.key_locks = GraphKeyLocks()
        # context ids must be block-unique per executor (CREATE addresses
        # hash the contextID — ChecksumAddress.h:83-97): take the highest
        # floor any participating shard has reached and align them all
        shards = {self.shard_of(tx.to) for tx in txs}
        base = max(s.ctx_floor() for s in shards)
        for s in shards:
            s.align(base + len(txs))
        self._ctx_base = base
        self._ctx_end = base + len(txs)
        for i, tx in enumerate(txs):
            executor_for(tx.to).schedule_in(start_message(i))

        for _ in range(self.max_rounds):
            pending = [d for d in dmc.values() if d.pool]
            if not pending and not retry_ctxs:
                break
            # restart conflicted contexts from their original tx
            for ctx in sorted(set(retry_ctxs)):
                if ctx not in reverted and receipts[ctx - self._ctx_base] is None:
                    executor_for(txs[ctx - self._ctx_base].to).schedule_in(
                        start_message(ctx - self._ctx_base)
                    )
            retry_ctxs = []
            pending = [d for d in dmc.values() if d.pool]
            # deterministic shard order; results are JOINED at the round
            # barrier before any re-scheduling — messages produced in round N
            # run in round N+1 (the reference joins its parallel_for the same
            # way, BlockExecutive.cpp:882-958), which is also what allows
            # genuine lock cycles to form instead of being serialized away
            round_results: list[ExecutionMessage] = []
            for d in sorted(pending, key=lambda d: d.contract):
                round_results.extend(d.go(self.recorder))
            msg_total += len(round_results)
            REGISTRY.observe(
                "fisco_dmc_messages_per_round",
                len(round_results),
                buckets=BATCH_BUCKETS,
                help="execution messages exchanged per DMC round",
            )
            # phase 1 — claims. The scheduler owns the lock graph: every
            # result (pause request or successful completion) carries the
            # rows its shard reported touched; claim them ALL before any
            # completion releases. Two contexts of the SAME round touching
            # the same row must conflict here — claiming and releasing
            # interleaved would let the later context commit a stale read
            # (it executed before the earlier one's writes merged). A
            # conflict restarts the whole context in a later round; the
            # failed acquire records the wait-for edge feeding the deadlock
            # detector. (Reference: key locks ship on ExecutionMessages and
            # DmcExecutor validates them scheduler-side — DmcExecutor.cpp.)
            conflicted: set[int] = set()
            for res in round_results:
                ctx = res.context_id
                if ctx in reverted or ctx in conflicted:
                    continue
                if res.type in (MsgType.MESSAGE, MsgType.FINISHED) and not all(
                    self.key_locks.acquire(ctx, tuple(k)) for k in res.key_locks
                ):
                    conflicted.add(ctx)
                    self._cancel_everywhere(ctx, dmc)
                    retry_ctxs.append(ctx)
            # phase 2 — settle survivors
            for res in round_results:
                    ctx = res.context_id
                    if ctx in reverted or ctx in conflicted:
                        continue
                    if res.type in (MsgType.FINISHED, MsgType.REVERT):
                        if res.to_addr == b"" and res.seq == 0:
                            # top-level settled: commit/discard atomically
                            # across every shard, then release locks
                            if res.type == MsgType.FINISHED:
                                for s in sorted(self._shards, key=lambda s: s.name):
                                    s.commit_context(ctx)
                            else:
                                for s in self._shards:
                                    s.cancel_context(ctx)
                            self.key_locks.release_all(ctx)
                            rc = TransactionReceipt(
                                status=res.status,
                                output=res.data,
                                gas_used=res.gas_used,
                                contract_address=res.create_address,
                            )
                            rc.log_entries = res.logs
                            receipts[ctx - self._ctx_base] = rc
                        else:  # response migrates back to the caller's shard
                            executor_for(res.to_addr).schedule_in(res)
                    else:  # outbound call migrates to the target contract
                        executor_for(res.to_addr).schedule_in(res)
            victims = self.key_locks.detect_deadlock()
            if victims:
                victim = max(victims)  # deterministic choice: highest ctx id
                _log.warning("deadlock: reverting context %s", victim)
                reverted.add(victim)
                self._cancel_everywhere(victim, dmc)
                self.key_locks.release_all(victim)
                retry_ctxs = [c for c in retry_ctxs if c != victim]
                receipts[victim - self._ctx_base] = TransactionReceipt(
                    status=int(TransactionStatus.REVERT_INSTRUCTION),
                    output=b"deadlock victim",
                )
            self.recorder.next_round()
        missing = [i for i, rc in enumerate(receipts) if rc is None]
        for i in missing:
            # drop the unfinished context's executives/overlays everywhere so
            # nothing leaks into the next block
            self._cancel_everywhere(self._ctx_base + i, dmc)
            self.key_locks.release_all(self._ctx_base + i)
            receipts[i] = TransactionReceipt(
                status=int(TransactionStatus.UNKNOWN),
                output=b"unfinished after max DMC rounds",
            )
        rounds = self.recorder.round - start_round
        REGISTRY.observe(
            "fisco_dmc_rounds_per_block",
            rounds,
            buckets=BATCH_BUCKETS,
            help="DMC scheduling rounds per executed block",
        )
        REGISTRY.counter_add(
            "fisco_dmc_messages_total",
            float(msg_total),
            help="execution messages exchanged across all DMC rounds",
        )
        if reverted:
            REGISTRY.counter_add(
                "fisco_dmc_deadlock_reverts_total",
                float(len(reverted)),
                help="contexts reverted as deadlock victims",
            )
        TRACER.record(
            "dmc.execute",
            t_exec0,
            time.perf_counter() - t_exec0,
            txs=len(txs),
            rounds=rounds,
            messages=msg_total,
        )
        return receipts  # type: ignore[return-value]
