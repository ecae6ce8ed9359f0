"""TransactionExecutor — block-scoped execution engine.

Reference: bcos-executor/src/executor/TransactionExecutor.cpp (2,749 lines)
implementing ParallelTransactionExecutorInterface: nextBlockHeader:334 (new
block state layer), executeTransactions:997 (per-contract batch),
dagExecuteTransactions:1063 (conflict-DAG parallel), getHash:1017 (state
root), 2PC prepare/commit/rollback:1681-1813, call:672 (read-only),
getCode:1881 / getABI:1999.

Contract execution routes per frame (TransactionExecutive::start analog):
system/benchmark precompiles at their fixed addresses, the EVM builtin
precompiles at 0x1..0x4 (vm/Precompiled.cpp:59-68 — ecRecover, sha256,
ripemd160, identity), and user bytecode through the EVM interpreter
(executor/evm.py). Deploys (tx.to empty or CREATE/CREATE2 opcodes) derive
addresses per ChecksumAddress.h:83-113 and store code/abi account fields in
the contract table (Common.h:63-67). Every frame runs on its own state
overlay: merge on success, drop on revert.

TPU-first shape: per-tx work (EVM/precompile dispatch) is host-side, exactly
as the reference's evmone runs are; the batchable math — state-root hashing,
receipt hashing, signature admission — are device programs elsewhere in the
stack. The DAG here reproduces the reference's conflict-key levelization
(extractConflictFields:1220 → TxDAG topo run); level execution order is
deterministic (tx order within a level) so results are bit-identical to
serial execution.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import threading
import time
from dataclasses import dataclass, field

from .. import native_bind
from ..codec.abi import ABICodec
from ..crypto.suite import CryptoSuite
from ..observability import BATCH_BUCKETS, TRACER
from ..observability.pipeline import PIPELINE
from ..protocol.block_header import BlockHeader
from ..protocol.receipt import LogEntry, TransactionReceipt, TransactionStatus
from ..protocol.transaction import Transaction
from ..storage.interfaces import (
    StorageInterface,
    TransactionalStorage,
    TwoPCParams,
    staged_rows,
)
from ..storage.entry import Entry
from ..storage.state_storage import StateStorage
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY
from ..utils.ripemd160 import ripemd160
from .evm import (
    F_CODE,
    F_CODE_HASH,
    MAX_CALL_DEPTH,
    MAX_CODE_SIZE,
    EVMCall,
    EVMHost,
    EVMResult,
    contract_table,
    interpret,
    native_engine_serves,
)
from . import eth_builtins
from .precompiled import default_registry
from .precompiled.account import ABOLISH, ACCOUNT_TABLE, FREEZE, account_status
from .precompiled.auth import acl_allows, acl_row, bind_admin, is_frozen, meta_row
from .precompiled.base import (
    BASE_GAS,
    Precompiled,
    PrecompiledCallContext,
    PrecompiledError,
)
from .wasm import WASM_MAGIC, wasm_deploy, wasm_interpret

_log = get_logger("executor")

# EVM builtin precompile addresses (vm/Precompiled.cpp:59-68)
_ECRECOVER = (1).to_bytes(20, "big")
_SHA256 = (2).to_bytes(20, "big")
_RIPEMD160 = (3).to_bytes(20, "big")
_IDENTITY = (4).to_bytes(20, "big")
_MODEXP = (5).to_bytes(20, "big")
_BN128_ADD = (6).to_bytes(20, "big")
_BN128_MUL = (7).to_bytes(20, "big")
_BN128_PAIRING = (8).to_bytes(20, "big")
_BLAKE2F = (9).to_bytes(20, "big")
_BUILTINS = (
    _ECRECOVER, _SHA256, _RIPEMD160, _IDENTITY,
    _MODEXP, _BN128_ADD, _BN128_MUL, _BN128_PAIRING, _BLAKE2F,
)
# 0x05-0x09 handlers (eth_builtins; reference Precompiled.cpp:101-263)
_EXT_BUILTINS = {
    _MODEXP: eth_builtins.modexp,
    _BN128_ADD: eth_builtins.bn128_add,
    _BN128_MUL: eth_builtins.bn128_mul,
    _BN128_PAIRING: eth_builtins.bn128_pairing,
    _BLAKE2F: eth_builtins.blake2f,
}


@dataclass
class BlockContext:
    number: int = 0
    timestamp: int = 0
    gas_limit: int = 3_000_000_000
    storage: StateStorage = field(default_factory=StateStorage)
    # monotonically increasing context-id base: every tx executed in this
    # block gets a unique contextID (the reference's scheduler numbers all
    # block txs once; CREATE addresses hash (number, contextID, seq) —
    # ChecksumAddress.h:83-97 — so ids must never repeat within a block)
    next_ctx: int = 0
    # addresses registered by SELFDESTRUCT this block
    # (BlockContext::m_suicides, BlockContext.h:147); applied by
    # killSuicides at getHash time.
    suicides: set = field(default_factory=set)
    # sender -> its governance status at this height, read once a sender by
    # the run frame: a status written at block N takes effect at N + 1
    # (precompiled/account.py), so it is constant while the block executes
    account_statuses: dict = field(default_factory=dict)


_PRECOMPILED_ERROR = int(TransactionStatus.PRECOMPILED_ERROR)
# account governance (TransactionExecutive.cpp:1292 checkAccountAvailable): a
# frozen or abolished origin cannot transact; its receipt's status and output
_ACCOUNT_REFUSALS = {
    FREEZE: (int(TransactionStatus.ACCOUNT_FROZEN), b"account is frozen"),
    ABOLISH: (int(TransactionStatus.ACCOUNT_ABOLISHED), b"account is abolished"),
}
# auth governance (ContractAuthMgr enforcement) of a deployed contract's call
_CONTRACT_FROZEN = (int(TransactionStatus.CONTRACT_FROZEN), b"contract is frozen")
_ACL_DENIES = (int(TransactionStatus.PERMISSION_DENIED), b"method ACL denies sender")
_REVERT = int(TransactionStatus.REVERT_INSTRUCTION)
_ZERO32 = b"\x00" * 32


def _call_precompile(
    pre: Precompiled, ctx: PrecompiledCallContext, data: bytes, gas: int
) -> tuple[int, bytes, int, list]:
    """One call of a registry precompile under its revert rule ->
    (status, output, gas left, logs). A PrecompiledError answers with its own
    status and message, any other fault with PRECOMPILED_ERROR; either leaves
    no gas, and the caller drops the call's writes."""
    try:
        result = pre.call(ctx, data)
    except PrecompiledError as e:
        return int(e.status), str(e).encode(), 0, []
    except Exception as e:  # malformed input etc. — revert, never crash
        return _PRECOMPILED_ERROR, f"precompile fault: {e}".encode(), 0, []
    return 0, result.output, max(gas - result.gas_used, 0), result.logs


_NO_KEYS: frozenset = frozenset()


def _sender_refusal(block: BlockContext, sender: bytes) -> tuple[int, bytes] | None:
    """A frozen or abolished sender's (status, output), the governance status
    looked up once a sender a block (``BlockContext.account_statuses``)."""
    statuses = block.account_statuses
    st = statuses.get(sender)
    if st is None:
        st = statuses[sender] = account_status(block.storage, sender, block.number)
    return _ACCOUNT_REFUSALS.get(st)


class _PrecompileFrame:
    """Top-level calls to registry precompiles on one block's state, one
    after another, without a frame chain each: one call context whose
    sender and callee are set a transaction, one overlay that a success
    merges into the block and a fault empties (``_call_precompile``: the
    statuses and outputs of ``_run_registry_precompile``), the sender's
    governance status looked up once a sender a block. No Executive, EVMCall
    or EVMHost: the callee is known to be a precompile, which uses none of
    them. The serial batch's runs and the DAG runner's levels both execute
    through ``execute``; they differ in how they group transactions and in
    whether a call's access sets are kept."""

    __slots__ = ("block", "overlay", "ctx")

    def __init__(self, executor: "TransactionExecutor", block: BlockContext):
        self.block = block
        self.overlay = StateStorage(block.storage)
        self.ctx = PrecompiledCallContext(
            storage=self.overlay,
            suite=executor.suite,
            codec=executor.codec,
            block_number=block.number,
            timestamp=block.timestamp,
            gas_limit=block.gas_limit,
        )

    def execute(
        self, pre: Precompiled, tx: Transaction, access_out: list | None = None
    ) -> TransactionReceipt:
        """One call, its receipt as ``_execute_one`` gives it. With
        `access_out`, what ``_execute_one(..., access_out=...)`` tracks is
        appended to it as (keys read through to the state below, keys
        written; none after a fault). The overlay is empty between calls,
        so a read of an earlier call's write falls through and is seen."""
        block, overlay, ctx = self.block, self.overlay, self.ctx
        gas, number, sender = block.gas_limit, block.number, tx.sender
        rc = TransactionReceipt(version=tx.version, block_number=number)
        overlay.read_track = reads = None if access_out is None else set()
        writes = _NO_KEYS
        refusal = _sender_refusal(block, sender)
        if refusal is not None:
            rc.status, rc.output = refusal
            rc.gas_used = BASE_GAS
        else:
            ctx.sender = ctx.origin = sender
            ctx.to = tx.to
            rc.status, rc.output, gas_left, rc.log_entries = _call_precompile(
                pre, ctx, tx.input, gas
            )
            rc.gas_used = max(gas - gas_left, BASE_GAS)
            if rc.status == 0:
                if reads is not None:
                    writes = set(overlay._data)
                overlay.merge_into_prev()
            else:
                overlay.discard()
        if reads is not None:
            access_out.append((reads, writes))
        return rc


class _Member:
    """What the engine's callbacks read of the member a contract frame is
    executing: the callee's table and address, the member's tracked read set
    (None where the level is not tracked), its logs, and the words it has
    read from below the frame's overlay."""

    __slots__ = ("table", "to", "reads", "logs", "seen")


def _storage_callbacks(member: _Member, overlay: StateStorage):
    """``EvmBinding``'s three callbacks over a contract frame's overlay. A
    slot the member wrote is read from the overlay's own dict (the ``Entry``
    its ``sstore`` put there, ``adopt_row``'s rule); any other from the state
    below, once a member (``SSTORE`` asks again for the word ``SLOAD`` just
    fetched), and noted in the tracked read set as the overlay's ``get_row``
    would. Words go to the engine as stored."""
    data = overlay._data
    below = overlay.prev.get_row

    def sload(slot: bytes) -> bytes:
        table = member.table
        row = data.get((table, slot))
        if row is not None:
            return row.fields["value"]
        value = member.seen.get(slot)
        if value is None:
            if member.reads is not None:
                member.reads.add((table, slot))
            row = below(table, slot)
            value = _ZERO32 if row is None else row.fields.get("value", b"")
            if len(value) != 32:  # a row no SSTORE wrote: EVMHost.get_storage's reading
                value = int.from_bytes(value, "big").to_bytes(32, "big")
            member.seen[slot] = value
        return value

    def sstore(slot: bytes, value: bytes) -> None:
        row = Entry()
        row.fields["value"] = value
        data[(member.table, slot)] = row

    def log(topics: list, payload: bytes) -> None:
        member.logs.append(LogEntry(address=member.to, topics=topics, data=payload))

    return sload, sstore, log


class _Callee:
    """What a contract frame keeps of one callee, beside the rows it read it
    from as the block's own dirty set held them at that moment (None where
    the block had not written the row): a later write to one of them puts
    another object there, and the callee is looked up again."""

    __slots__ = (
        "direct", "table", "code", "addr20", "frozen", "acls",
        "account_key", "account_row", "meta_key", "meta_row",
    )


class _ContractFrame:
    """Top-level, non-static calls to deployed EVM contracts on one block's
    state, one after another, without a frame chain each:
    ``_PrecompileFrame``'s sibling for bytecode. One overlay that a success
    merges into the block and a revert or an error empties; the native
    engine bound once, its storage callbacks on that overlay; what is the
    same for every member of a callee looked up once a callee: the code, the
    table, the freeze flag, the method ACL a (selector, sender). The
    receipts, the rows and the tracked access sets are ``_execute_one``'s.

    The frame stands aside, and the member goes through ``_execute_one``,
    where it observes that there is nothing of the kind to take out of the
    loop: a create, a registry or builtin callee, no code at the address, a
    wasm chain, a hash that is not keccak, no native engine, and a callee
    whose run the engine escaped from (that member again from the start,
    the callee's later members straight there). Every loop of a block's
    batch (the DAG levels, the serial rerun, the serial batch) executes its
    contract members through ``execute``."""

    __slots__ = (
        "executor", "block", "tally", "framed", "overlay", "_on", "_dirty",
        "_callees", "_engine", "_member",
    )

    def __init__(self, executor: "TransactionExecutor", block: BlockContext, tally: list):
        self.executor = executor
        self.block = block
        self.tally = tally  # the batch's: _execute_one's `tally`
        self.framed = 0  # members executed in here, the refused among them
        self._on: bool | None = None  # asked at the first member
        self._callees: dict[bytes, _Callee] = {}

    def _engage(self) -> bool:
        """Whether this chain's contract calls can run in the frame at all,
        found out once: then the overlay is made and the engine bound."""
        executor, storage = self.executor, self.block.storage
        self._on = False
        if (
            executor.is_wasm
            or type(storage) is not StateStorage
            or not native_engine_serves(executor.suite.hash)
        ):
            return False
        self.overlay = StateStorage(storage)
        self._dirty = storage._data
        self._member = _Member()
        self._engine = native_bind.bind_evm(
            *_storage_callbacks(self._member, self.overlay)
        )
        self._on = self._engine is not None
        return self._on

    def _lookup(self, to: bytes) -> _Callee:
        executor, storage, dirty = self.executor, self.block.storage, self._dirty
        callee = self._callees[to] = _Callee()
        callee.table = table = contract_table(to)
        callee.account_key = (table, b"#account")
        callee.account_row = dirty.get(callee.account_key)
        callee.meta_key = meta_row(to)
        callee.meta_row = dirty.get(callee.meta_key)
        code = b""
        if to and to not in executor.registry and to not in _BUILTINS:
            row = storage.get_row(table, b"#account")
            if row is not None:
                code = row.fields.get(F_CODE, b"")
        callee.direct = not code
        if code:
            callee.code = code
            callee.addr20 = native_bind.addr20(to)
            callee.frozen = is_frozen(storage, to)
            callee.acls = {}
        return callee

    def execute(
        self, tx: Transaction, context_id: int, access_out: list | None = None
    ) -> TransactionReceipt:
        """One block transaction whose callee is no registry precompile:
        its receipt, its access sets appended to `access_out` and its cost
        to the batch's tally, all as ``_execute_one`` gives them."""
        on = self._on
        if on is None:
            on = self._engage()
        if on:
            callee = self._callees.get(tx.to)
            if (
                callee is None
                or self._dirty.get(callee.account_key) is not callee.account_row
                or self._dirty.get(callee.meta_key) is not callee.meta_row
            ):
                callee = self._lookup(tx.to)
            if not callee.direct:
                rc = self._call(callee, tx, access_out)
                if rc is not None:
                    return rc
                callee.direct = True  # the engine escaped: not this frame's kind
        return self.executor._execute_one(
            tx, self.block, context_id=context_id, access_out=access_out,
            tally=self.tally,
        )

    def _refusal(self, callee: _Callee, tx: Transaction, reads: set | None):
        """``_execute_frames``' gates in its order -> (status, output) or
        None; `reads` gains the rows each gate passed stands on."""
        block, sender = self.block, tx.sender
        if reads is not None:
            reads.add(callee.account_key)
            reads.add((ACCOUNT_TABLE, sender))
        refusal = _sender_refusal(block, sender)
        if refusal is not None:
            return refusal
        if reads is not None:
            reads.add(callee.meta_key)
        if callee.frozen:
            return _CONTRACT_FROZEN
        selector = tx.input[:4]
        acl = callee.acls.get(selector)
        if acl is None or self._dirty.get(acl[0]) is not acl[1]:
            key = acl_row(tx.to, selector)
            acl = callee.acls[selector] = (key, self._dirty.get(key), {})
        if reads is not None:
            reads.add(acl[0])
        allowed = acl[2].get(sender)
        if allowed is None:
            allowed = acl[2][sender] = acl_allows(block.storage, tx.to, selector, sender)
        return None if allowed else _ACL_DENIES

    def _call(
        self, callee: _Callee, tx: Transaction, access_out: list | None
    ) -> TransactionReceipt | None:
        """The member in the frame, or None where the engine escaped (the
        overlay emptied, nothing appended anywhere)."""
        t0 = time.perf_counter()
        block, data = self.block, self.overlay._data
        gas = block.gas_limit
        rc = TransactionReceipt(version=tx.version, block_number=block.number)
        reads = None if access_out is None else set()
        writes = _NO_KEYS
        vm_s, engine = 0.0, ""
        refusal = self._refusal(callee, tx, reads)
        if refusal is not None:
            rc.status, rc.output = refusal
            rc.gas_used = BASE_GAS
        else:
            member = self._member
            member.table, member.to, member.reads = callee.table, tx.to, reads
            member.logs = logs = []
            member.seen = {}
            caller = native_bind.addr20(tx.sender)
            t_vm = time.perf_counter()
            try:
                out = self._engine.run(
                    callee.code, tx.input, callee.addr20, caller, caller, _ZERO32,
                    gas, block.number, block.timestamp, gas, 0,
                )
            except BaseException:
                data.clear()
                raise
            vm_s, engine = time.perf_counter() - t_vm, "native"
            if out is None or out[0] != "done":
                data.clear()
                return None
            _, status, gas_left, output = out
            rc.status = status
            if status == 0 or status == _REVERT:
                rc.output, rc.log_entries = output, logs
            else:  # an error status drops output and logs and leaves no gas
                gas_left = 0
            rc.gas_used = max(gas - gas_left, BASE_GAS)
            if status == 0:
                if reads is not None:
                    writes = set(data)
                self.overlay.merge_into_prev()
            else:
                data.clear()
        if reads is not None:
            access_out.append((reads, writes))
        self.framed += 1
        self.tally.append((time.perf_counter() - t0, vm_s, engine))
        return rc


def _level_conflicts(accesses: list[tuple[set, set]]) -> bool:
    """Whether a level's members touched overlapping state: every key a
    member wrote must be untouched (read OR written) by its peers, else the
    declarations lied and schedule order would decide the state.
    `accesses`: a member's (keys read through, keys written), in any order."""
    touched: dict[tuple, int] = {}
    for i, (reads, writes) in enumerate(accesses):
        for k in writes | reads:
            owner = touched.setdefault(k, i)
            if owner != i and (k in writes or k in accesses[owner][1]):
                return True
    return False


class TransactionExecutor:
    def __init__(
        self,
        backend: TransactionalStorage,
        suite: CryptoSuite,
        registry: dict[bytes, Precompiled] | None = None,
        is_wasm: bool = False,
        wasm_gas_mode: str = "dispatch",
    ):
        self.backend = backend
        self.suite = suite
        # chain-level WASM metering strategy (GenesisConfig.wasm_gas_mode)
        self.wasm_gas_mode = wasm_gas_mode
        self.codec = ABICodec(suite.hash)
        self.registry = registry if registry is not None else default_registry()
        # chain VM type from the genesis `is_wasm` flag (the reference gates
        # its dual-VM per chain — TransactionExecutive blockContext().isWasm()):
        # a wasm chain deploys only wasm modules, an EVM chain only EVM code
        self.is_wasm = is_wasm
        self._block: BlockContext | None = None
        # live block contexts by height — more than one is outstanding when
        # the scheduler pre-executes proposal N+1 on N's uncommitted state
        # (the block pipeline; ref SchedulerInterface.h:76 preExecuteBlock).
        # The guard serializes the current-context switch against the
        # commit WORKER's cleanup (pipelined commit): without it, commit's
        # compare-and-null of self._block could interleave with N+1's
        # next_block_header and null the context mid-execution
        self._blocks: dict[int, BlockContext] = {}
        self._ctx_guard = threading.Lock()

    # the scheduler may chain block N+1's state onto block N's executed,
    # uncommitted overlay (ref BlockExecutive keeps the previous block's
    # storage as its parent); composite/remote executors don't offer this
    supports_preexec = True

    # -- block lifecycle (nextBlockHeader:334 / getHash:1017) ---------------

    def next_block_header(
        self,
        header: BlockHeader,
        gas_limit: int = 3_000_000_000,
        base: StorageInterface | None = None,
    ) -> None:
        """Open the execution context for `header.number`. `base` chains the
        new overlay on a previous block's post-state instead of the durable
        backend (speculative pre-execution of N+1 while N commits)."""
        ctx = BlockContext(
            number=header.number,
            timestamp=header.timestamp,
            gas_limit=gas_limit,
            storage=StateStorage(base if base is not None else self.backend),
        )
        with self._ctx_guard:
            self._block = ctx
            self._blocks[header.number] = ctx

    def block_state(self, number: int) -> StateStorage | None:
        """Post-state overlay of an executed-but-uncommitted block."""
        ctx = self._blocks.get(number)
        return ctx.storage if ctx is not None else None

    def discard_blocks_above(self, number: int) -> None:
        """Drop speculative contexts built on state that is being replaced
        (a different proposal re-executed at or below their height)."""
        with self._ctx_guard:
            for n in [n for n in self._blocks if n > number]:
                ctx = self._blocks.pop(n)
                if self._block is ctx:
                    self._block = None

    def align_contexts(self, upto: int) -> None:
        """Raise the block's context-id floor (the DMC scheduler aligns every
        participating executor so ids never repeat per executor)."""
        if self._block is not None:
            self._block.next_ctx = max(self._block.next_ctx, upto)

    def known_callee(self, addr: bytes, storage: StorageInterface | None = None) -> bool:
        """True if a top-level call to `addr` has something to run (registry
        precompile, EVM builtin, or deployed code)."""
        if addr in self.registry or addr in _BUILTINS:
            return True
        st = storage if storage is not None else (
            self._block.storage if self._block else StateStorage(self.backend)
        )
        host = EVMHost(st, self.suite.hash, 0, 0, b"", 0)
        return bool(host.get_code(addr))

    def reserve_contexts(self, n: int) -> int:
        """Allocate n unique per-block context ids; returns the first."""
        if self._block is None:
            raise RuntimeError("no block in progress")
        base = self._block.next_ctx
        self._block.next_ctx += n
        return base

    def _apply_suicides(self, ctx: BlockContext) -> None:
        """killSuicides (BlockContext.cpp:107-137): for every address the
        block's SELFDESTRUCTs registered, empty the code and codeHash but
        KEEP the account row — the address stays used forever, so a CREATE2
        redeploy still fails with CONTRACT_ADDRESS_ALREADY_USED and the
        contract's orphaned storage slots are unreachable through code.
        Idempotent; sorted for a deterministic write order."""
        for addr in sorted(ctx.suicides):
            row = ctx.storage.get_row(contract_table(addr), b"#account")
            if row is None:
                continue
            # only code + codeHash are emptied — the reference's kill leaves
            # every other account field (incl. the ABI) untouched
            row.set(F_CODE, b"")
            row.set(F_CODE_HASH, self.suite.hash(b""))
            ctx.storage.set_row(contract_table(addr), b"#account", row)

    def get_hash_async(self):
        """Dispatch the state-root batch, defer the sync: () -> bytes."""
        if self._block is None:
            raise RuntimeError("no block in progress")
        self._apply_suicides(self._block)
        return self._block.storage.hash_async(self.suite)

    def get_hash(self) -> bytes:
        """State root of the current block's dirty set (one device batch)."""
        return self.get_hash_async()()

    # -- execution ----------------------------------------------------------

    def _builtin_precompile(self, msg: EVMCall) -> EVMResult | None:
        """EVM builtin precompiles (vm/Precompiled.cpp:59-68). Returns None
        if the address is not a builtin."""
        data = msg.data
        if msg.code_address == _ECRECOVER:
            out = b""
            if len(data) >= 128:
                h, v = data[:32], int.from_bytes(data[32:64], "big")
                sig65 = data[64:96] + data[96:128] + bytes([v & 0xFF])
                try:
                    pub = self.suite.signature_impl.recover(h, sig65)
                    out = b"\x00" * 12 + self.suite.calculate_address(pub)
                except Exception:
                    out = b""
            return EVMResult(output=out, gas_left=max(msg.gas - 3000, 0))
        if msg.code_address == _SHA256:
            return EVMResult(
                output=hashlib.sha256(data).digest(),
                gas_left=max(msg.gas - 60 - 12 * ((len(data) + 31) // 32), 0),
            )
        if msg.code_address == _RIPEMD160:
            # OpenSSL when the host has it, vendored pure-Python otherwise —
            # BOTH compute real RIPEMD-160 (vector-checked against each other
            # in tests/test_eth_builtins.py), so differing OpenSSL configs
            # can no longer fork state roots the way the old sha256-derived
            # fabricated fallback could (ref Precompiled.cpp:68 links a real
            # impl unconditionally).
            try:
                digest = hashlib.new("ripemd160", data).digest()
            except ValueError:  # OpenSSL 3.x without the legacy provider
                digest = ripemd160(data)
            return EVMResult(
                output=b"\x00" * 12 + digest,
                gas_left=max(msg.gas - 600 - 120 * ((len(data) + 31) // 32), 0),
            )
        if msg.code_address == _IDENTITY:
            return EVMResult(
                output=data,
                gas_left=max(msg.gas - 15 - 3 * ((len(data) + 31) // 32), 0),
            )
        ext = _EXT_BUILTINS.get(msg.code_address)
        if ext is not None:
            status, out, gas_left = ext(data, msg.gas)
            if status != 0:
                return EVMResult(
                    status=int(TransactionStatus.PRECOMPILED_ERROR),
                    output=b"",
                    gas_left=0,
                )
            return EVMResult(output=out, gas_left=gas_left)
        return None

    def _run_registry_precompile(
        self, pre: Precompiled, msg: EVMCall, storage: StorageInterface,
        block: BlockContext, origin: bytes,
    ) -> EVMResult:
        ctx = PrecompiledCallContext(
            storage=storage,
            suite=self.suite,
            codec=self.codec,
            sender=msg.sender,
            origin=origin,
            to=msg.to,
            block_number=block.number,
            timestamp=block.timestamp,
            gas_limit=block.gas_limit,
            static_call=msg.static,
        )
        status, output, gas_left, logs = _call_precompile(pre, ctx, msg.data, msg.gas)
        return EVMResult(status=status, output=output, gas_left=gas_left, logs=logs)

    def start_executive(
        self, msg: EVMCall, root_storage: StorageInterface, block: BlockContext,
        origin: bytes, context_id: int, seq_start: int = 0, abi: bytes = b"",
        is_local=None,
    ) -> "Executive":
        """Open an Executive (one tx frame chain) on `root_storage`."""
        return Executive(
            self, block, origin, context_id, seq_start, msg, root_storage,
            abi=abi, is_local=is_local,
        )

    def _execute_one(
        self, tx: Transaction, block: BlockContext, static_call: bool = False,
        context_id: int = 0, access_out: list | None = None,
        tally: list | None = None,
    ) -> TransactionReceipt:
        """One tx frame on its own overlay; merge on success, drop on revert
        (the reference's TransactionExecutive + revert semantics).

        With `access_out`, (the tx's external read-set, its write-set) is
        appended to it, the second filled on success — the DAG runner's
        runtime conflict validation inputs.

        With `tally` (a block's batch gives one, through its contract frame,
        for a callee that is no registry precompile), what the call cost on
        the thread that ran it is
        appended to it: (seconds in here, seconds inside the VM, the engine
        that finished the top-level frame). Two clock readings and one
        append a transaction (and two more readings around the
        VM's run, ``Executive.step``), no lock and no record: the batch sums
        the list once (``_record_contract_txs``)."""
        if tally is None:
            return self._execute_frames(tx, block, static_call, context_id, access_out)[0]
        t0 = time.perf_counter()
        rc, ex = self._execute_frames(tx, block, static_call, context_id, access_out)
        vm_s, engine = (ex.vm_s, ex.engine) if ex is not None else (0.0, "")  # None: refused
        tally.append((time.perf_counter() - t0, vm_s, engine))
        return rc

    def _execute_frames(
        self, tx: Transaction, block: BlockContext, static_call: bool,
        context_id: int, access_out: list | None,
    ) -> tuple[TransactionReceipt, "Executive | None"]:
        """``_execute_one``'s body -> (the receipt, the Executive that ran the
        frame chain, or None where the call was refused before one started)."""
        overlay = StateStorage(block.storage)
        if access_out is not None:
            overlay.read_track = set()
            writes: set = set()
            access_out.append((overlay.read_track, writes))
        rc = TransactionReceipt(version=tx.version, block_number=block.number)
        is_create = not tx.to
        if not is_create and not self.known_callee(tx.to, overlay):
            rc.status = int(TransactionStatus.CALL_ADDRESS_ERROR)
            rc.output = b"unknown contract address"
            rc.gas_used = BASE_GAS
            return rc, None
        if not static_call:
            refusal = _ACCOUNT_REFUSALS.get(
                account_status(overlay, tx.sender, block.number)
            )
            if refusal is not None:
                rc.status, rc.output = refusal
                rc.gas_used = BASE_GAS
                return rc, None
        # auth governance (ContractAuthMgr enforcement): frozen contracts and
        # method ACLs gate deployed-contract calls before a frame starts
        if not is_create and tx.to not in self.registry:
            if is_frozen(overlay, tx.to):
                rc.status, rc.output = _CONTRACT_FROZEN
                rc.gas_used = BASE_GAS
                return rc, None
            if not acl_allows(overlay, tx.to, tx.input[:4], tx.sender):
                rc.status, rc.output = _ACL_DENIES
                rc.gas_used = BASE_GAS
                return rc, None
        msg = EVMCall(
            kind="create" if is_create else "call",
            sender=tx.sender,
            to=tx.to,
            code_address=tx.to,
            data=tx.input,
            gas=block.gas_limit,
            static=static_call,
        )
        ex = self.start_executive(
            msg, overlay, block, tx.sender, context_id,
            abi=tx.abi.encode() if is_create else b"",
        )
        state, res = ex.step(None)
        assert state == "done", "serial executive cannot pause"
        rc.status = int(res.status)
        rc.output = res.output
        rc.gas_used = max(block.gas_limit - res.gas_left, BASE_GAS)
        rc.log_entries = res.logs
        rc.contract_address = res.create_address
        if res.ok and not static_call:
            if is_create and res.create_address:
                # deploy-time admin binding (AuthManager: the deployer
                # governs its contract's ACLs/freeze until handover)
                bind_admin(overlay, res.create_address, tx.sender)
            if access_out is not None:
                writes.update(overlay._data)
            overlay.merge_into_prev()
        return rc, ex

    # -- code/abi access (getCode:1881 / getABI:1999) -----------------------

    def get_code(self, addr: bytes) -> bytes:
        host = EVMHost(StateStorage(self.backend), self.suite.hash, 0, 0, b"", 0)
        return host.get_code(addr)

    def get_abi(self, addr: bytes) -> bytes:
        host = EVMHost(StateStorage(self.backend), self.suite.hash, 0, 0, b"", 0)
        return host.get_abi(addr)


    def execute_transactions(self, txs: list[Transaction]) -> list[TransactionReceipt]:
        """Serial batch on the current block (executeTransactions:997).

        The list is cut into maximal runs of consecutive calls to one
        registry precompile; a run of two or more executes inside one frame
        (``_execute_run``), every other transaction as ``_execute_one``.
        Order, receipts and state are those of ``_execute_one`` transaction
        by transaction: the run only stops paying per transaction what the
        input shows to be the same for all of them."""
        if self._block is None:
            raise RuntimeError("call next_block_header first")
        block = self._block
        base = self.reserve_contexts(len(txs))
        registry = self.registry
        # reentrant no-op under scheduler.execute_block's execute stage;
        # the REAL accounting seam for the Max executor-service processes,
        # where this is the block work's entry point
        with TRACER.span(
            "executor.execute", mode="serial", txs=len(txs)
        ) as span, PIPELINE.busy("execute"):
            t0 = time.perf_counter()
            out: list[TransactionReceipt] = []
            tally: list = []  # what each contract call cost: _execute_one
            contracts = _ContractFrame(self, block, tally)
            i, n = 0, len(txs)
            while i < n:
                to = txs[i].to
                j = i + 1
                if to in registry:
                    while j < n and txs[j].to == to:
                        j += 1
                    if j - i > 1:
                        out.extend(self._execute_run(registry[to], txs[i:j], block))
                    else:
                        out.append(self._execute_one(txs[i], block, context_id=base + i))
                else:
                    out.append(contracts.execute(txs[i], base + i))
                i = j
            if self._record_contract_txs(tally, contracts.framed)[0]:
                span.set(contract_txs=len(tally), contract_framed=contracts.framed)
        self._record_batch("serial", len(txs), time.perf_counter() - t0)
        return out

    def _execute_run(
        self, pre: Precompiled, txs: list[Transaction], block: BlockContext
    ) -> list[TransactionReceipt]:
        """Consecutive calls to one registry precompile, in order, in one
        frame (``_PrecompileFrame``)."""
        t0 = time.perf_counter()
        frame = _PrecompileFrame(self, block)
        with TRACER.span("executor.run", callee=txs[0].to.hex(), txs=len(txs)):
            receipts = [frame.execute(pre, tx) for tx in txs]
        self._record_batch("run", len(txs), time.perf_counter() - t0)
        REGISTRY.counter_add(
            "fisco_executor_run_txs_total",
            len(txs),
            help="txs executed inside a run frame (consecutive calls to one "
            "registry precompile); beside fisco_executor_batch_txs' serial "
            "sum it gives the share of a block the frame took",
        )
        return receipts

    def _record_batch(self, mode: str, n: int, dur: float) -> None:
        REGISTRY.observe(
            "fisco_executor_batch_latency_ms",
            dur * 1e3,
            help="per-block tx-batch execution wall latency by mode",
            mode=mode,
        )
        REGISTRY.observe(
            "fisco_executor_batch_txs",
            n,
            buckets=BATCH_BUCKETS,
            help="txs per execution batch by mode",
            mode=mode,
        )

    def _record_contract_txs(
        self, tally: list, framed: int
    ) -> tuple[int, int, float]:
        """A batch's contract calls (``_execute_one``'s `tally`, which the
        contract frame appends to as well) summed and added to the counters
        once, `framed` of them executed in the contract frame -> (calls,
        top-level frames the native engine finished, seconds inside the VM)."""
        if not tally:
            return 0, 0, 0.0
        seconds = sum(t[0] for t in tally)
        vm_s = sum(t[1] for t in tally)
        native = sum(1 for t in tally if t[2] == "native")
        interpreted = sum(1 for t in tally if t[2] == "interpreter")
        REGISTRY.counter_add(
            "fisco_executor_contract_txs_total",
            len(tally),
            help="block txs whose callee is no registry precompile (a deployed "
            "contract's call, a deploy), in the serial batch and the DAG "
            "runner: executed in the batch's contract frame, or each one frame "
            "chain on its own overlay through _execute_one",
        )
        REGISTRY.counter_add(
            "fisco_executor_contract_framed_txs_total",
            float(framed),
            help="those of them executed in the batch's contract frame (a "
            "deployed EVM contract's call: one overlay, the native engine bound "
            "once, the gates looked up once a callee); the others went through "
            "_execute_one (a create, no code, no native engine, a chain that is "
            "not keccak, a callee whose run the engine escaped from)",
        )
        REGISTRY.counter_add(
            "fisco_executor_contract_tx_seconds_total",
            seconds,
            help="seconds those txs took, each from the contract frame's entry "
            "to its receipt or inside _execute_one, on the thread that executes "
            "the block",
        )
        for engine, calls in (("native", native), ("interpreter", interpreted)):
            REGISTRY.counter_add(
                f'fisco_executor_evm_calls_total{{engine="{engine}"}}',
                calls,
                help="those txs' top-level EVM frames by the engine that "
                "finished them: native (the whole frame on fisco_evm_run) or "
                "interpreter (the Python loop ran it, or resumed it where the "
                "native engine escaped)",
            )
        REGISTRY.counter_add(
            "fisco_executor_evm_seconds_total",
            vm_s,
            help="those txs' seconds inside the VM: around the native engine's "
            "run with its storage callbacks for a framed tx, inside the frames' "
            "interpreter generators (the native run, the Python loop) for one "
            "through _execute_one",
        )
        return len(tally), native, vm_s

    # -- DAG parallel (dagExecuteTransactions:1063) -------------------------

    def extract_criticals(self, tx: Transaction) -> list[bytes] | None:
        """Conflict keys for one tx, namespaced by contract
        (extractConflictFields:1220). None → must serialize. Registry
        precompiles declare criticals in code; EVM/WASM user contracts
        declare them as conflictFields in their stored ABI
        (abi_conflict.py — the dag/Abi.h path)."""
        pre = self.registry.get(tx.to)
        if pre is not None:
            if not pre.parallel:
                return None
            keys = pre.criticals(self.codec, tx.input)
            if keys is None:
                return None
            return [tx.to + k for k in keys]
        if len(tx.input) < 4 or not tx.to:
            return None
        from . import abi_conflict

        storage = self._block.storage if self._block is not None else None
        host = EVMHost(
            storage if storage is not None else StateStorage(self.backend),
            self.suite.hash, 0, 0, b"", 0,
        )
        abi_text = host.get_abi(tx.to)
        if not abi_text:
            return None
        fn = abi_conflict.lookup(
            abi_text.decode(errors="replace"),
            self.suite.hash_impl.name,
            tx.input[:4],
        )
        if fn is None:
            return None
        blk = self._block
        keys = abi_conflict.extract_criticals(
            fn,
            tx.input,
            tx.sender or b"",
            tx.to,
            blk.timestamp if blk is not None else 0,
            blk.number if blk is not None else 0,
        )
        if keys is None:
            return None
        return [tx.to + k for k in keys]

    def dag_levels(self, txs: list[Transaction]) -> list[list[int]]:
        """Levelize by conflict keys: a tx depends on the last earlier tx
        sharing any key. Txs with no declaration form single-tx levels
        (serial), preserving tx order around them."""
        levels: list[list[int]] = []
        level_of: dict[int, int] = {}
        last_touch: dict[bytes, int] = {}
        barrier = -1  # last serial tx index; everything after depends on it
        for i, tx in enumerate(txs):
            keys = self.extract_criticals(tx)
            if keys is None:
                # serial tx: after everything before it, before everything after
                lvl = max(level_of.values(), default=-1) + 1
                barrier = i
            else:
                deps = [last_touch.get(k, -1) for k in keys]
                deps.append(barrier)
                lvl = max((level_of[d] for d in deps if d >= 0), default=-1) + 1
                for k in keys:
                    last_touch[k] = i
            level_of[i] = lvl
            while len(levels) <= lvl:
                levels.append([])
            levels[lvl].append(i)
        return levels

    def dag_execute_transactions(
        self, txs: list[Transaction]
    ) -> list[TransactionReceipt]:
        # same stage seam as the serial batch (reentrant under the
        # scheduler's execute stage; the entry point on a Max executor)
        with PIPELINE.busy("execute"):
            return self._dag_execute_transactions(txs)

    def _dag_execute_transactions(
        self, txs: list[Transaction]
    ) -> list[TransactionReceipt]:
        """Conflict-DAG execution (the reference's TxDAG2 axis, SURVEY §2.8
        row 5): level by level, every member of a level on this thread, in
        index order, then VALIDATED at runtime.

        A call to a registry precompile executes inside the one
        ``_PrecompileFrame`` of the call; any other callee through the
        call's ``_ContractFrame`` (a deployed EVM contract's call in the
        frame, whatever the frame stands aside for through
        ``_execute_one`` on its own overlay). That is one of the
        schedules a pool could have produced, and the only one that is the
        same on every host. A pool of threads for bytecode members never
        measured faster than this thread (the native EVM engine needs the
        interpreter back for every storage read and write): one can come back
        with an engine that takes a frame's storage without the interpreter,
        and with a reading that it wins.

        Determinism contract: context ids are pre-reserved per tx index, so
        for txs whose declared conflict sets are HONEST (disjoint state),
        any schedule produces serial-identical results. Because a lying
        declaration must not let the levels' order leak into the state root,
        every level wider than one has its members' actual read/write sets
        checked pairwise after it completes; ANY overlap discards the whole
        attempt and re-executes the block serially, member by member through
        the same two ways, the same deterministic outcome every node
        computes. The whole DAG run happens on a shadow overlay so the
        discard is clean. FISCO_DAG_SERIAL=1 pins that serial loop."""
        if self._block is None:
            raise RuntimeError("call next_block_header first")
        t_dag0 = time.perf_counter()
        base = self.reserve_contexts(len(txs))
        registry = self.registry
        # (width, framed) of every level that ran, in order (a serial
        # rerun's after the failed attempt's), and every check's verdict: the
        # facts of the block's one record; the seconds by stage are the span's
        # marks (levelize, run, validate)
        ran: list[tuple[int, int]] = []
        conflicts: list[bool] = []
        tally: list = []  # what each contract call cost: _execute_one
        frames: list[_ContractFrame] = []  # one a run (a rerun makes a second)

        def shadow_ctx() -> BlockContext:
            return BlockContext(
                number=self._block.number,
                timestamp=self._block.timestamp,
                gas_limit=self._block.gas_limit,
                storage=StateStorage(self._block.storage),
                account_statuses=self._block.account_statuses,
            )

        def run_serial(block: BlockContext) -> list:
            # receipts land at their TX INDEX (execution walks level order) —
            # a flattened comprehension here once misassigned receipts
            # whenever levelization reordered txs (review r5: consensus fork
            # between nodes; see
            # tests/test_abi_conflict.py::test_reordering_levels_keep_receipt_identity)
            out: list = [None] * len(txs)
            contracts = _ContractFrame(self, block, tally)
            frames.append(contracts)
            for level in levels:
                for i in level:
                    tx = txs[i]
                    if tx.to in registry:
                        out[i] = self._execute_one(tx, block, context_id=base + i)
                    else:
                        out[i] = contracts.execute(tx, base + i)
                ran.append((len(level), 0))
                span.stage("run")
            return out

        def run_levels(block: BlockContext) -> list | None:
            """The levels on `block`, or None where a level's check failed."""
            out: list = [None] * len(txs)
            frame = _PrecompileFrame(self, block)
            contracts = _ContractFrame(self, block, tally)
            frames.append(contracts)
            for level in levels:
                wide = len(level) > 1
                # every member of a wide level appends its access sets
                tracked: list | None = [] if wide else None
                framed = 0
                for i in level:
                    tx = txs[i]
                    pre = registry.get(tx.to)
                    if pre is not None:
                        out[i] = frame.execute(pre, tx, tracked)
                        framed += 1
                    else:
                        out[i] = contracts.execute(tx, base + i, tracked)
                ran.append((len(level), framed))
                span.stage("run")
                if not wide:
                    continue
                conflict = _level_conflicts(tracked)
                conflicts.append(conflict)
                span.stage("validate")
                if conflict:
                    _log.warning(
                        "DAG level of %d txs touched overlapping "
                        "state its conflict declarations called "
                        "disjoint; re-executing the block serially",
                        len(level),
                    )
                    return None
            return out

        with TRACER.span("executor.execute", mode="dag", txs=len(txs)) as span:
            levels = self.dag_levels(txs)
            span.stage("levelize")
            shadow = shadow_ctx()
            run = run_serial if os.environ.get("FISCO_DAG_SERIAL") else run_levels
            receipts = run(shadow)
            conflict = receipts is None
            if conflict:
                # the discarded attempt's suicide registrations die with its
                # shadow context; the serial rerun regenerates them — the same
                # deterministic outcome on every node
                shadow = shadow_ctx()
                receipts = run_serial(shadow)
            shadow.storage.merge_into_prev()
            self._block.suicides |= shadow.suicides
            widths, framed_by_level = zip(*ran) if ran else ((), ())
            contract_framed = sum(f.framed for f in frames)
            contract_txs, evm_native, evm_s = self._record_contract_txs(
                tally, contract_framed
            )
            span.set(
                levels=len(levels), reruns=int(conflict), widths=widths,
                framed=framed_by_level, conflicts=tuple(conflicts),
                contract_txs=contract_txs, contract_framed=contract_framed,
                evm_native=evm_native, evm_s=evm_s,
            )
        self._record_batch("dag", len(txs), time.perf_counter() - t_dag0)
        REGISTRY.counter_add(
            "fisco_executor_dag_levels_total",
            len(levels),
            help="dependent levels the DAG runner cut its batches into "
            "(a level's txs share no declared conflict key)",
        )
        for key, seconds in span.stages.items():
            REGISTRY.counter_add(
                f'fisco_executor_dag_stage_seconds_total{{stage="{key}"}}',
                seconds,
                help="seconds of the DAG runner by stage: levelize (conflict "
                "keys + levels), run (the levels' execution, in the two frames "
                "and through _execute_one, a serial rerun too), validate (the pairwise "
                "check of the access sets of a level wider than one)",
            )
        # the runner has no pool since PR 41. These two stay registered and
        # read 0 because the benchmark still reads them (dag_pooled_tx_share is
        # an entry of BENCHMARK.json, and tests/benchmark_checks holds
        # dag_pool_wait_ms_per_block to a number): PERF.md, Open questions
        REGISTRY.counter_add(
            "fisco_executor_dag_pooled_txs_total",
            0.0,
            help="always 0: the DAG runner has no thread pool since PR 41 "
            "(every member of a level executes on the thread that executes "
            "the block); kept for the benchmark's dag_pooled_tx_share",
        )
        REGISTRY.counter_add(
            "fisco_executor_dag_pool_wait_seconds_total",
            0.0,
            help="always 0: the DAG runner has no thread pool to wait for "
            "since PR 41; kept for the benchmark's dag_pool_wait_ms_per_block",
        )
        REGISTRY.counter_add(
            "fisco_executor_dag_framed_txs_total",
            sum(framed_by_level),
            help="txs the DAG runner executed inside its level frame, on the "
            "thread that executes the block (calls to registry precompiles); "
            "beside fisco_executor_batch_txs' dag sum, the frame's share",
        )
        if conflict:
            REGISTRY.counter_add(
                "fisco_executor_dag_conflict_reruns_total",
                help="DAG levels whose conflict declarations lied "
                "(block re-executed serially)",
            )
        return receipts  # type: ignore[return-value]

    # -- read-only call (call:672) ------------------------------------------

    def call(self, tx: Transaction) -> TransactionReceipt:
        block = BlockContext(storage=StateStorage(self.backend))
        return self._execute_one(tx, block, static_call=True)

    # -- 2PC (prepare:1681 / commit:1745 / rollback:1813) -------------------

    def prepare(
        self, params: TwoPCParams, extra_writes: StorageInterface | None = None
    ) -> dict[str, int] | None:
        """Stage the block's state (plus the scheduler's ledger writes)
        into the durable backend. The ledger rows are CHAINED as a
        traverse view, never merged into the block overlay: block N+1's
        speculative execution reads through that overlay while this 2PC
        is in flight (the pipelined commit), and a mutating merge here
        would be a torn read under it. Every backend's prepare is a
        per-key last-wins merge, so the chained order (block rows, then
        ledger rows) stages identically to the old in-place merge.
        Returns the backend's tally of the rows it staged
        (storage.interfaces.count_prepared), None where it gives none."""
        ctx = self._blocks.get(params.number)
        if ctx is None:
            raise RuntimeError(f"no executed block {params.number} to prepare")
        self._apply_suicides(ctx)  # idempotent; getHash normally ran already
        writes = (
            ctx.storage
            if extra_writes is None
            else _StagedWrites(ctx.storage, extra_writes)
        )
        t0 = time.perf_counter()
        staged = self.backend.prepare(params, writes)
        REGISTRY.observe(
            "fisco_storage_prepare_latency_ms",
            (time.perf_counter() - t0) * 1e3,
            help="2PC prepare (durable staging) wall latency",
        )
        return staged

    def commit(self, params: TwoPCParams) -> None:
        t0 = time.perf_counter()
        self.backend.commit(params)
        REGISTRY.observe(
            "fisco_storage_commit_latency_ms",
            (time.perf_counter() - t0) * 1e3,
            help="2PC commit (backend apply) wall latency",
        )
        # the committed overlay may still serve as the parent of block N+1's
        # speculative chain — popping the dict only drops OUR handle
        with self._ctx_guard:
            ctx = self._blocks.pop(params.number, None)
            if self._block is ctx:
                self._block = None

    def rollback(self, params: TwoPCParams) -> None:
        self.backend.rollback(params)
        with self._ctx_guard:
            ctx = self._blocks.pop(params.number, None)
            if self._block is ctx:
                self._block = None
        # children chained on the rolled-back state are invalid
        self.discard_blocks_above(params.number)


class _StagedWrites:
    """Read-only chained traverse over the 2PC staging layers — the
    non-mutating replacement for merging the scheduler's ledger rows into
    the block overlay (later layers win per key in every backend's
    per-key prepare merge)."""

    __slots__ = ("_layers",)

    def __init__(self, *layers):
        self._layers = layers

    def traverse(self):
        for layer in self._layers:
            yield from layer.traverse()

    def borrow_rows(self):
        """The layers' own objects where they lend them
        (TraversableStorage.borrow_rows), copies from a layer that cannot."""
        rows = {}
        for layer in self._layers:
            rows.update(staged_rows(layer)[0])
        return rows


class _ExecFrame:
    __slots__ = ("gen", "overlay", "msg", "create_addr", "abi")

    def __init__(self, gen, overlay, msg, create_addr=b"", abi=b""):
        self.gen = gen
        self.overlay = overlay
        self.msg = msg
        self.create_addr = create_addr
        self.abi = abi


class Executive:
    """One transaction frame chain — the reference's TransactionExecutive /
    CoroutineTransactionExecutive (executive/CoroutineTransactionExecutive.cpp)
    rebuilt on Python generators.

    Frames are explicit (a stack of interpreter generators over nested state
    overlays), so the executive can *pause* at any external call the driver
    declares non-local (`is_local`): ``step`` returns ("external", EVMCall)
    and the DMC scheduler migrates the request to the target contract's shard,
    resuming later with the EVMResult. The serial path passes no `is_local`
    (everything local) and runs straight to ("done", EVMResult).
    """

    def __init__(self, executor: TransactionExecutor, block: BlockContext,
                 origin: bytes, context_id: int, seq_start: int,
                 msg: EVMCall, root_storage: StorageInterface,
                 abi: bytes = b"", is_local=None):
        self.ex = executor
        self.block = block
        self.origin = origin
        self.context_id = context_id
        # creates inside this executive draw sub-sequence numbers from the
        # spawning message's seq (the reference threads newSeq through
        # ExecutionMessages; TransactionExecutive.cpp:95-115)
        self.seq = itertools.count(seq_start << 12)
        self.frames: list[_ExecFrame] = []
        self.root_storage = root_storage
        self._opened = False
        self._start_msg = msg
        self._start_abi = abi
        self.is_local = is_local if is_local is not None else (lambda addr: True)
        # seconds inside the frames' interpreter generators (`interpret`: the
        # native engine's run and the Python loop; not the frames' set-up),
        # and which engine finished the top-level frame (EVMResult.engine)
        self.vm_s = 0.0
        self.engine = ""

    def _host(self, overlay: StorageInterface) -> EVMHost:
        return EVMHost(
            overlay, self.ex.suite.hash, self.block.number,
            self.block.timestamp, self.origin, self.block.gas_limit,
            suicide_sink=self.block.suicides.add,
        )

    def _open(self, msg: EVMCall, parent: StorageInterface,
              abi: bytes = b"") -> EVMResult | None:
        """Resolve a call/create request: either an immediate EVMResult
        (builtins, precompiles, codeless calls, errors) or None with a new
        interpreter frame pushed."""
        if msg.depth >= MAX_CALL_DEPTH:
            return EVMResult(status=int(TransactionStatus.OUT_OF_STACK))
        overlay = StateStorage(parent)
        host = self._host(overlay)
        if msg.kind in ("create", "create2"):
            if msg.salt is not None:
                addr = host.create2_address(msg.sender, msg.salt, msg.data)
            else:
                addr = host.create_address(
                    self.block.number, self.context_id, next(self.seq)
                )
            if host.account_exists(addr):
                return EVMResult(
                    status=int(TransactionStatus.CONTRACT_ADDRESS_ALREADY_USED)
                )
            deploying_wasm = msg.data[:4] == WASM_MAGIC
            if deploying_wasm != self.ex.is_wasm:
                # the chain's VM type is a genesis-time decision; mixed
                # deploys are rejected like the reference's isWasm gate
                return EVMResult(
                    status=int(TransactionStatus.WASM_VALIDATION_FAILURE),
                    output=(
                        b"wasm deploy on an EVM chain"
                        if deploying_wasm
                        else b"EVM deploy on a wasm chain"
                    ),
                )
            run_msg = EVMCall(
                kind="call", sender=msg.sender, to=addr, code_address=addr,
                data=b"", gas=msg.gas, value=msg.value, depth=msg.depth,
            )
            if deploying_wasm:
                gen = wasm_deploy(host, run_msg, msg.data, self.ex.wasm_gas_mode)
            else:
                gen = interpret(host, run_msg, msg.data)
            self.frames.append(_ExecFrame(gen, overlay, msg, addr, abi))
            return None
        builtin = self.ex._builtin_precompile(msg)
        if builtin is not None:
            return builtin
        pre = self.ex.registry.get(msg.code_address)
        if pre is not None:
            res = self.ex._run_registry_precompile(
                pre, msg, overlay, self.block, self.origin
            )
            if res.ok and not msg.static:
                overlay.merge_into_prev()
            return res
        code = host.get_code(msg.code_address)
        if not code:
            # call to codeless address succeeds with empty output (EVM rule);
            # top-level txs to unknown addresses are rejected by execute()
            return EVMResult(status=0, output=b"", gas_left=msg.gas)
        # VM choice follows the CHAIN type, never the stored bytes: an EVM
        # init code could RETURN wasm-magic-prefixed runtime code, and
        # prefix dispatch would then run wasm on an EVM chain, bypassing
        # the genesis gate the deploy path enforces
        if self.ex.is_wasm:
            gen = wasm_interpret(host, msg, code, self.ex.wasm_gas_mode)
        else:
            gen = interpret(host, msg, code)
        self.frames.append(_ExecFrame(gen, overlay, msg))
        return None

    def step(self, response: EVMResult | None):
        """Advance until done or paused on a non-local call.

        Returns ("done", EVMResult) or ("external", EVMCall)."""
        if not self._opened:
            self._opened = True
            immediate = self._open(self._start_msg, self.root_storage,
                                   self._start_abi)
            if immediate is not None:
                return ("done", immediate)
            response = None
        while self.frames:
            fr = self.frames[-1]
            t_vm = time.perf_counter()
            try:
                req = fr.gen.send(response)
            except StopIteration as si:
                self.vm_s += time.perf_counter() - t_vm
                res: EVMResult = si.value
                self.frames.pop()
                if not self.frames:
                    self.engine = res.engine
                if fr.create_addr:
                    if res.ok:
                        if len(res.output) > MAX_CODE_SIZE:
                            res = EVMResult(status=int(TransactionStatus.OUT_OF_GAS))
                        else:
                            # init code that SELFDESTRUCTed still stores its
                            # runtime code here; the block-end killSuicides
                            # pass empties it (account row kept, address
                            # burned) — matching the reference, where the
                            # deploy completes and m_suicides wins at getHash
                            self._host(fr.overlay).set_code(
                                fr.create_addr, res.output, fr.abi
                            )
                            res = EVMResult(
                                status=0, output=b"", gas_left=res.gas_left,
                                logs=res.logs, create_address=fr.create_addr,
                            )
                            fr.overlay.merge_into_prev()
                elif res.ok and not fr.msg.static:
                    fr.overlay.merge_into_prev()
                response = res
                continue
            self.vm_s += time.perf_counter() - t_vm
            # external request from the top frame
            if req.kind in ("create", "create2") or self.is_local(req.code_address):
                immediate = self._open(req, fr.overlay)
                response = immediate  # None → frame pushed, drive it next
            else:
                return ("external", req)
        return ("done", response)
