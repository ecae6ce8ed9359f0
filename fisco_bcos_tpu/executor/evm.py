"""EVM interpreter + host context over Table storage.

The reference executes user contracts with evmone behind
`bcos-executor/src/vm/{VMFactory.h:32-49,VMInstance.cpp}`, bridged to Table
storage by `vm/HostContext.cpp` (718 lines: SLOAD/SSTORE → contract-table
rows, code/codeHash/abi fields per Common.h:63-67) and framed per tx by
`executive/TransactionExecutive.cpp` (create-address rules via
bcos-crypto/ChecksumAddress.h:83-113, revert semantics, depth limits).
Contract execution is inherently sequential per tx, so — exactly like the
reference — it stays on the host; the batchable crypto/state math lives in
the device plane.

Design notes:
- **Generator-based external calls.** The interpreter is a Python generator
  that ``yield``s an :class:`EVMCall` whenever the contract performs
  CALL/DELEGATECALL/STATICCALL/CREATE and receives the :class:`EVMResult`
  back via ``send``. The serial executor drives it to completion recursively
  (`run_message`); the DMC scheduler can instead *park* the generator when
  the callee lives on another executor shard and resume it when the migrated
  message returns — the moral equivalent of the reference's
  CoroutineTransactionExecutive (boost::context stackful coroutines,
  `executive/CoroutineTransactionExecutive.cpp`) without native stacks.
- Word arithmetic is exact Python int mod 2^256 — bit-identical everywhere.
- Gas: a real schedule (memory expansion, SSTORE set/reset, copy costs,
  keccak word costs) with constant-folded opcode base costs. It is a
  simplified schedule, not a fork-exact Ethereum one — the reference's gas
  numbers come from evmone revisions and differ between FISCO versions; what
  consensus requires is determinism, which this provides.
- Storage layout matches the reference: per-contract table
  ``/apps/<hex-address>`` (Common.h:382-389), EVM storage slots as 32-byte
  row keys, account fields ``code``/``codeHash``/``abi``/``nonce``
  (Common.h:63-67).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..protocol.receipt import LogEntry, TransactionStatus
from ..storage.entry import Entry
from ..storage.interfaces import StorageInterface

MOD = 1 << 256
MASK = MOD - 1
SIGN_BIT = 1 << 255
MAX_CODE_SIZE = 0x40000  # reference: EVMSchedule maxCodeSize (evmone default)
MAX_CALL_DEPTH = 1024
APPS_PREFIX = "/apps/"

# account field names (bcos-executor/src/Common.h:63-67)
F_CODE = "code"
F_CODE_HASH = "codeHash"
F_ABI = "abi"
F_NONCE = "nonce"
F_BALANCE = "balance"


def contract_table(addr: bytes) -> str:
    """Table name for a contract address (Common.h:382-389)."""
    return APPS_PREFIX + addr.hex()


@dataclass
class EVMCall:
    """External-call request yielded by the interpreter."""

    kind: str  # "call" | "delegatecall" | "staticcall" | "callcode" | "create" | "create2"
    sender: bytes = b""
    to: bytes = b""  # storage/recipient context (empty for create)
    code_address: bytes = b""
    data: bytes = b""
    gas: int = 0
    value: int = 0
    static: bool = False
    depth: int = 0
    salt: int | None = None  # create2


@dataclass
class EVMResult:
    status: int = 0  # TransactionStatus value; 0 = success
    output: bytes = b""
    gas_left: int = 0
    logs: list[LogEntry] = field(default_factory=list)
    create_address: bytes = b""
    # which engine finished the frame, where `interpret` ran it: "native"
    # (the whole frame on native/fisco_native.cpp fisco_evm_run) or
    # "interpreter" (the Python loop ran it, or resumed it where the native
    # engine escaped); empty for a result no EVM frame produced
    engine: str = ""

    @property
    def ok(self) -> bool:
        return self.status == 0


class EVMHost:
    """Storage/code bridge for one tx frame (vm/HostContext.cpp analog).

    All writes go through the tx overlay handed in by the executor, so
    revert = drop the overlay, exactly like the reference's per-executive
    StateStorage layering.
    """

    def __init__(self, storage: StorageInterface, hash_fn, block_number: int,
                 timestamp: int, tx_origin: bytes, gas_limit: int,
                 suicide_sink=None):
        self.storage = storage
        self.hash_fn = hash_fn
        self.block_number = block_number
        self.timestamp = timestamp
        self.tx_origin = tx_origin
        self.gas_limit = gas_limit
        # block-scoped suicide registry (BlockContext::suicide,
        # bcos-executor/src/executive/BlockContext.cpp:94-105): registration
        # is immediate and is NOT unwound by frame reverts — the reference
        # keeps one std::set per block with no revert hook
        self.suicide_sink = suicide_sink

    def register_suicide(self, addr: bytes) -> None:
        if self.suicide_sink is not None:
            self.suicide_sink(addr)

    # -- EVM storage (slot rows in the contract table) ----------------------

    def get_storage(self, addr: bytes, slot: int) -> int:
        row = self.storage.get_row(contract_table(addr), slot.to_bytes(32, "big"))
        return int.from_bytes(row.get(), "big") if row is not None else 0

    def set_storage(self, addr: bytes, slot: int, value: int) -> None:
        key = slot.to_bytes(32, "big")
        self.storage.set_row(
            contract_table(addr), key, Entry().set(value.to_bytes(32, "big"))
        )

    # -- accounts -----------------------------------------------------------

    def _account_row(self, addr: bytes, fld: str) -> bytes:
        row = self.storage.get_row(contract_table(addr), b"#account")
        return row.fields.get(fld, b"") if row is not None else b""

    def get_code(self, addr: bytes) -> bytes:
        return self._account_row(addr, F_CODE)

    def get_code_hash(self, addr: bytes) -> bytes:
        return self._account_row(addr, F_CODE_HASH)

    def get_abi(self, addr: bytes) -> bytes:
        return self._account_row(addr, F_ABI)

    def account_exists(self, addr: bytes) -> bool:
        return self.storage.get_row(contract_table(addr), b"#account") is not None

    def set_code(self, addr: bytes, code: bytes, abi: bytes = b"") -> None:
        row = self.storage.get_row(contract_table(addr), b"#account") or Entry()
        row.set(F_CODE, code)
        row.set(F_CODE_HASH, self.hash_fn(code))
        if abi:
            row.set(F_ABI, abi)
        row.set(F_NONCE, row.fields.get(F_NONCE, b"\x00"))
        self.storage.set_row(contract_table(addr), b"#account", row)

    # -- create-address rules (ChecksumAddress.h:83-113) --------------------

    def create_address(self, number: int, context_id: int, seq: int) -> bytes:
        pre = f"{number}_{context_id}_{seq}".encode()
        return self.hash_fn(pre)[:20]

    def create2_address(self, sender: bytes, salt: int, init_code: bytes) -> bytes:
        pre = b"\xff" + sender + salt.to_bytes(32, "big") + self.hash_fn(init_code)
        return self.hash_fn(pre)[:20]


# ---------------------------------------------------------------------------
# Gas schedule (simplified; deterministic)
# ---------------------------------------------------------------------------

G_ZERO = 0
G_BASE = 2
G_VERYLOW = 3
G_LOW = 5
G_MID = 8
G_HIGH = 10
G_JUMPDEST = 1
G_SLOAD = 200
G_SSTORE_SET = 20_000
G_SSTORE_RESET = 5_000
G_CREATE = 32_000
G_CALL = 700
G_LOG = 375
G_LOGDATA = 8
G_LOGTOPIC = 375
G_KECCAK = 30
G_KECCAK_WORD = 6
G_COPY_WORD = 3
G_MEMORY = 3
G_EXP = 10
G_EXP_BYTE = 50
G_BALANCE = 400
G_EXTCODE = 700

_OUT_OF_GAS = TransactionStatus.OUT_OF_GAS


class _VMError(Exception):
    def __init__(self, status: TransactionStatus):
        self.status = status


class _Frame:
    """Mutable machine state for one code run."""

    __slots__ = ("stack", "memory", "pc", "gas", "returndata", "logs")

    def __init__(self, gas: int):
        self.stack: list[int] = []
        self.memory = bytearray()
        self.pc = 0
        self.gas = gas
        self.returndata = b""
        self.logs: list[LogEntry] = []

    # stack helpers
    def push(self, v: int) -> None:
        if len(self.stack) >= 1024:
            raise _VMError(TransactionStatus.OUT_OF_STACK)
        self.stack.append(v & MASK)

    def pop(self) -> int:
        if not self.stack:
            raise _VMError(TransactionStatus.STACK_UNDERFLOW)
        return self.stack.pop()

    def use_gas(self, n: int) -> None:
        self.gas -= n
        if self.gas < 0:
            raise _VMError(_OUT_OF_GAS)

    # memory expansion: the EVM cost function Cmem(w) = 3w + w^2/512,
    # charged on the delta (evmone's grow_memory) — the quadratic term is
    # what makes big memories exponentially expensive; a flat per-word
    # price would let one tx hold arbitrary host memory cheaply. The 2 MiB
    # hard cap is belt-and-braces on top (a 2 MiB memory already costs
    # ~8.6M gas).
    def mem_extend(self, offset: int, size: int) -> None:
        if size == 0:
            return
        if offset + size > 0x200000:  # 2 MiB hard cap guards host memory
            raise _VMError(_OUT_OF_GAS)
        need = offset + size
        if need > len(self.memory):
            old_w = len(self.memory) // 32
            new_w = (need + 31) // 32
            self.use_gas(
                G_MEMORY * (new_w - old_w)
                + (new_w * new_w // 512 - old_w * old_w // 512)
            )
            self.memory.extend(b"\x00" * (new_w * 32 - len(self.memory)))

    def mread(self, offset: int, size: int) -> bytes:
        self.mem_extend(offset, size)
        return bytes(self.memory[offset : offset + size])

    def mwrite(self, offset: int, data: bytes) -> None:
        self.mem_extend(offset, len(data))
        self.memory[offset : offset + len(data)] = data


def _signed(v: int) -> int:
    return v - MOD if v >= SIGN_BIT else v


# keccak256(b"") — the native engine hardcodes keccak for SHA3, so it may
# only run for suites whose hash IS keccak (an SM chain computes sm3 storage
# slots; running the native engine there would fork state roots between
# nodes with and without the library)
_KECCAK_EMPTY = bytes.fromhex(
    "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
)


def native_engine_serves(hash_fn) -> bool:
    """Whether a frame of a chain with this hash may run on the native engine:
    it is not switched off (FISCO_NO_NATIVE_EVM) and the hash is keccak."""
    import os

    return not os.environ.get("FISCO_NO_NATIVE_EVM") and hash_fn(b"") == _KECCAK_EMPTY


def _native_prefix(host: EVMHost, msg: EVMCall, code: bytes, f: "_Frame"):
    """Run the frame's straight-line prefix on the native engine
    (native/fisco_native.cpp fisco_evm_run — the evmone analog). Returns an
    EVMResult when the whole frame finished natively; None when the frame
    should (continue to) run in Python — the engine may not serve this chain
    (``native_engine_serves``), the library is unavailable, or the engine escaped at an unmodeled opcode, in which case `f` has
    been seeded with the escaped pc/gas/stack/memory and Python resumes
    bit-identically (gas schedule and edge semantics are kept in lockstep;
    differential-tested by tests/test_native_evm.py)."""
    if not native_engine_serves(host.hash_fn):
        return None  # switched off, or a non-keccak suite (sm3): Python only

    from .. import native_bind

    def sload(slot: bytes) -> bytes:
        return host.get_storage(msg.to, int.from_bytes(slot, "big")).to_bytes(
            32, "big"
        )

    def sstore(slot: bytes, val: bytes) -> None:
        host.set_storage(
            msg.to, int.from_bytes(slot, "big"), int.from_bytes(val, "big")
        )

    def log(topics: list, data: bytes) -> None:
        f.logs.append(LogEntry(address=msg.to, topics=topics, data=data))

    out = native_bind.evm_run(
        code, msg.data, msg.to, msg.sender, host.tx_origin, msg.value,
        msg.gas, host.block_number, host.timestamp, host.gas_limit,
        msg.static, sload, sstore, log,
    )
    if out is None:
        return None
    if out[0] == "done":
        _, status, gas_left, output = out
        if status in (0, int(TransactionStatus.REVERT_INSTRUCTION)):
            return EVMResult(
                status=status, output=output, gas_left=gas_left, logs=f.logs,
                engine="native",
            )
        # error statuses drop logs and zero gas, like the _VMError path
        return EVMResult(
            status=status, output=b"", gas_left=0, logs=[], engine="native"
        )
    _, pc, gas_left, stack, memory = out
    f.pc = pc
    f.gas = gas_left
    f.stack = list(stack)
    f.memory = bytearray(memory)
    return None


def interpret(host: EVMHost, msg: EVMCall, code: bytes):
    """Generator: runs `code` under `msg`; yields EVMCall for external calls
    and expects an EVMResult back; returns the frame's EVMResult."""
    f = _Frame(msg.gas)
    code_len = len(code)

    nat = _native_prefix(host, msg, code, f)
    if nat is not None:
        return nat

    # JUMPDEST analysis (skip PUSH immediates)
    jumpdests = set()
    i = 0
    while i < code_len:
        op = code[i]
        if op == 0x5B:
            jumpdests.add(i)
        i += op - 0x5F + 1 if 0x60 <= op <= 0x7F else 1

    def ret(status: int, output: bytes = b"") -> EVMResult:
        return EVMResult(
            status=int(status), output=output, gas_left=max(f.gas, 0), logs=f.logs,
            engine="interpreter",
        )

    try:
        while f.pc < code_len:
            op = code[f.pc]
            f.pc += 1

            # PUSH0..PUSH32
            if 0x5F <= op <= 0x7F:
                n = op - 0x5F
                f.use_gas(G_BASE if n == 0 else G_VERYLOW)
                # immediates truncated by end-of-code are zero-padded on the
                # RIGHT (EVM rule; adversarial bytecode must match evmone)
                f.push(int.from_bytes(code[f.pc : f.pc + n].ljust(n, b"\x00"), "big"))
                f.pc += n
                continue
            # DUP1..DUP16
            if 0x80 <= op <= 0x8F:
                f.use_gas(G_VERYLOW)
                n = op - 0x7F
                if len(f.stack) < n:
                    raise _VMError(TransactionStatus.STACK_UNDERFLOW)
                f.push(f.stack[-n])
                continue
            # SWAP1..SWAP16
            if 0x90 <= op <= 0x9F:
                f.use_gas(G_VERYLOW)
                n = op - 0x8F
                if len(f.stack) < n + 1:
                    raise _VMError(TransactionStatus.STACK_UNDERFLOW)
                f.stack[-1], f.stack[-n - 1] = f.stack[-n - 1], f.stack[-1]
                continue

            if op == 0x00:  # STOP
                return ret(0)
            elif op == 0x01:  # ADD
                f.use_gas(G_VERYLOW)
                f.push(f.pop() + f.pop())
            elif op == 0x02:  # MUL
                f.use_gas(G_LOW)
                f.push(f.pop() * f.pop())
            elif op == 0x03:  # SUB
                f.use_gas(G_VERYLOW)
                a, b = f.pop(), f.pop()
                f.push(a - b)
            elif op == 0x04:  # DIV
                f.use_gas(G_LOW)
                a, b = f.pop(), f.pop()
                f.push(a // b if b else 0)
            elif op == 0x05:  # SDIV
                f.use_gas(G_LOW)
                a, b = _signed(f.pop()), _signed(f.pop())
                f.push(0 if b == 0 else abs(a) // abs(b) * (1 if (a < 0) == (b < 0) else -1))
            elif op == 0x06:  # MOD
                f.use_gas(G_LOW)
                a, b = f.pop(), f.pop()
                f.push(a % b if b else 0)
            elif op == 0x07:  # SMOD
                f.use_gas(G_LOW)
                a, b = _signed(f.pop()), _signed(f.pop())
                f.push(0 if b == 0 else (abs(a) % abs(b)) * (1 if a >= 0 else -1))
            elif op == 0x08:  # ADDMOD
                f.use_gas(G_MID)
                a, b, n = f.pop(), f.pop(), f.pop()
                f.push((a + b) % n if n else 0)
            elif op == 0x09:  # MULMOD
                f.use_gas(G_MID)
                a, b, n = f.pop(), f.pop(), f.pop()
                f.push((a * b) % n if n else 0)
            elif op == 0x0A:  # EXP
                a, e = f.pop(), f.pop()
                f.use_gas(G_EXP + G_EXP_BYTE * ((e.bit_length() + 7) // 8))
                f.push(pow(a, e, MOD))
            elif op == 0x0B:  # SIGNEXTEND
                f.use_gas(G_LOW)
                k, v = f.pop(), f.pop()
                if k < 31:
                    bit = 8 * (k + 1) - 1
                    if v & (1 << bit):
                        v |= MASK ^ ((1 << (bit + 1)) - 1)
                    else:
                        v &= (1 << (bit + 1)) - 1
                f.push(v)
            elif op == 0x10:  # LT
                f.use_gas(G_VERYLOW)
                f.push(1 if f.pop() < f.pop() else 0)
            elif op == 0x11:  # GT
                f.use_gas(G_VERYLOW)
                f.push(1 if f.pop() > f.pop() else 0)
            elif op == 0x12:  # SLT
                f.use_gas(G_VERYLOW)
                f.push(1 if _signed(f.pop()) < _signed(f.pop()) else 0)
            elif op == 0x13:  # SGT
                f.use_gas(G_VERYLOW)
                f.push(1 if _signed(f.pop()) > _signed(f.pop()) else 0)
            elif op == 0x14:  # EQ
                f.use_gas(G_VERYLOW)
                f.push(1 if f.pop() == f.pop() else 0)
            elif op == 0x15:  # ISZERO
                f.use_gas(G_VERYLOW)
                f.push(1 if f.pop() == 0 else 0)
            elif op == 0x16:  # AND
                f.use_gas(G_VERYLOW)
                f.push(f.pop() & f.pop())
            elif op == 0x17:  # OR
                f.use_gas(G_VERYLOW)
                f.push(f.pop() | f.pop())
            elif op == 0x18:  # XOR
                f.use_gas(G_VERYLOW)
                f.push(f.pop() ^ f.pop())
            elif op == 0x19:  # NOT
                f.use_gas(G_VERYLOW)
                f.push(f.pop() ^ MASK)
            elif op == 0x1A:  # BYTE
                f.use_gas(G_VERYLOW)
                i_, v = f.pop(), f.pop()
                f.push((v >> (8 * (31 - i_))) & 0xFF if i_ < 32 else 0)
            elif op == 0x1B:  # SHL
                f.use_gas(G_VERYLOW)
                sh, v = f.pop(), f.pop()
                f.push(v << sh if sh < 256 else 0)
            elif op == 0x1C:  # SHR
                f.use_gas(G_VERYLOW)
                sh, v = f.pop(), f.pop()
                f.push(v >> sh if sh < 256 else 0)
            elif op == 0x1D:  # SAR
                f.use_gas(G_VERYLOW)
                sh, v = f.pop(), _signed(f.pop())
                f.push((v >> sh if sh < 256 else (0 if v >= 0 else -1)) & MASK)
            elif op == 0x20:  # SHA3 / KECCAK256
                off, size = f.pop(), f.pop()
                f.use_gas(G_KECCAK + G_KECCAK_WORD * ((size + 31) // 32))
                f.push(int.from_bytes(host.hash_fn(f.mread(off, size)), "big"))
            elif op == 0x30:  # ADDRESS
                f.use_gas(G_BASE)
                f.push(int.from_bytes(msg.to, "big"))
            elif op == 0x31:  # BALANCE
                f.use_gas(G_BALANCE)
                f.pop()
                f.push(0)  # balances disabled (permissioned chain default)
            elif op == 0x32:  # ORIGIN
                f.use_gas(G_BASE)
                f.push(int.from_bytes(host.tx_origin, "big"))
            elif op == 0x33:  # CALLER
                f.use_gas(G_BASE)
                f.push(int.from_bytes(msg.sender, "big"))
            elif op == 0x34:  # CALLVALUE
                f.use_gas(G_BASE)
                f.push(msg.value)
            elif op == 0x35:  # CALLDATALOAD
                f.use_gas(G_VERYLOW)
                i_ = f.pop()
                f.push(int.from_bytes(msg.data[i_ : i_ + 32].ljust(32, b"\x00"), "big"))
            elif op == 0x36:  # CALLDATASIZE
                f.use_gas(G_BASE)
                f.push(len(msg.data))
            elif op == 0x37:  # CALLDATACOPY
                dst, src, size = f.pop(), f.pop(), f.pop()
                f.use_gas(G_VERYLOW + G_COPY_WORD * ((size + 31) // 32))
                f.mwrite(dst, msg.data[src : src + size].ljust(size, b"\x00"))
            elif op == 0x38:  # CODESIZE
                f.use_gas(G_BASE)
                f.push(code_len)
            elif op == 0x39:  # CODECOPY
                dst, src, size = f.pop(), f.pop(), f.pop()
                f.use_gas(G_VERYLOW + G_COPY_WORD * ((size + 31) // 32))
                f.mwrite(dst, code[src : src + size].ljust(size, b"\x00"))
            elif op == 0x3A:  # GASPRICE
                f.use_gas(G_BASE)
                f.push(0)
            elif op == 0x3B:  # EXTCODESIZE
                f.use_gas(G_EXTCODE)
                f.push(len(host.get_code(f.pop().to_bytes(32, "big")[12:])))
            elif op == 0x3C:  # EXTCODECOPY
                addr = f.pop().to_bytes(32, "big")[12:]
                dst, src, size = f.pop(), f.pop(), f.pop()
                f.use_gas(G_EXTCODE + G_COPY_WORD * ((size + 31) // 32))
                ext = host.get_code(addr)
                f.mwrite(dst, ext[src : src + size].ljust(size, b"\x00"))
            elif op == 0x3D:  # RETURNDATASIZE
                f.use_gas(G_BASE)
                f.push(len(f.returndata))
            elif op == 0x3E:  # RETURNDATACOPY
                dst, src, size = f.pop(), f.pop(), f.pop()
                f.use_gas(G_VERYLOW + G_COPY_WORD * ((size + 31) // 32))
                if src + size > len(f.returndata):
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                f.mwrite(dst, f.returndata[src : src + size])
            elif op == 0x3F:  # EXTCODEHASH
                f.use_gas(G_EXTCODE)
                h = host.get_code_hash(f.pop().to_bytes(32, "big")[12:])
                f.push(int.from_bytes(h, "big") if h else 0)
            elif op == 0x40:  # BLOCKHASH
                f.use_gas(G_BASE)
                f.pop()
                f.push(0)  # historical hashes not exposed (ref: EnvInfo limited)
            elif op == 0x41:  # COINBASE
                f.use_gas(G_BASE)
                f.push(0)
            elif op == 0x42:  # TIMESTAMP
                f.use_gas(G_BASE)
                f.push(host.timestamp)
            elif op == 0x43:  # NUMBER
                f.use_gas(G_BASE)
                f.push(host.block_number)
            elif op == 0x44:  # DIFFICULTY / PREVRANDAO
                f.use_gas(G_BASE)
                f.push(0)
            elif op == 0x45:  # GASLIMIT
                f.use_gas(G_BASE)
                f.push(host.gas_limit)
            elif op == 0x46:  # CHAINID
                f.use_gas(G_BASE)
                f.push(0)
            elif op == 0x47:  # SELFBALANCE
                f.use_gas(G_LOW)
                f.push(0)
            elif op == 0x48:  # BASEFEE
                f.use_gas(G_BASE)
                f.push(0)
            elif op == 0x50:  # POP
                f.use_gas(G_BASE)
                f.pop()
            elif op == 0x51:  # MLOAD
                f.use_gas(G_VERYLOW)
                f.push(int.from_bytes(f.mread(f.pop(), 32), "big"))
            elif op == 0x52:  # MSTORE
                f.use_gas(G_VERYLOW)
                off, v = f.pop(), f.pop()
                f.mwrite(off, v.to_bytes(32, "big"))
            elif op == 0x53:  # MSTORE8
                f.use_gas(G_VERYLOW)
                off, v = f.pop(), f.pop()
                f.mwrite(off, bytes([v & 0xFF]))
            elif op == 0x54:  # SLOAD
                f.use_gas(G_SLOAD)
                f.push(host.get_storage(msg.to, f.pop()))
            elif op == 0x55:  # SSTORE
                if msg.static:
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                slot, v = f.pop(), f.pop()
                old = host.get_storage(msg.to, slot)
                f.use_gas(G_SSTORE_SET if old == 0 and v != 0 else G_SSTORE_RESET)
                host.set_storage(msg.to, slot, v)
            elif op == 0x56:  # JUMP
                f.use_gas(G_MID)
                dst = f.pop()
                if dst not in jumpdests:
                    raise _VMError(TransactionStatus.BAD_JUMP_DESTINATION)
                f.pc = dst
            elif op == 0x57:  # JUMPI
                f.use_gas(G_HIGH)
                dst, cond = f.pop(), f.pop()
                if cond:
                    if dst not in jumpdests:
                        raise _VMError(TransactionStatus.BAD_JUMP_DESTINATION)
                    f.pc = dst
            elif op == 0x58:  # PC
                f.use_gas(G_BASE)
                f.push(f.pc - 1)
            elif op == 0x59:  # MSIZE
                f.use_gas(G_BASE)
                f.push(len(f.memory))
            elif op == 0x5A:  # GAS
                f.use_gas(G_BASE)
                f.push(f.gas)
            elif op == 0x5B:  # JUMPDEST
                f.use_gas(G_JUMPDEST)
            elif 0xA0 <= op <= 0xA4:  # LOG0..LOG4
                if msg.static:
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                ntopics = op - 0xA0
                off, size = f.pop(), f.pop()
                topics = [f.pop().to_bytes(32, "big") for _ in range(ntopics)]
                f.use_gas(G_LOG + G_LOGTOPIC * ntopics + G_LOGDATA * size)
                f.logs.append(
                    LogEntry(address=msg.to, topics=topics, data=f.mread(off, size))
                )
            elif op in (0xF0, 0xF5):  # CREATE / CREATE2
                if msg.static:
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                salt = None
                if op == 0xF5:
                    value, off, size, salt = f.pop(), f.pop(), f.pop(), f.pop()
                else:
                    value, off, size = f.pop(), f.pop(), f.pop()
                f.use_gas(G_CREATE)
                init = f.mread(off, size)
                gas_pass = f.gas - f.gas // 64
                f.use_gas(gas_pass)
                res = yield EVMCall(
                    kind="create2" if salt is not None else "create",
                    sender=msg.to,
                    data=init,
                    gas=gas_pass,
                    value=value,
                    depth=msg.depth + 1,
                    salt=salt,
                )
                f.gas += res.gas_left
                f.logs.extend(res.logs)
                f.returndata = b"" if res.ok else res.output
                f.push(int.from_bytes(res.create_address, "big") if res.ok else 0)
            elif op in (0xF1, 0xF2, 0xF4, 0xFA):  # CALL/CALLCODE/DELEGATECALL/STATICCALL
                f.use_gas(G_CALL)
                gas_req = f.pop()
                to = f.pop().to_bytes(32, "big")[12:]
                value = 0
                if op in (0xF1, 0xF2):
                    value = f.pop()
                in_off, in_size, out_off, out_size = f.pop(), f.pop(), f.pop(), f.pop()
                data = f.mread(in_off, in_size)
                f.mem_extend(out_off, out_size)
                gas_pass = min(gas_req, f.gas - f.gas // 64)
                f.use_gas(gas_pass)
                if msg.static and op == 0xF1 and value:
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                kind = {0xF1: "call", 0xF2: "callcode", 0xF4: "delegatecall", 0xFA: "staticcall"}[op]
                res = yield EVMCall(
                    kind=kind,
                    sender=msg.sender if op == 0xF4 else msg.to,
                    to=msg.to if op in (0xF2, 0xF4) else to,
                    code_address=to,
                    data=data,
                    gas=gas_pass,
                    value=msg.value if op == 0xF4 else value,
                    static=msg.static or op == 0xFA,
                    depth=msg.depth + 1,
                )
                f.gas += res.gas_left
                f.logs.extend(res.logs)
                f.returndata = res.output
                if out_size and res.output:
                    f.mwrite(out_off, res.output[:out_size])
                f.push(1 if res.ok else 0)
            elif op == 0xF3:  # RETURN
                off, size = f.pop(), f.pop()
                return ret(0, f.mread(off, size))
            elif op == 0xFD:  # REVERT
                off, size = f.pop(), f.pop()
                return ret(TransactionStatus.REVERT_INSTRUCTION, f.mread(off, size))
            elif op == 0xFE:  # INVALID
                raise _VMError(TransactionStatus.BAD_INSTRUCTION)
            elif op == 0xFF:  # SELFDESTRUCT
                # FISCO semantics (EVMHostInterface.cpp:145-152,
                # HostContext.h:152 suicide): the beneficiary is IGNORED (no
                # balance model) and the contract is added to the BLOCK's
                # suicide set. The kill itself — code and codeHash emptied,
                # account row KEPT so the address is burned for any future
                # CREATE2 — happens at end of block (killSuicides,
                # BlockContext.cpp:107-137, run from getHash
                # TransactionExecutor.cpp:1054). Like the reference, the
                # registration is immediate and survives a later revert of
                # this frame's ancestors (m_suicides has no unwind path),
                # and later txs in the SAME block still see the old code.
                if msg.static:
                    raise _VMError(TransactionStatus.BAD_INSTRUCTION)
                f.use_gas(5000)
                f.pop()  # beneficiary, ignored
                host.register_suicide(msg.to)
                return ret(0)
            else:
                raise _VMError(TransactionStatus.BAD_INSTRUCTION)
        return ret(0)
    except _VMError as e:
        return EVMResult(
            status=int(e.status), output=b"", gas_left=0, logs=[],
            engine="interpreter",
        )
