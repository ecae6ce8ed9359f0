"""Native EVM fast-prefix engine vs the Python interpreter — differential.

The two engines (native/fisco_native.cpp fisco_evm_run and executor/evm.py
interpret) must agree on status, output, gas, storage effects and logs for
every frame, since a node may run either depending on library availability —
any divergence forks consensus. FISCO_NO_NATIVE_EVM=1 pins the Python leg.
"""

import os

import pytest

from evm_asm import _deployer, asm, counter_runtime
from fisco_bcos_tpu import native_bind
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor.evm import EVMCall, EVMHost, interpret
from fisco_bcos_tpu.storage.memory_storage import MemoryStorage
from fisco_bcos_tpu.storage.state_storage import StateStorage

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)

pytestmark = pytest.mark.skipif(
    native_bind.load() is None, reason="native library unavailable"
)


def _run(code, data=b"", gas=1_000_000, static=False, native=True, store=None):
    """One frame through the chosen engine; returns (result, storage_dump)."""
    old = os.environ.pop("FISCO_NO_NATIVE_EVM", None)
    if not native:
        os.environ["FISCO_NO_NATIVE_EVM"] = "1"
    try:
        backing = MemoryStorage()
        if store:
            overlay0 = StateStorage(backing)
            for slot, val in store.items():
                host0 = EVMHost(overlay0, SUITE.hash, 0, 0, b"", 0)
                host0.set_storage(b"\x11" * 20, slot, val)
            overlay = overlay0
        else:
            overlay = StateStorage(backing)
        host = EVMHost(overlay, SUITE.hash, 7, 1_700_000_000, b"\x22" * 20,
                       3_000_000_000)
        msg = EVMCall(kind="call", sender=b"\x22" * 20, to=b"\x11" * 20,
                      code_address=b"\x11" * 20, data=data, gas=gas,
                      static=static)
        gen = interpret(host, msg, code)
        try:
            next(gen)
            raise AssertionError("unexpected external call")
        except StopIteration as si:
            res = si.value
        dump = sorted((k, e.get()) for t, k, e in overlay.traverse())
        return res, dump
    finally:
        if old is not None:
            os.environ["FISCO_NO_NATIVE_EVM"] = old
        else:
            os.environ.pop("FISCO_NO_NATIVE_EVM", None)


def _drive_with_calls(code, data=b"", gas=500_000, native=True):
    """Run a frame answering every yielded external call as a codeless
    callee (empty success, all gas returned). Returns
    (result, storage_dump, n_escaped_calls) — the shared driver for every
    escape-path test (review: three near-copies consolidated)."""
    from fisco_bcos_tpu.executor.evm import EVMResult

    old = os.environ.pop("FISCO_NO_NATIVE_EVM", None)
    if not native:
        os.environ["FISCO_NO_NATIVE_EVM"] = "1"
    try:
        overlay = StateStorage(MemoryStorage())
        host = EVMHost(overlay, SUITE.hash, 7, 1_700_000_000, b"\x22" * 20,
                       3_000_000_000)
        msg = EVMCall(kind="call", sender=b"\x22" * 20, to=b"\x11" * 20,
                      code_address=b"\x11" * 20, data=data, gas=gas)
        gen = interpret(host, msg, code)
        calls = 0
        try:
            req = next(gen)
            while True:
                calls += 1
                req = gen.send(EVMResult(status=0, output=b"", gas_left=req.gas))
        except StopIteration as si:
            dump = sorted((k, e.get()) for t, k, e in overlay.traverse())
            return si.value, dump, calls
    finally:
        if old is not None:
            os.environ["FISCO_NO_NATIVE_EVM"] = old
        else:
            os.environ.pop("FISCO_NO_NATIVE_EVM", None)


def _diff(code, data=b"", gas=1_000_000, static=False, store=None):
    rn, dn = _run(code, data, gas, static, native=True, store=store)
    rp, dp = _run(code, data, gas, static, native=False, store=store)
    assert rn.status == rp.status, (rn.status, rp.status, rp.output)
    assert rn.output == rp.output
    assert rn.gas_left == rp.gas_left, (gas - rn.gas_left, gas - rp.gas_left)
    assert dn == dp
    assert [(l.topics, l.data) for l in rn.logs] == [
        (l.topics, l.data) for l in rp.logs
    ]
    return rn


FIX = os.path.join(os.path.dirname(__file__), "fixtures")


class TestDifferential:
    def test_solc_helloworld_deploy_and_calls(self):
        code = bytes.fromhex(open(os.path.join(FIX, "hello_world_solc.hex")).read())
        # constructor (init code frame): returns the runtime
        r = _diff(code, gas=5_000_000)
        assert r.status == 0 and len(r.output) > 500
        runtime = r.output
        _diff(runtime, CODEC.encode_call("get()"), gas=5_000_000)
        _diff(runtime, CODEC.encode_call("set(string)", "differential run"),
              gas=5_000_000)
        _diff(runtime, b"\xde\xad\xbe\xef", gas=5_000_000)  # fallback revert

    def test_counter_asm(self):
        runtime = counter_runtime(CODEC)
        _diff(_deployer(runtime))
        _diff(runtime, CODEC.selector("inc()"))
        _diff(runtime, CODEC.selector("get()"), store={0: 41})

    @pytest.mark.parametrize("name,ops", [
        ("arith", [("PUSH", 7), ("PUSH", 3), "SUB", ("PUSH", 5), "MUL",
                   ("PUSH", 3), "SWAP1", "DIV", ("PUSH", 0), "MSTORE",
                   ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("signed", [("PUSH", (1 << 256) - 5), ("PUSH", 3), "SWAP1", "SDIV",
                    ("PUSH", (1 << 256) - 7), ("PUSH", 4), "SWAP1", "SMOD",
                    "ADD", ("PUSH", 0), "MSTORE",
                    ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("modmath", [("PUSH", 11), ("PUSH", 9), ("PUSH", 8), "ADDMOD",
                     ("PUSH", 7), ("PUSH", 6), ("PUSH", 5), "MULMOD", "ADD",
                     ("PUSH", 0), "MSTORE",
                     ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("exp", [("PUSH", 300), ("PUSH", 7), "EXP", ("PUSH", 0), "MSTORE",
                 ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("shifts", [("PUSH", ((1 << 255) | 0x1234).to_bytes(32, "big")),
                    ("PUSH", 4), "SWAP1",
                    "SAR", ("PUSH", 100), "SHL", ("PUSH", 17), "SHR",
                    ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("byte_signext", [("PUSH", (0xFF80).to_bytes(32, "big")),
                          ("PUSH", 0), "SIGNEXTEND",
                          ("PUSH", 30), "BYTE", ("PUSH", 0), "MSTORE",
                          ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("sha3", [("PUSH", 0xDEAD), ("PUSH", 0), "MSTORE",
                  ("PUSH", 32), ("PUSH", 0), "SHA3",
                  ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("env", ["ADDRESS", "CALLER", "XOR", "ORIGIN", "AND",
                 "TIMESTAMP", "NUMBER", "ADD", "ADD", "GASLIMIT", "ADD",
                 "CALLDATASIZE", "ADD", "MSIZE", "ADD", "PC", "ADD",
                 ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("memops", [("PUSH", 0xAB), ("PUSH", 100), "MSTORE8",
                    ("PUSH", 64), "MLOAD", ("PUSH", 0x11), "ADD",
                    ("PUSH", 200), "MSTORE", "MSIZE",
                    ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN"]),
        ("revert", [("PUSH", 0x42), ("PUSH", 0), "MSTORE",
                    ("PUSH", 32), ("PUSH", 0), "REVERT"]),
        ("invalid", ["INVALID"]),
        ("stack_under", ["POP"]),
    ])
    def test_op_corpus(self, name, ops):
        _diff(asm(*ops), data=b"\x01\x02\x03")

    def test_calldata_ops(self):
        code = asm(
            ("PUSH", 1), "CALLDATALOAD",  # partial word, zero-padded
            ("PUSH", 1000), "CALLDATALOAD", "ADD",  # out of range -> 0
            ("PUSH", 0), "MSTORE",
            ("PUSH", 8), ("PUSH", 2), ("PUSH", 40), "CALLDATACOPY",
            ("PUSH", 64), ("PUSH", 0), "RETURN",
        )
        _diff(code, data=bytes(range(1, 30)))

    def test_codecopy_and_truncated_push(self):
        code = asm(
            ("PUSH", 16), ("PUSH", 0), ("PUSH", 0), "CODECOPY",
            ("PUSH", 200), ("PUSH", 90), ("PUSH", 32), "CODECOPY",  # past end
            ("PUSH", 64), ("PUSH", 0), "RETURN",
        ) + b"\x7f\x01\x02"  # PUSH32 truncated by end of code
        _diff(code)

    def test_storage_set_reset_gas(self):
        sstore_fresh = asm(("PUSH", 5), ("PUSH", 1), "SSTORE", "STOP")
        r1 = _diff(sstore_fresh)  # set: 20k
        r2 = _diff(sstore_fresh, store={1: 9})  # reset: 5k
        assert (1_000_000 - r1.gas_left) - (1_000_000 - r2.gas_left) == 15_000

    def test_sload_roundtrip(self):
        code = asm(("PUSH", 3), "SLOAD", ("PUSH", 1), "ADD",
                   ("PUSH", 3), "SSTORE",
                   ("PUSH", 3), "SLOAD", ("PUSH", 0), "MSTORE",
                   ("PUSH", 32), ("PUSH", 0), "RETURN")
        r = _diff(code, store={3: 41})
        assert int.from_bytes(r.output, "big") == 42

    def test_logs(self):
        code = asm(
            ("PUSH", 0xCAFE), ("PUSH", 0), "MSTORE",
            ("PUSH", 0xAA), ("PUSH", 0xBB),
            ("PUSH", 32), ("PUSH", 0), "LOG2",
            "STOP",
        )
        r = _diff(code)
        assert len(r.logs) == 1 and len(r.logs[0].topics) == 2

    def test_static_frame_rejects_writes(self):
        _diff(asm(("PUSH", 1), ("PUSH", 1), "SSTORE", "STOP"), static=True)
        _diff(asm(("PUSH", 0), ("PUSH", 0), "LOG0", "STOP"), static=True)

    def test_jump_table(self):
        code = asm(
            ("PUSH", 0), "CALLDATALOAD", ("ref", "a"), "JUMPI",
            ("PUSH", 7), ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN",
            ("label", "a"), ("PUSH", 9), ("PUSH", 0), "MSTORE",
            ("PUSH", 32), ("PUSH", 0), "RETURN",
        )
        for data in (b"", b"\x00" * 31 + b"\x01"):
            _diff(code, data=data)

    def test_bad_jump(self):
        _diff(asm(("PUSH", 3), "JUMP", "STOP"))

    def test_out_of_gas_identical_point(self):
        # memory-expansion OOG mid-run: identical status and gas burn
        code = asm(("PUSH", 1), ("PUSH", 0x1FFFFF), "MSTORE8", "STOP")
        _diff(code, gas=3_000)
        _diff(code, gas=100_000_000)  # enough gas: succeeds on both
        # cap breach is OUT_OF_GAS on both
        _diff(asm(("PUSH", 1), ("PUSH", 0x200010), "MSTORE8", "STOP"),
              gas=100_000_000)

    def test_escape_resumes_python_identically(self):
        """A frame with a CALL escapes the native engine mid-frame; the
        Python resume must produce the same receipt as a pure-Python run.
        The inner call targets a codeless address (succeeds empty, EVM rule),
        so the whole thing still runs in one frame driver."""
        code = asm(
            ("PUSH", 0x55), ("PUSH", 64), "MSTORE",      # native prefix work
            ("PUSH", 0), ("PUSH", 0), ("PUSH", 0), ("PUSH", 0), ("PUSH", 0),
            ("PUSH", 0x9999), "GAS", "CALL",             # escapes here
            ("PUSH", 64), "MLOAD", "ADD",                # post-escape work
            ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN",
        )

        (rn, _, cn) = _drive_with_calls(code, native=True)
        (rp, _, cp) = _drive_with_calls(code, native=False)
        assert cn == cp == 1  # exactly one escaped CALL on both legs
        assert (rn.status, rn.output, rn.gas_left) == (rp.status, rp.output, rp.gas_left)
        assert int.from_bytes(rn.output, "big") == 0x55 + 1


def test_native_speedup_on_solc_code():
    """The point of the engine: a real solc frame should run much faster
    natively (informational; asserts only a sane lower bound)."""
    import time

    code = bytes.fromhex(open(os.path.join(FIX, "hello_world_solc.hex")).read())
    r, _ = _run(code, gas=5_000_000, native=True)
    runtime = r.output
    call = CODEC.encode_call("set(string)", "speed run " * 10)

    def t(native):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(20):
                _run(runtime, call, gas=5_000_000, native=native)
            best = min(best, time.perf_counter() - t0)
        return best

    tn, tp = t(True), t(False)
    print(f"native {tn*50:.2f} ms/frame vs python {tp*50:.2f} ms/frame "
          f"({tp/tn:.1f}x)")
    assert tn < tp  # native must not be slower


def test_sm_suite_frames_stay_on_python():
    """The native engine hardcodes keccak SHA3 — under the SM suite (sm3
    storage-slot hashing) it must decline the frame entirely, or nodes
    with/without the library would compute different state roots."""
    from fisco_bcos_tpu.crypto.suite import sm_suite
    from fisco_bcos_tpu.executor.evm import _Frame, _native_prefix

    sm = sm_suite()
    overlay = StateStorage(MemoryStorage())
    host = EVMHost(overlay, sm.hash, 1, 2, b"\x22" * 20, 3_000_000_000)
    msg = EVMCall(kind="call", sender=b"\x22" * 20, to=b"\x11" * 20,
                  code_address=b"\x11" * 20, data=b"", gas=100_000)
    code = asm(("PUSH", 32), ("PUSH", 0), "SHA3", ("PUSH", 0), "MSTORE",
               ("PUSH", 32), ("PUSH", 0), "RETURN")
    assert _native_prefix(host, msg, code, _Frame(msg.gas)) is None

    # and the full frame (Python path) produces the sm3 digest of 32 zeros
    gen = interpret(host, msg, code)
    try:
        next(gen)
        raise AssertionError
    except StopIteration as si:
        res = si.value
    from fisco_bcos_tpu.crypto.ref.sm3 import sm3

    assert res.output == sm3(b"\x00" * 32)


class TestDifferentialFuzz:
    """Seeded random-program fuzz: both engines must agree on EVERY program,
    including ones that trip errors mid-stream or escape at a CALL and
    resume in Python (the state-transfer path). Deterministic corpus."""

    OPS_POOL = [
        "ADD", "MUL", "SUB", "DIV", "SDIV", "MOD", "SMOD", "ADDMOD",
        "MULMOD", "EXP", "SIGNEXTEND", "LT", "GT", "SLT", "SGT", "EQ",
        "ISZERO", "AND", "OR", "XOR", "NOT", "BYTE", "SHL", "SHR", "SAR",
        "SHA3", "ADDRESS", "CALLER", "ORIGIN", "CALLVALUE", "CALLDATALOAD",
        "CALLDATASIZE", "CODESIZE", "TIMESTAMP", "NUMBER", "GASLIMIT",
        "POP", "MLOAD", "MSTORE", "MSTORE8", "SLOAD", "SSTORE", "PC",
        "MSIZE", "GAS", "DUP1", "DUP2", "DUP3", "SWAP1", "SWAP2",
    ]

    def _body_items(self, rng, pool=None) -> list:
        pool = pool or self.OPS_POOL
        items = []
        # seed the stack so early ops rarely underflow (underflow programs
        # are still valid corpus members — both engines must agree on them)
        for _ in range(rng.integers(2, 6)):
            width = int(rng.integers(1, 33))
            items.append(("PUSH", bytes(rng.integers(0, 256, width,
                                                     dtype="uint8"))))
        for _ in range(int(rng.integers(5, 40))):
            if rng.random() < 0.35:
                width = int(rng.integers(1, 33))
                items.append(("PUSH", bytes(rng.integers(0, 256, width,
                                                         dtype="uint8"))))
            else:
                items.append(pool[int(rng.integers(0, len(pool)))])
        return items

    def _program(self, rng):
        items = self._body_items(rng)
        ending = rng.random()
        if ending < 0.6:
            items += [("PUSH", 64), ("PUSH", 0), "RETURN"]
        elif ending < 0.8:
            items += [("PUSH", 32), ("PUSH", 0), "REVERT"]
        else:
            items.append("STOP")
        return asm(*items)

    def test_random_straightline_corpus(self):
        import numpy as np

        rng = np.random.default_rng(0xF15C0)
        for case in range(150):
            code = self._program(rng)
            data = bytes(rng.integers(0, 256, int(rng.integers(0, 68)),
                                      dtype="uint8"))
            store = {int(rng.integers(0, 4)): int(rng.integers(0, 1 << 62))}
            try:
                _diff(code, data=data, gas=300_000, store=store)
            except AssertionError:
                raise AssertionError(
                    f"engines diverged on fuzz case {case}: {code.hex()}"
                )

    def test_random_escape_resume_corpus(self):
        """Programs with a CALL in the middle: the native engine escapes and
        Python resumes — the resumed run must equal the pure-Python run.
        The corpus must actually EXERCISE the escape (a body can still
        error before reaching the CALL), so a minimum escaped-case count is
        asserted rather than trusted (review: the old byte-slicing version
        silently reached the CALL in only ~1/4 of cases)."""
        import numpy as np

        rng = np.random.default_rng(0xE5CA7E)
        # memory ops with unconstrained 256-bit offsets OOG almost instantly
        # (2 MiB cap) and kill the body before the CALL — mask them here;
        # the straightline corpus still covers them
        pool = [op for op in self.OPS_POOL
                if op not in ("SHA3", "MLOAD", "MSTORE", "MSTORE8", "EXP")]
        escaped = 0
        for case in range(40):
            items = self._body_items(rng, pool)  # NO ending: falls into CALL
            code = asm(*items,
                ("PUSH", 0), ("PUSH", 0), ("PUSH", 0), ("PUSH", 0),
                ("PUSH", 0), ("PUSH", 0x7777), "GAS", "CALL",
                ("PUSH", 3), "ADD",
                ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0), "RETURN",
            )
            rn, dn, cn = _drive_with_calls(code, data=b"\x05\x06",
                                           gas=300_000, native=True)
            rp, dp, cp = _drive_with_calls(code, data=b"\x05\x06",
                                           gas=300_000, native=False)
            assert cn == cp, f"call counts diverged on case {case}"
            escaped += 1 if cn else 0
            assert (rn.status, rn.output, rn.gas_left, dn) == (
                rp.status, rp.output, rp.gas_left, dp
            ), f"escape-resume diverged on case {case}: {code.hex()}"
        # the corpus only earns its name if most cases really escaped
        assert escaped >= 25, f"only {escaped}/40 cases reached the CALL"
