"""ctypes binding for the host-native crypto core (native/fisco_native.cpp).

Reference role: the wedpr-Rust/OpenSSL FFI layer of bcos-crypto.  The shared
library is built on demand with g++ (baked into the image; pybind11 is not —
ctypes keeps the dependency surface at zero).  Every consumer falls back to
the pure-Python crypto/ref implementations when the toolchain is missing, so
the native layer is a pure accelerator, never a requirement — and the test
suite asserts bit-identical outputs between both.
"""

from __future__ import annotations

import ctypes
import hashlib
import itertools
import os
import subprocess
import threading

from .utils.log import get_logger

_log = get_logger("native")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "native", "fisco_native.cpp")
_LIB = os.path.join(_REPO, "native", "libfisco_native.so")
_ISA_TAG = _LIB + ".isa"  # host-ISA signature of the existing build

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _host_isa() -> str:
    """Stable signature of this host's instruction set. The library is built
    with -march=native (2x on the 4x64 Montgomery core via mulx/adx), so a
    build moved to a different CPU — shared volume, docker image — must be
    rebuilt, not executed: a SIGILL would kill the process instead of
    falling back to crypto/ref."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha256(
                        " ".join(sorted(line.split()[2:])).encode()
                    ).hexdigest()[:16]
    except OSError:
        pass
    import platform

    return platform.machine()


def _build() -> bool:
    # -fopenmp parallelizes the batch loops across host cores; a toolchain
    # without libgomp still gets the single-threaded library.
    # Built under a per-process temp name and renamed into place: several
    # processes starting from a fresh checkout (four node processes, the
    # chip smoke's children) each compile their own copy, and whichever
    # rename lands last wins whole — nobody ever dlopens a half-written
    # file, which g++ writing straight to _LIB allowed.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-march=native", "-funroll-loops", "-shared",
            "-fPIC", "-o", tmp, _SRC]
    try:
        res = subprocess.run(
            base[:1] + ["-fopenmp"] + base[1:],
            capture_output=True,
            text=True,
            timeout=120,
        )
        if res.returncode != 0:
            res = subprocess.run(base, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _log.info("native build unavailable: %s", e)
        return False
    if res.returncode != 0:
        _log.warning("native build failed:\n%s", res.stderr[-2000:])
        return False
    try:
        os.replace(tmp, _LIB)
    except OSError as e:
        _log.warning("native build could not be moved into place: %s", e)
        return False
    try:
        with open(tmp, "w") as f:
            f.write(_host_isa())
        os.replace(tmp, _ISA_TAG)
    except OSError:
        pass  # untagged build: the next process rebuilds instead of trusting it
    return True


def _isa_tag() -> str | None:
    try:
        with open(_ISA_TAG) as f:
            return f.read().strip()
    except OSError:
        return None


def _needs_rebuild() -> bool:
    if not os.path.exists(_LIB):
        return True
    if os.path.exists(_SRC) and os.path.getmtime(_SRC) > os.path.getmtime(_LIB):
        return True
    tag = _isa_tag()
    if tag is not None and tag != _host_isa():
        return True  # -march=native artifact from a different CPU: SIGILL risk
    if tag is None:
        # unknown provenance: rebuild when we can; when we can't (source-less
        # packaged install), load() refuses it — the library was built with
        # -march=native and a wrong-CPU copy SIGILLs, which no symbol guard
        # can catch. Packaged installs must ship the .isa tag beside the .so.
        return True
    return False


def load() -> ctypes.CDLL | None:
    """The shared library, building it on first use; None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("FISCO_NO_NATIVE"):
            return None
        if _needs_rebuild():
            if not os.path.exists(_SRC):
                if os.path.exists(_LIB):
                    _log.warning(
                        "prebuilt %s has no matching .isa tag and no source "
                        "to rebuild from; refusing to load it (-march=native "
                        "artifacts SIGILL on other CPUs) — using pure-Python "
                        "crypto instead", _LIB,
                    )
                return None
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as e:
            _log.warning("native load failed: %s", e)
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        try:
            _bind_symbols(lib, u8p)
        except AttributeError as e:
            # a stale .so missing newer symbols: disable rather than crash
            # every later call (the mtime/ISA checks normally prevent this,
            # but a source-less packaged install can still hit it)
            _log.warning("native library is stale, ignoring it: %s", e)
            return None
        _lib = lib
        _log.info("native crypto core loaded (%s)", _LIB)
        return _lib


# EVM fast-prefix engine callback signatures (native/fisco_native.cpp)
_U8P = ctypes.POINTER(ctypes.c_uint8)
EVM_SLOAD_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, _U8P, _U8P)
EVM_SSTORE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, _U8P, _U8P)
EVM_LOG_FN = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, _U8P, ctypes.c_int, _U8P, ctypes.c_size_t
)
EVM_RESULT_FN = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
    ctypes.c_int64, _U8P, ctypes.c_size_t, _U8P, ctypes.c_size_t, _U8P,
    ctypes.c_size_t,
)


_ZERO32 = b"\x00" * 32


class EvmBinding:
    """``fisco_evm_run`` bound to one host: the four ``CFUNCTYPE`` objects are
    made once, over the host's plain-Python callbacks, and every ``run``
    passes them again, so a caller with many frames on one host (a block's
    contract frame) pays the binding once and a caller with one (``evm_run``)
    goes the same way.

    sload(slot32) -> bytes32, sstore(slot32, val32), log(topics list, data).
    ctypes swallows what a callback raises: the first such exception is kept
    and raised after the run."""

    __slots__ = ("_fn", "_callbacks", "_slot")

    def __init__(self, lib: ctypes.CDLL, sload, sstore, log):
        # [the run's result, the first exception of a callback]: a list, so
        # that the closures below hold no reference to this object
        self._slot = slot = [None, None]
        string_at, memmove = ctypes.string_at, ctypes.memmove

        def _sload(_ctx, slot_p, out_p):
            try:
                memmove(out_p, sload(string_at(slot_p, 32)), 32)
            except Exception as e:
                if slot[1] is None:
                    slot[1] = e
                memmove(out_p, _ZERO32, 32)

        def _sstore(_ctx, slot_p, val_p):
            try:
                sstore(string_at(slot_p, 32), string_at(val_p, 32))
            except Exception as e:
                if slot[1] is None:
                    slot[1] = e

        def _log(_ctx, topics_p, ntopics, data_p, dlen):
            try:
                raw = string_at(topics_p, 32 * ntopics) if ntopics else b""
                topics = [raw[32 * t : 32 * t + 32] for t in range(ntopics)]
                log(topics, string_at(data_p, dlen) if dlen else b"")
            except Exception as e:
                if slot[1] is None:
                    slot[1] = e

        def _result(_ctx, kind, status, pc, gas_left, stack_p, n_stack, mem_p,
                    mem_len, out_p, out_len):
            try:
                if kind == 0:
                    slot[0] = (
                        "done", status, gas_left,
                        string_at(out_p, out_len) if out_len else b"",
                    )
                else:
                    raw = string_at(stack_p, n_stack * 32) if n_stack else b""
                    stack = [
                        int.from_bytes(raw[i * 32 : i * 32 + 32], "big")
                        for i in range(n_stack)
                    ]
                    memory = string_at(mem_p, mem_len) if mem_len else b""
                    slot[0] = ("escape", pc, gas_left, stack, memory)
            except Exception as e:
                if slot[1] is None:
                    slot[1] = e

        self._fn = lib.fisco_evm_run
        self._callbacks = (
            EVM_SLOAD_FN(_sload), EVM_SSTORE_FN(_sstore), EVM_LOG_FN(_log),
            EVM_RESULT_FN(_result),
        )

    def run(self, code: bytes, calldata: bytes, self_addr20: bytes,
            caller20: bytes, origin20: bytes, value32: bytes, gas: int,
            block_number: int, timestamp: int, gas_limit: int, static_flag: int):
        """One frame -> ("done", status, gas_left, output) or
        ("escape", pc, gas_left, [stack ints bottom-first], memory bytes).
        The byte strings go to the engine as they are (no copy): addresses
        are 20 bytes, the value 32."""
        slot = self._slot
        slot[0] = slot[1] = None
        self._fn(
            code, len(code), calldata, len(calldata), self_addr20, caller20,
            origin20, value32, gas, block_number, timestamp, gas_limit,
            static_flag, None, *self._callbacks,
        )
        if slot[1] is not None:
            raise slot[1]
        return slot[0]


def addr20(addr: bytes) -> bytes:
    """An address as the engine takes it: 20 bytes, left-padded."""
    return addr if len(addr) == 20 else addr.rjust(20, b"\x00")[:20]


def bind_evm(sload, sstore, log) -> EvmBinding | None:
    """The native EVM fast-prefix engine bound to these callbacks; None when
    the native library is unavailable."""
    lib = load()
    return None if lib is None else EvmBinding(lib, sload, sstore, log)


def evm_run(code: bytes, calldata: bytes, self_addr: bytes, caller: bytes,
            origin: bytes, value: int, gas: int, block_number: int,
            timestamp: int, gas_limit: int, static_flag: bool,
            sload, sstore, log):
    """One frame on the native engine, the one-call form of ``EvmBinding``:
    its result, or None when the native library is unavailable."""
    engine = bind_evm(sload, sstore, log)
    if engine is None:
        return None
    return engine.run(
        code, calldata, addr20(self_addr), addr20(caller), addr20(origin),
        value.to_bytes(32, "big"), gas, block_number, timestamp, gas_limit,
        1 if static_flag else 0,
    )


def _bind_symbols(lib: ctypes.CDLL, u8p) -> None:
    for name in ("fisco_keccak256", "fisco_sha256", "fisco_sm3"):
        fn = getattr(lib, name)
        fn.argtypes = [u8p, ctypes.c_size_t, u8p]
        fn.restype = None
    for name in ("fisco_keccak256_batch", "fisco_sm3_batch"):
        fn = getattr(lib, name)
        fn.argtypes = [
            ctypes.c_size_t, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_char_p,
        ]
        fn.restype = None
    lib.fisco_sm4_cbc.argtypes = [
        u8p, u8p, u8p, ctypes.c_size_t, u8p, ctypes.c_int,
    ]
    lib.fisco_sm4_cbc.restype = None
    lib.fisco_secp256k1_verify.argtypes = [u8p, u8p, u8p, u8p]
    lib.fisco_secp256k1_verify.restype = ctypes.c_int
    lib.fisco_secp256k1_recover.argtypes = [u8p, u8p, u8p, ctypes.c_int, u8p]
    lib.fisco_secp256k1_recover.restype = ctypes.c_int
    lib.fisco_secp256k1_sign.argtypes = [
        u8p, u8p, u8p, u8p, ctypes.POINTER(ctypes.c_int),
    ]
    lib.fisco_secp256k1_sign.restype = ctypes.c_int
    lib.fisco_sm2_verify.argtypes = [u8p, u8p, u8p, u8p]
    lib.fisco_sm2_verify.restype = ctypes.c_int
    lib.fisco_sm2_sign.argtypes = [u8p, u8p, u8p, u8p]
    lib.fisco_sm2_sign.restype = ctypes.c_int
    lib.fisco_ec_pubkey.argtypes = [ctypes.c_int, u8p, u8p]
    lib.fisco_ec_pubkey.restype = ctypes.c_int
    lib.fisco_secp256k1_verify_batch.argtypes = [
        ctypes.c_size_t, u8p, u8p, u8p, u8p, u8p,
    ]
    lib.fisco_secp256k1_verify_batch.restype = None
    lib.fisco_secp256k1_recover_batch.argtypes = [
        ctypes.c_size_t, u8p, u8p, u8p, u8p, u8p, u8p,
    ]
    lib.fisco_secp256k1_recover_batch.restype = None
    lib.fisco_sm2_verify_batch.argtypes = [
        ctypes.c_size_t, u8p, u8p, u8p, u8p, u8p,
    ]
    lib.fisco_sm2_verify_batch.restype = None
    lib.fisco_ed25519_verify.argtypes = [u8p, u8p, ctypes.c_size_t, u8p]
    lib.fisco_ed25519_verify.restype = ctypes.c_int
    lib.fisco_ed25519_pubkey.argtypes = [u8p, u8p]
    lib.fisco_ed25519_pubkey.restype = ctypes.c_int
    lib.fisco_ed25519_sign.argtypes = [u8p, u8p, ctypes.c_size_t, u8p]
    lib.fisco_ed25519_sign.restype = ctypes.c_int
    # the inputs are read-only byte strings: c_char_p hands the engine the
    # bytes object's own buffer, where u8p would need a copy an argument
    cbytes = ctypes.c_char_p
    lib.fisco_evm_run.argtypes = [
        cbytes, ctypes.c_size_t,  # code
        cbytes, ctypes.c_size_t,  # calldata
        cbytes, cbytes, cbytes,   # self, caller, origin
        cbytes,                   # value (32B be)
        ctypes.c_int64,        # gas
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,  # number/ts/limit
        ctypes.c_int,          # static flag
        ctypes.c_void_p,       # ctx (unused; callbacks close over state)
        EVM_SLOAD_FN, EVM_SSTORE_FN, EVM_LOG_FN, EVM_RESULT_FN,
    ]
    lib.fisco_evm_run.restype = ctypes.c_int


def _hash_via(name: str, data: bytes) -> bytes | None:
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 32)()
    buf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(data or b"\x00")
    getattr(lib, name)(buf, len(data), out)
    return bytes(out)


def keccak256(data: bytes) -> bytes | None:
    return _hash_via("fisco_keccak256", data)


def sha256(data: bytes) -> bytes | None:
    return _hash_via("fisco_sha256", data)


def sm3(data: bytes) -> bytes | None:
    return _hash_via("fisco_sm3", data)


def _hash_batch_via(name: str, msgs: list[bytes]) -> list[bytes] | None:
    """Digests of ``msgs`` by one call of the library's batch entry."""
    lib = load()
    if lib is None:
        return None
    n = len(msgs)
    if n == 0:
        return []
    offsets = (ctypes.c_uint64 * (n + 1))(0, *itertools.accumulate(map(len, msgs)))
    out = ctypes.create_string_buffer(32 * n)
    getattr(lib, name)(n, b"".join(msgs), offsets, out)
    raw = out.raw
    return [raw[i : i + 32] for i in range(0, 32 * n, 32)]


def keccak256_batch(msgs: list[bytes]) -> list[bytes] | None:
    return _hash_batch_via("fisco_keccak256_batch", msgs)


def sm3_batch(msgs: list[bytes]) -> list[bytes] | None:
    return _hash_batch_via("fisco_sm3_batch", msgs)


def sm4_cbc(key: bytes, iv: bytes, data: bytes, decrypt: bool) -> bytes | None:
    """CBC over whole blocks (no padding — callers do PKCS7)."""
    lib = load()
    if lib is None or len(data) % 16:
        return None
    n = len(data) // 16
    out = (ctypes.c_uint8 * len(data))()
    kbuf = (ctypes.c_uint8 * 16).from_buffer_copy(key)
    ivbuf = (ctypes.c_uint8 * 16).from_buffer_copy(iv)
    ibuf = (ctypes.c_uint8 * max(1, len(data))).from_buffer_copy(data or b"\x00")
    lib.fisco_sm4_cbc(kbuf, ivbuf, ibuf, n, out, 1 if decrypt else 0)
    return bytes(out)


# ---------------------------------------------------------------------------
# Elliptic-curve single-item paths (the wedpr_secp256k1_* / SM2 EVP analog).
# All wrappers return None when the native core is unavailable so callers can
# fall back to crypto/ref; verified results are plain bool/bytes.
# ---------------------------------------------------------------------------


def _b32(v: int | bytes) -> bytes:
    return v if isinstance(v, bytes) else v.to_bytes(32, "big")


def _buf(data: bytes):
    return (ctypes.c_uint8 * len(data)).from_buffer_copy(data)


def secp256k1_verify(z: bytes, r: int, s: int, pub: bytes) -> bool | None:
    lib = load()
    if lib is None:
        return None
    if not (0 <= r < 1 << 256 and 0 <= s < 1 << 256) or len(pub) != 64:
        return False
    return bool(
        lib.fisco_secp256k1_verify(_buf(z), _buf(_b32(r)), _buf(_b32(s)), _buf(pub))
    )


def secp256k1_recover(z: bytes, r: int, s: int, v: int) -> bytes | None:
    """Recovered 64-byte pubkey, b"" when the signature is unrecoverable,
    None when the native core is unavailable."""
    lib = load()
    if lib is None:
        return None
    if not (0 <= r < 1 << 256 and 0 <= s < 1 << 256):
        return b""
    out = (ctypes.c_uint8 * 64)()
    ok = lib.fisco_secp256k1_recover(
        _buf(z), _buf(_b32(r)), _buf(_b32(s)), int(v), out
    )
    return bytes(out) if ok else b""


def secp256k1_sign(z: bytes, d: int) -> tuple[int, int, int] | None:
    lib = load()
    if lib is None:
        return None
    r_out = (ctypes.c_uint8 * 32)()
    s_out = (ctypes.c_uint8 * 32)()
    v_out = ctypes.c_int(0)
    ok = lib.fisco_secp256k1_sign(
        _buf(z), _buf(_b32(d)), r_out, s_out, ctypes.byref(v_out)
    )
    if not ok:
        return None
    return (
        int.from_bytes(bytes(r_out), "big"),
        int.from_bytes(bytes(s_out), "big"),
        v_out.value,
    )


def sm2_verify(e: bytes, r: int, s: int, pub: bytes) -> bool | None:
    """e = SM3(ZA ‖ M) — the caller computes the SM2 digest prefix."""
    lib = load()
    if lib is None:
        return None
    if not (0 <= r < 1 << 256 and 0 <= s < 1 << 256) or len(pub) != 64:
        return False
    return bool(lib.fisco_sm2_verify(_buf(e), _buf(_b32(r)), _buf(_b32(s)), _buf(pub)))


def sm2_sign(e: bytes, d: int) -> tuple[int, int] | None:
    lib = load()
    if lib is None:
        return None
    r_out = (ctypes.c_uint8 * 32)()
    s_out = (ctypes.c_uint8 * 32)()
    ok = lib.fisco_sm2_sign(_buf(e), _buf(_b32(d)), r_out, s_out)
    if not ok:
        return None
    return (int.from_bytes(bytes(r_out), "big"), int.from_bytes(bytes(s_out), "big"))


def ec_pubkey(curve: str, d: int) -> bytes | None:
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 64)()
    ok = lib.fisco_ec_pubkey(1 if curve == "sm2" else 0, _buf(_b32(d)), out)
    return bytes(out) if ok else None


def secp256k1_verify_batch(zs: bytes, rs: bytes, ss: bytes, pubs: bytes, n: int):
    """n-item loop in one native call (the suite's CPU verify leg).
    Returns a list[bool] or None when unavailable."""
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * n)()
    lib.fisco_secp256k1_verify_batch(n, _buf(zs), _buf(rs), _buf(ss), _buf(pubs), out)
    return [bool(b) for b in out]


def secp256k1_recover_batch(zs: bytes, rs: bytes, ss: bytes, vs: bytes, n: int):
    lib = load()
    if lib is None:
        return None
    pubs_out = (ctypes.c_uint8 * (64 * n))()
    ok_out = (ctypes.c_uint8 * n)()
    lib.fisco_secp256k1_recover_batch(
        n, _buf(zs), _buf(rs), _buf(ss), _buf(vs), pubs_out, ok_out
    )
    return bytes(pubs_out), [bool(b) for b in ok_out]


def ed25519_verify(pub: bytes, msg: bytes, sig: bytes) -> bool | None:
    lib = load()
    if lib is None:
        return None
    if len(pub) != 32 or len(sig) != 64:
        return False
    return bool(
        lib.fisco_ed25519_verify(
            _buf(pub), _buf(msg or b"\x00"), len(msg), _buf(sig)
        )
    )


def ed25519_pubkey(seed: bytes) -> bytes | None:
    lib = load()
    if lib is None or len(seed) != 32:
        return None
    out = (ctypes.c_uint8 * 32)()
    if not lib.fisco_ed25519_pubkey(_buf(seed), out):
        return None  # native failure: caller falls back to crypto/ref
    return bytes(out)


def ed25519_sign(seed: bytes, msg: bytes) -> bytes | None:
    lib = load()
    if lib is None or len(seed) != 32:
        return None
    out = (ctypes.c_uint8 * 64)()
    if not lib.fisco_ed25519_sign(_buf(seed), _buf(msg or b"\x00"), len(msg), out):
        return None  # native failure: caller falls back to crypto/ref
    return bytes(out)


def sm2_verify_batch(es: bytes, rs: bytes, ss: bytes, pubs: bytes, n: int):
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * n)()
    lib.fisco_sm2_verify_batch(n, _buf(es), _buf(rs), _buf(ss), _buf(pubs), out)
    return [bool(b) for b in out]
