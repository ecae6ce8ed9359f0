"""CryptoSuite — the crypto plugin seam, with first-class batch APIs.

Mirrors the capability surface of the reference's plugin layer
(bcos-crypto/interfaces/crypto/CryptoSuite.h:33-69, Signature.h:31-58,
Hash.h:37-60; suite selection in libinitializer/ProtocolInitializer.cpp:51-99:
``sm_crypto ? (SM3+SM2+SM4) : (Keccak256+Secp256k1+AES)``) — but where the
reference's `SignatureCrypto` is single-item only (the TPU batch API is the
whole point of this build, per BASELINE.json), every hash and signature
implementation here carries `hash_batch` / `batch_verify` / `batch_recover`
that run one fused device program over the whole batch.

Single-item calls use the pure-CPU reference implementations (crypto/ref) —
device round-trips don't pay off below ~hundreds of items; batch calls go to
the ops kernels. Both produce bit-identical results (golden-vector tested) —
any divergence would fork a chain.
"""

from __future__ import annotations

import functools
import os
import secrets
import threading
from dataclasses import dataclass

import numpy as np

from ..ops import keccak as keccak_ops
from ..ops import merkle as merkle_ops
from ..ops import secp256k1 as secp_ops
from ..ops import sha256 as sha256_ops
from ..ops import sm2 as sm2_ops
from ..ops import sm3 as sm3_ops
from ..utils.bytesutil import right160
from .ref import ecdsa as ref_ecdsa
from .ref import ed25519 as ref_ed25519
from .ref.keccak import keccak256 as ref_keccak256
from .ref.sha2 import sha256 as ref_sha256
from .ref.sm3 import sm3 as ref_sm3

# ---------------------------------------------------------------------------
# Hash implementations
# ---------------------------------------------------------------------------


def _hash_plane_exec(name: str, batch_async_direct):
    """Plane executor for one hash op: merge every queued request's messages
    into ONE bucket-padded device program, dispatch it WITHOUT syncing, and
    hand each request a slice resolver — queued hash programs from several
    callers overlap on device before anyone pays the first round trip."""

    def run(reqs):
        msgs: list[bytes] = []
        spans = []
        for r in reqs:
            spans.append((len(msgs), len(msgs) + r.n))
            msgs.extend(r.payload)
        from ..observability.device import device_span
        from ..ops.hash_common import bucket_batch

        # span covers the dispatch only (the sync happens in the caller's
        # resolver); the compile counter keys on the batch bucket as usual
        with device_span(name, len(msgs), shape_key=bucket_batch(max(len(msgs), 1))):
            resolve = batch_async_direct(msgs)
        memo: list = []
        lock = threading.Lock()

        def realize():
            with lock:
                if not memo:
                    memo.append(resolve())
                return memo[0]

        return [lambda lo=lo, hi=hi: realize()[lo:hi] for lo, hi in spans]

    return run


class HashImpl:
    """Hash interface (reference: bcos-crypto Hash.h:37-60 + AnyHasher).

    Batch calls route through the shared :class:`~..device.plane.DevicePlane`
    (coalesced, bucket-padded, priority-laned); ``FISCO_DEVICE_PLANE=0``
    restores the direct per-caller dispatch. Subclasses implement the
    ``_batch_direct`` / ``_batch_async_direct`` pair; the plane executor and
    the passthrough path both go through those, so the two modes cannot
    diverge.
    """

    name: str = ""

    def hash(self, data: bytes) -> bytes:
        raise NotImplementedError

    def _batch_direct(self, msgs) -> np.ndarray:
        """Direct (non-plane) batch dispatch: one device program."""
        raise NotImplementedError

    def _batch_async_direct(self, msgs):
        """Direct deferred-sync dispatch: () -> [B, 32]. Default dispatches
        eagerly; device-backed impls override with their ops *_batch_async
        so the plane executor can defer the sync."""
        out = self._batch_direct(msgs)
        return lambda: out

    def hash_batch(self, msgs) -> np.ndarray:
        """list[bytes] -> [B, 32] uint8 digests, one device program."""
        msgs = list(msgs)
        from ..device.plane import plane_route

        if plane_route() and msgs:
            return self.hash_batch_async(msgs)()
        return self._batch_direct(msgs)

    def hash_batch_async(self, msgs):
        """Dispatch the device batch, defer the sync: () -> [B, 32] uint8.

        Routed through the device plane so concurrent callers' hash
        programs coalesce AND overlap before the first sync (pre-plane,
        this default ran eagerly — each caller synced before the next
        could even dispatch)."""
        msgs = list(msgs)
        from ..device.plane import get_plane, plane_route, plane_wait_deferred

        if plane_route() and msgs:
            fut = get_plane().submit(
                f"hash.{self.name or type(self).__name__}",
                msgs,
                len(msgs),
                _hash_plane_exec(
                    self.name or type(self).__name__, self._batch_async_direct
                ),
            )
            return lambda: plane_wait_deferred(fut)
        return self._batch_async_direct(msgs)


class Keccak256(HashImpl):
    """Single-item host path: native C core when available (native_bind —
    the wedpr/EVP analog), pure-Python reference otherwise; batch path: TPU."""

    name = "keccak256"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.keccak256(data) or ref_keccak256(data)

    def _batch_direct(self, msgs) -> np.ndarray:
        return keccak_ops.keccak256_batch(msgs)

    def _batch_async_direct(self, msgs):
        return keccak_ops.keccak256_batch_async(msgs)


class SM3(HashImpl):
    name = "sm3"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.sm3(data) or ref_sm3(data)

    def _batch_direct(self, msgs) -> np.ndarray:
        return sm3_ops.sm3_batch(msgs)

    def _batch_async_direct(self, msgs):
        return sm3_ops.sm3_batch_async(msgs)


class Sha256(HashImpl):
    name = "sha256"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.sha256(data) or ref_sha256(data)

    def _batch_direct(self, msgs) -> np.ndarray:
        return sha256_ops.sha256_batch(msgs)

    def _batch_async_direct(self, msgs):
        return sha256_ops.sha256_batch_async(msgs)


class Poseidon(HashImpl):
    """SNARK-friendly hash lane (ISSUE 18): the succinct state plane's
    selectable commitment hasher. Single-item path is the pure-Python
    reference (no native core exists); batch path is the jitted sponge.
    Imports are lazy — deriving the Grain/Cauchy constant tables costs
    ~0.2 s and only nodes running `FISCO_STATE_HASH=poseidon` pay it."""

    name = "poseidon"

    def hash(self, data: bytes) -> bytes:
        from .ref.poseidon import poseidon_hash

        return poseidon_hash(data)

    def _batch_direct(self, msgs) -> np.ndarray:
        from ..ops import poseidon as poseidon_ops

        return poseidon_ops.poseidon_batch(msgs)

    def _batch_async_direct(self, msgs):
        from ..ops import poseidon as poseidon_ops

        return poseidon_ops.poseidon_batch_async(msgs)


_HASH_IMPLS: dict[str, type[HashImpl]] = {
    "keccak256": Keccak256,
    "sm3": SM3,
    "sha256": Sha256,
    "poseidon": Poseidon,
}


def hash_impl_by_name(name: str) -> HashImpl:
    """Hash impl registry lookup (`FISCO_STATE_HASH` selection seam). An
    unknown name raises — one node silently falling back to a different
    hasher than its peers would fork the state commitment."""
    try:
        return _HASH_IMPLS[name]()
    except KeyError:
        raise KeyError(f"unknown hash impl: {name!r}") from None


# ---------------------------------------------------------------------------
# Key pairs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KeyPair:
    """Secret scalar + uncompressed public key (reference: KeyPairInterface)."""

    secret: int
    pub: bytes  # 64 bytes, x‖y big-endian

    @property
    def pub_x(self) -> int:
        return int.from_bytes(self.pub[:32], "big")

    @property
    def pub_y(self) -> int:
        return int.from_bytes(self.pub[32:], "big")


def _make_keypair(curve: ref_ecdsa.Curve, secret: int | None) -> KeyPair:
    if secret is None:
        secret = secrets.randbelow(curve.n - 1) + 1
    x, y = ref_ecdsa.privkey_to_pubkey(curve, secret)
    return KeyPair(secret, x.to_bytes(32, "big") + y.to_bytes(32, "big"))


# ---------------------------------------------------------------------------
# Signature implementations
# ---------------------------------------------------------------------------

# Batches below this ride the native host loop instead of the device: a
# device program pays a fixed dispatch + transfer + sync cost regardless of
# batch size, while the native single-item path is ~0.3ms/sig, so there is
# a break-even batch.  PBFT QC signature lists (3-4 sigs per block,
# BlockValidator.cpp:141-177) and small-block admission are the
# beneficiaries.  The value is inherited, NOT measured on a local chip —
# deriving it from the device observatory is ROADMAP Queue 3.  Results are
# bit-identical across both legs (tests/test_native_ec.py pins it).
_SMALL_BATCH = 256


def device_min_batch() -> int:
    """Host-vs-device cutover: batches below this ride the native host loop.

    ``FISCO_DEVICE_MIN_BATCH`` overrides the hardcoded default — the right
    cutover depends on the device's fixed per-dispatch cost, which is not
    measured on a local chip yet. Read per call (an env read, ~100ns
    against a batch dispatch) so operators and tests can retune without a
    restart."""
    raw = os.environ.get("FISCO_DEVICE_MIN_BATCH")
    if raw:
        try:
            return max(0, int(raw))
        except ValueError:
            pass
    return _SMALL_BATCH


def _note_dispatch_path(op: str, path: str) -> None:
    """Labeled counter of which leg a batch actually took (native host loop
    vs device program) — the observable form of the `use_native_batch`
    policy, so a mistuned FISCO_DEVICE_MIN_BATCH shows up in /metrics
    instead of as a silent latency cliff."""
    from ..utils.metrics import REGISTRY

    REGISTRY.counter_add(
        f'fisco_device_dispatch_path_total{{op="{op}",path="{path}"}}',
        1.0,
        help="batch dispatches split by chosen leg (native host vs device)",
    )


def device_backend_is_cpu() -> bool:
    """True when the jax device plane is CPU XLA (no accelerator): there the
    native C loop beats the XLA program at EVERY batch size (~0.3ms/sig vs
    4-16ms/sig of emulated 256-bit limb arithmetic), so batch dispatchers
    should prefer the host path regardless of _SMALL_BATCH. The identity is
    memoised in utils.jaxenv; a backend that fails to initialise raises —
    "no chip" must never read as "CPU"."""
    from ..utils.jaxenv import device_identity

    return device_identity()["platform"] == "cpu"


def use_native_batch(n: int) -> bool:
    """Whether an n-item signature batch should ride the native host loop
    instead of a device program (threshold: :func:`device_min_batch`)."""
    return 0 < n and (n < device_min_batch() or device_backend_is_cpu())


# -- device-path circuit breaker (resilience/) -------------------------------

_DEVICE_BREAKER = None
_DEVICE_BREAKER_LOCK = threading.Lock()


def _device_breaker():
    """Breaker over the compiled device batch plane. It can fail in the
    field — a lost accelerator, device OOM on an oversized trace, a
    driver hiccup — and consensus must keep verifying: each failure falls
    back to the host loop for THAT batch, and repeated failures trip the
    breaker so admission stops paying a doomed device dispatch before every
    fallback. /health reports `device-crypto` degraded while tripped; a
    half-open probe re-closes it when the device plane answers again."""
    global _DEVICE_BREAKER
    if _DEVICE_BREAKER is None:
        from ..resilience import CircuitBreaker

        # double-checked: two racing callers must end up sharing ONE breaker
        # — split breakers would each see half the failures and never trip
        with _DEVICE_BREAKER_LOCK:
            if _DEVICE_BREAKER is None:
                _DEVICE_BREAKER = CircuitBreaker(
                    "device-crypto", failure_threshold=2, reset_timeout=60.0,
                    critical=False,  # host loop keeps serving: slower, not down
                )
    return _DEVICE_BREAKER


def _device_or_host(op: str, device_fn, host_fn, *args):
    """Run the compiled device path for ``op`` under the breaker, degrading
    to the bit-identical host loop. The failure only counts against the
    breaker when the host retry of the SAME args succeeds — a data error
    (bad shape/dtype) re-raises from the host path without tripping
    anything, so one malformed batch cannot demote a healthy device plane.

    Nothing here is silent: the leg taken lands in
    ``fisco_device_dispatch_path_total{op,path}`` (``device``, or
    ``host_fallback`` while the breaker is open), and every device-program
    failure the host loop covered for is counted and kept with its error in
    the device observatory (``GET /device`` → ``failures``)."""
    breaker = _device_breaker()
    if not breaker.allow():
        _note_dispatch_path(op, "host_fallback")
        return host_fn(*args)
    _note_dispatch_path(op, "device")
    try:
        out = device_fn(*args)
    except Exception as e:
        try:
            out = host_fn(*args)
        except BaseException:
            # both paths failed: a data error, not a device verdict — free
            # the half-open probe slot or the breaker wedges
            breaker.release_probe()
            raise
        from ..observability.device import LEDGER

        LEDGER.note_failure(op, e)
        breaker.record_failure(f"{type(e).__name__}: {str(e)[:200]}")
        return out
    breaker.record_success()
    return out


# -- device-plane executors ---------------------------------------------------
#
# One executor per (op, merge-convention): each merges every queued request
# into one batch, runs the impl's merged-batch body (the SAME body the
# passthrough path uses — the two modes cannot diverge), and slices the
# result back per request. Executors run on the plane worker with routing
# disabled, so nested seam calls (ed25519 recover → verify) take the direct
# path instead of deadlocking the worker.


def _verify_plane_exec(impl):
    """(hashes [n,32], pubs [n,64], sigs [n,L]) ndarray triples -> ok[n]."""

    def run(reqs):
        hs = np.concatenate([r.payload[0] for r in reqs], axis=0)
        ps = np.concatenate([r.payload[1] for r in reqs], axis=0)
        sg = np.concatenate([r.payload[2] for r in reqs], axis=0)
        ok = np.asarray(impl._verify_merged(hs, ps, sg))
        out, lo = [], 0
        for r in reqs:
            out.append(ok[lo : lo + r.n])
            lo += r.n
        return out

    return run


def _verify_plane_exec_lists(impl):
    """Same as :func:`_verify_plane_exec` for list-of-bytes payloads
    (ed25519's variable-form signatures)."""

    def run(reqs):
        hs: list[bytes] = []
        ps: list[bytes] = []
        sg: list[bytes] = []
        for r in reqs:
            h, p, s = r.payload
            hs += h
            ps += p
            sg += s
        ok = np.asarray(impl._verify_merged(hs, ps, sg))
        out, lo = [], 0
        for r in reqs:
            out.append(ok[lo : lo + r.n])
            lo += r.n
        return out

    return run


def _recover_plane_exec(impl):
    """(hashes [n,32], sigs [n,L]) -> (pubs [n,64], ok[n]) per request."""

    def run(reqs):
        hs = np.concatenate([r.payload[0] for r in reqs], axis=0)
        sg = np.concatenate([r.payload[1] for r in reqs], axis=0)
        pubs, ok = impl._recover_merged(hs, sg)
        pubs, ok = np.asarray(pubs), np.asarray(ok)
        out, lo = [], 0
        for r in reqs:
            out.append((pubs[lo : lo + r.n], ok[lo : lo + r.n]))
            lo += r.n
        return out

    return run


class SignatureCrypto:
    """Signature interface (reference: Signature.h:31-58) + batch extension.

    sign/verify/recover operate on 32-byte message hashes. `recover` returns
    the 64-byte uncompressed public key or raises; batch variants return
    validity masks instead of raising (invalid lanes lower a bit).
    """

    name: str = ""
    sig_len: int = 0

    def generate_keypair(self, secret: int | None = None) -> KeyPair:
        raise NotImplementedError

    def sign(self, kp: KeyPair, msg_hash: bytes) -> bytes:
        raise NotImplementedError

    def verify(self, pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
        raise NotImplementedError

    def recover(self, msg_hash: bytes, sig: bytes) -> bytes:
        raise NotImplementedError

    def batch_verify(
        self, msg_hashes: np.ndarray, pubs: np.ndarray, sigs: np.ndarray
    ) -> np.ndarray:
        raise NotImplementedError

    def batch_recover(
        self, msg_hashes: np.ndarray, sigs: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError


class Ed25519Crypto(SignatureCrypto):
    """Ed25519 (reference: signature/ed25519/Ed25519Crypto.cpp via wedpr).

    Host-side suite: 96-byte signatures R‖S‖pubkey32 — like the reference's
    SM2 scheme, "recover" parses the appended key then verifies
    (SM2Crypto.cpp:81-91 pattern); ed25519 has no algebraic recovery. The
    secret scalar is the 32-byte seed (little-endian int). Batch calls loop
    on the host: the device batch plane covers the two tx-signing curves
    (secp256k1/SM2); this suite exists for signature-surface parity.
    """

    name = "ed25519"
    sig_len = 96

    def generate_keypair(self, secret: int | None = None) -> KeyPair:
        from .. import native_bind

        if secret is None:
            secret = int.from_bytes(secrets.token_bytes(32), "little")
        seed = (secret % (1 << 256)).to_bytes(32, "little")
        pub = native_bind.ed25519_pubkey(seed) or ref_ed25519.seed_to_pubkey(seed)
        return KeyPair(int.from_bytes(seed, "little"), pub)

    @staticmethod
    def _seed(kp: KeyPair) -> bytes:
        return (kp.secret % (1 << 256)).to_bytes(32, "little")

    def sign(self, kp: KeyPair, msg_hash: bytes) -> bytes:
        from .. import native_bind

        sig = native_bind.ed25519_sign(self._seed(kp), msg_hash)
        if sig is None:
            sig = ref_ed25519.sign(self._seed(kp), msg_hash)
        return sig + kp.pub

    def verify(self, pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
        from .. import native_bind

        ok = native_bind.ed25519_verify(pub[:32], msg_hash, sig[:64])
        if ok is not None:
            return ok
        return ref_ed25519.verify(pub[:32], msg_hash, sig[:64])

    def recover(self, msg_hash: bytes, sig: bytes) -> bytes:
        pub = sig[64:96]
        if not self.verify(pub, msg_hash, sig[:64] + pub):
            raise ValueError("ed25519 signature does not verify")
        return pub

    def batch_verify(self, msg_hashes, pubs, sigs) -> np.ndarray:
        """One fused device program for the whole batch: all curve math
        (decompression, dual ladder, cofactored identity check) on device;
        SHA-512 challenges on host (ops/ed25519.py module docstring).
        Small batches and CPU-only backends ride the native host loop like
        the other curves (use_native_batch) — a QC list of 4 signatures
        must never pay a device dispatch or emulated-XLA limb math.
        Routed through the device plane (merged with concurrent callers;
        the host-vs-device cutover applies to the MERGED size)."""
        hashes = [bytes(h) for h in msg_hashes]
        pub_list = [bytes(p) for p in pubs]
        sig_list = [bytes(s) for s in sigs]
        from ..device.plane import get_plane, plane_route, plane_wait

        if plane_route() and sig_list:
            return plane_wait(get_plane().submit(
                "verify.ed25519",
                (hashes, pub_list, sig_list),
                len(sig_list),
                _verify_plane_exec_lists(self),
            ))
        return self._verify_merged(hashes, pub_list, sig_list)

    def _verify_merged(self, hashes, pub_list, sig_list) -> np.ndarray:
        if use_native_batch(len(sig_list)):
            from .. import native_bind

            if native_bind.load() is not None:
                _note_dispatch_path("ed25519_verify", "native")
                return np.array(
                    [
                        native_bind.ed25519_verify(p[:32], h, s[:64])
                        for h, p, s in zip(hashes, pub_list, sig_list)
                    ],
                    dtype=bool,
                )
        from ..ops import ed25519 as ed_ops

        _note_dispatch_path("ed25519_verify", "device")
        return ed_ops.verify_batch(hashes, pub_list, sig_list)

    def batch_recover(self, msg_hashes, sigs):
        """Parse the appended key, then device-batch-verify (ed25519 has no
        algebraic recovery; the 96-byte R‖S‖pub format carries the key).

        Malformed (short) signatures lower their lane's ok bit — they must
        never crash, and never reach the device as zero-filled dummies (a
        zero pubkey decompresses to a torsion point that can verify)."""
        sigs = [bytes(s) for s in sigs]
        wellformed = np.array([len(s) >= 96 for s in sigs])
        safe = [
            s if good else b"\x00" * 32 + b"\x01" + b"\x00" * 63
            for s, good in zip(sigs, wellformed)
        ]
        pubs = [s[64:96] for s in safe]
        ok = self.batch_verify(msg_hashes, pubs, safe) & wellformed
        out = np.frombuffer(
            b"".join(
                p if good else b"\x00" * 32 for p, good in zip(pubs, ok)
            ),
            np.uint8,
        ).reshape(-1, 32)
        return out, np.asarray(ok)


class Secp256k1Crypto(SignatureCrypto):
    """65-byte r‖s‖v signatures, v ∈ {0..3} ∪ {27, 28}
    (reference: Secp256k1Crypto.cpp:32-136).

    Single-item paths go through the native C core when available (the
    wedpr-FFI analog — every PBFT packet and single-tx RPC admission pays
    this latency, Secp256k1Crypto.cpp:57/:85), falling back to the
    bit-identical pure-Python reference."""

    name = "secp256k1"
    sig_len = 65

    def generate_keypair(self, secret: int | None = None) -> KeyPair:
        if secret is None:
            return _make_keypair(ref_ecdsa.SECP256K1, None)
        from .. import native_bind

        pub = native_bind.ec_pubkey("secp256k1", secret)
        if pub is None:
            return _make_keypair(ref_ecdsa.SECP256K1, secret)
        return KeyPair(secret, pub)

    def sign(self, kp: KeyPair, msg_hash: bytes) -> bytes:
        from .. import native_bind

        out = native_bind.secp256k1_sign(msg_hash, kp.secret)
        if out is None:
            out = ref_ecdsa.ecdsa_sign(msg_hash, kp.secret)
        r, s, v = out
        return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])

    def verify(self, pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
        from .. import native_bind

        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:64], "big")
        ok = native_bind.secp256k1_verify(msg_hash, r, s, pub)
        if ok is not None:
            return ok
        p = (int.from_bytes(pub[:32], "big"), int.from_bytes(pub[32:], "big"))
        return ref_ecdsa.ecdsa_verify(msg_hash, r, s, p)

    def recover(self, msg_hash: bytes, sig: bytes) -> bytes:
        from .. import native_bind

        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:64], "big")
        native = native_bind.secp256k1_recover(msg_hash, r, s, sig[64])
        if native is not None:
            if not native:
                raise ValueError("secp256k1 recover failed")
            return native
        pub = ref_ecdsa.ecdsa_recover(msg_hash, r, s, sig[64])
        if pub is None:
            raise ValueError("secp256k1 recover failed")
        x, y = pub
        return x.to_bytes(32, "big") + y.to_bytes(32, "big")

    def batch_verify(self, msg_hashes, pubs, sigs) -> np.ndarray:
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        pubs = np.asarray(pubs, dtype=np.uint8)
        from ..device.plane import get_plane, plane_route, plane_wait

        if plane_route() and len(sigs):
            return plane_wait(get_plane().submit(
                "verify.secp256k1",
                (hashes, pubs, sigs),
                len(sigs),
                _verify_plane_exec(self),
            ))
        return self._verify_merged(hashes, pubs, sigs)

    def _verify_merged(self, hashes, pubs, sigs) -> np.ndarray:
        n = len(sigs)
        if use_native_batch(n):
            from .. import native_bind

            out = native_bind.secp256k1_verify_batch(
                np.ascontiguousarray(hashes).tobytes(),
                np.ascontiguousarray(sigs[:, :32]).tobytes(),
                np.ascontiguousarray(sigs[:, 32:64]).tobytes(),
                np.ascontiguousarray(pubs).tobytes(),
                n,
            )
            if out is not None:
                _note_dispatch_path("secp256k1_verify", "native")
                return np.asarray(out, dtype=bool)
        return _device_or_host(
            "secp256k1_verify", secp_ops.verify_batch, self._host_verify_loop,
            hashes, sigs[:, :32], sigs[:, 32:64], pubs,
        )

    def _host_verify_loop(self, hashes, rs, ss, pubs) -> np.ndarray:
        """Degraded-mode fallback: per-item verify on the host (native C or
        pure-Python ref) — slow but bit-identical in outcome."""
        return np.array(
            [
                self.verify(
                    bytes(pubs[i]),
                    bytes(hashes[i]),
                    bytes(rs[i]) + bytes(ss[i]) + b"\x00",
                )
                for i in range(len(hashes))
            ],
            dtype=bool,
        )

    def _host_recover_loop(self, hashes, sigs):
        n = len(sigs)
        pubs = np.zeros((n, 64), dtype=np.uint8)
        ok = np.zeros(n, dtype=bool)
        for i in range(n):
            try:
                pub = self.recover(bytes(hashes[i]), bytes(sigs[i]))
            except ValueError:
                continue
            pubs[i] = np.frombuffer(pub, dtype=np.uint8)
            ok[i] = True
        return pubs, ok

    def batch_recover(self, msg_hashes, sigs):
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        from ..device.plane import get_plane, plane_route, plane_wait

        if plane_route() and len(sigs):
            return plane_wait(get_plane().submit(
                "recover.secp256k1",
                (hashes, sigs),
                len(sigs),
                _recover_plane_exec(self),
            ))
        return self._recover_merged(hashes, sigs)

    def _recover_merged(self, hashes, sigs):
        n = len(sigs)
        if use_native_batch(n):
            from .. import native_bind

            out = native_bind.secp256k1_recover_batch(
                np.ascontiguousarray(hashes).tobytes(),
                np.ascontiguousarray(sigs[:, :32]).tobytes(),
                np.ascontiguousarray(sigs[:, 32:64]).tobytes(),
                np.ascontiguousarray(sigs[:, 64]).tobytes(),
                n,
            )
            if out is not None:
                _note_dispatch_path("secp256k1_recover", "native")
                pubs_raw, oks = out
                pubs = np.frombuffer(pubs_raw, np.uint8).reshape(n, 64).copy()
                ok = np.asarray(oks, dtype=bool)
                pubs[~ok] = 0
                return pubs, ok
        return _device_or_host(
            "secp256k1_recover", secp_ops.recover_batch,
            self._host_recover_loop, hashes, sigs,
        )


class SM2Crypto(SignatureCrypto):
    """128-byte r‖s‖pubkey signatures; "recover" parses the carried pubkey and
    verifies (reference: SM2Crypto.cpp:29-91 — sign appends the pubkey,
    recover = parse-pub-then-verify)."""

    name = "sm2"
    sig_len = 128

    @staticmethod
    def _e_bytes(pub: bytes, msg_hash: bytes) -> bytes:
        """e = SM3(ZA ‖ M) with the default user id, riding the native
        hasher when available (layout lives in one place: ecdsa.sm2_za_bytes)."""
        from .. import native_bind

        return ref_ecdsa.sm2_e_bytes(
            pub, msg_hash, sm3_fn=lambda b: native_bind.sm3(b) or ref_sm3(b)
        )

    def generate_keypair(self, secret: int | None = None) -> KeyPair:
        if secret is None:
            return _make_keypair(ref_ecdsa.SM2_CURVE, None)
        from .. import native_bind

        pub = native_bind.ec_pubkey("sm2", secret)
        if pub is None:
            return _make_keypair(ref_ecdsa.SM2_CURVE, secret)
        return KeyPair(secret, pub)

    def sign(self, kp: KeyPair, msg_hash: bytes) -> bytes:
        from .. import native_bind

        out = None
        if native_bind.load() is not None:
            out = native_bind.sm2_sign(self._e_bytes(kp.pub, msg_hash), kp.secret)
        if out is None:
            out = ref_ecdsa.sm2_sign(msg_hash, kp.secret)
        r, s = out
        return r.to_bytes(32, "big") + s.to_bytes(32, "big") + kp.pub

    def verify(self, pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
        from .. import native_bind

        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:64], "big")
        if native_bind.load() is not None:
            ok = native_bind.sm2_verify(self._e_bytes(pub, msg_hash), r, s, pub)
            if ok is not None:
                return ok
        p = (int.from_bytes(pub[:32], "big"), int.from_bytes(pub[32:], "big"))
        return ref_ecdsa.sm2_verify(msg_hash, r, s, p)

    def recover(self, msg_hash: bytes, sig: bytes) -> bytes:
        pub = sig[64:128]
        if not self.verify(pub, msg_hash, sig[:64] + pub):
            raise ValueError("sm2 recover: carried pubkey fails verification")
        return pub

    def _native_batch_verify(self, hashes, pubs, rs, ss):
        """Native host loop for sub-threshold batches (e computed with the
        native SM3); None when the native core is unavailable."""
        from .. import native_bind

        if native_bind.load() is None:
            return None
        n = len(hashes)
        es = b"".join(
            self._e_bytes(bytes(pubs[i]), bytes(hashes[i])) for i in range(n)
        )
        out = native_bind.sm2_verify_batch(
            es,
            np.ascontiguousarray(rs).tobytes(),
            np.ascontiguousarray(ss).tobytes(),
            np.ascontiguousarray(pubs).tobytes(),
            n,
        )
        return None if out is None else np.asarray(out, dtype=bool)

    def batch_verify(self, msg_hashes, pubs, sigs) -> np.ndarray:
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        pubs = np.asarray(pubs, dtype=np.uint8)
        from ..device.plane import get_plane, plane_route, plane_wait

        if plane_route() and len(sigs):
            return plane_wait(get_plane().submit(
                "verify.sm2",
                (hashes, pubs, sigs),
                len(sigs),
                _verify_plane_exec(self),
            ))
        return self._verify_merged(hashes, pubs, sigs)

    def _verify_merged(self, hashes, pubs, sigs) -> np.ndarray:
        if use_native_batch(len(sigs)):
            out = self._native_batch_verify(
                hashes, pubs, sigs[:, :32], sigs[:, 32:64]
            )
            if out is not None:
                _note_dispatch_path("sm2_verify", "native")
                return out
        return _device_or_host(
            "sm2_verify", sm2_ops.verify_batch, self._host_verify_loop,
            hashes, sigs[:, :32], sigs[:, 32:64], pubs,
        )

    def _host_verify_loop(self, hashes, rs, ss, pubs) -> np.ndarray:
        """Degraded-mode fallback: per-item SM2 verify on the host."""
        return np.array(
            [
                self.verify(
                    bytes(pubs[i]),
                    bytes(hashes[i]),
                    bytes(rs[i]) + bytes(ss[i]) + bytes(pubs[i]),
                )
                for i in range(len(hashes))
            ],
            dtype=bool,
        )

    def batch_recover(self, msg_hashes, sigs):
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        from ..device.plane import get_plane, plane_route, plane_wait

        if plane_route() and len(sigs):
            return plane_wait(get_plane().submit(
                "recover.sm2",
                (hashes, sigs),
                len(sigs),
                _recover_plane_exec(self),
            ))
        return self._recover_merged(hashes, sigs)

    def _recover_merged(self, hashes, sigs):
        if use_native_batch(len(sigs)):
            pubs = sigs[:, 64:128]
            ok = self._native_batch_verify(
                hashes, pubs, sigs[:, :32], sigs[:, 32:64]
            )
            if ok is not None:
                _note_dispatch_path("sm2_recover", "native")
                out = np.where(ok[:, None], pubs, np.zeros_like(pubs))
                return out, ok

        def _host_recover(hashes_, sigs_):
            pubs_ = sigs_[:, 64:128]
            ok_ = self._host_verify_loop(
                hashes_, sigs_[:, :32], sigs_[:, 32:64], pubs_
            )
            return np.where(ok_[:, None], pubs_, np.zeros_like(pubs_)), ok_

        return _device_or_host(
            "sm2_recover", sm2_ops.recover_batch, _host_recover, hashes, sigs
        )


# ---------------------------------------------------------------------------
# The suite
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CryptoSuite:
    """Hash + signature bundle (reference: CryptoSuite.h:33-69)."""

    hash_impl: HashImpl
    signature_impl: SignatureCrypto

    def hash(self, data: bytes) -> bytes:
        return self.hash_impl.hash(data)

    def hash_batch(self, msgs) -> np.ndarray:
        return self.hash_impl.hash_batch(msgs)

    def hash_batch_async(self, msgs):
        return self.hash_impl.hash_batch_async(msgs)

    def calculate_address(self, pub: bytes) -> bytes:
        """right160(hash(pubkey)) — CryptoSuite.h:56-59."""
        return right160(self.hash_impl.hash(pub))

    def calculate_address_batch(self, pubs: np.ndarray) -> np.ndarray:
        digests = self.hash_impl.hash_batch([bytes(p) for p in np.asarray(pubs)])
        return digests[:, 12:]

    def fused_admission(self):
        """This suite's fused tx admission — ``(payloads, sigs) -> (senders,
        ok, pubkeys, tx hashes)``, one device program with one result
        transfer (crypto.admission.admit_batch) — or None for a suite that
        has none (ed25519), whose batches take hash_batch → batch_recover →
        calculate_address_batch instead."""
        from . import admission

        body = admission._body_of(self)
        if body is None:
            return None
        if body is admission._SECP:
            # the default suite is admit_batch's own default: the call stays
            # the two-argument one its other callers make
            return admission.admit_batch
        return functools.partial(admission.admit_batch, suite=self)

    def merkle_root_async(self, leaves: np.ndarray):
        """Dispatch-now, sync-later (() -> bytes) wide device merkle over
        ``[N, 32]`` uint8 leaves, hasher chosen by this suite.

        This is the DevicePlane seam protocol/ledger callers use instead of
        importing ``ops.merkle`` directly — the device-dispatch analyzer
        rejects kernel imports outside the crypto/device/ops/parallel seams.
        """
        return merkle_ops.merkle_root_async(leaves, hasher=self.hash_impl.name)

    def merkle_tree(self, leaves: np.ndarray) -> "merkle_ops.MerkleTree":
        """Build a full proof-capable tree (every level retained) over
        ``[N, 32]`` uint8 leaves — the ProofPlane's frozen-tree builder.

        Routed through the shared DevicePlane as the ``merkle_tree`` op on
        the caller's lane (the ProofPlane submits under
        ``device_lane("proof")``, the lane below ``sync``), so cache-miss
        tree builds from a proof storm queue BEHIND consensus, admission
        and gossip batches instead of competing with them. Leaf counts are
        bucket-padded inside :class:`~fisco_bcos_tpu.ops.merkle.MerkleTree`
        (``bucket_leaves``), so the compiled-program set stays within the
        ladder. Bit-identical to a direct ``MerkleTree(...)`` build by
        construction — both paths run the same constructor.
        """
        from ..device.plane import get_plane, plane_route, plane_wait
        from ..observability.device import device_span

        leaves = np.asarray(leaves, dtype=np.uint8)
        if plane_route() and len(leaves) > 1:
            # op name carries the hasher (like `hash.<name>` / `sm2_verify`):
            # the plane binds ONE executor per op name process-wide, and a
            # multi-suite host (keccak + SM groups) must not have the first
            # suite's hasher capture every group's tree builds
            return plane_wait(get_plane().submit(
                f"merkle_tree.{self.hash_impl.name}",
                leaves,
                len(leaves),
                _merkle_tree_plane_exec(self.hash_impl.name),
            ))
        # direct path gets the same span the plane executor wraps builds
        # in — tree hashing stays attributed with the plane off too
        with device_span(
            "merkle_tree",
            len(leaves),
            shape_key=(
                self.hash_impl.name,
                merkle_ops.bucket_leaves(max(len(leaves), 1)),
            ),
        ):
            return merkle_ops.MerkleTree(leaves, hasher=self.hash_impl.name)


def _merkle_tree_plane_exec(hasher: str):
    """Plane executor for proof-tree builds: each request is its own tree
    (different heights — there is nothing sound to merge across roots), but
    dispatching them through one plane slot serializes read-path hashing
    behind the priority lanes and shares the dispatch accounting."""

    def run(reqs):
        from ..observability.device import device_span

        out = []
        for r in reqs:
            leaves = r.payload
            with device_span(
                "merkle_tree",
                len(leaves),
                shape_key=(hasher, merkle_ops.bucket_leaves(max(len(leaves), 1))),
            ):
                out.append(merkle_ops.MerkleTree(leaves, hasher=hasher))
        return out

    return run


def ecdsa_suite() -> CryptoSuite:
    """Keccak256 + secp256k1 (the reference's default, non-SM suite)."""
    return CryptoSuite(Keccak256(), Secp256k1Crypto())


def sm_suite() -> CryptoSuite:
    """SM3 + SM2 (the reference's sm_crypto=true national suite)."""
    return CryptoSuite(SM3(), SM2Crypto())
