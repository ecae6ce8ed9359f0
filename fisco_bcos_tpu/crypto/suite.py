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
import secrets
import threading
from dataclasses import dataclass

import numpy as np

from ..device.dispatch import BatchOp, dispatch, enqueue
from ..device.plane import plane_wait, plane_wait_deferred
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

    Batch calls enter the shared :class:`~..device.plane.DevicePlane`
    through the dispatch seam (coalesced, bucket-padded, priority-laned).
    Subclasses implement ``_batch_async_direct``: the plane executor
    dispatches through it, and a call that must not queue
    (device/dispatch.enqueue) runs it inline.
    """

    name: str = ""

    def hash(self, data: bytes) -> bytes:
        raise NotImplementedError

    def hash_each(self, msgs: list[bytes]) -> list[bytes]:
        """Each message's digest, on the host: many short messages whose
        digests the caller needs at once (a block's receipts). One native
        call over all of them where the native core has a batch entry for
        this hash; else ``hash`` a message."""
        return [self.hash(m) for m in msgs]

    def _batch_async_direct(self, msgs):
        """Direct deferred-sync dispatch, one device program: () -> [B, 32]
        (an ops ``*_batch_async``, so the plane executor can defer the sync)."""
        raise NotImplementedError

    def hash_batch(self, msgs) -> np.ndarray:
        """list[bytes] -> [B, 32] uint8 digests, one device program."""
        return self.hash_batch_async(msgs)()

    def hash_batch_async(self, msgs):
        """Dispatch the device batch, defer the sync: () -> [B, 32] uint8.

        Queued into the device plane so concurrent callers' hash programs
        coalesce AND overlap before the first sync."""
        msgs = list(msgs)
        name = self.name or type(self).__name__
        fut = enqueue(
            f"hash.{name}", msgs, len(msgs),
            _hash_plane_exec(name, self._batch_async_direct),
        )
        if fut is None:
            return self._batch_async_direct(msgs)
        return lambda: plane_wait_deferred(fut)


class Keccak256(HashImpl):
    """Single-item host path: native C core when available (native_bind —
    the wedpr/EVP analog), pure-Python reference otherwise; batch path: TPU."""

    name = "keccak256"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.keccak256(data) or ref_keccak256(data)

    def hash_each(self, msgs):
        from .. import native_bind

        digests = native_bind.keccak256_batch(msgs)
        return digests if digests is not None else super().hash_each(msgs)

    def _batch_async_direct(self, msgs):
        return keccak_ops.keccak256_batch_async(msgs)


class SM3(HashImpl):
    name = "sm3"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.sm3(data) or ref_sm3(data)

    def hash_each(self, msgs):
        from .. import native_bind

        digests = native_bind.sm3_batch(msgs)
        return digests if digests is not None else super().hash_each(msgs)

    def _batch_async_direct(self, msgs):
        return sm3_ops.sm3_batch_async(msgs)


class Sha256(HashImpl):
    name = "sha256"

    def hash(self, data: bytes) -> bytes:
        from .. import native_bind

        return native_bind.sha256(data) or ref_sha256(data)

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

# Where a batch runs — native host loop, device program, or the host loop
# behind the breaker — is device/dispatch.py's decision. The classes below
# normalise their arguments and describe each batch operation as a BatchOp;
# every leg of one op takes the same fields and answers the same rows.


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

    def _host_verify_loop(self, hashes, pubs, sigs) -> np.ndarray:
        """Degraded-mode fallback: per-item verify on the host (native C or
        pure-Python ref) — slow but bit-identical in outcome."""
        return np.array(
            [
                self.verify(bytes(pubs[i]), bytes(hashes[i]), bytes(sigs[i]))
                for i in range(len(hashes))
            ],
            dtype=bool,
        )


class _DeviceCurveCrypto(SignatureCrypto):
    """The two tx-signing curves of the device batch plane: ``[n, L]`` uint8
    arrays in, one BatchOp per batch operation, the device legs the host
    wrappers of ``_ops``. A subclass gives ``_native_verify``,
    ``_native_recover`` (None without the library) and
    ``_host_recover_loop``."""

    _ops = None  # ops.secp256k1 / ops.sm2: verify_batch, recover_batch

    def __init__(self):
        self._verify_op = BatchOp(
            f"{self.name}_verify", f"verify.{self.name}", self._device_verify,
            self._native_verify, self._host_verify_loop,
        )
        self._recover_op = BatchOp(
            f"{self.name}_recover", f"recover.{self.name}", self._device_recover,
            self._native_recover, self._host_recover_loop,
        )

    def batch_verify(self, msg_hashes, pubs, sigs) -> np.ndarray:
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        pubs = np.asarray(pubs, dtype=np.uint8)
        return dispatch(self._verify_op, (hashes, pubs, sigs), len(sigs))

    def batch_recover(self, msg_hashes, sigs):
        sigs = np.asarray(sigs, dtype=np.uint8)
        hashes = np.asarray(msg_hashes, dtype=np.uint8)
        return dispatch(self._recover_op, (hashes, sigs), len(sigs))

    def _device_verify(self, hashes, pubs, sigs):
        return self._ops.verify_batch(hashes, sigs[:, :32], sigs[:, 32:64], pubs)

    def _device_recover(self, hashes, sigs):
        return self._ops.recover_batch(hashes, sigs)


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

    def __init__(self):
        self._verify_op = BatchOp(
            "ed25519_verify", "verify.ed25519", self._device_verify,
            self._native_verify, self._host_verify_loop,
        )

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
        the other curves — a QC list of 4 signatures must never pay a
        device dispatch or emulated-XLA limb math."""
        hashes = [bytes(h) for h in msg_hashes]
        pub_list = [bytes(p) for p in pubs]
        sig_list = [bytes(s) for s in sigs]
        return dispatch(
            self._verify_op, (hashes, pub_list, sig_list), len(sig_list)
        )

    @staticmethod
    def _native_verify(hashes, pub_list, sig_list):
        from .. import native_bind

        if native_bind.load() is None:
            return None
        return np.array(
            [
                native_bind.ed25519_verify(p[:32], h, s[:64])
                for h, p, s in zip(hashes, pub_list, sig_list)
            ],
            dtype=bool,
        )

    @staticmethod
    def _device_verify(hashes, pub_list, sig_list):
        from ..ops import ed25519 as ed_ops

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


class Secp256k1Crypto(_DeviceCurveCrypto):
    """65-byte r‖s‖v signatures, v ∈ {0..3} ∪ {27, 28}
    (reference: Secp256k1Crypto.cpp:32-136).

    Single-item paths go through the native C core when available (the
    wedpr-FFI analog — every PBFT packet and single-tx RPC admission pays
    this latency, Secp256k1Crypto.cpp:57/:85), falling back to the
    bit-identical pure-Python reference."""

    name = "secp256k1"
    sig_len = 65
    _ops = secp_ops

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

    @staticmethod
    def _native_verify(hashes, pubs, sigs):
        from .. import native_bind

        out = native_bind.secp256k1_verify_batch(
            np.ascontiguousarray(hashes).tobytes(),
            np.ascontiguousarray(sigs[:, :32]).tobytes(),
            np.ascontiguousarray(sigs[:, 32:64]).tobytes(),
            np.ascontiguousarray(pubs).tobytes(),
            len(sigs),
        )
        return None if out is None else np.asarray(out, dtype=bool)

    @staticmethod
    def _native_recover(hashes, sigs):
        from .. import native_bind

        n = len(sigs)
        out = native_bind.secp256k1_recover_batch(
            np.ascontiguousarray(hashes).tobytes(),
            np.ascontiguousarray(sigs[:, :32]).tobytes(),
            np.ascontiguousarray(sigs[:, 32:64]).tobytes(),
            np.ascontiguousarray(sigs[:, 64]).tobytes(),
            n,
        )
        if out is None:
            return None
        pubs_raw, oks = out
        pubs = np.frombuffer(pubs_raw, np.uint8).reshape(n, 64).copy()
        ok = np.asarray(oks, dtype=bool)
        pubs[~ok] = 0
        return pubs, ok

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


class SM2Crypto(_DeviceCurveCrypto):
    """128-byte r‖s‖pubkey signatures; "recover" parses the carried pubkey and
    verifies (reference: SM2Crypto.cpp:29-91 — sign appends the pubkey,
    recover = parse-pub-then-verify)."""

    name = "sm2"
    sig_len = 128
    _ops = sm2_ops

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

    def _native_verify(self, hashes, pubs, sigs):
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
            np.ascontiguousarray(sigs[:, :32]).tobytes(),
            np.ascontiguousarray(sigs[:, 32:64]).tobytes(),
            np.ascontiguousarray(pubs).tobytes(),
            n,
        )
        return None if out is None else np.asarray(out, dtype=bool)

    def _carried(self, verify, hashes, sigs):
        """recover = verify the key each signature carries, zero the rows
        that fail (None where ``verify`` cannot answer)."""
        pubs = sigs[:, 64:128]
        ok = verify(hashes, pubs, sigs)
        if ok is None:
            return None
        return np.where(ok[:, None], pubs, np.zeros_like(pubs)), ok

    def _native_recover(self, hashes, sigs):
        return self._carried(self._native_verify, hashes, sigs)

    def _host_recover_loop(self, hashes, sigs):
        return self._carried(self._host_verify_loop, hashes, sigs)


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

    def hash_each(self, msgs: list[bytes]) -> list[bytes]:
        return self.hash_impl.hash_each(msgs)

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
        leaves = np.asarray(leaves, dtype=np.uint8)
        hasher = self.hash_impl.name
        if len(leaves) > 1:
            # op name carries the hasher (like `hash.<name>` / `sm2_verify`):
            # the plane binds ONE executor per op name process-wide, and a
            # multi-suite host (keccak + SM groups) must not have the first
            # suite's hasher capture every group's tree builds
            fut = enqueue(
                f"merkle_tree.{hasher}", leaves, len(leaves),
                lambda reqs: [_build_tree(hasher, r.payload) for r in reqs],
            )
            if fut is not None:
                return plane_wait(fut)
        return _build_tree(hasher, leaves)


def _build_tree(hasher: str, leaves) -> "merkle_ops.MerkleTree":
    """One proof-tree build under its span. The plane executor runs this per
    request (different heights — there is nothing sound to merge across
    roots; one plane slot still serializes read-path hashing behind the
    priority lanes and shares the dispatch accounting), and so does a build
    that does not queue."""
    from ..observability.device import device_span

    with device_span(
        "merkle_tree",
        len(leaves),
        shape_key=(hasher, merkle_ops.bucket_leaves(max(len(leaves), 1))),
        hasher=hasher,
    ) as sp:
        tree = merkle_ops.MerkleTree(leaves, hasher=hasher)
        sp.path("fused" if tree.fused else "levels")
        return tree


def ecdsa_suite() -> CryptoSuite:
    """Keccak256 + secp256k1 (the reference's default, non-SM suite)."""
    return CryptoSuite(Keccak256(), Secp256k1Crypto())


def sm_suite() -> CryptoSuite:
    """SM3 + SM2 (the reference's sm_crypto=true national suite)."""
    return CryptoSuite(SM3(), SM2Crypto())
