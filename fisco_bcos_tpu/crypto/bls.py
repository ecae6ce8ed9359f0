"""BLSCrypto — the BLS12-381 aggregate-signature scheme in the CryptoSuite
plugin layer (the QC subsystem's heavy rung).

Single-item sign/verify ride the pure-Python reference
(:mod:`.ref.bls12_381`) with cached point deserialization — committee
pubkeys and quorum signatures deserialize once per process, not once per
check. Aggregate verification — THE hot call: one pairing check admits a
whole quorum — routes through the shared DevicePlane as the
``bls_aggregate_verify`` op on whatever lane the caller tagged (consensus
for QC admission), merging concurrent certificate checks from block-sync /
lightnode header storms into one jitted pairing program. CPU backends and
sub-threshold batches take the bit-identical host pairing: the dispatch
seam's policy (device/dispatch.py), as for the other curves.

Key model: BLS keypairs are DERIVED (secret scalar mod r) from the node's
main consensus secret, and the committee's BLS pubkeys are registered in
the consensus-node table (``ConsensusNode.qc_pub``) — registration is the
proof-of-possession boundary that makes same-message aggregation
rogue-key safe (consensus/qc.py docs).
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

from ..crypto.ref import bls12_381 as ref
from ..device.dispatch import BatchOp, dispatch, run_legs
from .suite import SignatureCrypto, KeyPair


@lru_cache(maxsize=4096)
def _g1_point(pub48: bytes):
    """Cached, validated pubkey deserialization (None = malformed/out of
    subgroup). The cache is what makes per-quorum aggregate verification
    pay only the pairing, not 2f+1 subgroup checks."""
    try:
        return ref.decompress_g1(pub48)
    except ValueError:
        return None


@lru_cache(maxsize=4096)
def _g2_point(sig96: bytes):
    try:
        return ref.decompress_g2(sig96)
    except ValueError:
        return None


@lru_cache(maxsize=1024)
def _apk_point(pubs: tuple[bytes, ...]):
    """Aggregate pubkey for a signer set (quorum bitmaps repeat across
    rounds, so the G1 additions amortize too)."""
    acc = None
    for p in pubs:
        pt = _g1_point(p)
        if pt is None:
            return None
        acc = ref.ec_add(acc, pt, ref.FP_OPS)
    return acc


def _triples(checks):
    """(aggregate pubkey, signature, H(m)) points per check, None where one
    is malformed. Deserialization and hash-to-G2 are host-side (cached)."""
    triples = []
    for pubs, msg, agg in checks:
        apk = _apk_point(pubs) if pubs else None
        sig = _g2_point(agg)
        hm = ref.hash_to_g2(msg) if apk is not None and sig is not None else None
        triples.append((apk, sig, hm))
    return triples


def _host_pairing_check(checks):
    from ..ops import bls12_381 as bls_ops

    return bls_ops.host_pairing_check_batch(_triples(checks))


def _device_pairing_check(checks):
    from ..observability.device import device_span
    from ..ops import bls12_381 as bls_ops
    from ..ops.hash_common import bucket_batch

    n = len(checks)
    with device_span("bls_aggregate_verify", n, shape_key=bucket_batch(max(n, 1))):
        return bls_ops.pairing_check_batch(_triples(checks))


def _host_multi_pairing(pairs):
    from ..ops import bls12_381 as bls_ops

    return bool(bls_ops.host_multi_pairing_check(pairs))


def _device_multi_pairing(pairs):
    from ..observability.device import device_span
    from ..ops import bls12_381 as bls_ops

    n = len(pairs)
    with device_span("bls_multi_pairing", n, shape_key=bls_ops.multi_pairing_pad(n)):
        return bool(bls_ops.multi_pairing_check(pairs))


# the pairing runs on device for large merged batches on accelerator
# backends, else on the bit-identical host reference
_AGGREGATE_VERIFY = BatchOp(
    "bls_aggregate_verify", "bls_aggregate_verify",
    _device_pairing_check, native=_host_pairing_check,
)
_MULTI_PAIRING = BatchOp(
    "bls_multi_pairing", None, _device_multi_pairing, native=_host_multi_pairing
)


class BLSCrypto(SignatureCrypto):
    """Min-pubkey-size BLS: 48-byte G1 pubkeys, 96-byte G2 signatures,
    same-message aggregation (the QC case)."""

    name = "bls12_381"
    sig_len = 96

    def generate_keypair(self, secret: int | None = None) -> KeyPair:
        import secrets as _secrets

        if secret is None:
            secret = int.from_bytes(_secrets.token_bytes(32), "big")
        sk, pub = ref.keygen(secret)
        return KeyPair(sk, pub)

    def sign(self, kp: KeyPair, msg_hash: bytes) -> bytes:
        return ref.sign(kp.secret, msg_hash)

    def verify(self, pub: bytes, msg_hash: bytes, sig: bytes) -> bool:
        pk = _g1_point(bytes(pub))
        s = _g2_point(bytes(sig))
        if pk is None or s is None:
            return False
        return ref.pairing_check(
            [(ref.ec_neg(ref.G1, ref.FP_OPS), s), (pk, ref.hash_to_g2(bytes(msg_hash)))]
        )

    def recover(self, msg_hash: bytes, sig: bytes) -> bytes:
        raise ValueError("BLS signatures carry no recoverable public key")

    def batch_verify(self, msg_hashes, pubs, sigs) -> np.ndarray:
        """Independent-message batch (per-signer isolation fallback): host
        loop over cached points — distinct messages have no shared pairing
        structure worth a merged program at QC sizes."""
        return np.array(
            [
                self.verify(bytes(p), bytes(h), bytes(s))
                for h, p, s in zip(msg_hashes, pubs, sigs)
            ],
            dtype=bool,
        )

    def batch_recover(self, msg_hashes, sigs):
        raise ValueError("BLS signatures carry no recoverable public key")

    # -- aggregation (the QC surface) ---------------------------------------

    def aggregate(self, sigs: list[bytes]) -> bytes:
        """Sum the G2 signatures into one 96-byte certificate signature."""
        acc = None
        for s in sigs:
            pt = _g2_point(bytes(s))
            if pt is None:
                raise ValueError("malformed signature in aggregate")
            acc = ref.ec_add(acc, pt, ref.FP2_OPS)
        return ref.compress_g2(acc)

    def aggregate_verify(
        self, pubs: list[bytes], msg_hash: bytes, agg_sig: bytes
    ) -> bool:
        """One pairing check for the whole signer set (same message)."""
        return bool(
            self.aggregate_verify_batch([(tuple(pubs), msg_hash, agg_sig)])[0]
        )

    def aggregate_verify_batch(self, checks) -> np.ndarray:
        """checks: [(pubs tuple, msg_hash, agg_sig)] -> bool[B], routed
        through the DevicePlane (op ``bls_aggregate_verify``) so
        concurrent QC admissions merge into one pairing program."""
        checks = [
            (tuple(bytes(p) for p in pubs), bytes(m), bytes(s))
            for pubs, m, s in checks
        ]
        return dispatch(_AGGREGATE_VERIFY, (checks,), len(checks))

    # -- succinct header sync (the multi-pairing surface) -------------------

    def multi_pairing_verify(self, checks) -> bool:
        """ONE accept/reject for a whole set of aggregate checks.

        ``checks`` is the same ``[(pubs tuple, msg_hash, agg_sig)]`` shape as
        :meth:`aggregate_verify_batch`, but instead of K independent pairing
        checks the set folds into a single K+1-pair product via a
        Fiat-Shamir random linear combination: scalars ``r_k`` are drawn
        from a hash transcript over every ``(msg, sig)`` AFTER all of them
        are fixed, and

            e(-G1, sum_k r_k*sig_k) * prod_k e(r_k*apk_k, Hm_k) == 1

        holds for random r_k iff every per-check equation holds (soundness
        error ~2^-128 — an adversary would have to predict the transcript).
        The succinct header-sync payoff: K header QCs cost ONE shared
        squaring chain in the Miller stage and ONE final exponentiation
        instead of K full pairing checks. Callers that need to know WHICH
        check failed fall back to :meth:`aggregate_verify_batch`.
        """
        import hashlib

        checks = [
            (tuple(bytes(p) for p in pubs), bytes(m), bytes(s))
            for pubs, m, s in checks
        ]
        if not checks:
            return True
        triples = []
        for pubs, msg, agg in checks:
            apk = _apk_point(pubs) if pubs else None
            sig = _g2_point(agg)
            if apk is None or sig is None:
                return False
            triples.append((apk, sig, ref.hash_to_g2(msg)))
        # transcript binds every message and signature before any scalar
        # is drawn — the Fiat-Shamir ordering that makes the RLC sound
        tr = hashlib.sha256()
        for (_, msg, agg) in checks:
            tr.update(len(msg).to_bytes(4, "big"))
            tr.update(msg)
            tr.update(agg)
        seed = tr.digest()
        scalars = [
            max(
                1,
                int.from_bytes(
                    hashlib.sha256(seed + k.to_bytes(8, "big")).digest()[:16],
                    "big",
                ),
            )
            for k in range(len(triples))
        ]
        sig_acc = None
        pairs = []
        for r, (apk, sig, hm) in zip(scalars, triples):
            sig_acc = ref.ec_add(
                sig_acc, ref.ec_mul(sig, r, ref.FP2_OPS), ref.FP2_OPS
            )
            pairs.append((ref.ec_mul(apk, r, ref.FP_OPS), hm))
        pairs.insert(0, (ref.ec_neg(ref.G1, ref.FP_OPS), sig_acc))

        return run_legs(_MULTI_PAIRING, len(pairs), pairs)


def bls_suite():
    """Keccak256 + BLS12-381 — the aggregate-QC suite, registered beside
    ecdsa_suite/sm_suite (reference: the ProtocolInitializer suite choice)."""
    from .suite import CryptoSuite, Keccak256

    return CryptoSuite(Keccak256(), BLSCrypto())
