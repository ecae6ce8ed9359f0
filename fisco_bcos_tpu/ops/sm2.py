"""Batch SM2 (GB/T 32918.2) signature verification on TPU — 国密 suite.

Reference counterpart: bcos-crypto signature/sm2/SM2Crypto.cpp:29-91 (wedpr
FFI) and the OpenSSL-tassl FastSM2 path (signature/fastsm2/fast_sm2.cpp).
Signature format follows the reference: 64-byte r‖s with the 64-byte
uncompressed public key appended, and "recover" = parse-pubkey-then-verify
(SM2Crypto.cpp:81-91) — SM2 has no algebraic pubkey recovery in this scheme.

The digest is e = SM3(ZA ‖ M) with ZA = SM3(ENTL ‖ ID ‖ a ‖ b ‖ Gx ‖ Gy ‖
Px ‖ Py) and the default user id "1234567812345678"; both are fixed-length
messages, assembled from limbs and hashed on the device (:func:`e_device`).

Verification: t = (r + s) mod n (t ≠ 0); (x1, y1) = s*G + t*Q;
valid iff (e + x1) mod n == r.

The EC plane is the lane-dense windowed ladder shared with secp256k1
(:mod:`fisco_bcos_tpu.ops.ec`); SM2's prime has a 225-bit complement, so
the field is the Montgomery one (``limb.MontField``). The prime is a Solinas
prime, p = 2^256 − 2^224 − 2^96 + 2^64 − 1, and so is −p^-1 mod 2^256 = 1 +
2^64 − 2^96 + 2^128 − 2^161 + 2^193 − 2^226: ``limb.make_mont_field`` finds
both sums in the modulus, and REDC multiplies by them as twelve shifted rows
where it ran two limb products (PR 46), so a field multiplication here is
one limb product, as secp256k1's is. ``limb.SparseFoldField`` is another
reduction for the same prime (plain domain, a dense table fold and a signed
shift-add round), bit-exact; on the chip it beats REDC by products (241.9
against 339.5 ms at 10,240 lanes) and loses to REDC by shifted rows (95.0)
at every size (PERF.md §6, PR 46); FISCO_SM2_SPARSE=1 still selects it (see
the note in :func:`fisco_bcos_tpu.ops.ec._make_curve_ops`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.ref.ecdsa import SM2_DEFAULT_ID
from .bigint import (
    bytes_be_to_limbs,
    digest_words_be_to_limbs,
    limbs_to_bytes_be,
    limbs_to_words_be_device,
)
from .ec import (
    SM2_OPS,
    add_mod_n,
    dual_mul_windowed,
    g_comb_table,
    lane_inv,
    on_curve,
    reduce_mod_n,
    valid_scalar,
)
from .hash_common import bucket_batch as _bucket
from .hash_common import pad_rows as _pad_rows
from . import limb
from .limb import const_rows, eq, is_zero, lt
from .sm3 import sm3_fixed, sm3_of_word_pair

_C = SM2_OPS


def verify_project_core(e, r, s, qx, qy, g_table):
    """Batch SM2 verify, projective part.

    Limb-leading [16, ...] plain-domain inputs (lane-dense [16, S, 128] from
    the jitted entry): e = SM3(ZA ‖ M) digest as an
    integer; (r, s): signature; (qx, qy): affine public key.
    Returns (X, Z [16, T] Montgomery-domain projective coords of
    s*G + t*Q, valid bool[T]) — the final comparison needs the affine x1
    value, so the lane-batched Z inversion happens outside in
    :func:`verify_finish`."""
    C = _C
    F = C.F
    p_rows = const_rows(C.p_limbs, e)
    valid = valid_scalar(r, C) & valid_scalar(s, C)
    valid &= lt(qx, p_rows) & lt(qy, p_rows)
    qx_e = F.from_plain(qx)
    qy_e = F.from_plain(qy)
    valid &= on_curve(qx_e, qy_e, C)
    t = add_mod_n(reduce_mod_n(r, C), s, C)
    valid &= ~is_zero(t)
    X, _Y, Z = dual_mul_windowed(s, t, (qx_e, qy_e), C, g_table)
    return X, Z, valid


def verify_finish(e, r, X, Z, valid):
    """(e + x1) mod n == r with the Z inversion batched across lanes
    (plain XLA; one Fermat chain per batch)."""
    C = _C
    F = C.F
    zinv = lane_inv(F, Z)
    x1_e = F.mul(X, zinv)
    x1 = reduce_mod_n(F.to_plain(x1_e), C)
    e_n = reduce_mod_n(e, C)
    R = add_mod_n(e_n, x1, C)
    return valid & ~is_zero(Z) & eq(R, r)


def verify_core(e, r, s, qx, qy, g_table):
    """Whole-program SM2 verify (plain-XLA path)."""
    X, Z, valid = verify_project_core(e, r, s, qx, qy, g_table)
    return verify_finish(e, r, X, Z, valid)


def verify_rows(e, r, s, qx, qy):
    """Batch SM2 verify over batch-major [B, 16] plain limbs -> bool[B]:
    the body of :func:`_verify_xla` and of the fused SM admission program
    (crypto.admission.sm_admission_core), unjitted."""
    gt = jnp.asarray(g_comb_table(_C.name))
    b = e.shape[0]
    ok = verify_core(*(limb.lane_dense(a) for a in (e, r, s, qx, qy)), gt)
    return limb.batch_lanes(ok, b)


@jax.jit
def _verify_xla(e, r, s, qx, qy):
    return verify_rows(e, r, s, qx, qy)


def verify_device(e, r, s, qx, qy):
    """Batch SM2 verify. All inputs [B, 16] plain-domain batch-major limbs."""
    return _verify_xla(e, r, s, qx, qy)


# ---------------------------------------------------------------------------
# e = SM3(ZA ‖ M) on the device
# ---------------------------------------------------------------------------


def _za_frame() -> tuple[np.ndarray, np.ndarray]:
    """What surrounds Px ‖ Py in ZA's padded SM3 input under the default user
    id, as big-endian 16-bit halfwords (a limb is one): the constant ENTL ‖ ID
    ‖ a ‖ b ‖ Gx ‖ Gy before it (146 bytes), the Merkle–Damgård padding after
    it; with the key's 64 bytes, four blocks."""
    c = _C.curve
    head = (len(SM2_DEFAULT_ID) * 8).to_bytes(2, "big") + SM2_DEFAULT_ID + b"".join(
        v.to_bytes(32, "big") for v in (c.a, c.b, c.gx, c.gy)
    )
    n = len(head) + 64
    total = ((n + 8) // 64 + 1) * 64
    tail = b"\x80" + bytes(total - n - 9) + (n * 8).to_bytes(8, "big")
    return tuple(np.frombuffer(b, dtype=">u2").astype(np.uint32) for b in (head, tail))


_ZA_FRAME = _za_frame()


def e_device(m_words, qx, qy):
    """e = SM3(ZA ‖ M) with ZA = SM3(ENTL ‖ ID ‖ a ‖ b ‖ Gx ‖ Gy ‖ Px ‖ Py)
    under the default user id, assembled from limbs and hashed on the device.
    m_words [B, 8]: the 32-byte message (the tx hash) as big-endian words;
    qx, qy [B, 16] plain limbs -> e as [B, 8] big-endian words."""
    head, tail = _ZA_FRAME
    b = qx.shape[0]
    hw = jnp.concatenate(
        [
            jnp.broadcast_to(jnp.asarray(head), (b, head.size)),
            qx[:, ::-1].astype(jnp.uint32),  # limbs, most significant first
            qy[:, ::-1].astype(jnp.uint32),
            jnp.broadcast_to(jnp.asarray(tail), (b, tail.size)),
        ],
        axis=1,
    )
    words = (hw[:, 0::2] << 16) | hw[:, 1::2]
    za = sm3_fixed(words.reshape(b, -1, 16))
    return sm3_of_word_pair(za, m_words)


@jax.jit
def _e_xla(m, qx, qy):
    return digest_words_be_to_limbs(e_device(limbs_to_words_be_device(m), qx, qy))


# ---------------------------------------------------------------------------
# Host wrappers
# ---------------------------------------------------------------------------


def sm2_e_batch(msg_hashes: np.ndarray, pubkeys: np.ndarray) -> np.ndarray:
    """e = SM3(ZA ‖ M) for a batch: [B,32] hashes + [B,64] pubkeys -> [B,32],
    one device program (:func:`e_device`), rows bucketed like every batch."""
    msg_hashes = np.asarray(msg_hashes, dtype=np.uint8)
    pubkeys = np.asarray(pubkeys, dtype=np.uint8)
    bsz = len(msg_hashes)
    bb = _bucket(max(bsz, 1))
    e = _e_xla(
        *(
            _pad_rows(bytes_be_to_limbs(a), bb)
            for a in (msg_hashes, pubkeys[:, :32], pubkeys[:, 32:])
        )
    )
    # analysis: allow(host-sync, wrapper-boundary materialization — the
    # caller marshals e into the verify program's operands)
    return limbs_to_bytes_be(np.asarray(e))[:bsz]


def verify_batch(
    msg_hashes: np.ndarray,
    rs: np.ndarray,
    ss: np.ndarray,
    pubkeys: np.ndarray,
) -> np.ndarray:
    """Host API: [B,32] tx hash, [B,32] r, [B,32] s, [B,64] pubkey -> bool[B]."""
    from ..observability.device import device_span

    bsz = len(msg_hashes)
    bb = _bucket(bsz)
    with device_span("sm2_verify", bsz, shape_key=bb) as sp:
        e = _pad_rows(
            bytes_be_to_limbs(sm2_e_batch(msg_hashes, pubkeys)), bb
        )
        r = _pad_rows(bytes_be_to_limbs(rs), bb)
        s = _pad_rows(bytes_be_to_limbs(ss), bb)
        pubkeys = np.asarray(pubkeys, dtype=np.uint8)
        qx = _pad_rows(bytes_be_to_limbs(pubkeys[:, :32]), bb)
        qy = _pad_rows(bytes_be_to_limbs(pubkeys[:, 32:]), bb)
        with sp.phase("transfer"):  # host->device staging of the operands
            ea, ra, sa = jnp.asarray(e), jnp.asarray(r), jnp.asarray(s)
            qxa, qya = jnp.asarray(qx), jnp.asarray(qy)
        out = verify_device(ea, ra, sa, qxa, qya)
        # analysis: allow(host-sync, wrapper-boundary materialization —
        # callers receive host bools; the plane overlaps batches, not lanes)
        return np.asarray(out)[:bsz]


def recover_batch(
    msg_hashes: np.ndarray, sigs_with_pub: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Reference-style SM2 "recover": signature is r‖s‖pubkey (128 bytes);
    parse the pubkey and verify (SM2Crypto.cpp:81-91).

    Returns (pubkeys [B,64], ok bool[B])."""
    sigs_with_pub = np.asarray(sigs_with_pub, dtype=np.uint8)
    pubs = sigs_with_pub[:, 64:128]
    ok = verify_batch(
        msg_hashes, sigs_with_pub[:, :32], sigs_with_pub[:, 32:64], pubs
    )
    out = np.where(ok[:, None], pubs, np.zeros_like(pubs))
    return out, ok


# -- progaudit shape spec (analysis/progaudit: canonical audited bucket) -----
PROGSPEC = {
    "_verify_xla": {
        "bucket": 256,
        "inputs": lambda b: [((b, 16), "uint32")] * 5,
    },
    "_e_xla": {
        "bucket": 256,
        "inputs": lambda b: [((b, 16), "uint32")] * 3,
    },
}
