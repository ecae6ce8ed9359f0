"""Batch SM2 (GB/T 32918.2) signature verification on TPU — 国密 suite.

Reference counterpart: bcos-crypto signature/sm2/SM2Crypto.cpp:29-91 (wedpr
FFI) and the OpenSSL-tassl FastSM2 path (signature/fastsm2/fast_sm2.cpp).
Signature format follows the reference: 64-byte r‖s with the 64-byte
uncompressed public key appended, and "recover" = parse-pubkey-then-verify
(SM2Crypto.cpp:81-91) — SM2 has no algebraic pubkey recovery in this scheme.

The digest is e = SM3(ZA ‖ M) with ZA = SM3(ENTL ‖ ID ‖ a ‖ b ‖ Gx ‖ Gy ‖
Px ‖ Py) and the default user id "1234567812345678"; both are fixed-length
messages, so e-derivation itself runs on the batch SM3 kernel.

Verification: t = (r + s) mod n (t ≠ 0); (x1, y1) = s*G + t*Q;
valid iff (e + x1) mod n == r.

The EC plane is the lane-dense windowed ladder shared with secp256k1
(:mod:`fisco_bcos_tpu.ops.ec`); SM2's prime has a 225-bit complement, so
the field is the generic Montgomery path (``limb.MontField``) by default.
The prime is also a Solinas prime (2^256 − p = 2^224 + 2^96 − 2^64 + 1),
and ``limb.SparseFoldField`` implements the shift-add fold bit-exactly —
opt in with FISCO_SM2_SPARSE=1 (kept off pending a measured win over
REDC; see the note in :func:`fisco_bcos_tpu.ops.ec._make_curve_ops`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.ref.ecdsa import SM2_DEFAULT_ID
from .bigint import bytes_be_to_limbs
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
from .sm3 import sm3_batch_async

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


@jax.jit
def _verify_xla(e, r, s, qx, qy):
    gt = jnp.asarray(g_comb_table(_C.name))
    b = e.shape[0]
    ok = verify_core(*(limb.lane_dense(a) for a in (e, r, s, qx, qy)), gt)
    return limb.batch_lanes(ok, b)


def verify_device(e, r, s, qx, qy):
    """Batch SM2 verify. All inputs [B, 16] plain-domain batch-major limbs."""
    return _verify_xla(e, r, s, qx, qy)


# ---------------------------------------------------------------------------
# Host wrappers
# ---------------------------------------------------------------------------


def sm2_e_batch(
    msg_hashes: np.ndarray, pubkeys: np.ndarray, user_id: bytes = SM2_DEFAULT_ID
) -> np.ndarray:
    """e = SM3(ZA ‖ M) for a batch: [B,32] hashes + [B,64] pubkeys -> [B,32].

    ZA inputs are fixed-length, so both SM3 passes run on the device kernel."""
    msg_hashes = np.asarray(msg_hashes, dtype=np.uint8)
    pubkeys = np.asarray(pubkeys, dtype=np.uint8)
    c = _C.curve
    entl = (len(user_id) * 8).to_bytes(2, "big")
    prefix = np.frombuffer(
        entl
        + user_id
        + c.a.to_bytes(32, "big")
        + c.b.to_bytes(32, "big")
        + c.gx.to_bytes(32, "big")
        + c.gy.to_bytes(32, "big"),
        dtype=np.uint8,
    )
    bsz = len(msg_hashes)
    za_in = np.concatenate(
        [np.broadcast_to(prefix, (bsz, len(prefix))), pubkeys], axis=1
    )
    # the span-less async entry: sm2_e_batch runs INSIDE the caller's
    # sm2_verify device_span — a nested sm3 span would double-count the
    # SM3 wall (and misfile its compiles as sm2 execute remainder); the
    # e-derivation is part of sm2's own phase decomposition
    za = sm3_batch_async([bytes(row) for row in za_in])()
    e_in = np.concatenate([za, msg_hashes], axis=1)
    return sm3_batch_async([bytes(row) for row in e_in])()


def verify_batch(
    msg_hashes: np.ndarray,
    rs: np.ndarray,
    ss: np.ndarray,
    pubkeys: np.ndarray,
    user_id: bytes = SM2_DEFAULT_ID,
) -> np.ndarray:
    """Host API: [B,32] tx hash, [B,32] r, [B,32] s, [B,64] pubkey -> bool[B]."""
    from ..observability.device import device_span

    bsz = len(msg_hashes)
    bb = _bucket(bsz)
    with device_span("sm2_verify", bsz, shape_key=bb) as sp:
        e = _pad_rows(
            bytes_be_to_limbs(sm2_e_batch(msg_hashes, pubkeys, user_id)), bb
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
}
