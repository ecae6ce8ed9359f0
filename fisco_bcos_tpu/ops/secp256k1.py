"""Batch secp256k1 ECDSA verify / recover on TPU — the north-star kernel.

Replaces the reference's per-signature Rust FFI calls (`wedpr_secp256k1_verify`
bcos-crypto/bcos-crypto/signature/secp256k1/Secp256k1Crypto.cpp:57,
`wedpr_secp256k1_recover_public_key` :85) that the TxPool admission path
(`Transaction::verify()` bcos-framework/bcos-framework/protocol/Transaction.h:64-84)
and the PBFT/BlockSync signature-list check
(bcos-pbft/bcos-pbft/core/BlockValidator.cpp:141-177) invoke one tx at a time on
CPU threads. Here a whole block's signatures are one device program.

One execution path: the ``*_core`` functions jitted as plain XLA on every
backend (the hand-tiled Pallas kernels that once traced the same bodies lost
to it on hardware, were never on a deployment's path, and went in PR 25).

Semantics match the reference:
- 65-byte signature r‖s‖v; v ∈ {0..3} or {27, 28} (Secp256k1Crypto.cpp:106-108).
- recover returns the uncompressed public key (x‖y, 64 bytes); the sender
  address is right160(keccak256(pubkey)) (CryptoSuite.h:56-59).

Invalid lanes never raise: every failure mode (bad range, off-curve pubkey,
non-residue x, infinity result) lowers a validity bit — one compiled program
serves adversarial and honest inputs alike, mandatory for consensus code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import limb
from .bigint import bytes_be_to_limbs, limbs_to_bytes_be
from .ec import (
    SECP256K1_OPS,
    g_comb_table_glv,
    glv_decompose,
    lane_inv,
    on_curve,
    pt_to_affine_batch,
    quad_mul_windowed,
    reduce_mod_n,
    valid_scalar,
)
from .hash_common import bucket_batch as _bucket
from .hash_common import pad_rows as _pad_rows
from .limb import const_rows, eq, is_zero, lt, select

_C = SECP256K1_OPS


def _g_table() -> jnp.ndarray:
    return jnp.asarray(g_comb_table_glv(_C.name))


# ---------------------------------------------------------------------------
# Batched scalar inversion
# ---------------------------------------------------------------------------


def inv_mod_n(x):
    """Batch x^-1 mod n via one Fermat exponentiation for the whole lane
    axis (:func:`lane_inv`). Canonicalizes first so an adversarial x ≡ 0
    (mod n) with nonzero limbs cannot poison the shared product tree."""
    return lane_inv(_C.Fn, reduce_mod_n(x, _C))


# ---------------------------------------------------------------------------
# Core bodies (limb-leading [16, ...]; the jitted entry points pass a
# lane-dense batch, limb.lane_dense)
# ---------------------------------------------------------------------------


def verify_core(z, r, s, qx, qy, sinv, g_table):
    """Batch ECDSA verify. z/r/s/qx/qy: [16, ...] plain-domain limbs;
    sinv = :func:`inv_mod_n`(s) computed outside (batched — garbage on
    s ≡ 0 lanes, which `valid` masks).

    Returns bool[T]: signature valid. The affine comparison is projective
    (x(R) ≡ r mod n ⟺ X = r·Z or X = (r+n)·Z, r+n < p) so no per-lane
    inversion remains anywhere in the verify path.
    """
    C = _C
    F, Fn = C.F, C.Fn
    p_rows = const_rows(C.p_limbs, z)
    valid = valid_scalar(r, C) & valid_scalar(s, C)
    valid &= lt(qx, p_rows) & lt(qy, p_rows)
    qx_e = F.from_plain(qx)
    qy_e = F.from_plain(qy)
    valid &= on_curve(qx_e, qy_e, C)
    z_n = reduce_mod_n(z, C)
    u1 = Fn.mul(z_n, sinv)
    u2 = Fn.mul(reduce_mod_n(r, C), sinv)
    ka, sa, kb, sb = glv_decompose(u2, C)
    X, _Y, Z = quad_mul_windowed(
        u1, ka, sa, kb, sb, (qx_e, qy_e), C, g_table
    )
    # r < n < p: r is already a canonical field element in the plain domain
    ok = eq(X, F.mul(r, Z))
    rn17 = limb.add_widen(r, const_rows(C.n_limbs, r))  # [17, T]
    rn_fits = (limb.row(rn17, 16) == 0) & lt(rn17[:16], p_rows)
    ok |= rn_fits & eq(X, F.mul(rn17[:16], Z))
    return valid & ~is_zero(Z) & ok


def recover_project_core(z, r, s, v, rinv, g_table):
    """Batch ECDSA public-key recovery, projective part.

    z, r, s: [16, ...] plain limbs; v: [...] int32 recovery id (0..3 or
    27/28, exactly the reference's accepted encodings —
    Secp256k1Crypto.cpp:106; 29/30 must NOT alias to 2/3);
    rinv = :func:`inv_mod_n`(r) computed outside.
    Returns (X, Y, Z [16, T] field-domain projective Q, ok bool[T]);
    :func:`recover_finish` converts to plain affine.
    """
    C = _C
    F, Fn = C.F, C.Fn
    valid = ((v >= 0) & (v <= 3)) | ((v >= 27) & (v <= 28))
    v = jnp.where(v >= 27, v - 27, v)
    valid &= valid_scalar(r, C) & valid_scalar(s, C)
    # x = r + (v & 2 ? n : 0); reject overflow past 2^256 or x >= p
    n_or_0 = select(
        (v & 2) != 0, const_rows(C.n_limbs, r), jnp.zeros_like(r)
    )
    x17 = limb.add_widen(r, n_or_0)  # [17, T]
    overflow = limb.row(x17, 16) != 0
    x = x17[:16]
    valid &= ~overflow & lt(x, const_rows(C.p_limbs, r))
    # y from the curve equation y^2 = x^3 + b (a = 0); p ≡ 3 (mod 4)
    y2 = F.add(F.mul(F.sqr(x), x), const_rows(C.b_enc, x))
    y = F.sqrt(y2)
    valid &= eq(F.sqr(y), y2)  # x^3 + b must be a quadratic residue
    flip = (limb.row(y, 0) & 1).astype(jnp.int32) != (v & 1)  # plain parity
    y = select(flip, F.neg(y), y)
    # Q = r^-1 * (s*R - z*G)
    z_n = reduce_mod_n(z, C)
    u1 = Fn.neg(Fn.mul(z_n, rinv))
    u2 = Fn.mul(s, rinv)
    ka, sa, kb, sb = glv_decompose(u2, C)
    X, Y, Z = quad_mul_windowed(u1, ka, sa, kb, sb, (x, y), C, g_table)
    return X, Y, Z, valid


def recover_finish(X, Y, Z, valid):
    """Projective Q -> plain affine (qx, qy, ok), Z inversion batched
    across lanes."""
    C = _C
    qx_e, qy_e, inf = pt_to_affine_batch((X, Y, Z), C)
    valid &= ~inf
    qx = select(valid, C.F.to_plain(qx_e), jnp.zeros_like(X))
    qy = select(valid, C.F.to_plain(qy_e), jnp.zeros_like(X))
    return qx, qy, valid


def recover_core(z, r, s, v, g_table):
    """Whole-program recovery (plain-XLA path): pre-inversion +
    :func:`recover_project_core` + :func:`recover_finish`."""
    rinv = inv_mod_n(r)
    X, Y, Z, valid = recover_project_core(z, r, s, v, rinv, g_table)
    return recover_finish(X, Y, Z, valid)


# ---------------------------------------------------------------------------
# Device entry points ([B, 16] batch-major public API; converted to the
# lane-dense [16, S, 128] once on the way in and back once on the way out)
# ---------------------------------------------------------------------------


@jax.jit
def _verify_xla(z, r, s, qx, qy):
    b = z.shape[0]
    z, r, s, qx, qy = (limb.lane_dense(a) for a in (z, r, s, qx, qy))
    ok = verify_core(z, r, s, qx, qy, inv_mod_n(s), _g_table())
    return limb.batch_lanes(ok, b)


@jax.jit
def _recover_xla(z, r, s, v):
    b = z.shape[0]
    z, r, s = (limb.lane_dense(a) for a in (z, r, s))
    qx, qy, ok = recover_core(z, r, s, limb.lane_mask(v, z), _g_table())
    return limb.batch_major(qx, b), limb.batch_major(qy, b), limb.batch_lanes(ok, b)


def verify_device(z, r, s, qx, qy):
    """Batch ECDSA verify. All inputs [B, 16] plain-domain limbs (batch
    major); returns bool[B]."""
    return _verify_xla(z, r, s, qx, qy)


def recover_device(z, r, s, v):
    """Batch ECDSA recover. z/r/s: [B, 16] limbs; v: [B] int32.
    Returns (qx, qy [B, 16] plain limbs, ok bool[B])."""
    return _recover_xla(z, r, s, v)


# ---------------------------------------------------------------------------
# Host wrappers (bytes in / bytes out, batch padded per hash_common._bucket)
# ---------------------------------------------------------------------------


def verify_batch(
    msg_hashes: np.ndarray, rs: np.ndarray, ss: np.ndarray, pubkeys: np.ndarray
) -> np.ndarray:
    """Host API: [B,32] hash, [B,32] r, [B,32] s, [B,64] uncompressed pubkey
    (all uint8 big-endian) -> bool[B]."""
    from ..observability.device import device_span

    bsz = len(msg_hashes)
    bb = _bucket(bsz)
    with device_span("secp256k1_verify", bsz, shape_key=bb) as sp:
        z = _pad_rows(bytes_be_to_limbs(msg_hashes), bb)
        r = _pad_rows(bytes_be_to_limbs(rs), bb)
        s = _pad_rows(bytes_be_to_limbs(ss), bb)
        pubkeys = np.asarray(pubkeys, dtype=np.uint8)
        qx = _pad_rows(bytes_be_to_limbs(pubkeys[:, :32]), bb)
        qy = _pad_rows(bytes_be_to_limbs(pubkeys[:, 32:]), bb)
        with sp.phase("transfer"):  # host->device staging of the operands
            za, ra, sa = jnp.asarray(z), jnp.asarray(r), jnp.asarray(s)
            qxa, qya = jnp.asarray(qx), jnp.asarray(qy)
        out = verify_device(za, ra, sa, qxa, qya)
        # analysis: allow(host-sync, wrapper-boundary materialization —
        # callers receive host bools; the plane overlaps batches, not lanes)
        return np.asarray(out)[:bsz]


def recover_batch(
    msg_hashes: np.ndarray, sigs65: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host API: [B,32] hash + [B,65] r‖s‖v signatures (uint8) ->
    (pubkeys [B,64] uint8, ok bool[B])."""
    from ..observability.device import device_span

    bsz = len(msg_hashes)
    bb = _bucket(bsz)
    with device_span("secp256k1_recover", bsz, shape_key=bb) as sp:
        sigs65 = np.asarray(sigs65, dtype=np.uint8)
        z = _pad_rows(bytes_be_to_limbs(msg_hashes), bb)
        r = _pad_rows(bytes_be_to_limbs(sigs65[:, :32]), bb)
        s = _pad_rows(bytes_be_to_limbs(sigs65[:, 32:64]), bb)
        v = _pad_rows(sigs65[:, 64].astype(np.int32), bb)
        with sp.phase("transfer"):  # host->device staging of the operands
            za, ra, sa, va = (
                jnp.asarray(z), jnp.asarray(r), jnp.asarray(s), jnp.asarray(v)
            )
        qx, qy, ok = recover_device(za, ra, sa, va)
        pubs = np.concatenate(
            # analysis: allow(host-sync, recover's contract returns host
            # pubkey bytes for address derivation + dedup — intended sync)
            [limbs_to_bytes_be(np.asarray(qx)), limbs_to_bytes_be(np.asarray(qy))],
            axis=-1,
        )
        # analysis: allow(host-sync, same boundary: ok bits ride the same
        # device round-trip as the pubkeys above)
        return pubs[:bsz], np.asarray(ok)[:bsz]


# -- progaudit shape spec (analysis/progaudit: canonical audited bucket) -----
PROGSPEC = {
    "_verify_xla": {
        "bucket": 256,
        "inputs": lambda b: [((b, 16), "uint32")] * 5,
    },
    "_recover_xla": {
        "bucket": 256,
        "inputs": lambda b: [((b, 16), "uint32")] * 3 + [((b,), "int32")],
    },
}
