"""Batch elliptic-curve arithmetic on TPU (secp256k1 and SM2 share one path).

Replaces the reference's per-signature CPU EC stack (wedpr-crypto Rust FFI
behind bcos-crypto — `wedpr_secp256k1_verify` at
bcos-crypto/bcos-crypto/signature/secp256k1/Secp256k1Crypto.cpp:57, SM2 at
signature/sm2/SM2Crypto.cpp:29-91) with batch complete-projective kernels
over the limb-leading field arithmetic in :mod:`fisco_bcos_tpu.ops.limb`.

TPU-first design:
- A point is a homogeneous (X : Y : Z) tuple of ``[16, ...]`` limb arrays
  (limb index leading, batch in the trailing dimensions: lane-dense
  ``[16, S, 128]`` from the secp256k1 and SM2 entry points, see
  :mod:`fisco_bcos_tpu.ops.limb`) in the curve's field domain (plain for
  the pseudo-Mersenne fast path, Montgomery for SM2); (0 : 1 : 0) is the
  identity.
- The group law is the Renes–Costello–Batina COMPLETE addition (section
  comment below): exceptional cases (identity operands, P == Q, P == -Q)
  are covered by the algebra itself — no per-lane select chains and no
  shadow doubling per add, which trims ~25% of the ladder's field muls.
- ``dual_mul_windowed`` computes u1*G + u2*Q with 4-bit windows and one
  shared doubling chain (Shamir): a 15-entry runtime projective table for
  Q, and a host-precomputed affine table {c*G} so G contributions are
  cheap mixed (Z2 = 1) additions with no runtime table build.
- The whole ladder is a ``lax.scan`` over 64 window steps; table selects
  are 15-way masked chains (schedule identical on every lane).

Plain XLA on every backend; integer semantics make every backend
bit-identical — mandatory for consensus.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.ref.ecdsa import SECP256K1, SM2_CURVE, Curve, point_add, point_mul
from . import limb
from .limb import (
    FoldField,
    MontField,
    const_rows,
    eq,
    is_zero,
    lt,
    make_fold_field,
    make_mont_field,
    select,
    sub_borrow,
)

_R = 1 << 256
WINDOW = 4
N_WINDOWS = 256 // WINDOW  # 64


@dataclass(frozen=True)
class CurveOps:
    """Static device context for one short-Weierstrass curve."""

    name: str
    curve: Curve
    F: FoldField | MontField  # field of the curve prime p
    Fn: FoldField | None  # scalar field mod n (None -> plain-limb helpers)
    a_is_zero: bool
    a_is_minus3: bool  # SM2: a = p - 3, so a·x = -(3x) — no full mul
    a_enc: np.ndarray  # a in field domain, [16]
    b_enc: np.ndarray  # b in field domain, [16]
    b3_small: int | None  # 3b when it fits a scalar broadcast (secp: 21)
    b3_enc: np.ndarray = field(repr=False)  # 3b in field domain
    p_limbs: np.ndarray = field(repr=False)
    n_limbs: np.ndarray = field(repr=False)

    def __hash__(self):
        return hash(self.name)

    def __eq__(self, other):
        return isinstance(other, CurveOps) and other.name == self.name


def _make_curve_ops(c: Curve) -> CurveOps:
    # Pseudo-Mersenne fast path when p = 2^256 - small (secp256k1);
    # Montgomery otherwise (SM2), whose REDC multiplies by its two constants
    # as shifted rows where the prime makes them short signed sums (SM2's p
    # does: make_mont_field decides from the modulus, PR 46). The same prime
    # has a plain-domain reduction, limb.SparseFoldField (a dense table fold
    # and a signed shift-add round, five exact chains where REDC has three):
    # exact; timed on the chip in PR 46 (PERF.md §6) it costs 82.8 / 170.0 /
    # 241.9 ms a call at 1,024 / 4,096 / 10,240 lanes where REDC by products
    # cost 119.5 / 245.9 / 339.5 and REDC by shifted rows costs 23.2 / 46.7 /
    # 95.0, so it stays opt-in (FISCO_SM2_SPARSE=1) and nothing turns it on.
    import logging
    import os

    from .limb import _SPARSE_COMPLEMENTS, make_sparse_fold_field

    if _R - c.p < 1 << 132:
        F = make_fold_field(c.p)
    elif c.p in _SPARSE_COMPLEMENTS and os.environ.get("FISCO_SM2_SPARSE") == "1":
        # read once at import (curve ops are module-level singletons).
        # Plain logging.getLogger: this runs at LIBRARY IMPORT time, and
        # the project logger helper installs root handlers (basicConfig),
        # which an importing application must stay free to configure.
        # warning level so the confirmation reaches the default lastResort
        # handler — at import time the app has not configured logging yet,
        # and an INFO record would be dropped silently
        logging.getLogger("fisco.ec").warning(
            "FISCO_SM2_SPARSE=1: %s uses the Solinas sparse-fold field "
            "(set BEFORE process start; changing it later has no effect)",
            c.name,
        )
        F = make_sparse_fold_field(c.p)
    else:
        flag = os.environ.get("FISCO_SM2_SPARSE")
        if (
            c.p in _SPARSE_COMPLEMENTS
            and flag is not None
            and flag not in ("", "0")  # explicit disables behave as intended
        ):
            logging.getLogger("fisco.ec").warning(
                "FISCO_SM2_SPARSE=%r ignored for %s (only the exact value "
                "'1' opts in, and only when set before process start)",
                flag, c.name,
            )
        F = make_mont_field(c.p)
    Fn = make_fold_field(c.n) if _R - c.n < 1 << 132 else None
    b3 = 3 * c.b % c.p
    return CurveOps(
        name=c.name,
        curve=c,
        F=F,
        Fn=Fn,
        a_is_zero=c.a == 0,
        a_is_minus3=c.a == c.p - 3,
        a_enc=F.enc(c.a),
        b_enc=F.enc(c.b),
        b3_small=b3 if (b3 < 1 << 15 and isinstance(F, FoldField)) else None,
        b3_enc=F.enc(b3),
        p_limbs=limb.int_to_rows(c.p),
        n_limbs=limb.int_to_rows(c.n),
    )


SECP256K1_OPS = _make_curve_ops(SECP256K1)
SM2_OPS = _make_curve_ops(SM2_CURVE)


# ---------------------------------------------------------------------------
# Complete projective group law (Renes–Costello–Batina 2016)
# ---------------------------------------------------------------------------
#
# Homogeneous (X : Y : Z), identity (0 : 1 : 0). The RCB formulas are
# COMPLETE on prime-order short-Weierstrass curves (both tx curves have
# cofactor 1): one straight-line program covers identity operands, P == Q
# and P == -Q with no exceptional cases — the branch-freedom consensus code
# needs comes from the algebra itself, with zero lane-select overhead, and
# (unlike the round-2 Jacobian law) no shadow jac_double evaluated per add
# just to cover the P == Q lane. Ladder cost drops ~25%.
#
# Dispatch: a = 0 (secp256k1) uses RCB algorithms 7/8/9 with b3 = 3b = 21 a
# cheap scalar-broadcast multiply; the generic-a path (SM2, a = -3) uses
# algorithms 1/2/3 with a·t = -(3t) addition chains.


def _b3_mul(x, C: "CurveOps"):
    if C.b3_small is not None:
        return C.F.mul_small(x, C.b3_small)
    return C.F.mul(x, const_rows(C.b3_enc, x))


def _a_mul(x, C: "CurveOps"):
    """a·x; SM2's a = p - 3 makes this -(3x)."""
    F = C.F
    if C.a_is_minus3:
        return F.neg(F.mul_small(x, 3))
    return F.mul(x, const_rows(C.a_enc, x))


def pt_add(P, Q, C: CurveOps):
    """Complete addition. a = 0: RCB alg 7 (12M + 2·b3); generic: alg 1
    (12M + 3·a + 2·b3)."""
    X1, Y1, Z1 = P
    X2, Y2, Z2 = Q
    F = C.F
    if C.a_is_zero:
        t0 = F.mul(X1, X2)
        t1 = F.mul(Y1, Y2)
        t2 = F.mul(Z1, Z2)
        t3 = F.mul(F.add(X1, Y1), F.add(X2, Y2))
        t3 = F.sub(t3, F.add(t0, t1))  # X1Y2 + X2Y1
        t4 = F.mul(F.add(Y1, Z1), F.add(Y2, Z2))
        t4 = F.sub(t4, F.add(t1, t2))  # Y1Z2 + Y2Z1
        x3 = F.mul(F.add(X1, Z1), F.add(X2, Z2))
        y3 = F.sub(x3, F.add(t0, t2))  # X1Z2 + X2Z1
        x3 = F.add(t0, t0)
        t0 = F.add(x3, t0)  # 3·X1X2
        t2 = _b3_mul(t2, C)
        z3 = F.add(t1, t2)
        t1 = F.sub(t1, t2)
        y3 = _b3_mul(y3, C)
        x3 = F.mul(t4, y3)
        t2 = F.mul(t3, t1)
        x3 = F.sub(t2, x3)
        y3 = F.mul(y3, t0)
        t1 = F.mul(t1, z3)
        y3 = F.add(t1, y3)
        t0 = F.mul(t0, t3)
        z3 = F.mul(z3, t4)
        z3 = F.add(z3, t0)
        return x3, y3, z3
    t0 = F.mul(X1, X2)
    t1 = F.mul(Y1, Y2)
    t2 = F.mul(Z1, Z2)
    t3 = F.mul(F.add(X1, Y1), F.add(X2, Y2))
    t3 = F.sub(t3, F.add(t0, t1))  # X1Y2 + X2Y1
    t4 = F.mul(F.add(X1, Z1), F.add(X2, Z2))
    t4 = F.sub(t4, F.add(t0, t2))  # X1Z2 + X2Z1
    t5 = F.mul(F.add(Y1, Z1), F.add(Y2, Z2))
    t5 = F.sub(t5, F.add(t1, t2))  # Y1Z2 + Y2Z1
    z3 = _a_mul(t4, C)
    x3 = _b3_mul(t2, C)
    z3 = F.add(x3, z3)
    x3 = F.sub(t1, z3)
    z3 = F.add(t1, z3)
    y3 = F.mul(x3, z3)
    t1 = F.add(t0, t0)
    t1 = F.add(t1, t0)  # 3·X1X2
    t2 = _a_mul(t2, C)
    t4b = _b3_mul(t4, C)
    t1 = F.add(t1, t2)
    t2 = _a_mul(F.sub(t0, t2), C)
    t4b = F.add(t4b, t2)
    t0 = F.mul(t1, t4b)
    y3 = F.add(y3, t0)
    t0 = F.mul(t5, t4b)
    x3 = F.mul(t3, x3)
    x3 = F.sub(x3, t0)
    t0 = F.mul(t3, t1)
    z3 = F.mul(t5, z3)
    z3 = F.add(z3, t0)
    return x3, y3, z3


def pt_add_mixed(P, A, C: CurveOps):
    """Complete mixed addition with affine A = (x2, y2), Z2 = 1 — A must be
    a genuine curve point (never identity; comb-table entries qualify).
    a = 0: RCB alg 8 (11M + 2·b3); generic: alg 2."""
    X1, Y1, Z1 = P
    X2, Y2 = A
    F = C.F
    if C.a_is_zero:
        t0 = F.mul(X1, X2)
        t1 = F.mul(Y1, Y2)
        t3 = F.mul(F.add(X2, Y2), F.add(X1, Y1))
        t3 = F.sub(t3, F.add(t0, t1))  # X1Y2 + X2Y1
        t4 = F.add(F.mul(X2, Z1), X1)  # X1 + X2Z1
        t5 = F.add(F.mul(Y2, Z1), Y1)  # Y1 + Y2Z1
        x3 = F.add(t0, t0)
        t0 = F.add(x3, t0)  # 3·X1X2
        t2 = _b3_mul(Z1, C)
        z3 = F.add(t1, t2)
        t1 = F.sub(t1, t2)
        y3 = _b3_mul(t4, C)
        x3 = F.mul(t5, y3)
        t2 = F.mul(t3, t1)
        x3 = F.sub(t2, x3)
        y3 = F.mul(y3, t0)
        t1 = F.mul(t1, z3)
        y3 = F.add(t1, y3)
        t0 = F.mul(t0, t3)
        z3 = F.mul(z3, t5)
        z3 = F.add(z3, t0)
        return x3, y3, z3
    t0 = F.mul(X1, X2)
    t1 = F.mul(Y1, Y2)
    t3 = F.mul(F.add(X2, Y2), F.add(X1, Y1))
    t3 = F.sub(t3, F.add(t0, t1))  # X1Y2 + X2Y1
    t4 = F.add(F.mul(X2, Z1), X1)  # X1 + X2Z1
    t5 = F.add(F.mul(Y2, Z1), Y1)  # Y1 + Y2Z1
    z3 = _a_mul(t4, C)
    x3 = _b3_mul(Z1, C)
    z3 = F.add(x3, z3)
    x3 = F.sub(t1, z3)
    z3 = F.add(t1, z3)
    y3 = F.mul(x3, z3)
    t1 = F.add(t0, t0)
    t1 = F.add(t1, t0)  # 3·X1X2
    t2 = _a_mul(Z1, C)
    t4b = _b3_mul(t4, C)
    t1 = F.add(t1, t2)
    t2 = _a_mul(F.sub(t0, t2), C)
    t4b = F.add(t4b, t2)
    t0 = F.mul(t1, t4b)
    y3 = F.add(y3, t0)
    t0 = F.mul(t5, t4b)
    x3 = F.mul(t3, x3)
    x3 = F.sub(x3, t0)
    t0 = F.mul(t3, t1)
    z3 = F.mul(t5, z3)
    z3 = F.add(z3, t0)
    return x3, y3, z3


def pt_double(P, C: CurveOps):
    """Complete doubling. a = 0: RCB alg 9 (6M + 2S + 1·b3); generic:
    alg 3."""
    X, Y, Z = P
    F = C.F
    if C.a_is_zero:
        t0 = F.sqr(Y)
        z3 = F.add(t0, t0)
        z3 = F.add(z3, z3)
        z3 = F.add(z3, z3)  # 8·Y^2
        t1 = F.mul(Y, Z)
        t2 = F.sqr(Z)
        t2 = _b3_mul(t2, C)
        x3 = F.mul(t2, z3)
        y3 = F.add(t0, t2)
        z3 = F.mul(t1, z3)
        t1 = F.add(t2, t2)
        t2 = F.add(t1, t2)  # 3·b3·Z^2
        t0 = F.sub(t0, t2)
        y3 = F.mul(t0, y3)
        y3 = F.add(x3, y3)
        t1 = F.mul(X, Y)
        x3 = F.mul(t0, t1)
        x3 = F.add(x3, x3)
        return x3, y3, z3
    t0 = F.sqr(X)
    t1 = F.sqr(Y)
    t2 = F.sqr(Z)
    t3 = F.mul(X, Y)
    t3 = F.add(t3, t3)
    z3 = F.mul(X, Z)
    z3 = F.add(z3, z3)
    x3 = _a_mul(z3, C)
    y3 = _b3_mul(t2, C)
    y3 = F.add(x3, y3)
    x3 = F.sub(t1, y3)
    y3 = F.add(t1, y3)
    y3 = F.mul(x3, y3)
    x3 = F.mul(t3, x3)
    z3 = _b3_mul(z3, C)
    t2a = _a_mul(t2, C)
    t3 = _a_mul(F.sub(t0, t2a), C)
    t3 = F.add(t3, z3)
    z3 = F.add(t0, t0)
    t0 = F.add(z3, t0)
    t0 = F.add(t0, t2a)
    t0 = F.mul(t0, t3)
    y3 = F.add(y3, t0)
    t2 = F.mul(Y, Z)
    t2 = F.add(t2, t2)
    t0 = F.mul(t2, t3)
    x3 = F.sub(x3, t0)
    z3 = F.mul(t2, t1)
    z3 = F.add(z3, z3)
    z3 = F.add(z3, z3)
    return x3, y3, z3


def pt_infinity(like: jax.Array, C: CurveOps):
    """Projective identity (0 : 1 : 0) — Y must be the field's one (the
    complete formulas READ it, unlike the Jacobian law's placeholder)."""
    z = jnp.zeros_like(like)
    return z, C.F.one(like), z


def pt_to_affine(P, C: CurveOps):
    """(X : Y : Z) -> (x, y, inf_mask); affine coords stay in the field
    domain. Identity lanes get x = y = 0 (F.inv(0) == 0)."""
    X, Y, Z = P
    F = C.F
    zinv = F.inv(Z)
    return F.mul(X, zinv), F.mul(Y, zinv), is_zero(Z)


def on_curve(x_enc: jax.Array, y_enc: jax.Array, C: CurveOps) -> jax.Array:
    """y^2 == x^3 + a*x + b (field domain) -> bool[T]."""
    F = C.F
    rhs = F.mul(F.sqr(x_enc), x_enc)
    if not C.a_is_zero:
        rhs = F.add(rhs, F.mul(const_rows(C.a_enc, x_enc), x_enc))
    rhs = F.add(rhs, const_rows(C.b_enc, x_enc))
    return eq(F.sqr(y_enc), rhs)


# ---------------------------------------------------------------------------
# Scalar-range helpers (plain-domain limbs)
# ---------------------------------------------------------------------------


def valid_scalar(x: jax.Array, C: CurveOps) -> jax.Array:
    """1 <= x < n (signature component range check)."""
    return ~is_zero(x) & lt(x, const_rows(C.n_limbs, x))


def reduce_mod_n(z: jax.Array, C: CurveOps) -> jax.Array:
    """z mod n for z < 2n (single conditional subtract; n > 2^255 for both
    curves, so any 256-bit z qualifies)."""
    return limb.cond_sub(z, C.n_limbs)


def add_mod_n(a: jax.Array, b: jax.Array, C: CurveOps) -> jax.Array:
    """(a + b) mod n for plain a, b < n (no field object needed)."""
    return limb.cond_sub(limb.add_widen(a, b), C.n_limbs)


# ---------------------------------------------------------------------------
# Fixed-base comb table for G (host-precomputed from curve constants)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def g_comb_table(name: str) -> np.ndarray:
    """[30, 16] uint32: field-domain affine coordinates of c * G for window
    value c in 1..15 — rows 0..14 hold the x coordinates, rows 15..29 the y
    coordinates (the 30-row leading axis keeps the 16-limb axis off the TPU
    lane dimension).

    G is a compile-time constant, so its window table is precomputed on the
    host in affine form — the ladder adds G contributions with cheap mixed
    (Z=1) additions and no runtime table build. The table is
    position-independent: in the MSB-first shared-doubling ladder each
    window's contribution picks up its 2^(4i) factor from the remaining
    doublings, exactly like the Q term."""
    C = {SECP256K1_OPS.name: SECP256K1_OPS, SM2_OPS.name: SM2_OPS}[name]
    c = C.curve
    tab = np.zeros((30, limb.LIMBS), dtype=np.uint32)
    acc = None
    for k in range(1, 16):
        acc = point_add(c, acc, (c.gx, c.gy))
        assert acc is not None  # k*G is never infinity (k < n)
        tab[k - 1] = C.F.enc(acc[0])
        tab[15 + k - 1] = C.F.enc(acc[1])
    return tab


def scalar_windows(k: jax.Array) -> jax.Array:
    """[16, T] plain limbs -> [64, T] 4-bit windows, LSB-first order (the
    window precompute the ladders scan over)."""
    rep = jnp.repeat(k, 16 // WINDOW, axis=0)  # [64, ...]
    shifts = limb.dev_vec((np.arange(N_WINDOWS) % (16 // WINDOW)) * WINDOW)
    return (rep >> shifts.reshape((-1,) + (1,) * (k.ndim - 1))) & np.uint32(0xF)


def _point_table_scan(t1, C: CurveOps):
    """Window table of k*P for k = 1..15 as three stacked [15, 16, T] arrays
    via a 14-step scan (compact HLO: fast CPU compiles)."""

    def step(prev, _):
        nxt = pt_add(prev, t1, C)
        return nxt, nxt

    _, rest = lax.scan(step, t1, None, length=14)
    tq_x = jnp.concatenate([t1[0][None], rest[0]], axis=0)
    tq_y = jnp.concatenate([t1[1][None], rest[1]], axis=0)
    tq_z = jnp.concatenate([t1[2][None], rest[2]], axis=0)
    return tq_x, tq_y, tq_z


def _per_lane(tab: jax.Array, w: jax.Array) -> jax.Array:
    """[15, 16] table of constants -> [15, 16, 1, ...]: broadcastable against
    limb arrays whose batch dimensions are those of the window array w."""
    return tab.reshape(tab.shape + (1,) * w.ndim)


def _select15(tab, w: jax.Array):
    """tab: 15 entries (list of arrays/tuples, or a [15, ..., T] stacked
    array), w [T] in 0..15 -> tab[w-1] (w==0 lanes get tab[0], callers must
    mask). 15-way masked chain — branch-free."""
    sel = tab[0]
    for c in range(2, 16):
        sel = select(w == c, tab[c - 1], sel)
    return sel


def dual_mul_windowed(k1, k2, Q, C: CurveOps, g_table: jax.Array):
    """k1*G + k2*Q — the ECDSA/SM2 verification kernel.

    k1, k2: [16, T] plain-domain scalars (< n); Q: (x, y) field-domain affine
    (not infinity; garbage lanes are fine — callers mask validity).
    g_table: device copy of :func:`g_comb_table` ([30, 16]).

    Schedule: 64 window steps, each 4 doublings + one full addition (runtime
    Q table) + one mixed addition (affine G table), all lane-uniform.
    """
    F = C.F
    one = F.one(k1)
    t1 = (Q[0], Q[1], one)
    acc0 = pt_infinity(k1, C)

    tq_x, tq_y, tq_z = _point_table_scan(t1, C)
    w1 = scalar_windows(k1)[::-1]  # MSB-first [64, T]
    w2 = scalar_windows(k2)[::-1]

    def sstep(acc, xs):
        w1_i, w2_i = xs
        for _ in range(WINDOW):
            acc = pt_double(acc, C)
        added = pt_add(
            acc, (_select15(tq_x, w2_i), _select15(tq_y, w2_i), _select15(tq_z, w2_i)), C
        )
        acc = select(w2_i == 0, acc, added)
        gx = _select15(_per_lane(g_table[:15], w1_i), w1_i)  # [16, ...]
        gy = _select15(_per_lane(g_table[15:], w1_i), w1_i)
        madded = pt_add_mixed(acc, (gx, gy), C)
        acc = select(w1_i == 0, acc, madded)
        return acc, None

    acc, _ = lax.scan(sstep, acc0, (w1, w2))
    return acc


# ---------------------------------------------------------------------------
# Batched inversion (Montgomery's trick along the lane axis)
# ---------------------------------------------------------------------------


def lane_inv(F, x: jax.Array) -> jax.Array:
    """Elementwise modular inverse of [16, T] via ONE Fermat exponentiation.

    Montgomery's trick as a log-depth halving tree over the lane axis: the
    up-sweep multiplies lane halves pairwise to the running product, one
    exponentiation inverts the [16, 1] root, and the down-sweep pushes
    inverses back out. ~2 muls/lane replaces a ~320-op exponentiation per
    lane — the inverse is unique mod m, so the result is bit-identical to
    ``F.inv`` per lane (0 maps to 0, as Fermat gives). T is padded to a
    power of two with ones.
    """
    shape = x.shape
    x = x.reshape(shape[0], -1)  # the tree halves the lanes: one flat axis
    t = x.shape[1]
    nz = ~is_zero(x)
    cur = select(nz, x, F.one(x))
    pw = 1 << max(0, (t - 1).bit_length())
    if pw != t:
        cur = jnp.concatenate(
            [cur, jnp.tile(F.one(x)[:, :1], (1, pw - t))], axis=1
        )
    stack = []
    while cur.shape[1] > 1:
        h = cur.shape[1] // 2
        a, b = cur[:, :h], cur[:, h:]
        stack.append((a, b))
        cur = F.mul(a, b)
    inv = F.inv(cur)  # the only exponentiation
    for a, b in reversed(stack):
        inv = jnp.concatenate([F.mul(inv, b), F.mul(inv, a)], axis=1)
    if pw != t:
        inv = inv[:, :t]
    return select(nz, inv, jnp.zeros_like(x)).reshape(shape)


def pt_to_affine_batch(P, C: CurveOps):
    """:func:`pt_to_affine` with the Z inversion batched across lanes
    (bit-identical output — the inverse is unique)."""
    X, Y, Z = P
    F = C.F
    zinv = lane_inv(F, Z)
    return F.mul(X, zinv), F.mul(Y, zinv), is_zero(Z)


# ---------------------------------------------------------------------------
# GLV endomorphism (secp256k1): u1*G + u2*Q with a half-length ladder
# ---------------------------------------------------------------------------

# secp256k1 has the efficient endomorphism φ(x, y) = (βx, y) = λ·(x, y)
# (β³ = 1 mod p, λ³ = 1 mod n). Splitting u2 = ka + kb·λ with |ka|, |kb| ~
# 2^128 and u1 positionally into 128-bit halves (against comb tables for G
# and 2^128·G) shortens the shared doubling chain 64 -> 33 windows: 132
# doublings + 132 adds instead of 256 + 128. The reference's wedpr secp
# backend gets the same win from libsecp256k1's split_lambda; here it is
# what makes the north-star ≥10x reachable on the VPU-issue-bound kernel.

_SECP_LAMBDA = 0x5363AD4CC05C30E0A5261C028812645A122E22EA20816678DF02967C1B23BD72
_SECP_BETA = 0x7AE96A2B657C07106E64479EAC3434E99CF0497512F58995C1396C28719501EE

N_QWINDOWS = 33  # ceil(131 / WINDOW) + guard: |ka|, |kb| < 2^131


def _glv_basis(n: int, lam: int) -> tuple[int, int, int, int]:
    """Short lattice basis (a1, b1), (a2, b2) with a + b·λ ≡ 0 (mod n),
    via the GLV partial extended Euclid (half-GCD stop at √n)."""
    rows = [(n, 0), (lam, 1)]  # r ≡ t·λ (mod n)
    while rows[-1][0] * rows[-1][0] >= n:
        q = rows[-2][0] // rows[-1][0]
        rows.append((rows[-2][0] - q * rows[-1][0], rows[-2][1] - q * rows[-1][1]))
    r1, t1 = rows[-1]
    r0, t0 = rows[-2]
    q = r0 // r1
    r2, t2 = r0 - q * r1, t0 - q * t1
    v1 = (r1, -t1)
    v2 = (r0, -t0) if r0 * r0 + t0 * t0 <= r2 * r2 + t2 * t2 else (r2, -t2)
    (a1, b1), (a2, b2) = v1, v2
    # device code assumes b1 < 0 < b2 (then both rounding coefficients are
    # non-negative); euclid remainders keep a1, a2 > 0 and the t signs
    # alternate, so a swap always suffices
    if b1 > 0:
        (a1, b1), (a2, b2) = (a2, b2), (a1, b1)
    assert a1 > 0 and a2 > 0 and b1 < 0 and b2 > 0
    assert (a1 + b1 * lam) % n == 0 and (a2 + b2 * lam) % n == 0
    return a1, b1, a2, b2


@dataclass(frozen=True)
class _GlvParams:
    beta_enc: np.ndarray
    g1: np.ndarray  # floor(b2 * 2^448 / n), 16-bit limbs
    g2: np.ndarray  # floor(-b1 * 2^448 / n)
    a1: np.ndarray
    b1_abs: np.ndarray
    a2: np.ndarray
    b2: np.ndarray


@lru_cache(maxsize=None)
def glv_params(name: str) -> _GlvParams:
    C = {SECP256K1_OPS.name: SECP256K1_OPS}[name]
    n = C.curve.n
    lam, beta = _SECP_LAMBDA, _SECP_BETA
    # pick the (λ, β) pairing that realises φ(x, y) = (βx, y) on this curve
    gx, gy = C.curve.gx, C.curve.gy
    lx, ly = point_mul(C.curve, lam, (gx, gy))
    assert ly == gy
    if lx != beta * gx % C.curve.p:
        beta = beta * beta % C.curve.p
        assert lx == beta * gx % C.curve.p
    a1, b1, a2, b2 = _glv_basis(n, lam)

    def limbs(v: int, w: int) -> np.ndarray:
        return limb.int_to_rows(v, w)

    return _GlvParams(
        beta_enc=C.F.enc(beta),
        g1=limbs(b2 * (1 << 448) // n, 21),
        g2=limbs(-b1 * (1 << 448) // n, 21),
        a1=limbs(a1, 9),
        b1_abs=limbs(-b1, 9),
        a2=limbs(a2, 9),
        b2=limbs(b2, 9),
    )


def _shr_limbs(x: jax.Array, drop: int, keep: int) -> jax.Array:
    """Static right-shift by whole limbs: rows drop..drop+keep of [L, T]."""
    return lax.slice_in_dim(x, drop, drop + keep, axis=0)


def _mul_c(x: jax.Array, c_limbs: np.ndarray, out: int) -> jax.Array:
    return limb.carry_norm(limb.mul_const_cols(x, c_limbs, out))[:out]


def _abs_diff(
    a: jax.Array, b: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(|a - b| limbs, sign) for equal-width normalized a, b."""
    d1, borrow = sub_borrow(a, b)
    d2, _ = sub_borrow(b, a)
    return select(borrow, d2, d1), borrow


def glv_decompose(u2: jax.Array, C: CurveOps):
    """u2 [16, T] plain < n -> (ka, sa, kb, sb) with
    u2 ≡ (-1)^sa·ka + (-1)^sb·kb·λ (mod n) and ka, kb < 2^131.

    Rounding is plain floor Barrett (error ≤ 2 per coefficient — the
    congruence holds for ANY rounding, slop only costs ladder-bound bits,
    and N_QWINDOWS covers it)."""
    P = glv_params(C.name)
    # c_i = floor(u2 * g_i / 2^448): 16x21-limb product, drop 28 limbs
    c1 = _shr_limbs(_mul_c(u2, P.g1, 37), 28, 9)
    c2 = _shr_limbs(_mul_c(u2, P.g2, 37), 28, 9)
    # ka = u2 - c1*a1 - c2*a2 (signed)
    s_a = limb.add_widen(_mul_c(c1, P.a1, 17), _mul_c(c2, P.a2, 17))  # 18 limbs
    u2p = limb._placed(u2, 0, 18)
    ka, sa = _abs_diff(u2p, s_a)
    # kb = c1*|b1| - c2*b2 (signed)
    kb, sb = _abs_diff(_mul_c(c1, P.b1_abs, 17), _mul_c(c2, P.b2, 17))
    return ka[:16], sa, kb[:16], sb


@lru_cache(maxsize=None)
def g_comb_table_glv(name: str) -> np.ndarray:
    """[60, 16] uint32: the :func:`g_comb_table` layout for G (rows 0..29)
    stacked with the same table for H = 2^128·G (rows 30..59) — the
    fixed-base combs for the positionally split u1 in the GLV ladder."""
    C = {SECP256K1_OPS.name: SECP256K1_OPS}[name]
    c = C.curve
    h = point_mul(c, 1 << 128, (c.gx, c.gy))
    tab = np.zeros((60, limb.LIMBS), dtype=np.uint32)
    tab[:30] = g_comb_table(name)
    acc = None
    for k in range(1, 16):
        acc = point_add(c, acc, h)
        assert acc is not None
        tab[30 + k - 1] = C.F.enc(acc[0])
        tab[45 + k - 1] = C.F.enc(acc[1])
    return tab


def _split_u1(u1: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[16, ...] -> 128-bit halves, each widened back to [16, ...]."""
    lo = limb._placed(lax.slice_in_dim(u1, 0, 8, axis=0), 0, 16)
    hi = limb._placed(lax.slice_in_dim(u1, 8, 16, axis=0), 0, 16)
    return lo, hi


def quad_mul_windowed(
    u1: jax.Array,
    ka: jax.Array,
    sa: jax.Array,
    kb: jax.Array,
    sb: jax.Array,
    Q,
    C: CurveOps,
    g_table2: jax.Array,
):
    """u1*G + (-1)^sa·ka*Q + (-1)^sb·kb*(λQ) — the GLV ECDSA kernel.

    u1: [16, T] plain scalar (< n), split positionally against the G /
    2^128·G combs; (ka, sa, kb, sb) from :func:`glv_decompose`;
    Q: field-domain affine; g_table2: :func:`g_comb_table_glv` on device.

    33 window steps of 4 doublings + 2 complete adds (runtime Q table and
    its on-the-fly β-scaled λQ view) + 2 mixed adds (G combs).
    """
    F = C.F
    P = glv_params(C.name)
    one = F.one(u1)
    t1 = (Q[0], Q[1], one)
    acc0 = pt_infinity(u1, C)
    u1lo, u1hi = _split_u1(u1)
    beta_c = const_rows(P.beta_enc, Q[0])

    ta_x, ta_y, ta_z = _point_table_scan(t1, C)
    tb_x = jnp.stack([F.mul(ta_x[i], beta_c) for i in range(15)], axis=0)
    wins = [
        scalar_windows(k)[:N_QWINDOWS][::-1]
        for k in (ka, kb, u1lo, u1hi)
    ]

    def sstep(acc, xs):
        wa, wb, wlo, whi = xs
        for _ in range(WINDOW):
            acc = pt_double(acc, C)
        ya = _select15(ta_y, wa)
        ya = select(sa, F.neg(ya), ya)
        added = pt_add(acc, (_select15(ta_x, wa), ya, _select15(ta_z, wa)), C)
        acc = select(wa == 0, acc, added)
        yb = _select15(ta_y, wb)
        yb = select(sb, F.neg(yb), yb)
        added = pt_add(acc, (_select15(tb_x, wb), yb, _select15(ta_z, wb)), C)
        acc = select(wb == 0, acc, added)
        for w, base in ((wlo, 0), (whi, 30)):
            gx = _select15(_per_lane(g_table2[base : base + 15], w), w)
            gy = _select15(_per_lane(g_table2[base + 15 : base + 30], w), w)
            madded = pt_add_mixed(acc, (gx, gy), C)
            acc = select(w == 0, acc, madded)
        return acc, None

    acc, _ = lax.scan(sstep, acc0, tuple(wins))
    return acc


def scalar_mul(k, P, C: CurveOps):
    """k*P for field-domain affine P — windowed, no G-comb (generic point).

    Used by tests and non-hot paths; the hot kernels go through
    :func:`dual_mul_windowed`."""
    F = C.F
    one = F.one(k)
    t1 = (P[0], P[1], one)

    tq_x, tq_y, tq_z = _point_table_scan(t1, C)
    w = scalar_windows(k)[::-1]

    def sstep(acc, w_i):
        for _ in range(WINDOW):
            acc = pt_double(acc, C)
        added = pt_add(
            acc, (_select15(tq_x, w_i), _select15(tq_y, w_i), _select15(tq_z, w_i)), C
        )
        return select(w_i == 0, acc, added), None

    acc, _ = lax.scan(sstep, pt_infinity(k, C), w)
    return acc


def generator_affine(C: CurveOps, like: jax.Array):
    """The curve generator (field domain) broadcast over T."""
    return (
        const_rows(C.F.enc(C.curve.gx), like),
        const_rows(C.F.enc(C.curve.gy), like),
    )


# Re-exported plain-limb helpers used by the signature kernels
__all__ = [
    "CurveOps",
    "SECP256K1_OPS",
    "SM2_OPS",
    "pt_double",
    "pt_add",
    "pt_add_mixed",
    "pt_infinity",
    "pt_to_affine",
    "on_curve",
    "valid_scalar",
    "reduce_mod_n",
    "add_mod_n",
    "g_comb_table",
    "g_comb_table_glv",
    "glv_decompose",
    "glv_params",
    "lane_inv",
    "pt_to_affine_batch",
    "quad_mul_windowed",
    "dual_mul_windowed",
    "scalar_mul",
    "generator_affine",
    "eq",
    "is_zero",
    "lt",
    "select",
    "sub_borrow",
]
