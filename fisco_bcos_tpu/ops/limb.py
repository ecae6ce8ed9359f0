"""256-bit modular arithmetic with the limb index off the tiled axes — the
TPU-native bignum core.

Replaces the reference's CPU bignum (wedpr-crypto Rust FFI / OpenSSL BN behind
bcos-crypto's secp256k1/SM2 paths) with a formulation shaped for the TPU VPU:

- A 256-bit number is 16 little-endian 16-bit limbs in a uint32 array whose
  **leading axis is the limb index** and whose trailing dimensions are the
  batch. The secp256k1 and SM2 entry points make the batch **lane-dense**
  (:func:`lane_dense`, which pads it to a multiple of 128 lanes): over 1,024
  lanes it becomes ``[L, S, 128]`` with ``S = lanes / 128``, so the batch
  fills both tiled dimensions (sublanes × lanes) and the limb index is on
  neither; up to 1,024 lanes it stays ``[L, T]``, limbs sharing vregs along
  the sublanes. Which form, and how many lanes at a time, is one measured
  rule, :func:`lane_plan` (its table is the chip's, PR 39: ``[L, S, 128]``
  is cheap an op up to 2,048 lanes and three times dearer a call at 2,560,
  so the 2,560 lanes of a four-chip host's shard run as two tiles). Every
  function here is written against ``[L, ...]`` and never looks at the
  trailing shape; programs that have not moved yet (ed25519, BLS12-381,
  Poseidon, bn128) still pass ``[L, T]``.
- Taking a limb, shifting by limbs, placing a partial product at a column,
  dropping the top limb and widening by a zero limb are each written as ONE
  ``lax.pad`` (:func:`_shift_limbs`; negative padding drops), never slice +
  concatenate: address arithmetic on an untiled axis in ``[L, S, 128]``, a
  sublane shift inside the consuming fusion in ``[L, T]``. The pads and the
  carry lookahead below are most of what PR 25 gained (394 -> 167 ms at
  10,240 lanes in ``[L, T]`` alone); the layout adds the rest (-> 149 ms). History, because two layouts were called "full utilisation"
  before a chip said otherwise: ``[B, 16]`` put the limbs in the lanes
  (12.5 %); ``[16, T]`` put them in the sublanes and built every shift and
  carry from slices and concatenates at sublane offsets, and the chip ran a
  1,024-lane admission call as 483,211 device ops — 175,505 of them bare
  slices, 99,400 the levels of the Kogge–Stone carry networks, 10,175 the
  fusions that multiply (PERF.md §6, PR 25).
- Multiplication is 16 unrolled limb MACs with 16-bit lo/hi splitting (every
  partial product and column sum stays inside uint32); there are no matmuls
  — int32 matmul does not map to the MXU.
- Carry propagation is a carry-lookahead by ONE machine addition
  (:func:`_carry_in`): generate and propagate bits are packed along the limb
  axis into 32-bit words, and ``(G | P) + G`` ripples through propagate runs
  in the adder. Never a sequential scan over limbs, and no longer a
  log-depth network whose every level was a device op of its own. It runs
  only where canonical limbs are consumed — a product of two variables, a
  comparison, a field operation's result: between a product and its last
  fold the limbs stay loose (:func:`carry_loose`: split passes alone, their
  bound carried statically), which the constant multiplications and column
  additions there take as well (PERF.md §6, PR 27).
- Modular reduction is **pseudo-Mersenne folding** (``FoldField``) for
  moduli of the form 2^256 − c with small c — secp256k1's p and n both
  qualify — and word Montgomery (``MontField``) for arbitrary odd moduli
  (SM2). REDC's two multiplications by constants (m' and m) are signed sums
  of shifted rows where the modulus makes both constants short in signed
  powers of two (SM2's p: seven and five terms, so a field multiplication is
  one limb product; :func:`make_mont_field` reads this off the modulus), and
  limb products for a dense modulus (SM2's n, BN254's Fr). Both fields
  present the same field-ops protocol so the EC layer in
  :mod:`fisco_bcos_tpu.ops.ec` is generic over them.

Everything here is plain ``jnp`` on values; integer semantics make every
backend bit-identical by construction, which is what consensus code requires.

Host-side byte/int conversions stay in :mod:`fisco_bcos_tpu.ops.bigint`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LIMBS = 16
LIMB_BITS = 16
# numpy scalar, not jnp: it stays a jaxpr literal, never a captured constant
_MASK = np.uint32(0xFFFF)
_R = 1 << 256
_ONES = (_R - 1) // 0xFFFF  # Σ 2^(16k), k < 16: 16 limbs ≤ b hold at most b · _ONES


def int_to_rows(x: int, width: int = LIMBS) -> np.ndarray:
    """Python int -> [width] uint32 little-endian 16-bit limbs."""
    if not 0 <= x < 1 << (LIMB_BITS * width):
        raise ValueError("int_to_rows: out of range")
    return np.array(
        [(x >> (LIMB_BITS * i)) & 0xFFFF for i in range(width)], dtype=np.uint32
    )


def rows_to_ints(a) -> list[int]:
    """[L, T] limbs -> list of T Python ints (host-side, for tests)."""
    a = np.asarray(a)
    a = a.reshape(a.shape[0], -1)
    return [
        sum(int(a[i, j]) << (LIMB_BITS * i) for i in range(a.shape[0]))
        for j in range(a.shape[1])
    ]


def dev_vec(arr, dtype=jnp.uint32) -> jax.Array:
    """1-D host constant -> device vector assembled from scalar constants
    (XLA constant-folds the stack)."""
    return jnp.stack([jnp.array(int(v), dtype) for v in arr])


def const_rows(limbs_np: np.ndarray, t: int | jax.Array) -> jax.Array:
    """[L] host constant -> [L, ...] broadcast (the batch dimensions from an
    int T or from a like-array's trailing dimensions): one embedded constant
    + one broadcast."""
    tail = (t,) if isinstance(t, int) else t.shape[1:]
    arr = np.asarray(limbs_np, dtype=np.uint32)
    return jnp.broadcast_to(
        jnp.asarray(arr).reshape((-1,) + (1,) * len(tail)), (arr.shape[0],) + tail
    )


# ---------------------------------------------------------------------------
# Entry conversions: batch-major [B, L] <-> lane-dense [L, S, 128]
# ---------------------------------------------------------------------------

LANES = 128  # the minor tiled dimension: one vreg row


_ROWS_MAX = 8 * LANES  # a limb is one vreg (8 sublanes x 128 lanes): the most lanes of [L, T]
_WHOLE_MAX = 2048  # the most lanes [L, S, 128] runs whole at the low price an op
_TILE_MAX = 1280  # the most lanes of a tile that runs at that price inside a loop


@dataclass(frozen=True)
class LanePlan:
    """How a body runs a batch of lanes: `tile` lanes at a time, each tile in
    the form `dense` says (``[L, S, 128]``, else ``[L, T]``)."""

    tile: int
    dense: bool

    def tiles(self, lanes: int) -> int:
        return -(-lanes // self.tile)


def _padded(lanes: int) -> int:
    return lanes + (-lanes % LANES)


def _whole_dense(lanes: int) -> bool:
    """The form of `lanes` (padded) lanes run as one block, one half of
    :func:`lane_plan`'s rule: ``[L, S, 128]`` over 1,024 lanes, else
    ``[L, T]``."""
    return lanes > _ROWS_MAX


def whole_plan(lanes: int) -> LanePlan:
    """The plan of a body that runs every batch as one block: one tile, in the
    form :func:`lane_plan`'s table gives that many lanes."""
    return LanePlan(lanes, _whole_dense(_padded(lanes)))


def lane_plan(lanes: int) -> LanePlan:
    """The one rule of the shape the limb arithmetic runs at, a pure function
    of a batch's lane count: how many lanes at a time (the tile; the fused
    secp256k1 admission body loops over the tiles inside its one program,
    ``crypto/admission._in_tiles``) and in which form (:func:`lane_dense`
    asks for the form of the lanes it is handed, a tile's or a whole
    batch's).

    - Up to 1,024 lanes: whole, ``[L, T]``, limbs sharing vregs along the
      sublanes. Over 1,024 lanes the form is ``[L, S, 128]``: the batch fills
      both tiled dimensions and the limb index is the leading, untiled axis.
    - Over 2,048 and up to 2,560 lanes (the 2,560 lanes a chip of a four-chip
      host is given of a 10,240-lane bucket): two equal tiles of at most
      1,280 lanes, a multiple of 128, each in ``[L, S, 128]``. Every other
      batch runs whole.

    The rule is the secp256k1 admission program's device time a call on one
    TPU v5e chip ("TPU v5 lite"), 2026-10-01, ``tool/admission_op_profile.py``
    with the device's ops line on (PERF.md §6, PR 39; 512 and 1,024 lanes
    are PR 27's profile, 10,240 the ledger's), ms:

    ====== ========== =============== ====================================
    lanes  ``[L, T]`` ``[L, S, 128]`` in tiles, one program
    ====== ========== =============== ====================================
    512    16.79      —               —
    1,024  19.61      —               —
    1,280  46.05      22.65           —
    1,536  59.61      22.69           —
    2,048  58.73      25.56           —
    2,560  76.69      74.37           **2 x 1,280 [L, S, 128] 45.37**;
                                      3 x 1,024 [L, T] 60.92;
                                      2 x 1,280 [L, T] 94.77
    4,096  86.35      73.38           2 x 2,048 [L, S, 128] 105.62
    6,144  —          93.67           3 x 2,048 [L, S, 128] 158.67
    8,192  —          99.54           —
    10,240 —          107.15          5 x 2,048 [L, S, 128] 264.56
    ====== ========== =============== ====================================

    A lane count takes the fastest measured row of the bucket it is: tiles
    only where a measured row won (2,560 lanes), whole wherever the table has
    no faster row, the sizes between the measured ones included (3,072 and
    5,120 lanes, a mesh of two's shares, were not measured and stay whole). A
    tile of 2,048 lanes costs 25.6 ms as a program of its own and 52.9 inside
    the loop, so no bucket over 2,560 lanes has a plan that beats it whole;
    three tiles of 1,024 lanes in ``[L, T]`` cost what three 1,024-lane calls
    cost. The SM2 body runs :func:`whole_plan` at every size. While its REDC
    ran three limb products a multiplication it had no cheap size at all
    (1,024 lanes 119.5 ms, 1,280 140.3, 2,560 whole 213.5, two tiles of 1,280
    261.8, 4,096 245.9, 10,240 339.6); with REDC's two constants as shifted
    rows (PR 46, PERF.md §6) the same body is cheap an op at every size
    measured, whole: 1,024 lanes 23.2 ms, 4,096 46.7, 10,240 95.0 (188,745 to
    223,569 device ops a call, where it was 240,945 to 269,898); no tiled
    plan of it has been timed.

    Not understood: the edge itself. Much the same device ops run at every
    size (114,000 to 125,000 a call), and past 1,024 lanes in ``[L, T]``, or
    2,048 in ``[L, S, 128]`` (1,280 inside a loop), the same op kinds cost up
    to thirty times more an op (``pad_shift-right-logical_fusion`` 0.46 ms a
    call at 1,280 lanes, 14.97 at 2,560), while the compiled programs assign
    their buffers to memory spaces in the same proportions on either side."""
    padded = _padded(lanes)
    if not _WHOLE_MAX < padded <= 2 * _TILE_MAX:
        return whole_plan(lanes)
    return whole_plan(-(-padded // (2 * LANES)) * LANES)


def lane_dense(x: jax.Array) -> jax.Array:
    """Batch-major [B, L] limbs -> limb-leading, the batch padded to a
    multiple of 128 lanes. The one relayout on the way in; the form is
    :func:`lane_plan`'s for this many lanes run as one block. Padding lanes
    hold zero: an invalid scalar, so they lower their validity bit like any
    other bad lane and never raise."""
    b, width = x.shape
    t = _padded(b)
    xt = jnp.pad(x.T, ((0, 0), (0, t - b)))
    return xt.reshape(width, -1, LANES) if _whole_dense(t) else xt


def lane_mask(v: jax.Array, like: jax.Array) -> jax.Array:
    """[B] per-lane vector -> the batch shape of `like` (a :func:`lane_dense`
    limb array), zero in the padding lanes."""
    shape = like.shape[1:]
    return jnp.pad(v, (0, int(np.prod(shape)) - v.shape[0])).reshape(shape)


def batch_major(x: jax.Array, b: int) -> jax.Array:
    """Limb-leading [L, ...] -> [B, L]: the one relayout on the way out,
    padding lanes dropped."""
    return x.reshape(x.shape[0], -1).T[:b]


def batch_lanes(mask: jax.Array, b: int) -> jax.Array:
    """A per-lane result of batch shape [...] -> [B], padding lanes dropped."""
    return mask.reshape(-1)[:b]


# ---------------------------------------------------------------------------
# Carry machinery (lookahead along the limb axis = axis 0)
# ---------------------------------------------------------------------------


def _shift_limbs(x: jax.Array, k: int, fill: int = 0, grow: int = 0) -> jax.Array:
    """[L, ...] -> [L + grow, ...] shifted k limbs toward the high end along
    the leading axis: `fill` enters below, the top k - grow limbs drop off.

    ONE ``lax.pad`` (negative high padding drops), never slice + concatenate:
    the limb axis is the leading, untiled axis of a lane-dense ``[L, S, 128]``
    batch, so the shift is address arithmetic inside whatever fusion consumes
    it. The concatenate form ended a fusion at every shift, and its slices
    ran as device ops of their own (PERF.md §6, PR 25)."""
    cfg = [(k, grow - k, 0)] + [(0, 0, 0)] * (x.ndim - 1)
    return lax.pad(x, jnp.array(fill, x.dtype), cfg)


def _shift_up(x: jax.Array) -> jax.Array:
    """[L, ...] -> [L, ...] shifted one limb toward the high end (axis 0)."""
    return _shift_limbs(x, 1)


def row(x: jax.Array, i: int) -> jax.Array:
    """Static row i of [L, ...] -> [...] via a static slice + squeeze
    (``x[i]`` lowers through dynamic_slice even for a constant index)."""
    return jnp.squeeze(lax.slice_in_dim(x, i, i + 1, axis=0), axis=0)


_WORD = 32


def _carry_in(g: jax.Array, p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-limb carry/borrow-in from generate/propagate (bool [L, ...], never
    both set in one limb); also returns the final carry-out (bool [...]).

    Carry lookahead by one machine addition: the limbs' bits are packed into
    32-bit words along the leading axis (G = Σ g_k·2^k, P likewise), and
    (G | P) + G carries through a run of propagate bits exactly as the limb
    chain does, so the carry into limb k is bit k of ((G | P) + G) ^ P. Two
    reductions over the limb axis and a dozen elementwise operations, in
    place of a Kogge–Stone network of log₂ L levels, each of which reads its
    input shifted and unshifted and so ended a fusion on the device (six
    fusions a carry chain, four chains a field multiplication: PERF.md §6,
    PR 25). The carries are the same bits."""
    n = g.shape[0]
    bit = lax.broadcasted_iota(jnp.uint32, (n,) + (1,) * (g.ndim - 1), 0) % _WORD
    gb = g.astype(jnp.uint32) << bit
    pb = p.astype(jnp.uint32) << bit
    carry = None  # carry out of the word below
    words = []
    for lo in range(0, n, _WORD):
        G = jnp.sum(gb[lo : lo + _WORD], axis=0, dtype=jnp.uint32)
        P = jnp.sum(pb[lo : lo + _WORD], axis=0, dtype=jnp.uint32)
        a = G | P  # a & G == G, a ^ G == P: G generates, P propagates
        s = a + G if carry is None else a + G + carry
        words.append(s ^ P)  # bit k: the carry into limb lo + k
        carry = ((a & G) | ((a | G) & ~s)) >> (_WORD - 1)  # out of bit 31
    if n % _WORD:
        cout = (words[-1] >> np.uint32(n % _WORD)) & 1
    else:
        cout = carry
    per_limb = jnp.concatenate(
        [jnp.broadcast_to(w, (min(_WORD, n - i * _WORD),) + w.shape)
         for i, w in enumerate(words)],
        axis=0,
    ) if len(words) > 1 else jnp.broadcast_to(words[0], (n,) + words[0].shape)
    cin = (per_limb >> bit) & 1
    return cin != 0, cout != 0


def _split(cols: jax.Array, grow: int = 1) -> jax.Array:
    """One pass of a normalisation: [L, ...] uint32, any value -> [L + grow,
    ...] of the same value, limb k = lo16(col k) + hi16(col k-1) ≤ 0xFFFF +
    (the largest column >> 16) ≤ 2^17 − 2. `grow` = 0 where the top column
    is known to be a limb already."""
    if grow:
        cols = _shift_limbs(cols, 0, grow=grow)
    return (cols & _MASK) + _shift_up(cols >> LIMB_BITS)


def carry_norm(cols: jax.Array) -> jax.Array:
    """Carry-propagate column sums, exactly: [L, ...] uint32 (mul_cols feeds
    columns < 2^22, mul_small up to ~2^31; the value must fit L + 1 limbs)
    -> [L+1, ...] normalized 16-bit limbs (top limb = final carry-out).

    One split pass, then the lookahead: a limb ≤ 2^17 − 2 with its carry-in
    stays under 2^17, so the carry out is one bit, generated at t > 0xFFFF
    and propagated at t = 0xFFFF."""
    t = _split(cols)
    cin, _ = _carry_in(t > _MASK, t == _MASK)
    return (t + cin.astype(jnp.uint32)) & _MASK


def carry_loose(
    cols: jax.Array, col_max: int = 0xFFFFFFFF, limb_max: int = 1 << LIMB_BITS
) -> tuple[jax.Array, int]:
    """The loose normalisation: [L, ...] column sums ≤ col_max -> ([L+1, ...]
    limbs of the same value, their static bound ≤ limb_max). Split passes
    only (one where col_max allows it, never more than two: limb_max ≥ 2^16),
    no reduction over the limb axis, no lookahead.

    Enough for a consumer that multiplies the limbs by constant limbs c only
    (limb_max · c < 2^32) or adds them into columns that are normalised
    again; a limb product of two variables, a comparison and a field
    operation's result take :func:`carry_norm`. Limbs are non-negative, so a
    nonzero limb k still implies value ≥ 2^(16k): what a static bound on the
    value says is zero may be sliced off a loose array as off a canonical
    one."""
    if limb_max < 1 << LIMB_BITS:
        raise ValueError("carry_loose cannot promise limbs under 2^16")
    out, bound = _split(cols), int(_MASK) + (col_max >> LIMB_BITS)
    while bound > limb_max:
        out, bound = _split(out, grow=0), int(_MASK) + (bound >> LIMB_BITS)
    return out, bound


def sub_borrow(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(a - b) limbwise over axis 0 -> (diff [L, T], borrow_out bool [T])."""
    g = a < b
    p = a == b
    bin_, bout = _carry_in(g, p)
    diff = (a + jnp.uint32(0x10000) - b - bin_.astype(jnp.uint32)) & _MASK
    return diff, bout


def _or_fold(x: jax.Array) -> jax.Array:
    """Nonzero where any limb of [L, ...] is nonzero -> [...]: one reduction
    over the leading axis (the maximum of unsigned limbs is zero exactly
    when all are)."""
    return jnp.max(x, axis=0)


def is_zero(a: jax.Array) -> jax.Array:
    return _or_fold(a) == 0


def eq(a: jax.Array, b: jax.Array) -> jax.Array:
    return _or_fold(a ^ b) == 0


def geq(a: jax.Array, b: jax.Array) -> jax.Array:
    _, borrow = sub_borrow(a, b)
    return ~borrow


def lt(a: jax.Array, b: jax.Array) -> jax.Array:
    _, borrow = sub_borrow(a, b)
    return borrow


def select(cond: jax.Array, a, b):
    """cond [T] -> cond ? a : b over [..., T] operands (or tuples of them)."""
    if isinstance(a, tuple):
        return tuple(select(cond, x, y) for x, y in zip(a, b))
    return jnp.where(jnp.expand_dims(cond, tuple(range(a.ndim - cond.ndim))), a, b)


# ---------------------------------------------------------------------------
# Multiplication (unrolled row MACs with 16-bit splitting; no matmuls)
# ---------------------------------------------------------------------------


def _placed(x: jax.Array, offset: int, out: int) -> jax.Array:
    """[n, ...] limbs placed at limb `offset` of an [out, ...] zero canvas
    (what does not fit is dropped) — one pad. NEVER `.at[...].add`: a
    static-slice scatter is the single most expensive op for XLA to compile
    (round-2 lesson: ~11k scatters made one EC program a >10-minute CPU
    compile)."""
    if offset >= out:
        return jnp.zeros((out,) + x.shape[1:], x.dtype)
    return _shift_limbs(x, offset, grow=out - x.shape[0])


def _add_rows(x: jax.Array) -> jax.Array:
    """Sum the limbs of [L, ...] -> [1, ...]. Caller bounds the values so
    sums cannot overflow uint32."""
    return jnp.sum(x, axis=0, keepdims=True, dtype=jnp.uint32)


def _sum_terms(terms: list[jax.Array]) -> jax.Array:
    """Balanced tree-add of equal-shape u32 arrays: elementwise adds of
    shifted operands, which XLA fuses into the consumer (a stack + sum would
    materialise the stack)."""
    while len(terms) > 1:
        nxt = [
            terms[i] + terms[i + 1] if i + 1 < len(terms) else terms[i]
            for i in range(0, len(terms), 2)
        ]
        terms = nxt
    return terms[0]


_MUL_COL_MAX = 32 * 0xFFFF  # a column of mul_cols: 16 low and 16 high halves
_SQR_COL_MAX = 64 * 0xFFFF  # of sqr_cols: the off-diagonal halves doubled


def mul_cols(a: jax.Array, b: jax.Array, out: int = 2 * LIMBS) -> jax.Array:
    """Column sums of a*b: [16, T] x [16, T] -> [out, T] raw columns.

    Column k collects lo16(a_i*b_j) for i+j == k and hi16 for i+j == k-1;
    every column sum is < 32 * 2^16 < 2^22, inside uint32. The 32 shifted
    row groups are summed with one stacked reduction (scatter-free).
    """
    terms = []
    for i in range(LIMBS):
        # static slice, not a[i]: integer indexing lowers via dynamic_slice
        prod = lax.slice_in_dim(a, i, i + 1, axis=0) * b  # [16, T], < 2^32
        terms.append(_placed(prod & _MASK, i, out))
        terms.append(_placed(prod >> LIMB_BITS, i + 1, out))
    return _sum_terms(terms)


def sqr_cols(a: jax.Array, out: int = 2 * LIMBS) -> jax.Array:
    """Column sums of a*a exploiting symmetry: the off-diagonal partial
    products a_i*a_j (i < j) are computed once and doubled, and all 16
    diagonal products come from ONE elementwise multiply — 136 partial-
    product rows instead of :func:`mul_cols`'s 256, with the same 32-term
    add tree. Doubling happens after the lo/hi split (terms < 2^17), so
    column sums stay < 32 * 2^17 < 2^23, inside carry_norm's budget."""
    d = a * a  # [16, ...] diagonal products a_i^2, column 2i
    # interleave limbs with zeros — interior padding of the leading axis:
    # (d0, 0, d1, 0, ...) -> columns 0,2,4,...; (0, h0, 0, h1, ...) -> 1,3,5,...
    zero = jnp.array(0, jnp.uint32)
    rest = [(0, 0, 0)] * (a.ndim - 1)
    d_lo = lax.pad(d & _MASK, zero, [(0, 1, 1)] + rest)
    d_hi = lax.pad(d >> LIMB_BITS, zero, [(1, 0, 1)] + rest)
    terms = [_placed(d_lo, 0, out), _placed(d_hi, 0, out)]
    for i in range(LIMBS - 1):
        ai = lax.slice_in_dim(a, i, i + 1, axis=0)  # [1, T]
        rest = lax.slice_in_dim(a, i + 1, LIMBS, axis=0)  # [15-i, T]
        prod = ai * rest  # rows j = i+1..15, value a_i*a_j < 2^32
        terms.append(_placed(((prod & _MASK) << 1), 2 * i + 1, out))
        terms.append(_placed(((prod >> LIMB_BITS) << 1), 2 * i + 2, out))
    return _sum_terms(terms)


def mul_const_cols(
    hi: jax.Array, c_limbs: np.ndarray, out: int
) -> jax.Array:
    """Column sums of hi * c for a small host constant c: [H, T] x [C] ->
    [out, T] raw columns (same lo/hi splitting as :func:`mul_cols`)."""
    terms = [jnp.zeros((out,) + hi.shape[1:], jnp.uint32)]
    for k, cval in enumerate(np.asarray(c_limbs, dtype=np.uint64)):
        cval = int(cval)
        if cval == 0:
            continue
        prod = hi * np.uint32(cval)  # < 2^32
        terms.append(_placed(prod & _MASK, k, out))
        terms.append(_placed(prod >> LIMB_BITS, k + 1, out))
    return _sum_terms(terms)


def add_widen(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact add of two normalized arrays (equal or different widths) ->
    [max(L)+1, T] normalized."""
    w = max(a.shape[0], b.shape[0])
    return carry_norm(_placed(a, 0, w) + _placed(b, 0, w))


def cond_sub(x: jax.Array, m_limbs: np.ndarray) -> jax.Array:
    """x - m if x >= m else x, for normalized x < 2m. Returns [16, T]. m may
    be as wide as x: a static offset both carry above limb 15 (x + a·R against
    m + a·R) compares as x against m and leaves the low 16 limbs what they
    were."""
    w = x.shape[0]
    m_pad = np.zeros(w, dtype=np.uint32)
    m_pad[: len(m_limbs)] = m_limbs
    mc = const_rows(m_pad, x)
    diff, borrow = sub_borrow(x, mc)
    return select(~borrow, diff, x)[:LIMBS]


def signed_terms(c: int, width: int) -> tuple[tuple[int, int, int], ...]:
    """c ≥ 0 as the shortest signed sum of powers of two, its non-adjacent
    form, each term as (limb offset, shift inside the limb, sign): c ≡ Σ sign
    · 2^shift · 2^(16·offset) mod 2^(16·width). A term at limb `width` or
    above is a multiple of that modulus and is left out."""
    terms, bit = [], 0
    while c:
        if c & 1:
            sign = 2 - (c & 3)  # ±1, whichever leaves the next bit clear
            c -= sign
            if bit < LIMB_BITS * width:
                terms.append((*divmod(bit, LIMB_BITS), sign))
        c >>= 1
        bit += 1
    return tuple(terms)


def signed_rows(
    x: jax.Array,
    terms: tuple[tuple[int, int, int], ...],
    out: int,
    x_max: int,
    plus: jax.Array | None = None,
    plus_max: int = 0,
) -> tuple[jax.Array, int]:
    """Column sums of x·c (+ `plus`) for a host constant c given as
    :func:`signed_terms`: [n, ...] limbs ≤ x_max (and [out, ...] columns ≤
    plus_max) -> ([out, ...] raw columns, a). No product: a term is x shifted
    inside its limbs and placed at its offset, one pad; what a row places
    past column `out` drops, as in :func:`mul_cols`.

    Columns are unsigned, so the negative rows have no sum of their own to be
    borrowed from: they are taken from a static bias, column by column at
    least what the negative rows can reach there (bias_k = 2^16·a_k − a_{k−1},
    a_k the least that covers column k: the sum telescopes), whose value is
    a · 2^(16·out). The columns returned hold x·c + plus + a·2^(16·out), less
    what dropped, each shown here to stay inside uint32."""
    n = x.shape[0]
    pos_max, neg_max = [plus_max] * out, [0] * out
    pos = [] if plus is None else [plus]
    neg = []
    for offset, shift, sign in terms:
        for k in range(offset, min(offset + n, out)):
            (pos_max if sign > 0 else neg_max)[k] += x_max << shift
        row = _placed(x << np.uint32(shift) if shift else x, offset, out)
        (pos if sign > 0 else neg).append(row)
    bias, a = [], 0
    for need in neg_max:
        above = -(-(need + a) >> LIMB_BITS)
        bias.append((above << LIMB_BITS) - a)
        a = above
    if max(b + p for b, p in zip(bias, pos_max)) > 0xFFFFFFFF:
        raise ValueError("signed_rows: a column would leave uint32")
    if not neg:
        return _sum_terms(pos), 0
    cols = _sum_terms(pos + [const_rows(np.array(bias, dtype=np.uint32), x)])
    return cols - _sum_terms(neg), a


# ---------------------------------------------------------------------------
# Field protocols
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FoldField:
    """GF(m) for pseudo-Mersenne m = 2^256 - c (c ≤ ~2^130): plain-domain
    values, reduction by folding hi*c back into the low words.

    secp256k1's p (c = 2^32 + 977) and n (c ≈ 1.27*2^128) both qualify —
    this is the fast path for the north-star kernel, replacing the generic
    Montgomery REDC of round 1 (3 wide products per mul) with one wide
    product plus cheap constant folds.
    """

    m_int: int
    c_limbs: np.ndarray = field(repr=False)
    m_limbs: np.ndarray = field(repr=False)

    def __hash__(self):
        return hash(("fold", self.m_int))

    def __eq__(self, other):
        return isinstance(other, FoldField) and other.m_int == self.m_int

    # -- domain conversions (plain domain: all identity) --
    def enc(self, v: int) -> np.ndarray:
        return int_to_rows(v % self.m_int)

    def from_plain(self, x: jax.Array) -> jax.Array:
        return x

    def to_plain(self, x: jax.Array) -> jax.Array:
        return x

    def one(self, t) -> jax.Array:
        return const_rows(int_to_rows(1), t)

    # -- reduction --
    @property
    def _hi_limb_max(self) -> int:
        """The largest limb `hi * c` takes inside uint32."""
        return 0xFFFFFFFF // int(max(self.c_limbs))

    def reduce_wide(
        self, x: jax.Array, bound: int, limb_max: int = 0xFFFF
    ) -> jax.Array:
        """x (limbs ≤ limb_max, value < bound, bound exclusive) -> x mod m,
        canonical. x may be loose (:func:`carry_loose`, limb_max its bound)
        where bound > 2m, so that a fold runs.

        Folds value = lo + hi*2^256 ≡ lo + hi*c (mod m) until the static
        value bound drops below 2m, then one conditional subtract. Only the
        last fold is normalised exactly (the subtract compares); the folds
        before it feed `hi * c` and an addition of columns, which take loose
        limbs, and the bound carries a loose low half's slack (limb_max in
        every limb, not 0xFFFF). Any contribution the static column clamp
        drops is provably zero (a nonzero write at column k implies value ≥
        2^(16k) > bound: limbs and columns are non-negative).
        """
        c_int = _R - self.m_int
        # a column of a fold: a low and a high half for every limb of c, and lo
        fold_cols = 2 * 0xFFFF * int(np.count_nonzero(self.c_limbs))
        while bound > 2 * self.m_int:
            lo, hi = x[:LIMBS], x[LIMBS:]
            if hi.shape[0] == 0:
                break
            if limb_max > self._hi_limb_max:
                raise ValueError("reduce_wide: hi * c would leave uint32")
            hi_max = (bound - 1) >> 256
            bound = limb_max * _ONES + hi_max * c_int + 1
            width = max((bound - 1).bit_length() + 15, 17 * 16) // 16
            cols = mul_const_cols(hi, self.c_limbs, width)
            cols = cols + _placed(lo, 0, width)
            if bound > 2 * self.m_int:
                x, limb_max = carry_loose(
                    cols, fold_cols + limb_max, self._hi_limb_max
                )
            else:
                x, limb_max = carry_norm(cols), 0xFFFF
            x = x[:width]
        return cond_sub(x, self.m_limbs)

    def reduce1(self, x: jax.Array) -> jax.Array:
        """x < 2m (16 limbs) -> x mod m (one conditional subtract)."""
        return cond_sub(x, self.m_limbs)

    # -- field ops --
    def _reduce_cols(self, cols: jax.Array, col_max: int, bound: int) -> jax.Array:
        """Raw columns ≤ col_max of a value < bound -> the value mod m."""
        # limbs the bound says are zero go: a product is 32, not 33
        width = ((bound - 1).bit_length() + 15) // 16
        if bound <= 2 * self.m_int:  # no fold will run: this normalisation is the last
            return cond_sub(carry_norm(cols)[:width], self.m_limbs)
        wide, limb_max = carry_loose(cols, col_max, self._hi_limb_max)
        return self.reduce_wide(wide[:width], bound, limb_max)

    def mul(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self._reduce_cols(mul_cols(a, b), _MUL_COL_MAX, (_R - 1) ** 2 + 1)

    def sqr(self, a: jax.Array) -> jax.Array:
        return self._reduce_cols(sqr_cols(a), _SQR_COL_MAX, (_R - 1) ** 2 + 1)

    def mul_small(self, a: jax.Array, c: int) -> jax.Array:
        """a * c for a small host constant c < 2^15 — one scalar-broadcast
        multiply + carry + fold (~1/10 of a full mul). The RCB complete
        group law multiplies by 3b per add; for secp256k1 b3 = 21."""
        if not 0 < c < 1 << 15:
            raise ValueError("mul_small needs 0 < c < 2^15")
        cols = a * np.uint32(c)  # limbs < 2^16 * 2^15 = 2^31: no overflow
        return self._reduce_cols(cols, 0xFFFF * c, (_R - 1) * c + 1)

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return cond_sub(add_widen(a, b), self.m_limbs)

    def sub(self, a: jax.Array, b: jax.Array) -> jax.Array:
        diff, borrow = sub_borrow(a, b)
        plus = add_widen(diff, const_rows(self.m_limbs, a))[:LIMBS]
        return select(borrow, plus, diff)

    def neg(self, a: jax.Array) -> jax.Array:
        return self.sub(jnp.zeros_like(a), a)

    def inv(self, a: jax.Array) -> jax.Array:
        """a^-1 mod m for prime m (Fermat); 0 -> 0."""
        return pow_static(self, a, self.m_int - 2)

    def sqrt(self, a: jax.Array) -> jax.Array:
        """Square root candidate for m ≡ 3 (mod 4): a^((m+1)/4). Caller must
        check sqr(result) == a to detect non-residues."""
        assert self.m_int % 4 == 3
        return pow_static(self, a, (self.m_int + 1) // 4)


def make_fold_field(m: int) -> FoldField:
    c = _R - m
    if not 0 < c < 1 << 132:
        raise ValueError("FoldField needs m = 2^256 - c with small c")
    nc = (c.bit_length() + 15) // 16
    return FoldField(
        m_int=m, c_limbs=int_to_rows(c, nc), m_limbs=int_to_rows(m)
    )


@dataclass(frozen=True)
class SparseFoldField(FoldField):
    """GF(m) for Solinas m where 2^256 - m = Σ 2^(16·o) − Σ 2^(16·o') —
    the complement is a signed sum of limb-aligned powers, so the fold
    hi·c is pure shifted adds/subs with NO multiplies at all. SM2's prime
    qualifies (2^256 − p = 2^224 + 2^96 − 2^64 + 1): this replaces the
    generic Montgomery REDC (~2.5 wide products per mul) with one wide
    product, one dense per-limb table fold and one signed shift-add round,
    and makes the domain conversions identity. Everything except
    :meth:`reduce_wide` is inherited from the plain-domain
    :class:`FoldField`."""

    pos_offsets: tuple[int, ...] = ()  # limb offsets o with +2^(16o)
    neg_offsets: tuple[int, ...] = ()
    # [16, 16] uint32: row k = limbs of 2^(256+16k) mod m (dense fold table)
    fold_rows: np.ndarray = field(default=None, repr=False)

    def __hash__(self):
        return hash(("sparsefold", self.m_int))

    def __eq__(self, other):
        return isinstance(other, SparseFoldField) and other.m_int == self.m_int

    @property
    def _c_pos(self) -> int:
        return sum(1 << (16 * o) for o in self.pos_offsets)

    @property
    def _hi_limb_max(self) -> int:
        return 0xFFFFFFFF // 0xFFFF  # hi meets the fold table's limbs, any of them

    def _table_fold(
        self, lo: jax.Array, hi: jax.Array, limb_max: int
    ) -> tuple[jax.Array, int]:
        """lo [16,T] + hi [H≤16,T] (limbs ≤ limb_max) -> normalized limbs of
        lo + Σ_k hi_k · (2^(256+16k) mod m), with its exclusive bound.

        One output column j sums h_k·T[k][j] over k: a single broadcast
        multiply per column plus a log-tree row sum (≤16 terms of < 2^16
        after the lo/hi split, so sums stay < 2^20 — far inside uint32)."""
        h = hi.shape[0]
        tab = self.fold_rows[:h]  # [h, 16]
        width = 18  # value < limb_max·(R/65,535 + 16·m) < 2^277
        terms = [_placed(lo, 0, width)]
        for j in range(LIMBS):
            tj = dev_vec(tab[:, j]).reshape((h,) + (1,) * (hi.ndim - 1))  # column constants
            prod = hi * tj  # [h, T], products < 2^32
            terms.append(_placed(_add_rows(prod & _MASK), j, width))
            terms.append(_placed(_add_rows(prod >> LIMB_BITS), j + 1, width))
        bound = limb_max * (_ONES + LIMBS * self.m_int) + 1
        return carry_norm(_sum_terms(terms))[:width], bound

    def reduce_wide(
        self, x: jax.Array, bound: int, limb_max: int = 0xFFFF
    ) -> jax.Array:
        """x (limbs ≤ limb_max ≤ 65,537, canonical or loose as FoldField's;
        value < bound) -> x mod m. Every chain here stays exact: no cell runs
        this field.

        Wide inputs (a full product) take ONE dense table fold
        (lo + Σ hi_k·(2^(256+16k) mod m)), leaving a ~2^21 hi that a single
        signed shift-add round (value = lo + Σ(hi<<16o) − Σ(hi<<16o'),
        which cannot go negative) folds under 2m. Narrow inputs skip
        straight to shift-add rounds."""
        c_pos = self._c_pos
        if limb_max > self._hi_limb_max:
            raise ValueError("reduce_wide: hi * table limb would leave uint32")
        if x.shape[0] > LIMBS + 2 and bound > 2 * self.m_int:
            x, bound = self._table_fold(x[:LIMBS], x[LIMBS:], limb_max)
            limb_max = 0xFFFF
        while bound > 2 * self.m_int:
            lo, hi = x[:LIMBS], x[LIMBS:]
            if hi.shape[0] == 0:
                break
            hi_max = (bound - 1) >> 256
            bound = limb_max * _ONES + hi_max * c_pos + 1
            width = max((bound - 1).bit_length() + 15 + 16, 17 * 16) // 16
            cols = _placed(lo, 0, width)
            for o in self.pos_offsets:
                cols = cols + _placed(hi, o, width)
            pos_n = carry_norm(cols)[:width]
            neg_cols = _placed(hi, self.neg_offsets[0], width)
            for o in self.neg_offsets[1:]:
                neg_cols = neg_cols + _placed(hi, o, width)
            neg_n = carry_norm(neg_cols)[:width]
            diff, _borrow = sub_borrow(pos_n, neg_n)  # value ≥ 0: no borrow
            x, limb_max = diff, 0xFFFF
        return cond_sub(x, self.m_limbs)


# Solinas decompositions of 2^256 − m into ±2^(16·o) terms, per modulus
_SPARSE_COMPLEMENTS: dict[int, tuple[tuple[int, ...], tuple[int, ...]]] = {
    # SM2 p: 2^256 − p = 2^224 + 2^96 − 2^64 + 1
    0xFFFFFFFEFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF00000000FFFFFFFFFFFFFFFF: (
        (14, 6, 0),
        (4,),
    ),
}


def make_sparse_fold_field(m: int) -> SparseFoldField:
    pos, neg = _SPARSE_COMPLEMENTS[m]
    c = _R - m
    assert sum(1 << (16 * o) for o in pos) - sum(1 << (16 * o) for o in neg) == c
    return SparseFoldField(
        m_int=m,
        # c_limbs is only read by FoldField.reduce_wide, which is overridden
        c_limbs=int_to_rows(c, (c.bit_length() + 15) // 16),
        m_limbs=int_to_rows(m),
        pos_offsets=pos,
        neg_offsets=neg,
        fold_rows=np.stack(
            [int_to_rows(pow(2, 256 + 16 * k, m)) for k in range(LIMBS)]
        ),
    )


@dataclass(frozen=True)
class MontField:
    """GF(m) for arbitrary odd m < 2^256: Montgomery-domain values (x·R mod m,
    R = 2^256), word REDC reduction: SM2's p, and the dense moduli (SM2's n,
    BN254's Fr).

    REDC multiplies by two constants, m' = −m^-1 mod R and m. Where the
    modulus makes both short signed sums of powers of two
    (:func:`make_mont_field` derives them and decides: `mprime_terms` and
    `m_terms` are then set), the two multiplications are shifted rows added
    and subtracted (:func:`signed_rows`) and a field multiplication is one
    limb product, not three; for any other modulus they are limb products by
    the constants' limbs (:func:`mul_cols`). One algorithm, the same exact
    chains, the same canonical limbs out."""

    m_int: int
    m_limbs: np.ndarray = field(repr=False)
    mprime: np.ndarray = field(repr=False)  # -m^-1 mod 2^256
    r1: np.ndarray = field(repr=False)  # R mod m (the field's 1)
    r2: np.ndarray = field(repr=False)  # R^2 mod m
    # m' mod R and m as signed_terms, or None for both: multiply by the limbs
    mprime_terms: tuple[tuple[int, int, int], ...] | None = None
    m_terms: tuple[tuple[int, int, int], ...] | None = None

    def __hash__(self):
        return hash(("mont", self.m_int))

    def __eq__(self, other):
        return isinstance(other, MontField) and other.m_int == self.m_int

    def enc(self, v: int) -> np.ndarray:
        return int_to_rows((v % self.m_int) * _R % self.m_int)

    def one(self, t) -> jax.Array:
        return const_rows(self.r1, t)

    def redc(self, t: jax.Array, t_max: int = 1 << LIMB_BITS) -> jax.Array:
        """t [32, ...] (limbs or raw columns ≤ t_max of a value t < m*R) ->
        t*R^-1 mod m, [16, ...] canonical.

        Two exact chains and the conditional subtract: m_val = t·m' mod R (17
        limbs), t + m_val·m (33), the subtract (17). t meets only constants
        and an addition of columns, and the columns past R drop, so any low
        half congruent to t mod R serves and t is never exact: loose limbs ≤
        2^16 where they multiply the limbs of `m'` (2^16 · 0xFFFF < 2^32), a
        product's raw columns where `m'` is a few shifted rows. m_val
        multiplies m and has to be < R for t + m_val·m < 2mR, so it is exact;
        m_val·m is never normalised on its own: its raw columns join t's.

        In the shifted-sum form the columns carry a static bias under their
        negative rows (:func:`signed_rows`). m_val's is a multiple of R and
        drops with the limb above R. That of t + m_val·m is a · 2^512: the 33
        limbs hold the sum (< 2mR < 2^513) with `a` more in the top limb, and
        the subtract compares against m + a·R, so no chain grows and no limb
        is repaired."""
        if self.m_terms is None:
            if t_max * 0xFFFF > 0xFFFFFFFF:
                raise ValueError("redc: t · m' would leave uint32")
            m_val = carry_norm(
                mul_cols(t[:LIMBS], const_rows(self.mprime, t), out=LIMBS)
            )[:LIMBS]
            cols, a = t + mul_cols(m_val, const_rows(self.m_limbs, t)), 0
        else:
            m_val = carry_norm(
                signed_rows(t[:LIMBS], self.mprime_terms, LIMBS, t_max)[0]
            )[:LIMBS]
            cols, a = signed_rows(
                m_val, self.m_terms, 2 * LIMBS, 0xFFFF, plus=t, plus_max=t_max
            )
        s = carry_norm(cols)  # [33, ...]; low 16 limbs are zero
        return cond_sub(s[LIMBS:], int_to_rows(self.m_int + a * _R, LIMBS + 1))

    def _redc_cols(self, cols: jax.Array, col_max: int) -> jax.Array:
        """Raw columns ≤ col_max of a product < m*R -> its REDC. Where t
        multiplies the limbs of `m'` it is normalised first (loosely: two
        split passes, because `m'` has limbs of 0xFFFF); shifted rows take
        the columns as the product left them."""
        if self.m_terms is None:
            cols, col_max = carry_loose(cols, col_max)
            cols = cols[: 2 * LIMBS]
        return self.redc(cols, col_max)

    def from_plain(self, x: jax.Array) -> jax.Array:
        return self.mul(x, const_rows(self.r2, x))

    def to_plain(self, x: jax.Array) -> jax.Array:
        return self.redc(_placed(x, 0, 2 * LIMBS))

    def mul(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self._redc_cols(mul_cols(a, b), _MUL_COL_MAX)

    def sqr(self, a: jax.Array) -> jax.Array:
        return self._redc_cols(sqr_cols(a), _SQR_COL_MAX)

    def mul_small(self, a: jax.Array, c: int) -> jax.Array:
        """a * c for tiny c via an addition chain (scaling commutes with the
        Montgomery representation; each step is one conditional subtract,
        far cheaper than a REDC mul). Used by the complete group law's
        a = -3 path (c = 3)."""
        if not 0 < c < 32:
            raise ValueError("MontField.mul_small supports 0 < c < 32")
        # double-and-add on the bits of c, msb first
        acc = None
        for bit in bin(c)[2:]:
            if acc is not None:
                acc = self.add(acc, acc)
            if bit == "1":
                acc = a if acc is None else self.add(acc, a)
        return acc

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return cond_sub(add_widen(a, b), self.m_limbs)

    def sub(self, a: jax.Array, b: jax.Array) -> jax.Array:
        diff, borrow = sub_borrow(a, b)
        plus = add_widen(diff, const_rows(self.m_limbs, a))[:LIMBS]
        return select(borrow, plus, diff)

    def neg(self, a: jax.Array) -> jax.Array:
        return self.sub(jnp.zeros_like(a), a)

    def inv(self, a: jax.Array) -> jax.Array:
        return pow_static(self, a, self.m_int - 2)

    def sqrt(self, a: jax.Array) -> jax.Array:
        assert self.m_int % 4 == 3
        return pow_static(self, a, (self.m_int + 1) // 4)


# The most terms a constant of REDC may have for the shifted-sum form. A
# product by a constant's 16 limbs is 16 row products whose low and high
# halves are 32 placed rows to add; a signed sum of n terms is n placed rows
# and no product, so up to 16 terms it adds at most half the rows. Between
# SM2's p (5 and 7 terms: 3.6 to 5.2 times faster a call on the chip than the
# products, PERF.md §6 PR 46) and the dense moduli (SM2's n 43 and 88,
# BN254's Fr 74 and 81) no modulus has been timed.
_SHIFT_TERMS_MAX = 16


@lru_cache(maxsize=None)
def make_mont_field(m: int) -> MontField:
    """The Montgomery field of m. The form of its REDC is read off the
    modulus: the non-adjacent forms of m and of −m^-1 mod R, and the
    shifted-sum form where both have at most `_SHIFT_TERMS_MAX` terms."""
    if m % 2 == 0 or not 2 < m < _R:
        raise ValueError("modulus must be odd and < 2^256")
    mprime = (-pow(m, -1, _R)) % _R
    mprime_terms = signed_terms(mprime, LIMBS)
    m_terms = signed_terms(m, 2 * LIMBS)
    if max(len(mprime_terms), len(m_terms)) > _SHIFT_TERMS_MAX:
        mprime_terms = m_terms = None
    return MontField(
        m_int=m,
        m_limbs=int_to_rows(m),
        mprime=int_to_rows(mprime),
        r1=int_to_rows(_R % m),
        r2=int_to_rows(_R * _R % m),
        mprime_terms=mprime_terms,
        m_terms=m_terms,
    )


# ---------------------------------------------------------------------------
# Windowed exponentiation with a static exponent
# ---------------------------------------------------------------------------

_POW_W = 4


def _exp_windows(e: int) -> np.ndarray:
    """Static exponent -> MSB-first 4-bit windows (leading zeros stripped)."""
    if e <= 0:
        raise ValueError("pow_static needs a positive exponent")
    nw = (e.bit_length() + _POW_W - 1) // _POW_W
    return np.array(
        [(e >> (_POW_W * i)) & 0xF for i in range(nw - 1, -1, -1)],
        dtype=np.uint32,
    )


def pow_static(F, a: jax.Array, e: int) -> jax.Array:
    """a^e in field F for a fixed Python-int exponent.

    4-bit windows, MSB first: per window 4 squarings + one table multiply
    selected branch-free from the 15 precomputed powers, as compact
    ``lax.scan`` programs (an unrolled form is ~15x the HLO, which is the
    difference between seconds and tens of minutes of XLA-CPU compile).
    """
    wins = _exp_windows(e)

    def _tab_step(prev, _):
        nxt = F.mul(prev, a)
        return nxt, nxt

    _, rest_tab = lax.scan(_tab_step, a, None, length=14)
    tab = jnp.concatenate([a[None], rest_tab], axis=0)  # [15, 16, T]

    first = int(wins[0])
    assert first != 0
    acc0 = tab[first - 1]
    if len(wins) == 1:
        return acc0

    def body(acc, c):
        for _ in range(_POW_W):
            acc = F.sqr(acc)
        sel = tab[0]
        for k in range(2, 16):
            sel = select(c == k, tab[k - 1], sel)
        with_mul = F.mul(acc, sel)
        return select(c == 0, acc, with_mul), None

    acc, _ = lax.scan(body, acc0, dev_vec(wins[1:]))
    return acc
