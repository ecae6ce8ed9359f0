"""The limb arithmetic on lane-dense batches, against Python integers.

``ops/limb.py`` keeps the limb index on the leading axis and the batch in the
trailing dimensions — ``[L, S, 128]`` in the admission program over 1,024
lanes (a limb is more than one vreg), ``[L, T]`` up to there.
Every case here runs the public batch-major ``[B, L]`` -> ``lane_dense`` ->
field operation -> ``batch_major`` path at a lane count that pads (24), at
whole multiples of 128 in ``[L, T]`` and at 1,152 lanes in ``[L, 9, 128]``, on
random and edge values, for secp256k1's p and n (``FoldField``) and SM2's p
(``MontField``, whose REDC multiplies by its two constants as signed shifted
rows since PR 46); ed25519's 2p, P-256's prime (the shifted rows again), SM2's
n and BN254's Fr (``MontField`` in the product form) and the Solinas form of
SM2's p at the lane counts that pad and that fill ``[L, 9, 128]``.

A field operation normalises exactly only where canonical limbs are consumed
(PR 27): the adversarial cases below drive the loose limbs ≤ 2^16 that run
between a product and its last fold, on raw operands that random draws do
not reach.

The structural guards at the end pin what the chip's op profile said costs
the time (PERF.md §6, PR 25): limb-axis slices and concatenates in a field
multiplication, and the size of one window step of the GLV ladder.
"""

import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fisco_bcos_tpu.ops import ec, limb

_R = 1 << 256
_P = ec.SECP256K1_OPS.curve.p
_N = ec.SECP256K1_OPS.curve.n
_SM2P = ec.SM2_OPS.curve.p
_SM2N = ec.SM2_OPS.curve.n
_ED2P = 2 * ((1 << 255) - 19)  # ed25519 keeps its values mod 2p
# BN254's scalar field (ops/poseidon.py): a dense modulus, REDC by products
_BN254R = 21888242871839275222246405745257275088548364400416034343698204186575808495617
# NIST P-256's prime: no cell runs it; a second modulus whose REDC constants
# are short signed sums, so the form is seen to be derived and not SM2's alone
_P256 = 2**256 - 2**224 + 2**192 + 2**96 - 1

FIELDS = {
    "fold-p": limb.make_fold_field(_P),
    "fold-n": limb.make_fold_field(_N),
    "mont-sm2p": limb.make_mont_field(_SM2P),
    "fold-ed2p": limb.make_fold_field(_ED2P),
    "mont-sm2n": limb.make_mont_field(_SM2N),
    "mont-bn254r": limb.make_mont_field(_BN254R),
    "mont-p256": limb.make_mont_field(_P256),
    "sparse-sm2p": limb.make_sparse_fold_field(_SM2P),
}
LANES = [24, 128, 512, 1024, 1152]
# the fields of the admission programs at every lane count, the others where
# the batch pads and where a limb is more than one vreg
FIELD_LANES = [
    (f, n)
    for f in sorted(FIELDS)
    for n in LANES
    if f in ("fold-p", "fold-n", "mont-sm2p") or n in (24, 1152)
]


def _edge(m: int) -> list[int]:
    return [0, 1, m - 1, m % m, (_R - 1) % m, 0xFFFF, (1 << 128) - 1, m - 2]


def _values(m: int, n: int, seed: int) -> list[int]:
    rng = random.Random(seed)
    vals = _edge(m) + [rng.randrange(m) for _ in range(n)]
    rng.shuffle(vals)
    return vals[:n]


def _dense(xs, width=limb.LIMBS):
    """Python ints -> batch-major [B, width] -> lane-dense limbs."""
    rows = np.stack([limb.int_to_rows(x, width) for x in xs])
    out = limb.lane_dense(jnp.asarray(rows))
    lanes = -(-len(xs) // 128) * 128
    # a limb over one vreg: [L, S, 128]; up to 1,024 lanes limbs share vregs, [L, T]
    assert out.shape == ((width, lanes // 128, 128) if lanes > 1024 else (width, lanes))
    return out


def _ints(x, n):
    """Lane-dense limbs -> the first n lanes as Python ints, through the
    public way out (padding lanes dropped)."""
    rows = np.asarray(limb.batch_major(x, n))
    assert rows.shape == (n, x.shape[0])
    return [sum(int(v) << (16 * i) for i, v in enumerate(r)) for r in rows]


def _codec(F):
    m = F.m_int
    if isinstance(F, limb.MontField):
        rinv = pow(_R, -1, m)
        return (lambda xs: _dense([x * _R % m for x in xs])), (
            lambda a, n: [v * rinv % m for v in _ints(a, n)]
        )
    return _dense, _ints


BINARY = {
    "mul": (lambda F, a, b: F.mul(a, b), lambda m, x, y: x * y % m),
    "add": (lambda F, a, b: F.add(a, b), lambda m, x, y: (x + y) % m),
    "sub": (lambda F, a, b: F.sub(a, b), lambda m, x, y: (x - y) % m),
}
UNARY = {
    "sqr": (lambda F, a: F.sqr(a), lambda m, x: x * x % m),
    "neg": (lambda F, a: F.neg(a), lambda m, x: -x % m),
    "mul_small": (lambda F, a: F.mul_small(a, 21), lambda m, x: 21 * x % m),
}


@pytest.mark.parametrize("op", sorted(BINARY))
@pytest.mark.parametrize("field,lanes", FIELD_LANES)
def test_field_binary_ops_match_python_ints(field, op, lanes):
    F = FIELDS[field]
    m = F.m_int
    enc, dec = _codec(F)
    xs, ys = _values(m, lanes, 1), _values(m, lanes, 2)
    dev, ref = BINARY[op]
    assert dec(dev(F, enc(xs), enc(ys)), lanes) == [ref(m, x, y) for x, y in zip(xs, ys)]


@pytest.mark.parametrize("op", sorted(UNARY))
@pytest.mark.parametrize("field,lanes", FIELD_LANES)
def test_field_unary_ops_match_python_ints(field, op, lanes):
    F = FIELDS[field]
    m = F.m_int
    enc, dec = _codec(F)
    xs = _values(m, lanes, 3)
    dev, ref = UNARY[op]
    assert dec(dev(F, enc(xs)), lanes) == [ref(m, x) for x in xs]


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("field", ["fold-p", "fold-n"])
def test_reduce_wide_folds_a_full_product(field, lanes):
    F = FIELDS[field]
    rng = random.Random(4)
    wide = [(_R - 1) ** 2, 0, _R, F.m_int * F.m_int] + [
        rng.randrange((_R - 1) ** 2 + 1) for _ in range(lanes)
    ]
    wide = wide[:lanes]
    got = F.reduce_wide(_dense(wide, 32), (_R - 1) ** 2 + 1)
    assert _ints(got, lanes) == [w % F.m_int for w in wide]


# -- loose limbs between a product and its last fold (PR 27) ------------------

# a pair whose product leaves two limbs of the loose normalisation at exactly
# 2^16 (found by search: about one structured draw in a thousand leaves one)
_LOOSE_A = 0xFFFFFFFFFFFF80010001000000FF80017FFF00FFFFFEDAEA0000000080000001
_LOOSE_B = 0x2000000018001FFFE0000FFFFFFFE000000FF4A3780017FFF1096FF00FFFF
_POOL = (0, 1, 2, 0xFFFF, 0xFFFE, 0x8000, 0x7FFF, 0x8001, 0x00FF, 0xFF00)


def _adversarial(top: int, n: int, seed: int, head=()) -> list[int]:
    """n raw operands < top: `head`, the edges, then limbs drawn from the
    values that make products carry (0xFFFF, 0x8000, ...)."""
    rng = random.Random(seed)
    vals = [v for v in head if v < top] + [0, 1, top - 1, top - 2, (_R - 1) % top]
    while len(vals) < n:
        x = sum(rng.choice(_POOL) << (16 * i) for i in range(limb.LIMBS))
        if x < top:
            vals.append(x)
    return vals[:n]


def _raw(F) -> tuple[int, int]:
    """(r, rinv): on raw limbs a Montgomery field's product carries R^-1 and
    its conversions R^±1; a plain-domain field's carry 1."""
    if isinstance(F, limb.MontField):
        return _R % F.m_int, pow(_R, -1, F.m_int)
    return 1, 1


def _wide_cases(F, n):
    """32-limb inputs of the reduction with their exclusive bound: a full
    product for the fold, t < m·R for REDC (its low half 0 in every third)."""
    m = F.m_int
    if isinstance(F, limb.MontField):
        top = m * _R
        his = _adversarial(m, n, 14)
        los = _adversarial(_R, n, 15)
        wide = [h * _R + (0 if i % 3 == 0 else l) for i, (h, l) in enumerate(zip(his, los))]
        return [(m - 1) ** 2, top - 1, (m - 1) * _R, _R - 1] + wide[: n - 4], top
    top = (_R - 1) ** 2 + 1
    his = _adversarial(_R - 2, n, 14)  # hi ≤ R − 3 leaves the low half free
    los = _adversarial(_R, n, 15, head=(_R - 1,))
    return [top - 1, _R * _R - 3 * _R + 1] + [h * _R + l for h, l in zip(his, los)][: n - 2], top


ADVERSARIAL_OPS = ("mul", "sqr", "mul_small", "reduce", "plain")


# every field in [16, 128]; the Montgomery fields, both forms of REDC, also
# where a limb is more than one vreg ([16, 9, 128])
ADVERSARIAL_FIELD_LANES = [
    (f, n) for f in sorted(FIELDS) for n in (128, 1152) if n == 128 or f.startswith("mont-")
]


@pytest.mark.parametrize("op", ADVERSARIAL_OPS)
@pytest.mark.parametrize("field,n", ADVERSARIAL_FIELD_LANES)
def test_field_ops_on_adversarial_raw_operands(field, n, op):
    """mul, sqr, mul_small, the wide reduction (reduce_wide / redc) and the
    domain conversions against Python integers on raw canonical operands:
    0, 1, m − 1, limbs of 0xFFFF, the pair whose product's loose limbs reach
    2^16, a REDC input whose low half is 0, (m − 1)^2 and m·R − 1."""
    F = FIELDS[field]
    m = F.m_int
    r, rinv = _raw(F)
    xs = _adversarial(m, n, 11, head=(_LOOSE_A, 1 << 128))
    ys = _adversarial(m, n, 12, head=(_LOOSE_B, 1 << 128))
    ys[5:10] = xs[5:10][::-1]  # the edges against each other
    a, b = _dense(xs), _dense(ys)
    if op == "mul":
        assert _ints(F.mul(a, b), n) == [x * y * rinv % m for x, y in zip(xs, ys)]
    elif op == "sqr":
        assert _ints(F.sqr(a), n) == [x * x * rinv % m for x in xs]
    elif op == "mul_small":
        for c in (1, 3, 21):
            assert _ints(F.mul_small(a, c), n) == [c * x % m for x in xs]
    elif op == "reduce":
        wide, top = _wide_cases(F, n)
        assert max(wide) < top
        if isinstance(F, limb.MontField):
            assert _ints(F.redc(_dense(wide, 32)), n) == [w * rinv % m for w in wide]
        else:
            assert _ints(F.reduce_wide(_dense(wide, 32), top), n) == [w % m for w in wide]
    else:
        assert _ints(F.from_plain(a), n) == [x * r % m for x in xs]
        assert _ints(F.to_plain(a), n) == [x * rinv % m for x in xs]


# -- REDC by signed shifted rows where the modulus allows it (PR 46) ----------

# the two sums make_mont_field must derive for SM2's p, as ISSUE 46 states
# them: (limb offset, shift inside the limb, sign)
_SM2P_TERMS = ((0, 0, -1), (4, 0, 1), (6, 0, -1), (14, 0, -1), (16, 0, 1))  # p
_SM2P_MPRIME_TERMS = (  # 1 + 2^64 − 2^96 + 2^128 − 2·2^160 + 2·2^192 − 4·2^224
    (0, 0, 1), (4, 0, 1), (6, 0, -1), (8, 0, 1), (10, 1, -1), (12, 1, 1), (14, 2, -1)
)


def _terms_value(terms) -> int:
    return sum(sign << (16 * offset + shift) for offset, shift, sign in terms)


# P-256: p = 2^256 − 2^224 + 2^192 + 2^96 − 1, m' = 1 + 2^96 + 2·2^192 − 2^224
_P256_TERMS = ((0, 0, -1), (6, 0, 1), (12, 0, 1), (14, 0, -1), (16, 0, 1))
_P256_MPRIME_TERMS = ((0, 0, 1), (6, 0, 1), (12, 1, 1), (14, 0, -1))


@pytest.mark.parametrize(
    "field,terms",
    [
        ("mont-sm2p", (_SM2P_TERMS, _SM2P_MPRIME_TERMS)),
        ("mont-p256", (_P256_TERMS, _P256_MPRIME_TERMS)),
        ("mont-sm2n", None),
        ("mont-bn254r", None),
    ],
)
def test_make_mont_field_reads_the_form_off_the_modulus(field, terms):
    """The shifted-sum REDC for SM2's p, with exactly the two term lists of
    the issue, and for P-256's prime; the product form for the dense moduli
    (SM2's n: 43 and 88 terms; BN254's Fr: 74 and 81). Whatever the form, the
    non-adjacent forms are the constants."""
    F = FIELDS[field]
    m = F.m_int
    mprime = -pow(m, -1, _R) % _R
    assert limb.rows_to_ints(F.mprime[:, None]) == [mprime]
    assert _terms_value(limb.signed_terms(m, 32)) == m
    assert _terms_value(limb.signed_terms(mprime, 16)) % _R == mprime
    if terms is None:
        assert F.m_terms is None and F.mprime_terms is None
        assert len(limb.signed_terms(m, 32)) > limb._SHIFT_TERMS_MAX
        assert len(limb.signed_terms(mprime, 16)) > limb._SHIFT_TERMS_MAX
    else:
        assert (F.m_terms, F.mprime_terms) == terms
    if field == "mont-sm2p":
        assert m == 2**256 - 2**224 - 2**96 + 2**64 - 1
        assert mprime == 0xFFFFFFFC00000001FFFFFFFE00000000FFFFFFFF000000010000000000000001
    assert limb.make_mont_field(m) is F  # one field a modulus


def _dense_rows(rows):
    """Explicit limb or column rows (lists of ints < 2^32) -> lane-dense."""
    return limb.lane_dense(jnp.asarray(np.array(rows, dtype=np.uint32)))


def _columns_under(top: int, col_max: int, n: int, seed: int) -> list[list[int]]:
    """n rows of 32 columns ≤ col_max whose value stays under `top`: every
    column at col_max as far up as the value allows, then seeded mixes of
    col_max, 2^16, 0xFFFF and 0."""
    rng = random.Random(seed)

    def value(cols):
        return sum(c << (16 * i) for i, c in enumerate(cols))

    rows = []
    while len(rows) < n:
        pick = (lambda: col_max) if not rows else (
            lambda: rng.choice((col_max, col_max, min(col_max, 1 << 16), 0xFFFF, 0)))
        cols = [pick() for _ in range(32)]
        k = 31
        while value(cols) >= top:  # lower the top columns until the value fits
            cols[k] = 0 if cols[k] == 0 or k > 30 else cols[k] >> 1
            k = k - 1 if cols[k] == 0 else k
        rows.append(cols)
    return rows


@pytest.mark.parametrize("lanes", [128, 1152])
@pytest.mark.parametrize("field", ["mont-sm2p", "mont-p256", "mont-sm2n", "mont-bn254r"])
def test_redc_takes_loose_limbs_and_raw_columns(field, lanes):
    """REDC on inputs no canonical operand reaches: t with every limb at
    carry_loose's bound 2^16 (either form), and a product's raw columns, up
    to sqr_cols' bound in every column, which the shifted rows take as they
    are and the product form refuses (its `· m'` would leave uint32)."""
    F = FIELDS[field]
    m = F.m_int
    _, rinv = _raw(F)
    top = m * _R
    loose = _columns_under(top, 1 << 16, lanes, 46)
    assert loose[0][:30] == [1 << 16] * 30
    want = [sum(c << (16 * i) for i, c in enumerate(cols)) * rinv % m for cols in loose]
    assert _ints(F.redc(_dense_rows(loose)), lanes) == want
    raw = _columns_under(top, limb._SQR_COL_MAX, lanes, 47)
    assert raw[0][:29] == [limb._SQR_COL_MAX] * 29
    if F.m_terms is None:
        with pytest.raises(ValueError):
            F.redc(_dense_rows(raw), limb._SQR_COL_MAX)
        return
    want = [sum(c << (16 * i) for i, c in enumerate(cols)) * rinv % m for cols in raw]
    assert _ints(F.redc(_dense_rows(raw), limb._SQR_COL_MAX), lanes) == want


@pytest.mark.parametrize(
    "c,width,fits",
    [
        pytest.param(_SM2P, 32, True, id="sm2p"),
        pytest.param(-pow(_SM2P, -1, _R) % _R, 16, True, id="sm2p-mprime"),
        pytest.param(0xFFFF, 16, True, id="one-limb"),
        pytest.param(0x5555_5555, 16, True, id="sixteen-terms"),
        pytest.param((1 << 255) + (1 << 20) - 1, 17, True, id="shift-15"),
        pytest.param((1 << 255) + (1 << 47) + (1 << 31) - 1, 17, False, id="three-shifts-of-15"),
    ],
)
def test_signed_rows_multiply_by_a_constant(c, width, fits):
    """x · c by signed shifted rows against Python integers, modulo what
    drops past the columns asked for; the bias under the negative rows is
    a · 2^(16 · out); a sum that could leave uint32 raises when traced (three
    rows shifted by 15 bits in one column; any constant on limbs of 2^31)."""
    terms = limb.signed_terms(c, width)
    assert _terms_value(terms) % (1 << (16 * width)) == c % (1 << (16 * width))
    xs = _adversarial(_R, 128, 48, head=(_R - 1, _LOOSE_A))
    x = _dense(xs)
    x_max = 0xFFFF
    with pytest.raises(ValueError):
        limb.signed_rows(x, terms, width, 1 << 31)
    if not fits:
        with pytest.raises(ValueError):
            limb.signed_rows(x, terms, width, x_max)
        return
    cols, a = limb.signed_rows(x, terms, width, x_max)
    got = _ints(limb.carry_norm(cols), 128)
    mod = 1 << (16 * width)
    assert [g % mod for g in got] == [v * c % mod for v in xs]
    if any(sign < 0 for _, _, sign in terms):
        assert a > 0
    # the same with columns added in: what REDC's second sum does with t
    plus = _dense([v ^ 0x5A5A for v in xs], width)
    cols, a = limb.signed_rows(x, terms, width, x_max, plus=plus, plus_max=0xFFFF)
    got = _ints(limb.carry_norm(cols), 128)
    assert [g % mod for g in got] == [(v * c + (v ^ 0x5A5A)) % mod for v in xs]


@pytest.mark.parametrize("limbs", [16, 33, 48])
def test_carry_loose_keeps_the_value_and_bounds_the_limbs(limbs):
    """The loose normalisation on columns up to 2^32 − 1: the same value, one
    limb more, every limb ≤ 2^16 (and = 2^16 somewhere), under the bound it
    returns; one pass where the columns' bound allows the limbs asked for.
    The exact one agrees on the same columns, also where one generate at
    limb 2 runs through propagates to the top limb."""
    rng = random.Random(27)
    full = 0xFFFFFFFF
    run = [full, 0xFFFF] + [0xFFFF] * (limbs - 2)  # loose: 2^16 at limb 2, 0xFFFF above
    cols = [[full] * limbs, run, [0] * limbs, [full, 0] * (limbs // 2) + [full] * (limbs % 2)]
    cols += [[rng.choice((0, 1, 0xFFFF, 0x10000, 0xFFFF0000, full, rng.randrange(full + 1)))
              for _ in range(limbs)] for _ in range(124)]
    n = len(cols)
    dense = limb.lane_dense(jnp.asarray(np.array(cols, dtype=np.uint32)))
    value = [sum(c << (16 * i) for i, c in enumerate(col)) for col in cols]
    loose, bound = limb.carry_loose(dense)
    assert loose.shape[0] == limbs + 1
    assert int(jnp.max(loose)) == bound == 1 << 16
    assert _ints(loose, n) == value
    got = np.asarray(limb.batch_major(loose, n))[1]
    assert got[2] == 1 << 16 and (got[3:limbs] == 0xFFFF).all()
    # columns of a product (< 2^21): one pass where 0xFFFF + 31 will do, two for 2^16
    small = dense & np.uint32(limb._MUL_COL_MAX)
    for limb_max, passes_bound in ((0xFFFF + 31, 0xFFFF + 31), (1 << 16, 1 << 16)):
        once, bound = limb.carry_loose(small, limb._MUL_COL_MAX, limb_max)
        assert bound == passes_bound and int(jnp.max(once)) <= bound
        assert _ints(once, n) == _ints(small, n)
    with pytest.raises(ValueError):
        limb.carry_loose(dense, full, 0xFFFF)
    # L + 1 canonical limbs hold the value once the top column is a limb
    fits = dense.at[limbs - 1].set(dense[limbs - 1] & 0xFFFF)
    exact = limb.carry_norm(fits)
    assert int(jnp.max(exact)) <= 0xFFFF
    assert _ints(exact, n) == _ints(limb.carry_loose(fits)[0], n)
    # the product pair: the loose limbs of a·b reach 2^16 twice
    prod, _ = limb.carry_loose(limb.mul_cols(_dense([_LOOSE_A]), _dense([_LOOSE_B])))
    assert int((np.asarray(limb.batch_major(prod, 1)) == 1 << 16).sum()) == 2


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("limbs", [16, 33, 48])
def test_carry_norm_and_sub_borrow_chains(limbs, lanes):
    """Carry chains of every length the programs use (33: a product; 48:
    BLS, two lookahead words), with the all-0xFFFF runs that carry end to
    end."""
    top = (1 << (16 * limbs)) - 1
    rng = random.Random(5)
    xs = ([top, top, 0, 1, top - 1] + [rng.randrange(top + 1) for _ in range(lanes)])[:lanes]
    ys = ([1, top, 0, top, 1] + [rng.randrange(top + 1) for _ in range(lanes)])[:lanes]
    a, b = _dense(xs, limbs), _dense(ys, limbs)
    assert _ints(limb.add_widen(a, b), lanes) == [x + y for x, y in zip(xs, ys)]
    diff, borrow = limb.sub_borrow(a, b)
    assert _ints(diff, lanes) == [(x - y) % (top + 1) for x, y in zip(xs, ys)]
    assert list(np.asarray(limb.batch_lanes(borrow, lanes))) == [x < y for x, y in zip(xs, ys)]
    # raw column sums far above 16 bits (what mul_small feeds: < 2^31)
    cols = a * np.uint32(0x7FFF)
    assert _ints(limb.carry_norm(cols), lanes) == [x * 0x7FFF for x in xs]


@pytest.mark.parametrize("lanes", LANES)
def test_compare_and_select(lanes):
    xs, ys = _values(_P, lanes, 6), _values(_P, lanes, 7)
    ys[:4] = xs[:4]  # equal lanes
    a, b = _dense(xs), _dense(ys)

    def lanes_of(mask):
        return list(np.asarray(limb.batch_lanes(mask, lanes)))

    assert lanes_of(limb.lt(a, b)) == [x < y for x, y in zip(xs, ys)]
    assert lanes_of(limb.geq(a, b)) == [x >= y for x, y in zip(xs, ys)]
    assert lanes_of(limb.eq(a, b)) == [x == y for x, y in zip(xs, ys)]
    assert lanes_of(limb.is_zero(a)) == [x == 0 for x in xs]
    picked = limb.select(limb.lt(a, b), a, b)
    assert _ints(picked, lanes) == [min(x, y) for x, y in zip(xs, ys)]
    pair = limb.select(limb.lt(a, b), (a, b), (b, a))
    assert _ints(pair[1], lanes) == [max(x, y) for x, y in zip(xs, ys)]


def test_mul_cols_is_the_plain_product():
    xs, ys = _values(_R, 128, 8), _values(_R, 128, 9)
    xs[0] = ys[0] = _R - 1
    wide = limb.carry_norm(limb.mul_cols(_dense(xs), _dense(ys)))
    assert _ints(wide, 128) == [x * y for x, y in zip(xs, ys)]
    sq = limb.carry_norm(limb.sqr_cols(_dense(xs)))
    assert _ints(sq, 128) == [x * x for x in xs]


# -- the admission program's recover against the benchmark's plain reference --


@pytest.mark.parametrize("n", [128, 1152])
def test_recover_matches_refcrypto_with_corrupted_lanes(n):
    """128 lanes (``[16, 128]``) and 1,152 (``[16, 9, 128]``, the layout of
    the full-block program): every sound lane recovers the signer's key bit
    for bit; r = 0, s >= n, v = 29, a non-residue x and r >= n each lower
    their lane's bit, leak no key and never raise."""
    from benchmark import refcrypto
    from fisco_bcos_tpu.ops import secp256k1
    from fisco_bcos_tpu.ops.bigint import bytes_be_to_limbs, limbs_to_bytes_be

    rng = random.Random(2500)
    secrets = [rng.randrange(1, refcrypto.N) for _ in range(8)]
    digests = [refcrypto.keccak256(b"lane %d" % i) for i in range(n)]
    sigs = np.frombuffer(
        b"".join(refcrypto.sign(d, secrets[i % 8]) for i, d in enumerate(digests)), np.uint8
    ).reshape(n, 65).copy()
    pubs = [refcrypto.pubkey_bytes(s) for s in secrets]
    order = np.frombuffer(refcrypto.N.to_bytes(32, "big"), np.uint8)
    sigs[3, :32] = 0  # r = 0
    sigs[17, 32:64] = order  # s = n
    sigs[40, 64] = 29  # v = 29 must not alias to 2
    x = next(v for v in range(2, 99) if pow(v**3 + 7, (_P - 1) // 2, _P) != 1)
    sigs[77, :32] = np.frombuffer(x.to_bytes(32, "big"), np.uint8)  # x^3 + 7 is no square
    sigs[127, :32] = np.frombuffer((_P - 1).to_bytes(32, "big"), np.uint8)  # r >= n
    rejected = {3, 17, 40, 77, 127}

    z = jnp.asarray(bytes_be_to_limbs(np.frombuffer(b"".join(digests), np.uint8).reshape(n, 32)))
    r = jnp.asarray(bytes_be_to_limbs(sigs[:, :32]))
    s = jnp.asarray(bytes_be_to_limbs(sigs[:, 32:64]))
    v = jnp.asarray(sigs[:, 64].astype(np.int32))
    qx, qy, ok = secp256k1.recover_device(z, r, s, v)
    ok = np.asarray(ok)
    got = np.concatenate(
        [limbs_to_bytes_be(np.asarray(qx)), limbs_to_bytes_be(np.asarray(qy))], axis=-1
    )
    for i in range(n):
        if i in rejected:
            assert not ok[i], f"lane {i} admitted"
            assert not got[i].any(), f"lane {i} leaked a key"
        else:
            assert ok[i] and bytes(got[i]) == pubs[i % 8], f"lane {i}"


# -- structural guards (tracing only: seconds on the CPU) ---------------------


def _count(jaxpr, names) -> int:
    """Equations named in `names`, nested jaxprs multiplied out once each."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            total += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, names)
    return total


_DYNAMIC_SHUFFLES = ("dynamic_slice", "gather", "scatter")


def _tool():
    """tool/admission_op_profile.py: the chain tally is its chip-free half."""
    if "tool" not in sys.path:
        sys.path.insert(0, "tool")
    import admission_op_profile

    return admission_op_profile


# field -> (equations a multiplication may trace, limb-axis slices, the limbs
# of its exact carry chains, its `mul` equations: a row product each, and a
# fold's multiplications by the limbs of c); before PR 27: 337 equations for
# p, 542 for n (its nine-limb complement folds wider), 636 for REDC (three
# limb products: 48 operand limbs), chains of 33, 20, 18, 17 limbs and 33, 17,
# 33, 33, 17. Since PR 46 a REDC whose constants are short signed sums
# (SM2's p) holds one limb product, 16 row products where it held 48 (522
# equations, 57 slices before); a dense modulus (SM2's n) keeps the three.
MUL_CEILINGS = {
    "fold-p": (265, 40, [17, 18], 20),
    "fold-n": (440, 40, [17, 18], 43),
    "mont-sm2p": (300, 32, [17, 17, 33], 16),
    "mont-sm2n": (540, 64, [17, 17, 33], 48),
}


@pytest.mark.parametrize("field", sorted(MUL_CEILINGS))
def test_field_mul_shuffles_no_limbs(field):
    """A field multiplication holds no concatenate at all (97 before PR 25),
    at most 40 limb-axis slices (135 before: the operand's 16 limbs, the
    fold's lo/hi halves, a dropped top limb), and every limb shift or
    placement is one pad. The op count cannot creep back unseen. Exact
    carry chains (``_carry_in``): the last fold and the conditional subtract
    of a fold (four before PR 27); m_val, t + m_val·m and the subtract of a
    REDC (five before), in either form of it. The row products are pinned:
    how often the shifted-sum REDC engages is static, not sampled."""
    eqns, slices, chains, products = MUL_CEILINGS[field]
    a = jax.ShapeDtypeStruct((16, 8, 128), jnp.uint32)
    jaxpr = jax.make_jaxpr(FIELDS[field].mul)(a, a).jaxpr
    assert _count(jaxpr, ("concatenate",)) <= 8
    assert _count(jaxpr, ("slice",)) <= slices
    assert _count(jaxpr, _DYNAMIC_SHUFFLES) == 0
    assert len(jaxpr.eqns) <= eqns
    assert _count(jaxpr, ("mul",)) == products
    tally = _tool().chain_tally(jaxpr)
    assert sorted(limbs for (_, limbs), n in tally.items() for _ in range(n)) == chains, tally


@pytest.mark.parametrize(
    "program,ceiling,chains", [("secp", 16_500, 15_757), ("sm", 43_100, 37_480)]
)
def test_admission_program_runs_few_exact_chains(program, ceiling, chains):
    """Executed exact carry chains a call of the two admission programs,
    traced at 1,024 lanes (24,164 and 48,717 before PR 27), and their packed
    lookahead words, Σ ⌈limbs / 32⌉ (27,977 and 65,573): a later edit that
    puts a lookahead back where nothing consumes canonical limbs is seen here,
    without a chip. PR 46 took REDC's two products by constants out of the SM
    program and no chain in or out: 37,480 chains and 43,099 words as before
    (5,618 REDCs a call, three chains each), and the secp program's 15,757.
    Tracing only: nothing compiles."""
    from fisco_bcos_tpu.observability.device import LEDGER

    tool = _tool()
    LEDGER.reset()
    tally = tool.program_chains(program)
    totals = tool.chain_totals(tally)
    assert LEDGER.cold_compile_count() == 0 and LEDGER.snapshot() == []
    assert 0 < totals["chains"] <= totals["words"] <= ceiling
    assert totals["chains"] <= chains
    if program == "sm":  # every REDC of the call is in the tally under its field operation
        redcs = {c: n for (c, limbs), n in tally.items() if c.endswith("redc>carry_norm") and limbs == 33}
        assert set(redcs) == {"MontField.mul>_redc_cols>redc>carry_norm",
                              "MontField.sqr>_redc_cols>redc>carry_norm",
                              "MontField.to_plain>redc>carry_norm"}, redcs


def test_glv_window_step_stays_small():
    """One window step of quad_mul_windowed (4 doublings, 2 complete and 2
    mixed additions): at most 1,000 concatenates (10,554 before PR 25)."""
    C = ec.SECP256K1_OPS
    a = jax.ShapeDtypeStruct((16, 8, 128), jnp.uint32)

    def step(x, y, z, qx, qy):
        acc = (x, y, z)
        for _ in range(ec.WINDOW):
            acc = ec.pt_double(acc, C)
        for _ in range(2):
            acc = ec.pt_add(acc, (qx, qy, z), C)
        for _ in range(2):
            acc = ec.pt_add_mixed(acc, (qx, qy), C)
        return acc

    jaxpr = jax.make_jaxpr(step)(a, a, a, a, a).jaxpr
    assert _count(jaxpr, ("concatenate",)) <= 1000
    assert len(jaxpr.eqns) <= 45_000  # 67,298 before
