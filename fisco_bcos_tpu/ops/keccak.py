"""Batch Keccak-256 on TPU.

Replaces the per-message CPU keccak of the reference (bcos-crypto
hash/Keccak256.h via OpenSSL EVP; hot in tx hashing, Transaction.h:64-84
verify, merkle builds) with a lane-parallel formulation: thousands of
independent messages hashed by one XLA program.

64-bit lanes are modeled as (lo, hi) uint32 pairs — TPUs have no 64-bit
integer datapath. The f[1600] permutation runs as a lax.scan over the 24
rounds; multi-block messages scan over block slots with per-lane masking
(static shapes, no data-dependent control flow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .hash_common import digest_words_to_bytes_le, pad_keccak

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_RC_LO = np.array([rc & 0xFFFFFFFF for rc in _RC], dtype=np.uint32)
_RC_HI = np.array([rc >> 32 for rc in _RC], dtype=np.uint32)

# rho rotation offsets r[x][y] and the pi lane permutation, flattened to lane
# index = x + 5y: for each destination lane, (source lane, rotation).
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_PI: list[tuple[int, int]] = [(0, 0)] * 25
for _x in range(5):
    for _y in range(5):
        _dst = _y + 5 * ((2 * _x + 3 * _y) % 5)
        _PI[_dst] = (_x + 5 * _y, _ROT[_x][_y])


def _chi1(i: int) -> int:
    return (i // 5) * 5 + ((i % 5) + 1) % 5


def _chi2(i: int) -> int:
    return (i // 5) * 5 + ((i % 5) + 2) % 5


def _rotl64(lo, hi, n: int):
    """Rotate a (lo, hi) uint32 pair left by static n."""
    n %= 64
    if n == 0:
        return lo, hi
    if n == 32:
        return hi, lo
    if n < 32:
        return (
            (lo << n) | (hi >> (32 - n)),
            (hi << n) | (lo >> (32 - n)),
        )
    n -= 32
    return (
        (hi << n) | (lo >> (32 - n)),
        (lo << n) | (hi >> (32 - n)),
    )


def _round(state, rc):
    """One Keccak-f round, LANE-MAJOR: state = (lo, hi), each a 25-tuple of
    [...] batch arrays.

    The batch lives in the MINOR axis (the 128-lane vector axis) exactly
    like the limb-major EC kernels: every theta/rho/pi/chi term is a full
    VPU-width elementwise op on a [B] vector, and all 25-lane indexing is
    static Python (unrolled), so XLA never relayouts a 25-wide minor axis
    — the previous [B, 25] layout wasted ~4/5 of each vector and paid a
    stack+roll relayout per round."""
    lo, hi = state
    rc_lo, rc_hi = rc
    # theta — column parities c[x] = xor over y of lane[x + 5y]
    c_lo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20] for x in range(5)]
    c_hi = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20] for x in range(5)]
    d = []
    for x in range(5):
        r_lo, r_hi = _rotl64(c_lo[(x + 1) % 5], c_hi[(x + 1) % 5], 1)
        d.append((c_lo[(x + 4) % 5] ^ r_lo, c_hi[(x + 4) % 5] ^ r_hi))
    lo = [lo[i] ^ d[i % 5][0] for i in range(25)]
    hi = [hi[i] ^ d[i % 5][1] for i in range(25)]
    # rho + pi — static per-lane rotations into permuted positions
    b_lo = [None] * 25
    b_hi = [None] * 25
    for dst, (src, rot) in enumerate(_PI):
        b_lo[dst], b_hi[dst] = _rotl64(lo[src], hi[src], rot)
    # chi — s[x + 5y] = b[x] ^ (~b[x+1] & b[x+2]) within each row y
    lo = [
        b_lo[i] ^ (~b_lo[_chi1(i)] & b_lo[_chi2(i)]) for i in range(25)
    ]
    hi = [
        b_hi[i] ^ (~b_hi[_chi1(i)] & b_hi[_chi2(i)]) for i in range(25)
    ]
    # iota
    lo[0] = lo[0] ^ rc_lo
    hi[0] = hi[0] ^ rc_hi
    return (tuple(lo), tuple(hi))


def keccak_f1600_lanes(lo, hi):
    """Keccak-f[1600] over lane-major state: 25-tuples of [...] batch
    arrays (scan over the 24 rounds)."""

    def body(state, rc):
        return _round(state, rc), None

    (lo, hi), _ = lax.scan(
        body, (tuple(lo), tuple(hi)), (jnp.asarray(_RC_LO), jnp.asarray(_RC_HI))
    )
    return lo, hi




@jax.jit
def keccak256_blocks(blocks: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Sponge over pre-padded blocks.

    blocks: [B, M, 17, 2] uint32 (rate lanes as lo/hi), nblocks: [B] int32.
    Returns digests as [B, 8] uint32 little-endian words.

    Internally lane-major: the state is 25 independent [B] vectors (batch
    in the VPU's minor axis), so the whole permutation is full-width
    elementwise work with static lane indexing — the one relayout left is
    the final 8-word squeeze."""
    bsz, m_max, lanes, _ = blocks.shape
    zeros = jnp.zeros((bsz,), jnp.uint32)
    lo0 = (zeros,) * 25
    hi0 = (zeros,) * 25

    def absorb(state, xs):
        lo, hi = state
        blk, idx = xs  # blk [17, 2, B]: lane rows are contiguous [B] slices
        alo = tuple(
            lo[l] ^ blk[l, 0] if l < lanes else lo[l] for l in range(25)
        )
        ahi = tuple(
            hi[l] ^ blk[l, 1] if l < lanes else hi[l] for l in range(25)
        )
        plo, phi = keccak_f1600_lanes(alo, ahi)
        active = idx < nblocks
        return (
            tuple(jnp.where(active, plo[l], lo[l]) for l in range(25)),
            tuple(jnp.where(active, phi[l], hi[l]) for l in range(25)),
        ), None

    # one up-front transpose to [M, 17, 2, B] so every absorbed lane is a
    # contiguous batch row inside the scan
    (lo, hi), _ = lax.scan(
        absorb,
        (lo0, hi0),
        (jnp.moveaxis(blocks, 0, -1), jnp.arange(m_max, dtype=jnp.int32)),
    )
    # squeeze 32 bytes = lanes 0..3 -> words [lo0, hi0, lo1, hi1, ...]
    out = jnp.stack(
        [lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], lo[3], hi[3]], axis=-1
    )
    return out


def keccak256_batch(msgs) -> np.ndarray:
    """Host convenience: list of bytes -> [B, 32] uint8 digests (device batch)."""
    from ..observability.device import device_span
    from .hash_common import bucket_batch

    n = len(msgs)
    # shape key approximates the compiled program (batch bucket only — the
    # message-block dim also shapes it, so compile counts are a lower bound)
    with device_span("keccak256", n, shape_key=bucket_batch(n)):
        return keccak256_batch_async(msgs)()


def keccak256_batch_async(msgs):
    """Dispatch the device batch and defer the sync: returns a resolver
    () -> [B, 32] uint8. Lets callers queue several hash programs (tx
    root, receipts root, state root) before paying any device round
    trip. The segments are marked on the enclosing device_span (the
    wrapper above, or the hash-plane executor's); a resolver called after
    that span has closed, on the caller's thread inside its
    ``device.plane.wait``, marks its sync and unpack as segments of their
    own."""
    from ..observability.device import device_phase

    n = len(msgs)
    with device_phase("marshal"):
        blocks, nblocks = pad_keccak(msgs)  # batch dim bucketed; slice below
    with device_phase("enqueue"):
        words = keccak256_blocks(jnp.asarray(blocks), jnp.asarray(nblocks))

    def resolve():
        with device_phase("sync", op="keccak256"):
            # analysis: allow(host-sync, deferred resolver — the sync happens
            # when the caller RESOLVES the plane future, not at dispatch)
            host = np.asarray(words)
        with device_phase("unpack", op="keccak256"):
            return digest_words_to_bytes_le(host)[:n]

    return resolve


# -- progaudit shape spec (analysis/progaudit: canonical audited bucket) -----
PROGSPEC = {
    "keccak256_blocks": {
        "bucket": 256,
        "inputs": lambda b: [((b, 1, 17, 2), "uint32"), ((b,), "int32")],
    },
}
