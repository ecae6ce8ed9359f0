"""Host-side 256-bit limb/byte conversions + device digest→limb adapters.

Number layout: a 256-bit value is 16 little-endian 16-bit limbs. Host-side
(numpy) arrays here are **batch-major** ``[B, 16]`` — the stable public
layout of the crypto suite APIs; the device math core
(:mod:`fisco_bcos_tpu.ops.limb`) keeps the limb index on the leading axis
and the batch lane-dense behind it (``[16, S, 128]``), converting once at
its entry points.

The device-side converters keep hash → EC pipelines fused on device (the
reference round-trips through CPU byte buffers between OpenSSL EVP hashing
and wedpr EC calls instead).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LIMBS = 16  # 16 x 16-bit limbs = 256 bits
_R = 1 << 256


# ---------------------------------------------------------------------------
# Host-side conversions (numpy, exact Python ints)
# ---------------------------------------------------------------------------


def int_to_limbs(x: int) -> np.ndarray:
    """Python int -> [16] uint32 little-endian 16-bit limbs."""
    if not 0 <= x < _R:
        raise ValueError("int_to_limbs: out of range")
    return np.array([(x >> (16 * i)) & 0xFFFF for i in range(LIMBS)], dtype=np.uint32)


def limbs_to_int(a) -> int:
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(a[..., i]) << (16 * i) for i in range(a.shape[-1]))


def ints_to_limbs(xs) -> np.ndarray:
    """Iterable of ints -> [B, 16] uint32."""
    return np.stack([int_to_limbs(int(x)) for x in xs])


def limbs_to_ints(arr) -> list[int]:
    arr = np.asarray(arr)
    flat = arr.reshape(-1, arr.shape[-1])
    return [sum(int(row[i]) << (16 * i) for i in range(arr.shape[-1])) for row in flat]


def bytes_be_to_limbs(data: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 big-endian byte rows -> [B, 16] uint32 limbs (vectorized)."""
    data = np.asarray(data, dtype=np.uint8)
    pairs = data.reshape(*data.shape[:-1], 16, 2).astype(np.uint32)
    be16 = pairs[..., 0] * 256 + pairs[..., 1]
    return be16[..., ::-1].copy()


def limbs_to_bytes_be(limbs: np.ndarray) -> np.ndarray:
    """[B, 16] uint32 limbs -> [B, 32] uint8 big-endian byte rows."""
    limbs = np.asarray(limbs, dtype=np.uint32)[..., ::-1]
    hi = (limbs >> 8).astype(np.uint8)
    lo = (limbs & 0xFF).astype(np.uint8)
    return np.stack([hi, lo], axis=-1).reshape(*limbs.shape[:-1], 32)


# ---------------------------------------------------------------------------
# Device-side digest-word -> limb conversion (keeps hash -> EC pipelines
# fused on device)
# ---------------------------------------------------------------------------


def _bswap32(w: jax.Array) -> jax.Array:
    w = w.astype(jnp.uint32)
    return ((w & 0xFF) << 24) | ((w & 0xFF00) << 8) | ((w >> 8) & 0xFF00) | (w >> 24)


def _chunks32_be_to_limbs(chunks: jax.Array) -> jax.Array:
    """[..., 8] uint32 big-endian-ordered 32-bit chunks -> [..., 16] limbs."""
    rc = chunks[..., ::-1]  # chunk 7 holds the least-significant 32 bits
    lo = rc & 0xFFFF
    hi = rc >> 16
    return jnp.stack([lo, hi], axis=-1).reshape(*chunks.shape[:-1], LIMBS)


def digest_words_le_to_limbs(words: jax.Array) -> jax.Array:
    """Keccak digest words ([..., 8] uint32 little-endian byte order, digest
    read as a big-endian 256-bit integer) -> [..., 16] limbs, on device."""
    return _chunks32_be_to_limbs(_bswap32(words))


def digest_words_be_to_limbs(words: jax.Array) -> jax.Array:
    """SHA-256/SM3 digest words ([..., 8] uint32 big-endian) -> [..., 16] limbs."""
    return _chunks32_be_to_limbs(words.astype(jnp.uint32))


def limbs_to_words_be_device(limbs: jax.Array) -> jax.Array:
    """[..., 16] limbs -> [..., 8] uint32 big-endian words (the value's 32
    bytes as SHA-256/SM3 block words), on device: the inverse of
    :func:`digest_words_be_to_limbs`."""
    rev = limbs[..., ::-1].astype(jnp.uint32)  # the most significant limb first
    return (rev[..., 0::2] << 16) | rev[..., 1::2]


def limbs_to_bytes_device(limbs: jax.Array) -> jax.Array:
    """[..., 16] limbs -> [..., 32] big-endian bytes (uint32 lanes), on device."""
    rev = limbs[..., ::-1].astype(jnp.uint32)
    hi = rev >> 8
    lo = rev & 0xFF
    return jnp.stack([hi, lo], axis=-1).reshape(*limbs.shape[:-1], 32)
