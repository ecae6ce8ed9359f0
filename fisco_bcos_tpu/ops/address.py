"""Device-side sender-address derivation.

The reference computes the tx sender as right160(keccak256(uncompressed
pubkey)) on CPU after each single-signature recover (CryptoSuite.h:56-59,
``calculateAddress``; called from ``Transaction::verify()``
bcos-framework/bcos-framework/protocol/Transaction.h:64-84). Here the whole
batch of recovered pubkeys is hashed in one fused device program — a 64-byte
message plus keccak padding fits a single rate block, so ``nblocks`` is 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .bigint import limbs_to_bytes_device, limbs_to_words_be_device
from .keccak import keccak256_blocks
from .sm3 import sm3_of_word_pair

_RATE_BYTES = 136
_RATE_LANES = 17


def _bytes_to_blocks(msg_bytes: jax.Array) -> jax.Array:
    """[B, 136] uint32 byte values -> [B, 1, 17, 2] uint32 lane halves (the
    block tensor layout keccak256_blocks consumes)."""
    b = msg_bytes.astype(jnp.uint32).reshape(-1, 2 * _RATE_LANES, 4)
    w = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    return jnp.stack([w[..., 0::2], w[..., 1::2]], axis=-1)[:, None, :, :]


@jax.jit
def sender_address_device(qx: jax.Array, qy: jax.Array) -> jax.Array:
    """Batch address derivation: affine pubkey limbs ([B, 16] each, plain
    domain) -> [B, 20] uint32 address byte values.

    address = keccak256(qx_be32 ‖ qy_be32)[12:32]; multi-rate padding
    (0x01 at byte 64, 0x80 at byte 135) is applied inline.
    """
    batch = qx.shape[0]
    msg = jnp.zeros((batch, _RATE_BYTES), jnp.uint32)
    msg = msg.at[:, 0:32].set(limbs_to_bytes_device(qx))
    msg = msg.at[:, 32:64].set(limbs_to_bytes_device(qy))
    msg = msg.at[:, 64].set(0x01)
    msg = msg.at[:, 135].set(0x80)
    words = keccak256_blocks(
        _bytes_to_blocks(msg), jnp.ones((batch,), jnp.int32)
    )  # [B, 8] little-endian digest words
    idx = jnp.arange(12, 32)
    return (words[:, idx // 4] >> (8 * (idx % 4))) & 0xFF


def sm_sender_address_device(qx: jax.Array, qy: jax.Array) -> jax.Array:
    """The national-crypto suite's address, right160(SM3(Px ‖ Py)): affine
    pubkey limbs ([B, 16] each, plain domain) -> [B, 20] uint32 address byte
    values. Unjitted: it only runs inside the fused SM admission program
    (crypto.admission.sm_admission_core)."""
    words = sm3_of_word_pair(
        limbs_to_words_be_device(qx), limbs_to_words_be_device(qy)
    )  # [B, 8] big-endian digest words
    idx = jnp.arange(12, 32)
    return (words[:, idx // 4] >> (8 * (3 - idx % 4))) & 0xFF


# -- progaudit shape spec (analysis/progaudit: canonical audited bucket) -----
PROGSPEC = {
    "sender_address_device": {
        "bucket": 256,
        "inputs": lambda b: [((b, 16), "uint32")] * 2,
    },
}
