"""Batch SM3 (GB/T 32905) on TPU — the 国密 hash for sm_crypto chains.

Reference counterpart: bcos-crypto hash/SM3.h (OpenSSL-tassl EVP), hot in tx
hashing, state roots and merkle when the chain runs SM2/SM3 suites.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .hash_common import digest_words_to_bytes_be, pad_md64

_IV = np.array(
    [0x7380166F, 0x4914B2B9, 0x172442D7, 0xDA8A0600,
     0xA96F30BC, 0x163138AA, 0xE38DEE4D, 0xB0FB0E4E],
    dtype=np.uint32,
)

def _rotl_int(v: int, n: int) -> int:
    n %= 32
    return ((v << n) | (v >> (32 - n))) & 0xFFFFFFFF


# Tj <<< j precomputed for the 64 rounds
_TJ = np.array(
    [_rotl_int(0x79CC4519 if j < 16 else 0x7A879D8A, j) for j in range(64)],
    dtype=np.uint32,
)


def _rotl(x, n: int):
    n %= 32
    if n == 0:
        return x
    return (x << n) | (x >> (32 - n))


def _p0(x):
    return x ^ _rotl(x, 9) ^ _rotl(x, 17)


def _p1(x):
    return x ^ _rotl(x, 15) ^ _rotl(x, 23)


def _schedule(block):
    """block [B, 16] -> (W [68, B], W1 [64, B]), unrolled over per-word
    [B] vectors (batch in the VPU minor axis; the scanned [B, 16] window
    version paid a minor-axis concat relayout per step)."""
    words = [block[:, i] for i in range(16)]
    for t in range(52):
        words.append(
            _p1(words[t] ^ words[t + 7] ^ _rotl(words[t + 13], 15))
            ^ _rotl(words[t + 3], 7)
            ^ words[t + 10]
        )
    w = jnp.stack(words, axis=0)  # [68, B]
    w1 = w[:64] ^ w[4:68]
    return w, w1


def _round(carry, xs):
    """One of the 64 rounds, on eight [B] registers: a `lax.scan` step."""
    a, b, c, d, e, f, g, h = carry
    tj, wt, w1t, j16 = xs
    a12 = _rotl(a, 12)
    ss1 = _rotl(a12 + e + tj, 7)
    ss2 = ss1 ^ a12
    ff_lin = a ^ b ^ c
    ff_maj = (a & b) | (a & c) | (b & c)
    gg_lin = e ^ f ^ g
    gg_ch = (e & f) | (~e & g)
    ff = jnp.where(j16, ff_maj, ff_lin)
    gg = jnp.where(j16, gg_ch, gg_lin)
    tt1 = ff + d + ss2 + w1t
    tt2 = gg + h + ss1 + wt
    return (tt1, a, _rotl(b, 9), c, _p0(tt2), e, _rotl(f, 19), g), None


_J16 = np.arange(64) >= 16


def _compress(state, block):
    """state [B, 8], block [B, 16] -> new state [B, 8]."""
    w, w1 = _schedule(block)

    # a step function of this call's own, as it has always been: a trace that
    # hashes more than once (the SM admission program, five times) then lowers
    # to the module it always has, symbol for symbol, and finds its compiled
    # program in the cache; `_round` itself as the step would share one body
    def rnd(carry, xs):
        return _round(carry, xs)

    init = tuple(state[:, i] for i in range(8))
    out, _ = lax.scan(rnd, init, (jnp.asarray(_TJ), w[:64], w1, jnp.asarray(_J16)))
    return state ^ jnp.stack(out, axis=1)


def _compress_rolling(state, block, unroll: int):
    """`_compress` with the message expanded as the rounds go: the scan
    carries the sixteen words the next rounds read beside the registers, so a
    compression is 64 / unroll loop steps and nothing else. For a batch a
    vector register wide or less (a merkle level), whose time on the chip is
    the device ops it launches (PERF.md §6, PR 45), and for XLA-CPU, which
    takes 2 s to compile `_schedule`'s 68 stacked words wherever they are
    traced; `_compress` keeps that one pass for the wide batches."""

    def step(carry, xs):
        regs, win = carry
        tj, j16 = xs
        regs, _ = _round(regs, (tj, win[0], win[0] ^ win[4], j16))
        nxt = (
            _p1(win[0] ^ win[7] ^ _rotl(win[13], 15)) ^ _rotl(win[3], 7) ^ win[10]
        )
        return (regs, (*win[1:], nxt)), None

    init = (
        tuple(state[:, i] for i in range(8)),
        tuple(block[:, i] for i in range(16)),
    )
    (out, _), _ = lax.scan(
        step, init, (jnp.asarray(_TJ), jnp.asarray(_J16)), unroll=unroll
    )
    return state ^ jnp.stack(out, axis=1)


@jax.jit
def sm3_blocks(blocks: jax.Array, nblocks: jax.Array) -> jax.Array:
    """blocks [B, M, 16] uint32 BE words, nblocks [B] -> digests [B, 8] uint32."""
    bsz, m_max, _ = blocks.shape
    state0 = jnp.broadcast_to(jnp.asarray(_IV), (bsz, 8))

    def absorb(state, xs):
        blk, idx = xs
        new = _compress(state, blk)
        return jnp.where((idx < nblocks)[:, None], new, state), None

    state, _ = lax.scan(
        absorb,
        state0,
        (jnp.moveaxis(blocks, 1, 0), jnp.arange(m_max, dtype=jnp.int32)),
    )
    return state


def sm3_absorb(blocks: jax.Array, unroll: int) -> jax.Array:
    """SM3 inside a jit trace, every lane absorbing all of its blocks from
    the IV, no mask: blocks [B, M, 16] padded big-endian words -> digests
    [B, 8] uint32. The fused merkle tree's levels (ops/merkle) hash this way:
    at most 64 lanes wide, their time is device ops launched and not
    arithmetic, so they ask for the round scan unrolled ``unroll`` times."""
    state0 = jnp.broadcast_to(jnp.asarray(_IV), (blocks.shape[0], 8))
    state, _ = lax.scan(
        lambda state, blk: (_compress_rolling(state, blk, unroll), None),
        state0,
        jnp.moveaxis(blocks, 1, 0),
    )
    return state


# the padding block of any 64-byte message: 0x80, zeros, bit length 512
_TAIL64 = np.array([0x80000000] + [0] * 14 + [512], dtype=np.uint32)


# analysis: allow(shape-bucket) — runs INSIDE jit traces (the fused SM
# admission, sm2._e_xla) whose batch their host wrappers already bucketed. It
# calls the jitted sm3_blocks and not its body on purpose: as calls of one
# function the program's five hashes ran 6.8 % faster on the chip than traced
# inline (431 against 459 ms a 10,240-lane block: PERF.md §6, PR 26)
def sm3_fixed(blocks: jax.Array) -> jax.Array:
    """SM3 of messages assembled on the device, every lane as long as the
    next: blocks [B, M, 16] padded big-endian words, all M absorbed ->
    digests [B, 8] uint32."""
    bsz, m = blocks.shape[:2]
    return sm3_blocks(blocks, jnp.full((bsz,), m, jnp.int32))


def sm3_of_word_pair(a: jax.Array, b: jax.Array) -> jax.Array:
    """SM3(a ‖ b) for two [B, 8] big-endian word tensors: a 64-byte message
    is one data block and one constant padding block. SM2's e = SM3(ZA ‖ M)
    and the sender address SM3(Px ‖ Py) are hashed this way without leaving
    the device."""
    tail = jnp.broadcast_to(jnp.asarray(_TAIL64), (a.shape[0], 16))
    return sm3_fixed(jnp.stack([jnp.concatenate([a, b], axis=1), tail], axis=1))


def sm3_batch(msgs) -> np.ndarray:
    """Host convenience: list of bytes -> [B, 32] uint8 digests (device batch)."""
    from ..observability.device import device_span

    # the default shape key is the batch bucket — it approximates the
    # compiled program (the message-block dim also shapes it, so compile
    # counts are a lower bound)
    with device_span("sm3", len(msgs)):
        return sm3_batch_async(msgs)()


def sm3_batch_async(msgs):
    """Dispatch the device batch and defer the sync: returns a resolver
    () -> [B, 32] uint8. Lets callers queue several hash programs (tx
    root, receipts root, state root) before paying any device round
    trip."""
    n = len(msgs)
    blocks, nblocks = pad_md64(msgs)  # batch dim bucketed; slice below
    words = sm3_blocks(jnp.asarray(blocks), jnp.asarray(nblocks))
    # analysis: allow(host-sync, deferred resolver — the sync happens when
    # the caller RESOLVES the plane future, not at dispatch)
    return lambda: digest_words_to_bytes_be(np.asarray(words))[:n]


# -- progaudit shape spec (analysis/progaudit: canonical audited bucket) -----
PROGSPEC = {
    "sm3_blocks": {
        "bucket": 256,
        "inputs": lambda b: [((b, 1, 16), "uint32"), ((b,), "int32")],
    },
}
