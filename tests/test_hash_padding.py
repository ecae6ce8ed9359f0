"""The padders of ops/hash_common.py build a batch's block tensor by array
(one join, one gather, indexed stores). The plain reference they are held to,
byte for byte with dtype, shape and nblocks, is padding one message at a time:
the loop the padders were until PR 29, kept here and nowhere else."""

import numpy as np
import pytest

from fisco_bcos_tpu.ops.hash_common import bucket_batch, pad_keccak, pad_md64

KECCAK_RATE = 136


def _loop_keccak(msgs, rate=KECCAK_RATE):
    b_pad = bucket_batch(max(len(msgs), 1))
    nblocks = np.array(
        [len(m) // rate + 1 for m in msgs] + [1] * (b_pad - len(msgs)), dtype=np.int32
    )
    m_max = bucket_batch(int(nblocks.max()))
    buf = np.zeros((b_pad, m_max * rate), dtype=np.uint8)
    for i, m in enumerate(msgs):
        buf[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        buf[i, len(m)] ^= 0x01
        buf[i, nblocks[i] * rate - 1] ^= 0x80
    buf[len(msgs):, 0] = 0x01  # pad rows: the padded empty message
    buf[len(msgs):, rate - 1] = 0x80
    words = buf.view("<u4").reshape(b_pad, m_max, rate // 8, 2)
    return words.astype(np.uint32), nblocks


def _loop_md64(msgs):
    b_pad = bucket_batch(max(len(msgs), 1))
    nblocks = np.array(
        [(len(m) + 8) // 64 + 1 for m in msgs] + [1] * (b_pad - len(msgs)), dtype=np.int32
    )
    m_max = bucket_batch(int(nblocks.max()))
    buf = np.zeros((b_pad, m_max * 64), dtype=np.uint8)
    for i, m in enumerate(msgs):
        buf[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        buf[i, len(m)] = 0x80
        end = nblocks[i] * 64
        buf[i, end - 8 : end] = np.frombuffer(
            (len(m) * 8).to_bytes(8, "big"), dtype=np.uint8
        )
    buf[len(msgs):, 0] = 0x80  # pad rows: empty message, zero bit length
    return buf.view(">u4").reshape(b_pad, m_max, 16).astype(np.uint32), nblocks


PADDERS = {"keccak": (pad_keccak, _loop_keccak), "md64": (pad_md64, _loop_md64)}
# where the padding's bytes change blocks: the 0x01/0x80 pair around the rate,
# and the 0x80 and the length field around the 64-byte block
EDGES = {
    "keccak": [0, 1, KECCAK_RATE - 1, KECCAK_RATE, KECCAK_RATE + 1,
               2 * KECCAK_RATE - 1, 2 * KECCAK_RATE],
    "md64": [0, 1, 55, 56, 63, 64, 119, 120],
}


def _rand(n, length, seed=29):
    rng = np.random.default_rng([seed, n, length])
    return [rng.bytes(length) for _ in range(n)]


def _ragged(n, top, seed=29):
    rng = np.random.default_rng([seed, n, top])
    return [rng.bytes(int(k)) for k in rng.integers(0, top, size=n)]


def _batches(kind):
    """(id, messages) for one padder; the ids name the case of ISSUE 29."""
    edges = EDGES[kind]
    yield "empty_list", []
    yield "one_empty_message", [b""]
    for length in edges:
        yield f"one_message_of_{length}", _rand(1, length)
    yield "every_edge_in_one_batch", [m for k in edges for m in _rand(1, k)]
    # the longest message takes 5 blocks (keccak) or 11 (md64): M is bucketed
    # above it, and every shorter row is zero behind its own last block
    yield "ragged_longest_sets_bucketed_m", _ragged(37, 300) + _rand(1, 5 * 128 + 60)
    yield "not_a_bucket_size", _rand(5, 106)
    yield "bytearray_and_memoryview", [bytearray(b"ab" * 40), memoryview(b"c" * 7), b""]


CASES = [
    pytest.param(kind, msgs, ladder, id=f"{kind}-{name}-{ladder}")
    for kind in PADDERS
    for name, msgs in _batches(kind)
    for ladder in ("production_ladder", "test_bucket")
]


def _assert_equals_the_loop(kind, msgs, ladder, monkeypatch):
    if ladder == "production_ladder":
        monkeypatch.delenv("FISCO_TEST_BUCKET", raising=False)
    else:
        monkeypatch.setenv("FISCO_TEST_BUCKET", "32")
    pad, loop = PADDERS[kind]
    blocks, nblocks = pad(msgs)
    want_blocks, want_nblocks = loop(msgs)
    assert blocks.dtype == want_blocks.dtype == np.uint32
    assert nblocks.dtype == want_nblocks.dtype == np.int32
    assert blocks.shape == want_blocks.shape and nblocks.shape == want_nblocks.shape
    assert blocks.flags.c_contiguous and blocks.flags.writeable
    np.testing.assert_array_equal(nblocks, want_nblocks)
    np.testing.assert_array_equal(blocks, want_blocks)
    # the rows behind the batch are the padded empty message, block 0 only
    empty = loop([b""])[0][0, 0]
    n = len(msgs)
    assert (nblocks[n:] == 1).all()
    assert (blocks[n:, 0] == empty).all() and not blocks[n:, 1:].any()


@pytest.mark.parametrize("kind,msgs,ladder", CASES)
def test_padder_equals_the_per_message_loop(kind, msgs, ladder, monkeypatch):
    _assert_equals_the_loop(kind, msgs, ladder, monkeypatch)


@pytest.mark.parametrize("kind", PADDERS)
def test_padder_equals_the_loop_on_a_block_of_10000(kind, monkeypatch):
    """The stream cells' block: 10,000 payloads of 106 bytes, bucket 10,240
    (made here and not at collection, which every worker pays)."""
    _assert_equals_the_loop(kind, _rand(10_000, 106), "production_ladder", monkeypatch)


@pytest.mark.parametrize("kind", PADDERS)
def test_padder_leaves_its_input_alone_and_repeats(kind):
    pad, _ = PADDERS[kind]
    msgs = _ragged(9, 200)
    before = [bytes(m) for m in msgs]
    first, again = pad(msgs), pad(msgs)
    assert msgs == before
    np.testing.assert_array_equal(first[0], again[0])
    first[0][...] = 0  # a caller may write into what it was given
    np.testing.assert_array_equal(pad(msgs)[0], again[0])
