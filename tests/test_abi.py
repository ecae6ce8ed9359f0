"""ABI codec parity with the Solidity ABI spec (what the reference
ContractABICodec implements): golden head/tail vectors, tuples, fixed and
nested arrays, strict decode.

The hex vectors for f()/g()/sam() are the canonical worked examples from the
Solidity ABI specification — byte-for-byte what the reference codec (and any
EVM toolchain) produces.
"""

import pytest

from fisco_bcos_tpu.codec.abi import (
    ABICodec,
    abi_decode,
    abi_encode,
    parse_type,
    split_toplevel,
)
from fisco_bcos_tpu.crypto.ref.keccak import keccak256


def _hx(*words: str) -> bytes:
    return bytes.fromhex("".join(words))


W = "{:064x}".format  # one 32-byte big-endian word


def test_spec_vector_sam():
    # sam(bytes,bool,uint256[]) with ("dave", true, [1,2,3])
    expect = _hx(
        W(0x60),
        W(1),
        W(0xA0),
        W(4),
        "6461766500000000000000000000000000000000000000000000000000000000",
        W(3),
        W(1),
        W(2),
        W(3),
    )
    got = abi_encode(["bytes", "bool", "uint256[]"], [b"dave", True, [1, 2, 3]])
    assert got == expect
    assert abi_decode(["bytes", "bool", "uint256[]"], got) == [
        b"dave",
        True,
        [1, 2, 3],
    ]


def test_spec_vector_f():
    # f(uint256,uint32[],bytes10,bytes) with
    # (0x123, [0x456, 0x789], "1234567890", "Hello, world!")
    expect = _hx(
        W(0x123),
        W(0x80),
        "3132333435363738393000000000000000000000000000000000000000000000",
        W(0xE0),
        W(2),
        W(0x456),
        W(0x789),
        W(0xD),
        "48656c6c6f2c20776f726c642100000000000000000000000000000000000000",
    )
    types = ["uint256", "uint32[]", "bytes10", "bytes"]
    vals = [0x123, [0x456, 0x789], b"1234567890", b"Hello, world!"]
    got = abi_encode(types, vals)
    assert got == expect
    assert abi_decode(types, got) == vals


def test_spec_vector_g_nested_dynamic():
    # g(uint256[][],string[]) with ([[1,2],[3]], ["one","two","three"])
    expect = _hx(
        W(0x40),
        W(0x140),
        W(2),
        W(0x40),
        W(0xA0),
        W(2),
        W(1),
        W(2),
        W(1),
        W(3),
        W(3),
        W(0x60),
        W(0xA0),
        W(0xE0),
        W(3),
        "6f6e650000000000000000000000000000000000000000000000000000000000",
        W(3),
        "74776f0000000000000000000000000000000000000000000000000000000000",
        W(5),
        "7468726565000000000000000000000000000000000000000000000000000000",
    )
    types = ["uint256[][]", "string[]"]
    vals = [[[1, 2], [3]], ["one", "two", "three"]]
    got = abi_encode(types, vals)
    assert got == expect
    assert abi_decode(types, got) == vals


def test_tuple_head_tail_layout():
    # (uint256,(string,uint256[2]),bool) with (7, ("hi",[1,2]), true):
    # the tuple is dynamic (holds a string) -> one offset word in the head;
    # inside the tuple the string offset is relative to the TUPLE body
    types = ["uint256", "(string,uint256[2])", "bool"]
    vals = [7, ["hi", [1, 2]], True]
    expect = _hx(
        W(7),
        W(0x60),
        W(1),
        W(0x60),
        W(1),
        W(2),
        W(2),
        "6869000000000000000000000000000000000000000000000000000000000000",
    )
    got = abi_encode(types, vals)
    assert got == expect
    assert abi_decode(types, got) == vals


def test_static_tuple_and_fixed_arrays_inline():
    # all-static composites occupy their full width in the head, no offsets
    types = ["(uint128,uint128)", "uint256[3]", "bytes4"]
    vals = [[1, 2], [7, 8, 9], b"\xde\xad\xbe\xef"]
    got = abi_encode(types, vals)
    assert got == _hx(
        W(1), W(2), W(7), W(8), W(9),
        "deadbeef00000000000000000000000000000000000000000000000000000000",
    )
    assert abi_decode(types, got) == vals


def test_fixed_array_of_dynamic_elements():
    # string[2] is dynamic (elements are): offsets relative to its body
    types = ["string[2]"]
    vals = [["ab", "cde"]]
    got = abi_encode(types, vals)
    assert got == _hx(
        W(0x20),  # offset of the array body
        W(0x40),  # "ab" offset (relative to body)
        W(0x80),  # "cde"
        W(2),
        "6162000000000000000000000000000000000000000000000000000000000000",
        W(3),
        "6364650000000000000000000000000000000000000000000000000000000000",
    )
    assert abi_decode(types, got) == vals


@pytest.mark.parametrize(
    "types,vals",
    [
        (["(uint256,string)[]"], [[[1, "a"], [2, "bb"]]]),
        (["uint8[2][3]"], [[[1, 2], [3, 4], [5, 6]]]),
        (["(bool,(address,bytes))"], [[True, [b"\x11" * 20, b"xyz"]]]),
        (["int256[]", "string"], [[-5, 0, 7], "neg"]),
        (["bytes[]"], [[b"", b"\x00" * 33, b"q"]]),
        (["(uint256[],(string,bool))[2]"], [[[[1], ["x", True]], [[], ["", False]]]]),
    ],
)
def test_nested_roundtrip(types, vals):
    assert abi_decode(types, abi_encode(types, vals)) == vals


def test_parse_and_split():
    t = parse_type("(uint256,(string,bytes3)[2])[]")
    assert t.base == "array" and t.length == -1
    assert t.elem.base == "tuple" and t.elem.components[1].length == 2
    assert split_toplevel("uint256,(string,uint256[2]),bool") == [
        "uint256",
        "(string,uint256[2])",
        "bool",
    ]
    with pytest.raises(ValueError):
        parse_type("uint7")
    with pytest.raises(ValueError):
        parse_type("bytes33")
    with pytest.raises(ValueError):
        parse_type("(uint256")


def test_encode_rejects_bad_values():
    with pytest.raises(ValueError):
        abi_encode(["uint8"], [256])
    with pytest.raises(ValueError):
        abi_encode(["uint256"], [-1])
    with pytest.raises(ValueError):
        abi_encode(["int8"], [128])
    with pytest.raises(ValueError):
        abi_encode(["uint256[2]"], [[1]])
    with pytest.raises(ValueError):
        abi_encode(["(uint256,bool)"], [[1]])


def test_decode_strictness():
    good = abi_encode(["string"], ["hello"])
    with pytest.raises(ValueError):
        abi_decode(["string"], good[:-30])  # truncated tail
    bad_offset = bytes.fromhex(W(0x2000))
    with pytest.raises(ValueError):
        abi_decode(["string"], bad_offset)  # offset beyond calldata
    # declared array length far beyond the calldata must raise, not allocate
    huge = bytes.fromhex(W(0x20)) + bytes.fromhex(W(1 << 40))
    with pytest.raises(ValueError):
        abi_decode(["uint256[]"], huge)
    with pytest.raises(ValueError):
        abi_decode(["uint256", "uint256"], bytes.fromhex(W(1)))  # short head


def test_selector_and_call_roundtrip():
    codec = ABICodec(keccak256)
    # canonical spec selectors (keccak-based chains)
    assert codec.selector("sam(bytes,bool,uint256[])").hex() == "a5643bf2"
    assert codec.selector("f(uint256,uint32[],bytes10,bytes)").hex() == "8be65246"
    data = codec.encode_call(
        "h((uint256,string),address[])",
        [5, "five"],
        [b"\xaa" * 20],
    )
    assert data[:4] == codec.selector("h((uint256,string),address[])")
    assert codec.decode_input("h((uint256,string),address[])", data) == [
        [5, "five"],
        [b"\xaa" * 20],
    ]


# -- compiled coders against the generic coder --------------------------------
#
# ABICodec goes through a coder compiled once a signature or type list
# (codec.abi.coder_for): a direct form for lists of one-word types and
# string / bytes, the generic coder with its types parsed once for the rest.
# abi_encode / abi_decode stay the generic coder: the reference here.

import random  # noqa: E402

from fisco_bcos_tpu.codec.abi import Coder, coder_for  # noqa: E402

CODER_TYPE_LISTS = [
    # direct form: one-word types, string, bytes
    ["uint256"], ["uint8", "int16", "bool", "address", "bytes4"], ["string"], ["bytes"],
    ["string", "uint256"], ["string", "string", "uint256"], ["uint256", "uint256"],
    ["bytes32", "string", "int256", "bytes", "bool"], ["address", "uint8"], ["int32"], [],
    # generic form: arrays, nested arrays, tuples
    ["uint256[]"], ["uint8[3]"], ["string[]", "uint256"], ["uint256[][]", "string[]"],
    ["(uint256,string)", "address[]"], ["(uint256[],(string,bool))[2]"], ["bytes[2][]"],
    ["string", "(int8,bytes3)[]", "bool"],
]


def _random_value(rng: random.Random, t):
    if t.base == "uint":
        return rng.choice([0, 1, (1 << t.bits) - 1, rng.getrandbits(t.bits)])
    if t.base == "int":
        half = 1 << (t.bits - 1)
        return rng.choice([0, -1, -half, half - 1, rng.getrandbits(t.bits) - half])
    if t.base == "bool":
        return rng.random() < 0.5
    if t.base == "address":
        return rng.randbytes(20)
    if t.base == "fbytes":
        return rng.randbytes(t.bits)
    if t.base == "bytes":
        return rng.randbytes(rng.choice([0, 1, 31, 32, 33, 100]))
    if t.base == "string":
        return "".join(rng.choice("abcxyz é中0 ") for _ in range(rng.choice([0, 1, 31, 32, 40])))
    if t.base == "array":
        n = t.length if t.length >= 0 else rng.randrange(4)
        return [_random_value(rng, t.elem) for _ in range(n)]
    assert t.base == "tuple"
    return [_random_value(rng, c) for c in t.components]


@pytest.mark.parametrize("types", CODER_TYPE_LISTS, ids=lambda ts: ",".join(ts) or "empty")
def test_compiled_coder_matches_generic(types):
    rng = random.Random(",".join(types))
    coder = coder_for(tuple(types))
    assert coder is coder_for(tuple(types)), "built once a type list"
    codec = ABICodec(keccak256)
    signature = "f(" + ",".join(types) + ")"
    for _ in range(50):
        vals = [_random_value(rng, parse_type(t)) for t in types]
        want = abi_encode(types, vals)
        assert coder.encode(vals) == want
        assert coder.decode(want) == abi_decode(types, want)
        # the four codec methods every caller uses
        assert codec.encode_output(types, *vals) == want
        assert codec.decode_output(types, want) == abi_decode(types, want)
        call = codec.encode_call(signature, *vals)
        assert call == codec.selector(signature) + want
        assert codec.decode_input(signature, call) == abi_decode(types, want)
        # damaged input: both refuse, or both read the same values
        for damaged in (want[:-1], want[: len(want) // 2], want[:31],
                        _with_word(want, rng.randrange(max(1, len(want) // 32)),
                                   rng.choice([len(want), 1 << 255, len(want) - 1, 7]))):
            try:
                expect = abi_decode(types, damaged)
            except ValueError as e:
                with pytest.raises(ValueError) as caught:
                    coder.decode(damaged)
                assert str(caught.value) == str(e)
            else:
                assert coder.decode(damaged) == expect


def _with_word(data: bytes, index: int, value: int) -> bytes:
    """``data`` with its ``index``-th word replaced: a bad offset or length."""
    if len(data) < 32 * (index + 1):
        return data
    return data[: 32 * index] + value.to_bytes(32, "big") + data[32 * (index + 1):]


@pytest.mark.parametrize("types,vals", [
    (["uint8"], [256]), (["uint256"], [-1]), (["int8"], [128]), (["address"], [b"\x01" * 19]),
    (["bytes4"], [b"12345"]), (["uint256", "string"], [1]), (["string"], ["a", "b"]),
    (["uint256[2]"], [[1]]), (["(uint256,bool)"], [[1]]),
])
def test_compiled_coder_refuses_what_generic_refuses(types, vals):
    with pytest.raises(ValueError) as generic:
        abi_encode(types, vals)
    with pytest.raises(ValueError) as compiled:
        coder_for(tuple(types)).encode(vals)
    assert str(compiled.value) == str(generic.value)
    with pytest.raises(ValueError):
        ABICodec(keccak256).encode_output(types, *vals)


def test_coder_forms_and_unknown_types():
    direct, generic = Coder(("string", "uint256")), Coder(("uint256[]",))
    assert direct.encode.__qualname__.startswith("_direct_coder")
    assert generic.encode.__qualname__.startswith("_generic_coder")
    with pytest.raises(ValueError):
        coder_for(("uint7",))
    with pytest.raises(ValueError):
        ABICodec(keccak256).encode_call("f(fixed128x18)", 1)
    # a selector needs no coder: only the signature's bytes
    assert len(ABICodec(keccak256).selector("f(fixed128x18)")) == 4


def test_generators_user_add_call_is_pinned():
    """benchmark/generators/transfer_batches.py builds every chain cell's
    corpus through this call; `correct` replays the bytes."""
    codec = ABICodec(keccak256)
    assert codec.encode_call("userAdd(string,uint256)", "user-0000042", 1_000_000).hex() == (
        "3fe8e3f5"
        + W(0x40) + W(1_000_000) + W(12)
        + "757365722d303030303034320000000000000000000000000000000000000000"
    )
    assert codec.decode_input(
        "userAdd(string,uint256)",
        codec.encode_call("userAdd(string,uint256)", "user-0000042", 1_000_000),
    ) == ["user-0000042", 1_000_000]
