"""``benchmark/reftransfer.py``, the plain replay that decides `correct` in the
parallel-transfer cell: hand-worked vectors for every return code of
``userAdd`` and ``userTransfer``, the bytes it reads written out by hand, and
the precompiled contract itself on a seeded random list of 2,000 calls."""

import random
import struct

import pytest

from benchmark import refcrypto, reftransfer as ref

SEED = 2**31 + 3200


def word(n: int) -> bytes:
    return n.to_bytes(32, "big")


def tail(s: str) -> bytes:
    raw = s.encode()
    return word(len(raw)) + raw + bytes(-len(raw) % 32)


def add_call(user: str, amount: int) -> bytes:
    """``userAdd(string,uint256)`` by hand: one offset, the amount, the tail."""
    return ref.SEL_ADD + word(64) + word(amount) + tail(user)


def transfer_call(payer: str, payee: str, amount: int) -> bytes:
    first = tail(payer)
    return (ref.SEL_TRANSFER + word(96) + word(96 + len(first)) + word(amount)
            + first + tail(payee))


def wire(call: bytes, to: bytes = ref.DAG_TRANSFER, nonce: str = "n") -> bytes:
    """A wire transaction by hand: the signed bytes, a signature, the
    annotations (``codec/flat.py``: little-endian, length-prefixed)."""
    def blob(b: bytes) -> bytes:
        return struct.pack("<I", len(b)) + b

    data = (struct.pack("<I", 1) + blob(b"chain0") + blob(b"group0") + struct.pack("<q", 500)
            + blob(nonce.encode()) + blob(to) + blob(call) + blob(b""))
    return blob(data) + blob(bytes(65)) + struct.pack("<I", 4) + struct.pack("<q", 0) + blob(b"")


def test_the_selectors_are_the_signatures_keccak():
    assert ref.SEL_ADD == refcrypto.keccak256(b"userAdd(string,uint256)")[:4]
    assert ref.SEL_TRANSFER.hex() == refcrypto.keccak256(
        b"userTransfer(string,string,uint256)")[:4].hex()
    assert ref.DAG_TRANSFER == (0x100C).to_bytes(20, "big")


def test_calls_are_decoded_from_the_bytes():
    assert ref.decode_call(wire(add_call("alice", 7))) == ("add", "alice", 7)
    long_name = "n" * 45  # a name of two words
    assert ref.decode_call(wire(transfer_call(long_name, "bob", 2**200))) == (
        "transfer", long_name, "bob", 2**200)
    assert ref.decode_call(wire(transfer_call("", "", 0))) == ("transfer", "", "", 0)
    # another contract, another function: not a call of the two
    assert ref.decode_call(wire(add_call("alice", 7), to=bytes(20))) is None
    assert ref.decode_call(wire(b"\x12\x34\x56\x78" + word(0))) is None
    with pytest.raises(ValueError):
        ref.decode_call(wire(ref.SEL_ADD + word(64)))  # no amount, no tail
    with pytest.raises(ValueError):
        ref.decode_call(wire(ref.SEL_ADD + word(4096) + word(1)))  # offset outside


ADD_VECTORS = [
    # (balances before, user, amount) -> (code, balances after)
    ({}, "alice", 5, 0, {"alice": 5}),
    ({}, "", 5, 1, {}),
    ({"alice": 5}, "alice", 9, 2, {"alice": 5}),  # the first write wins
    ({}, "zero", 0, 0, {"zero": 0}),
]

TRANSFER_VECTORS = [
    # (before, payer, payee, amount) -> (code, after)
    ({"a": 10, "b": 1}, "a", "b", 4, 0, {"a": 6, "b": 5}),
    ({"a": 10, "b": 1}, "a", "b", 10, 0, {"a": 0, "b": 11}),  # to the last unit
    ({"a": 10, "b": 1}, "", "b", 4, 1, {"a": 10, "b": 1}),
    ({"a": 10, "b": 1}, "a", "", 4, 1, {"a": 10, "b": 1}),
    ({"b": 1}, "a", "b", 4, 2, {"b": 1}),  # no payer
    ({"a": 10}, "a", "b", 4, 3, {"a": 10}),  # no payee
    ({"a": 3, "b": 1}, "a", "b", 4, 4, {"a": 3, "b": 1}),  # would overdraw: nothing moves
    ({"a": 3}, "a", "b", 4, 4, {"a": 3}),  # overdrawn is answered before the payee is looked up
    ({}, "a", "a", 4, 2, {}),  # payer = payee, not there
    ({"a": 10}, "a", "a", 4, 0, {"a": 10}),  # payer = payee: accepted, nothing moves
    ({"a": 3}, "a", "a", 4, 4, {"a": 3}),  # payer = payee, overdrawn
    ({"a": 10, "b": ref.U256_MAX - 3}, "a", "b", 4, 5, {"a": 10, "b": ref.U256_MAX - 3}),
    ({"a": 10, "b": ref.U256_MAX - 4}, "a", "b", 4, 0, {"a": 6, "b": ref.U256_MAX}),  # the edge
    ({"a": 10, "b": 1}, "a", "b", 0, 0, {"a": 10, "b": 1}),
]


@pytest.mark.parametrize("before,user,amount,code,after", ADD_VECTORS)
def test_user_add_by_hand(before, user, amount, code, after):
    balances = dict(before)
    assert ref.user_add(balances, user, amount) == code and balances == after


@pytest.mark.parametrize("before,payer,payee,amount,code,after", TRANSFER_VECTORS)
def test_user_transfer_by_hand(before, payer, payee, amount, code, after):
    balances = dict(before)
    assert ref.user_transfer(balances, payer, payee, amount) == code and balances == after
    # the same through the bytes
    balances = dict(before)
    got, codes = ref.replay([[wire(transfer_call(payer, payee, amount))]], balances)
    assert codes == [[code]] and got == after


def test_replay_walks_blocks_in_order_and_skips_other_calls():
    blocks = [
        [wire(add_call("a", 5)), wire(add_call("b", 1)), wire(add_call("a", 99))],
        [wire(transfer_call("a", "b", 5)), wire(transfer_call("a", "b", 1)),
         wire(b"\xde\xad\xbe\xef"), wire(transfer_call("b", "c", 1))],
        [wire(add_call("c", 0)), wire(transfer_call("b", "c", 6)), wire(transfer_call("b", "a", 1))],
    ]
    balances, codes = ref.replay(blocks)
    assert codes == [[0, 0, 2], [0, 4, None, 3], [0, 0, 4]]
    assert balances == {"a": 0, "b": 0, "c": 6}
    # order is the result: the same transactions the other way round
    balances, codes = ref.replay([list(reversed(b)) for b in reversed(blocks)])
    assert balances != {"a": 0, "b": 0, "c": 6}


def test_against_the_precompiled_contract_on_2000_seeded_calls():
    """The contract the chain runs (``DagTransferPrecompiled``), one call
    after another on one state, against the dict: every return code and every
    balance. Names include the empty one and one that is never opened;
    amounts reach past the balances, and one account sits at the overflow edge."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.executor.precompiled.base import PrecompiledCallContext
    from fisco_bcos_tpu.executor.precompiled.bench_contracts import DagTransferPrecompiled
    from fisco_bcos_tpu.storage import MemoryStorage
    from fisco_bcos_tpu.storage.state_storage import StateStorage

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    contract = DagTransferPrecompiled()
    state = StateStorage(MemoryStorage())
    ctx = PrecompiledCallContext(storage=state, suite=suite, codec=codec, to=DAG_TRANSFER_ADDRESS)
    assert DAG_TRANSFER_ADDRESS == ref.DAG_TRANSFER

    rng = random.Random(SEED)
    names = [f"acct-{i}" for i in range(24)] + ["", "never-opened", "edge"]
    calls = [("userAdd(string,uint256)", ("edge", ref.U256_MAX - 20))]
    for _ in range(1999):
        if rng.random() < 0.15:
            calls.append(("userAdd(string,uint256)", (rng.choice(names), rng.randrange(0, 60))))
        else:
            calls.append(("userTransfer(string,string,uint256)",
                          (rng.choice(names), rng.choice(names), rng.randrange(0, 40))))
    balances: dict[str, int] = {}
    seen_codes = set()
    for n, (sig, args) in enumerate(calls):
        data = codec.encode_call(sig, *args)
        got = int.from_bytes(contract.call(ctx, data).output, "big")
        want = ref.apply(balances, ref.decode_call(wire(data, nonce=str(n))))
        assert got == want, (n, sig, args)
        seen_codes.add((sig[:8], got))
    assert {c for s, c in seen_codes if s == "userAdd("} == {0, 1, 2}
    assert {c for s, c in seen_codes if s == "userTran"} == {0, 1, 2, 3, 4, 5}
    for name in names:
        row = state.get_row("dag_transfer", name.encode())
        held = None if row is None else int(row.get("balance").decode())
        assert held == balances.get(name), name
