"""The deployed-contract cell's plain reference (``benchmark/refcontract.py``)
held to hand-worked vectors and to the deployed runtime on both engines of the
program, and the contract's files held to each other: the runtime's hex is its
listing assembled again, the ABI's selectors are keccak256 of its signatures,
its ``conflictFields`` are the configuration's."""

import json
import os
import random

import pytest

from benchmark import manifest, refcontract, refcrypto
from benchmark.generators import parallelok_batches as gen
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.evm import contract_table
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.transaction import Transaction
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.utils.metrics import REGISTRY

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)
CONFIG = manifest.config_of(manifest.load(), "air4-parallelok")
RUNTIME, ABI_TEXT = gen.contract_files(CONFIG["contract"])
CONTRACT = bytes(range(1, 21))
SENDER = b"\x0b" * 20
TOP = refcontract.MOD - 1


def wire(data: bytes, to: bytes = CONTRACT) -> bytes:
    return Transaction(to=to, input=data, sender=SENDER, nonce="n").encode()


def call(signature: str, *args) -> bytes:
    return wire(CODEC.encode_call(signature, *args))


# -- the contract's files, held to each other ---------------------------------


OPCODES = {
    "STOP": 0x00, "ADD": 0x01, "SUB": 0x03, "LT": 0x10, "EQ": 0x14, "SHR": 0x1C, "SHA3": 0x20,
    "CALLDATALOAD": 0x35, "CALLDATASIZE": 0x36, "CALLDATACOPY": 0x37, "POP": 0x50,
    "MSTORE": 0x52, "SLOAD": 0x54, "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57,
    "DUP1": 0x80, "DUP2": 0x81, "DUP3": 0x82, "SWAP1": 0x90, "RETURN": 0xF3, "REVERT": 0xFD,
}


def assembled(listing: str) -> bytes:
    """The listing's own grammar, two passes: ``name:`` is a JUMPDEST,
    ``PUSHn 0x..`` a literal of n bytes, ``PUSH2 @name`` a label's offset."""
    lines = [words for words in (line.split(";")[0].split() for line in listing.splitlines())
             if words]
    labels, at = {}, 0
    for words in lines:
        if words[0].endswith(":"):
            labels[words[0][:-1]] = at
        at += 1 + (int(words[0][4:]) if words[0].startswith("PUSH") else 0)
    out = bytearray()
    for words in lines:
        if words[0].endswith(":"):
            out.append(0x5B)
        elif words[0].startswith("PUSH"):
            width = int(words[0][4:])
            value = labels[words[1][1:]] if words[1].startswith("@") else int(words[1], 16)
            out += bytes([0x5F + width]) + value.to_bytes(width, "big")
        else:
            out.append(OPCODES[words[0]])
    return bytes(out)


def test_the_runtime_is_its_listing_assembled_again():
    with open(os.path.join(gen.CONTRACTS, CONFIG["contract"] + ".asm")) as f:
        assert assembled(f.read()) == RUNTIME
    assert len(RUNTIME) == 157


def test_the_abi_names_the_sources_functions_their_selectors_and_conflict_fields():
    abi = json.loads(ABI_TEXT)
    signatures = {
        f"{e['name']}({','.join(i['type'] for i in e['inputs'])})": e for e in abi}
    assert sorted(signatures) == sorted(CONFIG["functions"])
    for signature, entry in signatures.items():
        selector = refcrypto.keccak256(signature.encode())[:4]
        assert selector == CODEC.selector(signature)
        assert b"\x63" + selector in RUNTIME, f"the dispatch pushes {signature}'s selector"
        assert entry.get("conflictFields", []) == CONFIG["conflict_fields"].get(signature, [])
    assert (refcontract.SEL_TRANSFER, refcontract.SEL_SET, refcontract.SEL_BALANCE_OF) == tuple(
        refcrypto.keccak256(s.encode())[:4] for s in CONFIG["functions"])
    with open(os.path.join(gen.CONTRACTS, CONFIG["contract"] + ".sol")) as f:
        source = f.read()
    assert all(f"function {s.split('(')[0]}(" in source for s in CONFIG["functions"])


# -- hand-worked vectors --------------------------------------------------------


def test_a_balance_wraps_below_zero_and_above_the_top():
    balances, receipts = refcontract.replay([[
        call("set(string,uint256)", "a", 5),
        call("transfer(string,string,uint256)", "a", "b", 7),   # a: 5 - 7
        call("set(string,uint256)", "c", TOP),
        call("transfer(string,string,uint256)", "b", "c", 3),   # c: 2^256 - 1 + 3
    ]], CONTRACT)
    assert balances == {"a": refcontract.MOD - 2, "b": 4, "c": 2}
    assert receipts == [[(0, b"")] * 4]


def test_from_equal_to_nets_nothing_and_an_unknown_name_reads_zero():
    balances, receipts = refcontract.replay([[
        call("set(string,uint256)", "a", 9),
        call("transfer(string,string,uint256)", "a", "a", 4),
        call("balanceOf(string)", "a"),
        call("balanceOf(string)", "nobody"),
        call("transfer(string,string,uint256)", "ghost", "a", 1),  # 0 - 1 wraps
    ]], CONTRACT)
    assert balances == {"a": 10, "ghost": TOP}
    assert receipts[0][2] == (0, (9).to_bytes(32, "big"))
    assert receipts[0][3] == (0, bytes(32))


def test_what_the_dispatch_refuses_reverts_and_other_callees_are_left_alone():
    blocks = [[wire(b"\x12\x34\x56\x78" + bytes(64)), wire(b"\x9b\x80\xb0"), wire(b""),
               call("set(string,uint256)", "a", 1)],
              [wire(CODEC.encode_call("set(string,uint256)", "a", 2), to=b"\x77" * 20)]]
    balances, receipts = refcontract.replay(blocks, CONTRACT)
    assert balances == {"a": 1}
    assert receipts == [[(16, b""), (16, b""), (16, b""), (0, b"")], [None]]
    with pytest.raises(ValueError):
        refcontract.decode_call(wire(refcontract.SEL_SET + (64).to_bytes(32, "big")), CONTRACT)


def test_the_slot_is_soliditys_for_a_string_key_at_slot_zero():
    assert refcontract.slot_of("alice") == SUITE.hash(b"alice" + bytes(32))
    assert refcontract.slot_of("") == SUITE.hash(bytes(32))


# -- against the deployed runtime, both engines --------------------------------

NAMES = [f"n{i}" for i in range(40)] + ["", "a-name-longer-than-one-word-of-thirty-two-bytes-x"]


def seeded_calls(n: int, seed: int) -> list[Transaction]:
    rng = random.Random(seed)
    amounts = [0, 1, 7, 10, 999_999, 1 << 255, TOP, TOP - 3]
    txs = []
    for _ in range(n):
        kind = rng.random()
        if kind < 0.25:
            data = CODEC.encode_call("set(string,uint256)", rng.choice(NAMES), rng.choice(amounts))
        elif kind < 0.80:
            data = CODEC.encode_call("transfer(string,string,uint256)", rng.choice(NAMES),
                                     rng.choice(NAMES), rng.choice(amounts))
        elif kind < 0.95:
            data = CODEC.encode_call("balanceOf(string)", rng.choice(NAMES))
        elif kind < 0.98:
            data = rng.randbytes(4) + bytes(rng.randrange(0, 96))
        else:
            data = rng.randbytes(rng.randrange(0, 4))
        txs.append(Transaction(to=b"", input=data, sender=SENDER, nonce=f"s{len(txs)}"))
    return txs


def through_the_program(txs):
    """Deploy and the calls through the serial batch of a fresh executor ->
    (the contract's address, receipts, each name's storage row, state root,
    this run's EVM calls by engine)."""
    ex = TransactionExecutor(MemoryStorage(), SUITE)
    ex.next_block_header(BlockHeader(number=1))
    create = Transaction(to=b"", input=gen.creation_code(RUNTIME), sender=SENDER, abi=ABI_TEXT)
    (deployed,) = ex.execute_transactions([create])
    assert deployed.status == 0
    address = deployed.contract_address
    for tx in txs:
        tx.to = address
    before = {e: sum(REGISTRY.counters_matching(
        f'fisco_executor_evm_calls_total{{engine="{e}"}}').values()) for e in ("native", "interpreter")}
    receipts = ex.execute_transactions(txs)
    engines = {e: sum(REGISTRY.counters_matching(
        f'fisco_executor_evm_calls_total{{engine="{e}"}}').values()) - before[e] for e in before}
    rows = {}
    for name in NAMES:
        row = ex._block.storage.get_row(contract_table(address), refcontract.slot_of(name))
        rows[name] = 0 if row is None else int.from_bytes(row.get(), "big")
    return address, receipts, rows, ex.get_hash(), engines


def test_the_reference_is_the_runtime_on_both_engines(monkeypatch):
    txs = seeded_calls(2000, 2**31 + 4040)
    monkeypatch.delenv("FISCO_NO_NATIVE_EVM", raising=False)
    address, native, native_rows, native_root, engines = through_the_program(txs)
    assert engines == {"native": 2000, "interpreter": 0}, "no frame of the contract escapes"
    monkeypatch.setenv("FISCO_NO_NATIVE_EVM", "1")
    same, python, python_rows, python_root, engines = through_the_program(txs)
    assert engines == {"native": 0, "interpreter": 2000}
    assert same == address and python_root == native_root and python_rows == native_rows
    assert [(r.status, r.output, r.gas_used) for r in python] == [
        (r.status, r.output, r.gas_used) for r in native], "byte for byte, gas too"

    balances, receipts = refcontract.replay([[tx.encode() for tx in txs]], address)
    assert [(r.status, r.output) for r in native] == receipts[0]
    assert {name: balances.get(name, 0) for name in NAMES} == native_rows
    statuses = {r.status for r in native}
    assert statuses == {0, 16} and any(v > 1 << 255 for v in native_rows.values())
