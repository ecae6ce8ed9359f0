"""Plain reference for the ``air4-parallelok`` configuration's `correct`: what a
chain of calls to a deployed ``ParallelOk`` leaves behind, worked out from the
blocks' bytes alone, one transaction after another in block order.

It follows the Solidity source (``benchmark/contracts/ParallelOk.sol``), not
the bytecode, and knows no EVM: ``mapping(string => uint256) _balance`` is a
dict name -> int; ``set`` assigns; ``transfer`` is ``_balance[from] -= num;
_balance[to] += num`` modulo 2^256 with ``from`` stored before ``to`` is read
(so ``from == to`` nets nothing) and no check of either (solc 0.6: "overflow
is ok"); ``balanceOf`` returns the word; a name never written reads 0. A call
succeeds with status 0 and an empty output (``balanceOf``: the 32-byte word);
calldata shorter than a selector, or an unknown selector, reverts with status
16 (the node's ``REVERT_INSTRUCTION``) and an empty output, changing nothing.
The storage slot of ``_balance[name]`` is Solidity's for a mapping at slot 0
with a ``string`` key: ``keccak256(bytes(name) ++ uint256(0))``.

Departures from the source, each noted here and under ``assumed`` in the
configuration's file: arguments that are not well-formed ABI (a string whose
offset or length points outside the calldata) raise ``ValueError`` here, where
solc's decoder reverts and the hand-assembled runtime reads zeros; the traffic
has none. Gas is not modelled: the two engines of the program are held to each
other on it (``tests/benchmark_checks/test_refcontract.py``).

It imports ``refcrypto.py`` (keccak256: the three selectors and the slots) and
``reftransfer.py``'s readers of the wire layout and of the ABI head/tail
layout, which import nothing of the program either."""

from __future__ import annotations

from benchmark import refcrypto
from benchmark.reftransfer import _string, _word, call_of

MOD = 1 << 256
SEL_TRANSFER = refcrypto.keccak256(b"transfer(string,string,uint256)")[:4]
SEL_SET = refcrypto.keccak256(b"set(string,uint256)")[:4]
SEL_BALANCE_OF = refcrypto.keccak256(b"balanceOf(string)")[:4]
OK, REVERT = 0, 16  # receipt statuses


def slot_of(name: str) -> bytes:
    """The 32-byte storage key of ``_balance[name]``."""
    return refcrypto.keccak256(name.encode() + bytes(32))


def decode_call(wire: bytes, contract: bytes):
    """-> ("set", name, num), ("transfer", from, to, num), ("balanceOf", name),
    ("revert",) for calldata the dispatch refuses, or None where the
    transaction is not a call to ``contract``."""
    to, call = call_of(wire)
    if to != contract:
        return None
    selector, args = call[:4], call[4:]
    if len(selector) == 4:
        if selector == SEL_TRANSFER:
            return "transfer", _string(args, 0), _string(args, 1), _word(args, 2)
        if selector == SEL_SET:
            return "set", _string(args, 0), _word(args, 1)
        if selector == SEL_BALANCE_OF:
            return "balanceOf", _string(args, 0)
    return ("revert",)


def apply(balances: dict[str, int], call) -> tuple[int, bytes] | None:
    """One decoded call on the dict -> its receipt's (status, output); None:
    not a call to the contract, nothing changes."""
    if call is None:
        return None
    if call[0] == "set":
        balances[call[1]] = call[2]
    elif call[0] == "transfer":
        _, payer, payee, num = call
        balances[payer] = (balances.get(payer, 0) - num) % MOD
        balances[payee] = (balances.get(payee, 0) + num) % MOD
    elif call[0] == "balanceOf":
        return OK, balances.get(call[1], 0).to_bytes(32, "big")
    else:
        return REVERT, b""
    return OK, b""


def replay(blocks: list[list[bytes]], contract: bytes, balances: dict[str, int] | None = None):
    """Blocks of wire transactions, in chain order -> (balances after the
    last, every transaction's (status, output) as ``receipts[block][index]``)."""
    balances = {} if balances is None else balances
    receipts = [[apply(balances, decode_call(wire, contract)) for wire in block]
                for block in blocks]
    return balances, receipts
