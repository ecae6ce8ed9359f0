"""Plain reference for the parallel-transfer configuration's `correct`: what a
chain of DagTransfer blocks leaves behind, worked out from the blocks' bytes
alone, one transaction after another in block order.

A dict user -> balance is replayed from wire transactions: ``userAdd`` with
its three return codes, ``userTransfer`` with its five (and the empty name
both share), every other call left alone. The serial replay *is* the
guarantee: conflict-DAG execution has to leave the state, and the return code
of every receipt, that executing the block's transactions one by one leaves.

It imports ``refcrypto.py`` (keccak256, for the two selectors) and nothing of
the program: the wire layout (little-endian lengths, ``codec/flat.py``) and
the ABI head/tail layout of the two calls are written out again below."""

from __future__ import annotations

import struct

from benchmark import refcrypto

DAG_TRANSFER = bytes.fromhex("000000000000000000000000000000000000100c")
U256_MAX = (1 << 256) - 1
SEL_ADD = refcrypto.keccak256(b"userAdd(string,uint256)")[:4]
SEL_TRANSFER = refcrypto.keccak256(b"userTransfer(string,string,uint256)")[:4]

# return codes (the precompiled contract's ``uint256`` output)
OK, EMPTY_NAME = 0, 1
ADD_EXISTS = 2
NO_PAYER, NO_PAYEE, INSUFFICIENT, OVERFLOW = 2, 3, 4, 5


# -- the bytes, read again ------------------------------------------------------


def _blob(buf: bytes, off: int) -> tuple[bytes, int]:
    (n,) = struct.unpack_from("<I", buf, off)
    if off + 4 + n > len(buf):
        raise ValueError("truncated")
    return buf[off + 4:off + 4 + n], off + 4 + n


def call_of(wire: bytes) -> tuple[bytes, bytes]:
    """A wire transaction -> (to, input) of its signed bytes."""
    data, _ = _blob(wire, 0)
    off = 4  # version
    _chain, off = _blob(data, off)
    _group, off = _blob(data, off)
    off += 8  # block limit
    _nonce, off = _blob(data, off)
    to, off = _blob(data, off)
    call, off = _blob(data, off)
    return to, call


def _word(args: bytes, i: int) -> int:
    if 32 * i + 32 > len(args):
        raise ValueError("short arguments")
    return int.from_bytes(args[32 * i:32 * i + 32], "big")


def _string(args: bytes, i: int) -> str:
    """The dynamic ``string`` whose head word is the ``i``-th."""
    at = _word(args, i)
    if at + 32 > len(args):
        raise ValueError("string offset outside the arguments")
    size = int.from_bytes(args[at:at + 32], "big")
    if at + 32 + size > len(args):
        raise ValueError("string runs past the arguments")
    return args[at + 32:at + 32 + size].decode()


def decode_call(wire: bytes):
    """-> ("add", user, amount), ("transfer", payer, payee, amount), or None
    where the transaction calls anything else."""
    to, call = call_of(wire)
    if to != DAG_TRANSFER:
        return None
    args = call[4:]
    if call[:4] == SEL_ADD:
        return "add", _string(args, 0), _word(args, 1)
    if call[:4] == SEL_TRANSFER:
        return "transfer", _string(args, 0), _string(args, 1), _word(args, 2)
    return None


# -- the contract, in a dict ----------------------------------------------------


def user_add(balances: dict[str, int], user: str, amount: int) -> int:
    if not user:
        return EMPTY_NAME
    if user in balances:
        return ADD_EXISTS  # the first write of a user wins
    balances[user] = amount
    return OK


def user_transfer(balances: dict[str, int], payer: str, payee: str, amount: int) -> int:
    if not payer or not payee:
        return EMPTY_NAME
    if payer not in balances:
        return NO_PAYER
    if balances[payer] < amount:
        return INSUFFICIENT  # commits with this code and moves nothing
    if payee not in balances:
        return NO_PAYEE
    if payer == payee:
        return OK
    if balances[payee] + amount > U256_MAX:
        return OVERFLOW
    balances[payer] -= amount
    balances[payee] += amount
    return OK


def apply(balances: dict[str, int], call) -> int | None:
    """One decoded call on the dict -> its return code (None: not a call of
    the two, nothing changes)."""
    if call is None:
        return None
    if call[0] == "add":
        return user_add(balances, call[1], call[2])
    return user_transfer(balances, call[1], call[2], call[3])


def replay(blocks: list[list[bytes]], balances: dict[str, int] | None = None):
    """Blocks of wire transactions, in chain order -> (balances after the
    last, the return code of every transaction as ``codes[block][index]``)."""
    balances = {} if balances is None else balances
    codes = [[apply(balances, decode_call(wire)) for wire in block] for block in blocks]
    return balances, codes
