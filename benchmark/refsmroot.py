"""Plain reference for a national-crypto block's transactions root: the wide
merkle tree the program states (``fisco_bcos_tpu/ops/merkle.py``, after
upstream's ``bcos-crypto/merkle/Merkle.h``, width 16), written again from that
description over ``refsm.sm3`` in Python integers and bytes. Imports nothing of
the program.

The rule, for ``n`` leaves of 32 bytes each:

1. pad the leaves with zero digests (32 zero bytes) up to ``bucket_leaves(n)``:
   ``n`` itself up to 16 leaves; above that the smallest ``m * 2**j >= n``
   with ``16 <= m <= 32`` (so 17 to 32 leaves stay as they are, 33 pads to 34,
   1,000 to 1,024, 4,097 to 4,352);
2. a level's nodes are grouped by up to 16, in order; each group's digests
   are concatenated at their true length (a last group of fewer than 16 is
   that much shorter) and hashed; the hashes are the next level; up to one
   node, the padded root. One leaf is its own padded root;
3. the root is ``H(padded root ‖ u64(n))``, big-endian: trees of different
   ``n`` in one bucket differ, and a single leaf is not its own root.

Which leaves: ``protocol/block.py`` (``Block.calculate_txs_root_async``) builds
the header's ``txs_root`` over the block's transaction hashes in block order,
one 32-byte leaf a transaction, each the hash of the transaction's signed
payload (``Transaction.encode_data()``) under the chain's hash: SM3 on an
``sm_crypto=true`` chain. ``tests/benchmark_checks/test_refsmroot.py`` holds
this file to the program's ``merkle_root(..., hasher="sm3")`` and to two
hand-worked vectors."""

from __future__ import annotations

from benchmark import refsm

WIDTH = 16
ZERO = bytes(32)


def bucket_leaves(n: int) -> int:
    if n <= WIDTH:
        return n
    step = 1 << (n.bit_length() - 5)
    return -(-n // step) * step


def padded_root(leaves: list[bytes]) -> bytes:
    level = list(leaves) + [ZERO] * (bucket_leaves(len(leaves)) - len(leaves))
    while len(level) > 1:
        level = [refsm.sm3(b"".join(level[i:i + WIDTH])) for i in range(0, len(level), WIDTH)]
    return level[0]


def root(leaves: list[bytes]) -> bytes:
    """The transactions root of a block whose transaction hashes are
    ``leaves``, in block order (at least one)."""
    if not leaves or any(len(leaf) != 32 for leaf in leaves):
        raise ValueError("a root is over one or more 32-byte leaves")
    return refsm.sm3(padded_root(leaves) + len(leaves).to_bytes(8, "big"))


def txs_root(payloads: list[bytes]) -> bytes:
    """The same from the transactions' signed payloads."""
    return root([refsm.sm3(p) for p in payloads])
