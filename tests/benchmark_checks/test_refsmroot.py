"""``benchmark/refsmroot.py`` (the wide merkle rule written again over plain
SM3) against the program's ``merkle_root(..., hasher="sm3")`` on seeded leaves,
and against two vectors worked by hand from the rule."""

import random

import numpy as np
import pytest

from benchmark import refsm, refsmroot


def _leaves(n: int, seed: int = 44) -> list[bytes]:
    rng = random.Random(seed * 100003 + n)
    return [rng.randbytes(32) for _ in range(n)]


@pytest.mark.parametrize("n", [1, 2, 15, 16, 17, 256, 1000, 4097])
def test_root_is_the_programs_merkle_root_under_sm3(n):
    from fisco_bcos_tpu.ops.merkle import merkle_root

    leaves = _leaves(n)
    array = np.frombuffer(b"".join(leaves), np.uint8).reshape(n, 32)
    assert refsmroot.root(leaves) == merkle_root(array, hasher="sm3")


@pytest.mark.parametrize("n,bucket", [(1, 1), (16, 16), (17, 17), (32, 32), (33, 34), (256, 256),
                                      (257, 272), (1000, 1024), (4097, 4352), (10000, 10240)])
def test_bucket_is_the_programs(n, bucket):
    from fisco_bcos_tpu.ops.merkle import bucket_leaves

    assert refsmroot.bucket_leaves(n) == bucket == bucket_leaves(n)


def test_one_leaf_is_its_own_padded_root_bound_to_the_count():
    (leaf,) = _leaves(1)
    assert refsmroot.padded_root([leaf]) == leaf
    assert refsmroot.root([leaf]) == refsm.sm3(leaf + (1).to_bytes(8, "big"))
    assert refsmroot.root([leaf]) != leaf


def test_seventeen_leaves_are_a_group_of_sixteen_and_a_group_of_one():
    """17 leaves are their own bucket: the first level is a full group of 16
    and a last group of the 17th leaf alone, hashed at its true length of 32
    bytes; the second level is those two digests, 64 bytes; the root binds the
    count 17."""
    leaves = _leaves(17)
    first = refsm.sm3(b"".join(leaves[:16]))
    last = refsm.sm3(leaves[16])
    top = refsm.sm3(first + last)
    assert refsmroot.padded_root(leaves) == top
    assert refsmroot.root(leaves) == refsm.sm3(top + (17).to_bytes(8, "big"))


def test_the_count_is_bound_where_the_padded_trees_are_one():
    """33 leaves pad to 34 with a zero digest: 34 leaves whose last is that
    zero digest have the same padded tree, and another root."""
    leaves = _leaves(33)
    assert refsmroot.padded_root(leaves) == refsmroot.padded_root(leaves + [bytes(32)])
    assert refsmroot.root(leaves) != refsmroot.root(leaves + [bytes(32)])


def test_txs_root_hashes_the_payloads_first():
    payloads = [b"tx %d" % i for i in range(5)]
    assert refsmroot.txs_root(payloads) == refsmroot.root([refsm.sm3(p) for p in payloads])


@pytest.mark.parametrize("leaves", [[], [b"short"], [bytes(32), bytes(31)]],
                         ids=["none", "not_a_digest", "one_short"])
def test_a_root_is_over_digests(leaves):
    with pytest.raises(ValueError):
        refsmroot.root(leaves)


def test_it_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(refsmroot))
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "benchmark"}
