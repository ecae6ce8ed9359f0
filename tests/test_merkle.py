"""Merkle layer tests (vs a straightforward host recomputation).

Reference model: bcos-crypto/test/unittests/testMerkle.cpp — roots and proofs
across widths and leaf counts, negative proof cases.
"""

import time

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.ref.keccak import keccak256
from fisco_bcos_tpu.crypto.ref.sm3 import sm3
from fisco_bcos_tpu.ops.merkle import MerkleTree, merkle_root

_REF_HASH = {"keccak256": keccak256, "sm3": sm3}


def _host_root(leaves, width, hasher):
    """Independent reimplementation of the padded-bucket root definition:
    zero-pad to the 5-bit-mantissa bucket (smallest m*2^j >= n, 16<=m<=32,
    for >16 leaves), fold the wide tree, then bind the REAL leaf count with
    one more hash."""
    h = _REF_HASH[hasher]
    n = len(leaves)
    cur = [bytes(x) for x in leaves]
    if n > 16:
        j = n.bit_length() - 5
        bucket = -(-n // (1 << j)) << j
    else:
        bucket = n
    cur += [b"\x00" * 32] * (bucket - n)
    while len(cur) > 1:
        cur = [h(b"".join(cur[i : i + width])) for i in range(0, len(cur), width)]
    return h(cur[0] + n.to_bytes(8, "big"))


@pytest.mark.parametrize("n", [1, 2, 15, 16, 17, 100])
@pytest.mark.parametrize("width", [2, 16])
def test_root_matches_host(n, width):
    rng = np.random.default_rng(n * 31 + width)
    leaves = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    assert merkle_root(leaves, width=width) == _host_root(leaves, width, "keccak256")


def test_sm3_root():
    rng = np.random.default_rng(5)
    leaves = rng.integers(0, 256, (33, 32), dtype=np.uint8)
    assert merkle_root(leaves, hasher="sm3") == _host_root(leaves, 16, "sm3")


@pytest.mark.parametrize("width", [2, 16])
def test_proofs_verify(width):
    rng = np.random.default_rng(9)
    leaves = rng.integers(0, 256, (70, 32), dtype=np.uint8)
    tree = MerkleTree(leaves, width=width)
    for idx in (0, 1, 37, 69):
        proof = tree.proof(idx)
        assert MerkleTree.verify_proof(bytes(leaves[idx]), idx, 70, proof, tree.root, width=width)
        # wrong leaf fails
        other = bytes(leaves[(idx + 1) % 70])
        assert not MerkleTree.verify_proof(other, idx, 70, proof, tree.root, width=width)
    # tampered root fails
    bad_root = bytes(tree.root[:-1]) + bytes([tree.root[-1] ^ 1])
    assert not MerkleTree.verify_proof(bytes(leaves[0]), 0, 70, tree.proof(0), bad_root, width=width)


def test_repartitioned_group_cannot_forge_membership():
    """Entries in a proof group must each be 32 bytes: repartitioning the
    same concatenated group bytes (identical parent hash input) must not
    certify a 32-byte window straddling two real digests as a leaf."""
    from fisco_bcos_tpu.ops.merkle import MerkleProofItem

    rng = np.random.default_rng(17)
    leaves = rng.integers(0, 256, (32, 32), dtype=np.uint8)
    tree = MerkleTree(leaves, width=16)
    proof = tree.proof(0)
    cat = b"".join(proof[0].group)  # 16 x 32 = 512 bytes
    fake_leaf = cat[48:80]  # straddles leaves 1 and 2
    # 16 entries with the SAME concatenation: 48, 14 x 32, 16 bytes
    bounds = [0, 48] + [48 + 32 * i for i in range(1, 15)] + [512]
    forged_group = tuple(cat[bounds[i] : bounds[i + 1]] for i in range(16))
    assert b"".join(forged_group) == cat and len(forged_group) == 16
    forged = [MerkleProofItem(group=forged_group, index=1)] + list(proof[1:])
    assert not MerkleTree.verify_proof(fake_leaf, 1, 32, forged, tree.root, width=16)


def test_truncated_proof_cannot_certify_internal_node():
    """A proof with its first level dropped must NOT verify the level-1
    internal digest as a 'leaf' (depth binding)."""
    rng = np.random.default_rng(13)
    leaves = rng.integers(0, 256, (256, 32), dtype=np.uint8)
    tree = MerkleTree(leaves, width=16)
    full = tree.proof(0)
    internal = full[1].group[0]  # hash of leaves 0..15
    truncated = full[1:]
    assert not MerkleTree.verify_proof(internal, 0, 256, truncated, tree.root, width=16)
    # and a proof that's too long fails as well
    padded = full + [full[-1]]
    assert not MerkleTree.verify_proof(bytes(leaves[0]), 0, 256, padded, tree.root, width=16)


# leaf counts of the fused SM3 tree: a full bucket (256, 1,024), one
# filler-only group (257 pads to 272), a remainder group at each level (600
# pads to 608: 38 groups, then 2 and a group of 6), a top group of one, and
# the headline 10,000
_FUSED_NS = [256, 257, 272, 600, 1000, 1024, 4097, 10_000]
_FUSED_CASES = [("keccak256", n, 16) for n in (256, 271, 400, 1000)] + [
    ("sm3", n, width) for width in (2, 16) for n in _FUSED_NS
]


def _leaves(n, width=0):
    return np.random.default_rng(n + width).integers(0, 256, (n, 32), dtype=np.uint8)


@pytest.mark.parametrize("hasher,n,width", _FUSED_CASES)
def test_fused_device_root_matches_host_path(hasher, n, width, monkeypatch):
    """merkle_root's >= 256-leaf fused single-program device path (keccak's
    root program, SM3's tree program) must be bit-identical to the generic
    MerkleTree levels (consensus-critical: tx/receipt roots) — including
    short last groups at every level, and for device-resident (jax.Array)
    leaf input. The device route is FORCED here: on CPU+native hosts
    merkle_root prefers the host tree (backend-aware routing, r5), which
    would silently drop this cross-route identity coverage."""
    import jax.numpy as jnp

    from fisco_bcos_tpu.ops import merkle as M

    leaves = _leaves(n, width)
    want = MerkleTree(leaves, width=width, hasher=hasher).root  # host route
    if hasher == "sm3" and width == 16 and n <= 1024:
        # the benchmark's plain rule over the same digests (pure Python)
        from benchmark import refsmroot

        assert want == refsmroot.root([bytes(x) for x in leaves])
    monkeypatch.setattr(M, "_prefer_host_tree", lambda: False)
    assert M.merkle_root(leaves, width=width, hasher=hasher) == want
    assert M.merkle_root(jnp.asarray(leaves), width=width, hasher=hasher) == want


@pytest.mark.parametrize("width", [2, 16])
@pytest.mark.parametrize("n", _FUSED_NS)
def test_fused_sm3_tree_is_the_host_tree_level_for_level(n, width, monkeypatch):
    """An SM3 proof tree of 256 leaves and more on the device route is ONE
    program and one transfer: its levels are the host hasher's, row for row,
    and a proof cut from them verifies (and a flipped byte does not)."""
    from fisco_bcos_tpu.ops import merkle as M

    leaves = _leaves(n, width)
    host = MerkleTree(leaves, width=width, hasher="sm3")
    assert not host.fused
    monkeypatch.setattr(M, "_prefer_host_tree", lambda: False)
    tree = MerkleTree(leaves, width=width, hasher="sm3")
    assert tree.fused
    want = M._levels(host.levels[0], width, M._host_hash_batch("sm3"))
    assert len(tree.levels) == len(want)
    for got, level in zip(tree.levels, want):
        assert got.dtype == np.uint8 and np.array_equal(got, level)
    assert tree.padded_root == host.padded_root and tree.root == host.root
    for idx in (0, n // 2, n - 1):
        proof = tree.proof(idx)
        leaf = bytes(leaves[idx])
        assert MerkleTree.verify_proof(leaf, idx, n, proof, tree.root, width=width, hasher="sm3")
        flipped = bytes([leaf[0] ^ 1]) + leaf[1:]
        assert not MerkleTree.verify_proof(
            flipped, idx, n, proof, tree.root, width=width, hasher="sm3")


def test_small_and_other_trees_keep_the_level_path(monkeypatch):
    """Under 256 leaves, and under a hasher with no fused tree, MerkleTree is
    the level-by-level path on the device route too."""
    from fisco_bcos_tpu.ops import merkle as M

    monkeypatch.setattr(M, "_prefer_host_tree", lambda: False)
    assert not MerkleTree(_leaves(255), hasher="sm3").fused
    assert not MerkleTree(_leaves(17), hasher="keccak256").fused


def test_fused_device_root_input_validation():
    from fisco_bcos_tpu.ops.merkle import merkle_root

    leaves = np.zeros((300, 32), dtype=np.uint8)
    with pytest.raises(ValueError):
        merkle_root(leaves, width=1)  # would never shrink
    with pytest.raises(ValueError):
        merkle_root(np.zeros((300, 64), dtype=np.uint8))


@pytest.mark.parametrize("hasher", ["keccak256", "sm3"])
def test_bucket_padding_reuses_device_program(hasher, monkeypatch):
    """Block sizes within one bucket must hit the SAME compiled tree program
    (the per-leaf-count recompile churn fix), with padding overhead bounded
    by the 5-bit mantissa (<= 1/16). Device route forced (see above)."""
    import fisco_bcos_tpu.ops.merkle as M
    from fisco_bcos_tpu.ops.merkle import bucket_leaves, merkle_root

    monkeypatch.setattr(M, "_prefer_host_tree", lambda: False)

    assert bucket_leaves(10) == 10          # tiny trees stay exact
    assert bucket_leaves(256) == 256
    assert bucket_leaves(257) == 272
    assert bucket_leaves(500) == 512
    assert bucket_leaves(512) == 512
    assert bucket_leaves(10_000) == 10_240  # headline tree: +2.4%, not +64%
    for n in (17, 300, 999, 4097, 12_345, 100_000):
        b = bucket_leaves(n)
        assert n <= b <= n + (n >> 4) + 16   # overhead bound
        assert bucket_leaves(b) == b         # buckets are fixed points

    maker = M._device_root_fn if hasher == "keccak256" else M._device_tree_fn
    before = maker.cache_info().currsize
    rng = np.random.default_rng(3)
    for n in (497, 500, 505, 512):           # one bucket: 512
        merkle_root(rng.integers(0, 256, (n, 32), dtype=np.uint8), hasher=hasher)
        if hasher == "sm3":                  # a proof tree shares the root's program
            assert MerkleTree(_leaves(n), hasher=hasher).fused
    added = maker.cache_info().currsize - before
    assert added <= 1  # one program for the whole bucket


def test_a_merkle_root_counts_the_path_it_took(monkeypatch):
    """The mechanism's counter: with the device route forced, one SM3 root of
    1,000 leaves is one ``device.merkle_root`` span, one fused program, one
    count on ``fisco_device_dispatch_path_total{op="merkle_root",path="fused"}``
    and no `sm3_blocks` call; a 100-leaf one counts ``path="levels"``. A proof
    tree through the suite counts under ``op="merkle_tree"``."""
    import fisco_bcos_tpu.ops.merkle as M
    from fisco_bcos_tpu.crypto.suite import sm_suite
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    monkeypatch.setattr(M, "_prefer_host_tree", lambda: False)
    level_batches = []
    monkeypatch.setitem(
        M._HASHERS, "sm3",
        lambda msgs, real=M._HASHERS["sm3"]: level_batches.append(len(msgs)) or real(msgs))
    paths = "fisco_device_dispatch_path_total{"

    def took(call):
        before = REGISTRY.counters_matching(paths)
        t0 = time.perf_counter()
        call()
        spans = [r for r in TRACER.spans()
                 if r.name in ("device.merkle_root", "device.merkle_tree") and r.ts >= t0]
        delta = {k[len(paths):-1]: v - before.get(k, 0.0)
                 for k, v in REGISTRY.counters_matching(paths).items()
                 if v - before.get(k, 0.0)}
        return spans, delta

    spans, delta = took(lambda: M.merkle_root(_leaves(1000), hasher="sm3"))
    assert delta == {'op="merkle_root",path="fused"': 1.0}
    assert [(r.name, r.attrs["path"]) for r in spans] == [("device.merkle_root", "fused")]
    assert level_batches == []
    spans, delta = took(lambda: M.merkle_root(_leaves(100), hasher="sm3"))
    assert delta == {'op="merkle_root",path="levels"': 1.0}
    assert len(spans) == 1 and level_batches == [7, 1]  # 112 padded leaves: two levels
    spans, delta = took(lambda: sm_suite().merkle_tree(_leaves(1000)))
    assert delta == {'op="merkle_tree",path="fused"': 1.0}
    assert [(r.name, r.attrs["path"]) for r in spans] == [("device.merkle_tree", "fused")]
    _, delta = took(lambda: sm_suite().merkle_tree(_leaves(100)))
    assert delta == {'op="merkle_tree",path="levels"': 1.0}
