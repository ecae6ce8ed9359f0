"""Vectorized wide merkle trees on the batch hash kernels.

Reference counterpart: bcos-crypto/bcos-crypto/merkle/Merkle.h:35-230 (templated
on hasher and width, default width 16; `generateMerkle` / `generateMerkleProof`
/ `verifyMerkleProof`) and the 2.x parallel variant
bcos-protocol/ParallelMerkleProof.cpp:32-100 (tbb::parallel_for). Used for a
block's transaction/receipt roots (bcos-ledger merkle proofs) — 10k+ leaves per
block at the reference's headline TPS.

TPU formulation: a level with L nodes is one fixed-row-length batch hash —
group up to `width` child digests, concatenate (short groups keep their true
byte length, matching a variable-arity last group), hash all groups in one
device call. The whole tree is O(log_width N) device calls of shrinking batch
size instead of N sequential hashes.

Proofs follow the reference's wide-proof shape: per level, the full child
group of the target node (the verifier re-hashes the group and ascends).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .keccak import keccak256_batch_async, keccak256_blocks
from .sha256 import sha256_batch_async
from .sm3 import sm3_absorb, sm3_batch_async

HashBatchFn = Callable[[Sequence[bytes]], np.ndarray]

# span-LESS async entries, resolved eagerly: the per-level hash calls run
# inside the enclosing merkle device_span (merkle_root / the plane's
# merkle_tree executor) — a nested per-level hash span would book the same
# wall twice and misfile a cold hash-program compile as merkle execute
# remainder (same reasoning as sm2_e_batch)
def _poseidon_batch(msgs: Sequence[bytes]) -> np.ndarray:
    # lazy: deriving the Grain/Cauchy constant tables costs ~0.2 s at
    # ops.poseidon import, and only the succinct state plane pays it
    from .poseidon import poseidon_batch_async

    return poseidon_batch_async(msgs)()


_HASHERS: dict[str, HashBatchFn] = {
    "keccak256": lambda msgs: keccak256_batch_async(msgs)(),
    "sm3": lambda msgs: sm3_batch_async(msgs)(),
    "sha256": lambda msgs: sha256_batch_async(msgs)(),
    "poseidon": _poseidon_batch,
}


def _host_hash(hasher: str, data: bytes) -> bytes:
    """Single-item host-side hash (native C when available) — the root
    binding is one tiny hash; a device batch call for it would cost a whole
    dispatch + sync."""
    from .. import native_bind

    if hasher not in _HASHERS:
        # same rejection the device route gets from its dict lookup — an
        # unknown name must never silently fall through to sha256 (one
        # node raising while another silently hashes is a divergence)
        raise KeyError(hasher)
    if hasher == "keccak256":
        from ..crypto.ref.keccak import keccak256 as ref

        return native_bind.keccak256(data) or ref(data)
    if hasher == "sm3":
        from ..crypto.ref.sm3 import sm3 as ref

        return native_bind.sm3(data) or ref(data)
    if hasher == "poseidon":
        # no native core: the pure-Python reference IS the host path (bit-
        # identical to the jitted sponge by the ops/poseidon.py import pin)
        from ..crypto.ref.poseidon import poseidon_hash as ref_poseidon

        return ref_poseidon(data)
    from ..crypto.ref.sha2 import sha256 as ref

    return native_bind.sha256(data) or ref(data)


def bucket_leaves(n: int) -> int:
    """Leaf-count bucket: every tree is built over a padded size (zero-digest
    filler leaves) so the fused device program compiles once per bucket
    instead of once per distinct block size — a production chain with
    variable block sizes would otherwise recompile the multi-minute tree
    program continuously (r3/r4 advisor churn note).

    Buckets are 5-bit-mantissa floats: the smallest m·2^j ≥ n with
    16 ≤ m ≤ 32. Padding overhead is ≤ 1/16 (vs up to 2× for plain
    power-of-two buckets — the 10k-leaf headline tree pads to 10,240, not
    16,384) while a whole octave of block sizes still shares ≤ 16 compiled
    programs. ≤16 leaves keep their exact size (single-group trees)."""
    if n <= 16:
        return n
    j = n.bit_length() - 5
    return -(-n // (1 << j)) << j


def bind_root(padded_root: bytes, n: int, hasher: str = "keccak256") -> bytes:
    """Final root = H(padded_root ‖ u64(n)). Binding the REAL leaf count
    makes trees of different n in the same bucket (whose padded trees could
    otherwise alias via trailing zero leaves) distinct, and gives single-leaf
    trees leaf≠root domain separation."""
    return _host_hash(hasher, bytes(padded_root) + int(n).to_bytes(8, "big"))


def _prefer_host_tree() -> bool:
    """True when tree levels should be hashed by the native C loop instead
    of a device batch program: on a CPU-only jax backend the XLA keccak
    program costs ~70 ms per 600-leaf root (measured, flood profile r5)
    while the sequential native loop is ~20x faster — the same
    backend-aware routing admit_batch applies to EC. Device backends keep
    the fused device tree (leaves are usually already device-resident)."""
    from .. import native_bind
    from ..utils.jaxenv import device_backend_is_cpu

    return device_backend_is_cpu() and native_bind.load() is not None


def _host_hash_batch(hasher: str) -> HashBatchFn:
    """Sequential native-C hash_batch with the exact grouping/output shape
    of the device batch fns — roots stay bit-identical across routes."""

    def hb(groups: Sequence[bytes]) -> np.ndarray:
        return np.frombuffer(
            b"".join(_host_hash(hasher, g) for g in groups), dtype=np.uint8
        ).reshape(len(groups), 32).copy()

    return hb


@dataclass(frozen=True)
class MerkleProofItem:
    """One level of a wide merkle proof: the child group containing the
    target, plus the target's index within the group."""

    group: tuple[bytes, ...]
    index: int


def _levels(leaves: np.ndarray, width: int, hash_batch: HashBatchFn) -> list[np.ndarray]:
    """All tree levels bottom-up; level 0 = leaves, last = [1, 32] root."""
    levels = [leaves]
    cur = leaves
    while len(cur) > 1:
        n = len(cur)
        groups = [
            bytes(cur[i : i + width].reshape(-1)) for i in range(0, n, width)
        ]
        cur = hash_batch(groups)
        levels.append(cur)
    return levels


class MerkleTree:
    """Wide merkle tree over 32-byte leaf hashes.

    `leaves` is a [N, 32] uint8 array (already-hashed items, e.g. tx hashes —
    the reference also trees over hashes, Merkle.h:43).
    """

    def __init__(self, leaves: np.ndarray, width: int = 16, hasher: str = "keccak256"):
        leaves = np.asarray(leaves, dtype=np.uint8)
        if leaves.ndim != 2 or leaves.shape[1] != 32:
            raise ValueError("leaves must be [N, 32] uint8")
        if len(leaves) == 0:
            raise ValueError("merkle tree needs at least one leaf")
        if width < 2:
            raise ValueError("width must be >= 2")
        self.width = width
        self.hasher = hasher
        self.n = len(leaves)
        b = bucket_leaves(self.n)
        if b > self.n:  # zero-digest filler up to the bucket (see bucket_leaves)
            leaves = np.vstack([leaves, np.zeros((b - self.n, 32), np.uint8)])
        host = _prefer_host_tree()
        # every level from one device program and one transfer (the fused
        # tree, below), or a hash batch and a sync a level
        self.fused = hasher in _FUSED_TREE and self.n >= _FUSED_MIN_LEAVES and not host
        if self.fused:
            rows = np.asarray(_device_tree_fn(hasher, b, width)(leaves))
            self.levels = [leaves, *np.split(rows, _level_offsets(b, width))]
        else:
            self.levels = _levels(
                leaves, width, _host_hash_batch(hasher) if host else _HASHERS[hasher]
            )

    @property
    def padded_root(self) -> bytes:
        """Root of the bucket-padded tree (what the device programs emit)."""
        return bytes(self.levels[-1][0])

    @property
    def root(self) -> bytes:
        return bind_root(self.padded_root, self.n, self.hasher)

    def proof(self, leaf_index: int) -> list[MerkleProofItem]:
        """Proof for leaf `leaf_index`: one child group per level below root."""
        if not 0 <= leaf_index < self.n:
            raise IndexError("leaf index out of range")
        items: list[MerkleProofItem] = []
        idx = leaf_index
        for level in self.levels[:-1]:
            g0 = (idx // self.width) * self.width
            group = tuple(bytes(h) for h in level[g0 : g0 + self.width])
            items.append(MerkleProofItem(group=group, index=idx - g0))
            idx //= self.width
        return items

    @staticmethod
    def verify_proof(
        leaf: bytes,
        leaf_index: int,
        n_leaves: int,
        proof: list[MerkleProofItem],
        root: bytes,
        width: int = 16,
        hasher: str = "keccak256",
    ) -> bool:
        """Recompute the path from a *positioned* leaf up to `root`.

        Binding to (leaf_index, n_leaves) pins the proof depth and every
        group's size/offset — without it, a truncated proof could certify an
        internal digest as a leaf (no leaf/inner domain separation exists in
        the reference's digest-over-digests scheme either, Merkle.h:43; the
        verifier there likewise knows the leaf count from the block header).
        """
        if not 0 <= leaf_index < n_leaves:
            return False
        if len(leaf) != 32:
            return False
        cur = leaf
        # the tree is built over the bucket-padded leaf set; group sizes and
        # depth follow the PADDED size, the final binding hash pins the REAL n
        idx, size = leaf_index, bucket_leaves(n_leaves)
        for item in proof:
            if size <= 1:
                return False  # proof longer than the tree is deep
            g0 = (idx // width) * width
            if item.index != idx - g0:
                return False
            if len(item.group) != min(width, size - g0):
                return False
            # every entry must be a digest: without this, a repartition of the
            # same concatenated bytes forges membership of a 32-byte window
            # straddling two real digests
            if any(len(h) != 32 for h in item.group):
                return False
            if item.group[item.index] != cur:
                return False
            # one tiny hash per level: host-side always (a device batch of
            # size 1 would cost a whole dispatch + sync — same reasoning
            # as bind_root; bit-identical to the device kernels)
            cur = _host_hash(hasher, b"".join(item.group))
            idx //= width
            size = -(-size // width)
        if size != 1:
            return False  # proof shorter than the tree is deep
        return bind_root(cur, n_leaves, hasher) == root


# ---------------------------------------------------------------------------
# Fused device tree (root-only hot path)
# ---------------------------------------------------------------------------
#
# The generic MerkleTree path above does one host round trip per level with
# Python per-group byte packing — fine for small trees, but every level is a
# dispatch, a transfer and a device sync, so a 10k-leaf root costs ~4 syncs +
# host loops. The fused path packs a level's hash blocks with pure jnp
# reshapes (keccak's sponge lanes, SM3's big-endian words) and runs ALL levels
# in one jitted device program: one transfer in, and out either the root's 32
# bytes (`_device_root_fn`, keccak) or every level above the leaves as one
# array (`_device_tree_fn`: an SM3 root, and an SM3 proof tree, whose levels a
# proof is cut from). Bit-identical to the host path (same grouping, same
# short-last-group semantics).

_FUSED_MIN_LEAVES = 256  # below it a tree is a few small hash batches
_FUSED_ROOT = ("keccak256", "sm3")  # merkle_root_async: one program a root
# MerkleTree: one program a proof tree. Keccak's proof trees stay level by
# level (3.9 ms of device time a flood block: ROADMAP Queue 3)
_FUSED_TREE = ("sm3",)

_LANES = 17  # keccak rate 136 bytes = 17 64-bit lanes


def _group_pad_const(msg_len: int, m_pad: int) -> np.ndarray:
    """Keccak 0x01..0x80 multi-rate padding bytes for a msg_len-byte group,
    zero-extended so every group occupies m_pad sponge blocks."""
    pad = np.zeros(m_pad * 136 - msg_len, dtype=np.uint8)
    padlen = (msg_len // 136 + 1) * 136 - msg_len
    if padlen == 1:
        pad[0] = 0x81
    else:
        pad[0] = 0x01
        pad[padlen - 1] |= 0x80
    return pad


def _bytes_to_lanes(buf, m: int):
    """[B, m*136] uint8 -> [B, m, 17, 2] uint32 little-endian lo/hi."""
    b = buf.reshape(buf.shape[0], m, _LANES, 2, 4).astype(jnp.uint32)
    return (
        b[..., 0]
        | (b[..., 1] << 8)
        | (b[..., 2] << 16)
        | (b[..., 3] << 24)
    )


def _words_to_bytes(words):
    """[B, 8] uint32 LE digest words -> [B, 32] uint8 (device)."""
    by = jnp.stack(
        [(words >> (8 * k)) & 0xFF for k in range(4)], axis=-1
    )  # [B, 8, 4]
    return by.reshape(words.shape[0], 32).astype(jnp.uint8)


# analysis: allow(shape-bucket) — runs INSIDE jit traces whose leaf count was
# already padded to bucket_leaves by _device_root_fn's callers
def _device_level(cur, width: int):
    """One tree level on device: [L, 32] uint8 -> [ceil(L/width), 32]."""
    L = cur.shape[0]
    gfull, rem = divmod(L, width)
    m_pad = (width * 32) // 136 + 1  # blocks per full group (4 at width 16)
    bufs = []
    nblocks = []
    if gfull:
        full = cur[: gfull * width].reshape(gfull, width * 32)
        pad = jnp.broadcast_to(
            jnp.asarray(_group_pad_const(width * 32, m_pad)), (gfull, m_pad * 136 - width * 32)
        )
        bufs.append(jnp.concatenate([full, pad], axis=1))
        nblocks += [width * 32 // 136 + 1] * gfull
    if rem:
        msg = rem * 32
        tail = cur[gfull * width :].reshape(1, msg)
        pad = jnp.asarray(_group_pad_const(msg, m_pad))[None]
        bufs.append(jnp.concatenate([tail, pad], axis=1))
        nblocks.append(msg // 136 + 1)
    buf = bufs[0] if len(bufs) == 1 else jnp.concatenate(bufs, axis=0)
    lanes = _bytes_to_lanes(buf, m_pad)
    words = keccak256_blocks(lanes, jnp.asarray(np.array(nblocks, np.int32)))
    return _words_to_bytes(words)


# A tree's batches are at most L/width lanes wide (64 at 1,024 leaves): a
# compression costs the device ops it launches, not its arithmetic, so the
# round scan is unrolled; and a batch of fewer than eight lanes (the short
# last group, the top of the tree) is padded to eight, because the chip's
# compiler runs narrower ones on its scalar core, 0.7 ms a 1,024-leaf tree
# slower (PERF.md §6, PR 45 has the chip's table: 1.35 ms a tree, 7.2 as three
# `sm3_blocks` programs; no loop at all is 0.19 ms, and XLA-CPU takes 30 s
# a tree to compile it)
_SM3_ROUND_UNROLL = 16
_SM3_MIN_LANES = 8


def _sm3_pad_words(msg_words: int) -> np.ndarray:
    """SM3's padding of a message of msg_words 32-bit words (a group of
    digests is whole words), as big-endian words: 0x80, zeros to the block's
    last two words, the bit length."""
    n = -(msg_words + 3) % 16
    return np.array([0x80000000] + [0] * (n + 1) + [msg_words * 32], dtype=np.uint32)


# analysis: allow(shape-bucket) — as _device_level: inside jit traces whose
# leaf count _device_tree_fn's callers padded to bucket_leaves
def _sm3_level(cur, width: int):
    """One SM3 tree level on device: [L, 32] uint8 -> [ceil(L/width), 32].
    The full groups are one batch and the short last group a second, each
    lane of a batch absorbing the same number of blocks (a full group of 16
    digests is eight data blocks and one constant padding block)."""
    L = cur.shape[0]
    b = cur.reshape(L, 8, 4).astype(jnp.uint32)
    words = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    gfull, rem = divmod(L, width)
    digests = []
    for groups, size, start in ((gfull, width, 0), (1, rem, gfull * width)):
        if groups and size:
            pad = _sm3_pad_words(size * 8)
            msg = words[start : start + groups * size].reshape(groups, size * 8)
            blocks = jnp.concatenate(
                [msg, jnp.broadcast_to(jnp.asarray(pad), (groups, len(pad)))], axis=1
            ).reshape(groups, -1, 16)
            if groups < _SM3_MIN_LANES:
                blocks = jnp.pad(blocks, ((0, _SM3_MIN_LANES - groups), (0, 0), (0, 0)))
            digests.append(sm3_absorb(blocks, _SM3_ROUND_UNROLL)[:groups])
    out = jnp.concatenate(digests, axis=0)
    by = jnp.stack([(out >> s) & 0xFF for s in (24, 16, 8, 0)], axis=-1)
    return by.reshape(out.shape[0], 32).astype(jnp.uint8)


_DEVICE_LEVEL = {"keccak256": _device_level, "sm3": _sm3_level}


def _device_levels(leaves, width: int, hasher: str) -> list:
    """Every level above the leaves, inside a jit trace; the last is [1, 32]."""
    levels = []
    cur = leaves
    while cur.shape[0] > 1:
        cur = _DEVICE_LEVEL[hasher](cur, width)
        levels.append(cur)
    return levels


def _level_offsets(n: int, width: int) -> list[int]:
    """Where `_device_tree_fn`'s rows split into levels (for np.split)."""
    offsets, at = [], 0
    while n > 1:
        n = -(-n // width)
        at += n
        offsets.append(at)
    return offsets[:-1]


@lru_cache(maxsize=64)
def _device_root_fn(n: int, width: int):
    @jax.jit
    def run(leaves):
        return _device_levels(leaves, width, "keccak256")[-1][0]

    return run


@lru_cache(maxsize=64)
def _device_tree_fn(hasher: str, n: int, width: int):
    """The tree over n (bucketed) leaves as one program: [n, 32] uint8 ->
    every level above the leaves, bottom-up, as the rows of one array (one
    transfer back; `_level_offsets` splits them), the root the last row."""

    @jax.jit
    def tree(leaves):
        return jnp.concatenate(_device_levels(leaves, width, hasher), axis=0)

    return tree


def merkle_root_async(
    leaves: np.ndarray, width: int = 16, hasher: str = "keccak256"
):
    """Dispatch the root computation, defer the device sync: () -> bytes.

    Large keccak and SM3 trees dispatch the fused single-program device path
    and resolve on call (letting the sealing path queue tx root, receipts
    root and state root before paying any device round trip); small trees,
    the host route and other hashers compute eagerly inside this call.
    Which of the two a root took is counted:
    ``fisco_device_dispatch_path_total{op="merkle_root",path="fused"|"levels"}``."""
    from ..observability.device import device_phase, device_span

    if not isinstance(leaves, jax.Array):
        leaves = np.asarray(leaves, dtype=np.uint8)
    # same validation whichever path runs (MerkleTree re-checks on its path)
    if leaves.ndim != 2 or leaves.shape[1] != 32:
        raise ValueError("leaves must be [N, 32] uint8")
    if width < 2:
        raise ValueError("width must be >= 2")
    # the span lives HERE (not in the merkle_root sync wrapper) so the
    # sealing path's suite.merkle_root_async calls are attributed too; it
    # covers the dispatch only — the resolver's sync is the caller's wait
    # and a segment of its own (``device.merkle_root.sync``): this program
    # is dispatched on the caller's thread and not through the plane
    n = len(leaves)
    key = (hasher, width, bucket_leaves(max(n, 1)))
    with device_span("merkle_root", n, shape_key=key, hasher=hasher) as sp:
        if (
            hasher in _FUSED_ROOT
            and n >= _FUSED_MIN_LEAVES
            and not _prefer_host_tree()
        ):
            sp.path("fused")
            # jax.Array input stays on device — tx/receipt hashes come from
            # the batch hash kernels, so the hot sealing path never
            # round-trips the leaf tensor through the host. Padding to the
            # leaf-count bucket happens OUTSIDE the jit so the tree
            # program's input shape (and hence its compilation) is shared
            # by every block size in the bucket.
            b = bucket_leaves(n)
            with sp.phase("marshal"):
                arr = jnp.asarray(leaves).astype(jnp.uint8)
                if b > n:
                    arr = jnp.concatenate([arr, jnp.zeros((b - n, 32), jnp.uint8)])
            with sp.phase("enqueue"):
                if hasher == "keccak256":
                    dev = _device_root_fn(b, width)(arr)
                else:
                    dev = _device_tree_fn(hasher, b, width)(arr)

            def resolve() -> bytes:
                with device_phase("sync", op="merkle_root"):
                    # the root's 32 bytes, or the tree's rows: the root last
                    padded_root = np.asarray(dev).reshape(-1, 32)[-1]
                    return bind_root(bytes(padded_root), n, hasher)

            return resolve
        sp.path("levels")
        root = MerkleTree(
            np.asarray(leaves, dtype=np.uint8), width=width, hasher=hasher
        ).root
        return lambda: root


def merkle_root(
    leaves: np.ndarray, width: int = 16, hasher: str = "keccak256"
) -> bytes:
    """Root only (the hot path for block sealing: tx/receipt roots).
    The device_span lives in :func:`merkle_root_async` — a second one here
    would double-count the dispatch."""
    return merkle_root_async(leaves, width=width, hasher=hasher)()


# -- progaudit shape spec: the tree programs are maker products — audit the
# width-16 keccak root and the width-16 SM3 tree at one ladder leaf count.
PROGSPEC = {
    "_device_root_fn.run": {
        "bucket": 256,
        "call": lambda b: _device_root_fn(b, 16),
        "inputs": lambda b: [((b, 32), "uint8")],
    },
    "_device_tree_fn.tree": {
        "bucket": 256,
        "call": lambda b: _device_tree_fn("sm3", b, 16),
        "inputs": lambda b: [((b, 32), "uint8")],
    },
}
