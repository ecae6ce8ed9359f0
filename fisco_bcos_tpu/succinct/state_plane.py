"""StatePlane — an incremental merkle commitment over the KeyPage state.

Commitment shape
----------------
Every live row ``(table, key, entry)`` hashes to one leaf::

    key_blob = flat(str table) ‖ flat(bytes key)     (the StateStorage
    leaf     = H(key_blob ‖ entry.encode())           XOR-root preimage)

Keys bucket into a FIXED number of pages (``FISCO_STATE_PAGES``, default
64) by ``H(key_blob)[:2] mod n_pages`` — the KeyPage analog: a page is the
unit of locality, and a block only dirties the pages its touched keys land
in. Each non-empty page is a wide merkle subtree over its leaves sorted by
``key_blob`` (an empty page contributes a 32-zero-byte placeholder), and
the header commitment is the root of a top tree over the page roots. Both
trees ride :class:`fisco_bcos_tpu.ops.merkle.MerkleTree` (count-bound
roots), hashed by the plane's OWN hasher (``FISCO_STATE_HASH`` —
``poseidon`` makes the whole commitment SNARK-friendly) through the
CryptoSuite seam, so batch hashing coalesces on the DevicePlane like every
other caller's.

Incremental maintenance
-----------------------
The plane never recomputes the full state: at execute time
(:meth:`preview`) the block's touched-key set updates ONLY the pages it
dirtied — copy-on-write page dicts chain block N+1's preview onto block
N's (speculative pre-execution included), untouched pages share structure
all the way back to the base. :meth:`promote` (commit time) turns the
preview into the new base and freezes it as a served height. The delta
cost is ``O(touched keys + touched pages · page size + n_pages)`` hashes,
not ``O(state)``.

Serving (the ProofPlane machinery)
----------------------------------
Per-height frozen snapshots, page trees built lazily under a per-
``(height, page)`` singleflight, every serve identity-checked against the
CURRENT ``s_number_2_hash`` row, eager eviction on rollback re-drive and
storage failover, builds dispatched under ``device_lane("proof")`` — the
lane below sync, exactly like tx/receipt proofs.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

from ..codec.flat import FlatWriter
from ..crypto.suite import CryptoSuite, hash_impl_by_name
from ..observability import TRACER
from ..ops.merkle import (  # host-safe names
    MerkleProofItem,
    MerkleTree,
    bind_root,
    bucket_leaves,
)
from ..proofs.plane import MAX_PROOF_BATCH
from ..utils.log import get_logger, note_swallowed
from ..utils.metrics import REGISTRY

_log = get_logger("succinct")

_ZERO32 = b"\x00" * 32

# chain-DATA tables (ledger.prewrite_block's rows) stay OUT of the
# commitment: they are staged at commit time outside the executor overlay,
# they are derivable from the blocks themselves, and block N's rows embed
# block N's header — whose preimage contains this very commitment (the
# circularity that forces every state-root scheme to scope itself to
# execution state). s_consensus/s_config stay IN: committee and config
# changes are executor writes through precompiled contracts.
EXCLUDED_TABLES = frozenset(
    {
        "s_number_2_header",
        "s_number_2_hash",
        "s_hash_2_number",
        "s_current_state",
        "s_number_2_txs",
        "s_block_number_2_nonces",
        "s_hash_2_tx",
        "s_hash_2_receipt",
    }
)

# state-proof batches share the tx/receipt proof cap — same reasoning: the
# gateway accepts frames far larger than any sane batch
MAX_STATE_PROOF_BATCH = MAX_PROOF_BATCH

# commit-time delta update: touched-leaf hashing + touched-page subtrees +
# the 64-leaf top tree (ms-class for block-sized write sets)
STATE_COMMIT_BUCKETS_MS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0,
)


def _key_blob(table: str, key: bytes) -> bytes:
    """The leaf's key prefix — EXACTLY StateStorage's XOR-root layout
    (state_storage.py hash_async), so the commitment and the state root
    agree on what a row's identity bytes are."""
    w = FlatWriter()
    w.str_(table)
    w.bytes_(key)
    return w.out()


def state_page_of(table: str, key: bytes, n_pages: int, hash_fn) -> int:
    """Fixed hash bucketing: ``H(key_blob)[:2] mod n_pages``."""
    return int.from_bytes(hash_fn(_key_blob(table, key))[:2], "big") % n_pages


def state_leaf(table: str, key: bytes, entry_bytes: bytes, hash_fn) -> bytes:
    """leaf = H(key_blob ‖ entry.encode())."""
    return hash_fn(_key_blob(table, key) + bytes(entry_bytes))


@dataclass(frozen=True)
class StateProofResult:
    """One served state proof: two chained wide-merkle proofs (leaf inside
    its page subtree, page root inside the top tree) plus the row bytes the
    client re-hashes into the leaf."""

    number: int
    page: int
    n_pages: int
    leaf_index: int
    n_leaves: int  # REAL leaf count of the page subtree
    page_items: list[MerkleProofItem]
    top_items: list[MerkleProofItem]
    entry_bytes: bytes
    commitment: bytes


def verify_state_proof(
    table: str,
    key: bytes,
    res: StateProofResult,
    commitment: bytes,
    hasher: str = "keccak256",
    n_pages: int = 64,
    width: int = 16,
) -> bool:
    """Client-side verification against a header's ``state_commitment``:
    re-derive the leaf from the served row bytes, walk the page subtree to
    its (count-bound) root, then walk the top tree to the commitment. The
    page index itself is re-derived from the key — a proof relocated to a
    different bucket fails even if both subtrees are internally sound."""
    hash_fn = hash_impl_by_name(hasher).hash
    if res.n_pages != n_pages or res.page != state_page_of(
        table, key, n_pages, hash_fn
    ):
        return False
    leaf = state_leaf(table, key, res.entry_bytes, hash_fn)
    # the page root is not transmitted: recompute it by ascending the page
    # proof from the re-derived leaf (count-bound), then prove THAT root's
    # membership in the top tree — tampering with either half breaks one walk
    page_root = _ascend(leaf, res.leaf_index, res.n_leaves, res.page_items,
                        hasher, width)
    if page_root is None:
        return False
    return MerkleTree.verify_proof(
        page_root, res.page, n_pages, res.top_items, commitment,
        width=width, hasher=hasher,
    )


def _ascend(
    leaf: bytes, idx: int, n: int, items: list[MerkleProofItem],
    hasher: str, width: int,
) -> bytes | None:
    """Recompute a tree's BOUND root from a leaf + proof (the first half of
    ``MerkleTree.verify_proof``, returning the root instead of comparing)."""
    hash_fn = hash_impl_by_name(hasher).hash
    if not 0 <= idx < n or len(leaf) != 32:
        return None
    cur, size = leaf, bucket_leaves(n)
    for item in items:
        if size <= 1:
            return None
        g0 = (idx // width) * width
        if item.index != idx - g0:
            return None
        if len(item.group) != min(width, size - g0):
            return None
        if any(len(h) != 32 for h in item.group):
            return None
        if item.group[item.index] != cur:
            return None
        cur = hash_fn(b"".join(item.group))
        idx //= width
        size = -(-size // width)
    if size != 1:
        return None
    return bind_root(cur, n, hasher)


# ---------------------------------------------------------------------------
# Independent reference walker (acceptance oracle — no ops.merkle, no
# device dispatch: plain loops over the same spec)
# ---------------------------------------------------------------------------


def _ref_hash_fn(hasher: str):
    if hasher == "keccak256":
        from ..crypto.ref.keccak import keccak256

        return keccak256
    if hasher == "sm3":
        from ..crypto.ref.sm3 import sm3

        return sm3
    if hasher == "sha256":
        from ..crypto.ref.sha2 import sha256

        return sha256
    if hasher == "poseidon":
        from ..crypto.ref.poseidon import poseidon_hash

        return poseidon_hash
    raise KeyError(hasher)


def _ref_bucket(n: int) -> int:
    if n <= 16:
        return n
    j = n.bit_length() - 5
    return -(-n // (1 << j)) << j


def _ref_tree_root(leaves: list[bytes], hasher: str, width: int = 16) -> bytes:
    """Independent wide-merkle fold: bucket-pad with zero leaves, hash
    width-groups per level, bind the real count."""
    h = _ref_hash_fn(hasher)
    n = len(leaves)
    cur = list(leaves) + [_ZERO32] * (_ref_bucket(n) - n)
    while len(cur) > 1:
        cur = [
            h(b"".join(cur[i : i + width])) for i in range(0, len(cur), width)
        ]
    return h(cur[0] + n.to_bytes(8, "big"))


def reference_state_commitment(
    rows, hasher: str = "keccak256", n_pages: int = 64, width: int = 16
) -> bytes:
    """Full-recompute oracle: fold EVERY live row of ``rows`` (an iterable
    of ``(table, key, Entry)``, deleted rows skipped) into the commitment —
    the value the plane's incremental path must match after any churn."""
    h = _ref_hash_fn(hasher)
    pages: list[list[tuple[bytes, bytes]]] = [[] for _ in range(n_pages)]
    for t, k, e in rows:
        if e.deleted or t in EXCLUDED_TABLES:
            continue
        kb = _key_blob(t, bytes(k))
        pages[int.from_bytes(h(kb)[:2], "big") % n_pages].append(
            (kb, h(kb + e.encode()))
        )
    roots = []
    for bucket in pages:
        if not bucket:
            roots.append(_ZERO32)
            continue
        bucket.sort(key=lambda kv: kv[0])
        roots.append(_ref_tree_root([lf for _, lf in bucket], hasher, width))
    return _ref_tree_root(roots, hasher, width)


# ---------------------------------------------------------------------------
# The plane
# ---------------------------------------------------------------------------


@dataclass
class _Snapshot:
    """One height's full state image. ``pages`` dicts are copy-on-write:
    NEVER mutated after publication — a block's preview copies only the
    pages it touches, so untouched pages share structure across heights."""

    number: int
    block_hash: bytes  # b"" until promoted
    pages: tuple  # tuple[dict[key_blob, (leaf, entry_bytes)], ...]
    page_roots: list[bytes]
    commitment: bytes


class StatePlane:
    """Per-node state-commitment maintainer + proof server (Node wires it
    into ``scheduler.state_plane`` / ``ledger.state_plane`` and the
    rollback/failover hooks, exactly like the ProofPlane)."""

    def __init__(
        self,
        ledger,
        suite: CryptoSuite,
        backend=None,
        hasher: str | None = None,
        n_pages: int | None = None,
        capacity: int | None = None,
    ):
        import os

        from . import state_hash_name, state_pages

        self.ledger = ledger
        self.backend = backend
        self.hasher = hasher if hasher is not None else state_hash_name()
        self.n_pages = n_pages if n_pages is not None else state_pages()
        # the plane's own suite: commitment hasher + the node's signer —
        # batch hashing and tree builds route through the same DevicePlane
        # seams as the consensus suite's, just under the `hash.<name>` /
        # `merkle_tree.<name>` op of the chosen hasher
        self.suite = CryptoSuite(hash_impl_by_name(self.hasher),
                                 suite.signature_impl)
        if capacity is None:
            try:
                capacity = int(os.environ.get("FISCO_STATE_PROOF_CAP", "64"))
            except ValueError:
                capacity = 64
        self.capacity = max(2, int(capacity))
        self._lock = threading.Lock()
        self._base: _Snapshot | None = None
        self._previews: dict[int, _Snapshot] = {}
        self._heights: OrderedDict[int, _Snapshot] = OrderedDict()
        # frozen page subtrees, built lazily per (height, page) under a
        # singleflight future (the ProofPlane discipline)
        self._trees: OrderedDict[tuple[int, int], MerkleTree] = OrderedDict()
        self._tree_cap = max(self.capacity * 4, 64)
        self._building: dict[tuple[int, int], Future] = {}
        # stats (under _lock; snapshot via stats())
        self.requests = 0
        self.hits = 0
        self.misses = 0
        self.previews = 0
        self.promotes = 0
        self.coalesced_builds = 0
        self.rebuilds = 0
        self.evictions: dict[str, int] = {}
        self._bootstrap()

    # -- base maintenance -----------------------------------------------------

    def _host_hash(self, data: bytes) -> bytes:
        return self.suite.hash(data)

    def _bootstrap(self) -> None:
        """(Re)build the base image from the durable backend — boot, and
        the failover/rollback recovery path. Backends without ``traverse``
        start from an empty image (commitments then cover post-boot deltas
        only; every in-tree transactional backend is traversable)."""
        number = self.ledger.block_number()
        rows = []
        if self.backend is not None and hasattr(self.backend, "traverse"):
            rows = [
                (t, k, e)
                for t, k, e in self.backend.traverse()
                if not e.deleted and t not in EXCLUDED_TABLES
            ]
        elif self.backend is not None:
            _log.warning(
                "state plane backend %s is not traversable: starting from an "
                "empty base image", type(self.backend).__name__,
            )
        pages: list[dict] = [{} for _ in range(self.n_pages)]
        if rows:
            blobs = [_key_blob(t, bytes(k)) for t, k, _ in rows]
            encs = [e.encode() for _, _, e in rows]
            digests = self.suite.hash_batch(
                blobs + [kb + enc for kb, enc in zip(blobs, encs)]
            )
            for i, kb in enumerate(blobs):
                pg = int.from_bytes(bytes(digests[i][:2]), "big") % self.n_pages
                pages[pg][kb] = (bytes(digests[len(blobs) + i]), encs[i])
        roots = [self._page_root(pg) for pg in pages]
        commitment = self._top_root(roots)
        snap = _Snapshot(
            number=number,
            block_hash=self.ledger.block_hash_by_number(number) or b"",
            pages=tuple(pages),
            page_roots=roots,
            commitment=commitment,
        )
        with self._lock:
            self._base = snap
            self.rebuilds += 1
            if snap.block_hash:
                self._insert_height_locked(snap)

    def _page_root(self, page: dict) -> bytes:
        if not page:
            return _ZERO32
        leaves = [lf for _, (lf, _) in sorted(page.items())]
        arr = np.frombuffer(b"".join(leaves), dtype=np.uint8).reshape(-1, 32)
        return self.suite.merkle_tree(arr).root

    def _top_root(self, page_roots: list[bytes]) -> bytes:
        arr = np.frombuffer(
            b"".join(page_roots), dtype=np.uint8
        ).reshape(-1, 32)
        return self.suite.merkle_tree(arr).root

    # -- execute-time preview / commit-time promote ---------------------------

    def preview(self, number: int, writes) -> bytes:
        """Apply a block's touched-key set to the chain of images and
        return the header commitment. Called at execute time (under the
        scheduler lock — single writer); chains onto block N-1's preview
        when N-1 is executed-but-uncommitted (speculative pre-execution)."""
        t0 = time.perf_counter()
        with self._lock:
            base = self._previews.get(number - 1) or self._base
        if base is None or base.number != number - 1:
            # the image chain is broken (failover cleared it / plane created
            # mid-run): rebuild the base from the durable backend, which is
            # exactly the state block `number` executes against
            self._bootstrap()
            with self._lock:
                base = self._base
            if base is None or base.number != number - 1:
                raise ValueError(
                    f"state plane base at {base.number if base else None}, "
                    f"cannot preview block {number}"
                )
        writes = [
            (t, bytes(k), e)
            for t, k, e in writes
            if t not in EXCLUDED_TABLES
        ]
        with TRACER.span("succinct.preview", block=number, writes=len(writes)):
            blobs = [_key_blob(t, k) for t, k, _ in writes]
            live = [
                (i, e.encode()) for i, (_, _, e) in enumerate(writes)
                if not e.deleted
            ]
            digests = (
                self.suite.hash_batch(
                    blobs + [blobs[i] + enc for i, enc in live]
                )
                if blobs
                else np.zeros((0, 32), np.uint8)
            )
            page_of = [
                int.from_bytes(bytes(digests[i][:2]), "big") % self.n_pages
                for i in range(len(blobs))
            ]
            leaf_at = {
                i: bytes(digests[len(blobs) + j])
                for j, (i, _) in enumerate(live)
            }
            enc_at = dict(live)
            pages = list(base.pages)
            roots = list(base.page_roots)
            touched: set[int] = set()
            for i, (kb, pg) in enumerate(zip(blobs, page_of)):
                if pg not in touched:
                    pages[pg] = dict(pages[pg])
                    touched.add(pg)
                if i in leaf_at:
                    pages[pg][kb] = (leaf_at[i], enc_at[i])
                else:
                    pages[pg].pop(kb, None)  # delete tombstone
            for pg in touched:
                roots[pg] = self._page_root(pages[pg])
            commitment = self._top_root(roots)
        snap = _Snapshot(
            number=number,
            block_hash=b"",
            pages=tuple(pages),
            page_roots=roots,
            commitment=commitment,
        )
        with self._lock:
            # a re-execution at `number` replaces anything speculated above
            for n in [n for n in self._previews if n >= number]:
                self._previews.pop(n)
            self._previews[number] = snap
            self.previews += 1
        REGISTRY.observe(
            "fisco_state_commit_update_ms",
            (time.perf_counter() - t0) * 1e3,
            buckets=STATE_COMMIT_BUCKETS_MS,
            help="incremental state-commitment delta update per executed "
            "block (touched-leaf hashing + touched-page subtrees + top tree)",
            pages=str(len(touched)),
        )
        return commitment

    def promote(self, number: int, block_hash: bytes) -> None:
        """Commit landed: the height's preview becomes the new base and a
        served height. Runs on the commit path (cheap: dict swaps) — must
        never throw into it."""
        with TRACER.span("succinct.promote", block=number):
            self._promote(number, block_hash)

    def _promote(self, number: int, block_hash: bytes) -> None:
        try:
            with self._lock:
                snap = self._previews.pop(number, None)
                if snap is None:
                    base = self._base
                    if base is not None and base.number == number:
                        return  # already promoted (idempotent re-drive)
                    need_rebuild = True
                else:
                    need_rebuild = False
                    snap = _Snapshot(
                        number=snap.number,
                        block_hash=bytes(block_hash),
                        pages=snap.pages,
                        page_roots=snap.page_roots,
                        commitment=snap.commitment,
                    )
                    self._base = snap
                    for n in [n for n in self._previews if n <= number]:
                        self._previews.pop(n)
                    self._insert_height_locked(snap)
                    self.promotes += 1
            if need_rebuild:
                # commit of a block this plane never previewed (created
                # mid-run / image chain dropped): fall back to a full
                # rebuild from the now-durable backend
                _log.warning(
                    "state plane missed preview of block %d: rebuilding",
                    number,
                )
                self._bootstrap()
        except Exception as e:  # the commit path must survive plane faults
            note_swallowed("succinct.promote", e)

    def _insert_height_locked(self, snap: _Snapshot) -> None:
        if snap.number in self._heights:
            self._evict_height_locked(snap.number, "replace")
        self._heights[snap.number] = snap
        self._heights.move_to_end(snap.number)
        while len(self._heights) > self.capacity:
            old = next(iter(self._heights))
            self._evict_height_locked(old, "lru")

    def _evict_height_locked(self, number: int, reason: str) -> None:
        if self._heights.pop(number, None) is None:
            return
        for key in [k for k in self._trees if k[0] == number]:
            self._trees.pop(key)
        self.evictions[reason] = self.evictions.get(reason, 0) + 1
        REGISTRY.counter_add(
            f'fisco_state_plane_evictions_total{{reason="{reason}"}}',
            1.0,
            help="frozen state-height evictions by reason (lru/replace/"
            "identity/rollback/failover)",
        )

    # -- serving --------------------------------------------------------------

    def head_commitment(self) -> bytes | None:
        with self._lock:
            return self._base.commitment if self._base is not None else None

    def state_proof(
        self, table: str, key: bytes, number: int | None = None
    ) -> StateProofResult | None:
        return self.state_proof_batch([(table, bytes(key))], number)[0]

    def state_proof_batch(
        self, reqs: list[tuple[str, bytes]], number: int | None = None
    ) -> list[StateProofResult | None]:
        """N membership proofs against one height's commitment (default:
        the committed head). Unknown keys (and unserved heights) yield
        ``None`` at their position — absence proofs are not part of the
        fixed-page commitment's contract."""
        if len(reqs) > MAX_STATE_PROOF_BATCH:
            raise ValueError(
                f"state proof batch over {MAX_STATE_PROOF_BATCH} keys"
            )
        with self._lock:
            self.requests += len(reqs)
            if number is None:
                number = self._base.number if self._base is not None else -1
        out: list[StateProofResult | None] = [None] * len(reqs)
        snap = self._height(number)
        if snap is None:
            with self._lock:
                self.misses += len(reqs)
            return out
        with TRACER.span("succinct.serve", block=number, n=len(reqs)):
            served = 0
            for i, (table, key) in enumerate(reqs):
                kb = _key_blob(table, bytes(key))
                pg = (
                    int.from_bytes(self._host_hash(kb)[:2], "big")
                    % self.n_pages
                )
                row = snap.pages[pg].get(kb)
                if row is None:
                    continue
                tree = self._page_tree(snap, pg)
                keys_sorted = sorted(snap.pages[pg])
                leaf_idx = keys_sorted.index(kb)
                top = self._top_tree(snap)
                out[i] = StateProofResult(
                    number=number,
                    page=pg,
                    n_pages=self.n_pages,
                    leaf_index=leaf_idx,
                    n_leaves=tree.n,
                    page_items=tree.proof(leaf_idx),
                    top_items=top.proof(pg),
                    entry_bytes=row[1],
                    commitment=snap.commitment,
                )
                served += 1
        with self._lock:
            self.hits += served
            self.misses += len(reqs) - served
        REGISTRY.counter_add(
            "fisco_state_proofs_served_total",
            float(served),
            help="state membership proofs served by the StatePlane",
        )
        return out

    def _height(self, number: int) -> _Snapshot | None:
        """Identity-checked height lookup: a snapshot whose block hash no
        longer matches the CURRENT ``s_number_2_hash`` row never serves."""
        cur = self.ledger.block_hash_by_number(number)
        with self._lock:
            snap = self._heights.get(number)
            if snap is None:
                return None
            if cur is None or snap.block_hash != cur:
                self._evict_height_locked(number, "identity")
                return None
            self._heights.move_to_end(number)
            return snap

    def _page_tree(self, snap: _Snapshot, pg: int) -> MerkleTree:
        """Get-or-build the frozen page subtree under a per-(height, page)
        singleflight — concurrent proof storms for one page cost one build.
        Builds dispatch under the `proof` device lane (below sync)."""
        key = (snap.number, pg)
        while True:
            my_fut: Future | None = None
            with self._lock:
                tree = self._trees.get(key)
                if tree is not None:
                    self._trees.move_to_end(key)
                    return tree
                wait_fut = self._building.get(key)
                if wait_fut is None:
                    my_fut = self._building[key] = Future()
            if my_fut is None:
                with self._lock:
                    self.coalesced_builds += 1
                tree = wait_fut.result(timeout=120.0)
                if tree is not None:
                    return tree
                continue
            try:
                from ..device.plane import device_lane

                leaves = [lf for _, (lf, _) in sorted(snap.pages[pg].items())]
                arr = np.frombuffer(
                    b"".join(leaves), dtype=np.uint8
                ).reshape(-1, 32)
                with device_lane("proof"):
                    tree = self.suite.merkle_tree(arr)
            except BaseException as e:
                with self._lock:
                    self._building.pop(key, None)
                my_fut.set_exception(e)
                raise
            with self._lock:
                self._building.pop(key, None)
                self._trees[key] = tree
                self._trees.move_to_end(key)
                while len(self._trees) > self._tree_cap:
                    self._trees.popitem(last=False)
            my_fut.set_result(tree)
            return tree

    def _top_tree(self, snap: _Snapshot) -> MerkleTree:
        """Top tree over the page roots (n_pages leaves — cheap; built
        per serve call from the frozen roots, no cache needed)."""
        from ..device.plane import device_lane

        arr = np.frombuffer(
            b"".join(snap.page_roots), dtype=np.uint8
        ).reshape(-1, 32)
        with device_lane("proof"):
            return self.suite.merkle_tree(arr)

    # -- wiring hooks ----------------------------------------------------------

    def on_rolled_back(self, number: int) -> None:
        """2PC rollback re-drive declared ``number`` dead: evict it and
        everything above, and rebuild the base if it had advanced past."""
        with self._lock:
            for n in [n for n in self._previews if n >= number]:
                self._previews.pop(n)
            for n in [n for n in self._heights if n >= number]:
                self._evict_height_locked(n, "rollback")
            stale_base = self._base is not None and self._base.number >= number
        if stale_base:
            self._bootstrap()

    def on_failover(self) -> None:
        """Storage-backend switch: the recovered backend may disagree about
        everything — drop the whole image chain and rebuild the base."""
        with self._lock:
            self._previews.clear()
            for n in list(self._heights):
                self._evict_height_locked(n, "failover")
            self._trees.clear()
        _log.warning("state plane cleared on storage failover")
        self._bootstrap()

    def invalidate(self, number: int, reason: str = "rollback") -> None:
        with self._lock:
            self._evict_height_locked(number, reason)

    # -- introspection ---------------------------------------------------------

    def pending_builds(self) -> int:
        with self._lock:
            return len(self._building)

    def stats(self) -> dict:
        with self._lock:
            return {
                "hasher": self.hasher,
                "n_pages": self.n_pages,
                "base_number": self._base.number if self._base else None,
                "requests": self.requests,
                "hits": self.hits,
                "misses": self.misses,
                "previews": self.previews,
                "promotes": self.promotes,
                "rebuilds": self.rebuilds,
                "coalesced_builds": self.coalesced_builds,
                "evictions": dict(sorted(self.evictions.items())),
                "heights": len(self._heights),
                "capacity": self.capacity,
            }
