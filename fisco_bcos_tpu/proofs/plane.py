"""ProofPlane: frozen-tree cache + coalesced builds for tx/receipt proofs.

Serving model
-------------
A proof for leaf ``i`` of block ``N`` is an O(depth) slice of the tree's
level stack (``MerkleTree.proof``). The expensive part is building the
stack: hashing every level, and for receipt trees first re-reading and
re-hashing every receipt in the block. The plane builds that stack ONCE
per (height, kind) and freezes it:

- **Commit-time build (head)**: the scheduler's commit-notify listener
  hands the plane the just-committed block — transactions and receipts in
  hand, so the head's trees are built with zero storage re-reads, off the
  consensus path (the notify worker thread).
- **Lazy build (historical)**: a cache miss reads the height's rows once,
  builds, and inserts into a bounded LRU. Concurrent misses for the same
  height coalesce on a per-height singleflight future — 10^5 clients
  asking for block N cost one build, not 10^5.
- **Device dispatch**: cache-miss tree hashing routes through the
  DevicePlane as the ``merkle_tree`` op on the ``proof`` lane — the lane
  BELOW ``sync`` — so a proof storm queues behind consensus, admission and
  gossip instead of starving them.

Invalidation contract (resilience)
----------------------------------
Every entry records the block hash it was built against. On every serve
the plane re-reads ``s_number_2_hash`` and refuses a stale entry (evicted,
rebuilt from current rows) — so a proof can never certify against a root
the chain no longer holds, even mid-rollback. Eager eviction hooks ride
the resilience seams: ``DistributedStorage.on_rollback`` (2PC rollback
re-drive declares a height dead → both kinds evicted) and the storage
switch handler (failover term switch → the whole cache is cleared; the
recovered backend may disagree about any height).

Locks: the single plane lock guards only the cache/singleflight dicts.
Builds — storage reads and device hashing — always run OUTSIDE it (the
runtime lock-order recorder forbids blocking IO under held locks).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

from ..observability import BATCH_BUCKETS, TRACER
from ..ops.merkle import MerkleProofItem  # host-safe name
from ..utils.log import get_logger, note_swallowed
from ..utils.metrics import REGISTRY

_log = get_logger("proofs")

KIND_TX = "tx"
KIND_RECEIPT = "receipt"
KINDS = (KIND_TX, KIND_RECEIPT)

# one batched request may carry at most this many hashes — enforced by BOTH
# request surfaces (JSON-RPC getProofBatch and the LIGHTNODE_GET_PROOFS
# frame): the gateway accepts frames far larger than any sane batch, and an
# uncapped request would let one client buy millions of locator reads and a
# multi-hundred-MB response for one frame
MAX_PROOF_BATCH = 1024

# serve = cache slice + identity row read (sub-ms steady state); build =
# storage reads + a full tree hash (tens of ms for a 2k-tx block on host)
PROOF_SERVE_BUCKETS_MS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 100.0)
PROOF_BUILD_BUCKETS_MS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)

# one queued proof request: (number, items, idx, n) — everything the RPC /
# lightnode surfaces need to answer and the client needs to verify
ProofResult = tuple[int, list[MerkleProofItem], int, int]


@dataclass
class _Entry:
    """One frozen tree: the level stack (pre-materialized as bytes — the
    MerkleTree holds numpy rows, and re-converting rows to bytes per proof
    is ~10x the cost of the slice itself), the O(1) leaf locator, and the
    block identity it was built against (the serve-time staleness check)."""

    levels: list[list[bytes]]  # bucket-padded level stack, bottom-up
    n: int  # REAL leaf count (proof depth/shape pins to the padded size)
    width: int
    index: dict[bytes, int]  # tx hash -> leaf index (both kinds align on it)
    block_hash: bytes
    kind: str
    source: str  # "commit" | "lazy"

    def proof(self, leaf_index: int) -> list[MerkleProofItem]:
        """Byte-identical to ``MerkleTree.proof`` on the same leaves: one
        child group per level below the root, sliced from frozen bytes."""
        if not 0 <= leaf_index < self.n:
            raise IndexError("leaf index out of range")
        items: list[MerkleProofItem] = []
        idx = leaf_index
        for level in self.levels[:-1]:
            g0 = (idx // self.width) * self.width
            items.append(
                MerkleProofItem(
                    group=tuple(level[g0 : g0 + self.width]), index=idx - g0
                )
            )
            idx //= self.width
        return items


class ProofPlane:
    """The per-node read-path proof server (one per Ledger; Node wires it
    into ``ledger.proof_plane``, the scheduler's commit listeners and the
    storage rollback/failover hooks). Metrics are process-global like every
    other plane's — multi-node test processes aggregate."""

    def __init__(self, ledger, suite, capacity: int | None = None):
        import os

        self.ledger = ledger
        self.suite = suite
        if capacity is None:
            try:
                capacity = int(os.environ.get("FISCO_PROOF_CACHE_CAP", "256"))
            except ValueError:
                capacity = 256
        self.capacity = max(2, int(capacity))
        self._lock = threading.Lock()
        self._cache: OrderedDict[tuple[int, str], _Entry] = OrderedDict()
        self._building: dict[tuple[int, str], Future] = {}
        # tx hash -> block number memo: skips the per-request receipt
        # row read + decode for repeat clients. SAFE to be stale: a hit is
        # only ever used to pick which frozen tree to consult, and the
        # tree's own identity-checked index is the authority — a miss
        # there falls back to the receipt row (and re-memoizes)
        self._hash2num: OrderedDict[bytes, int] = OrderedDict()
        self._hash2num_cap = 1 << 17
        # stats (mutated under _lock; snapshot via stats())
        self.requests = 0
        self.hits = 0
        self.misses = 0
        self.builds_commit = 0
        self.builds_lazy = 0
        self.coalesced_builds = 0  # misses served by another caller's build
        self.evictions: dict[str, int] = {}

    # -- public serving surface ----------------------------------------------

    def tx_proof(self, tx_hash: bytes):
        """Ledger-shaped single proof: (items, idx, n) vs header.txs_root."""
        res = self._serve_one(tx_hash, KIND_TX)
        return None if res is None else res[1:]

    def receipt_proof(self, tx_hash: bytes):
        """(items, idx, n) for the receipt leaf vs header.receipts_root."""
        res = self._serve_one(tx_hash, KIND_RECEIPT)
        return None if res is None else res[1:]

    def proof_batch(
        self, hashes: list[bytes], kind: str = KIND_TX
    ) -> list[ProofResult | None]:
        """N proofs in one call (the getProofBatch / LIGHTNODE_GET_PROOFS
        backend): requests are grouped per height so each height's tree is
        looked up (or built) exactly once, then every proof is an O(depth)
        slice. Unknown hashes yield None at their position."""
        if kind not in KINDS:
            raise ValueError(f"unknown proof kind {kind!r}")
        t0 = time.perf_counter()
        with self._lock:
            lazy0, coal0 = self.builds_lazy, self.coalesced_builds
        with TRACER.span("proof.serve", kind=kind, n=len(hashes)):
            out: list[ProofResult | None] = [None] * len(hashes)
            by_height: dict[int, list[int]] = {}
            retry: list[int] = []
            with self._lock:
                memo = [self._hash2num.get(h) for h in hashes]
            fresh: list[tuple[bytes, int]] = []
            for i, (h, number) in enumerate(zip(hashes, memo)):
                if number is None:
                    number = self._locate(h)
                    if number is None:
                        continue
                    fresh.append((h, number))
                by_height.setdefault(number, []).append(i)
            if fresh:
                # one lock round for the whole batch's new locations — a
                # 1024-hash cold batch previously took the plane lock per
                # hash, interleaving with writers each time
                self._memo_many(fresh)
            for number, idxs in by_height.items():
                ent = self._tree(number, kind)
                for i in idxs:
                    leaf_idx = ent.index.get(hashes[i]) if ent is not None else None
                    if leaf_idx is None:
                        # memo (or tree) disagreed with the current chain:
                        # fall back to the receipt row once for this hash
                        if memo[i] is not None:
                            retry.append(i)
                        continue
                    out[i] = (number, ent.proof(leaf_idx), leaf_idx, ent.n)
            for i in retry:
                h = hashes[i]
                number = self._locate(h)
                if number is None or number == memo[i]:
                    continue
                self._memo_height(h, number)
                ent = self._tree(number, kind)
                leaf_idx = ent.index.get(h) if ent is not None else None
                if leaf_idx is not None:
                    out[i] = (number, ent.proof(leaf_idx), leaf_idx, ent.n)
        if REGISTRY.enabled and hashes:
            REGISTRY.counter_add(
                f'fisco_proof_requests_total{{kind="{kind}"}}',
                float(len(hashes)),
                help="individual proofs requested from the ProofPlane",
            )
            REGISTRY.counter_add(
                f'fisco_proofs_served_total{{kind="{kind}"}}',
                float(sum(1 for r in out if r is not None)),
                help="proofs successfully served (rate = proofs/sec)",
            )
            REGISTRY.observe(
                "fisco_proof_batch_size",
                len(hashes),
                buckets=BATCH_BUCKETS,
                help="proof requests per batch call",
                kind=kind,
            )
            with self._lock:
                slice_only = (
                    self.builds_lazy == lazy0 and self.coalesced_builds == coal0
                )
            if slice_only:
                # batches that paid (or waited on) a tree build are already
                # recorded in fisco_proof_build_latency_ms — mixing them in
                # here would turn the documented "cached slice" signal into
                # a build-storm histogram
                REGISTRY.observe(
                    "fisco_proof_serve_latency_ms",
                    (time.perf_counter() - t0) * 1e3,
                    buckets=PROOF_SERVE_BUCKETS_MS,
                    help="proof batch serve wall latency for cache-hit "
                    "batches (slice + identity check; build latency is "
                    "fisco_proof_build_latency_ms)",
                    kind=kind,
                )
        return out

    def _serve_one(self, tx_hash: bytes, kind: str) -> ProofResult | None:
        res = self.proof_batch([tx_hash], kind)
        return res[0]

    # -- cache core ------------------------------------------------------------

    def _locate(self, tx_hash: bytes) -> int | None:
        """tx hash -> committed block number (via its receipt row — the
        same mapping the direct path uses)."""
        rc = self.ledger.receipt_by_hash(tx_hash)
        return None if rc is None else rc.block_number

    def _memo_height(self, tx_hash: bytes, number: int) -> None:
        self._memo_many([(tx_hash, number)])

    def _memo_many(self, pairs: list[tuple[bytes, int]]) -> None:
        with self._lock:
            for tx_hash, number in pairs:
                self._hash2num[tx_hash] = number
            while len(self._hash2num) > self._hash2num_cap:
                self._hash2num.popitem(last=False)

    def _tree(self, number: int, kind: str) -> _Entry | None:
        """Get-or-build the frozen tree for (number, kind), identity-checked
        against the CURRENT stored block hash — a cached entry for a dead
        root never serves."""
        cur_hash = self.ledger.block_hash_by_number(number)
        if cur_hash is None:
            # the height is gone (rolled back / never committed): anything
            # cached for it is dead
            self.invalidate(number, reason="identity")
            return None
        key = (number, kind)
        while True:
            wait_fut: Future | None = None
            my_fut: Future | None = None
            with self._lock:
                self.requests += 1
                ent = self._cache.get(key)
                if ent is not None and ent.block_hash == cur_hash:
                    self._cache.move_to_end(key)
                    self.hits += 1
                    self._count(kind, hit=True)
                    return ent
                if ent is not None:  # stale identity: the height was re-driven
                    self._evict_locked(key, "identity")
                self.misses += 1
                self._count(kind, hit=False)
                wait_fut = self._building.get(key)
                if wait_fut is None:
                    my_fut = self._building[key] = Future()
            if wait_fut is not None:
                # coalesce on the in-flight build (never under the lock).
                # A build ERROR propagates to every coalesced caller — the
                # direct path would surface the same storage error, and
                # degrading it to None would tell a light client "not
                # committed" over a transient read fault
                with self._lock:
                    self.coalesced_builds += 1
                ent = wait_fut.result(timeout=120.0)
                if ent is not None and ent.block_hash == cur_hash:
                    return ent
                # builder found nothing / built a different identity:
                # retry loop (re-reads the current hash path once more)
                cur_hash = self.ledger.block_hash_by_number(number)
                if cur_hash is None:
                    return None
                continue
            # this caller builds (outside the lock: storage + device IO);
            # errors reach the caller AND the coalesced waiters. A None
            # build result (empty height / partial receipts) is the real
            # "nothing to prove" and stays None.
            try:
                ent = self._build(number, kind, cur_hash)
            except BaseException as e:
                with self._lock:
                    self._building.pop(key, None)
                my_fut.set_exception(e)
                raise
            with self._lock:
                self._building.pop(key, None)
                if ent is not None:
                    self._insert_locked(key, ent)
                    self.builds_lazy += 1
            my_fut.set_result(ent)
            return ent

    def _count(self, kind: str, hit: bool) -> None:
        if not REGISTRY.enabled:
            return
        name = (
            "fisco_proof_cache_hits_total" if hit else "fisco_proof_cache_misses_total"
        )
        REGISTRY.counter_add(
            f'{name}{{kind="{kind}"}}',
            1.0,
            help="frozen-tree cache hits/misses per proof kind",
        )

    def _build(self, number: int, kind: str, block_hash: bytes) -> _Entry | None:
        """Read the height's rows once and freeze its tree (the lazy path).
        Hashing dispatches through the DevicePlane on the `proof` lane."""
        t0 = time.perf_counter()
        with TRACER.span("proof.build", block=number, kind=kind):
            tx_hashes = self.ledger.tx_hashes_by_number(number)
            if not tx_hashes:
                return None
            if kind == KIND_TX:
                leaves = tx_hashes
            else:
                rcs = [self.ledger.receipt_by_hash(h) for h in tx_hashes]
                if any(rc is None for rc in rcs):
                    return None  # partial receipts: nothing sound to freeze
                leaves = [rc.hash(self.suite) for rc in rcs]
            ent = self._freeze(tx_hashes, leaves, block_hash, kind, "lazy")
        if REGISTRY.enabled:
            REGISTRY.observe(
                "fisco_proof_build_latency_ms",
                (time.perf_counter() - t0) * 1e3,
                buckets=PROOF_BUILD_BUCKETS_MS,
                help="frozen-tree build wall latency (storage reads + device"
                " merkle levels)",
                kind=kind,
                source="lazy",
            )
        return ent

    def _freeze(
        self,
        tx_hashes: list[bytes],
        leaves: list[bytes],
        block_hash: bytes,
        kind: str,
        source: str,
    ) -> _Entry:
        from ..device.plane import device_lane

        arr = np.frombuffer(b"".join(leaves), dtype=np.uint8).reshape(-1, 32)
        # the `proof` lane sits below sync: a historical-proof storm queues
        # behind every consensus/admission/gossip batch on the device
        with device_lane("proof"):
            tree = self.suite.merkle_tree(arr)
        return _Entry(
            levels=[[bytes(h) for h in lvl] for lvl in tree.levels],
            n=tree.n,
            width=tree.width,
            index={h: i for i, h in enumerate(tx_hashes)},
            block_hash=block_hash,
            kind=kind,
            source=source,
        )

    def _insert_locked(self, key: tuple[int, str], ent: _Entry) -> None:
        if key in self._cache:
            self._evict_locked(key, "replace")
        self._cache[key] = ent
        self._cache.move_to_end(key)
        while len(self._cache) > self.capacity:
            old, _ = next(iter(self._cache.items()))
            self._evict_locked(old, "lru")

    def _evict_locked(self, key: tuple[int, str], reason: str) -> None:
        if self._cache.pop(key, None) is None:
            return
        self.evictions[reason] = self.evictions.get(reason, 0) + 1
        REGISTRY.counter_add(
            f'fisco_proof_cache_evictions_total{{reason="{reason}"}}',
            1.0,
            help="frozen-tree evictions by reason (lru/replace/identity/"
            "rollback/failover)",
        )

    # -- wiring hooks ----------------------------------------------------------

    def on_committed(self, number: int, block) -> None:
        """Commit-notify listener: freeze the new head's trees from the
        in-hand block (zero storage re-reads). Runs on the scheduler's
        notify worker — never on the consensus path — and must never throw
        into it."""
        try:
            txs = block.transactions
            if not txs:
                return
            t0 = time.perf_counter()
            # everything this listener does is under one span: a reader
            # tells the background build from the block path by its thread
            with TRACER.span(
                "proof.build", block=number, kind="both", source="commit"
            ):
                tx_hashes = block.tx_hashes(self.suite)
                block_hash = block.header.hash(self.suite)
                ents = {
                    (number, KIND_TX): self._freeze(
                        tx_hashes, tx_hashes, block_hash, KIND_TX, "commit"
                    )
                }
                if len(block.receipts) == len(txs):
                    rc_hashes = [rc.hash(self.suite) for rc in block.receipts]
                    ents[(number, KIND_RECEIPT)] = self._freeze(
                        tx_hashes, rc_hashes, block_hash, KIND_RECEIPT, "commit"
                    )
                with self._lock:
                    for key, ent in ents.items():
                        self._insert_locked(key, ent)
                        self.builds_commit += 1
                    for h in tx_hashes:  # warm the locator for the new head
                        self._hash2num[h] = number
                    while len(self._hash2num) > self._hash2num_cap:
                        self._hash2num.popitem(last=False)
            if REGISTRY.enabled:
                REGISTRY.observe(
                    "fisco_proof_build_latency_ms",
                    (time.perf_counter() - t0) * 1e3,
                    buckets=PROOF_BUILD_BUCKETS_MS,
                    help="frozen-tree build wall latency (storage reads +"
                    " device merkle levels)",
                    kind="both",
                    source="commit",
                )
        except Exception as e:  # cache warm failure must not break notify
            note_swallowed("proofs.on_committed", e)

    def on_rolled_back(self, number: int) -> None:
        """2PC rollback (re-)drive declared `number` dead: evict both kinds
        eagerly. The serve-time identity check is the backstop; this hook
        makes the eviction prompt and observable."""
        self.invalidate(number, reason="rollback")

    def on_failover(self) -> None:
        """Storage-backend switch: the recovered backend may disagree about
        any height — drop everything (identity checks would catch each
        entry lazily; clearing is cheap and prompt)."""
        with self._lock:
            for key in list(self._cache):
                self._evict_locked(key, "failover")
        _log.warning("proof cache cleared on storage failover")

    def invalidate(self, number: int, reason: str = "rollback") -> None:
        with self._lock:
            for kind in KINDS:
                self._evict_locked((number, kind), reason)

    # -- introspection ---------------------------------------------------------

    def cache_hit_ratio(self) -> float:
        with self._lock:
            total = self.hits + self.misses
            return self.hits / total if total else 0.0

    def pending_builds(self) -> int:
        """Frozen-tree builds currently in flight (singleflight futures) —
        the read-path watermark the pipeline observatory samples."""
        with self._lock:
            return len(self._building)

    def stats(self) -> dict:
        with self._lock:
            return {
                "requests": self.requests,
                "hits": self.hits,
                "misses": self.misses,
                "hit_ratio": round(
                    self.hits / (self.hits + self.misses), 4
                )
                if (self.hits + self.misses)
                else 0.0,
                "builds_commit": self.builds_commit,
                "builds_lazy": self.builds_lazy,
                "coalesced_builds": self.coalesced_builds,
                "evictions": dict(sorted(self.evictions.items())),
                "entries": len(self._cache),
                "capacity": self.capacity,
            }
