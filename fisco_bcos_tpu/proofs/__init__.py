"""ProofPlane — the read-path proof-serving subsystem (ISSUE 7 tentpole).

The reference serves merkle proofs one-at-a-time through
MerkleProofUtility.cpp: every getTransactionProof re-reads the block's tx
hashes and rebuilds the whole tree, and getReceiptProof additionally
re-fetches and re-hashes every receipt in the block *per request*. Our port
inherited that shape (`ledger/ledger.py` tx_proof/receipt_proof), which
caps the read path at a few hundred proofs/sec — nowhere near the
"millions of light clients" the ROADMAP's proof-serving item targets (ACE
Runtime 2603.10242 / ZK-hashing 2407.03511: verification itself is the
product).

This package owns that read path:

- :mod:`.plane` — :class:`ProofPlane`: a per-height **frozen-tree cache**
  (the tx-root and receipts-root ``MerkleTree`` level stacks are built once
  — at commit time for the head, lazily + LRU for historical heights — so
  a proof becomes an O(depth) slice of cached levels), **coalesced builds**
  (concurrent cache-miss requests for one height share a single build via
  per-height singleflight futures, and the tree hashing dispatches through
  the DevicePlane as the ``merkle_tree`` op on the ``proof`` lane — BELOW
  ``sync`` priority, so read traffic can never starve consensus), and an
  **invalidation contract**: entries carry the block hash they were built
  against and are re-checked against storage on every serve (a proof can
  never certify against a root the chain no longer holds), evicted eagerly
  on 2PC rollback re-drive (`DistributedStorage.on_rollback`) and cleared
  on storage-failover term switches.

Batch surfaces ride on it: JSON-RPC ``getProofBatch`` (rpc/jsonrpc.py) and
the multi-hash ``LIGHTNODE_GET_PROOFS`` frame (lightnode/lightnode.py) so
one round trip fetches N proofs, each still verified client-side against
synced headers. ``FISCO_PROOF_PLANE=0`` disables the plane entirely —
every caller takes the exact pre-plane direct rebuild path (the cache-off
fallback kept in ledger.py).

Bench: ``scenario.run_proof_storm_bench`` (scenario/proof_storm.py)
hammers batched proofs from ~10^5 simulated light clients while the chain
floods; ``tool/check_proofs.py`` runs it as the CI smoke. See docs/proofs.md.
"""

from __future__ import annotations

import os

from .plane import (  # noqa: F401
    MAX_PROOF_BATCH,
    PROOF_BUILD_BUCKETS_MS,
    PROOF_SERVE_BUCKETS_MS,
    ProofPlane,
)


def proof_plane_enabled() -> bool:
    """Master switch, read per call (tool smoke flips it mid-process):
    off = every proof request takes the direct per-request rebuild path."""
    return os.environ.get("FISCO_PROOF_PLANE", "1") != "0"


__all__ = [
    "MAX_PROOF_BATCH",
    "PROOF_BUILD_BUCKETS_MS",
    "PROOF_SERVE_BUCKETS_MS",
    "ProofPlane",
    "proof_plane_enabled",
]
