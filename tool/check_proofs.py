#!/usr/bin/env python
"""ProofPlane smoke check (ISSUE 7 acceptance shape, small scale).

Four phases, runnable locally and from CI next to the other check_* tools:

1. **Static analysis stays clean** — the new proofs/ module obeys the
   device-dispatch / shape-bucket / lock-order / contract checkers
   (`python -m fisco_bcos_tpu.analysis` baseline: no new, no stale).
2. **Bit-identity** — ProofPlane-served tx/receipt proofs byte-equal the
   direct per-request `Ledger` rebuild across a bucket-ladder boundary,
   and `MerkleTree.verify_proof` accepts both.
3. **Storm, live** — a 4-node chain floods while >= 8 client threads
   hammer batched proofs (the proof-storm bench at reduced scale).
   Asserts: every queued client served, cache hit ratio > 0.9 at steady
   state, ZERO failed verifications, and the write path kept committing.
4. **RPC surface** — `getProofBatch` answers over a live node with
   verifiable proofs and None for unknown hashes.
5. **State plane (ISSUE 18)** — a live `FISCO_STATE_PROOF=1` chain:
   replicas agree on the header-carried commitment, the incremental
   commitment byte-equals the full-recompute reference walker over raw
   storage, membership proofs serve commit-warm (hits, no misses) and
   verify, and a tampered entry / wrong key is rejected.

Exit 0 on success, 1 with a named failure otherwise::

    python tool/check_proofs.py              # all fast legs
    python tool/check_proofs.py --poseidon   # + compile the jitted Poseidon
                                             #   sponge and cross-check it
                                             #   against crypto/ref (minutes
                                             #   of XLA-CPU compile)
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("FISCO_DEVICE_WINDOW_MS", "0")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def check_analysis_clean() -> None:
    from fisco_bcos_tpu.analysis import check_repo

    new, stale = check_repo()
    if new:
        for f in new:
            print(f"  {f.render()}")
        fail(f"{len(new)} new static-analysis finding(s) — proofs/ must obey the checkers")
    if stale:
        fail(f"{len(stale)} stale analysis baseline entr(ies): {stale}")
    print("ok: static-analysis baseline clean")


def check_bit_identity() -> None:
    import hashlib

    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.ledger import Ledger
    from fisco_bcos_tpu.ledger.ledger import (
        SYS_HASH_2_RECEIPT,
        SYS_NUMBER_2_HASH,
        SYS_NUMBER_2_TXS,
        _encode_hash_list,
    )
    from fisco_bcos_tpu.proofs import ProofPlane
    from fisco_bcos_tpu.protocol.receipt import TransactionReceipt
    from fisco_bcos_tpu.storage import MemoryStorage
    from fisco_bcos_tpu.storage.entry import Entry

    suite = ecdsa_suite()
    storage = MemoryStorage()
    ledger = Ledger(storage, suite)
    for number, k in ((1, 16), (2, 17), (3, 48)):  # the ladder boundary
        hashes = [hashlib.sha256(b"%d-%d" % (number, i)).digest() for i in range(k)]
        storage.set_row(
            SYS_NUMBER_2_TXS, str(number).encode(),
            Entry().set(_encode_hash_list(hashes)),
        )
        for h in hashes:
            storage.set_row(
                SYS_HASH_2_RECEIPT, h,
                Entry().set(TransactionReceipt(block_number=number).encode()),
            )
        storage.set_row(
            SYS_NUMBER_2_HASH, str(number).encode(),
            Entry().set(hashlib.sha256(b"hdr%d" % number).digest()),
        )
        probe = hashes[k // 2]
        direct_tx = ledger.tx_proof(probe)
        direct_rc = ledger.receipt_proof(probe)
        ledger.proof_plane = ProofPlane(ledger, suite)
        if ledger.tx_proof(probe) != direct_tx:
            fail(f"tx proof diverges from the direct path at {k} leaves")
        if ledger.receipt_proof(probe) != direct_rc:
            fail(f"receipt proof diverges from the direct path at {k} leaves")
        ledger.proof_plane = None
    print("ok: plane-served proofs byte-equal the direct path across the ladder")


def check_storm_live() -> None:
    from fisco_bcos_tpu.scenario import run_proof_storm_bench

    doc = run_proof_storm_bench(
        seed=1, scale=0.1, workers=8, clients=6000, deadline_s=420
    )
    if doc.get("error"):
        fail(f"proof storm errored: {doc['error']}")
    if doc["proofs_served"] != doc["queued_clients"]:
        fail(
            f"only {doc['proofs_served']}/{doc['queued_clients']} queued "
            "clients served"
        )
    if doc["verify_failures"]:
        fail(f"{doc['verify_failures']} served proofs failed verification")
    if doc["cache_hit_ratio"] <= 0.9:
        fail(f"steady-state cache hit ratio {doc['cache_hit_ratio']} <= 0.9")
    if doc["flood"]["committed"] <= 0:
        fail("the concurrent flood committed nothing")
    state = doc.get("state_proofs")
    if not state or state["proofs_served"] <= 0:
        fail("the state-proof lane served nothing")
    if state["verify_failures"]:
        fail(f"{state['verify_failures']} state proofs failed verification")
    sync = doc.get("header_sync")
    if not sync or sync.get("error") or sync["headers_per_s"] <= 0:
        fail(f"the header-sync lane did not admit its chain: {sync}")
    print(
        f"ok: succinct lanes — {state['proofs_per_s']} state proofs/s over "
        f"{state['committed_keys']} committed keys, header sync "
        f"{sync['headers_per_s']} headers/s aggregate vs "
        f"{sync['headers_per_s_sequential']}/s per-header "
        f"({sync['speedup_vs_per_header']}x)"
    )
    print(
        f"ok: storm served {doc['proofs_served']} proofs from 8 client "
        f"threads at {doc['proofs_per_s']}/s (steady "
        f"{doc['proofs_per_s_steady']}/s, direct "
        f"{doc['direct_baseline_proofs_per_s']}/s, hit ratio "
        f"{doc['cache_hit_ratio']}), flood committed "
        f"{doc['flood']['committed']} txs concurrently"
    )


def check_rpc_surface() -> None:
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    from test_pbft import leader_of, make_chain, submit_txs

    from fisco_bcos_tpu.ops.merkle import MerkleProofItem, MerkleTree
    from fisco_bcos_tpu.rpc.jsonrpc import JsonRpcImpl
    from fisco_bcos_tpu.utils.bytesutil import from_hex, to_hex

    nodes, _gw = make_chain(4)
    leader = leader_of(nodes, 1)
    submit_txs(leader, 4)
    if not leader.sealer.seal_and_submit():
        fail("smoke chain could not commit a block")
    node = nodes[0]
    hashes = node.ledger.tx_hashes_by_number(1)
    rpc = JsonRpcImpl(node)
    out = rpc.handle(
        {
            "jsonrpc": "2.0", "id": 1, "method": "getProofBatch",
            "params": ["group0", "", [to_hex(h) for h in hashes] + ["0x" + "00" * 32], "tx"],
        }
    )
    res = out.get("result") or fail(f"getProofBatch errored: {out}")
    if res["proofs"][-1] is not None:
        fail("unknown hash did not map to None")
    header = node.ledger.header_by_number(1)
    suite = node.suite
    for h, doc in zip(hashes, res["proofs"]):
        idx = doc["index"]
        rebuilt = []
        for grp in doc["path"]:
            g0 = (idx // 16) * 16
            rebuilt.append(
                MerkleProofItem(
                    group=tuple(from_hex(g) for g in grp), index=idx - g0
                )
            )
            idx //= 16
        if not MerkleTree.verify_proof(
            h, doc["index"], doc["leaves"], rebuilt, header.txs_root,
            hasher=suite.hash_impl.name,
        ):
            fail("getProofBatch proof fails verification against the header")
    print(f"ok: getProofBatch served {len(hashes)} verifiable proofs + None")


def check_state_plane() -> None:
    import dataclasses

    os.environ["FISCO_STATE_PROOF"] = "1"
    try:
        sys.path.insert(0, os.path.join(_REPO, "tests"))
        from test_pbft import leader_of, make_chain, submit_txs

        from fisco_bcos_tpu.succinct import verify_state_proof
        from fisco_bcos_tpu.succinct.state_plane import (
            reference_state_commitment,
        )

        nodes, _gw = make_chain(4)
        for number in (1, 2):
            leader = leader_of(nodes, number)
            submit_txs(leader, 4, start=number * 10)
            if not leader.sealer.seal_and_submit():
                fail(f"state smoke chain could not commit block {number}")
        node = nodes[0]
        plane = node.state_plane
        if plane is None:
            fail("FISCO_STATE_PROOF=1 did not wire a StatePlane")
        head = plane.head_commitment()
        if head is None:
            fail("no committed head commitment after two blocks")
        if {n.state_plane.head_commitment() for n in nodes} != {head}:
            fail("replicas disagree on the state commitment")
        header = node.ledger.header_by_number(2)
        if header.state_commitment != head:
            fail("committed header does not carry the head commitment")
        ref = reference_state_commitment(
            node.storage.traverse(),
            hasher=plane.hasher,
            n_pages=plane.n_pages,
        )
        if ref != head:
            fail(
                "incremental commitment diverges from the full-recompute "
                "reference walker"
            )
        before = plane.stats()
        reqs = [("s_consensus", b"key"), ("s_config", b"tx_count_limit")]
        proofs = plane.state_proof_batch(reqs)
        after = plane.stats()
        if any(p is None for p in proofs):
            fail("committed system keys did not yield membership proofs")
        if after["hits"] - before["hits"] != len(reqs) or (
            after["misses"] != before["misses"]
        ):
            fail("commit-warm serve was not a pure snapshot hit")
        for (table, key), proof in zip(reqs, proofs):
            if not verify_state_proof(
                table, key, proof, head,
                hasher=plane.hasher, n_pages=plane.n_pages,
            ):
                fail(f"state proof for {table}:{key!r} fails verification")
        tampered = dataclasses.replace(
            proofs[0], entry_bytes=proofs[0].entry_bytes + b"\x01"
        )
        if verify_state_proof(
            "s_consensus", b"key", tampered, head,
            hasher=plane.hasher, n_pages=plane.n_pages,
        ):
            fail("tampered entry bytes were accepted")
        if verify_state_proof(
            "s_consensus", b"wrong", proofs[0], head,
            hasher=plane.hasher, n_pages=plane.n_pages,
        ):
            fail("proof verified against a key it does not bind")
        print(
            "ok: state plane — replicas agree, incremental == reference, "
            f"{len(reqs)} commit-warm proofs verify, tamper rejected"
        )
    finally:
        os.environ.pop("FISCO_STATE_PROOF", None)


def check_poseidon_kernel() -> None:
    """Opt-in (--poseidon): one XLA-CPU compile of the 65-round Montgomery
    scan costs minutes — cross-check the jitted sponge bit-exact against
    the pure-Python reference across the padding-boundary ladder."""
    import time

    from fisco_bcos_tpu.crypto.ref import poseidon as ref
    from fisco_bcos_tpu.ops.poseidon import poseidon_batch

    msgs = [bytes([i & 0xFF] * n) for i, n in enumerate(
        (0, 1, 30, 31, 32, 61, 62, 63, 93, 124, 125, 200)
    )]
    t0 = time.monotonic()
    got = poseidon_batch(msgs)
    dt = time.monotonic() - t0
    for i, m in enumerate(msgs):
        if bytes(got[i]) != ref.poseidon_hash(m):
            fail(f"device poseidon diverges from reference at len={len(m)}")
    print(
        f"ok: jitted poseidon bit-exact vs reference across "
        f"{len(msgs)} padding boundaries ({dt:.1f}s incl. compile)"
    )


def main() -> None:
    check_analysis_clean()
    check_bit_identity()
    check_storm_live()
    check_rpc_surface()
    check_state_plane()
    if "--poseidon" in sys.argv[1:]:
        check_poseidon_kernel()
    print("ALL PROOF CHECKS PASSED")


if __name__ == "__main__":
    main()
