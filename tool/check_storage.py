#!/usr/bin/env python
"""Storage-observatory smoke check (ISSUE 19 CI acceptance).

Floods a 4-node in-process PBFT chain whose nodes commit through DURABLE
sqlite backends, then asserts:

- the commit-path ledger recorded every committed height with rows
  written, entries copied and commit-context codec bytes — and those
  codec bytes EXPLAIN >= 90% of the bytes the durable backends actually
  applied in their 2PC commits (``SQLiteStorage.bytes_written``, the
  backend-owned ground truth the recorder never touches);
- ``GET /storage`` serves the per-block ledger + codec/copy document
  over the Air HTTP surface;
- ``tool/check_perf.py`` flags a synthetic +30% codec-bytes/block
  regression between two storage artifacts, and passes an unchanged
  pair.

Runnable locally and from CI::

    python tool/check_storage.py [--txs N] [--block-cap N]

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("FISCO_STORAGE_OBS", "1")  # the observatory under test
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def _build_chain(block_cap: int, secret_base: int, db_dir: str, n_nodes=4):
    """A 4-node in-proc chain where every node commits through its OWN
    sqlite file — the durable backend whose byte counters ground the
    accounting gate (an in-memory backend has no ``bytes_written``)."""
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)
    keypairs = [
        suite.signature_impl.generate_keypair(secret=secret_base + i)
        for i in range(n_nodes)
    ]
    cons = [ConsensusNode(kp.pub, weight=1) for kp in keypairs]
    gw = InprocGateway(auto=True)
    nodes = []
    for i, kp in enumerate(keypairs):
        cfg = NodeConfig(
            db_path=os.path.join(db_dir, f"node{i}.db"),
            genesis=GenesisConfig(
                consensus_nodes=list(cons), tx_count_limit=block_cap
            ),
        )
        node = Node(cfg, keypair=kp)
        gw.connect(node.front)
        nodes.append(node)

    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=secret_base + 99)

    def make_txs(prefix: str, n: int):
        return [
            fac.create_signed(
                sender, chain_id="chain0", group_id="group0", block_limit=500,
                nonce=f"{prefix}-{i}", to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call(
                    "userAdd(string,uint256)", f"{prefix}{i}", 1
                ),
            )
            for i in range(n)
        ]

    def leader_for(height: int):
        idx = nodes[0].pbft_config.leader_index(height, 0)
        target = nodes[0].pbft_config.nodes[idx].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    return nodes, make_txs, leader_for


def _durable_backend(node):
    """The SQLiteStorage under whatever wrapping the node config chose."""
    st = node.storage
    while not hasattr(st, "bytes_written") and hasattr(st, "backend"):
        st = st.backend
    if not hasattr(st, "bytes_written"):
        fail(f"node storage {type(node.storage).__name__} is not durable")
    return st


def run_flood_and_reconcile(n_txs: int, block_cap: int, db_dir: str) -> None:
    from fisco_bcos_tpu.observability.storagelog import STORAGE

    if not STORAGE.enabled:
        fail("storage observatory disabled — set FISCO_STORAGE_OBS=1")
    nodes, make_txs, leader_for = _build_chain(
        block_cap, secret_base=0x519, db_dir=db_dir
    )
    backends = [_durable_backend(nd) for nd in nodes]
    # genesis bootstrap wrote outside any commit window: measure deltas
    written_before = [b.bytes_written for b in backends]
    STORAGE.reset()
    txs = make_txs("sto", n_txs)
    entry = nodes[0]
    results = entry.txpool.submit_batch(txs)
    rejected = sum(1 for r in results if r.status != 0)
    if rejected:
        fail(f"{rejected}/{n_txs} txs rejected at admission")
    entry.tx_sync.maintain()
    stalls = 0
    while entry.txpool.pending_count() > 0 and stalls < 5:
        if not leader_for(nodes[0].block_number() + 1).sealer.seal_and_submit():
            stalls += 1
    if entry.txpool.pending_count() > 0:
        fail(f"chain stalled with {entry.txpool.pending_count()} txs pending")
    for nd in nodes:
        if not nd.scheduler.drain_commits(60.0):
            fail("commit worker failed to drain")
    heights = {nd.block_number() for nd in nodes}
    if len(heights) != 1:
        fail(f"replicas diverged after the flood: {sorted(heights)}")
    tip = heights.pop()
    if tip < 1:
        fail("flood committed no blocks")

    # -- ledger mechanics: every committed height has a closed record ----
    blocks = STORAGE.blocks_snapshot()
    closed = {
        b["height"]: b for b in blocks if not b.get("aborted")
    }
    missing = [h for h in range(1, tip + 1) if h not in closed]
    if missing:
        fail(f"commit ledger missing heights {missing} (tip={tip})")
    bad = [
        h for h, b in closed.items()
        if b["rows_written"] <= 0 or b["bytes_encoded"] <= 0
    ]
    if bad:
        fail(f"ledger records without rows/bytes at heights {sorted(bad)}")
    snap = STORAGE.snapshot()
    if not snap["copies"]:
        fail("no entry-copy sites recorded during the flood")
    commit_keys = [k for k in snap["codec"] if k.startswith("encode:commit")]
    if not commit_keys:
        fail("no commit-context encode traffic recorded during the flood")

    # -- the accounting gate: the ledger must EXPLAIN the durable bytes --
    truth = sum(
        b.bytes_written - w0 for b, w0 in zip(backends, written_before)
    )
    if truth <= 0:
        fail("durable backends report zero bytes written during the flood")
    explained = STORAGE.commit_bytes_total()
    ratio = explained / truth
    if ratio < 0.9:
        fail(
            f"commit-context codec bytes explain only {ratio:.1%} of the "
            f"{truth} bytes the durable backends applied (need >= 90%)"
        )
    amp = snap["totals"]["copy_amplification_mean"]
    print(
        f"storage ledger ok: {tip} blocks on 4 sqlite-backed nodes, "
        f"{explained} commit-codec bytes explain {ratio:.1%} of {truth} "
        f"durable bytes, copy amplification {amp:.2f} copies/row, "
        f"{len(snap['copies'])} copy sites"
    )


def check_storage_endpoint() -> None:
    """GET /storage over the Air HTTP surface serves the live document
    (recorder state left over from the flood leg)."""
    from fisco_bcos_tpu.observability.storagelog import storage_doc
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer

    server = RpcHttpServer(impl=None, port=0, storage=storage_doc)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/storage", timeout=10) as resp:
            if not resp.headers["Content-Type"].startswith("application/json"):
                fail("/storage content type is not application/json")
            doc = json.loads(resp.read())
    finally:
        server.stop()
    if not doc.get("enabled"):
        fail("/storage served enabled=false with the observatory on")
    if not doc.get("blocks"):
        fail("/storage served no per-block ledger after the flood")
    if not doc.get("codec"):
        fail("/storage served no codec accounting after the flood")
    b = doc["blocks"][-1]
    for key in ("height", "rows_written", "entries_copied", "bytes_encoded"):
        if key not in b:
            fail(f"/storage block record missing '{key}'")
    print(
        f"endpoint ok: /storage served {len(doc['blocks'])} block records, "
        f"{len(doc['codec'])} codec series, tip height {b['height']}"
    )


def check_perf_storage_gate(tmpdir: str) -> None:
    """check_perf.py must flag a synthetic +30% codec-bytes/block
    regression between storage artifacts and pass an unchanged pair."""
    import subprocess

    old = {
        "tag": "flood",
        "storage_commit": {
            "codec_bytes_per_block": 1900.0,
            "entries_copied_per_block": 120.0,
            "shard_prepare_p95_ms": 12.0,
            "shard_commit_p95_ms": 8.0,
        },
    }
    regressed = json.loads(json.dumps(old))
    regressed["storage_commit"]["codec_bytes_per_block"] = 1900.0 * 1.3
    paths = {}
    for name, doc in (("old", old), ("new", regressed), ("same", old)):
        paths[name] = os.path.join(tmpdir, f"storage_{name}.json")
        with open(paths[name], "w") as f:
            json.dump(doc, f)
    tool = os.path.join(_REPO, "tool", "check_perf.py")
    rc_bad = subprocess.run(
        [sys.executable, tool, paths["old"], paths["new"]],
        capture_output=True,
    ).returncode
    if rc_bad == 0:
        fail("check_perf.py passed a +30% codec-bytes/block regression")
    rc_ok = subprocess.run(
        [sys.executable, tool, paths["old"], paths["same"]],
        capture_output=True,
    ).returncode
    if rc_ok != 0:
        fail(f"check_perf.py failed an identical storage pair (rc={rc_ok})")
    print("check_perf ok: +30% codec-bytes/block flagged, identity passes")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--txs", type=int, default=96)
    ap.add_argument("--block-cap", type=int, default=32)
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as dbs:
        run_flood_and_reconcile(args.txs, args.block_cap, dbs)
        check_storage_endpoint()
    with tempfile.TemporaryDirectory() as tmp:
        check_perf_storage_gate(tmp)
    print("PASS: storage observatory live end to end")
    return 0


if __name__ == "__main__":
    sys.exit(main())
