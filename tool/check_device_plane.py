#!/usr/bin/env python
"""Device-plane smoke check (ISSUE 3 acceptance):

Flood one node with CONCURRENT ragged admission batches, proposal
verification (full-tx re-verification) and tx-sync imports, then assert:

- the device compile counter stays ≤ the bucket-ladder size per op
  (ragged shapes must converge onto the ladder, not compile per size);
- queue wait p99 is bounded (default 750 ms, --wait-p99-ms);
- every submitted tx was admitted exactly once (slices never crossed).

Runnable locally and from CI::

    python tool/check_device_plane.py [--txs N] [--wait-p99-ms MS]

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def _make_node():
    from fisco_bcos_tpu.crypto.suite import ecdsa_suite
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig

    suite = ecdsa_suite()
    kp = suite.signature_impl.generate_keypair(secret=0xDE71CE)
    cfg = NodeConfig(
        genesis=GenesisConfig(
            consensus_nodes=[ConsensusNode(kp.pub, weight=1)],
            tx_count_limit=2000,
        )
    )
    return Node(cfg, keypair=kp)


def _flood_txs(suite, tag: str, n: int):
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    fac = TransactionFactory(suite)
    sender = suite.signature_impl.generate_keypair(secret=0xF10C0)
    return [
        fac.create_signed(
            sender,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce=f"plane-{tag}-{i}",
            to=b"\x11" * 20,
            input=b"\x00" * (i % 96),
        )
        for i in range(n)
    ]


def check_plane_flood(n_txs: int, wait_p99_ms: float) -> None:
    """Concurrent ragged admission + proposal verification + sync imports
    through one shared plane."""
    from fisco_bcos_tpu.device.plane import device_lane, get_plane
    from fisco_bcos_tpu.observability.device import compile_counts
    from fisco_bcos_tpu.ops.hash_common import bucket_ladder
    from fisco_bcos_tpu.txpool.validator import batch_admit

    node = _make_node()
    suite = node.suite

    # ragged batch schedule: adversarial sizes that would each compile a
    # distinct program without bucketing
    sizes = [1, 2, 3, 5, 7, 11, 13, 17, 23, 29, 31, 37, 41, 53, 64, 100]
    sizes = [s for s in sizes if s <= max(n_txs, 1)]
    errors: list[str] = []
    admitted = [0]
    lock = threading.Lock()

    def rpc_flood(tag: int):
        # RPC-side admission (default lane)
        for k, sz in enumerate(sizes):
            txs = _flood_txs(suite, f"rpc{tag}-{k}", sz)
            results = node.txpool.submit_batch(txs)
            bad = [r for r in results if r.status != 0]
            with lock:
                admitted[0] += len(results) - len(bad)
            if bad:
                errors.append(f"rpc{tag}: {len(bad)}/{len(txs)} rejected")

    def proposal_verify():
        # consensus-lane re-verification of carried signatures
        for k, sz in enumerate(sizes):
            txs = _flood_txs(suite, f"prop-{k}", sz)
            with device_lane("consensus"):
                ok = batch_admit(txs, suite)
            if not ok.all():
                errors.append(f"proposal batch {k}: verify failed")

    def sync_import():
        for k, sz in enumerate(sizes):
            txs = _flood_txs(suite, f"sync-{k}", sz)
            results = node.txpool.submit_batch(txs, lane="sync")
            bad = [r for r in results if r.status != 0]
            with lock:
                admitted[0] += len(results) - len(bad)
            if bad:
                errors.append(f"sync batch {k}: {len(bad)} rejected")

    threads = [
        threading.Thread(target=rpc_flood, args=(0,)),
        threading.Thread(target=rpc_flood, args=(1,)),
        threading.Thread(target=proposal_verify),
        threading.Thread(target=sync_import),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    plane = get_plane()
    if not plane.drain(30.0):
        fail("plane did not drain within 30s")
    if errors:
        fail("; ".join(errors[:5]))

    expected = 3 * sum(sizes)  # 2 rpc floods + 1 sync flood (unique nonces)
    if admitted[0] != expected:
        fail(f"admitted {admitted[0]} txs, expected {expected}")

    # compile counter vs the bucket ladder: +1 slack for the pinned
    # "native" shape key ops emit on the host leg
    max_batch = plane.high_water  # merged batches never exceed high water by more than one request
    ladder_n = len(bucket_ladder(max(max_batch, max(sizes))))
    comp = compile_counts()
    print(f"compile counts per op: {comp} (ladder size {ladder_n})")
    for op, n in comp.items():
        if n > ladder_n + 1:
            fail(
                f"op {op} compiled {n} distinct shapes > ladder {ladder_n} "
                "(+1 native) — shape bucketing is not converging"
            )

    p99 = plane.wait_p99_ms()
    print(f"plane stats: {plane.stats()}")
    print(
        f"coalesce ratio {plane.coalesce_ratio():.2f}, wait p99 {p99:.2f} ms"
    )
    if p99 > wait_p99_ms:
        fail(f"queue wait p99 {p99:.1f} ms > bound {wait_p99_ms} ms")
    print("OK: plane flood (compile bound, wait p99, slice integrity)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--txs", type=int, default=100, help="max batch size")
    ap.add_argument(
        "--wait-p99-ms",
        type=float,
        default=750.0,
        help="queue-wait p99 bound (generous: CI hosts are 1-core)",
    )
    args = ap.parse_args()
    check_plane_flood(args.txs, args.wait_p99_ms)
    print("PASS: device plane smoke")


if __name__ == "__main__":
    main()
