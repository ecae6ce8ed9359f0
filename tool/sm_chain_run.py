#!/usr/bin/env python3
"""A national-crypto Air chain end to end, once: four in-process nodes with
``sm_crypto=True`` over ``InprocGateway``, batches of signed DagTransfer
``userAdd`` transactions through ``txpool.submit_batch`` at the next leader
(the fused SM admission on the device, the replicas' on the sync lane), sealed
and committed on all four. A run, not a benchmark cell: it sizes
``sm-air4-transfer.flood`` (PERF.md §7) and shows the chain reaches the fused
program.

    python3 tool/sm_chain_run.py --blocks 4 --batch-txs 1000

prints one JSON line: the device the run was on, the admission dispatch split,
per-block admission and seal times, and what was checked against the plain
reference (``benchmark/refsm.py``) and a dict replay. ``run()`` is what
``tests/test_sm_chain.py`` drives at a tiny size on the CPU."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_PATH = 'fisco_device_dispatch_path_total{op="admission",path="'


def _paths() -> dict:
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    return {k[len(_PATH):].split('"')[0]: v for k, v in REGISTRY.counters_matching(_PATH).items()}


def run(blocks: int, batch_txs: int, senders: int = 16, seed: int = 26, warm: int = 1) -> dict:
    """-> what happened, in plain values (see the module docstring)."""
    from benchmark import refsm
    from fisco_bcos_tpu.codec.abi import ABICodec
    from fisco_bcos_tpu.crypto.suite import sm_suite
    from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
    from fisco_bcos_tpu.front import InprocGateway
    from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig
    from fisco_bcos_tpu.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol.transaction import TransactionFactory

    suite = sm_suite()
    sign, codec, fac = suite.signature_impl, ABICodec(suite.hash), TransactionFactory(suite)
    node_keys = [sign.generate_keypair(secret=0x5C41B + i) for i in range(4)]
    committee = [ConsensusNode(kp.pub, weight=1) for kp in node_keys]
    gw = InprocGateway(auto=True)
    nodes = []
    for kp in node_keys:
        node = Node(NodeConfig(sm_crypto=True, genesis=GenesisConfig(
            consensus_nodes=list(committee), tx_count_limit=max(batch_txs, 1000))), keypair=kp)
        gw.connect(node.front)
        nodes.append(node)

    def head() -> int:
        return max(nd.engine.consensus_head()[0] for nd in nodes)

    def leader_for(height: int):
        cfg = nodes[0].pbft_config
        target = cfg.nodes[cfg.leader_index(height, 0)].node_id
        return next(nd for nd in nodes if nd.node_id == target)

    secrets = [0x5EED0000 + seed * 7919 + 104729 * i for i in range(senders)]
    keys = [sign.generate_keypair(secret=s) for s in secrets]
    limit = head() + 500
    batches, records = [], []
    for k in range(warm + blocks):
        txs, recs = [], []
        for i in range(batch_txs):
            j = k * batch_txs + i
            user, amount, who = f"sm{seed:x}-{j}", 1 + (j * 7919) % 999_983, j % senders
            signed = fac.create_signed(
                keys[who], chain_id="chain0", group_id="group0", block_limit=limit,
                nonce=f"n{seed:x}-{j}", to=DAG_TRANSFER_ADDRESS,
                input=codec.encode_call("userAdd(string,uint256)", user, amount))
            txs.append(fac.decode(signed.encode()))  # as on the wire: nothing cached
            recs.append((user, amount, who))
        batches.append(txs)
        records.append(recs)

    series, acks = [], []
    paths0 = {}
    try:
        for k, batch in enumerate(batches):
            if k == warm:
                paths0 = _paths()  # the warm batches load or compile the shapes
            entry = leader_for(head() + 1)
            t0 = time.perf_counter()
            results = entry.txpool.submit_batch(batch)
            entry.tx_sync.maintain()
            t1 = time.perf_counter()
            deadline = time.monotonic() + 120.0
            while entry.txpool.pending_count() > 0:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"chain stalled at height {head()}")
                if not leader_for(head() + 1).sealer.seal_and_submit():
                    time.sleep(0.002)
            for nd in nodes:
                nd.scheduler.drain_commits(60.0)
            tip = max(nd.block_number() for nd in nodes)
            while any(nd.block_number() < tip for nd in nodes):
                if time.monotonic() > deadline:
                    raise RuntimeError(f"replicas did not converge on height {tip}")
                time.sleep(0.002)
            t2 = time.perf_counter()
            acks.append([(int(r.status), bytes(r.tx_hash), bytes(r.sender)) for r in results])
            if k >= warm:
                series.append({"admit_ms": (t1 - t0) * 1e3, "seal_ms": (t2 - t1) * 1e3})
        paths = {p: v - paths0.get(p, 0) for p, v in _paths().items() if v - paths0.get(p, 0)}

        # the plain reference: every acknowledged hash and sender, from the
        # bytes the first replica stored; balances against a dict replay
        pubs = [refsm.pubkey_bytes(s) for s in secrets]
        crypto_off = unacked = 0
        balances: dict[str, int] = {}
        for batch, recs, ack in zip(batches, records, acks):
            for (user, amount, who), (status, ack_hash, ack_sender) in zip(recs, ack):
                if status != 0:
                    unacked += 1
                    continue
                balances.setdefault(user, amount)
                stored = nodes[0].ledger.tx_by_hash(ack_hash)
                ok, sender, pub, digest = (
                    refsm.admit(stored.encode_data(), bytes(stored.signature))
                    if stored is not None else (False, b"", b"", b""))
                if not (ok and digest == ack_hash and sender == ack_sender and pub == pubs[who]):
                    crypto_off += 1
        calls = {
            user: fac.create(chain_id="chain0", group_id="group0", block_limit=0, nonce="",
                             to=DAG_TRANSFER_ADDRESS,
                             input=codec.encode_call("userBalance(string)", user))
            for user in balances
        }
        balance_off = 0
        roots, heights = set(), set()
        for nd in nodes:
            n = nd.block_number()
            heights.add(n)
            roots.add(nd.ledger.header_by_number(n).state_root.hex())
            for user, want in balances.items():
                code, got = codec.decode_output(
                    ["uint256", "uint256"], nd.scheduler.call(calls[user]).output)
                balance_off += not (code == 0 and got == want)
    finally:
        for nd in nodes:
            nd.stop()
    return {
        "blocks": blocks, "batch_txs": batch_txs, "admission_paths": paths, "series": series,
        "valid_not_acknowledged": unacked, "acks_differing_from_plain_sm": crypto_off,
        "balances_differing_from_replay": balance_off, "state_roots": len(roots),
        "heights": sorted(heights), "committed": len(balances),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--batch-txs", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=26)
    args = ap.parse_args(argv)
    from fisco_bcos_tpu.utils.jaxenv import configure_compile_cache, device_identity

    configure_compile_cache()
    ident = device_identity()
    from fisco_bcos_tpu.observability.device import install_observatory

    install_observatory()
    doc = run(args.blocks, args.batch_txs, seed=args.seed)
    doc["device"] = {"platform": ident["platform"], "kind": ident["device_kind"],
                     "count": ident["count"]}
    total = sum(doc["admission_paths"].values())
    doc["device_leg_share"] = 100.0 * doc["admission_paths"].get("device", 0) / total if total else None
    print(json.dumps(doc), flush=True)
    sound = (doc["state_roots"] == 1 and len(doc["heights"]) == 1
             and not doc["valid_not_acknowledged"] and not doc["acks_differing_from_plain_sm"]
             and not doc["balances_differing_from_replay"])
    return 0 if sound else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # the program's daemon threads have no shutdown (benchmark/run.py)
