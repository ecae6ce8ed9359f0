#!/usr/bin/env python
"""Fleet-observatory smoke check (ISSUE 16 CI acceptance).

Drives live in-process committees and asserts the observatory's contract:

- a 4-node flood with one injected laggard: ``GET /fleet`` (served over
  real HTTP) returns all four nodes reachable, and the round forensics
  (``GET /round/<h>``) name the laggard's committee index as the
  straggler signer;
- a byzantine replica (vote-conflict attack from the PR 15 catalog): the
  merged fleet document carries the evidence totals and the evidence
  board attributes the offender's committee index;
- a ``scheduler.mid_2pc`` crash plan (the ``FISCO_CRASH_PLAN`` grammar)
  kills one replica mid-commit: the dead node leaves ``flight_<node>.json``
  showing the armed point firing, and the post-mortem loader places its
  last events on the fleet timeline;
- ``FISCO_FLEET_OBS=0``: no federation endpoint, noop ledger, and the
  chain still commits.

Runnable locally and from CI::

    python tool/check_fleet.py [--txs N]

Exit 0 on success, 1 with a named failure otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("FISCO_DEVICE_WINDOW_MS", "0")
# every Node.stop() in this smoke flushes a flight dump — keep them out
# of the repo, and give the crash leg a directory it can post-mortem
FLIGHT_DIR = tempfile.mkdtemp(prefix="check-fleet-")
os.environ["FISCO_FLIGHT_DIR"] = FLIGHT_DIR
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

# a CPU smoke: CPU platform, fast-compile XLA flags, the test suite's 32-lane
# batch bucket and the shared compile cache
pin_cpu()


def fail(msg: str) -> None:
    print(f"FAIL: {msg}")
    raise SystemExit(1)


def _build_chain(secret_base: int, n_nodes: int = 4, block_cap: int = 16):
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
    for kp in keypairs:
        cfg = NodeConfig(
            genesis=GenesisConfig(
                consensus_nodes=list(cons), tx_count_limit=block_cap
            )
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

    return nodes, gw, make_txs, leader_for


def _flood(nodes, make_txs, leader_for, n_txs: int, tag: str) -> None:
    entry = nodes[0]
    results = entry.txpool.submit_batch(make_txs(tag, n_txs))
    if any(r.status != 0 for r in results):
        fail(f"{tag}: txs rejected at admission")
    entry.tx_sync.maintain()
    stalls = 0
    while entry.txpool.pending_count() > 0 and stalls < 5:
        if not leader_for(nodes[0].block_number() + 1).sealer.seal_and_submit():
            stalls += 1
    if entry.txpool.pending_count() > 0:
        fail(f"{tag}: chain stalled")


def check_laggard_forensics(n_txs: int) -> None:
    """One quorum-critical replica processes every PBFT frame ~20 ms late
    (its own delivery thread — the inline mesh must not serialize the lag
    into everyone else's frames): the live chain commits through its late
    votes, /fleet (over HTTP) shows all four nodes, and /round/<h> names
    the laggard's committee index as the straggler."""
    import queue

    from fisco_bcos_tpu.front import ModuleID
    from fisco_bcos_tpu.rpc.http_server import RpcHttpServer
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    nodes, gw, make_txs, leader_for = _build_chain(secret_base=0x16A0)
    try:
        # block 1: all four replicas, no interference
        _flood(nodes, make_txs, leader_for, n_txs, tag="warm")
        if nodes[0].block_number() != 1:
            fail("warm block did not commit")

        # the laggard round at height 2: silence one replica so the
        # 3-of-4 quorum NEEDS the laggard's votes (late votes for a
        # committed height fall outside the engine's waterline — the lag
        # must be load-bearing to be observable), and push the laggard's
        # PBFT frames through a delayed worker thread
        height = 2
        leader = leader_for(height)
        others = [n for n in nodes if n is not leader]
        lag = next(n for n in others if n is not nodes[0])
        silent = next(n for n in others if n is not lag and n is not nodes[0])
        lag_index = next(
            i for i, c in enumerate(nodes[0].pbft_config.nodes)
            if c.node_id == lag.node_id
        )
        gw.disconnect(silent.node_id)
        frames: queue.Queue = queue.Queue()
        orig_on_receive = lag.front.on_receive

        def worker():
            while True:
                item = frames.get()
                if item is None:
                    return
                time.sleep(0.02)
                orig_on_receive(*item)

        def tardy_on_receive(module_id, src, payload):
            if int(module_id) == int(ModuleID.PBFT):
                frames.put((module_id, src, payload))
            else:
                orig_on_receive(module_id, src, payload)

        lag.front.on_receive = tardy_on_receive
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            results = leader.txpool.submit_batch(make_txs("lag", n_txs))
            if any(r.status != 0 for r in results):
                fail("laggard round: txs rejected at admission")
            leader.tx_sync.maintain()
            leader.sealer.seal_and_submit()
            live = [n for n in nodes if n is not silent]
            deadline = time.monotonic() + 30
            while any(n.block_number() < height for n in live):
                if time.monotonic() > deadline:
                    fail(
                        "laggard round stalled: "
                        f"{[n.block_number() for n in live]}"
                    )
                time.sleep(0.005)
        finally:
            frames.put(None)
            t.join(5.0)
            del lag.front.on_receive  # restore the class method
        # bring the silenced replica back and let block sync catch it up
        gw.connect(silent.front)
        deadline = time.monotonic() + 30
        while len({n.block_number() for n in nodes}) != 1:
            if time.monotonic() > deadline:
                fail("silenced replica never caught up")
            for n in nodes:
                n.block_sync.maintain()

        svc = nodes[0].fleet
        if svc is None:
            fail("fleet service missing with FISCO_FLEET_OBS unset")
        srv = RpcHttpServer(
            None, port=0,
            fleet=svc.fleet_doc,
            round_doc=svc.round_forensics,
            rounds=svc.rounds_forensics,
        )
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{base}/fleet", timeout=30) as resp:
                doc = json.loads(resp.read())
            if not doc.get("enabled"):
                fail(f"/fleet disabled: {doc}")
            if len(doc["nodes"]) != 4 or doc["reachable"] != 4:
                fail(
                    f"/fleet merged {len(doc['nodes'])} nodes, "
                    f"{doc['reachable']} reachable (want 4/4)"
                )
            if any(
                h["durable"] != height for h in doc["heights"].values()
            ):
                fail(f"/fleet heights disagree: {doc['heights']}")
            with urllib.request.urlopen(
                f"{base}/round/{height}", timeout=30
            ) as resp:
                rd = json.loads(resp.read())
            if not rd.get("found"):
                fail(f"/round/{height} found nothing: {rd}")
            aligned = rd["rounds"][0]
            # the silenced replica never saw round 2 — 3 observers minimum
            if len(aligned["nodes"]) < 3:
                fail(f"round {height} aligned {len(aligned['nodes'])} nodes")
            if aligned.get("straggler") != lag_index:
                fail(
                    f"straggler not named: got {aligned.get('straggler')} "
                    f"(lateness {aligned.get('vote_lateness_ms')}), "
                    f"want laggard index {lag_index}"
                )
            with urllib.request.urlopen(f"{base}/rounds?last=8", timeout=30) as resp:
                rr = json.loads(resp.read())
            if rr["skew_ms"]["n"] < 1:
                fail(f"/rounds carries no skew samples: {rr['skew_ms']}")
        finally:
            srv.stop()
        out = REGISTRY.render()
        for metric in (
            "fisco_round_phase_ms", "fisco_vote_arrival_spread_ms",
            "fisco_round_skew_ms",
        ):
            if metric not in out:
                fail(f"{metric} missing from /metrics after the flood")
        print(
            f"ok: laggard forensics — {height} blocks on 4 nodes, /fleet "
            f"4/4 reachable, /round/{height} straggler=index {lag_index} "
            f"(lateness {aligned['straggler_lateness_ms']:.1f} ms), "
            f"skew p95 {rr['skew_ms']['p95']:.2f} ms"
        )
    finally:
        for n in nodes:
            n.stop()


def check_byzantine_evidence() -> None:
    """A vote-conflict attack from the PR 15 catalog: the fleet document
    (pulled over the queued mesh, pumped by a background thread) merges the
    evidence totals, and the board attributes the adversary's index."""
    from fisco_bcos_tpu.consensus.audit import EVIDENCE
    from fisco_bcos_tpu.scenario import ByzantineHarness

    EVIDENCE.reset()
    h = ByzantineHarness(seed=1)
    try:
        for _ in range(2):
            if not h.commit_block(3):
                fail("byzantine leg: warmup commit failed")
        res = h.run_attack("vote_conflict")
        if not res.get("detected"):
            fail(f"vote_conflict not detected: {res}")

        observer = h.honest[0]
        if observer.fleet is None:
            fail("harness nodes carry no fleet service")
        # the harness mesh is queued (auto=False): pump deliveries while
        # the observer's pulls wait on their condition variable
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                h.deliver()
                time.sleep(0.002)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        try:
            doc = observer.fleet.fleet_doc()
        finally:
            stop.set()
            t.join(5.0)
        if doc["reachable"] != len(h.nodes):
            fail(
                f"byzantine leg: {doc['reachable']}/{len(h.nodes)} peers "
                f"reachable over the queued mesh"
            )
        if doc["evidence_total"].get("vote_conflict", 0) < 1:
            fail(f"/fleet evidence missing the attack: {doc['evidence_total']}")
        offenders = {
            r["from_index"] for r in EVIDENCE.snapshot()
            if r["kind"] == "vote_conflict"
        }
        if offenders != {h.adv_index}:
            fail(
                f"evidence attributes {offenders}, want adversary index "
                f"{h.adv_index}"
            )
        print(
            f"ok: byzantine evidence — vote_conflict on /fleet "
            f"(totals {doc['evidence_total']}), offender index "
            f"{h.adv_index} attributed"
        )
    finally:
        EVIDENCE.reset()
        for n in h.nodes:
            n.stop()


def check_crash_flight() -> None:
    """Arm ``scheduler.mid_2pc`` through the FISCO_CRASH_PLAN grammar and
    kill one replica mid-commit: the death leaves ``flight_<node>.json``
    showing the armed point firing, and post_mortem() rebuilds a timeline."""
    from fisco_bcos_tpu.observability.flight import post_mortem
    from fisco_bcos_tpu.resilience.crashpoints import (
        CrashPlan,
        InjectedCrash,
        clear_crash_plan,
        install_crash_plan,
    )

    nodes, gw, make_txs, leader_for = _build_chain(secret_base=0x16C0)
    try:
        _flood(nodes, make_txs, leader_for, 3, tag="warm")
        height = nodes[0].block_number() + 1
        target = next(n for n in nodes if n is not leader_for(height))
        scope = target.engine.crash_scope
        install_crash_plan(CrashPlan.from_spec(f"scheduler.mid_2pc@{scope}"))
        try:
            entry = nodes[0]
            entry.txpool.submit_batch(make_txs("crash", 3))
            entry.tx_sync.maintain()
            try:
                leader_for(height).sealer.seal_and_submit()
            except InjectedCrash:
                pass  # the armed replica died mid-cascade
        finally:
            clear_crash_plan()
        if not target.engine._crashed:
            fail("scheduler.mid_2pc never fired on the scoped replica")
        path = os.path.join(FLIGHT_DIR, f"flight_{scope}.json")
        if not os.path.exists(path):
            fail(f"dead node left no flight dump at {path}")
        with open(path) as f:
            doc = json.load(f)
        if doc["reason"] not in ("crash:scheduler.mid_2pc", "fatal_halt"):
            fail(f"flight dump reason {doc['reason']!r}")
        names = {(e["category"], e["name"]) for e in doc["events"]}
        if ("crash", "armed") not in names or ("crash", "fired") not in names:
            fail(f"flight dump missing armed/fired: {sorted(names)[:10]}")
        fired = [
            e for e in doc["events"]
            if e["category"] == "crash" and e["name"] == "fired"
        ]
        if fired[-1]["detail"].get("point") != "scheduler.mid_2pc":
            fail(f"fired event names {fired[-1]['detail']}")
        pm = post_mortem(FLIGHT_DIR)
        if scope not in pm["nodes"] or not pm["timeline"]:
            fail(f"post_mortem lost the dead node: {sorted(pm['nodes'])}")
        print(
            f"ok: crash flight — scheduler.mid_2pc killed {scope}, "
            f"flight dump shows the armed point firing "
            f"({len(doc['events'])} ring events), post-mortem timeline "
            f"{len(pm['timeline'])} events"
        )
    finally:
        gw  # noqa: B018 — keep the gateway alive until nodes stop
        for n in nodes:
            n.stop()


def check_wire_mesh() -> None:
    """The same forensics contract over REAL TCP sockets (ISSUE 17): a
    5-node :class:`WireHarness` committee where one replica's monotonic
    clock is skewed +250 ms — the clock probe must MEASURE that offset
    over the wire — and one replica receives every PBFT frame ~20 ms
    late while a fifth is partitioned off so the 4-of-5 quorum needs the
    late votes. With the probed correction applied to the skewed
    observer's ledger, the aligner must still name the true laggard
    (20 ms real delay), not the node whose uncorrected timeline is off
    by an order of magnitude more."""
    import queue

    from fisco_bcos_tpu.consensus.audit import EVIDENCE
    from fisco_bcos_tpu.front import ModuleID
    from fisco_bcos_tpu.resilience import HEALTH
    from fisco_bcos_tpu.resilience.faults import clear_fault_plan
    from fisco_bcos_tpu.scenario.wire import WireHarness
    from fisco_bcos_tpu.txpool.quota import get_quotas

    get_quotas().reset()
    HEALTH.reset()
    EVIDENCE.reset()
    clear_fault_plan()
    h = WireHarness(seed=0x17A, hosts=5)
    try:
        if not h.commit_block(4):
            fail("wire mesh: warm block over TCP failed")
        observer = h.nodes[0]
        svc = observer.fleet
        if svc is None:
            fail("wire mesh: fleet service missing with FISCO_FLEET_OBS unset")

        # leg A: nonzero measured offset correction over real sockets —
        # skew one peer's roundlog clock by a known amount and require
        # the midpoint-corrected probe to measure it through the RTT
        skewed = h.nodes[1]
        skew_s = 0.25
        base_clock = skewed.engine.roundlog.clock
        skewed.engine.roundlog.clock = lambda: base_clock() + skew_s
        offset, rtt = svc.probe_offset(skewed.node_id)
        if not (0.6 * skew_s < offset < 1.4 * skew_s):
            fail(
                f"wire mesh: probe measured {offset * 1e3:.1f} ms for an "
                f"injected {skew_s * 1e3:.0f} ms skew (rtt {rtt * 1e3:.1f} ms)"
            )

        # leg B: straggler naming through the correction — partition one
        # uninvolved replica off (4-of-5 quorum now NEEDS the laggard's
        # votes; late votes for committed heights fall outside the
        # waterline) and delay the laggard's PBFT delivery by ~20 ms
        number = h.height() + 1
        leader = h.leader_for(number)
        pool = [n for n in h.nodes if n not in (leader, observer, skewed)]
        lag, extra = pool[0], pool[1]
        lag_index = next(
            i for i, c in enumerate(observer.pbft_config.nodes)
            if c.node_id == lag.node_id
        )
        plan = h.cut([extra])
        frames: queue.Queue = queue.Queue()
        orig_on_receive = lag.front.on_receive

        def worker():
            while True:
                item = frames.get()
                if item is None:
                    return
                time.sleep(0.02)
                orig_on_receive(*item)

        def tardy_on_receive(module_id, src, payload):
            if int(module_id) == int(ModuleID.PBFT):
                frames.put((module_id, src, payload))
            else:
                orig_on_receive(module_id, src, payload)

        lag.front.on_receive = tardy_on_receive
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        alive = [n for n in h.nodes if n is not extra]
        try:
            if not h.commit_block_among(alive, n_txs=4):
                fail("wire mesh: laggard round stalled over TCP")
            height = max(n.block_number() for n in alive)
        finally:
            frames.put(None)
            t.join(5.0)
            del lag.front.on_receive  # restore the class method
        h.heal(plan)
        h.catch_up()

        doc = svc.round_forensics(height)
        if not doc.get("found"):
            fail(f"wire mesh: round {height} not found in any ledger: {doc}")
        aligned = doc["rounds"][0]
        # the partitioned replica never saw the round — 4 observers min
        if len(aligned["nodes"]) < 4:
            fail(
                f"wire mesh: round {height} aligned only "
                f"{len(aligned['nodes'])} observers"
            )
        if aligned.get("straggler") != lag_index:
            fail(
                f"wire mesh: straggler not named over TCP: got "
                f"{aligned.get('straggler')} "
                f"(lateness {aligned.get('vote_lateness_ms')}), want "
                f"laggard index {lag_index} — a miss here usually means "
                f"the {skew_s * 1e3:.0f} ms clock skew leaked through the "
                f"offset correction"
            )
        print(
            f"ok: wire mesh — 5 nodes on TCP sockets, probe measured "
            f"{offset * 1e3:.1f} ms of {skew_s * 1e3:.0f} ms injected skew "
            f"(rtt {rtt * 1e3:.2f} ms), /round/{height} straggler=index "
            f"{lag_index} (lateness "
            f"{aligned['straggler_lateness_ms']:.1f} ms) despite the "
            f"skewed observer"
        )
    finally:
        h.stop()
        get_quotas().reset()
        HEALTH.reset()
        EVIDENCE.reset()
        clear_fault_plan()


def check_obs_off() -> None:
    """FISCO_FLEET_OBS=0: no federation endpoint, the engine rides the
    noop ledger, and the chain still commits — zero-overhead off switch."""
    from fisco_bcos_tpu.front import ModuleID
    from fisco_bcos_tpu.observability.roundlog import NOOP_LEDGER

    os.environ["FISCO_FLEET_OBS"] = "0"
    try:
        nodes, _gw, make_txs, leader_for = _build_chain(secret_base=0x16D0)
        try:
            for n in nodes:
                if n.fleet is not None:
                    fail("fleet service built with FISCO_FLEET_OBS=0")
                if n.engine.roundlog is not NOOP_LEDGER:
                    fail("engine not on the noop ledger with obs off")
                if int(ModuleID.FLEET_TELEMETRY) in n.front._dispatch:
                    fail("4007 module registered with obs off")
            _flood(nodes, make_txs, leader_for, 4, tag="off")
            if nodes[0].block_number() < 1:
                fail("obs-off chain committed nothing")
            if nodes[0].engine.roundlog.snapshot()["rounds"]:
                fail("noop ledger recorded rounds")
            print(
                f"ok: FISCO_FLEET_OBS=0 — no 4007 endpoint, noop ledger, "
                f"{nodes[0].block_number()} blocks committed"
            )
        finally:
            for n in nodes:
                n.stop()
    finally:
        os.environ.pop("FISCO_FLEET_OBS", None)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--txs", type=int, default=8)
    args = ap.parse_args()
    check_laggard_forensics(args.txs)
    check_byzantine_evidence()
    check_crash_flight()
    check_wire_mesh()
    check_obs_off()
    print("check_fleet: all checks passed")


if __name__ == "__main__":
    main()
