"""Scenario lab: deterministic generation, composition, live isolation.

ISSUE 6 — the generation-side contract (same seed ⇒ bit-identical event
stream) is tier-1; the live multi-group runner case is marked ``slow``
(tool/check_scenarios.py exercises it at larger scale in CI).
"""

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from fisco_bcos_tpu.scenario import (  # noqa: E402
    SCENARIOS,
    Scenario,
    ScenarioRunner,
    SubmitTxs,
    WorkloadContext,
    get_scenario,
    list_scenarios,
)
from fisco_bcos_tpu.scenario import workloads  # noqa: E402

SCALE = 0.04  # a handful of batches per stream: fast, still multi-event


def test_catalog_names_the_issue_workloads():
    names = {n for n, _d in list_scenarios()}
    assert {
        "invalid-sig-storm", "mempool-churn", "hot-contract",
        "cross-group", "sync-storm", "isolation", "flood",
    } <= names
    for _n, desc in list_scenarios():
        assert desc  # every entry documents itself
    with pytest.raises(KeyError):
        get_scenario("no-such-scenario")


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_same_seed_same_stream(name):
    s = get_scenario(name)
    assert s.digest(21, SCALE) == s.digest(21, SCALE)


def test_different_seed_different_stream():
    s = get_scenario("invalid-sig-storm")
    assert s.digest(21, SCALE) != s.digest(22, SCALE)


def test_event_shapes_and_group_routing():
    iso = get_scenario("isolation")
    evs = list(iso.events(5, SCALE))
    assert evs and all(isinstance(e, SubmitTxs) for e in evs)
    groups = {e.group for e in evs}
    assert groups == {"groupA", "groupB"}
    assert iso.abusive_groups == ("groupA",)
    # the abuser's txs are statically admissible but signature-garbage
    bad = [e for e in evs if e.group == "groupA"]
    ctx = WorkloadContext()
    sig_len = ctx.suite.signature_impl.sig_len
    for e in bad:
        assert e.source == "spammer"
        for tx in e.txs:
            assert len(tx.signature) == sig_len
            assert tx.group_id == "groupA" and tx.chain_id == "chain0"


def test_sync_storm_rides_sync_lane_from_peer_sources():
    s = get_scenario("sync-storm")
    evs = list(s.events(5, SCALE))
    lanes = {e.lane for e in evs}
    assert "sync" in lanes  # the storm half
    peers = {e.source for e in evs if e.lane == "sync"}
    assert peers and all(p.startswith("peer:") for p in peers)
    # composition with a fault plan, seeded from the scenario seed
    plan = s.fault_plan(5)
    assert plan is not None and plan.seed == 5
    assert any(r.action == "delay" for r in plan._rules)
    assert get_scenario("flood").fault_plan(5) is None  # clean scenarios stay clean


def test_churn_contains_duplicates_and_replacements():
    ctx = WorkloadContext()
    import random

    evs = list(workloads.mempool_churn(ctx, random.Random(3), "group0", 6))
    txs = [t for e in evs for t in e.txs]
    nonces = [t.nonce for t in txs]
    assert len(nonces) > len(set(nonces))  # same-nonce spam present
    # replacement: same nonce, different payload bytes
    by_nonce = {}
    replaced = False
    for t in txs:
        prev = by_nonce.setdefault(t.nonce, t)
        if prev is not t and prev.input != t.input:
            replaced = True
    assert replaced


def test_scenario_digest_is_cross_instance_stable():
    # two independently-constructed Scenario walks (fresh WorkloadContext,
    # fresh keypair caches) — the digest must not depend on object identity
    a = get_scenario("cross-group").digest(9, SCALE)
    b = get_scenario("cross-group").digest(9, SCALE)
    assert a == b and len(a) == 64


@pytest.mark.slow
def test_isolation_runner_live_small():
    """Abuser + victim on one 4-host multi-group chain: the victim commits,
    the spammer is demoted, shedding is labeled by group and /health shows
    degraded-but-not-critical (tool/check_scenarios.py runs the larger
    version; this pins the contract in-suite)."""
    from fisco_bcos_tpu.resilience import HEALTH
    from fisco_bcos_tpu.txpool.quota import get_quotas
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    ScenarioRunner._reset_shared_state()
    # cold-compile stalls can stretch the spam batches minutes apart on
    # this 1-core host; widen the strike window so the test asserts the
    # DEMOTION mechanics, not the wall-clock of XLA compilation
    quotas = get_quotas()
    prev_window = quotas.strike_window_s
    quotas.strike_window_s = 600.0
    doc = ScenarioRunner(
        "isolation", seed=3, hosts=4, scale=0.5, seal_every=2, deadline_s=600
    ).run()
    try:
        assert not doc.get("error"), doc.get("error")
        victim, abuser = doc["groups"]["groupB"], doc["groups"]["groupA"]
        assert victim["committed"] > 0 and victim["height"] >= 1
        assert abuser["rejected"].get("sig", 0) > 0
        assert abuser["rejected"].get("demoted", 0) > 0
        assert doc["quotas"]["groupA"]["demote_drops"] > 0
        shed = REGISTRY.counters_matching("fisco_ratelimit_dropped_total")
        assert any('group="groupA"' in k for k in shed)
        snap = HEALTH.snapshot()
        comp = snap["components"]["admission:groupA"]
        assert comp["status"] == "degraded" and not comp["critical"]
        assert snap["status"] != "critical"
        # the runner's digest of what it actually submitted matches pure
        # generation — the run replays the generated stream bit-for-bit
        assert doc["determinism_digest"] == get_scenario("isolation").digest(
            3, 0.5
        )
    finally:
        quotas.strike_window_s = prev_window
        ScenarioRunner._reset_shared_state()


@pytest.mark.slow
def test_cross_group_runner_commits_both_groups():
    ScenarioRunner._reset_shared_state()
    doc = ScenarioRunner(
        "cross-group", seed=1, hosts=4, scale=0.1, seal_every=3,
        deadline_s=600,
    ).run()
    try:
        for g in ("group0", "group1"):
            assert doc["groups"][g]["committed"] > 0, doc["groups"][g]
    finally:
        ScenarioRunner._reset_shared_state()


def test_proof_storm_flood_is_deterministic():
    """The proof-storm bench's submission side keeps the lab's seed
    contract (the read-side hammer never touches chain state, so the
    flood stream is the whole determinism surface)."""
    from fisco_bcos_tpu.scenario.proof_storm import _flood_scenario

    s = _flood_scenario()
    assert s.digest(33, SCALE) == s.digest(33, SCALE)
    assert s.digest(33, SCALE) != s.digest(34, SCALE)


def test_proof_storm_is_a_bench_entry_point():
    # proof-storm is reached through run_proof_storm_bench, not the
    # catalog: it is no Scenario (it needs the three-leg runner)
    from fisco_bcos_tpu.scenario import run_proof_storm_bench

    assert callable(run_proof_storm_bench)
    assert "proof-storm" not in SCENARIOS
