"""Scenario lab — seed-deterministic adversarial + multi-tenant workloads.

ROADMAP's "Scenario lab" item: every bench run so far was one well-behaved
flood on a single group, so the fault-injection (resilience/faults.py),
tracing and isolation layers had never met the traffic a
millions-of-users deployment actually sees. This package generates that
traffic as composable, *bit-deterministic* workload primitives
(:mod:`workloads`), names canned compositions (:data:`SCENARIOS`) and
drives them against a live multi-group chain (:mod:`runner`) emitting a
per-group TPS/latency artifact (``tool/check_scenarios.py``,
``ScenarioRunner(...).run()``).

Seed contract: ``scenario.events(seed)`` is a pure function of
``(scenario, seed)`` — same seed, same byte-identical transaction/event
sequence (:meth:`Scenario.digest` proves it; tool/check_scenarios.py and
tests/test_scenarios.py assert it). All randomness flows from
``random.Random`` instances derived from the seed; signatures are RFC6979
deterministic; nothing reads clocks or global RNGs during generation.
"""

from .base import (
    SCENARIOS,
    Scenario,
    SubmitTxs,
    WorkloadContext,
    get_scenario,
    list_scenarios,
)
from .byzantine import (
    ATTACK_NAMES,
    ByzantineHarness,
    ByzantineReplica,
    run_byzantine_scenario,
)
from .proof_storm import run_proof_storm_bench
from .runner import ScenarioRunner
from .wire import (
    WireHarness,
    run_wire_catalog,
    run_wire_colluders,
    run_wire_partition,
)

__all__ = [
    "ATTACK_NAMES",
    "ByzantineHarness",
    "ByzantineReplica",
    "SCENARIOS",
    "Scenario",
    "ScenarioRunner",
    "SubmitTxs",
    "WireHarness",
    "WorkloadContext",
    "get_scenario",
    "list_scenarios",
    "run_byzantine_scenario",
    "run_proof_storm_bench",
    "run_wire_catalog",
    "run_wire_colluders",
    "run_wire_partition",
]
