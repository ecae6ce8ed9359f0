"""``benchmark/program_spans.py`` on a hand-made span list (one case per
quantity of BENCHMARK.json that reads program spans, and the rule's small
cases), then a CPU rehearsal: every ``program_span`` entry of BENCHMARK.json
resolves to a reader that runs to its end in a traced run."""

import io
import types

import pytest

import manifest_rules as rules
from benchmark import manifest, program_spans as ps, run

DRIVER, WORKER, NOTIFY, OTHER_BG = 1, 2, 3, 4
T0, T1, BLOCKS = 100.0, 110.0, 2


def rec(name, start, end, tid=DRIVER, derived=False):
    return types.SimpleNamespace(name=name, ts=start, dur=end - start, tid=tid, derived=derived)


ROWS = [
    ("bench.submit_batch", 100.0, 100.5), ("bench.seal_and_submit", 100.5, 101.5),
    ("bench.submit_batch", 102.0, 102.2), ("bench.seal_and_submit", 102.2, 102.6),
    ("bench.admit_batch", 103.0, 103.5),  # another cell's row: not a chain interval
    ("bench.submit_batch", 111.0, 112.0),  # after the window
]
RECORDS = [
    rec("boot", 1.0, 2.0),  # the ring reaches back before the window
    # block 1, admission: the entry node, then the replicas inside the gossip
    rec("txpool.submit_batch", 100.01, 100.30),
    rec("device.plane.wait", 100.05, 100.25),
    rec("txsync.maintain", 100.30, 100.48),
    rec("txpool.submit_batch", 100.32, 100.46),
    rec("device.plane.wait", 100.35, 100.45),
    rec("txpool.pool_wait", 100.0, 100.5, derived=True),
    # block 1, seal: one recursive PBFT round on the driving thread
    rec("seal", 100.50, 100.60),
    rec("device.merkle_root", 100.52, 100.55),  # no group of its own: counts with seal
    rec("pbft.propose", 100.60, 101.40),
    rec("pbft.message", 100.62, 101.38),
    rec("pbft.prepare", 100.60, 101.30, derived=True),  # a quorum wait, over all of it
    rec("pbft.execute_and_checkpoint", 100.70, 101.00),
    rec("scheduler.execute_block", 100.72, 100.98),
    rec("executor.execute", 100.75, 100.85),
    rec("gc.gen2", 100.80, 100.83),  # charged to the span it interrupted
    rec("device.merkle_root.sync", 100.90, 100.95),
    rec("pbft.checkpoint_commit", 101.05, 101.30),
    rec("scheduler.commit_block", 101.10, 101.28),
    rec("scheduler.2pc_prepare", 101.12, 101.20),
    rec("mystery.tail", 101.45, 101.60),  # crosses the seal span's end; maps to no group
    # block 2: one span that is the whole admission, a seal with no span at all
    rec("txpool.submit_batch", 102.0, 102.2),
    # the plane worker
    rec("device.plane.dispatch", 100.06, 100.24, WORKER),
    rec("device.admission", 100.07, 100.23, WORKER),
    rec("device.admission.marshal", 100.07, 100.09, WORKER),
    rec("device.admission.enqueue", 100.09, 100.10, WORKER),
    rec("device.admission.sync", 100.10, 100.22, WORKER),
    rec("device.admission.unpack", 100.22, 100.23, WORKER),
    rec("device.admission_sharded.marshal", 102.05, 102.08, WORKER),
    rec("device.admission_sharded.sync", 102.08, 102.18, WORKER),
    rec("device.keccak256.marshal", 102.30, 102.31, WORKER),  # not admission
    # commit-notify workers
    rec("proof.build", 100.60, 100.90, NOTIFY),
    rec("device.plane.wait", 100.65, 100.80, NOTIFY),
    rec("gc.gen1", 100.70, 100.71, NOTIFY),
    rec("proof.build", 102.30, 102.40, NOTIFY),
    rec("succinct.serve", 103.00, 103.05, OTHER_BG),
]
EXPECTED_MS = {  # per block, two blocks
    "admission_host_ms_per_block": (0.09 + 0.04 + 0.04 + 0.20) * 500,
    "seal_pbft_ms_per_block": (0.10 + 0.04 + 0.21 + 0.04 + 0.07) * 500,
    "seal_execute_ms_per_block": (0.26 - 0.05) * 500,
    "seal_commit_ms_per_block": 0.18 * 500,
    "seal_device_wait_ms_per_block": 0.05 * 500,
    "host_unattributed_ms_per_block": (0.03 + 0.05 + 0.05 + 0.40) * 500,
    "marshal_span_ms_per_block": (0.02 + 0.01 + 0.03) * 500,
    "device_sync_ms_per_block": (0.12 + 0.10) * 500,
    "gc_pause_ms_per_block": (0.03 + 0.01) * 500,
    "background_ms_per_block": (0.30 + 0.10 + 0.05) * 500,
}
STREAM = ("marshal_span_ms_per_block", "device_sync_ms_per_block")  # the stream cells' too


def suffixes(quantity):
    """No suffix = ``verify_tps``: the seam's two spans, which the stream cells write."""
    return ("", ".flood", ".paced") if quantity in STREAM else (".flood", ".paced")


NAMES = [quantity + suffix for quantity in EXPECTED_MS for suffix in suffixes(quantity)]
# the cells each list had when this file was written: still on it, at the front
CHAIN = ["air4-transfer.flood", "air4-catchup.backlog", "air4-dagtransfer.flood",
         "air4-parallelok.flood", "sm-air4-transfer.flood"]
FRONT = {"": ["verify10k.stream", "sm-verify10k.stream", "verify10k-quad.stream"],
         ".flood": CHAIN, ".paced": ["air4-transfer.paced"],
         # PR 30 kept the catch-up cell off this one: the threads beside its driving
         # thread are BlockSync's own, which the sync_* entries split
         "background_ms_per_block.flood": [c for c in CHAIN if c != "air4-catchup.backlog"]}


def entries(doc):
    return [m for m in doc["per_layer"] if m["name"] in NAMES]


def manifest_rule(doc):
    """Every quantity of ``EXPECTED_MS`` is a ``program_span`` entry in
    milliseconds under each of its suffixes, and its list holds to
    ``manifest_rules``."""
    mine = entries(doc)
    assert sorted(m["name"] for m in mine) == sorted(NAMES)
    for m in mine:
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_span")
        dot, suffix = m["name"].partition(".")[1:]
        holds = rules.admitting_list_holds if m["name"].startswith("admission_") else rules.list_holds
        holds(doc, m, FRONT.get(m["name"], FRONT[dot + suffix]))
        if suffix == "":
            assert m["moves"] == "verify_tps"


def ctx_for(records):
    return types.SimpleNamespace(
        program_spans=ps.split(records, ROWS, T0, T1, BLOCKS, DRIVER),
        cell=types.SimpleNamespace(window_blocks=BLOCKS), t0=T0, t1=T1)


def test_every_program_span_quantity_has_a_case():
    doc = manifest.load()
    manifest_rule(doc)
    # counted from this file's own table: a quantity times the suffixes that list it
    assert len(entries(doc)) == sum(len(suffixes(q)) for q in EXPECTED_MS)


@pytest.mark.parametrize("quantity", sorted(EXPECTED_MS))
def test_quantity_on_a_hand_made_span_list(quantity):
    read = manifest.reader_of(quantity)
    assert read(ctx_for(RECORDS)) == pytest.approx(EXPECTED_MS[quantity], abs=1e-6)
    # the ring's oldest record is younger than t0: a missing number, not a partial one
    assert read(ctx_for(RECORDS[1:])) is None
    assert read(ctx_for([])) is None


def test_the_split_accounts_for_every_instant_of_both_spans():
    parts = ps.split(RECORDS, ROWS, T0, T1, BLOCKS, DRIVER)
    for kind in (ps.SUBMIT, ps.SEAL):
        pieces = sum(v for k, v in parts.items() if k.startswith(kind + "|"))
        assert pieces == pytest.approx(parts[kind], abs=1e-9)
    assert parts[ps.SUBMIT] == pytest.approx(0.7 * 500) and parts[ps.SEAL] == pytest.approx(1.4 * 500)


RULE_CASES = {
    "innermost_wins": (
        [("scheduler.commit_block", 1.0, 3.0), ("pbft.checkpoint_commit", 0.5, 3.5),
         ("device.plane.wait", 1.5, 2.0)],
        {ps.PBFT: 1.0, ps.COMMIT: 1.5, ps.WAIT: 0.5, ps.NONE: 1.0}),
    "a_child_inherits_its_parents_group": (
        [("scheduler.execute_block", 0.0, 4.0), ("dmc.execute", 1.0, 2.0), ("serial", 1.2, 1.8)],
        {ps.EXECUTE: 4.0}),
    "a_span_crossing_either_edge_is_clipped": (
        [("pbft.message", -1.0, 1.0), ("scheduler.execute_block", 3.0, 9.0)],
        {ps.PBFT: 1.0, ps.NONE: 2.0, ps.EXECUTE: 1.0}),
    "a_span_outside_the_interval_counts_for_nothing": (
        [("seal", -2.0, -1.0), ("seal", 5.0, 6.0)], {ps.NONE: 4.0}),
    "a_child_that_outlives_its_parent_is_cut_to_it": (
        [("seal", 0.0, 2.0), ("scheduler.commit_block", 1.0, 3.0)],
        {ps.PBFT: 1.0, ps.COMMIT: 1.0, ps.NONE: 2.0}),
    "a_name_that_maps_to_nothing_is_other": (
        [("rpc.request", 1.0, 2.0), ("txpool.verify_block", 1.2, 1.4)],
        {ps.OTHER: 0.8, ps.PBFT: 0.2, ps.NONE: 3.0}),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_small_cases(case):
    spans, want = RULE_CASES[case]
    got = ps.innermost(spans, 0.0, 4.0, ps.SEAL)
    assert {k: pytest.approx(v) for k, v in want.items()} == got


def test_derived_records_and_other_threads_are_left_out_of_the_driving_thread():
    records = [
        rec("boot", 1.0, 2.0),
        rec("pbft.commit", 100.5, 101.5, derived=True),
        rec("scheduler.execute_block", 100.6, 100.9, tid=NOTIFY),
    ]
    parts = ps.split(records, ROWS[:2], T0, T1, 1, DRIVER)
    assert parts[f"{ps.SEAL}|{ps.NONE}"] == pytest.approx(1000.0)
    assert f"{ps.SEAL}|{ps.PBFT}" not in parts and f"{ps.SEAL}|{ps.EXECUTE}" not in parts
    assert parts["background"] == pytest.approx(300.0)


def test_a_program_that_does_not_mark_its_gaps_is_not_read():
    """The parent commit's records have no ``derived`` field: its quorum waits
    would be summed as work, so every quantity is missing there, not wrong."""
    unmarked = [types.SimpleNamespace(name=r.name, ts=r.ts, dur=r.dur, tid=r.tid) for r in RECORDS]
    assert ps.split(unmarked, ROWS, T0, T1, BLOCKS, DRIVER) is None


# -- CPU rehearsal --------------------------------------------------------------

# on the CPU admission takes the native leg, which has no device phases
NATIVE_LEG_ONLY = {"marshal_span_ms_per_block", "device_sync_ms_per_block"}
SEAL_PARTS = ("seal_pbft_ms_per_block", "seal_execute_ms_per_block",
              "seal_commit_ms_per_block", "seal_device_wait_ms_per_block")


@pytest.fixture
def tiny_traffic(monkeypatch):
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)


@pytest.mark.parametrize("workload", [w["name"] for w in manifest.load()["workloads"]])
def test_every_program_span_entry_resolves_to_a_reader_that_runs(workload, tiny_traffic):
    """A traced run at a tiny size on the CPU: every new entry of the cell is
    read to its end; a number where the CPU path writes the spans, left out
    where it takes the native leg."""
    args = run.parse(["--workload", workload, "--seed", str(2**31 + 77),
                      "--seconds", "0.7", "--trace", "1"])
    line = run.run(args, require_chip=False, out=io.StringIO())
    assert line["correct"] is True
    mine = [m for m in manifest.metrics_of(manifest.load(), "per_layer", workload)
            if m["source"] == "program_span"]
    if not mine:
        pytest.skip("the cell lists no program_span entry")
    for entry in mine:
        name = entry["name"]
        assert manifest.reader_path(name).endswith(name.split(".", 1)[0] + ".py")
        if name.split(".", 1)[0] in NATIVE_LEG_ONLY:
            assert name not in line["metrics"]
        else:
            assert line["metrics"][name]["value"] >= 0.0
            assert line["metrics"][name]["unit"] == entry["unit"]
    m = {k.split(".", 1)[0]: v["value"] for k, v in line["metrics"].items()}
    if "consensus_ms_per_block" in m:
        # a cell that seals blocks (it reports consensus_ms_per_block): the seal span
        # splits into its groups and what the spans miss
        parts = sum(m[k] for k in SEAL_PARTS)
        assert 0.0 < parts <= m["consensus_ms_per_block"] * (1 + 1e-6)
        assert parts + m["host_unattributed_ms_per_block"] >= m["consensus_ms_per_block"] * (1 - 1e-6)
