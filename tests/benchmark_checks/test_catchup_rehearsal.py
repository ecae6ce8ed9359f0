"""CPU rehearsals of the catch-up cell at a tiny size (6 blocks of 8
transactions behind, gathers of 3 blocks, 3 more blocks for the traced
gather): no device, no speed. The sound run and ``checks.py``'s loop over the
controls come with the cell's name in ``test_benchmark_rehearsal.py``; here:
the comparisons one by one against ``benchmark/refsync.py``, each of the four
controls by name, the gathers' size, a window that closes on the clock,
``correct`` false when admission underneath lets a broken lane through, and
false on the block sync of before PR 30, whose observed behaviour is the first
control."""

import importlib.util
import io
import os
import sys

import numpy as np
import pytest

from benchmark import manifest, refsync, run
from benchmark.spans import Spans

CELL = "air4-catchup.backlog"
SEED = 2**31 + 30303  # the driver's seeds are large
HERE = os.path.dirname(os.path.abspath(__file__))
COMPARED = [
    "replica_blocks_behind_the_head", "backlog_txs_not_committed",
    "heights_with_another_state_root", "balances_differing_from_replay",
    "sampled_txs_differing_from_plain_crypto", "blocks_refused_in_window",
    "broken_block_applied", "strikes_off_one_for_broken_block",
    "genuine_block_not_applied_after",
]
SYNC_LAYERS = [
    "sync_verify_ms_per_block", "sync_verify_wait_ms_per_block", "sync_qc_ms_per_block",
    "sync_decode_ms_per_block", "sync_execute_ms_per_block", "sync_commit_ms_per_block",
    "sync_serve_ms_per_block", "sync_unattributed_ms_per_block", "sync_lanes_per_call",
]


@pytest.fixture(autouse=True)
def tiny(monkeypatch):
    from fisco_bcos_tpu.sync import block_sync

    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    monkeypatch.setattr(block_sync, "VERIFY_LANES_MAX", 24)  # three blocks of eight


def _run(seconds=5.0, trace=0):
    out = io.StringIO()
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", str(seconds),
                      "--trace", str(trace)])
    return run.run(args, require_chip=False, out=out), out.getvalue()


def _outside(said):
    return [ln.split("compared ")[1].split(":")[0] for ln in said.splitlines()
            if "compared " in ln and "<-- outside" in ln]


def test_a_sound_run_compares_exactly_against_the_plain_reference():
    line, said = _run()
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 48
    assert set(line["metrics"]) == {"committed_tps", "setup_s"}
    compared = [ln.split("compared ")[1] for ln in said.splitlines() if "compared " in ln]
    assert [c.split(":")[0] for c in compared] == COMPARED
    assert all(c.endswith(": 0 (limit 0)") for c in compared)
    assert '"backlog_s"' in said and "series [" in said


def test_a_traced_run_reports_every_layer_of_block_sync():
    line, _said = _run(trace=1)
    assert line["correct"] is True
    assert set(SYNC_LAYERS) <= set(line["metrics"])
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert value["sync_lanes_per_call"] == 24.0  # two gathers of three blocks
    assert 0 < value["sync_verify_wait_ms_per_block"] <= value["sync_verify_ms_per_block"]
    assert value["sync_execute_ms_per_block"] > value["sync_commit_ms_per_block"] > 0
    assert value["window_compiles.flood"] == 0
    # the window less its parts: what is left is small beside them
    parts = sum(value[k] for k in SYNC_LAYERS[:7] if k != "sync_verify_wait_ms_per_block")
    assert value["sync_unattributed_ms_per_block"] + parts == pytest.approx(
        value["consensus_ms_per_block.flood"], rel=0.02)
    # the chain cells' split reads this cell too: the same block, from the spans
    # (execution there is less its waits for the device, a group of their own)
    assert 0 < value["seal_execute_ms_per_block.flood"] <= value["sync_execute_ms_per_block"]
    assert value["sync_execute_ms_per_block"] <= (
        value["seal_execute_ms_per_block.flood"] + value["seal_device_wait_ms_per_block.flood"])
    assert value["seal_commit_ms_per_block.flood"] == pytest.approx(
        value["sync_commit_ms_per_block"], rel=0.1)
    assert 0 < value["seal_pbft_ms_per_block.flood"] <= value["sync_qc_ms_per_block"]
    # a device number is never read off the CPU
    assert not {"admission_us_per_sig.flood", "device_idle_share.flood"} & set(value)


@pytest.fixture
def cell():
    doc = manifest.load()
    _entry, config, traffic = manifest.resolve(doc, CELL)
    c = manifest.driver_of(config).Cell(config, traffic, SEED, Spans())
    c.setup(5.0)
    try:
        c.window(5.0)
        c.traced(int(traffic["trace_blocks"]))
        c.after_window()
        yield c
    finally:
        c.close()


def test_each_control_is_caught_by_its_own_comparison(cell):
    sound = cell.compare(cell.observe())
    assert [c["name"] for c in sound] == COMPARED and not any(c["value"] for c in sound)
    want = {
        "broken_applied": {"broken_block_applied", "strikes_off_one_for_broken_block"},
        "lost_write": {"balances_differing_from_replay"},
        "forked_root": {"heights_with_another_state_root"},
        "neighbours_sender": {"sampled_txs_differing_from_plain_crypto"},
    }
    controls = cell.controls()
    assert set(controls) == set(want)
    for name, degrade in controls.items():
        seen = cell.observe()
        degrade(seen)
        assert {c["name"] for c in cell.compare(seen) if c["value"] > c["limit"]} == want[name]


def test_the_observation_is_what_the_plain_reference_judges(cell):
    """The bytes the peers served, judged whole by refsync: every block
    quorum-signed and admitted, the senders the replica executed with, the
    balances it reads back, the heights it stands at."""
    seen = cell.observe()
    want = refsync.judge(seen["backlog"], seen["committee"])
    assert [b["applied"] for b in want["blocks"]] == [True] * 6
    assert want["height"] == seen["head"] == seen["height"]
    assert want["balances"] == seen["balances"] and len(want["balances"]) == 48
    assert [b["state_root"].hex() for b in want["blocks"]] == seen["roots"]
    for (k, i), got in zip(cell.picks, seen["sample"]):
        assert got["hash"] == want["blocks"][k]["hashes"][i]
        assert got["sender"] == want["blocks"][k]["senders"][i] and len(got["sender"]) == 20
    assert seen["sync"] == {"lanes": 48, "calls": 2, "applied": 6, "refused": 0}
    # the traced gather: the second chain's replica, one call of three blocks
    assert [s["height"] for s in cell.traced_series] == [1, 2, 3]
    # around the block served broken: refused, struck once, then the genuine one
    assert [(s["height"] - seen["head"], s["stored"], s["strikes"]) for s in seen["served"]] == [
        (0, False, 0), (0, False, 1), (1, True, 0)]
    broken = refsync.judge([cell.served_bytes["broken"]], seen["committee"])["blocks"][0]
    assert broken["qc"] and not broken["admits"]
    assert [i for i, s in enumerate(broken["senders"]) if not s] == cell.broken_lanes


def test_a_window_that_closes_on_the_clock_is_finished_after_it(monkeypatch):
    from fisco_bcos_tpu.sync import block_sync

    monkeypatch.setattr(block_sync, "MAX_BLOCKS_PER_REQUEST", 2)
    line, said = _run(seconds=0.02)
    assert line["correct"] is True and line["failed"] == 0
    assert 0 < line["attempted"] < 48  # the first range's blocks, no more
    assert not _outside(said)


def test_correct_is_false_when_admission_lets_a_broken_lane_through(monkeypatch):
    from fisco_bcos_tpu.crypto import admission

    real = admission.admit_batch

    def lenient(payloads, sigs65):
        senders, ok, pubs, digests = real(payloads, sigs65)
        return senders, np.ones_like(np.asarray(ok)), pubs, digests

    monkeypatch.setattr(admission, "admit_batch", lenient)
    line, said = _run()
    assert line["correct"] is False
    assert "broken_block_applied" in _outside(said)


def test_the_comparison_catches_the_block_sync_of_before_this_cell(monkeypatch):
    """The parent's sync applies the block served with r = 0 on a lane and
    executes with empty senders: the cell says not correct, by the first
    control's comparison and by the sample's."""
    from fisco_bcos_tpu.node import node

    name = "fisco_bcos_tpu.sync.block_sync_before_pr30"
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "block_sync_before_pr30.py"))
    before = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, name, before)
    spec.loader.exec_module(before)
    monkeypatch.setattr(
        node, "BlockSync", lambda *a, group_id="", **kw: before.BlockSync(*a, **kw))
    line, said = _run()
    assert line["correct"] is False
    assert {"broken_block_applied", "strikes_off_one_for_broken_block",
            "genuine_block_not_applied_after",
            "sampled_txs_differing_from_plain_crypto"} == set(_outside(said))


def test_a_program_whose_sync_does_not_reverify_leaves_at_once(monkeypatch, capsys):
    from fisco_bcos_tpu.sync import block_sync

    monkeypatch.delattr(block_sync, "VERIFY_LANES_MAX")
    with pytest.raises(SystemExit) as e:
        _run()
    assert e.value.code == run.RC_NO_PROGRAM
    assert "does not re-verify downloaded blocks" in capsys.readouterr().err
