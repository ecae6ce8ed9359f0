"""CPU rehearsals of the benchmark at a tiny size (blocks of 8): no device,
no speed. They skip the harness's look for a chip and drive the rest of a
run: the schedule repeats for a seed, a stall is charged to the batches
behind it, ``correct`` is true on a sound run, false under every control and
false when the timed path underneath accepts a corrupted lane."""

import io
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import checks, manifest, run
from benchmark.generators import signed_payloads, transfer_batches
from benchmark.spans import Spans

CELLS = [w["name"] for w in manifest.load()["workloads"]]
SEED = 2**31 + 12345  # the driver's seeds are large


@pytest.fixture(autouse=True)
def tiny_traffic(monkeypatch):
    """Every mix at the tiny sizes its own file states (``tiny``)."""
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)


def _run(workload, seconds=0.7):
    out = io.StringIO()
    args = run.parse(["--workload", workload, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.run(args, require_chip=False, out=out), out.getvalue()


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct_and_reports_the_cells_metrics(workload):
    line, said = _run(workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    doc = manifest.load()
    assert set(line["metrics"]) == {
        m["name"] for m in manifest.metrics_of(doc, "end_to_end", workload)}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # a rehearsal says what it ran on
    compared = [ln for ln in said.splitlines() if "compared " in ln]
    assert compared and all("(limit " in ln for ln in compared)
    assert "set-up {" in said and "series [" in said


@pytest.mark.parametrize("workload", CELLS)
def test_every_control_comes_out_not_correct(workload):
    """checks.py's own loop: sound on the seed, and each degraded variant of
    the observation (a lost write, a forked root, truncated digests, an
    accepted r = 0 lane, a wrong sender) is judged not correct."""
    assert checks.main(["seeds", "--workload", workload, "--seeds", "1",
                        "--seconds", "0.5", "--off-chip"]) == 0


@pytest.mark.parametrize("workload", ["air4-transfer.paced", "verify10k.stream"])
def test_correct_is_false_when_the_timed_path_accepts_a_corrupted_lane(workload, monkeypatch):
    from fisco_bcos_tpu.crypto import admission

    real = admission.admit_batch

    def lenient(payloads, sigs65):
        senders, ok, pubs, digests = real(payloads, sigs65)
        return senders, np.ones_like(np.asarray(ok)), pubs, digests

    monkeypatch.setattr(admission, "admit_batch", lenient)
    line, said = _run(workload)
    assert line["correct"] is False
    assert "corrupted_lanes_accepted: 0" not in said and "<-- outside" in said


def test_schedule_and_corpus_repeat_for_a_seed():
    traffic = dict(manifest.traffic_of("paced"))
    offsets = transfer_batches.due_offsets(traffic, 2.0)
    assert offsets == transfer_batches.due_offsets(traffic, 2.0)
    assert offsets == pytest.approx([0.0, 0.3, 0.6, 0.9, 1.2, 1.5, 1.8])
    assert transfer_batches.due_offsets(manifest.traffic_of("flood"), 2.0) is None

    def corpus(seed):
        c = transfer_batches.Corpus(traffic, seed, block_limit=500)
        c.sign_until(2)
        return c

    a, b, other = corpus(SEED), corpus(SEED), corpus(SEED + 1)
    assert [(tx.encode_data(), tx.signature) for tx in a.batches[1]] == [
        (tx.encode_data(), tx.signature) for tx in b.batches[1]]
    assert a.records == b.records and a.records != other.records
    assert [len(x) for x in a.batches] == [len(x) for x in other.batches] == [8, 8]
    assert a.corrupt(1) == b.corrupt(1) and len(a.corrupt(1)) == 4

    s1, s2 = (signed_payloads.Corpus(manifest.traffic_of("stream"), SEED) for _ in range(2))
    assert all((x["sigs"] == y["sigs"]).all() and x["payloads"] == y["payloads"]
               for x, y in zip(s1.blocks, s2.blocks))
    assert len(s1.blocks[0]["payloads"]) == 16


def test_a_stall_is_charged_to_the_batches_behind_it(monkeypatch):
    """Batches fall due faster than the chain commits them: each is still
    timed from its due time, so the commit times grow down the series and the
    generator's lateness shows it."""
    doc = manifest.load()
    config = manifest.config_of(doc, "air4-transfer")
    traffic = dict(manifest.traffic_of("paced"), tick_s=0.02)
    cell = manifest.driver_of(config).Cell(config, traffic, SEED, Spans())
    cell.setup(0.1)
    try:
        cell.window(0.1)
    finally:
        cell.close()
    series = cell.series
    assert [s["due_s"] for s in series] == pytest.approx([0.02 * k for k in range(5)])
    assert len({s["height"] for s in series}) == 5  # one batch, one block
    for prev, cur in zip(series, series[1:]):
        assert cur["late_ms"] > prev["late_ms"] and cur["commit_ms"] > prev["commit_ms"]
        # submitted as soon as the block before it commits, not at its own tick
        assert cur["late_ms"] == pytest.approx(prev["commit_ms"] - 20.0, abs=5.0)
    assert series[-1]["commit_ms"] > series[-1]["block_ms"] * 2


def test_off_the_chip_the_benchmark_refuses_and_prints_no_metric(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "verify10k.stream", "--seed", "1", "--seconds", "1"])
    assert e.value.code == run.RC_NO_CHIP
    captured = capsys.readouterr()
    assert "metrics" not in captured.out and "needs 1 TPU chip" in captured.err


def test_in_a_checkout_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest.load()["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "verify10k.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == run.RC_NO_PROGRAM
    assert "metrics" not in done.stdout and "not in this checkout" in done.stderr
