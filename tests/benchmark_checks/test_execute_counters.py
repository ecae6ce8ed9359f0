"""The executor's counters and their two readers (``benchmark/execute_counters.py``,
``layers/execute_loop_ms_per_block.py``, ``layers/execute_run_share.py``),
checked without a device: on a block run through an executor here, on a
program that has none of the counters, and in a traced rehearsal of a chain
cell at a tiny size, where every block is one run of ``userAdd``."""

import io

import pytest

import manifest_rules as rules
from benchmark import execute_counters, manifest, run
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS, SMALLBANK_ADDRESS
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.transaction import Transaction
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.utils import metrics

# the cells each list had when this file was written: still on it, at the front
FRONT = {".flood": ["air4-transfer.flood", "air4-catchup.backlog"], ".paced": ["air4-transfer.paced"]}
NAMES = [quantity + suffix for quantity in ("execute_loop_ms_per_block", "execute_run_share")
         for suffix in FRONT]
ENTRIES = [m for m in manifest.load()["per_layer"] if m["name"] in NAMES]


def manifest_rule(doc):
    mine = [m for m in doc["per_layer"] if m["name"] in NAMES]
    assert sorted(m["name"] for m in mine) == sorted(NAMES)
    for m in mine:
        assert m["source"] == "program_counter"
        assert m["layer"] == "Sealer, PBFT, scheduler, storage"
        rules.list_holds(doc, m, FRONT["." + m["name"].partition(".")[2]])


def test_the_four_entries_are_counters_of_the_executors_layer():
    manifest_rule(manifest.load())


def test_readers_on_a_block_executed_here():
    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)

    def call(to, sig, *args):
        return Transaction(to=to, input=codec.encode_call(sig, *args), sender=b"\x0b" * 20)

    ex = TransactionExecutor(MemoryStorage(), suite)
    ex.next_block_header(BlockHeader(number=1))
    before = execute_counters.totals()
    ex.execute_transactions(
        [call(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", f"u{i}", i) for i in range(6)]
        + [call(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "a", 1)]
        + [call(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "v", 1)])
    after = execute_counters.totals()
    assert after["batches"] - before["batches"] == 1  # the run's own observation is left out
    assert after["txs"] - before["txs"] == 8
    assert after["run_txs"] - (before["run_txs"] or 0) == 6
    assert after["loop_ms"] > before["loop_ms"]
    loop = manifest.reader_of("execute_loop_ms_per_block.flood")(None)
    share = manifest.reader_of("execute_run_share.paced")(None)
    assert loop == pytest.approx(after["loop_ms"] / after["batches"])
    assert share == pytest.approx(100.0 * after["run_txs"] / after["txs"]) and 0 < share <= 100


def test_readers_give_none_on_a_program_without_the_counters(monkeypatch):
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert execute_counters.totals() == {"loop_ms": 0, "batches": 0, "txs": 0, "run_txs": None}
    for m in ENTRIES:
        assert manifest.reader_of(m["name"])(None) is None
    # the parent of PR 31: the batch histograms, no run counter
    metrics.REGISTRY.observe("fisco_executor_batch_latency_ms", 40.0, mode="serial")
    metrics.REGISTRY.observe("fisco_executor_batch_txs", 1000, mode="serial")
    assert manifest.reader_of("execute_loop_ms_per_block.paced")(None) == 40.0
    assert manifest.reader_of("execute_run_share.flood")(None) is None


def test_a_chain_cells_blocks_are_runs_of_user_add(monkeypatch):
    """A traced run of the flood cell at a tiny size on the CPU: both
    quantities are in the line, and every executed transaction ran in a frame."""
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    args = run.parse(["--workload", "air4-transfer.flood", "--seed", str(2**31 + 31),
                      "--seconds", "0.5", "--trace", "1"])
    before = execute_counters.totals()
    line = run.run(args, require_chip=False, out=io.StringIO())
    after = execute_counters.totals()
    assert line["correct"] is True
    # the line's numbers are the process's totals (other tests' blocks too);
    # this run's own part of them: every transaction in a frame
    executed = after["txs"] - before["txs"]
    assert executed >= 4 * 8 * 3 and after["run_txs"] - (before["run_txs"] or 0) == executed
    assert 0.0 < line["metrics"]["execute_run_share.flood"]["value"] <= 100.0
    assert line["metrics"]["execute_run_share.flood"]["unit"] == "%"
    assert line["metrics"]["execute_loop_ms_per_block.flood"]["value"] == pytest.approx(
        after["loop_ms"] / after["batches"])
