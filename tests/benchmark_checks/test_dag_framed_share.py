"""The reader of ``dag_framed_tx_share`` (``benchmark/layers/dag_framed_tx_share.py``),
checked without a device: on registries built by hand, on a program without
the counter, and on a DAG block run through an executor here."""

import pytest

import manifest_rules as rules
from benchmark import manifest
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
from fisco_bcos_tpu.observability import BATCH_BUCKETS
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.transaction import Transaction
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.utils import metrics

NAME = "dag_framed_tx_share"
COUNTER = "fisco_executor_dag_framed_txs_total"


def read():
    return manifest.reader_of(NAME)(None)


def batch(registry, mode, txs):
    registry.observe("fisco_executor_batch_txs", txs, buckets=BATCH_BUCKETS, mode=mode)
    registry.observe("fisco_executor_batch_latency_ms", 40.0, mode=mode)


@pytest.fixture
def registry(monkeypatch):
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def manifest_rule(doc):
    entry = rules.entry_of(doc, NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "Sealer, PBFT, scheduler, storage", "moves": "committed_tps"}
    # the cell the list had when this file was written: still on it, at the front
    rules.list_holds(doc, entry, ["air4-dagtransfer.flood"])


def test_the_entry_is_a_counter_of_the_executors_layer_in_the_dag_cell():
    manifest_rule(manifest.load())


@pytest.mark.parametrize("framed,blocks,want", [
    (3000, [1000, 1000, 1000], 100.0),  # every DAG transaction in the frame
    (1500, [1000, 1000], 75.0),
    (0, [1000], 0.0),  # the counter is there and did not move: a block of contract calls
])
def test_share_is_the_framed_counter_over_the_dag_transactions(registry, framed, blocks, want):
    for txs in blocks:
        batch(registry, "dag", txs)
    registry.counter_add(COUNTER, framed)
    assert read() == pytest.approx(want)


def test_serial_and_run_series_are_never_read(registry):
    batch(registry, "dag", 1000)
    registry.counter_add(COUNTER, 1000)
    before = read()
    for _ in range(10):  # the opening blocks of set-up and their run frames
        batch(registry, "serial", 1000)
        batch(registry, "run", 1000)
    registry.counter_add("fisco_executor_run_txs_total", 10_000)
    assert read() == before == 100.0


@pytest.mark.parametrize("build", [
    lambda r: None,  # a program with no executor metric at all
    lambda r: batch(r, "dag", 1000),  # the parent: DAG blocks, no framed counter
    lambda r: r.counter_add(COUNTER, 0),  # the counter, and no DAG block yet
    lambda r: (batch(r, "serial", 1000), r.counter_add(COUNTER, 0)),
], ids=["nothing", "parent", "no_dag_block", "serial_only"])
def test_reader_gives_none_where_there_is_nothing_to_read(registry, build):
    build(registry)
    assert read() is None


def test_reader_on_a_dag_block_executed_here():
    """The process's own registry: other tests' DAG blocks are in the totals
    too, so the share is checked through this block's part of both sums."""
    from benchmark import dag_counters

    suite = ecdsa_suite()
    codec = ABICodec(suite.hash)

    def call(sig, *args):
        return Transaction(to=DAG_TRANSFER_ADDRESS, input=codec.encode_call(sig, *args),
                           sender=b"\x0b" * 20)

    def framed():
        return sum(metrics.REGISTRY.counters_matching(COUNTER).values())

    ex = TransactionExecutor(MemoryStorage(), suite)
    ex.next_block_header(BlockHeader(number=1))
    ex.execute_transactions([call("userAdd(string,uint256)", f"u{i}", 100) for i in range(6)])
    txs0, framed0 = dag_counters.snapshot()["txs"], framed()
    receipts = ex.dag_execute_transactions(
        [call("userTransfer(string,string,uint256)", f"u{i}", f"u{(i + 1) % 6}", 1)
         for i in range(6)])
    assert [rc.status for rc in receipts] == [0] * 6
    assert dag_counters.snapshot()["txs"] - txs0 == 6 and framed() - framed0 == 6
    share = read()
    assert share == pytest.approx(100.0 * framed() / dag_counters.snapshot()["txs"])
    assert 0.0 < share <= 100.0
