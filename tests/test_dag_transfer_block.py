"""Blocks of the parallel-transfer cell's own generator
(``benchmark/generators/dag_transfer_batches.py``: ``userTransfer`` between
existing accounts, payer and payee Zipf 0.99, every transaction DAG) through
the conflict-DAG runner, at 64 transactions over 40 accounts: whatever the
number of workers it gives the receipts, in index order, and the state root of
``execute_transactions`` on the same list; its counters and spans say what it
did; a declaration that lies is caught and the block still ends on the serial
root. And the generator: the same bytes for a seed, rank frequencies that
follow theta."""

import math
import random

import pytest

from benchmark import manifest
from benchmark.generators import dag_transfer_batches as gen
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS, default_registry
from fisco_bcos_tpu.executor.precompiled.bench_contracts import DagTransferPrecompiled
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.transaction import TransactionAttribute
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.utils.metrics import REGISTRY

SUITE = ecdsa_suite()
SEED = 2**31 + 3232
CONFIG = dict(manifest.config_of(manifest.load(), "air4-dagtransfer"), user_batches=5)
TRAFFIC = dict(manifest.traffic_of("flood"), batch_txs=8, senders=4)  # 5 x 8 = 40 accounts
BLOCK_TXS = 64


def corpus(seed=SEED, blocks=1):
    c = gen.Corpus(CONFIG, TRAFFIC, seed, block_limit=500)
    c.sign_opening()
    c.sign_until(blocks * BLOCK_TXS // c.batch_txs)
    senders = [SUITE.calculate_address(kp.pub) for kp in c._keys]
    for recs, batch in zip(c.opening_records + c.records, c.opening + c.batches):
        for rec, tx in zip(recs, batch):
            tx.sender = senders[rec[-1]]  # what admission would have filled
    return c


def block_of(c, n=0):
    per = BLOCK_TXS // c.batch_txs
    return [tx for batch in c.batches[n * per:(n + 1) * per] for tx in batch]


def opened(c, registry=None):
    """An executor inside block 1 with the corpus's accounts open."""
    ex = TransactionExecutor(MemoryStorage(), SUITE, registry=registry)
    ex.next_block_header(BlockHeader(number=1))
    opening = ex.execute_transactions([tx for batch in c.opening for tx in batch])
    assert all(rc.status == 0 and int.from_bytes(rc.output, "big") == 0 for rc in opening)
    return ex


def plain(receipts):
    return [(rc.status, rc.output, rc.gas_used, rc.block_number) for rc in receipts]


def counter(name):
    return sum(REGISTRY.counters_matching(name).values())


def stage_seconds():
    return {s: counter(f'fisco_executor_dag_stage_seconds_total{{stage="{s}"}}')
            for s in ("levelize", "run", "validate")}


@pytest.fixture(scope="module")
def serial():
    """The same list through ``execute_transactions``: the guarantee's other side."""
    c = corpus()
    ex = opened(c)
    receipts = ex.execute_transactions(block_of(c))
    return plain(receipts), ex.get_hash()


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_dag_runner_is_serial_equivalent_whatever_the_workers(workers, serial, monkeypatch):
    monkeypatch.setenv("FISCO_DAG_WORKERS", str(workers))
    c = corpus()
    txs = block_of(c)
    assert all(tx.attribute & TransactionAttribute.DAG for tx in txs)
    ex = opened(c)
    levels = ex.dag_levels(txs)
    assert 1 < len(levels) < len(txs), "a block with a wide level and a chain"
    assert sorted(i for level in levels for i in level) == list(range(len(txs)))

    before = {
        "levels": counter("fisco_executor_dag_levels_total"),
        "pooled": counter("fisco_executor_dag_pooled_txs_total"),
        "reruns": counter("fisco_executor_dag_conflict_reruns_total"),
        "stage": stage_seconds(),
    }
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert (plain(receipts), ex.get_hash()) == serial
    codes = {int.from_bytes(rc.output, "big") for rc in receipts}
    assert codes <= {0, 4} and 0 in codes

    # the counters say what dag_levels says
    assert counter("fisco_executor_dag_levels_total") - before["levels"] == len(levels)
    wide = sum(len(level) for level in levels if len(level) > 1)
    assert counter("fisco_executor_dag_pooled_txs_total") - before["pooled"] == (
        wide if workers > 1 else 0)
    assert counter("fisco_executor_dag_conflict_reruns_total") == before["reruns"]
    after = stage_seconds()
    assert after["levelize"] > before["stage"]["levelize"]
    assert after["run"] > before["stage"]["run"]
    assert (after["validate"] > before["stage"]["validate"]) == (workers > 1)

    # and so do the spans: one a level, one a checked level, under one block record
    spans = TRACER.spans()
    block = [s for s in spans if s.name == "executor.execute"]
    assert len(block) == 1
    assert block[0].attrs["mode"] == "dag" and block[0].attrs["txs"] == len(txs)
    assert block[0].attrs["levels"] == len(levels) and block[0].attrs["reruns"] == 0
    ran = [s for s in spans if s.name == "executor.dag_level"]
    assert [s.attrs["width"] for s in ran] == [len(level) for level in levels]
    assert [s.attrs["pooled"] for s in ran] == [
        workers > 1 and len(level) > 1 for level in levels]
    checked = [s for s in spans if s.name == "executor.dag_validate"]
    assert len(checked) == (sum(1 for level in levels if len(level) > 1) if workers > 1 else 0)
    (levelize,) = [s for s in spans if s.name == "executor.dag_levelize"]
    assert levelize.attrs["levels"] == len(levels)
    inside = [s for s in spans if s.name.startswith("executor.dag_")]
    assert all(s.parent == "executor.execute" for s in inside)
    parts = sum(s.dur for s in inside)
    assert parts <= block[0].dur and parts == pytest.approx(
        sum(after[k] - before["stage"][k] for k in after), rel=1e-6)


def test_several_blocks_in_a_row_keep_the_serial_state(monkeypatch):
    """Hot accounts carry their balance from block to block."""
    monkeypatch.setenv("FISCO_DAG_WORKERS", "4")
    c = corpus(blocks=3)
    dag, ser = opened(c), opened(c)
    for n in range(3):
        txs = block_of(c, n)
        assert plain(dag.dag_execute_transactions(txs)) == plain(ser.execute_transactions(txs))
    assert dag.get_hash() == ser.get_hash()


class PayerOnly(DagTransferPrecompiled):
    """Declares the payer as a transfer's only conflict key: a lie, since the
    payee's row is written too."""

    def criticals(self, codec, data):
        keys = super().criticals(codec, data)
        return keys[:1] if keys else keys


def test_a_declaration_that_lies_is_caught_and_the_block_ends_on_the_serial_root(
        serial, monkeypatch):
    monkeypatch.setenv("FISCO_DAG_WORKERS", "8")
    c = corpus()
    txs = block_of(c)
    ex = opened(c, registry={**default_registry(), DAG_TRANSFER_ADDRESS: PayerOnly()})
    honest = TransactionExecutor(MemoryStorage(), SUITE)
    assert len(ex.dag_levels(txs)) < len(honest.dag_levels(txs))
    reruns = counter("fisco_executor_dag_conflict_reruns_total")
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert counter("fisco_executor_dag_conflict_reruns_total") == reruns + 1
    assert (plain(receipts), ex.get_hash()) == serial
    (block,) = [s for s in TRACER.spans() if s.name == "executor.execute"]
    assert block.attrs["reruns"] == 1
    assert any(s.attrs["conflict"] for s in TRACER.spans() if s.name == "executor.dag_validate")


# -- the generator ----------------------------------------------------------------


def test_the_generator_repeats_for_a_seed_and_marks_what_it_signs():
    a, b, other = corpus(), corpus(), corpus(SEED + 1)

    def signed(c):  # the wire form less its import time, which is the clock's
        return [(tx.encode_data(), tx.signature, tx.attribute)
                for batch in c.opening + c.batches for tx in batch]

    assert signed(a) == signed(b) and signed(a) != signed(other)
    assert a.records == b.records and a.opening_records == b.opening_records
    assert a.records != other.records
    assert [len(x) for x in a.opening] == [8] * 5 and len(a.names) == len(set(a.names)) == 40
    assert all(tx.attribute == 0 for batch in a.opening for tx in batch)
    assert all(tx.attribute == TransactionAttribute.DAG for batch in a.batches for tx in batch)
    for recs in a.opening_records:
        assert all(1 <= balance <= 999_999 for _user, balance, _who in recs)
    for recs in a.records:
        for payer, payee, amount, who in recs:
            assert payer != payee and {payer, payee} <= set(a.names)
            assert 1 <= amount <= 10 and 0 <= who < 4
    nonces = [tx.nonce for batch in a.opening + a.batches for tx in batch]
    assert len(nonces) == len(set(nonces))
    assert a.corrupt(0) == b.corrupt(0) and len(a.corrupt(0)) == 4


def test_rank_frequencies_follow_theta():
    """40,000 payers drawn over 40 ranks. Stated tolerance: each of the first
    ten ranks within four standard deviations of 1 / (rank + 1) ** theta over
    the normalising sum, and the least-squares slope of log frequency on
    log rank over all ranks within 0.05 of -theta."""
    theta, n, draws = 0.99, 40, 40_000
    ranks = gen.ZipfRanks(n, theta)
    rng = random.Random(SEED)
    seen = [0] * n
    for _ in range(draws):
        seen[ranks.draw(rng)] += 1
    norm = sum((r + 1) ** -theta for r in range(n))
    for r in range(10):
        p = (r + 1) ** -theta / norm
        assert abs(seen[r] / draws - p) < 4 * math.sqrt(p * (1 - p) / draws), r
    xs = [math.log(r + 1) for r in range(n)]
    ys = [math.log(seen[r] / draws) for r in range(n)]
    mx, my = sum(xs) / n, sum(ys) / n
    slope = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sum((x - mx) ** 2 for x in xs)
    assert abs(slope + theta) < 0.05
    # the pair: independent draws, the payee again while it equals the payer
    c = corpus()
    pairs = [c.draw_pair() for _ in range(2_000)]
    assert all(a != b for a, b in pairs)
    assert sum(1 for a, _b in pairs if a == 0) > sum(1 for a, _b in pairs if a == n - 1)
