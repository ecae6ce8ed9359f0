"""Blocks of the deployed-contract cell's own generator
(``benchmark/generators/parallelok_batches.py``: ``ParallelOk.transfer``
between existing accounts, payer and payee Zipf 0.99, every transaction DAG,
the conflict keys read from the ABI the contract was deployed with) through the
conflict-DAG runner, at 256 transactions over 400 accounts: it gives the
receipts, in index order, the storage rows and the state root of
``_execute_one`` on the same list, member by member, and those are the plain
reference's; its counters and its one record say what the contract leg did
(every member in the call's contract frame on the thread that executes the
block, the engine that finished each call, the seconds in the VM); an ABI that
lies about ``transfer`` is caught at block size and the block is run again in
level order, to the receipts and the root of ``FISCO_DAG_SERIAL=1``."""

import json

import pytest

from benchmark import contract_counters, manifest, refcontract
from benchmark.generators import parallelok_batches as gen
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.evm import contract_table
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.transaction import TransactionAttribute
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.utils.metrics import REGISTRY

SUITE = ecdsa_suite()
SEED = 2**31 + 4040
CONFIG = dict(manifest.config_of(manifest.load(), "air4-parallelok"), user_batches=50)
TRAFFIC = dict(manifest.traffic_of("flood"), batch_txs=8, senders=4)  # 50 x 8 = 400 accounts
BLOCK_TXS = 256


def deploy_on(ex, create):
    (rc,) = ex.execute_transactions([create])
    assert rc.status == 0 and rc.contract_address
    return rc.contract_address


DEPLOYER = b"\x0d" * 20


def corpus(abi=None):
    """The generator's corpus, its contract deployed by the first transaction of
    block 1 (so the address is the same on every executor of this file); with
    `abi`, the create transaction carries that ABI instead of the contract's."""
    c = gen.Corpus(CONFIG, TRAFFIC, SEED, block_limit=int(CONFIG["block_limit_ahead"]))
    c.deploy.sender = DEPLOYER
    if abi is not None:
        c.deploy = c._factory.create_signed(
            c._keys[0], chain_id="chain0", group_id="group0", block_limit=500, nonce="lying",
            to=b"", input=gen.creation_code(gen.contract_files(CONFIG["contract"])[0]), abi=abi)
        c.deploy.sender = DEPLOYER
    c.sign_opening()
    c.sign_until(BLOCK_TXS // c.batch_txs)
    senders = [SUITE.calculate_address(kp.pub) for kp in c._keys]
    for recs, batch in zip(c.opening_records + c.records, c.opening + c.batches):
        for rec, tx in zip(recs, batch):
            tx.sender = senders[rec[-1]]  # what admission would have filled
    return c


def block_of(c):
    return [tx for batch in c.batches for tx in batch]


def opened(c):
    """An executor inside block 1 with the contract deployed and the accounts open."""
    ex = TransactionExecutor(MemoryStorage(), SUITE)
    ex.next_block_header(BlockHeader(number=1))
    assert deploy_on(ex, c.deploy) == c._to
    opening = ex.execute_transactions([tx for batch in c.opening for tx in batch])
    assert all(rc.status == 0 and rc.output == b"" for rc in opening)
    return ex


def plain(receipts):
    return [(rc.status, rc.output, rc.gas_used, rc.block_number, rc.contract_address)
            for rc in receipts]


def rows(ex, c):
    out = {}
    for name in c.names:
        row = ex._block.storage.get_row(contract_table(c._to), refcontract.slot_of(name))
        out[name] = 0 if row is None else int.from_bytes(row.get(), "big")
    return out


def counted():
    got = contract_counters.snapshot()
    got["framed"] = sum(
        REGISTRY.counters_matching("fisco_executor_contract_framed_txs_total").values())
    got["pooled_txs"] = sum(REGISTRY.counters_matching("fisco_executor_dag_pooled_txs_total").values())
    got["reruns"] = sum(
        REGISTRY.counters_matching("fisco_executor_dag_conflict_reruns_total").values())
    return {k: v or 0.0 for k, v in got.items()}


def moved(before):
    return {k: v - before[k] for k, v in counted().items()}


def member_by_member(ex, txs):
    """`txs` through ``_execute_one`` one by one, in block order: what the
    batches' frames are held to (the DAG runner and the serial batch both
    execute a contract member in their contract frame, so neither is the
    other's judge)."""
    base = ex.reserve_contexts(len(txs))
    return [ex._execute_one(tx, ex._block, context_id=base + i) for i, tx in enumerate(txs)]


@pytest.fixture(scope="module")
def serial():
    """The same list through ``_execute_one`` member by member, and through
    the plain reference."""
    c = corpus()
    ex = opened(c)
    receipts = member_by_member(ex, block_of(c))
    wires = [[c.deploy.encode()], [tx.encode() for b in c.opening for tx in b],
             [tx.encode() for tx in block_of(c)]]
    balances, expected = refcontract.replay(wires, c._to)
    assert [(rc.status, rc.output) for rc in receipts] == expected[2]
    assert rows(ex, c) == {name: balances.get(name, 0) for name in c.names}
    return plain(receipts), rows(ex, c), ex.get_hash()


def test_the_dag_run_is_the_serial_loop_is_the_reference(serial):
    c = corpus()
    txs = block_of(c)
    assert len(txs) == BLOCK_TXS and all(tx.attribute & TransactionAttribute.DAG for tx in txs)
    ex = opened(c)
    levels = ex.dag_levels(txs)
    wide = sum(len(level) for level in levels if len(level) > 1)
    assert 1 < len(levels) < len(txs) and 0 < wide < len(txs), "wide levels and a chain"

    before = counted()
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert (plain(receipts), rows(ex, c), ex.get_hash()) == serial

    # every member, of a wide level or of a level of one, is one contract
    # call the native engine finished in the call's contract frame, none of
    # them a future
    got = moved(before)
    assert (got["contract_txs"], got["evm_native"], got["evm_interpreter"]) == (BLOCK_TXS, BLOCK_TXS, 0)
    assert got["framed"] == got["contract_txs"]
    assert (got["pooled_txs"], got["pool_wait_s"], got["reruns"]) == (0, 0, 0)
    assert 0 < got["evm_s"] < got["contract_tx_s"]
    (block,) = [s for s in TRACER.spans()
                if s.name == "executor.execute" and s.attrs["mode"] == "dag"]
    assert sum(block.attrs["widths"]) == BLOCK_TXS and sum(block.attrs["framed"]) == 0
    assert (block.attrs["contract_txs"], block.attrs["evm_native"]) == (BLOCK_TXS, BLOCK_TXS)
    assert block.attrs["contract_framed"] == block.attrs["contract_txs"]
    assert block.attrs["evm_s"] == pytest.approx(got["evm_s"])
    assert not [s for s in TRACER.spans() if s.name == "executor.tx"], "no record a transaction"


def test_the_serial_batch_counts_its_contract_calls_once_a_batch(serial):
    c = corpus()
    ex = opened(c)
    before = counted()
    TRACER.clear()
    receipts = ex.execute_transactions(block_of(c))
    assert (plain(receipts), rows(ex, c), ex.get_hash()) == serial
    got = moved(before)
    assert (got["contract_txs"], got["evm_native"], got["pool_wait_s"]) == (BLOCK_TXS, BLOCK_TXS, 0)
    assert got["framed"] == BLOCK_TXS
    assert 0 < got["evm_s"] < got["contract_tx_s"]
    (batch,) = [s for s in TRACER.spans()
                if s.name == "executor.execute" and s.attrs["mode"] == "serial"]
    assert (batch.attrs["contract_txs"], batch.attrs["contract_framed"]) == (BLOCK_TXS, BLOCK_TXS)


def test_an_abi_that_declares_only_from_is_caught_and_the_block_rerun(serial, monkeypatch):
    """``transfer`` writes both names; an ABI that declares the first alone puts
    transfers to one payee into one level. The check after that level sees the
    two writes of one row, and the block goes through the serial loop in level
    order: the receipts and the root of ``FISCO_DAG_SERIAL=1`` on the same
    list, and of ``_execute_one`` member by member in that order."""
    monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
    abi = json.loads(gen.contract_files(CONFIG["contract"])[1])
    transfer = next(e for e in abi if e["name"] == "transfer")
    transfer["conflictFields"] = transfer["conflictFields"][:1]
    c = corpus(abi=json.dumps(abi))
    txs = block_of(c)
    ex = opened(c)
    honest = opened(corpus())
    assert len(ex.dag_levels(txs)) < len(honest.dag_levels(txs)), "the lie packs the levels"

    before = counted()
    receipts = ex.dag_execute_transactions(txs)
    got = moved(before)
    assert got["reruns"] == 1
    assert got["contract_txs"] > BLOCK_TXS, "the discarded attempt's calls and the rerun's"
    assert got["framed"] == got["contract_txs"] == got["evm_native"], "both in the frame"
    monkeypatch.setenv("FISCO_DAG_SERIAL", "1")
    pinned = opened(c)
    pinned_receipts = pinned.dag_execute_transactions(txs)
    monkeypatch.delenv("FISCO_DAG_SERIAL")
    assert (plain(receipts), ex.get_hash()) == (plain(pinned_receipts), pinned.get_hash())
    judge = opened(c)
    in_level_order = [i for level in judge.dag_levels(txs) for i in level]
    base = judge.reserve_contexts(len(txs))
    for i in in_level_order:
        want = judge._execute_one(txs[i], judge._block, context_id=base + i)
        assert plain([receipts[i]]) == plain([want]), i
    assert ex.get_hash() == judge.get_hash()
    # the rerun walks the lying levels, so its receipts are the serial loop's
    # only where the lie reordered nothing that conflicts: hold it to a plain
    # replay of the same order instead
    order = [i for level in ex.dag_levels(txs) for i in level]
    wires = [[c.deploy.encode()], [tx.encode() for b in c.opening for tx in b],
             [txs[i].encode() for i in order]]
    balances, expected = refcontract.replay(wires, c._to)
    assert [(receipts[i].status, receipts[i].output) for i in order] == expected[2]
    assert rows(ex, c) == {name: balances.get(name, 0) for name in c.names}
    # transfers commute (addition modulo 2^256), so the balances are the honest run's
    assert rows(ex, c) == serial[1]
