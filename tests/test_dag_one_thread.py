"""The conflict-DAG runner has one thread, the one that executes the block
(PR 41): a DAG call starts none and runs every member where it was called,
whatever the callee; a level's members execute in index order and the levels
in level order; a contract block executed while another thread fights for
the interpreter ends on the root of ``_execute_one`` member by member; and
the block's one record and the counters say so (no ``pooled``, no ``pool_wait_s``; the two pool
counters the benchmark still reads stay registered and never move)."""

import os
import threading

import pytest

import test_contract_dag_block as con
import test_dag_transfer_block as pre
from test_abi_conflict import Env, _call
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor import executor as executor_module
from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.protocol.transaction import TransactionAttribute
from fisco_bcos_tpu.utils.metrics import MetricsRegistry

DAG = TransactionAttribute.DAG
TRANSFER = "userTransfer(string,string,uint256)"


class Watch:
    """Every member's execution as (tx index, thread), in the order it
    happened, through the runner's three ways to execute a member (a member
    the contract frame hands on to ``_execute_one`` is seen once)."""

    def __init__(self, monkeypatch, txs):
        self.seen: list[tuple[int, int]] = []
        index = {id(tx): i for i, tx in enumerate(txs)}
        seen = self.seen

        def watched(real, tx_at):  # `real` with its transaction at args[tx_at]
            def method(*args, **kw):
                at = (index.get(id(args[tx_at])), threading.get_ident())
                if at[0] is not None and seen[-1:] != [at]:
                    seen.append(at)
                return real(*args, **kw)
            return method

        monkeypatch.setattr(TransactionExecutor, "_execute_one",
                            watched(TransactionExecutor._execute_one, 1))
        monkeypatch.setattr(executor_module._PrecompileFrame, "execute",
                            watched(executor_module._PrecompileFrame.execute, 2))
        monkeypatch.setattr(executor_module._ContractFrame, "execute",
                            watched(executor_module._ContractFrame.execute, 1))

    @property
    def order(self):
        return [i for i, _thread in self.seen]

    @property
    def threads(self):
        return {thread for _i, thread in self.seen}


def precompile_block():
    c = pre.corpus()
    return pre.opened(c), pre.block_of(c)


def contract_block():
    c = con.corpus()
    return con.opened(c), con.block_of(c)


def mixed_block():
    c = pre.corpus()
    ex = pre.opened(c)
    return ex, pre.mixed_block(c, pre.deployed_setfor(ex))


# -- (a) no thread is started, every member runs where the call was made ----------


@pytest.mark.parametrize("make", [precompile_block, contract_block, mixed_block])
def test_a_dag_call_starts_no_thread_and_runs_every_member_on_the_calling_thread(
        make, monkeypatch):
    ex, txs = make()
    assert max(map(len, ex.dag_levels(txs))) > 1, "a level wider than one"
    watch = Watch(monkeypatch, txs)
    before = set(threading.enumerate())
    receipts = ex.dag_execute_transactions(txs)
    assert set(threading.enumerate()) <= before
    assert not [t.name for t in threading.enumerate() if t.name.startswith("dag-exec")]
    assert watch.threads == {threading.get_ident()}
    assert sorted(watch.order) == list(range(len(txs))), "each member once, no rerun"
    assert len(receipts) == len(txs) and all(rc is not None for rc in receipts)


# -- (b) index order inside a level, level order across levels --------------------


def one_wide_level():
    """Transfers between pairs of accounts no two of which share a name."""
    c = pre.corpus()
    txs = [pre.call(DAG_TRANSFER_ADDRESS, TRANSFER, c.names[2 * k], c.names[2 * k + 1], 1 + k)
           for k in range(len(c.names) // 2)]
    return pre.opened(c), txs, 1


def a_chain_on_one_hot_account():
    """Every transfer pays out of the hottest account: levels of one, a block long."""
    c = pre.corpus()
    txs = [pre.call(DAG_TRANSFER_ADDRESS, TRANSFER, c.names[0], c.names[1 + k % 30], 1)
           for k in range(48)]
    return pre.opened(c), txs, len(txs)


def the_generators_zipf_block():
    ex, txs = precompile_block()
    return ex, txs, None


def the_contract_generators_zipf_block():
    ex, txs = contract_block()
    return ex, txs, None


@pytest.mark.parametrize("make", [one_wide_level, a_chain_on_one_hot_account,
                                  the_generators_zipf_block, the_contract_generators_zipf_block])
def test_members_execute_in_index_order_and_levels_in_level_order(make, monkeypatch):
    ex, txs, n_levels = make()
    levels = ex.dag_levels(txs)
    if n_levels is None:
        assert 1 < len(levels) < len(txs), "wide levels and a chain"
    else:
        assert len(levels) == n_levels
    assert all(level == sorted(level) for level in levels)
    watch = Watch(monkeypatch, txs)
    ex.dag_execute_transactions(txs)
    assert watch.order == [i for level in levels for i in level]


# -- (c) another thread fighting for the interpreter changes nothing --------------


def test_a_contract_block_beside_a_thread_that_takes_the_interpreter_ends_on_the_serial_root():
    ex, txs = contract_block()
    ser, _ = contract_block()
    want = con.plain(con.member_by_member(ser, txs)), ser.get_hash()
    stop = threading.Event()
    turns = [0]

    def spin():  # holds the interpreter, gives it up at every switch interval
        while not stop.is_set():
            turns[0] += 1

    other = threading.Thread(target=spin, name="interpreter-hog", daemon=True)
    other.start()
    try:
        got = con.plain(ex.dag_execute_transactions(txs)), ex.get_hash()
    finally:
        stop.set()
        other.join()
    assert turns[0] > 0 and got == want


# -- (d) the record and the counters of a contract block --------------------------


def test_a_contract_blocks_record_has_no_pool_in_it_and_the_pool_counters_never_move(
    monkeypatch,
):
    # a registry of the test's own: what another test of this worker process wrote
    # into the global one (test_contract_cell.py's snapshot test sets a pool counter
    # by hand) is not the runner's doing, and which files share a worker changes
    registry = MetricsRegistry()
    monkeypatch.setattr(executor_module, "REGISTRY", registry)

    def pool_counters():
        return {name: registry.counters_matching(name)
                for name in ("fisco_executor_dag_pooled_txs_total",
                             "fisco_executor_dag_pool_wait_seconds_total")}

    ex, txs = contract_block()
    ex.dag_execute_transactions(txs)  # registers what a first call registers
    before = pool_counters()
    ex, txs = contract_block()
    levels = ex.dag_levels(txs)
    TRACER.clear()
    ex.dag_execute_transactions(txs)
    (block,) = [s for s in TRACER.spans()
                if s.name == "executor.execute" and s.attrs["mode"] == "dag"]
    at = block.attrs
    assert at["widths"] == tuple(map(len, levels)) and at["framed"] == (0,) * len(levels)
    assert "pooled" not in at and "pool_wait_s" not in at
    assert at["contract_txs"] == at["evm_native"] == len(txs) and at["reruns"] == 0
    assert at["contract_framed"] == at["contract_txs"], "every member in the contract frame"
    # dag_pooled_tx_share (BENCHMARK.json) and dag_pool_wait_ms_per_block
    # (tests/benchmark_checks) read these two: present, one series each, at 0
    assert pool_counters() == before
    assert all(list(series.values()) == [0.0] for series in before.values())
    help_text = registry._help["fisco_executor_dag_pooled_txs_total"]
    assert "no thread pool since PR 41" in help_text


# -- what a block costs depends on nothing the host or the environment says -------


def test_the_runner_asks_neither_the_environment_nor_the_host_for_a_width(monkeypatch):
    with open(executor_module.__file__) as f:
        source = f.read()
    assert "concurrent.futures" not in source and "cpu_count" not in source
    assert not hasattr(executor_module, "ThreadPoolExecutor")
    ex, txs = contract_block()
    ser, _ = contract_block()
    asked = []
    real = os.environ.get
    monkeypatch.setattr(os, "cpu_count", lambda: pytest.fail("the host's cores were counted"))
    caller = threading.get_ident()

    def get(key, *a):  # what other threads of the worker ask is not the runner's asking
        if threading.get_ident() == caller:
            asked.append(key)
        return real(key, *a)

    monkeypatch.setattr(os.environ, "get", get)
    receipts = ex.dag_execute_transactions(txs)
    monkeypatch.undo()
    assert [key for key in asked if "DAG" in key] == ["FISCO_DAG_SERIAL"]
    # the contract frame asks once a call whether the native engine is switched off
    assert sorted(set(asked)) == ["FISCO_DAG_SERIAL", "FISCO_NO_NATIVE_EVM"]
    assert asked.count("FISCO_NO_NATIVE_EVM") == 1
    assert con.plain(receipts) == con.plain(con.member_by_member(ser, txs))


# -- the scheduler's blocks take the same one thread ------------------------------


def test_a_served_block_of_contract_calls_executes_on_the_thread_that_executes_the_block(
        monkeypatch):
    env = Env()
    addr = env.deploy_setfor()
    txs = [env.tx(addr, _call(k, 700 + k), attribute=DAG) for k in range(8)]
    watch = Watch(monkeypatch, txs)
    before = set(threading.enumerate())
    blk = env.run_block(txs)
    assert all(rc.status == 0 for rc in blk.receipts)
    assert not [t.name for t in set(threading.enumerate()) - before
                if t.name.startswith("dag-exec")]
    sealed = {id(tx) for tx in blk.transactions}
    assert sealed == {id(tx) for tx in txs}, "the pool seals the objects it was given"
    assert watch.threads == {threading.get_ident()} and sorted(watch.order) == list(range(8))
