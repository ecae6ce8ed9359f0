"""Blocks of the parallel-transfer cell's own generator
(``benchmark/generators/dag_transfer_batches.py``: ``userTransfer`` between
existing accounts, payer and payee Zipf 0.99, every transaction DAG) through
the conflict-DAG runner, at 64 transactions over 40 accounts: it gives the
receipts, in index order, and the state root of ``execute_transactions`` on
the same list; its counters and spans say what it did (calls to registry
precompiles in the level frame, a deployed contract's calls through
``_execute_one``, all on the calling thread); a declaration that lies, by a write or by a
read, is caught and the block still ends on the serial root; a framed member
that faults leaves what ``_execute_one`` leaves. And the generator: the same
bytes for a seed, rank frequencies that follow theta."""

import json
import math
import random

import pytest

from benchmark import manifest
from benchmark.generators import dag_transfer_batches as gen
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor import executor as executor_module
from fisco_bcos_tpu.executor.precompiled import (
    ACCOUNT_MGR_ADDRESS,
    DAG_TRANSFER_ADDRESS,
    default_registry,
)
from fisco_bcos_tpu.executor.precompiled.base import PrecompiledError
from fisco_bcos_tpu.executor.precompiled.bench_contracts import DagTransferPrecompiled
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.receipt import TransactionStatus
from fisco_bcos_tpu.protocol.transaction import Transaction, TransactionAttribute
from fisco_bcos_tpu.storage import Entry, MemoryStorage
from fisco_bcos_tpu.utils.metrics import REGISTRY

from evm_asm import _deployer
from test_abi_conflict import SETFOR_ABI, _setfor_runtime

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)
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


def opened(c, registry=None, backend=None):
    """An executor inside block 1 with the corpus's accounts open."""
    ex = TransactionExecutor(backend or MemoryStorage(), SUITE, registry=registry)
    ex.next_block_header(BlockHeader(number=1))
    opening = ex.execute_transactions([tx for batch in c.opening for tx in batch])
    assert all(rc.status == 0 and int.from_bytes(rc.output, "big") == 0 for rc in opening)
    return ex


def plain(receipts):
    return [(rc.status, rc.output, rc.gas_used, rc.block_number, rc.contract_address)
            for rc in receipts]


def call(to, signature, *args, sender=b"\x0b" * 20):
    return Transaction(to=to, input=CODEC.encode_call(signature, *args), sender=sender)


def dag_counts():
    return {k: counter(f"fisco_executor_dag_{k}_total")
            for k in ("levels", "pooled_txs", "framed_txs", "conflict_reruns")}


def moved(before):
    return {k: v - before[k] for k, v in dag_counts().items()}


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


def test_dag_runner_is_serial_equivalent(serial):
    c = corpus()
    txs = block_of(c)
    assert all(tx.attribute & TransactionAttribute.DAG for tx in txs)
    ex = opened(c)
    levels = ex.dag_levels(txs)
    assert 1 < len(levels) < len(txs), "a block with a wide level and a chain"
    assert sorted(i for level in levels for i in level) == list(range(len(txs)))

    before, stage_before = dag_counts(), stage_seconds()
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert (plain(receipts), ex.get_hash()) == serial
    codes = {int.from_bytes(rc.output, "big") for rc in receipts}
    assert codes <= {0, 4} and 0 in codes

    # the counters say what dag_levels says, and that every call ran in the
    # frame: no future, the check on every wide level
    assert moved(before) == {"levels": len(levels), "pooled_txs": 0,
                             "framed_txs": len(txs), "conflict_reruns": 0}
    after = stage_seconds()
    assert all(after[k] > stage_before[k] for k in after)

    # and so does the block's one record: a width and a framed count a level,
    # a verdict a checked level, the seconds of the three stages
    spans = TRACER.spans()
    (block,) = [s for s in spans if s.name == "executor.execute"]
    assert not [s for s in spans if s.name.startswith("executor.dag_")]
    at = block.attrs
    assert at["mode"] == "dag" and at["txs"] == len(txs)
    assert at["levels"] == len(levels) and at["reruns"] == 0
    assert at["widths"] == at["framed"] == tuple(len(level) for level in levels)
    assert "pooled" not in at and "pool_wait_s" not in at
    assert at["conflicts"] == (False,) * sum(1 for level in levels if len(level) > 1)
    assert set(at["stages"]) == {"levelize", "run", "validate"}
    assert "marks" not in at, "a mark a level and a check: sums only"
    parts = sum(at["stages"].values())
    assert parts <= block.dur and parts == pytest.approx(
        sum(after[k] - stage_before[k] for k in after), rel=1e-6)


def test_several_blocks_in_a_row_keep_the_serial_state():
    """Hot accounts carry their balance from block to block."""
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


def test_a_declaration_that_lies_is_caught_and_the_block_ends_on_the_serial_root(serial):
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
    assert block.attrs["reruns"] == 1 and block.attrs["conflicts"][-1] is True


class BlindBalance(DagTransferPrecompiled):
    """Declares nothing for ``userBalance``: a lie by omission, since the
    call reads the account's row, which a transfer of its level may write."""

    def criticals(self, codec, data):
        if data[:4] == codec.selector("userBalance(string)"):
            return []
        return super().criticals(codec, data)


def test_a_read_of_a_peers_write_inside_a_framed_level_is_caught(monkeypatch):
    """Balance reads that declare no key land in level 0 beside transfers that
    write the rows they read, some before their writer in block order and some
    after it. The block ends where the pinned serial loop ends
    (``FISCO_DAG_SERIAL=1``: ``_execute_one`` in level order, the rerun's own
    order, which is not block order once a declaration lies)."""
    c = corpus()
    transfers = block_of(c)
    hot = [c.names[rank] for rank in range(4)]
    txs = ([call(DAG_TRANSFER_ADDRESS, "userBalance(string)", name) for name in hot[:2]]
           + transfers
           + [call(DAG_TRANSFER_ADDRESS, "userBalance(string)", name) for name in hot[2:]])
    registry = {**default_registry(), DAG_TRANSFER_ADDRESS: BlindBalance()}
    ex, judge = opened(c, registry=registry), opened(c, registry=registry)
    level0 = ex.dag_levels(txs)[0]
    written = {name for i in level0 if 2 <= i < 2 + len(transfers)
               for name in c.records[(i - 2) // c.batch_txs][(i - 2) % c.batch_txs][:2]}
    assert {0, 1, len(txs) - 2, len(txs) - 1} <= set(level0) and written & set(hot)
    before = dag_counts()
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert moved(before)["conflict_reruns"] == 1 and moved(before)["pooled_txs"] == 0
    (block,) = [s for s in TRACER.spans() if s.name == "executor.execute"]
    monkeypatch.setenv("FISCO_DAG_SERIAL", "1")
    assert plain(receipts) == plain(judge.dag_execute_transactions(txs))
    assert ex.get_hash() == judge.get_hash()
    assert moved(before)["conflict_reruns"] == 1, "the pinned loop checks nothing"
    # the first level ran in the frame and failed its check; then every level again
    at = block.attrs
    assert at["conflicts"] == (True,) and at["reruns"] == 1
    assert at["widths"] == (len(level0),) + tuple(len(level) for level in ex.dag_levels(txs))
    assert at["framed"] == (len(level0),) + (0,) * (len(at["widths"]) - 1)


def deployed_setfor(ex):
    """``setFor(uint256,uint256)``, parallel by its first parameter
    (``tests/test_abi_conflict.py``), deployed inside the executor's block."""
    (rc,) = ex.execute_transactions([Transaction(
        to=b"", input=_deployer(_setfor_runtime()), abi=json.dumps(SETFOR_ABI),
        sender=b"\x0d" * 20)])
    assert rc.status == 0 and rc.contract_address
    return rc.contract_address


def mixed_block(c, contract):
    """24 transfers with six contract calls among them: five on keys of their
    own, the sixth on a key already taken, so it waits a level."""
    calls = [call(contract, "setFor(uint256,uint256)", key, 100 + n)
             for n, key in enumerate((0, 1, 2, 3, 4, 1))]
    txs = []
    for n, tx in enumerate(block_of(c)[:24]):
        txs.append(tx)
        if n % 4 == 0:
            txs.append(calls[n // 4])
    return txs


def test_a_level_of_precompile_and_contract_calls_splits_by_callee():
    c = corpus()
    ex, ser = opened(c), opened(c)
    contract = deployed_setfor(ex)
    assert deployed_setfor(ser) == contract
    txs = mixed_block(c, contract)
    levels = ex.dag_levels(txs)
    of_contract = [[i for i in level if txs[i].to == contract] for level in levels]
    assert len(of_contract[0]) == 5 and len(levels[0]) > 5, "one level, both kinds of callee"
    assert sum(map(len, of_contract)) == 6

    before = dag_counts()
    TRACER.clear()
    receipts = ex.dag_execute_transactions(txs)
    assert plain(receipts) == plain(ser.execute_transactions(txs))
    assert ex.get_hash() == ser.get_hash()
    assert all(rc.status == 0 for rc in receipts)

    # a precompile's member is framed, a contract's goes through _execute_one:
    # a level's widths less its framed are those members
    assert moved(before) == {"levels": len(levels), "pooled_txs": 0,
                             "framed_txs": len(txs) - 6, "conflict_reruns": 0}
    (block,) = [s for s in TRACER.spans()
                if s.name == "executor.execute" and s.attrs["mode"] == "dag"]
    assert block.attrs["framed"] == tuple(
        len(level) - len(mine) for mine, level in zip(of_contract, levels))
    assert tuple(w - f for w, f in zip(block.attrs["widths"], block.attrs["framed"])) == tuple(
        map(len, of_contract))
    assert block.attrs["contract_txs"] == 6
    assert len(block.attrs["conflicts"]) == sum(1 for level in levels if len(level) > 1)


@pytest.mark.parametrize("block", ["precompile", "mixed"])
def test_no_dag_call_opens_a_pool(block, serial, monkeypatch):
    """The runner's module has no executor of futures to build, none is built
    anywhere while a DAG call runs, and no thread is started."""
    import concurrent.futures
    import threading

    assert not hasattr(executor_module, "ThreadPoolExecutor")
    c = corpus()
    ex = opened(c)
    txs = block_of(c) if block == "precompile" else mixed_block(c, deployed_setfor(ex))
    started = []
    real_start = threading.Thread.start
    for kind in ("ThreadPoolExecutor", "ProcessPoolExecutor"):
        monkeypatch.setattr(concurrent.futures, kind,
                            lambda *a, **kw: pytest.fail("a pool was opened"))
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: started.append(self.name) or real_start(self))
    receipts = ex.dag_execute_transactions(txs)
    monkeypatch.undo()
    assert started == []
    if block == "precompile":
        assert (plain(receipts), ex.get_hash()) == serial
    else:
        assert all(rc.status == 0 for rc in receipts)


GOVERNOR, ALICE, BOB = b"\x0a" * 20, b"\x0b" * 20, b"\x0c" * 20


class SaveFaults(DagTransferPrecompiled):
    """``userSave`` writes the row, then faults."""

    def _save(self, ctx, user, amount):
        self._set_balance(ctx, user, 10**9)
        raise PrecompiledError("save refused")


def test_a_framed_member_that_faults_leaves_what_execute_one_leaves():
    """A PrecompiledError after a write, a frozen sender and input no selector
    matches, the first two inside a level wider than one: status, output and
    gas are ``_execute_one``'s, and none of the faulted call's writes stay."""
    c = corpus()
    n = c.names

    def in_block_two():
        backend = MemoryStorage()
        backend.set_row("s_config", b"auth_governors",
                        Entry().set(("0x" + GOVERNOR.hex()).encode()))
        ex = opened(c, registry={**default_registry(), DAG_TRANSFER_ADDRESS: SaveFaults()},
                    backend=backend)
        (rc,) = ex.execute_transactions([call(
            ACCOUNT_MGR_ADDRESS, "setAccountStatus(address,uint8)", ALICE, 1, sender=GOVERNOR)])
        assert rc.status == 0
        ex._block.storage.merge_into_prev()  # what the scheduler's 2PC does live
        ex.next_block_header(BlockHeader(number=2))
        return ex

    transfer = "userTransfer(string,string,uint256)"
    txs = [
        call(DAG_TRANSFER_ADDRESS, transfer, n[0], n[1], 1, sender=BOB),
        call(DAG_TRANSFER_ADDRESS, "userSave(string,uint256)", n[2], 5, sender=BOB),
        call(DAG_TRANSFER_ADDRESS, transfer, n[3], n[4], 1, sender=ALICE),
        call(DAG_TRANSFER_ADDRESS, "userBalance(string)", n[2], sender=BOB),
        Transaction(to=DAG_TRANSFER_ADDRESS, input=b"\x01\x02", sender=BOB),
        call(DAG_TRANSFER_ADDRESS, transfer, n[5], n[6], 2, sender=BOB),
    ]
    ex, judge = in_block_two(), in_block_two()
    assert ex.dag_levels(txs) == [[0, 1, 2], [3], [4], [5]]
    before = dag_counts()
    receipts = ex.dag_execute_transactions(txs)
    base = judge.reserve_contexts(len(txs))
    want = [judge._execute_one(tx, judge._block, context_id=base + i)
            for i, tx in enumerate(txs)]
    assert plain(receipts) == plain(want) and ex.get_hash() == judge.get_hash()
    assert [rc.log_entries for rc in receipts] == [rc.log_entries for rc in want]
    assert moved(before) == {"levels": 4, "pooled_txs": 0, "framed_txs": 6,
                             "conflict_reruns": 0}

    fault = int(TransactionStatus.PRECOMPILED_ERROR)
    assert [rc.status for rc in receipts] == [
        0, fault, int(TransactionStatus.ACCOUNT_FROZEN), 0, fault, 0]
    assert receipts[1].output == b"save refused"
    assert receipts[2].output == b"account is frozen"
    opening = {user: balance for recs in c.opening_records for user, balance, _who in recs}
    assert CODEC.decode_output(["uint256", "uint256"], receipts[3].output) == [0, opening[n[2]]]
    rows = dict(((t, k), e) for t, k, e in ex._block.storage.traverse())
    touched = {k.decode() for t, k in rows if t == "dag_transfer"}
    assert touched == {n[0], n[1], n[5], n[6]}, "the frozen sender's transfer moved nothing"


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
