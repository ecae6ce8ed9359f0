"""ABI codec, precompiles, DAG levelization, scheduler execute/commit."""

import pytest

from fisco_bcos_tpu.codec.abi import ABICodec, abi_decode, abi_encode
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor.precompiled import (
    CONSENSUS_ADDRESS,
    DAG_TRANSFER_ADDRESS,
    KV_TABLE_ADDRESS,
    SMALLBANK_ADDRESS,
    SYS_CONFIG_ADDRESS,
    TABLE_MANAGER_ADDRESS,
)
from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig, Ledger
from fisco_bcos_tpu.protocol import Block, BlockHeader, ParentInfo
from fisco_bcos_tpu.protocol.transaction import TransactionAttribute, TransactionFactory
from fisco_bcos_tpu.scheduler import Scheduler
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.txpool import TxPool

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)


def test_abi_roundtrip():
    types = ["uint256", "string", "address", "bool", "bytes"]
    vals = [123456789, "héllo", b"\x11" * 20, True, b"\x01\x02"]
    enc = abi_encode(types, vals)
    assert abi_decode(types, enc) == vals
    # dynamic arrays
    enc2 = abi_encode(["uint256[]", "string"], [[1, 2, 3], "x"])
    assert abi_decode(["uint256[]", "string"], enc2) == [[1, 2, 3], "x"]
    # selector matches solidity convention (keccak4)
    sel = CODEC.selector("userTransfer(string,string,uint256)")
    assert len(sel) == 4
    call = CODEC.encode_call("userTransfer(string,string,uint256)", "a", "b", 7)
    assert call[:4] == sel
    assert CODEC.decode_input("userTransfer(string,string,uint256)", call) == ["a", "b", 7]


class Env:
    def __init__(self):
        self.store = MemoryStorage()
        self.ledger = Ledger(self.store, SUITE)
        self.ledger.build_genesis(
            GenesisConfig(consensus_nodes=[ConsensusNode(b"\x01" * 64)])
        )
        self.pool = TxPool(SUITE, self.ledger)
        self.executor = TransactionExecutor(self.store, SUITE)
        self.scheduler = Scheduler(self.executor, self.ledger, self.store, SUITE, self.pool)
        self.fac = TransactionFactory(SUITE)
        self.kp = SUITE.signature_impl.generate_keypair(secret=4242)
        self._nonce = 0

    def tx(self, to, sig, *args, attribute=0):
        self._nonce += 1
        return self.fac.create_signed(
            self.kp,
            chain_id="chain0",
            group_id="group0",
            block_limit=500,
            nonce=f"n{self._nonce}",
            to=to,
            input=CODEC.encode_call(sig, *args),
            attribute=attribute,
        )

    def run_block(self, txs):
        for t in txs:
            r = self.pool.submit(t)
            assert r.status == 0, r
        sealed, _ = self.pool.seal_txs(len(txs))
        parent_num = self.ledger.block_number()
        parent = self.ledger.header_by_number(parent_num)
        blk = Block(
            header=BlockHeader(
                number=parent_num + 1,
                parent_info=[ParentInfo(parent_num, parent.hash(SUITE))],
                timestamp=1000 + parent_num,
            ),
            transactions=sealed,
        )
        header = self.scheduler.execute_block(blk)
        self.scheduler.commit_block(header)
        return blk


def test_dag_transfer_lifecycle():
    env = Env()
    blk = env.run_block(
        [
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "alice", 100),
            env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "bob", 50),
        ]
    )
    assert all(rc.status == 0 for rc in blk.receipts)
    assert env.ledger.block_number() == 1

    blk2 = env.run_block(
        [
            env.tx(
                DAG_TRANSFER_ADDRESS,
                "userTransfer(string,string,uint256)",
                "alice",
                "bob",
                30,
                attribute=TransactionAttribute.DAG,
            ),
            env.tx(
                DAG_TRANSFER_ADDRESS,
                "userDraw(string,uint256)",
                "bob",
                10,
                attribute=TransactionAttribute.DAG,
            ),
        ]
    )
    assert all(rc.status == 0 for rc in blk2.receipts)
    # balances via read-only call
    q = env.tx(DAG_TRANSFER_ADDRESS, "userBalance(string)", "bob")
    rc = env.scheduler.call(q)
    ok, bal = CODEC.decode_output(["uint256", "uint256"], rc.output)
    assert (ok, bal) == (0, 70)
    q2 = env.tx(DAG_TRANSFER_ADDRESS, "userBalance(string)", "alice")
    _, bal_a = CODEC.decode_output(["uint256", "uint256"], env.scheduler.call(q2).output)
    assert bal_a == 70

    # insufficient transfer reverts with code 4, state unchanged
    blk3 = env.run_block(
        [
            env.tx(
                DAG_TRANSFER_ADDRESS,
                "userTransfer(string,string,uint256)",
                "alice",
                "bob",
                10_000,
            )
        ]
    )
    (code,) = CODEC.decode_output(["uint256"], blk3.receipts[0].output)
    assert code == 4
    _, bal_a2 = CODEC.decode_output(
        ["uint256", "uint256"], env.scheduler.call(q2).output
    )
    assert bal_a2 == 70


def test_dag_levels_respect_conflicts():
    env = Env()
    txs = [
        env.tx(DAG_TRANSFER_ADDRESS, "userTransfer(string,string,uint256)", "a", "b", 1),
        env.tx(DAG_TRANSFER_ADDRESS, "userTransfer(string,string,uint256)", "c", "d", 1),
        env.tx(DAG_TRANSFER_ADDRESS, "userTransfer(string,string,uint256)", "b", "c", 1),
        env.tx(SYS_CONFIG_ADDRESS, "setValueByKey(string,string)", "tx_count_limit", "500"),
        env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "e", 1),
    ]
    levels = env.executor.dag_levels(txs)
    # tx0 ∥ tx1 (disjoint), tx2 conflicts with both, tx3 serial barrier, tx4 after
    assert levels[0] == [0, 1]
    assert levels[1] == [2]
    assert levels[2] == [3]
    assert levels[3] == [4]


def test_dag_execution_matches_serial():
    env1, env2 = Env(), Env()
    mk = lambda env: [
        env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "u%d" % i, 100)
        for i in range(6)
    ] + [
        env.tx(
            DAG_TRANSFER_ADDRESS,
            "userTransfer(string,string,uint256)",
            "u%d" % i,
            "u%d" % ((i + 1) % 6),
            5 + i,
        )
        for i in range(6)
    ]
    env1.executor.next_block_header(BlockHeader(number=1))
    rc_serial = env1.executor.execute_transactions(mk(env1))
    env2.executor.next_block_header(BlockHeader(number=1))
    rc_dag = env2.executor.dag_execute_transactions(mk(env2))
    assert [r.encode() for r in rc_serial] == [r.encode() for r in rc_dag]
    assert env1.executor.get_hash() == env2.executor.get_hash()


def test_system_and_kv_precompiles():
    env = Env()
    node_hex = ("07" * 64)
    blk = env.run_block(
        [
            env.tx(SYS_CONFIG_ADDRESS, "setValueByKey(string,string)", "tx_count_limit", "2000"),
            env.tx(CONSENSUS_ADDRESS, "addSealer(string,uint256)", node_hex, 3),
            env.tx(TABLE_MANAGER_ADDRESS, "createKVTable(string,string,string)", "kv1", "k", "v"),
        ]
    )
    assert all(rc.status == 0 for rc in blk.receipts), [
        (rc.status, rc.output) for rc in blk.receipts
    ]
    assert env.ledger.ledger_config().tx_count_limit == 2000
    nodes = env.ledger.consensus_nodes()
    assert any(n.node_id == bytes.fromhex(node_hex) and n.weight == 3 for n in nodes)

    blk2 = env.run_block(
        [env.tx(KV_TABLE_ADDRESS, "set(string,string,string)", "kv1", "kk", "vv")]
    )
    assert blk2.receipts[0].status == 0
    rc = env.scheduler.call(env.tx(KV_TABLE_ADDRESS, "get(string,string)", "kv1", "kk"))
    assert CODEC.decode_output(["bool", "string"], rc.output) == [True, "vv"]

    # unknown config key reverts
    blk3 = env.run_block(
        [env.tx(SYS_CONFIG_ADDRESS, "setValueByKey(string,string)", "bogus", "1")]
    )
    assert blk3.receipts[0].status != 0


def test_smallbank():
    env = Env()
    blk = env.run_block(
        [
            env.tx(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "alice", 1000),
            env.tx(SMALLBANK_ADDRESS, "updateSaving(string,uint256)", "alice", 200),
            env.tx(SMALLBANK_ADDRESS, "sendPayment(string,string,uint256)", "alice", "bob", 400),
            env.tx(SMALLBANK_ADDRESS, "amalgamate(string,string)", "alice", "bob"),
        ]
    )
    assert all(rc.status == 0 for rc in blk.receipts)
    rc = env.scheduler.call(env.tx(SMALLBANK_ADDRESS, "getBalance(string)", "bob"))
    (bal,) = CODEC.decode_output(["uint256"], rc.output)
    assert bal == 400 + 200  # payment + amalgamated saving


def test_unknown_address_and_bad_selector():
    env = Env()
    blk = env.run_block([env.tx(b"\x99" * 20, "nope()")])
    assert blk.receipts[0].status != 0
    bad = env.tx(DAG_TRANSFER_ADDRESS, "nonexistent(uint256)", 1)
    blk2 = env.run_block([bad])
    assert blk2.receipts[0].status != 0


def test_commit_rejects_header_mismatch():
    env = Env()
    t = env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "x", 1)
    env.pool.submit(t)
    sealed, _ = env.pool.seal_txs(1)
    parent = env.ledger.header_by_number(0)
    blk = Block(
        header=BlockHeader(number=1, parent_info=[ParentInfo(0, parent.hash(SUITE))]),
        transactions=sealed,
    )
    header = env.scheduler.execute_block(blk)
    forged = BlockHeader.decode(header.encode())
    forged.state_root = b"\xff" * 32
    from fisco_bcos_tpu.scheduler.scheduler import SchedulerError

    with pytest.raises(SchedulerError):
        env.scheduler.commit_block(forged)
    env.scheduler.commit_block(header)
    assert env.ledger.block_number() == 1


class TestBlockPipeline:
    """preExecuteBlock analog (ref SchedulerInterface.h:76, StateMachine.cpp:47
    asyncPreApply): proposal N+1 executes on N's uncommitted post-state while
    N's commit quorum round-trips; commits then land in order."""

    def _blk(self, env, number, txs, parent_hash=None):
        parent = env.ledger.header_by_number(number - 1)
        ph = parent.hash(SUITE) if parent is not None else (parent_hash or b"\x00" * 32)
        return Block(
            header=BlockHeader(
                number=number,
                parent_info=[ParentInfo(number - 1, ph)],
                timestamp=1000 + number,
            ),
            transactions=txs,
        )

    def test_speculative_execute_then_ordered_commit(self):
        env = Env()
        b1 = self._blk(env, 1, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "ann", 100)])
        h1 = env.scheduler.execute_block(b1)
        # block 2 SPENDS state written by uncommitted block 1
        b2 = self._blk(env, 2, [env.tx(
            DAG_TRANSFER_ADDRESS, "userTransfer(string,string,uint256)", "ann", "ann", 1
        )])
        h2 = env.scheduler.execute_block(b2)  # speculative: ledger still at 0
        assert env.ledger.block_number() == 0
        assert all(rc.status == 0 for rc in b2.receipts), [rc.status for rc in b2.receipts]
        env.scheduler.commit_block(h1)
        env.scheduler.commit_block(h2)
        assert env.ledger.block_number() == 2
        # committed balance reflects both blocks
        rc = env.scheduler.call(env.tx(DAG_TRANSFER_ADDRESS, "userBalance(string)", "ann"))
        ok, bal = CODEC.decode_output(["uint256", "uint256"], rc.output)
        assert (ok, bal) == (0, 100)

    def test_speculation_matches_sequential_roots(self):
        def run(pipelined: bool):
            env = Env()
            b1 = self._blk(env, 1, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "bob", 7)])
            b2txs = [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "cat", 9)]
            h1 = env.scheduler.execute_block(b1)
            if pipelined:
                b2 = self._blk(env, 2, b2txs, parent_hash=h1.hash(SUITE))
                h2 = env.scheduler.execute_block(b2)
                env.scheduler.commit_block(h1)
                env.scheduler.commit_block(h2)
            else:
                env.scheduler.commit_block(h1)
                b2 = self._blk(env, 2, b2txs)
                h2 = env.scheduler.execute_block(b2)
                env.scheduler.commit_block(h2)
            return h2.state_root, h2.receipts_root

        assert run(True) == run(False)

    def test_reexecution_drops_stale_speculation(self):
        env = Env()
        b1 = self._blk(env, 1, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "dee", 5)])
        env.scheduler.execute_block(b1)
        b2 = self._blk(env, 2, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "eve", 6)])
        env.scheduler.execute_block(b2)
        # view change: a DIFFERENT proposal lands at height 1 — the height-2
        # speculation was chained on dead state and must vanish
        b1b = self._blk(env, 1, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "fox", 8)])
        h1b = env.scheduler.execute_block(b1b)
        assert 2 not in env.scheduler._executed
        env.scheduler.commit_block(h1b)
        assert env.ledger.block_number() == 1
        # height 2 re-executes cleanly on the new committed state
        b2b = self._blk(env, 2, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "gus", 3)])
        h2b = env.scheduler.execute_block(b2b)
        env.scheduler.commit_block(h2b)
        assert env.ledger.block_number() == 2

    def test_out_of_order_without_chain_still_rejected(self):
        env = Env()
        b3 = self._blk(env, 3, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "hal", 1)],
                       parent_hash=b"\x11" * 32)
        with pytest.raises(Exception):
            env.scheduler.execute_block(b3)

    def test_out_of_order_commit_rejected(self):
        """A speculative N+1 must NOT be committable before N — it would
        stage only N+1's overlay deltas and leave a durable hole at N."""
        env = Env()
        b1 = self._blk(env, 1, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "ida", 4)])
        h1 = env.scheduler.execute_block(b1)
        b2 = self._blk(env, 2, [env.tx(DAG_TRANSFER_ADDRESS, "userAdd(string,uint256)", "joe", 5)],
                       parent_hash=h1.hash(SUITE))
        h2 = env.scheduler.execute_block(b2)
        with pytest.raises(Exception, match="out of order"):
            env.scheduler.commit_block(h2)
        env.scheduler.commit_block(h1)
        env.scheduler.commit_block(h2)
        assert env.ledger.block_number() == 2


class TestSelfdestructPipeline:
    """SELFDESTRUCT's block-end kill (killSuicides at getHash) must be
    visible to a speculatively pre-executed N+1: the scheduler publishes
    N's post-state only after getHash, so the pipelined and sequential
    chains must produce identical roots and receipts when N kills a
    contract N+1 then calls."""

    def _deploy_tx(self, env, init):
        env._nonce += 1
        return env.fac.create_signed(
            env.kp, chain_id="chain0", group_id="group0", block_limit=500,
            nonce=f"sd{env._nonce}", to=b"", input=init,
        )

    _blk = TestBlockPipeline._blk

    def test_pipelined_call_sees_block_end_kill(self):
        from evm_asm import _deployer, asm

        from fisco_bcos_tpu.protocol.receipt import TransactionStatus

        victim_init = _deployer(asm(("PUSH", 0), "SELFDESTRUCT"))

        def run(pipelined: bool):
            env = Env()
            # block 1: deploy the victim; commit so its address is known
            b1 = self._blk(env, 1, [self._deploy_tx(env, victim_init)])
            h1 = env.scheduler.execute_block(b1)
            env.scheduler.commit_block(h1)
            victim = b1.receipts[0].contract_address
            assert victim
            # block 2 selfdestructs it; block 3 calls it
            b2 = self._blk(env, 2, [env.tx(victim, "any()")])
            call_tx = env.tx(victim, "any()")
            h2 = env.scheduler.execute_block(b2)
            if pipelined:
                b3 = self._blk(env, 3, [call_tx], parent_hash=h2.hash(SUITE))
                h3 = env.scheduler.execute_block(b3)  # speculative on b2 state
                env.scheduler.commit_block(h2)
                env.scheduler.commit_block(h3)
            else:
                env.scheduler.commit_block(h2)
                b3 = self._blk(env, 3, [call_tx])
                h3 = env.scheduler.execute_block(b3)
                env.scheduler.commit_block(h3)
            assert b2.receipts[0].status == 0
            # the killed contract is codeless -> unknown callee
            assert b3.receipts[0].status == int(TransactionStatus.CALL_ADDRESS_ERROR)
            return h3.state_root, h3.receipts_root

        assert run(True) == run(False)


# -- the run frame against the per-transaction path ---------------------------
#
# execute_transactions runs consecutive calls to one registry precompile in
# one frame (executor._execute_run). Every case below executes the same
# blocks twice, through execute_transactions and through _execute_one
# transaction by transaction, on both suites, and compares every receipt's
# wire form (also against the flat codec written out by hand), digest and
# gas, the state root and the receipts root.

import numpy as np  # noqa: E402
from receipt_ref import flat_receipt  # noqa: E402

from fisco_bcos_tpu.crypto.suite import sm_suite  # noqa: E402
from fisco_bcos_tpu.executor.precompiled import (  # noqa: E402
    ACCOUNT_MGR_ADDRESS,
    CPU_HEAVY_ADDRESS,
)
from fisco_bcos_tpu.protocol.receipt import TransactionStatus  # noqa: E402
from fisco_bcos_tpu.protocol.transaction import Transaction  # noqa: E402
from fisco_bcos_tpu.storage.entry import Entry  # noqa: E402
from fisco_bcos_tpu.utils.metrics import REGISTRY  # noqa: E402

SUITES = {"keccak256": SUITE, "sm3": sm_suite()}
GOVERNOR, ALICE, BOB = b"\x0a" * 20, b"\x0b" * 20, b"\x0c" * 20
U256_MAX = (1 << 256) - 1
ADD, SAVE, DRAW = "userAdd(string,uint256)", "userSave(string,uint256)", "userDraw(string,uint256)"
TRANSFER = "userTransfer(string,string,uint256)"


class _Calls:
    """Transactions for one suite's selectors, senders forced."""

    def __init__(self, suite):
        self.codec = ABICodec(suite.hash)

    def __call__(self, to, sig, *args, sender=ALICE):
        return Transaction(to=to, input=self.codec.encode_call(sig, *args), sender=sender)

    def dag(self, sig, *args, sender=ALICE):
        return self(DAG_TRANSFER_ADDRESS, sig, *args, sender=sender)

    def raw(self, to, data, sender=ALICE):
        return Transaction(to=to, input=data, sender=sender)

    def status(self, account, status):
        return self(ACCOUNT_MGR_ADDRESS, "setAccountStatus(address,uint8)", account, status,
                    sender=GOVERNOR)


def _full_user_add(c):
    return [[c.dag(ADD, f"user-{i}", 1000 + i, sender=bytes([1 + i % 7]) * 20)
             for i in range(64)]]


def _name_added_twice(c):
    return [[c.dag(ADD, "ann", 5), c.dag(ADD, "bea", 6), c.dag(ADD, "ann", 7),
             c.dag(ADD, "cy", 8)]]


def _empty_user(c):
    return [[c.dag(ADD, "ann", 5), c.dag(ADD, "", 6), c.dag(SAVE, "", 1), c.dag(ADD, "bea", 7)]]


def _save_draw_transfer_chain(c):
    return [[c.dag(ADD, "a", 100), c.dag(ADD, "b", 0), c.dag(ADD, "c", 0),
             c.dag(SAVE, "a", 50), c.dag(DRAW, "a", 30),
             c.dag(TRANSFER, "a", "b", 120), c.dag(TRANSFER, "b", "c", 70),
             c.dag("userBalance(string)", "c")]]


def _insufficient_and_overflow(c):
    return [[c.dag(ADD, "a", 10), c.dag(ADD, "b", U256_MAX),
             c.dag(DRAW, "a", 11), c.dag(TRANSFER, "a", "b", 11),  # insufficient: 4
             c.dag(SAVE, "b", 1), c.dag(TRANSFER, "a", "b", 1),  # overflow: 3, 5
             c.dag(DRAW, "nobody", 1), c.dag(TRANSFER, "a", "nobody", 1),
             c.dag(SAVE, "a", 0), c.dag(TRANSFER, "a", "a", 3)]]


def _unknown_selector_mid_run(c):
    return [[c.dag(ADD, "a", 1), c.dag("nonexistent(uint256)", 1), c.dag(ADD, "b", 2),
             c.raw(DAG_TRANSFER_ADDRESS, b"\x01\x02"), c.dag(ADD, "c", 3)]]


def _truncated_input_mid_run(c):
    whole = c.dag(ADD, "dora", 9).input
    return [[c.dag(ADD, "a", 1), c.raw(DAG_TRANSFER_ADDRESS, whole[:40]),
             c.raw(DAG_TRANSFER_ADDRESS, whole[:-30]), c.dag(ADD, "dora", 9),
             c.raw(DAG_TRANSFER_ADDRESS, whole[:4] + b"\xff" * 64), c.dag(ADD, "b", 2)]]


def _fault_drops_its_writes(c):
    # sendPayment debits a, then the credit of b overflows nothing but the
    # draw below raises after a write: the run goes on from the state before
    return [[c(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "a", 10),
             c(SMALLBANK_ADDRESS, "sendPayment(string,string,uint256)", "a", "b", 11),
             c(SMALLBANK_ADDRESS, "writeCheck(string,uint256)", "a", 11),
             c(SMALLBANK_ADDRESS, "sendPayment(string,string,uint256)", "a", "b", 4),
             c(SMALLBANK_ADDRESS, "getBalance(string)", "a"),
             c(SMALLBANK_ADDRESS, "getBalance(string)", "b")]]


def _frozen_sender_mid_run(c):
    return [[c.status(ALICE, 1)],
            [c.dag(ADD, "a", 1, sender=BOB), c.dag(ADD, "b", 2), c.dag(ADD, "c", 3, sender=BOB),
             c.dag(ADD, "d", 4), c.dag(ADD, "e", 5, sender=BOB)]]


def _abolished_sender_mid_run(c):
    return [[c.status(ALICE, 2)],
            [c.dag(ADD, "a", 1, sender=BOB), c.dag(ADD, "b", 2), c.dag(ADD, "c", 3, sender=BOB)]]


def _freeze_takes_effect_next_block(c):
    # the block that freezes ALICE still carries her calls: status 0 there,
    # frozen from the next block on; a second write in the block changes nothing
    return [[c.dag(ADD, "a", 1), c.status(ALICE, 1), c.dag(ADD, "b", 2), c.dag(ADD, "c", 3),
             c.status(ALICE, 0), c.status(ALICE, 1), c.dag(ADD, "d", 4), c.dag(ADD, "e", 5)],
            [c.dag(ADD, "f", 6), c.dag(ADD, "g", 7, sender=BOB), c.dag(ADD, "h", 8)]]


def _governor_run(c):
    # a run of AccountManager calls: an unauthorised caller's soft code, a
    # governor's own status (a fault), an unknown status
    return [[c.status(ALICE, 1), c.status(BOB, 2),
             c(ACCOUNT_MGR_ADDRESS, "setAccountStatus(address,uint8)", BOB, 1, sender=BOB),
             c.status(GOVERNOR, 1), c.status(ALICE, 7),
             c(ACCOUNT_MGR_ADDRESS, "getAccountStatus(address)", ALICE)]]


def _broken_by_evm_create_and_precompiled(c):
    from evm_asm import _deployer, counter_runtime

    init = _deployer(counter_runtime(c.codec))
    inc = c.codec.selector("inc()")
    # block 1 deploys; its address is the create address of (1, context 0, seq 0)
    return [[c.raw(b"", init)],
            lambda addr: [
                c.dag(ADD, "a", 1), c.dag(ADD, "b", 2),
                c.raw(addr, inc),  # an EVM call
                c.dag(ADD, "c", 3), c.dag(ADD, "a", 9),
                c.raw(b"", init),  # a create
                c.dag(ADD, "d", 4),  # a run of one
                c(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "a", 10),
                c(SMALLBANK_ADDRESS, "updateSaving(string,uint256)", "a", 5),
                c.raw(b"\x99" * 20, b"\x00" * 4),  # no such callee
                c.dag(ADD, "e", 5), c.dag(ADD, "f", 6),
                c.raw((4).to_bytes(20, "big"), b"identity"),  # an EVM builtin
                c.dag(ADD, "g", 7), c.dag(TRANSFER, "a", "g", 1),
            ]]


def _run_of_one(c):
    return [[c.dag(ADD, "solo", 1)], [c.dag(ADD, "solo", 1)]]


def _smallbank_run(c):
    return [[c(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "alice", 1000),
             c(SMALLBANK_ADDRESS, "updateSaving(string,uint256)", "alice", 200),
             c(SMALLBANK_ADDRESS, "sendPayment(string,string,uint256)", "alice", "bob", 400),
             c(SMALLBANK_ADDRESS, "amalgamate(string,string)", "alice", "bob"),
             c(SMALLBANK_ADDRESS, "writeCheck(string,uint256)", "bob", 1),
             c(SMALLBANK_ADDRESS, "getBalance(string)", "bob")]]


def _cpu_heavy_run(c):
    return [[c(CPU_HEAVY_ADDRESS, "sort(uint256,uint256)", 50, 7),
             c(CPU_HEAVY_ADDRESS, "sort(uint256,uint256)", 1_000_001, 7),  # too large: a fault
             c(CPU_HEAVY_ADDRESS, "sort(uint256,uint256)", 0, 0),
             c(CPU_HEAVY_ADDRESS, "sort(uint256,uint256)", 300, 1)]]


RUN_CASES = [
    _full_user_add, _name_added_twice, _empty_user, _save_draw_transfer_chain,
    _insufficient_and_overflow, _unknown_selector_mid_run, _truncated_input_mid_run,
    _fault_drops_its_writes, _frozen_sender_mid_run, _abolished_sender_mid_run,
    _freeze_takes_effect_next_block, _governor_run, _broken_by_evm_create_and_precompiled,
    _run_of_one, _smallbank_run, _cpu_heavy_run,
]




def _execute_blocks(suite, case, framed: bool):
    """-> per block (receipts, state root, receipts root), through the run
    frame or transaction by transaction."""
    backend = MemoryStorage()
    backend.set_row("s_config", b"auth_governors",
                    Entry().set(("0x" + GOVERNOR.hex()).encode()))
    ex = TransactionExecutor(backend, suite)
    out, created = [], b""
    for number, txs in enumerate(case(_Calls(suite)), start=1):
        if callable(txs):
            txs = txs(created)
        ex.next_block_header(BlockHeader(number=number, timestamp=1_700_000_000 + number))
        if framed:
            receipts = ex.execute_transactions(txs)
        else:
            base = ex.reserve_contexts(len(txs))
            receipts = [ex._execute_one(tx, ex._block, context_id=base + i)
                        for i, tx in enumerate(txs)]
        created = created or receipts[0].contract_address
        state_root = ex.get_hash()
        out.append((receipts, state_root, Block(receipts=receipts).calculate_receipts_root(suite)))
        ex._block.storage.merge_into_prev()  # what the scheduler's 2PC does live
    return out


@pytest.mark.parametrize("suite_name", sorted(SUITES))
@pytest.mark.parametrize("case", RUN_CASES, ids=lambda f: f.__name__.lstrip("_"))
def test_run_frame_matches_per_transaction_path(case, suite_name):
    suite = SUITES[suite_name]
    framed = _execute_blocks(suite, case, framed=True)
    plain = _execute_blocks(suite, case, framed=False)
    assert len(framed) == len(plain)
    for (got, got_state, got_root), (want, want_state, want_root) in zip(framed, plain):
        assert [rc.status for rc in got] == [rc.status for rc in want]
        assert [rc.output for rc in got] == [rc.output for rc in want]
        assert [rc.gas_used for rc in got] == [rc.gas_used for rc in want]
        assert [rc.encode() for rc in got] == [flat_receipt(rc) for rc in want]
        assert [rc.hash(suite) for rc in got] == [suite.hash(flat_receipt(rc)) for rc in want]
        assert [rc.encode() for rc in want] == [flat_receipt(rc) for rc in want]
        assert got_state == want_state and got_root == want_root
        leaves = np.frombuffer(b"".join(suite.hash(flat_receipt(rc)) for rc in want),
                               dtype=np.uint8).reshape(-1, 32)
        assert got_root == suite.merkle_root_async(leaves)()


# sha256 over every receipt's encoding, then the state root and the receipts
# root, block by block, of ``execute_transactions`` at ed1e450 (PR 32), where
# the run frame's body stood inline in ``_execute_run``: the serial cells'
# path stays byte for byte where it was now that the DAG runner shares it
PINNED_AT_PR32 = {
    ("_broken_by_evm_create_and_precompiled", "keccak256"):
        "f0bc2a38f08a3e306b100651210c366bec93dc61d691fa8124a7af49d5fc5d07",
    ("_broken_by_evm_create_and_precompiled", "sm3"):
        "f03217187fcb9170a1736b659e4b13702a4da2595df24f0b263c2f88ae6dab85",
    ("_fault_drops_its_writes", "keccak256"):
        "a6f616e14cb22bb20440de332c63f78deb2e402297e621464202cac7ff0aa054",
    ("_fault_drops_its_writes", "sm3"):
        "a9958d183238af4f05292d728fd0f4cb50f3c63aafc8e1b2b8ee8017269f69de",
    ("_frozen_sender_mid_run", "keccak256"):
        "347e2ee42212592d67b650d5fdfbbe83c8c4f0084b4f5510befd126b2521ed0e",
    ("_frozen_sender_mid_run", "sm3"):
        "6644e969163f054376747ab8c09455fc39e95e11e89c9635069af086a06c769f",
}


@pytest.mark.parametrize("case_name,suite_name", sorted(PINNED_AT_PR32))
def test_shared_frame_body_keeps_the_serial_path_where_it_was(case_name, suite_name):
    import hashlib

    digest = hashlib.sha256()
    for receipts, state_root, receipts_root in _execute_blocks(
            SUITES[suite_name], globals()[case_name], framed=True):
        for rc in receipts:
            digest.update(rc.encode())
        digest.update(state_root)
        digest.update(receipts_root)
    assert digest.hexdigest() == PINNED_AT_PR32[case_name, suite_name]


def test_run_frame_cases_show_what_they_claim():
    """The cases above compare two paths; this pins the outcomes they are
    named for, so an equal pair of wrong answers cannot pass."""
    def statuses(case, block=-1):
        return [rc.status for rc in _execute_blocks(SUITE, case, framed=True)[block][0]]

    def codes(case, block=-1):
        return [CODEC.decode_output(["uint256"], rc.output)[0]
                if rc.status == 0 and len(rc.output) == 32 else None
                for rc in _execute_blocks(SUITE, case, framed=True)[block][0]]

    fault = int(TransactionStatus.PRECOMPILED_ERROR)
    assert codes(_name_added_twice) == [0, 0, 2, 0]
    assert codes(_empty_user) == [0, 1, 1, 0]
    assert codes(_save_draw_transfer_chain)[:7] == [0] * 7
    last = _execute_blocks(SUITE, _save_draw_transfer_chain, framed=True)[0][0][-1]
    assert CODEC.decode_output(["uint256", "uint256"], last.output) == [0, 70]
    assert codes(_insufficient_and_overflow) == [0, 0, 4, 4, 3, 5, 2, 3, 1, 0]
    assert statuses(_unknown_selector_mid_run) == [0, fault, 0, fault, 0]
    assert statuses(_truncated_input_mid_run) == [0, fault, fault, 0, fault, 0]
    assert statuses(_fault_drops_its_writes) == [0, fault, fault, 0, 0, 0]
    rcs = _execute_blocks(SUITE, _fault_drops_its_writes, framed=True)[0][0]
    assert [CODEC.decode_output(["uint256"], rc.output)[0] for rc in rcs[-2:]] == [6, 4]
    frozen = int(TransactionStatus.ACCOUNT_FROZEN)
    assert statuses(_frozen_sender_mid_run) == [0, frozen, 0, frozen, 0]
    assert statuses(_abolished_sender_mid_run) == [
        0, int(TransactionStatus.ACCOUNT_ABOLISHED), 0]
    assert statuses(_freeze_takes_effect_next_block, block=0) == [0] * 8
    assert statuses(_freeze_takes_effect_next_block, block=1) == [frozen, 0, frozen]
    assert statuses(_governor_run) == [0, 0, 0, fault, fault, 0]
    mixed = statuses(_broken_by_evm_create_and_precompiled)
    assert mixed[9] == int(TransactionStatus.CALL_ADDRESS_ERROR)
    assert [s for i, s in enumerate(mixed) if i != 9] == [0] * 14
    assert codes(_broken_by_evm_create_and_precompiled)[4] == 2  # "a" was added in the run before
    assert codes(_run_of_one, block=1) == [2]
    assert statuses(_cpu_heavy_run) == [0, fault, 0, 0]


def test_run_frame_counts_its_transactions():
    """Runs of two or more take the frame and say so: the run counter, the
    mode "run" of the batch histograms and one executor.run span a run."""
    from fisco_bcos_tpu.observability import TRACER

    def counted():
        return sum(REGISTRY.counters_matching("fisco_executor_run_txs_total").values())

    c = _Calls(SUITE)
    ex = TransactionExecutor(MemoryStorage(), SUITE)
    ex.next_block_header(BlockHeader(number=1))
    before = counted()
    TRACER.clear()
    with TRACER.span("test.block"):
        rcs = ex.execute_transactions(
            [c.dag(ADD, f"u{i}", i) for i in range(5)]
            + [c(SMALLBANK_ADDRESS, "updateBalance(string,uint256)", "a", 1)]  # a run of one
            + [c.dag(ADD, f"v{i}", i) for i in range(3)]
        )
    assert [rc.status for rc in rcs] == [0] * 9
    assert counted() - before == 8
    runs = [r for r in TRACER.spans() if r.name == "executor.run"]
    assert [r.attrs["txs"] for r in runs] == [5, 3]
    assert {r.attrs["callee"] for r in runs} == {DAG_TRANSFER_ADDRESS.hex()}
    assert {r.parent for r in runs} == {"executor.execute"}
