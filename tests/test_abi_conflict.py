"""ABI conflict-field DAG for user contracts (ref dag/Abi.h:76,
TransactionExecutor.cpp:1220-1395 extractConflictFields)."""

import json

import pytest

from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import ecdsa_suite
from fisco_bcos_tpu.executor import TransactionExecutor, abi_conflict
from fisco_bcos_tpu.ledger import ConsensusNode, GenesisConfig, Ledger
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.protocol import Block, BlockHeader, ParentInfo
from fisco_bcos_tpu.protocol.transaction import TransactionAttribute, TransactionFactory
from fisco_bcos_tpu.scheduler import Scheduler
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.txpool import TxPool
from fisco_bcos_tpu.utils.metrics import REGISTRY

from evm_asm import _deployer, asm

SUITE = ecdsa_suite()
CODEC = ABICodec(SUITE.hash)

SETFOR_ABI = [
    {
        "type": "function",
        "name": "setFor",
        "inputs": [{"type": "uint256"}, {"type": "uint256"}],
        # parallel by first parameter — disjoint keys never conflict
        "conflictFields": [{"kind": 3, "value": [0], "slot": 0}],
    }
]


def _setfor_runtime() -> bytes:
    sel = int.from_bytes(CODEC.selector("setFor(uint256,uint256)"), "big")
    return asm(
        ("PUSH", 0), "CALLDATALOAD", ("PUSH", 224), "SHR",
        ("PUSH", sel), "EQ", ("ref", "set"), "JUMPI",
        ("PUSH", 0), ("PUSH", 0), "REVERT",
        ("label", "set"),
        ("PUSH", 36), "CALLDATALOAD",  # value
        ("PUSH", 4), "CALLDATALOAD",   # key
        "SSTORE", "STOP",
    )


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
        self.kp = SUITE.signature_impl.generate_keypair(secret=9191)
        self._nonce = 0

    def tx(self, to, data, attribute=0, abi=""):
        self._nonce += 1
        return self.fac.create_signed(
            self.kp, chain_id="chain0", group_id="group0", block_limit=500,
            nonce=f"ac{self._nonce}", to=to, input=data,
            attribute=attribute, abi=abi,
        )

    def run_block(self, txs):
        for t in txs:
            r = self.pool.submit(t)
            assert r.status == 0, r
        sealed, _ = self.pool.seal_txs(len(txs))
        parent = self.ledger.header_by_number(self.ledger.block_number())
        blk = Block(
            header=BlockHeader(
                number=parent.number + 1,
                parent_info=[ParentInfo(parent.number, parent.hash(SUITE))],
                timestamp=1000,
            ),
            transactions=sealed,
        )
        self.scheduler.commit_block(self.scheduler.execute_block(blk))
        return blk

    def deploy_setfor(self) -> bytes:
        rc = self.run_block(
            [self.tx(b"", _deployer(_setfor_runtime()), abi=json.dumps(SETFOR_ABI))]
        ).receipts[0]
        assert rc.status == 0, rc.output
        return rc.contract_address


# -- unit: kind semantics ----------------------------------------------------


def _fn(conflicts):
    return abi_conflict._Fn("setFor", ["uint256", "uint256"], conflicts)


def _call(k, v):
    return CODEC.encode_call("setFor(uint256,uint256)", k, v)


def test_kind_all_serializes():
    fn = _fn([{"kind": 0, "value": [], "slot": 0}])
    assert abi_conflict.extract_criticals(fn, _call(1, 2), b"s", b"c", 0, 0) is None


def test_kind_len_is_function_level():
    fn = _fn([{"kind": 1, "value": [], "slot": 3}])
    a = abi_conflict.extract_criticals(fn, _call(1, 2), b"s", b"c", 0, 0)
    b = abi_conflict.extract_criticals(fn, _call(9, 9), b"x", b"c", 0, 0)
    assert a == b == [(3).to_bytes(4, "big")]


def test_kind_env_caller_and_params():
    fn = _fn([{"kind": 2, "value": [0], "slot": 0},
              {"kind": 3, "value": [0], "slot": 1}])
    a = abi_conflict.extract_criticals(fn, _call(7, 1), b"alice", b"c", 0, 0)
    b = abi_conflict.extract_criticals(fn, _call(7, 2), b"bob", b"c", 0, 0)
    assert a[0] != b[0]      # different caller
    assert a[1] == b[1]      # same first param -> same key
    c = abi_conflict.extract_criticals(fn, _call(8, 1), b"alice", b"c", 0, 0)
    assert a[0] == c[0] and a[1] != c[1]


def test_kind_const_and_unannotated():
    fn = _fn([{"kind": 4, "value": [1, 2, 3], "slot": 0}])
    assert abi_conflict.extract_criticals(fn, _call(1, 1), b"s", b"c", 0, 0) == [
        (0).to_bytes(4, "big") + b"\x01\x02\x03"
    ]
    assert abi_conflict.extract_criticals(_fn([]), _call(1, 1), b"s", b"c", 0, 0) is None


def test_lookup_by_selector():
    text = json.dumps(SETFOR_ABI)
    fn = abi_conflict.lookup(text, "keccak256", CODEC.selector("setFor(uint256,uint256)"))
    assert fn is not None and fn.name == "setFor"
    assert abi_conflict.lookup(text, "keccak256", b"\x00\x00\x00\x00") is None


# -- integration: user-contract txs levelize through the stored ABI ----------


def test_user_contract_dag_parallel_levels():
    env = Env()
    addr = env.deploy_setfor()
    dag = TransactionAttribute.DAG
    txs = [env.tx(addr, _call(i, 100 + i), attribute=dag) for i in range(4)]
    for t in txs:
        t.force_sender(b"\x22" * 20)
    env.executor.next_block_header(BlockHeader(number=2, timestamp=1000))
    levels = env.executor.dag_levels(txs)
    assert len(levels) == 1 and levels[0] == [0, 1, 2, 3]  # fewer rounds than txs

    # same first param -> conflict -> must order
    clash = [env.tx(addr, _call(5, 1), attribute=dag),
             env.tx(addr, _call(5, 2), attribute=dag)]
    for t in clash:
        t.force_sender(b"\x22" * 20)
    assert len(env.executor.dag_levels(clash)) == 2


def test_user_contract_dag_receipts_match_serial():
    def run(parallel: bool):
        env = Env()
        addr = env.deploy_setfor()
        attr = TransactionAttribute.DAG if parallel else 0
        blk = env.run_block(
            [env.tx(addr, _call(i % 3, 50 + i), attribute=attr) for i in range(6)]
        )
        assert all(rc.status == 0 for rc in blk.receipts)
        header = env.ledger.header_by_number(2)
        return [rc.encode() for rc in blk.receipts], header.state_root

    par_rcs, par_root = run(True)
    ser_rcs, ser_root = run(False)
    assert par_rcs == ser_rcs
    assert par_root == ser_root


def test_liquid_path_key_accepted():
    """liquid-generated ABIs spell the component selector "path" (the
    reference's transfer.wasm fixture ABI); solidity ABIs spell it "value"
    — both must produce the same criticals."""
    a = _fn([{"kind": 3, "value": [0], "slot": 0}])
    b = _fn([{"kind": 3, "path": [0], "slot": 0}])
    ka = abi_conflict.extract_criticals(a, _call(7, 1), b"s", b"c", 0, 0)
    kb = abi_conflict.extract_criticals(b, _call(7, 1), b"s", b"c", 0, 0)
    assert ka == kb and ka is not None


def test_dag_levels_match_serial(monkeypatch):
    """The level runner (tracked overlays, the check after the wide level)
    must be bit-identical to the pinned serial loop (pre-reserved context
    ids + per-tx overlays + disjoint criticals)."""
    def run(levels: bool):
        if levels:
            monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
        else:
            monkeypatch.setenv("FISCO_DAG_SERIAL", "1")
        env = Env()
        addr = env.deploy_setfor()
        blk = env.run_block([
            env.tx(addr, _call(i, 900 + i), attribute=TransactionAttribute.DAG)
            for i in range(8)
        ])
        assert all(rc.status == 0 for rc in blk.receipts)
        return ([rc.encode() for rc in blk.receipts],
                env.ledger.header_by_number(2).state_root)

    assert run(True) == run(False)


def _lying_setfixed():
    """setFixed(uint256,uint256) IGNORES param 0 and always writes slot 7,
    but its ABI (dishonestly) declares parallelism by param 0."""
    sel = int.from_bytes(CODEC.selector("setFixed(uint256,uint256)"), "big")
    runtime = asm(
        ("PUSH", 0), "CALLDATALOAD", ("PUSH", 224), "SHR",
        ("PUSH", sel), "EQ", ("ref", "go"), "JUMPI",
        ("PUSH", 0), ("PUSH", 0), "REVERT",
        ("label", "go"),
        ("PUSH", 7), "SLOAD", ("PUSH", 36), "CALLDATALOAD", "ADD",
        ("PUSH", 7), "SSTORE", "STOP",
    )
    lying_abi = [{
        "type": "function", "name": "setFixed",
        "inputs": [{"type": "uint256"}, {"type": "uint256"}],
        "conflictFields": [{"kind": 3, "value": [0], "slot": 0}],
    }]
    return runtime, lying_abi


def test_lying_declaration_detected_and_serialized(monkeypatch, caplog):
    """Two txs whose conflictFields claim disjoint state but whose code
    writes the SAME storage slot: the level runner must detect the overlap
    at runtime and re-execute serially, producing the serial result — a
    lying annotation must never let anything but the block decide the state
    root (review finding r5)."""
    monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
    runtime, lying_abi = _lying_setfixed()

    def run(levels: bool):
        if levels:
            monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
        else:
            monkeypatch.setenv("FISCO_DAG_SERIAL", "1")
        env = Env()
        rc = env.run_block(
            [env.tx(b"", _deployer(runtime), abi=json.dumps(lying_abi))]
        ).receipts[0]
        assert rc.status == 0
        addr = rc.contract_address
        blk = env.run_block([
            env.tx(addr, CODEC.encode_call("setFixed(uint256,uint256)", i, 10 + i),
                   attribute=TransactionAttribute.DAG)
            for i in range(4)
        ])
        assert all(r.status == 0 for r in blk.receipts)
        return ([r.encode() for r in blk.receipts],
                env.ledger.header_by_number(2).state_root)

    # levelization puts all 4 in one level (disjoint declared keys)...
    checked = run(True)
    serial = run(False)
    # ...but the runtime validation must force the serial outcome anyway
    assert checked == serial


def _dag_record_of(run_block):
    """`run_block()` with the tracer cleared before it -> (its result, the
    attributes of the one DAG ``executor.execute`` record it left, what it
    added to ``fisco_executor_contract_framed_txs_total``)."""
    name = "fisco_executor_contract_framed_txs_total"
    before = sum(REGISTRY.counters_matching(name).values())
    TRACER.clear()
    out = run_block()
    (record,) = [s for s in TRACER.spans()
                 if s.name == "executor.execute" and s.attrs.get("mode") == "dag"]
    return out, record.attrs, sum(REGISTRY.counters_matching(name).values()) - before


def _member_by_member(env_and_txs):
    """The same block on a second chain, every transaction through
    ``_execute_one`` in block order: what the contract frame is held to."""
    env, txs = env_and_txs()
    number = env.ledger.block_number() + 1
    env.executor.next_block_header(BlockHeader(number=number, timestamp=1000))
    for t in txs:
        t.force_sender(SUITE.calculate_address(env.kp.pub))
    base = env.executor.reserve_contexts(len(txs))
    receipts = [env.executor._execute_one(t, env.executor._block, context_id=base + i)
                for i, t in enumerate(txs)]
    return [rc.encode() for rc in receipts], env.executor.get_hash()


def _honest_block():
    env = Env()
    addr = env.deploy_setfor()
    return env, [env.tx(addr, _call(i % 5, 700 + i), attribute=TransactionAttribute.DAG)
                 for i in range(8)]


def _lying_block():
    runtime, lying_abi = _lying_setfixed()
    env = Env()
    rc = env.run_block([env.tx(b"", _deployer(runtime), abi=json.dumps(lying_abi))]).receipts[0]
    return env, [env.tx(rc.contract_address,
                        CODEC.encode_call("setFixed(uint256,uint256)", i, 10 + i),
                        attribute=TransactionAttribute.DAG) for i in range(4)]


@pytest.mark.parametrize("make, reruns", [(_honest_block, 0), (_lying_block, 1)],
                         ids=["honest", "lying"])
def test_a_served_dag_blocks_contract_calls_run_in_the_contract_frame(
        make, reruns, monkeypatch):
    """Every contract member of a served DAG block executes in the call's
    contract frame, the members of a discarded attempt and of its rerun
    alike, and the block is the one ``_execute_one`` gives member by member."""
    monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
    env, txs = make()
    blk, at, framed = _dag_record_of(lambda: env.run_block(txs))
    assert all(rc.status == 0 for rc in blk.receipts)
    assert at["reruns"] == reruns
    assert at["contract_txs"] == at["contract_framed"] == framed == len(txs) * (1 + reruns)
    assert at["evm_native"] == at["contract_txs"]
    want_receipts, want_root = _member_by_member(make)
    assert [rc.encode() for rc in blk.receipts] == want_receipts
    assert env.ledger.header_by_number(env.ledger.block_number()).state_root == want_root


def test_reordering_levels_keep_receipt_identity(monkeypatch):
    """Levelization that REORDERS txs (conflicting tx sinks to level 1 while
    a later tx stays in level 0) must still put every receipt at its tx
    index — on the pinned serial path, the level runner's path, and the
    conflict-fallback path (review r5: a flattened serial loop swapped
    receipts and forked the receipts root between nodes)."""
    def run(mode: str):
        if mode == "serial":
            monkeypatch.setenv("FISCO_DAG_SERIAL", "1")
        else:
            monkeypatch.delenv("FISCO_DAG_SERIAL", raising=False)
        env = Env()
        addr = env.deploy_setfor()
        dag = TransactionAttribute.DAG
        # levels: [tx0(k0), tx2(k1)], [tx1(k0)]
        blk = env.run_block([
            env.tx(addr, _call(0, 100), attribute=dag),
            env.tx(addr, _call(0, 200), attribute=dag),
            env.tx(addr, _call(1, 300), attribute=dag),
        ])
        assert all(rc.status == 0 for rc in blk.receipts)
        return blk.receipts, env.ledger.header_by_number(2).state_root

    for mode in ("serial", "levels"):
        receipts, root = run(mode)
        # tx1 re-writes slot 0 (SSTORE reset, 5k); tx0/tx2 first-write their
        # slots (SSTORE set, 20k) — a receipt swap inverts this relation
        assert receipts[1].gas_used < receipts[0].gas_used, mode
        assert receipts[1].gas_used < receipts[2].gas_used, mode
        assert receipts[0].gas_used == receipts[2].gas_used, mode
    assert run("serial") == run("levels")


def test_malformed_conflictfields_serialize_not_crash():
    """Attacker-deployed ABIs with malformed conflictFields (slot='abc',
    slot=2**40, value=5, non-int path entries) must degrade to 'serialize',
    never raise through execute_block (review r5: deterministic chain halt)."""
    import json as _json

    bad_abis = [
        [{"type": "function", "name": "setFor",
          "inputs": [{"type": "uint256"}, {"type": "uint256"}],
          "conflictFields": [{"kind": 3, "value": [0], "slot": "abc"}]}],
        [{"type": "function", "name": "setFor",
          "inputs": [{"type": "uint256"}, {"type": "uint256"}],
          "conflictFields": [{"kind": 3, "value": [0], "slot": 2**40}]}],
        [{"type": "function", "name": "setFor",
          "inputs": [{"type": "uint256"}, {"type": "uint256"}],
          "conflictFields": [{"kind": 2, "value": 5, "slot": 0}]}],
        [{"type": "function", "name": "setFor",
          "inputs": [{"type": "uint256"}, {"type": "uint256"}],
          "conflictFields": [{"kind": 3, "value": ["x"], "slot": 0}]}],
        [{"type": "function", "name": "setFor",
          "inputs": [{"type": "uint256"}, {"type": "uint256"}],
          "conflictFields": [{"kind": 4, "value": [None], "slot": 0}]}],
    ]
    for bad in bad_abis:
        env = Env()
        rc = env.run_block(
            [env.tx(b"", _deployer(_setfor_runtime()), abi=_json.dumps(bad))]
        ).receipts[0]
        assert rc.status == 0
        blk = env.run_block([
            env.tx(rc.contract_address, _call(i, i),
                   attribute=TransactionAttribute.DAG)
            for i in range(2)
        ])
        assert all(r.status == 0 for r in blk.receipts), bad
        # and the levels serialized (None criticals -> one tx per level)
        env.executor.next_block_header(__import__("fisco_bcos_tpu.protocol.block_header", fromlist=["BlockHeader"]).BlockHeader(number=3, timestamp=1))
        t = [env.tx(rc.contract_address, _call(9, 9), attribute=TransactionAttribute.DAG),
             env.tx(rc.contract_address, _call(8, 8), attribute=TransactionAttribute.DAG)]
        for x in t:
            x.force_sender(b"\x33" * 20)
        assert len(env.executor.dag_levels(t)) == 2
