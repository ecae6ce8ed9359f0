"""A block's contract frame (``executor._ContractFrame``, PR 42) against
``_execute_one``, member by member on two executors opened alike: the
receipts (status, output, gas used, logs, contract address), the tracked
``(reads, writes)`` of every member, the engine each call's tally names, and
after the last member every dirty row of the block and its state root. Over
seeded ``ParallelOk`` calls of the deployed-contract cell's own generator, and
over the cases where the frame must not trust what it kept (the contract
frozen, a method ACL set, a contract deployed, by an earlier transaction of
the same batch) or must stand aside (a frozen or abolished sender is refused
in it; an escape, an SM chain, no native engine go to ``_execute_one``)."""

import pytest

import test_contract_dag_block as con
from evm_asm import _deployer, caller_runtime, counter_runtime, logger_runtime
from fisco_bcos_tpu.codec.abi import ABICodec
from fisco_bcos_tpu.crypto.suite import sm_suite
from fisco_bcos_tpu.executor import TransactionExecutor
from fisco_bcos_tpu.executor import executor as executor_module
from fisco_bcos_tpu.executor.evm import contract_table
from fisco_bcos_tpu.executor.precompiled import ACCOUNT_MGR_ADDRESS, AUTH_MANAGER_ADDRESS
from fisco_bcos_tpu.protocol import BlockHeader
from fisco_bcos_tpu.protocol.receipt import TransactionStatus
from fisco_bcos_tpu.protocol.transaction import Transaction
from fisco_bcos_tpu.storage import MemoryStorage
from fisco_bcos_tpu.storage.entry import Entry
from fisco_bcos_tpu.utils.metrics import REGISTRY

SUITE = con.SUITE
CODEC = ABICodec(SUITE.hash)
TRANSFER = "transfer(string,string,uint256)"
SET = "set(string,uint256)"
TOP = 2**256 - 1
ALICE, BOB, CAROL = b"\x0a" * 20, b"\x0b" * 20, b"\x0c" * 20
GOVERNOR = b"\x99" * 20
FRAMED = "fisco_executor_contract_framed_txs_total"


def call(to, signature, *args, sender=BOB, codec=CODEC):
    return Transaction(to=to, input=codec.encode_call(signature, *args), sender=sender)


def hexed(addr):
    return "0x" + addr.hex()


def receipt(rc):
    return (rc.status, rc.output, rc.gas_used, rc.block_number, rc.contract_address,
            [(log.address, log.topics, log.data) for log in rc.log_entries])


def dirty(ex):
    return {(t, k): e.encode() for t, k, e in ex._block.storage.traverse()}


def both(make, txs, tracked=True):
    """`txs` member by member on two executors `make` opens alike: through
    ``_execute_one`` on the judge, through one contract frame on the other
    (a registry callee through ``_execute_one`` there too, as a batch's loops
    send it). Every member's receipt and access sets are compared as it
    executes -> (the frame, the receipts, both tallies)."""
    judge, ex = make(), make()
    base = judge.reserve_contexts(len(txs))
    assert ex.reserve_contexts(len(txs)) == base
    tally_j: list = []
    tally_f: list = []
    frame = executor_module._ContractFrame(ex, ex._block, tally_f)
    receipts = []
    for i, tx in enumerate(txs):
        sets_j, sets_f = ([], []) if tracked else (None, None)
        builtin = tx.to in ex.registry
        want = judge._execute_one(tx, judge._block, context_id=base + i, access_out=sets_j,
                                  tally=None if builtin else tally_j)
        if builtin:
            got = ex._execute_one(tx, ex._block, context_id=base + i, access_out=sets_f)
        else:
            got = frame.execute(tx, base + i, sets_f)
        assert receipt(got) == receipt(want), f"member {i}"
        assert sets_f == sets_j, f"member {i}"
        receipts.append(got)
    assert dirty(ex) == dirty(judge)
    assert ex.get_hash() == judge.get_hash()
    assert [t[2] for t in tally_f] == [t[2] for t in tally_j], "the engine of every call"
    assert all(t[0] >= t[1] >= 0 for t in tally_f)
    assert not frame._on or not frame.overlay._data, "the overlay is empty between members"
    return frame, receipts, tally_f, tally_j


# -- seeded ParallelOk calls --------------------------------------------------------


def parallelok_calls(c):
    n, to = c.names, c._to
    edge = [
        call(to, TRANSFER, "nobody", n[0], 7),            # 0 - 7: wraps below zero
        call(to, SET, n[1], TOP),
        call(to, TRANSFER, n[2], n[1], 9),                # TOP + 9: wraps above the top
        call(to, TRANSFER, n[3], n[3], 5),                # from == to
        call(to, TRANSFER, n[4], "never written", 1),     # an unknown name reads 0
        call(to, "balanceOf(string)", n[1]),
        call(to, "mint(string,uint256)", n[0], 1),        # an unknown selector
        Transaction(to=to, input=b"\x01\x02", sender=BOB),  # short calldata
        Transaction(to=to, input=b"", sender=BOB),
        call(to, TRANSFER, n[0], n[5], 3),
    ]
    return con.block_of(c) + edge


@pytest.mark.parametrize("tracked", [True, False], ids=["tracked", "untracked"])
def test_seeded_parallelok_calls_are_execute_ones_member_by_member(tracked):
    c = con.corpus()
    txs = parallelok_calls(c)
    frame, receipts, tally, _ = both(lambda: con.opened(c), txs, tracked)
    assert frame.framed == len(txs) and all(t[2] == "native" for t in tally)
    revert = int(TransactionStatus.REVERT_INSTRUCTION)
    assert [rc.status for rc in receipts[con.BLOCK_TXS:]] == [
        0, 0, 0, 0, 0, 0, revert, revert, revert, 0]
    table = contract_table(c._to)
    balance = {k: int.from_bytes(e.get(), "big")
               for t, k, e in frame.block.storage.traverse() if t == table and len(k) == 32}
    slot = con.refcontract.slot_of
    assert balance[slot("nobody")] == 2**256 - 7
    assert balance[slot(c.names[1])] == 8  # TOP + 9 modulo 2^256
    assert int.from_bytes(receipts[con.BLOCK_TXS + 5].output, "big") == 8


def test_a_level_wider_than_one_hands_the_check_what_execute_one_hands_it():
    """The tracked sets name the gates' rows and the slots read through, so
    two members on one payee conflict and two on disjoint names do not."""
    c = con.corpus()
    n, to = c.names, c._to
    txs = [call(to, TRANSFER, n[0], n[1], 1), call(to, TRANSFER, n[2], n[3], 1),
           call(to, TRANSFER, n[4], n[1], 1)]
    ex = con.opened(c)
    frame = executor_module._ContractFrame(ex, ex._block, [])
    sets: list = []
    for i, tx in enumerate(txs):
        frame.execute(tx, i, sets)
    table = contract_table(to)
    slot = con.refcontract.slot_of
    reads, writes = sets[0]
    assert writes == {(table, slot(n[0])), (table, slot(n[1]))} <= reads
    assert {(table, b"#account"), ("s_account", BOB),
            ("s_contract_auth", to + b":#meta"),
            ("s_contract_auth", to + b":" + txs[0].input[:4])} <= reads
    assert not executor_module._level_conflicts(sets[:2])
    assert executor_module._level_conflicts(sets)


# -- what the frame kept is what _execute_one would read at that point --------------


def frozen_mid_block(c):
    to, who = c._to, con.DEPLOYER
    return [
        call(to, TRANSFER, c.names[0], c.names[1], 1),
        call(AUTH_MANAGER_ADDRESS, "setContractStatus(string,bool)", hexed(to), True, sender=who),
        call(to, TRANSFER, c.names[0], c.names[1], 1),
        call(to, "balanceOf(string)", c.names[1]),
        call(AUTH_MANAGER_ADDRESS, "setContractStatus(string,bool)", hexed(to), False, sender=who),
        call(to, TRANSFER, c.names[0], c.names[1], 1),
    ], [0, 0, 21, 21, 0, 0]


def acl_mid_block(c):
    to, who = c._to, con.DEPLOYER
    sel = CODEC.selector(TRANSFER)
    return [
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=ALICE),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=CAROL),
        call(AUTH_MANAGER_ADDRESS, "setMethodAuthType(string,bytes4,uint8)", hexed(to), sel, 1,
             sender=who),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=ALICE),  # a white list, nobody on it
        call(AUTH_MANAGER_ADDRESS, "openMethodAuth(string,bytes4,string)", hexed(to), sel,
             hexed(ALICE), sender=who),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=ALICE),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=CAROL),
        call(to, SET, c.names[2], 5, sender=CAROL),  # another method: no list
        call(AUTH_MANAGER_ADDRESS, "setMethodAuthType(string,bytes4,uint8)", hexed(to), sel, 2,
             sender=who),
        call(AUTH_MANAGER_ADDRESS, "openMethodAuth(string,bytes4,string)", hexed(to), sel,
             hexed(CAROL), sender=who),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=ALICE),
        call(to, TRANSFER, c.names[0], c.names[1], 1, sender=CAROL),  # on the black list
    ], [0, 0, 0, 18, 0, 0, 18, 0, 0, 0, 0, 18]


@pytest.mark.parametrize("case", [frozen_mid_block, acl_mid_block])
def test_governance_written_by_an_earlier_transaction_of_the_block_is_seen(case):
    c = con.corpus()
    txs, statuses = case(c)
    frame, receipts, _, _ = both(lambda: con.opened(c), txs)
    assert [rc.status for rc in receipts] == statuses
    assert frame.framed == sum(1 for tx in txs if tx.to == c._to), "refused in the frame"


@pytest.mark.parametrize("status, refused", [(1, TransactionStatus.ACCOUNT_FROZEN),
                                             (2, TransactionStatus.ACCOUNT_ABOLISHED)])
def test_a_frozen_and_an_abolished_sender_are_refused_as_execute_one_refuses_them(
        status, refused):
    c = con.corpus()

    def in_block_two():
        backend = MemoryStorage()
        backend.set_row("s_config", b"auth_governors", Entry().set(hexed(GOVERNOR).encode()))
        ex = TransactionExecutor(backend, SUITE)
        ex.next_block_header(BlockHeader(number=1))
        receipts = ex.execute_transactions(
            [c.deploy] + [tx for batch in c.opening for tx in batch]
            + [call(ACCOUNT_MGR_ADDRESS, "setAccountStatus(address,uint8)", ALICE, status,
                    sender=GOVERNOR)])
        assert all(rc.status == 0 for rc in receipts)
        ex._block.storage.merge_into_prev()  # what the scheduler's 2PC does live
        ex.next_block_header(BlockHeader(number=2))
        return ex

    n, to = c.names, c._to
    txs = [call(to, TRANSFER, n[0], n[1], 2), call(to, TRANSFER, n[0], n[1], 2, sender=ALICE),
           call(to, TRANSFER, n[1], n[0], 1)]
    frame, receipts, tally, _ = both(in_block_two, txs)
    assert [rc.status for rc in receipts] == [0, int(refused), 0]
    assert frame.framed == 3 and [t[2] for t in tally] == ["native", "", "native"]


def test_a_contract_deployed_by_an_earlier_transaction_of_the_batch_is_called_in_the_frame():
    def make():
        ex = TransactionExecutor(MemoryStorage(), SUITE)
        ex.next_block_header(BlockHeader(number=1))
        return ex

    base = make().reserve_contexts(0)
    # the create is member 1: its address by the chain's rule, context base + 1, seq 0
    addr = SUITE.hash(f"1_{base + 1}_0".encode())[:20]
    inc = CODEC.selector("inc()")
    txs = [
        Transaction(to=addr, input=inc, sender=BOB),  # nothing there yet
        Transaction(to=b"", input=_deployer(counter_runtime(CODEC)), sender=ALICE),
        Transaction(to=addr, input=inc, sender=BOB),
        Transaction(to=addr, input=CODEC.selector("get()"), sender=BOB),
    ]
    frame, receipts, tally, _ = both(make, txs)
    assert receipts[1].contract_address == addr
    assert [rc.status for rc in receipts] == [
        int(TransactionStatus.CALL_ADDRESS_ERROR), 0, 0, 0]
    assert int.from_bytes(receipts[3].output, "big") == 1
    assert frame.framed == 2, "the unknown address and the create went through _execute_one"
    assert [t[2] for t in tally] == ["", "native", "native", "native"]


def test_logs_are_the_receipts_logs():
    def make():
        ex = TransactionExecutor(MemoryStorage(), SUITE)
        ex.next_block_header(BlockHeader(number=1))
        (rc,) = ex.execute_transactions(
            [Transaction(to=b"", input=_deployer(logger_runtime()), sender=ALICE)])
        make.addr = rc.contract_address
        return ex

    make()
    txs = [Transaction(to=make.addr, input=(0xBEEF + k).to_bytes(32, "big"), sender=BOB)
           for k in range(3)]
    frame, receipts, _, _ = both(make, txs)
    assert frame.framed == 3
    assert [len(rc.log_entries) for rc in receipts] == [1, 1, 1]
    assert receipts[2].log_entries[0].address == make.addr


# -- where the frame stands aside ----------------------------------------------------


def two_contracts(suite=SUITE):
    codec = ABICodec(suite.hash)

    def make():
        ex = TransactionExecutor(MemoryStorage(), suite)
        ex.next_block_header(BlockHeader(number=1))
        receipts = ex.execute_transactions([
            Transaction(to=b"", input=_deployer(counter_runtime(codec)), sender=ALICE),
            Transaction(to=b"", input=_deployer(caller_runtime(codec)), sender=ALICE)])
        make.counter, make.caller = (rc.contract_address for rc in receipts)
        assert make.counter and make.caller
        return ex

    make()
    return make, codec


def test_a_call_that_escapes_is_executed_again_by_execute_one_and_its_callee_goes_there():
    make, codec = two_contracts()
    via = Transaction(to=make.caller, input=make.counter.rjust(32, b"\x00"), sender=BOB)
    inc = Transaction(to=make.counter, input=codec.selector("inc()"), sender=BOB)
    get = Transaction(to=make.counter, input=codec.selector("get()"), sender=BOB)
    frame, receipts, tally, _ = both(make, [via, inc, via, via, inc, get])
    assert all(rc.status == 0 for rc in receipts)
    assert int.from_bytes(receipts[-1].output, "big") == 5
    # the caller's frame ends in the Python loop, as _execute_one reports it;
    # the counter called directly stays in the frame
    assert [t[2] for t in tally] == ["interpreter", "native", "interpreter", "interpreter",
                                     "native", "native"]
    assert frame.framed == 3 and frame._callees[make.caller].direct
    assert not frame._callees[make.counter].direct


def test_an_sm_chain_frames_nothing():
    make, codec = two_contracts(sm_suite())
    txs = [Transaction(to=make.counter, input=codec.selector("inc()"), sender=BOB),
           Transaction(to=make.counter, input=codec.selector("get()"), sender=BOB)]
    frame, receipts, tally, _ = both(make, txs)
    assert [rc.status for rc in receipts] == [0, 0]
    assert frame.framed == 0 and frame._on is False
    assert [t[2] for t in tally] == ["interpreter", "interpreter"]


def framed_and_engines():
    got = {"framed": sum(REGISTRY.counters_matching(FRAMED).values())}
    for engine in ("native", "interpreter"):
        got[engine] = sum(REGISTRY.counters_matching(
            f'fisco_executor_evm_calls_total{{engine="{engine}"}}').values())
    return got


def test_without_the_native_engine_nothing_is_framed_and_the_interpreter_is_counted(
        monkeypatch):
    c = con.corpus()
    txs = con.block_of(c)[:32]
    want = con.plain(con.opened(c).execute_transactions(txs))
    monkeypatch.setenv("FISCO_NO_NATIVE_EVM", "1")
    frame, _, tally, _ = both(lambda: con.opened(c), txs)
    assert frame.framed == 0 and all(t[2] == "interpreter" for t in tally)
    ex = con.opened(c)
    before = framed_and_engines()
    assert con.plain(ex.execute_transactions(txs)) == want, "the engines agree"
    after = framed_and_engines()
    assert {k: after[k] - before[k] for k in after} == {
        "framed": 0, "native": 0, "interpreter": len(txs)}
    assert REGISTRY.counters_matching(FRAMED), "registered, at what it counted"


def test_a_callback_that_raises_surfaces_after_the_run_and_leaves_the_overlay_empty():
    """A slot row wider than a word (no SSTORE writes one): the engine's read
    of it raises inside the callback, ctypes swallows that, and the run's
    caller raises it, in the frame as through ``_execute_one``."""
    make, codec = two_contracts()
    inc = Transaction(to=make.counter, input=codec.selector("inc()"), sender=BOB)

    def broken():
        ex = make()
        ex._block.storage.set_row(contract_table(make.counter), (0).to_bytes(32, "big"),
                                  Entry().set(b"\x01" * 33))
        return ex

    judge, ex = broken(), broken()
    with pytest.raises(OverflowError) as want:
        judge._execute_one(inc, judge._block)
    frame = executor_module._ContractFrame(ex, ex._block, [])
    with pytest.raises(OverflowError) as got:
        frame.execute(inc, 0, [])
    assert str(got.value) == str(want.value)
    assert frame.overlay._data == {} and frame.framed == 0 and frame.tally == []
    assert dirty(ex) == dirty(judge)
    # and the frame is whole: the row repaired, the next member runs in it
    for e in (ex, judge):
        e._block.storage.set_row(contract_table(make.counter), (0).to_bytes(32, "big"),
                                 Entry().set((41).to_bytes(32, "big")))
    assert receipt(frame.execute(inc, 1)) == receipt(judge._execute_one(inc, judge._block))
    assert frame.framed == 1 and dirty(ex) == dirty(judge)
