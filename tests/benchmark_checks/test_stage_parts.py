"""``benchmark/stage_parts.py`` on a hand-made ring (one case per quantity that
reads the spans' stage marks, the identities between them, the cases that give
None), the sums of a window's records against the counter family's delta, and
a traced tiny run on the CPU: every new entry of ``air4-transfer.flood`` reads
a number."""

import io
import threading
import types

import pytest

import manifest_rules as rules
from benchmark import manifest, run, stage_parts as sp

DRIVER, WORKER, NOTIFY = 1, 2, 3
T0, T1, BLOCKS = 100.0, 110.0, 2
_NO = types.MappingProxyType({})


def rec(name, start, end, tid=DRIVER, derived=False, span_id=0, parent_id=None, **stages):
    return types.SimpleNamespace(
        name=name, ts=start, dur=end - start, tid=tid, derived=derived, span_id=span_id,
        parent_id=parent_id, attrs={"stages": stages} if stages else _NO)


ADMISSION = [
    # the entry node, then the three replicas' pushes inside the gossip (two shown)
    rec("txpool.submit_batch", 100.00, 100.30, static=0.02, verify=0.25, insert=0.03),
    rec("device.plane.wait", 100.04, 100.21),  # a wait is not a call outstanding
    rec("gc.gen2", 100.10, 100.12),
    rec("txsync.maintain", 100.30, 100.50, span_id=7),
    rec("txsync.push", 100.31, 100.40, span_id=8, parent_id=7, decode=0.01),
    rec("txpool.submit_batch", 100.32, 100.40, parent_id=8, static=0.01, verify=0.06, insert=0.01),
    rec("txsync.push", 100.40, 100.49, span_id=9, parent_id=7, decode=0.02),
    rec("txpool.submit_batch", 100.42, 100.49, parent_id=9, static=0.01, verify=0.05, insert=0.01),
    rec("txpool.pool_wait", 100.0, 100.5, derived=True),
    # the plane worker: the admission program from its dispatch to its answer
    rec("device.admission.marshal", 100.03, 100.05, WORKER),
    rec("device.admission.enqueue", 100.05, 100.06, WORKER),
    rec("device.admission.sync", 100.06, 100.20, WORKER),
]
CHAIN = [
    rec("scheduler.execute_block", 100.60, 100.80, fillBlock=0.01, execute=0.08, stateRoot=0.03,
        txsRoot=0.02, receiptsRoot=0.02, roots=0.03, store=0.005),
    rec("device.keccak256.enqueue", 100.62, 100.63, WORKER),
    rec("device.merkle_root.enqueue", 100.64, 100.65),
    rec("gc.gen2", 100.65, 100.68),
    rec("device.keccak256.sync", 100.70, 100.72),
    rec("device.merkle_root.sync", 100.72, 100.74),
    rec("gc.gen0", 100.70, 100.71, NOTIFY),  # on a thread that was in none of the spans
    rec("scheduler.execute_block", 100.85, 100.90, cached=0.01, roots=0.03),  # a cache hit
    # a proof tree on the plane worker, beside the block: that thread waiting for the
    # interpreter, not the chip at work: in the log line, left out of the union
    rec("device.merkle_tree.enqueue", 100.86, 100.94, WORKER),
    rec("device.merkle_tree.sync", 100.94, 100.95, WORKER),
    rec("scheduler.commit_block", 101.00, 101.20, gate=0.01, roots=0.02, prewrite=0.04,
        prepare=0.05, commit=0.03, booked=0.02),
    rec("scheduler.2pc_prepare", 101.08, 101.12),
    rec("gc.gen1", 101.05, 101.06),
    # another node's thread executes too: in the sums, not under the driving thread's idle
    rec("scheduler.execute_block", 102.00, 102.10, NOTIFY, execute=0.06, stateRoot=0.01,
        txsRoot=0.01, receiptsRoot=0.01, roots=0.005),
    rec("scheduler.execute_block", 111.0, 111.5, execute=0.4),  # after the window
]
BOOT = [rec("boot", 1.0, 2.0)]  # the ring reaches back before the window
RECORDS = BOOT + ADMISSION + CHAIN
EXPECTED_MS = {  # per block, two blocks
    "exec_loop_ms_per_block": (0.08 + 0.06) * 500,
    "exec_state_root_ms_per_block": (0.03 + 0.01) * 500,
    "exec_txs_root_ms_per_block": (0.02 + 0.01) * 500,
    "exec_receipts_root_ms_per_block": (0.02 + 0.01) * 500,
    "exec_roots_wait_ms_per_block": (0.03 + 0.03 + 0.005 + 0.02) * 500,  # the commit's 0.02 too
    "exec_other_ms_per_block": (0.35 - 0.14 - 0.04 - 0.03 - 0.03 - 0.065) * 500,
    "commit_prewrite_ms_per_block": 0.04 * 500,
    "commit_prepare_ms_per_block": 0.05 * 500,
    "commit_write_ms_per_block": 0.03 * 500,
    "commit_book_ms_per_block": (0.20 - 0.04 - 0.05 - 0.03) * 500,
    "admit_static_ms_per_block": (0.02 + 0.01 + 0.01) * 500,
    "admit_verify_ms_per_block": (0.25 + 0.06 + 0.05) * 500,
    "admit_insert_ms_per_block": (0.03 + 0.01 + 0.01) * 500,
    "admit_gossip_ms_per_block": (0.01 + 0.02 + 0.20 - 0.09 - 0.09) * 500,
    "gc_in_execute_ms_per_block": 0.03 * 500,
    "gc_in_commit_ms_per_block": 0.01 * 500,
    "gc_in_admission_ms_per_block": 0.02 * 500,
    # outstanding: 100.05-100.20, 100.62-100.63, 100.64-100.65, 100.70-100.74 (no proof tree)
    "idle_in_execute_ms_per_block": (0.20 - 0.06 + 0.05) * 500,
    "idle_in_commit_ms_per_block": 0.20 * 500,
    "idle_in_admission_ms_per_block": (0.50 - 0.15) * 500,
    "idle_elsewhere_ms_per_block": (10.0 - 0.21 - 0.19 - 0.20 - 0.35) * 500,
}


def suffixes(quantity):
    """The collector's pauses and the idle time are split for the flood only."""
    return (".flood",) if quantity.startswith(("gc_in_", "idle_")) else (".flood", ".paced")


NAMES = [quantity + suffix for quantity in EXPECTED_MS for suffix in suffixes(quantity)]
# the cells each list had when this file was written: still on it, at the front
FRONT = {".flood": ["air4-transfer.flood", "air4-catchup.backlog", "air4-dagtransfer.flood"],
         ".paced": ["air4-transfer.paced"]}


def entries(doc):
    return [m for m in doc["per_layer"] if m["name"] in NAMES]


def manifest_rule(doc):
    """Every quantity of ``EXPECTED_MS`` is an entry under each of its suffixes,
    a counter in milliseconds, and its list holds to ``manifest_rules``; an
    ``admit_*`` list names no cell whose driver says its window admits nothing."""
    mine = entries(doc)
    assert sorted(m["name"] for m in mine) == sorted(NAMES)
    for m in mine:
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower", "program_counter")
        holds = rules.admitting_list_holds if m["name"].startswith("admit_") else rules.list_holds
        holds(doc, m, FRONT["." + m["name"].partition(".")[2]])


def ctx_for(records):
    return types.SimpleNamespace(
        stage_parts=sp.split(records, T0, T1, BLOCKS, DRIVER),
        cell=types.SimpleNamespace(window_blocks=BLOCKS), t0=T0, t1=T1)


def test_every_entry_has_a_case_and_lists_the_cells_that_write_its_spans():
    doc = manifest.load()
    manifest_rule(doc)
    # counted from this file's own table: a quantity times the suffixes that list it
    assert len(entries(doc)) == sum(len(suffixes(q)) for q in EXPECTED_MS)


@pytest.mark.parametrize("quantity", sorted(EXPECTED_MS))
def test_quantity_on_a_hand_made_ring(quantity):
    read = manifest.reader_of(quantity)
    assert read(ctx_for(RECORDS)) == pytest.approx(EXPECTED_MS[quantity], abs=1e-6)
    # the ring's oldest record is younger than t0: a missing number, not a partial one
    assert read(ctx_for(RECORDS[1:])) is None
    assert read(ctx_for([])) is None
    # a program without the stage clock (the parent commit): nothing to read
    bare = [types.SimpleNamespace(**{**vars(r), "attrs": _NO}) for r in RECORDS]
    assert read(ctx_for(bare)) is None


def test_the_parts_add_up():
    parts = sp.split(RECORDS, T0, T1, BLOCKS, DRIVER)
    ms = {k: v * 500 for k, v in {"execute": 0.35, "commit": 0.20, "submit": 0.45}.items()}
    # the log line carries what the identities are held against
    assert (parts["sum:scheduler.execute_block"], parts["sum:scheduler.commit_block"],
            parts["sum:txpool.submit_batch"], parts["window"]) == pytest.approx(
        (ms["execute"], ms["commit"], ms["submit"], (T1 - T0) * 500))
    assert parts["outstanding:admission"] == pytest.approx(0.15 * 500)
    assert parts["outstanding:merkle_tree"] == pytest.approx(0.09 * 500)
    assert sum(v for k, v in parts.items() if k.startswith("exec_")) \
        - parts["roots_under_commit"] == pytest.approx(ms["execute"])
    assert sum(v for k, v in parts.items() if k.startswith("commit_")) \
        == pytest.approx(ms["commit"])
    assert parts["admit_static"] + parts["admit_verify"] + parts["admit_insert"] \
        == pytest.approx(ms["submit"])
    gc_total = (0.02 + 0.03 + 0.01 + 0.01) * 500  # what gc_pause_ms_per_block reads
    assert sum(v for k, v in parts.items() if k.startswith("gc_in_")) <= gc_total
    outstanding = 0.15 + 0.01 + 0.01 + 0.04
    assert sum(v for k, v in parts.items() if k.startswith("idle_")) \
        == pytest.approx((T1 - T0 - outstanding) * 500)


def test_a_cell_that_admits_nothing_reads_no_admit_quantity():
    """The catch-up cell's window: executions and 2PCs, no pool, no gossip."""
    parts = sp.split(BOOT + CHAIN, T0, T1, BLOCKS, DRIVER)
    assert not [k for k in parts if k.startswith("admit_")]
    assert parts["idle_in_admission"] == 0.0 and parts["gc_in_admission"] == 0.0
    assert parts["exec_loop"] == pytest.approx(EXPECTED_MS["exec_loop_ms_per_block"])
    ctx = types.SimpleNamespace(stage_parts=parts)
    assert sp.read(ctx, "admit_verify_ms_per_block") is None
    assert sp.read(ctx, "commit_write_ms_per_block") == pytest.approx(15.0)


MARKS = {  # quantity -> the (span, stage) whose counter it is the window's delta of
    "exec_loop": ("scheduler.execute_block", "execute"),
    "exec_state_root": ("scheduler.execute_block", "stateRoot"),
    "exec_txs_root": ("scheduler.execute_block", "txsRoot"),
    "exec_receipts_root": ("scheduler.execute_block", "receiptsRoot"),
    "exec_roots_wait": ("scheduler.execute_block", "roots"),
    "commit_prewrite": ("scheduler.commit_block", "prewrite"),
    "commit_prepare": ("scheduler.commit_block", "prepare"),
    "commit_write": ("scheduler.commit_block", "commit"),
    "admit_static": ("txpool.submit_batch", "static"),
    "admit_verify": ("txpool.submit_batch", "verify"),
    "admit_insert": ("txpool.submit_batch", "insert"),
}


def test_a_windows_increments_sum_to_the_counters_delta():
    """The quantities are read from the increments the records carry, because
    the chain cells' drivers take no snapshot of the counter: the two are one
    number."""
    import time

    from fisco_bcos_tpu.observability import Tracer
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    def counters():
        return REGISTRY.counters_matching("fisco_span_stage_seconds_total")

    tracer = Tracer()
    with tracer.span("scheduler.execute_block") as span:  # before the window: not in the delta
        span.stage("execute")
    time.sleep(0.001)
    before, t0 = counters(), time.perf_counter()
    for _ in range(3):
        for name in dict(MARKS.values()):
            with tracer.span(name) as span:
                for stage in [s for n, s in MARKS.values() if n == name]:
                    time.sleep(0.0003)
                    span.stage(stage)
    t1, after = time.perf_counter(), counters()
    parts = sp.split(tracer.spans(), t0, t1, 3, threading.get_ident())
    for quantity, (name, stage) in MARKS.items():
        key = f'fisco_span_stage_seconds_total{{span="{name}",stage="{stage}"}}'
        delta = after[key] - before.get(key, 0.0)
        assert delta >= 3 * 0.0003
        assert parts[quantity] * 3 / 1e3 == pytest.approx(delta, rel=1e-9)


# -- CPU rehearsal --------------------------------------------------------------


def test_every_new_entry_reads_a_number_in_a_traced_run(monkeypatch, capfd):
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    workload = "air4-transfer.flood"
    args = run.parse(["--workload", workload, "--seed", str(2**31 + 36),
                      "--seconds", "0.7", "--trace", "1"])
    line = run.run(args, require_chip=False, out=io.StringIO())
    assert line["correct"] is True
    mine = [m["name"] for m in entries(manifest.load()) if workload in m["workloads"]]
    assert len(mine) == len(EXPECTED_MS)  # every quantity's .flood entry
    got = {name.split(".", 1)[0]: line["metrics"][name]["value"] for name in mine}
    assert all(v >= 0.0 for v in got.values()), got
    for name in mine:
        assert line["metrics"][name]["unit"] == "ms"
    # a block was executed, committed and admitted on four nodes, with the loop inside it
    for quantity in ("exec_loop", "exec_state_root", "commit_prewrite", "commit_prepare",
                     "commit_write", "commit_book", "admit_static", "admit_verify",
                     "admit_insert", "admit_gossip", "idle_in_execute", "idle_elsewhere"):
        assert got[quantity + "_ms_per_block"] > 0.0, quantity
    seal = line["metrics"]["consensus_ms_per_block.flood"]["value"]
    admission = line["metrics"]["admission_ms_per_block.flood"]["value"]
    exec_sum = sum(v for k, v in got.items() if k.startswith("exec_"))
    commit_sum = sum(v for k, v in got.items() if k.startswith("commit_"))
    assert exec_sum + commit_sum <= seal * (1 + 1e-6)
    assert sum(v for k, v in got.items() if k.startswith("admit_")) <= admission * (1 + 1e-6)
    assert sum(v for k, v in got.items() if k.startswith("gc_in_")) \
        <= line["metrics"]["gc_pause_ms_per_block.flood"]["value"] + 1e-9
    assert sum(v for k, v in got.items() if k.startswith("idle_")) <= (seal + admission) * 1.05
    assert "stage parts, ms per block:" in capfd.readouterr().err
