"""The deployed-contract cell's own pieces, on the CPU: the configuration's
file beside the precompile cell's, the manifest's lists the cell joined, the
four entries of the contract leg on hand-made snapshots and on the program's
registry, and the driver ``air4_parallelok`` at the rehearsal's size: a block
the Python interpreter executed is not correct, the engine counter read one
call short is not correct, and a program that does not say which engine
finished a call makes the cell leave at once."""

import io
import types

import pytest

import manifest_rules as rules
from benchmark import contract_counters, manifest, run
from benchmark.run import judge
from benchmark.spans import Spans

CELL = "air4-parallelok.flood"
SIBLING = "air4-dagtransfer.flood"
SEED = 2**31 + 40040  # the driver's seeds are large
LAYER = "Sealer, PBFT, scheduler, storage"
# the contract leg's own entries: this cell's alone when this file was written
READERS = ("contract_tx_us_per_tx", "evm_call_us_per_tx", "evm_native_call_share",
           "contract_framed_tx_share")


def test_the_configuration_is_the_precompile_cells_where_the_deployment_is_the_same():
    doc = manifest.load()
    mine, theirs = (manifest.config_of(doc, n) for n in ("air4-parallelok", "air4-dagtransfer"))
    same = ("architecture", "replicas", "faulty_tolerated", "tx_count_limit", "crypto", "attribute",
            "user_batches", "payer_payee_draw", "zipf_theta", "amount", "opening_balance", "hosts",
            "network_delay_ms", "rpc_front_door", "engine_workers", "storage", "total_txs",
            "block_limit_ahead", "reduced", "reduced_why", "chip_mapping")
    assert {k: mine[k] for k in same} == {k: theirs[k] for k in same}
    assert mine["architecture"] is None and mine["driver"] == "air4_parallelok"
    assert theirs["guarantees"][:5] == mine["guarantees"][:5] and len(mine["guarantees"]) == 7
    assert {"source_contract", "compiler", "runtime", "payer_payee_draw"} <= set(mine["assumed"])
    for key in ("payer_payee_draw", "zipf_theta", "amount", "senders", "block_limit_ahead"):
        assert mine["assumed"][key] == theirs["assumed"][key]
    assert manifest.cell(doc, CELL) == dict(
        manifest.cell(doc, CELL), config="air4-parallelok", traffic="flood", chips=1)


# some of what the precompile cell reports and this cell has to as well: the split
# of a block's host time, the device's share, and the DAG runner's own counters
JOINED = ("consensus_ms_per_block.flood", "seal_execute_ms_per_block.flood",
          "seal_commit_ms_per_block.flood", "admission_ms_per_block.flood",
          "device_idle_share.flood", "window_compiles.flood", "dag_levels_per_block",
          "dag_loop_ms_per_block", "dag_reruns_per_block")

@pytest.mark.parametrize("name", JOINED + ("committed_tps",))
def test_the_cell_is_on_the_list_of_a_metric_its_sibling_reports(name):
    doc = manifest.load()
    (entry,) = [m for m in doc["per_layer"] + doc["end_to_end"] if m["name"] == name]
    assert CELL in entry["workloads"] and SIBLING in entry["workloads"]


def reader_entry_holds(doc, name):
    entry = rules.entry_of(doc, name)
    assert (entry["source"], entry["moves"], entry["layer"]) == (
        "program_counter", "committed_tps", LAYER)
    rules.list_holds(doc, entry, [CELL])
    assert manifest.reader_path(name).endswith(f"benchmark/layers/{name}.py")


def manifest_rule(doc):
    """The cell stands, behind its sibling, on the lists that split a block; the
    contract leg's entries are counters of the scheduler's layer that it was the
    first on; and every list it is on holds to ``manifest_rules``."""
    for name in JOINED + rules.BLOCK_SPLIT:
        listed = rules.entry_of(doc, name)["workloads"]
        assert SIBLING in listed and listed.index(CELL) > listed.index(SIBLING), name
    for name in READERS:
        reader_entry_holds(doc, name)
    for name in rules.listing(doc, CELL):
        entry = rules.entry_of(doc, name)
        assert entry["moves"] == "committed_tps"
        rules.list_holds(doc, entry, ())


def test_the_lists_the_cell_is_on_hold_to_the_manifests_rule():
    manifest_rule(manifest.load())


def test_no_entry_reads_the_pool_that_pr_41_took_out():
    doc = manifest.load()
    assert not [m["name"] for m in doc["per_layer"] if "pool" in m["name"]]
    with pytest.raises(SystemExit):
        manifest.reader_of("dag_pooled_tx_share")
    with pytest.raises(SystemExit):
        manifest.reader_of("dag_pool_wait_ms_per_block")


@pytest.mark.parametrize("name", READERS)
def test_a_reader_of_the_contract_leg_is_an_entry_of_this_cell(name):
    reader_entry_holds(manifest.load(), name)


# -- the readers on hand-made snapshots -----------------------------------------


def _snap(txs=0.0, tx_s=0.0, native=0.0, interpreted=0.0, evm_s=0.0, framed=0.0):
    return {"contract_txs": txs, "contract_tx_s": tx_s, "evm_native": native,
            "evm_interpreter": interpreted, "evm_s": evm_s, "contract_framed": framed}


def _ctx(before=None, after=None):
    cell = types.SimpleNamespace()
    if before is not None:
        cell.contract0, cell.contract1 = before, after
    return types.SimpleNamespace(cell=cell)


WINDOW = _ctx(_snap(txs=4000, tx_s=1.0, native=4000, evm_s=0.5, framed=4000),
              _snap(txs=12000, tx_s=5.0, native=11000, interpreted=1000, evm_s=2.5, framed=10000))
NO_COUNTERS = _ctx(dict.fromkeys(_snap(), None), dict.fromkeys(_snap(), None))


@pytest.mark.parametrize("reader,want", [
    ("contract_tx_us_per_tx", 500.0),  # 4 s over 8,000 calls
    ("evm_call_us_per_tx", 250.0),
    ("evm_native_call_share", 87.5),  # 7,000 of 8,000
    ("contract_framed_tx_share", 75.0),  # 6,000 of 8,000: the frame stood aside for the rest
])
def test_a_reader_gives_the_windows_delta(reader, want):
    assert manifest.reader_of(reader)(WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("case,ctx", [
    ("a program without the counters", NO_COUNTERS),
    ("a driver that took no snapshots", _ctx()),
    ("a window in which nothing ran", _ctx(_snap(), _snap())),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_a_reader_gives_none_where_there_is_nothing_to_read(reader, case, ctx):
    assert manifest.reader_of(reader)(ctx) is None


def test_a_counter_first_seen_inside_the_window_counts_from_zero():
    ctx = _ctx(dict.fromkeys(_snap(), None), _snap(txs=10, tx_s=0.001, native=10, evm_s=0.0005))
    assert manifest.reader_of("evm_native_call_share")(ctx) == 100.0
    assert manifest.reader_of("contract_framed_tx_share")(ctx) == 0.0  # seen, and did not move
    assert manifest.reader_of("contract_tx_us_per_tx")(ctx) == pytest.approx(100.0)


def test_snapshot_reads_the_programs_counters():
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    before = contract_counters.snapshot()
    for name, value in ((contract_counters.COUNTERS["contract_txs"], 3.0),
                        (contract_counters.COUNTERS["evm_native"], 2.0),
                        (contract_counters.COUNTERS["evm_interpreter"], 1.0),
                        ("fisco_executor_dag_pooled_txs_total", 9.0)):  # another counter
        REGISTRY.counter_add(name, value)
    cell = types.SimpleNamespace(contract0=before, contract1=contract_counters.snapshot())
    assert contract_counters.window(cell, "contract_txs") == 3.0
    assert contract_counters.evm_calls(cell) == 3.0
    assert contract_counters.window(cell, "nothing") is None
    assert contract_counters.window(types.SimpleNamespace(), "contract_txs") is None


# -- the driver at the rehearsal's size -------------------------------------------


def _cell():
    doc = manifest.load()
    config = manifest.config_of(doc, "air4-parallelok")
    traffic = manifest.tiny_traffic_of("flood")
    return manifest.driver_of(config).Cell(config, traffic, SEED, Spans())


def _drive(cell, seconds=0.4):
    cell.setup(seconds)
    try:
        cell.window(seconds)
        cell.after_window()
        return cell.observe()
    finally:
        cell.close()


def _values(compared):
    return {c["name"]: c["value"] for c in compared}


def _mute(_msg):
    pass


def test_a_sound_run_holds_every_number_and_the_contract_legs_entries_read_it(monkeypatch, capsys):
    for name in ("FISCO_NO_NATIVE_EVM", "FISCO_DAG_SERIAL"):
        monkeypatch.delenv(name, raising=False)
    cell = _cell()
    seen = _drive(cell)
    sound = cell.compare(seen)
    assert len(sound) == 13 and set(_values(sound).values()) == {0} and judge(sound, _mute)
    assert seen["native_calls"] == 4 * cell.window_blocks * cell.batch_txs > 0
    assert len(seen["replicas"][0]["balances"]) == 80 == len(cell.corpus.names)
    assert set(cell.controls()) == {"lost_write", "forked_root", "truncated_digest",
                                    "accepted_corrupt", "lost_update", "one_call_short"}
    # what the driver's line on standard error said is the entries' to say now
    assert "contract leg" not in capsys.readouterr().err
    ctx = types.SimpleNamespace(cell=cell)
    readings = {name: manifest.reader_of(name)(ctx) for name in READERS}
    assert None not in readings.values() and all(v > 0 for v in readings.values())
    assert readings["evm_native_call_share"] == 100.0
    assert readings["contract_framed_tx_share"] == 100.0
    assert readings["evm_call_us_per_tx"] < readings["contract_tx_us_per_tx"]
    assert contract_counters.window(cell, "evm_interpreter") == 0.0
    cell.controls()["one_call_short"](seen)
    assert _values(cell.compare(seen))["calls_not_on_the_native_engine"] == 1
    seen["native_calls"] += 2  # a block counted twice is no sounder than a call missed
    assert _values(cell.compare(seen))["calls_not_on_the_native_engine"] == 1


def test_a_block_the_python_interpreter_executed_is_not_correct(monkeypatch):
    """Every balance, receipt and root is right, and the cell is not correct:
    the configuration guarantees the native engine."""
    monkeypatch.setenv("FISCO_NO_NATIVE_EVM", "1")
    cell = _cell()
    seen = _drive(cell)
    got = _values(cell.compare(seen))
    executions = 4 * cell.window_blocks * cell.batch_txs
    assert got.pop("calls_not_on_the_native_engine") == executions > 0
    assert got.pop("calls_the_python_interpreter_ran") == executions
    assert set(got.values()) == {0}
    assert not judge(cell.compare(seen), _mute)


def test_a_program_that_does_not_name_the_engine_leaves_at_once(monkeypatch, capsys):
    from fisco_bcos_tpu.executor.evm import EVMResult

    monkeypatch.delitem(EVMResult.__dataclass_fields__, "engine")
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.3", "--trace", "0"])
    with pytest.raises(SystemExit) as e:
        run.run(args, require_chip=False, out=io.StringIO())
    assert e.value.code == run.RC_NO_PROGRAM
    assert "does not say which engine" in capsys.readouterr().err
