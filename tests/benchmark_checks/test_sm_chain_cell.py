"""The national-crypto chain cell's own pieces, on the CPU: the manifest's
entries for ``sm-air4-transfer.flood``, the configuration's file beside the
two it is made of, the generator's corpus and its six broken lanes, and the
driver ``air4_sm`` at the rehearsal's size with the device leg pinned
(``FISCO_FORCE_DEVICE_ADMISSION=1``: the fused SM program on the CPU's XLA, a
few seconds a call), traced through the harness and untraced through the
driver: ``correct`` true with every number 0, the seal identity
``test_program_spans.py`` holds every cell that seals blocks to, each of the eight
controls not correct, a window the native loop answered for not correct, and a
program without the fused SM admission leaving at once.

The sound run and the controls on the native leg (nothing pinned: the
program's rule keeps admission on the host loop here and the cell expects no
device call) come with the cell's name in ``test_benchmark_rehearsal.py`` and
``test_program_spans.py``, which run every cell of the manifest."""

import ast
import contextlib
import copy
import io
import json
import types

import pytest

import manifest_rules as rules
from benchmark import manifest, refsm, run, sm_counters
from benchmark.generators import sm_transfer_batches
from benchmark.generators.sm_signed_payloads import BROKEN
from benchmark.run import judge
from benchmark.spans import Spans

CELL = "sm-air4-transfer.flood"
SIBLING = "air4-parallelok.flood"
SEED = 2**31 + 44044  # the driver's seeds are large
# the 18 lists the cell arrived on (PR 44): what a chain cell under the flood reports
JOINED = (
    "admission_ms_per_block.flood", "consensus_ms_per_block.flood", "plane_queue_ms.flood",
    "device_leg_share.flood", "admission_us_per_sig.flood", "hash_device_ms_per_block.flood",
    "window_compiles.flood", "device_idle_share.flood", "admission_host_ms_per_block.flood",
    "seal_pbft_ms_per_block.flood", "seal_execute_ms_per_block.flood",
    "seal_commit_ms_per_block.flood", "seal_device_wait_ms_per_block.flood",
    "host_unattributed_ms_per_block.flood", "marshal_span_ms_per_block.flood",
    "device_sync_ms_per_block.flood", "gc_pause_ms_per_block.flood",
    "background_ms_per_block.flood",
)
# the SM leg's own entries: this cell's alone when this file was written
OWN = {"merkle_fused_call_share": "Device programs", "sm_admission_sync_ms_per_call": "Crypto seam"}
NUMBERS = (
    "valid_not_acknowledged", "acknowledged_not_committed", "balances_differing_from_replay",
    "sampled_txs_differing_from_plain_crypto", "corrupted_lanes_accepted",
    "replica_height_spread", "state_roots_beyond_one", "window_blocks_not_one_batch",
    "admission_calls_not_on_the_device_leg", "sm_lanes_outside_the_fused_program",
    "sampled_tx_roots_differing_from_plain_sm3",
)
CONTROLS = ("lost_write", "forked_root", "truncated_digest", "accepted_corrupt",
            "accepted_neighbours_key", "one_call_short", "one_batch_not_fused", "flipped_root")


# -- the manifest ------------------------------------------------------------------


def test_the_cell_is_one_chip_of_the_sm_chain_under_the_flood_as_it_is():
    doc = manifest.load()
    assert manifest.cell(doc, CELL) == dict(
        manifest.cell(doc, CELL), config="sm-air4-transfer", traffic="flood", chips=1)
    # the ninth cell and the eighth configuration, where PR 44 appended them
    assert doc["workloads"][8]["name"] == CELL and doc["configs"][7]["name"] == "sm-air4-transfer"
    # the mix as a run is given it: the parent's, key for key (its tiny sizes are left out)
    assert manifest.traffic_of("flood") == {
        "generator": "transfer_batches",
        "loop": "backlog: the next full block is offered as soon as the last one is "
                "committed on all replicas",
        "batch_txs": 1000, "tick_s": 0, "senders": 64, "corpus_batches": 120, "trace_blocks": 1,
    }
    assert manifest.tiny_traffic_of("flood") == dict(
        manifest.traffic_of("flood"), batch_txs=8, corpus_batches=3)
    (tps,) = [m for m in doc["end_to_end"] if m["name"] == "committed_tps"]
    assert tps["workloads"][:5][-1] == CELL
    assert {m["name"] for m in manifest.metrics_of(doc, "end_to_end", CELL)} == {
        "committed_tps", "setup_s"}


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_is_appended_to_a_list_its_sibling_is_on(name):
    (entry,) = [m for m in manifest.load()["per_layer"] if m["name"] == name]
    listed = entry["workloads"]
    assert listed.index(CELL) == listed.index(SIBLING) + 1  # appended behind it, nothing moved
    assert entry["moves"] == "committed_tps"


def manifest_rule(doc):
    """The cell stands right behind its sibling on the 18 lists it arrived on and
    on the 22 that split a block; the SM leg's two entries are counters it was
    the first on; and every list it is on holds to ``manifest_rules``."""
    assert len(JOINED) == 18 and len(rules.BLOCK_SPLIT) == 22
    for name in JOINED + rules.BLOCK_SPLIT:
        listed = rules.entry_of(doc, name)["workloads"]
        assert listed.index(CELL) == listed.index(SIBLING) + 1, name
    for name, layer in OWN.items():
        entry = rules.entry_of(doc, name)
        assert (entry["source"], entry["moves"], entry["layer"]) == (
            "program_counter", "committed_tps", layer)
        rules.list_holds(doc, entry, [CELL])
    mine = set(rules.listing(doc, CELL))
    assert set(JOINED) | set(rules.BLOCK_SPLIT) | set(OWN) <= mine
    for name in mine:
        entry = rules.entry_of(doc, name)
        assert entry["moves"] == "committed_tps"
        rules.list_holds(doc, entry, ())


def test_the_cell_is_on_those_lists_and_they_hold_to_the_manifests_rule():
    manifest_rule(manifest.load())


def test_the_configuration_is_air4_transfers_under_sm_verify10ks_suite():
    doc = manifest.load()
    mine, chain, suite = (manifest.config_of(doc, n)
                          for n in ("sm-air4-transfer", "air4-transfer", "sm-verify10k"))
    same = ("replicas", "faulty_tolerated", "tx_count_limit", "precompiled", "conflict_share",
            "hosts", "network_delay_ms", "rpc_front_door", "engine_workers", "storage",
            "total_txs", "block_limit_ahead", "reduced", "reduced_why")
    assert {k: mine[k] for k in same} == {k: chain[k] for k in same}
    stated = ("crypto", "signature_bytes", "signature_layout", "user_id", "e", "address")
    assert {k: mine[k] for k in stated} == {k: suite[k] for k in stated}
    assert mine["sm_crypto"] is True and mine["architecture"] is None
    assert (mine["driver"], mine["generator"]) == ("air4_sm", "sm_transfer_batches")
    assert set(chain) - set(mine) == set() and len(mine["source"]) <= 200
    assert mine["guarantees"][:3] == chain["guarantees"][:3] and len(mine["guarantees"]) == 5
    assert "plain SM2/SM3" in mine["guarantees"][3] and "plain SM3" in mine["guarantees"][4]
    assert mine["assumed"]["senders"] == 64 and mine["assumed"]["block_limit_ahead"] == 500
    assert {"corpus_signer", "standards_examples"} <= set(mine["assumed"])
    for number in NUMBERS[8:]:
        assert number in mine["how_correct"]


# -- the generator -----------------------------------------------------------------


def _corpus(seed=SEED, batches=2):
    corpus = sm_transfer_batches.Corpus(manifest.tiny_traffic_of("flood"), seed, 500)
    corpus.sign_until(batches)
    return corpus


def test_the_corpus_repeats_for_a_seed_and_is_signed_as_an_sm_chain_signs():
    a, b, other = _corpus(), _corpus(), _corpus(SEED + 1)
    wire = [(tx.encode_data(), bytes(tx.signature)) for tx in a.batches[1]]
    assert wire == [(tx.encode_data(), bytes(tx.signature)) for tx in b.batches[1]]
    assert a.records == b.records and a.records != other.records
    assert [len(x) for x in a.batches] == [8, 8]
    for (data, sig), (_user, _amount, who), tx in zip(wire, a.records[1], a.batches[1]):
        ok, sender, pub, _digest = refsm.admit(data, sig)
        assert ok and len(sig) == 128 and pub == refsm.pubkey_bytes(a.secrets[who])
        assert sender == refsm.address(pub)
        assert not tx.sender  # as on the wire: what the node acknowledges, it computed


def test_the_six_broken_lanes_are_sm_signed_payloads_and_the_reference_refuses_each():
    a, b = _corpus(), _corpus()
    lanes = a.corrupt(1)
    assert lanes == b.corrupt(1) == sorted(lanes) and len(set(lanes)) == len(BROKEN) == 6
    zero, order = bytes(32), refsm.N.to_bytes(32, "big")
    sigs = [bytes(a.batches[1][lane].signature) for lane in lanes]
    assert [sigs[0][:32], sigs[1][32:64], sigs[2][:32], sigs[3][32:64]] == [zero, zero, order, order]

    def pub(sig):
        return tuple(int.from_bytes(sig[lo:lo + 32], "big") for lo in (64, 96))

    assert not refsm.on_curve(pub(sigs[4])) and refsm.on_curve(pub(sigs[5]))
    who = a.records[1][lanes[5]][2]
    assert sigs[5][64:] == refsm.pubkey_bytes(a.secrets[(who + 1) % len(a.secrets)])
    for lane in range(8):
        tx = a.batches[1][lane]
        assert refsm.admit(tx.encode_data(), bytes(tx.signature))[0] == (lane not in lanes)


# -- the driver at the rehearsal's size, the device leg pinned ---------------------


def _cell():
    doc = manifest.load()
    config = manifest.config_of(doc, "sm-air4-transfer")
    traffic = manifest.tiny_traffic_of("flood")
    return manifest.driver_of(config).Cell(config, traffic, SEED, Spans())


def _values(compared):
    return {c["name"]: c["value"] for c in compared}


def _mute(_msg):
    pass


@pytest.fixture(scope="module")
def traced():
    """One ``--trace 1`` run through the harness -> (result line, log, standard error)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
        mp.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
        args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.4",
                          "--trace", "1"])
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stderr(err):
            line = run.run(args, require_chip=False, out=out)
    return line, out.getvalue(), err.getvalue()


@pytest.fixture(scope="module")
def driven():
    """One untraced run through the driver -> (the cell, what it observed)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
        cell = _cell()
        try:
            cell.setup(0.4)
            cell.window(0.4)
            cell.after_window()
            seen = cell.observe()
        finally:
            cell.close()
    return cell, seen


def test_a_traced_run_is_correct_with_every_number_zero(traced):
    line, said, _err = traced
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    compared = [ln.split("compared ")[1] for ln in said.splitlines() if "compared " in ln]
    assert [c.split(":")[0] for c in compared] == list(NUMBERS)
    assert all(c.endswith(": 0 (limit 0)") for c in compared)
    assert line["device"]["platform"] == "cpu"  # a rehearsal says what it ran on


def test_a_traced_run_reports_the_cells_lists_and_the_device_leg(traced):
    line, _said, _err = traced
    m = line["metrics"]
    doc = manifest.load()
    listed = {e["name"]: e for e in manifest.metrics_of(doc, "per_layer", CELL)}
    # the CPU's trace has no device plane: the device_trace readers find nothing
    assert set(m) <= {name for name, e in listed.items() if e["source"] != "device_trace"}
    assert set(JOINED) | set(rules.BLOCK_SPLIT) | set(OWN) <= set(m) | {
        name for name, e in listed.items() if e["source"] == "device_trace"}
    assert set(JOINED) - set(m) == {"admission_us_per_sig.flood", "hash_device_ms_per_block.flood",
                                    "device_idle_share.flood"}
    assert m["window_compiles.flood"]["value"] == 0.0
    assert m["device_leg_share.flood"]["value"] == 100.0
    # with the device leg the seam's phases are written, which the native leg has none of
    assert m["device_sync_ms_per_block.flood"]["value"] > 0.0
    assert m["marshal_span_ms_per_block.flood"]["value"] > 0.0


def test_a_traced_run_reports_the_split_of_a_block_and_the_sm_legs_entries(traced):
    """The 22 lists PR 47 appended the cell to, and its own two entries: every one
    a number, none negative, the stage clock's identities holding."""
    line, _said, err = traced
    got = {k.split(".", 1)[0]: v["value"] for k, v in line["metrics"].items()}
    split = [name.split(".", 1)[0] for name in rules.BLOCK_SPLIT]
    assert all(got[q] >= 0.0 for q in split)
    for quantity in ("exec_loop", "exec_state_root", "commit_prewrite", "commit_prepare",
                     "commit_write", "admit_static", "admit_verify", "admit_insert"):
        assert got[quantity + "_ms_per_block"] > 0.0, quantity
    assert got["commit_moved_row_share"] == 100.0
    (said,) = [ln for ln in err.splitlines() if ln.startswith("stage parts, ms per block: ")]
    parts = ast.literal_eval(said.split(": ", 1)[1].rsplit(" (idle inside", 1)[0])
    assert sum(v for k, v in parts.items() if k.startswith("exec_")) \
        - parts["roots_under_commit"] == pytest.approx(parts["sum:scheduler.execute_block"])
    assert sum(v for k, v in parts.items() if k.startswith("commit_")) \
        == pytest.approx(parts["sum:scheduler.commit_block"])
    outstanding = sum(v for k, v in parts.items() if k.startswith("outstanding:"))
    assert sum(v for k, v in parts.items() if k.startswith("idle_")) <= parts["window"] * (1 + 1e-9)
    assert outstanding > 0.0
    # the SM leg's own: four device calls a block were waited for; a block of 8 leaves
    # is under the fused tree's 256, so every merkle call of this window went level by level
    assert got["sm_admission_sync_ms_per_call"] > 0.0
    assert got["merkle_fused_call_share"] == 0.0


def test_the_seal_span_splits_into_its_groups_and_what_the_spans_miss(traced):
    """``test_program_spans.py`` holds every cell that reports
    ``consensus_ms_per_block`` to this on the native leg; here the device leg is pinned."""
    m = {k.split(".", 1)[0]: v["value"] for k, v in traced[0]["metrics"].items()}
    parts = sum(m[k] for k in ("seal_pbft_ms_per_block", "seal_execute_ms_per_block",
                               "seal_commit_ms_per_block", "seal_device_wait_ms_per_block"))
    assert 0.0 < parts <= m["consensus_ms_per_block"] * (1 + 1e-6)
    assert parts + m["host_unattributed_ms_per_block"] >= m["consensus_ms_per_block"] * (1 - 1e-6)


def test_a_run_says_the_rule_and_the_sm_legs_counts_on_standard_error(traced):
    _line, said, err = traced
    assert "sm leg: on cpu the program's rule sends a batch of 8 to the fused SM program: " \
           "4 device calls a block expected" in err
    (leg,) = [ln for ln in err.splitlines() if ln.startswith("sm leg, counts by op over the window: ")]
    split = ast.literal_eval(leg.split(": ", 1)[1])
    blocks = split["blocks"]
    assert blocks >= 1 and split["admission"] == {"calls_device": 4.0 * blocks}  # the seam's legs
    assert split["admission_sm"] == {"items": 4 * blocks * 8}  # the fused program's lanes
    # the SM3 programs by use: the hash plane's batches apart from the merkle levels
    # and every merkle level of this chain under SM3, by the label its series carries
    assert split["sm3"]["items"] > 0 and split["merkle_root"]["items_sm3"] > 0
    assert not [k for op in ("merkle_root", "merkle_tree") for k in split.get(op, {})
                if k.startswith("items") and k != "items_sm3"]
    # nothing went hash -> e -> verify -> address as programs of their own, or to the host loop
    assert "sm2_verify" not in split and "admission_native" not in split
    # whole counts only: the milliseconds are the entries'
    assert not [k for row in split.values() if isinstance(row, dict) for k in row
                if k == "ms" or k.endswith("_ms")]
    assert json.loads(said.split("counters ", 1)[1].splitlines()[0])["admission_paths"] == {
        "device": 4.0 * blocks}


def _ctx(cell):
    return types.SimpleNamespace(cell=cell)


def test_the_sm_legs_two_readers_give_the_windows_deltas():
    cell = _cell()
    cell.window_blocks = 4
    cell.sm0 = {"admission": {"calls_device": 8.0}, "admission_sm": {"items": 64.0, "sync_ms": 100.0},
                "merkle_root": {"calls_fused": 10.0, "calls_levels": 2.0}}
    cell.sm1 = {"admission": {"calls_device": 24.0},
                "admission_sm": {"items": 192.0, "sync_ms": 900.0, "ms": 1000.0},
                "merkle_root": {"calls_fused": 62.0, "calls_levels": 2.0, "items_sm3": 52000.0},
                "merkle_tree": {"calls_fused": 20.0, "calls_levels": 12.0},  # first seen in the window
                "sm2_verify": {"calls_native": 0.0}}
    assert manifest.reader_of("sm_admission_sync_ms_per_call")(_ctx(cell)) == 50.0  # 800 ms, 16 calls
    assert manifest.reader_of("merkle_fused_call_share")(_ctx(cell)) == pytest.approx(
        100.0 * 72 / 84)
    assert cell._sm_counts() == {
        "blocks": 4, "admission": {"calls_device": 16.0}, "admission_sm": {"items": 128.0},
        "merkle_root": {"calls_fused": 52.0, "items_sm3": 52000.0},
        "merkle_tree": {"calls_fused": 20.0, "calls_levels": 12.0},  # no sm2_verify: it stood
    }
    assert sm_counters.window(cell, "admission_sm", "sync_ms") == 800.0
    assert sm_counters.window(cell, "keccak256", "items") == 0.0  # never seen: it did not move


@pytest.mark.parametrize("reader", sorted(OWN))
@pytest.mark.parametrize("case,build", [
    ("a driver that took no snapshots", lambda c: None),
    ("a program without the counters", lambda c: setattr(c, "sm0", {}) or setattr(c, "sm1", {})),
    ("a window on the native loop, no tree hashed",
     lambda c: setattr(c, "sm0", {"admission": {"calls_native": 4.0}})
     or setattr(c, "sm1", {"admission": {"calls_native": 8.0}, "admission_sm": {"sync_ms": 0.0}})),
], ids=lambda v: v.replace(" ", "_").replace(",", "") if isinstance(v, str) else None)
def test_an_sm_leg_reader_gives_none_where_there_is_nothing_to_read(reader, case, build):
    cell = types.SimpleNamespace()
    build(cell)
    assert manifest.reader_of(reader)(_ctx(cell)) is None


def test_an_untraced_run_is_correct_with_every_number_zero(driven):
    cell, seen = driven
    sound = cell.compare(seen)
    assert [c["name"] for c in sound] == list(NUMBERS)
    assert set(_values(sound).values()) == {0} and judge(sound, _mute)
    assert cell.device_leg is True and cell.window_blocks >= 1
    assert seen["device_calls"] == 4 * cell.window_blocks
    assert seen["fused_lanes"] == 4 * cell.window_blocks * 8
    assert cell.end_to_end()["committed_tps"] > 0 and cell.failed_count() == 0
    assert len(cell.corrupt_lanes) == 6 and set(cell.controls()) == set(CONTROLS)
    for rep in seen["replicas"]:
        assert len(rep["sample"]) == 256 and len(rep["roots"]) == min(8, cell.window_blocks)
        assert all(len(got["payloads"]) == 8 for got in rep["roots"].values())


@pytest.mark.parametrize("control,outside", [
    ("lost_write", "balances_differing_from_replay"),
    ("forked_root", "state_roots_beyond_one"),
    ("truncated_digest", "sampled_txs_differing_from_plain_crypto"),
    ("accepted_corrupt", "corrupted_lanes_accepted"),
    ("accepted_neighbours_key", "corrupted_lanes_accepted"),
    ("one_call_short", "admission_calls_not_on_the_device_leg"),
    ("one_batch_not_fused", "sm_lanes_outside_the_fused_program"),
    ("flipped_root", "sampled_tx_roots_differing_from_plain_sm3"),
])
def test_each_control_comes_out_not_correct(driven, control, outside):
    cell, seen = driven
    degraded = copy.deepcopy(seen)
    cell.controls()[control](degraded)
    got = cell.compare(degraded)
    assert not judge(got, _mute)
    assert [c["name"] for c in got if c["value"] > c["limit"]] == [outside]


def test_a_surplus_of_device_calls_is_as_wrong_as_a_shortfall(driven):
    cell, seen = driven
    degraded = copy.deepcopy(seen)
    degraded["device_calls"] += 4  # a block counted twice
    degraded["fused_lanes"] -= 8  # a batch that went through programs of its own
    got = _values(cell.compare(degraded))
    assert got["admission_calls_not_on_the_device_leg"] == 4
    assert got["sm_lanes_outside_the_fused_program"] == 8


def test_a_window_the_native_loop_answered_for_is_not_correct(monkeypatch):
    """Set-up finds the device leg (pinned, as the chip's rule gives it) and
    expects four device calls a block; the window's batches then go to the
    native loop, as under an open breaker. Every hash, sender, balance and
    root is right, and the cell is not correct."""
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    cell = _cell()
    try:
        cell.setup(0.4)
        assert cell.device_leg is True
        monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION")
        cell.window(0.4)
        cell.after_window()
        seen = cell.observe()
    finally:
        cell.close()
    got = _values(cell.compare(seen))
    assert got.pop("admission_calls_not_on_the_device_leg") == 4 * cell.window_blocks > 0
    assert got.pop("sm_lanes_outside_the_fused_program") == 4 * cell.window_blocks * 8
    assert set(got.values()) == {0}
    assert not judge(cell.compare(seen), _mute)


def test_a_program_without_the_fused_sm_admission_leaves_at_once(monkeypatch, capsys):
    from fisco_bcos_tpu.crypto.suite import CryptoSuite

    monkeypatch.setattr(CryptoSuite, "fused_admission", lambda self: None)
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.3", "--trace", "0"])
    with pytest.raises(SystemExit) as e:
        run.run(args, require_chip=False, out=io.StringIO())
    assert e.value.code == run.RC_NO_PROGRAM
    assert "no fused SM2/SM3 admission" in capsys.readouterr().err
