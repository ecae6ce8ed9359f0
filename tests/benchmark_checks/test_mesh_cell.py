"""The four-chip admission cell's own pieces, on the CPU: the three readers on
hand-made contexts, ``mesh_counters.snapshot`` against the program's registry,
and the driver ``admit_mesh`` on the forced devices (a mesh of eight): at the
rehearsal's size the program's rule keeps a block on one device and no mesh
call is expected; with the threshold lowered every block has to have come
back over the mesh, a block the host answered for is not correct, and the
counter read one call short is not correct either way. A program without the
counter makes the cell leave at once."""

import io
import types

import pytest

from benchmark import manifest, mesh_counters, run
from benchmark.run import judge
from benchmark.spans import Spans

CELL = "verify10k-quad.stream"
SEED = 2**31 + 38038  # the driver's seeds are large


def _ctx(mesh0=None, mesh1=None, blocks=4, traced=1, red=None):
    cell = types.SimpleNamespace(window_blocks=blocks, traced_series=[{}] * traced)
    if mesh0 is not None:
        cell.mesh0, cell.mesh1 = mesh0, mesh1
    return types.SimpleNamespace(cell=cell, red=red)


def _snap(calls=0.0, device=0.0, sharded=0.0, place=0.0):
    return {"calls": {"admission/4": calls}, "device_lanes": device,
            "sharded_lanes": sharded, "phase_ms": {"place": place, "sync": 70.0 * place}}


def test_the_three_entries_are_in_the_manifest_for_this_cell_only():
    doc = manifest.load()
    mine = {m["name"]: m for m in doc["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"mesh_lane_share", "shard_place_ms_per_block",
                         "shard_program_ms_per_block"}
    assert all(m["moves"] == "verify_tps" for m in mine.values())
    assert mine["shard_program_ms_per_block"]["source"] == "device_trace"
    assert manifest.cell(doc, CELL)["chips"] == 4
    config = manifest.config_of(doc, "verify10k-quad")
    assert config["devices"] == 4 and config["lanes_per_device"] * 4 == 10240
    assert config["architecture"] is None and config["driver"] == "admit_mesh"
    one_chip = manifest.config_of(doc, "verify10k")
    for key in ("lanes", "signers", "crypto", "chain_around_it", "reduced"):
        assert config[key] == one_chip[key]
    assert set(one_chip["guarantees"]) < set(config["guarantees"])


@pytest.mark.parametrize("case,ctx,want", [
    ("every lane over the mesh",
     _ctx(_snap(device=20000, sharded=20000), _snap(device=60000, sharded=60000)), 100.0),
    ("one block of four on one chip",
     _ctx(_snap(device=20000, sharded=20000), _snap(device=60000, sharded=50000)), 75.0),
    ("no device lanes in the window (the native leg)", _ctx(_snap(), _snap()), None),
    ("a driver that took no snapshots", _ctx(), None),
    ("a program without the counters", _ctx({}, {}), None),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_mesh_lane_share(case, ctx, want):
    assert manifest.reader_of("mesh_lane_share")(ctx) == want


@pytest.mark.parametrize("case,ctx,want", [
    ("the window's sum over its blocks", _ctx(_snap(place=10.0), _snap(place=16.0)), 1.5),
    ("no place phase (one chip, or the parent's program)", _ctx(_snap(), _snap()), None),
    ("no block", _ctx(_snap(), _snap(place=3.0), blocks=0), None),
    ("a driver that took no snapshots", _ctx(), None),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_shard_place_ms_per_block(case, ctx, want):
    got = manifest.reader_of("shard_place_ms_per_block")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("case,ctx,want", [
    ("one traced block; the reduction's mean over the devices",
     _ctx(red={"program_s": {"jit_admission_shard": 0.0425, "jit_run": 0.001}}), 42.5),
    ("two traced blocks", _ctx(traced=2, red={"program_s": {"jit_admission_shard": 0.09}}), 45.0),
    ("the one-chip program is not the shard's",
     _ctx(red={"program_s": {"jit__admission_packed": 0.107}}), None),
    ("no trace", _ctx(red=None), None),
    ("no traced block", _ctx(traced=0, red={"program_s": {"jit_admission_shard": 0.04}}), None),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_shard_program_ms_per_block(case, ctx, want):
    got = manifest.reader_of("shard_program_ms_per_block")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_snapshot_reads_the_programs_counters():
    from fisco_bcos_tpu.observability.device import LEDGER, observe_phase
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    before = mesh_counters.snapshot()
    LEDGER.note_mesh_call("admission", 4, 2560)
    LEDGER.note_mesh_call("admission", 4, 2560)
    LEDGER.note_mesh_call("admission_sm", 4, 2560)  # another op: not this cell's
    LEDGER.note_mesh_call("admission", 8, 1280)  # another mesh: not the configuration's
    observe_phase("admission_sharded", "place", 2.5)
    observe_phase("admission_sharded", "sync", 70.0)  # another phase
    observe_phase("keccak256", "place", 9.0)  # another op
    observe_phase("admission", "sync", 107.0)  # the one-chip leg
    for op, lanes in (("admission_sharded", 20000.0), ("admission", 10000.0),
                      ("admission_native", 7.0)):
        REGISTRY.counter_add(f'fisco_device_items_total{{op="{op}"}}', lanes)
    after = mesh_counters.snapshot()
    assert mesh_counters.mesh_calls(before, after, "admission", 4) == 2
    assert mesh_counters.mesh_calls(before, after, "admission", 8) == 1
    assert mesh_counters.mesh_calls(before, after, "admission", 2) == 0
    assert mesh_counters.mesh_calls({}, {}, "admission", 4) == 0
    ctx = _ctx(before, after, blocks=2)
    assert mesh_counters.window(ctx, "place_ms") == pytest.approx(2.5)
    moved = {k: v for k, v in mesh_counters.phase_ms(before, after).items() if v}
    assert moved == pytest.approx({"place": 2.5, "sync": 70.0})
    assert mesh_counters.window(ctx, "sharded_lanes") == 20000
    assert mesh_counters.window(ctx, "device_lanes") == 30000  # the native loop's left out
    assert manifest.reader_of("mesh_lane_share")(ctx) == pytest.approx(100 * 2 / 3)
    assert manifest.reader_of("shard_place_ms_per_block")(ctx) == pytest.approx(1.25)


# -- the driver on the CPU's forced devices -----------------------------------


def _cell(devices: int):
    doc = manifest.load()
    config = dict(manifest.config_of(doc, "verify10k-quad"), devices=devices)
    traffic = manifest.tiny_traffic_of("stream")
    return manifest.driver_of(config).Cell(config, traffic, SEED, Spans())


def _drive(cell, seconds=0.3):
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


def test_at_the_rehearsals_size_no_mesh_call_is_expected(monkeypatch, capsys):
    """16 lanes are under the program's threshold: its rule says one device,
    nothing goes out over a mesh, and the number holds; read one call short
    it does not."""
    monkeypatch.delenv("FISCO_DEVICE_SHARD_MIN", raising=False)
    cell = _cell(devices=4)
    seen = _drive(cell)
    assert "the program's rule sends a bucket of 32 lanes over 1 " in capsys.readouterr().err
    assert cell.mesh_expected is False and seen["mesh_calls"] == 0
    sound = cell.compare(seen)
    assert len(sound) == 5 and set(_values(sound).values()) == {0} and judge(sound, _mute)
    assert set(cell.controls()) == {"accepted_corrupt", "truncated_digest", "wrong_sender",
                                    "one_call_short"}
    cell.controls()["one_call_short"](seen)
    short = cell.compare(seen)
    assert _values(short)["blocks_not_over_the_mesh"] == 1 and not judge(short, _mute)


def test_with_the_threshold_lowered_every_block_has_to_come_back_over_the_mesh(monkeypatch, capsys):
    import jax

    ndev = len(jax.devices())
    assert ndev == 8, "conftest pins eight virtual CPU devices"
    monkeypatch.setenv("FISCO_DEVICE_SHARD_MIN", "8")
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")  # the CPU backend's rule is the native loop
    cell = _cell(devices=ndev)
    seen = _drive(cell)
    said = capsys.readouterr().err
    assert "over 8 (4 lanes a device)" in said
    phases = said.split("mesh leg phases, ms per block: ")[1].splitlines()[0]
    assert [p for p in ("marshal", "place", "enqueue", "sync", "unpack") if f"'{p}'" in phases] \
        == ["marshal", "place", "enqueue", "sync", "unpack"]
    assert cell.mesh_expected and cell.window_blocks >= 1
    assert seen["mesh_calls"] == cell.window_blocks
    sound = cell.compare(seen)
    assert set(_values(sound).values()) == {0} and judge(sound, _mute)
    ctx = types.SimpleNamespace(cell=cell, red=None)
    assert manifest.reader_of("mesh_lane_share")(ctx) == 100.0
    assert manifest.reader_of("shard_place_ms_per_block")(ctx) > 0.0
    for name, degrade in cell.controls().items():
        degraded = cell.observe()
        degrade(degraded)
        assert not judge(cell.compare(degraded), _mute), name
    # under a configuration of another mesh size none of these calls counts
    assert mesh_counters.mesh_calls(cell.mesh0, cell.mesh1, "admission", 4) == 0


def test_a_block_the_host_answered_for_is_not_correct(monkeypatch):
    """The rule says mesh, and the batch goes to the host loop all the same
    (here: the CPU backend's policy; on a chip: the breaker's fallback): every
    lane is right, and the cell is not correct."""
    monkeypatch.setenv("FISCO_DEVICE_SHARD_MIN", "8")
    monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
    cell = _cell(devices=8)
    seen = _drive(cell)
    got = _values(cell.compare(seen))
    assert cell.mesh_expected and seen["mesh_calls"] == 0
    assert got.pop("blocks_not_over_the_mesh") == cell.window_blocks >= 1
    assert set(got.values()) == {0}
    assert not judge(cell.compare(seen), _mute)


def test_a_program_without_the_counter_leaves_at_once(monkeypatch, capsys):
    from fisco_bcos_tpu.observability.device import CompileLedger

    monkeypatch.delattr(CompileLedger, "note_mesh_call")
    monkeypatch.setattr(manifest, "traffic_of", manifest.tiny_traffic_of)
    args = run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.3", "--trace", "0"])
    with pytest.raises(SystemExit) as e:
        run.run(args, require_chip=False, out=io.StringIO())
    assert e.value.code == run.RC_NO_PROGRAM
    assert "does not count its mesh calls" in capsys.readouterr().err
