"""The admission body's lane plan (``ops/limb.lane_plan``) and its tiles
(``crypto/admission._in_tiles``).

The plan is a pure function of the lane count, so it is held over every
bucket of the one-chip ladder and every share a mesh of 2, 4 and 8 gives:
the tiles cover the lanes, the pad lanes are fewer than one tile, and the
shapes of the six cells that must not move (512, 1,024 and 10,240 lanes) are
one tile in the form they had. The tiling itself runs through the internal
entry that takes the tile as an argument, at a size the CPU compiles: 40
lanes in tiles of 16, so the eight pad lanes fall in the last tile, with the
broken lanes (r = 0, s = 0, r = n, s = n) on a tile's first and last lane and
on the pads' neighbour; the answer is the whole body's byte for byte, and the
plain references' (``benchmark/refcrypto.py``, ``benchmark/refsm.py``) lane
for lane."""

import jax
import numpy as np
import pytest

from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.ops import limb
from fisco_bcos_tpu.ops.hash_common import bucket_ladder
from test_admission_mesh_leg import _secp_case, _sm_case

LADDER = [512, 1024, 2048, 4096, 6144, 8192, 10240]  # bucket_ladder(10240) from 512 up


def _lane_counts():
    counts = set()
    for bucket in LADDER:
        counts.add(bucket)
        counts.update(bucket // mesh for mesh in (2, 4, 8) if bucket % mesh == 0)
    return sorted(counts)


def test_the_ladder_the_plans_are_held_over_is_the_programs_own(monkeypatch):
    monkeypatch.delenv("FISCO_TEST_BUCKET", raising=False)
    ladder = bucket_ladder(10240)
    assert [b for b in ladder if b >= 512] == LADDER
    assert all(limb.lane_plan(b) == limb.LanePlan(b, False) for b in ladder if b < 512)


@pytest.mark.parametrize("lanes", _lane_counts())
def test_a_plan_covers_its_lanes_with_less_than_a_tile_of_pads(lanes):
    plan = limb.lane_plan(lanes)
    tiles = plan.tiles(lanes)
    assert tiles >= 1 and tiles * plan.tile >= lanes
    assert tiles * plan.tile - lanes < plan.tile
    assert plan.tile % limb.LANES == 0 or tiles == 1
    # a tile is run as the rule runs that many lanes on their own: one table
    assert limb.lane_plan(plan.tile) == limb.LanePlan(plan.tile, plan.dense)


@pytest.mark.parametrize("lanes,dense", [(512, False), (1024, False), (10240, True)])
def test_the_shapes_of_the_six_cells_that_must_not_move_are_one_tile_as_before(lanes, dense):
    assert limb.lane_plan(lanes) == limb.LanePlan(lanes, dense)
    x = jax.ShapeDtypeStruct((lanes, 16), np.uint32)
    want = (16, lanes // 128, 128) if dense else (16, lanes)
    assert jax.eval_shape(limb.lane_dense, x).shape == want


def test_the_four_chip_shard_is_planned_in_tiles():
    plan = limb.lane_plan(10240 // 4)
    assert plan == limb.LanePlan(1280, True) and plan.tiles(2560) == 2
    assert admission._SECP.plan(2560) == plan
    # no other bucket or share of the ladder is: their fastest measured row is whole
    assert [n for n in _lane_counts() if limb.lane_plan(n).tiles(n) > 1] == [2560]
    # and the SM body has no cheap size to tile to
    assert all(admission._SM.plan(n) == limb.whole_plan(n) for n in _lane_counts())
    assert admission._SM.plan(2560) == limb.LanePlan(2560, True)


# lane -> what is wrong with it: the first tile's first and last lane, the
# second tile's first and last, the last tile's first, and the batch's last
# lane, which the eight pad lanes repeat
LANES, TILE = 40, 16
BROKEN = {0: "r = 0", 15: "s = 0", 16: "r = n", 31: "s = n", 32: "s = 0", 39: "r = n"}


@pytest.mark.parametrize("case,body,whole", [
    (_secp_case, admission._SECP, admission._admission_whole),
    (_sm_case, admission._SM, admission._sm_admission_packed),
], ids=["secp256k1_keccak256", "sm2_sm3"])
def test_tiles_answer_as_the_whole_body_and_the_plain_reference(case, body, whole):
    payloads, sigs, want = case(LANES, BROKEN)
    assert sum(not w[0] for w in want) == len(BROKEN)
    operands = tuple(o[:LANES] for o in body.marshal(payloads, sigs, 64))
    assert all(o.shape[0] == LANES for o in operands)
    as_whole = np.asarray(jax.jit(whole)(*operands))
    in_tiles = np.asarray(
        jax.jit(lambda *ops: admission._in_tiles(whole, TILE, *ops))(*operands))
    assert in_tiles.shape == (LANES, 117)  # the pad lanes are cut off
    assert (in_tiles == as_whole).all()
    for i, (w_ok, w_sender, w_pub, w_digest) in enumerate(want):
        row = in_tiles[i]
        assert bool(row[20]) == w_ok == (i not in BROKEN), i
        assert bytes(row[85:117]) == w_digest, i  # a refused lane owes its digest
        if w_ok:
            assert bytes(row[:20]) == w_sender and bytes(row[21:85]) == w_pub, i


def test_one_tile_is_the_whole_body_with_no_loop_around_it():
    spec = [jax.ShapeDtypeStruct(shape, dtype)
            for shape, dtype in admission.PROGSPEC["_admission_packed"]["inputs"](32)]

    def loops(fn):
        return sum(e.primitive.name in ("scan", "while")
                   for e in jax.make_jaxpr(fn)(*spec).jaxpr.eqns)

    whole = admission._admission_whole
    assert loops(lambda *o: admission._in_tiles(whole, 32, *o)) == loops(whole)
    assert loops(lambda *o: admission._in_tiles(whole, 64, *o)) == loops(whole)
    assert loops(lambda *o: admission._in_tiles(whole, 16, *o)) == 1  # the loop over the tiles
    assert loops(admission._admission_packed) == loops(whole)  # 32 lanes: one tile


def _tool():
    """tool/admission_op_profile.py: ``--lanes`` names the plans the table measures."""
    import sys

    if "tool" not in sys.path:
        sys.path.insert(0, "tool")
    import admission_op_profile

    return admission_op_profile


@pytest.mark.parametrize("shape,want", [
    ("1000", ("secp", 1000, 1, False, None, None)),
    ("sm:10000", ("sm", 10000, 1, False, None, None)),
    ("10000/4", ("secp", 10000, 4, False, None, None)),
    ("10000/4@1280", ("secp", 10000, 4, True, 1280, None)),
    ("10000/4@rows", ("secp", 10000, 4, True, None, False)),
    ("10000/4@dense", ("secp", 10000, 4, True, None, True)),
    ("sm:10000/4@1024rows", ("sm", 10000, 4, True, 1024, False)),
    ("10000@2560dense", ("secp", 10000, 1, True, 2560, True)),
])
def test_the_profiles_lanes_grammar_names_a_plan(shape, want):
    spec = _tool().parse_shape(shape)
    assert tuple(spec[k] for k in ("suite", "n", "shards", "planned", "tile", "dense")) == want


@pytest.mark.parametrize("shape", ["10000/4@", "10000@fast", "sm2:10000", "10000/", "@1280", ""])
def test_the_profiles_lanes_grammar_refuses_what_it_cannot_read(shape):
    with pytest.raises(ValueError):
        _tool().parse_shape(shape)
