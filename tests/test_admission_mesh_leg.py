"""The mesh leg of admission as a node on a multi-chip host takes it: through
the public ``admit_batch``, the fan-out threshold lowered so that the test
bucket (32 lanes) clears it on the CPU's forced devices (a mesh of eight, four
lanes a device). The batch is not the bucket's size, so the pad lanes fall on
the last shard only; the broken lanes (r = 0, s = 0, r = n, s = n) sit on
different shards, on a shard's first and last lane. Held lane for lane to the
benchmark's plain references (``benchmark/refcrypto.py``,
``benchmark/refsm.py``); the span carries the five phases in order and the
mesh's size, and the counters move by one call and the batch's lanes. The
secp256k1 body runs twice: a shard one tile, as the rule plans four lanes, and
a shard in two tiles of two lanes (the plan is the test's), as the four-chip
host's 2,560 lanes a chip are planned: the span then says so and the lanes
count as tiled. The SM body runs every shard whole."""

import time

import jax
import numpy as np
import pytest

from benchmark import refcrypto, refsm
from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
from fisco_bcos_tpu.observability import TRACER
from fisco_bcos_tpu.ops import limb
from fisco_bcos_tpu.observability.device import LEDGER, device_doc
from fisco_bcos_tpu.utils.metrics import REGISTRY

N_LANES = 30  # bucket 32: two pad lanes, both on the eighth shard
# lane -> what is wrong with it: first lane of shard 0, last of shard 1,
# first of shard 3, last of shard 5 (four lanes a shard)
BROKEN = {0: "r = 0", 7: "s = 0", 12: "r = n", 23: "s = n"}
PHASES = ["marshal", "place", "enqueue", "sync", "unpack"]


def _break(sigs, order: int, broken):
    sigs = sigs.copy()
    n = np.frombuffer(order.to_bytes(32, "big"), np.uint8)
    for lane, what in broken.items():
        cols = slice(0, 32) if what.startswith("r") else slice(32, 64)
        sigs[lane, cols] = n if what.endswith("n") else 0
    return sigs


def _secp_case(n_lanes=N_LANES, broken=BROKEN):
    payloads = [b"mesh leg secp %02d " % i + b"m" * (i * 17 % 100) for i in range(n_lanes)]
    secrets = [0xC0FFEE + 7919 * i for i in range(n_lanes)]
    digests = [refcrypto.keccak256(p) for p in payloads]
    pubs = [refcrypto.pubkey_bytes(d) for d in secrets]
    sigs = _break(np.frombuffer(
        b"".join(refcrypto.sign(z, d) for z, d in zip(digests, secrets)), np.uint8
    ).reshape(n_lanes, 65), refcrypto.N, broken)
    want = []
    for z, sig, pub in zip(digests, sigs, pubs):
        # the plain check of the signer's key; a lane it rejects owes its digest
        ok = refcrypto.verify(z, bytes(sig), pub)
        want.append((ok, refcrypto.address(pub) if ok else None, pub if ok else None, z))
    return payloads, sigs, want


def _sm_case(n_lanes=N_LANES, broken=BROKEN):
    payloads = [b"mesh leg sm %02d " % i + b"\x5a" * (i * 23 % 100) for i in range(n_lanes)]
    sigs = _break(np.frombuffer(
        b"".join(refsm.sign_tx(p, 0x5A17 + 104729 * i) for i, p in enumerate(payloads)),
        np.uint8,
    ).reshape(n_lanes, 128), refsm.N, broken)
    return payloads, sigs, [refsm.admit(p, bytes(s)) for p, s in zip(payloads, sigs)]


def _lanes(name: str) -> float:
    return REGISTRY.counters_matching(f'fisco_device_items_total{{op="{name}"}}').get(
        f'fisco_device_items_total{{op="{name}"}}', 0.0)


def _mesh_calls(op: str, devices: int) -> float:
    name = f'fisco_device_mesh_calls_total{{op="{op}",devices="{devices}"}}'
    return REGISTRY.counters_matching(name).get(name, 0.0)


def _tiled_lanes(name: str) -> float:
    series = f'fisco_device_tiled_items_total{{op="{name}"}}'
    return REGISTRY.counters_matching(series).get(series, 0.0)


@pytest.mark.parametrize("suite,case,op,tile", [
    (ecdsa_suite, _secp_case, "admission", None), (sm_suite, _sm_case, "admission_sm", None),
    (ecdsa_suite, _secp_case, "admission", 2),
], ids=["secp256k1_keccak256", "sm2_sm3", "secp256k1_keccak256-two_tiles_a_shard"])
def test_admit_batch_over_the_mesh_answers_as_the_plain_reference(
        suite, case, op, tile, monkeypatch):
    ndev = len(jax.devices())
    assert ndev == 8, "conftest pins eight virtual CPU devices"
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")  # the CPU backend's rule is the native loop
    monkeypatch.setenv("FISCO_DEVICE_SHARD_MIN", "8")
    assert admission.mesh_devices(32) == ndev and admission.mesh_devices(4) == 1
    assert limb.lane_plan(4) == limb.LanePlan(4, False)  # the rule: a shard of four lanes whole
    if tile:
        # a shard is more than one tile, as the four-chip host's 2,560 lanes
        # are: the plan of four lanes is the test's, the program is built anew
        # under it, and the two broken lanes that ended a shard end a tile too
        plan = limb.lane_plan
        monkeypatch.setattr(
            limb, "lane_plan",
            lambda lanes: limb.LanePlan(tile, False) if lanes == 4 else plan(lanes))
        monkeypatch.setattr(admission, "_SHARD_CACHE", {})
    payloads, sigs, want = case()
    assert sum(not w[0] for w in want) == len(BROKEN)
    sharded = op + "_sharded"
    lanes0, calls0, tiled0 = _lanes(sharded), _mesh_calls(op, ndev), _tiled_lanes(sharded)
    t_mark = time.perf_counter()
    senders, ok, pubs, digests = admission.admit_batch(payloads, sigs, suite=suite())

    for i, (w_ok, w_sender, w_pub, w_digest) in enumerate(want):
        assert bool(ok[i]) == w_ok == (i not in BROKEN), i
        assert bytes(digests[i]) == w_digest, i
        if w_ok:
            assert bytes(senders[i]) == w_sender and bytes(pubs[i]) == w_pub, i
    assert len(ok) == N_LANES  # the pad lanes of the last shard are cut off

    # one call over the mesh, the batch's lanes, none by the one-chip program
    assert _mesh_calls(op, ndev) - calls0 == 1
    assert _lanes(sharded) - lanes0 == N_LANES
    # a call whose shards are planned in tiles counts its lanes as tiled too
    assert _tiled_lanes(sharded) - tiled0 == (N_LANES if tile else 0)
    mesh = device_doc()["mesh"][op]
    assert mesh["devices"] == ndev and mesh["lanes_per_device"] == 32 // ndev
    assert mesh["calls"] >= 1

    mine = {r.name: r for r in TRACER.spans()
            if r.ts >= t_mark and r.name.startswith(f"device.{sharded}")}
    assert set(mine) == {f"device.{sharded}"} | {f"device.{sharded}.{p}" for p in PHASES}
    span = mine[f"device.{sharded}"]
    assert span.attrs["devices"] == ndev and span.attrs["lanes_per_device"] == 32 // ndev
    assert span.attrs["batch"] == N_LANES
    assert (span.attrs["tiles"], span.attrs["tile_lanes"]) == ((2, 2) if tile else (1, 4))
    cursor = span.ts
    for p in PHASES:
        ph = mine[f"device.{sharded}.{p}"]
        assert ph.parent_id == span.span_id
        assert cursor <= ph.ts <= ph.ts + ph.dur <= span.ts + span.dur
        cursor = ph.ts + ph.dur
    assert set(PHASES) <= set(LEDGER.phase_totals()[sharded])


def test_a_bucket_under_the_threshold_is_no_mesh_call(monkeypatch):
    """The default threshold (4,096 lanes) keeps the test bucket on one
    device: the one-chip program answers and the mesh counter stands still."""
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    monkeypatch.delenv("FISCO_DEVICE_SHARD_MIN", raising=False)
    assert admission.mesh_devices(32) == 1
    payloads, sigs, want = _secp_case()
    before = REGISTRY.counters_matching("fisco_device_mesh_calls_total")
    lanes0 = _lanes("admission")
    _senders, ok, _pubs, _digests = admission.admit_batch(payloads, sigs)
    assert [bool(x) for x in ok] == [w[0] for w in want]
    assert REGISTRY.counters_matching("fisco_device_mesh_calls_total") == before
    assert _lanes("admission") - lanes0 == N_LANES
