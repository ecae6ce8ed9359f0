"""The fused national-crypto admission (SM3 → e = SM3(ZA ‖ hash) → SM2 verify
of the carried key → address): one device program, held lane for lane to the
benchmark's plain reference (benchmark/refsm.py), to the three-program path
it replaces, and to the host leg."""

import numpy as np
import pytest

from benchmark import refsm
from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.suite import ecdsa_suite, sm_suite
from fisco_bcos_tpu.observability.device import LEDGER

N_LANES = 12
BROKEN = {  # lane -> what is wrong with it
    1: "r = 0", 3: "s = 0", 4: "r = n", 6: "s = n",
    7: "carried key off the curve", 9: "carried key is the neighbour's",
}


def _corpus():
    payloads = [b"sm admission %02d " % i + b"\x5a" * (i * 23 % 150) for i in range(N_LANES)]
    secrets = [0x5A17 + 104729 * i for i in range(N_LANES)]
    sigs = np.frombuffer(
        b"".join(refsm.sign_tx(p, d) for p, d in zip(payloads, secrets)), np.uint8
    ).reshape(N_LANES, 128).copy()
    return payloads, sigs


def _broken(sigs):
    sigs = sigs.copy()
    order = np.frombuffer(refsm.N.to_bytes(32, "big"), np.uint8)
    sigs[1, :32] = 0
    sigs[3, 32:64] = 0
    sigs[4, :32] = order
    sigs[6, 32:64] = order
    sigs[7, 64 + 31] ^= 0x01  # one bit of Px: no longer a point of the curve
    sigs[9, 64:] = sigs[10, 64:]  # a valid point, not the signer's
    return sigs


@pytest.fixture(scope="module")
def corpus():
    return _corpus()


@pytest.fixture
def device_leg(monkeypatch):
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")


def _want(payloads, sigs):
    return [refsm.admit(p, bytes(s)) for p, s in zip(payloads, sigs)]


def _assert_lanes(got, want):
    senders, ok, pubs, digests = got
    for i, (w_ok, w_sender, w_pub, w_digest) in enumerate(want):
        assert bool(ok[i]) == w_ok, i
        assert bytes(senders[i]) == w_sender, i
        assert bytes(pubs[i]) == w_pub, i
        assert bytes(digests[i]) == w_digest, i


def test_reference_rejects_exactly_the_broken_lanes(corpus):
    payloads, sigs = corpus
    assert all(w[0] for w in _want(payloads, sigs))
    bad = _want(payloads, _broken(sigs))
    assert {i for i, w in enumerate(bad) if not w[0]} == set(BROKEN)
    assert not refsm.on_curve(
        tuple(int.from_bytes(bytes(_broken(sigs)[7, lo:lo + 32]), "big") for lo in (64, 96)))


@pytest.mark.parametrize("broken", [False, True], ids=["valid", "six_broken_lanes"])
def test_fused_program_answers_as_the_plain_reference(corpus, device_leg, broken):
    payloads, sigs = corpus
    if broken:
        sigs = _broken(sigs)
    got = admission.admit_batch(payloads, sigs, suite=sm_suite())
    _assert_lanes(got, _want(payloads, sigs))


def test_one_device_program_and_one_dispatch_op(corpus, device_leg):
    """An SM batch is ONE device op (the compile ledger's dispatches hold
    ``admission_sm`` and neither ``sm3`` nor ``sm2_verify``), noted as the
    node's admission."""
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    payloads, sigs = corpus
    admission.admit_batch(payloads, sigs, suite=sm_suite())  # the shape's first call
    LEDGER.reset()
    key = 'fisco_device_dispatch_path_total{op="admission",path="device"}'
    before = REGISTRY.counters_matching(key).get(key, 0)
    admission.admit_batch(payloads, sigs, suite=sm_suite())
    # the plane notes its queue segment under its own op, admission.sm
    ran = [op for op, _t0, _dur, phases in LEDGER.dispatches() if set(phases) - {"queue"}]
    assert ran == ["admission_sm"]
    assert REGISTRY.counters_matching(key)[key] == before + 1
    phases = LEDGER.phase_totals()["admission_sm"]
    assert {"marshal", "enqueue", "sync", "unpack"} <= set(phases)


def test_fused_program_against_the_three_program_path(corpus, device_leg, monkeypatch):
    """hash_batch → batch_recover → calculate_address_batch, the path SM
    batches took before: every lane's validity, key, sender and digest equal."""
    monkeypatch.setenv("FISCO_DEVICE_MIN_BATCH", "0")
    suite = sm_suite()
    payloads, sigs = corpus
    sigs = _broken(sigs)
    senders, ok, pubs, digests = admission.admit_batch(payloads, sigs, suite=suite)
    hs = suite.hash_batch(payloads)
    old_pubs, old_ok = suite.signature_impl.batch_recover(hs, sigs)
    old_senders = suite.calculate_address_batch(old_pubs)
    np.testing.assert_array_equal(ok, old_ok)
    np.testing.assert_array_equal(digests, hs)
    np.testing.assert_array_equal(pubs, old_pubs)
    np.testing.assert_array_equal(senders[ok], old_senders[old_ok])
    assert not senders[~ok].any() and not pubs[~ok].any()


@pytest.mark.parametrize("leg", ["native", "host_loop"])
def test_device_leg_and_host_leg_agree_bit_for_bit(corpus, device_leg, leg):
    from fisco_bcos_tpu import native_bind

    payloads, sigs = corpus
    sigs = _broken(sigs)
    if leg == "native" and native_bind.load() is None:
        pytest.skip("native library unavailable")
    host = admission._admit_batch_host_sm(payloads, sigs, native=leg == "native")
    dev = admission.admit_batch(payloads, sigs, suite=sm_suite())
    for a, b in zip(host, dev):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_marshal_builds_the_operands_python_integers_give(corpus):
    """A batch that is no bucket size: blocks and nblocks are pad_md64's, and
    r, s, Px, Py are the signature's four big-endian values as limbs, each
    bucket-sized with zero rows behind the batch, dtype and shape those the
    program was compiled for."""
    from fisco_bcos_tpu.ops.bigint import ints_to_limbs
    from fisco_bcos_tpu.ops.hash_common import bucket_batch, pad_md64

    payloads, sigs = corpus
    bb = bucket_batch(N_LANES)
    assert bb > N_LANES
    sigs = _broken(sigs)  # zero and order-sized values among the lanes
    blocks, nblocks, *limbs = admission._marshal_sm(payloads, sigs, bb)
    want_blocks, want_nblocks = pad_md64(payloads)
    np.testing.assert_array_equal(blocks, want_blocks)
    np.testing.assert_array_equal(nblocks, want_nblocks)
    assert len(limbs) == 4
    for got, lo in zip(limbs, (0, 32, 64, 96)):
        want = np.zeros((bb, 16), dtype=np.uint32)
        want[:N_LANES] = ints_to_limbs(
            int.from_bytes(bytes(row), "big") for row in sigs[:, lo : lo + 32]
        )
        assert got.dtype == np.uint32 and got.shape == (bb, 16) and got.flags.c_contiguous
        np.testing.assert_array_equal(got, want)


def test_policy_sends_a_cpu_backend_to_the_native_loop(corpus, monkeypatch):
    """Without the pin the CPU backend rides the host loop, like the secp
    body: same answer, dispatch noted as native."""
    from fisco_bcos_tpu import native_bind
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    if native_bind.load() is None:
        pytest.skip("native library unavailable")
    monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
    payloads, sigs = corpus
    key = 'fisco_device_dispatch_path_total{op="admission",path="native"}'
    before = REGISTRY.counters_matching(key).get(key, 0)
    got = admission.admit_batch(payloads, sigs, suite=sm_suite())
    _assert_lanes(got, _want(payloads, sigs))
    assert REGISTRY.counters_matching(key)[key] == before + 1


def test_the_suite_decides_not_the_width(corpus):
    payloads, sigs = corpus
    with pytest.raises(ValueError, match="128-byte signatures"):
        admission.admit_batch(payloads, sigs)  # the default suite signs 65 bytes
    with pytest.raises(ValueError, match="65-byte signatures"):
        admission.admit_batch(payloads, sigs[:, :65], suite=sm_suite())
    assert sm_suite().fused_admission() is not None
    assert ecdsa_suite().fused_admission() is admission.admit_batch
    from fisco_bcos_tpu.crypto.suite import CryptoSuite, Ed25519Crypto, Sha256

    assert CryptoSuite(Sha256(), Ed25519Crypto()).fused_admission() is None
    # the two bodies never merge in the plane
    assert admission._body_of(sm_suite()).plane_op != admission._body_of(None).plane_op
