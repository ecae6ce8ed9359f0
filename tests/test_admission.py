"""Fused admission step + device address derivation + sharded verification."""

import time

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import admission
from fisco_bcos_tpu.crypto.ref import ecdsa as ref
from fisco_bcos_tpu.crypto.ref.keccak import keccak256
from fisco_bcos_tpu.ops import bigint


def _signed(payloads):
    sigs = []
    pubs = []
    for i, p in enumerate(payloads):
        d = 0xA11CE + 31337 * i
        r, s, v = ref.ecdsa_sign(keccak256(p), d)
        sigs.append(r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v]))
        pubs.append(ref.privkey_to_pubkey(ref.SECP256K1, d))
    return np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(-1, 65).copy(), pubs


def test_digest_words_to_limbs_roundtrip():
    rng = np.random.default_rng(7)
    digests = rng.integers(0, 256, size=(5, 32), dtype=np.uint8)
    import jax.numpy as jnp

    words_le = np.ascontiguousarray(digests).view("<u4").astype(np.uint32)
    got = np.asarray(bigint.digest_words_le_to_limbs(jnp.asarray(words_le)))
    np.testing.assert_array_equal(got, bigint.bytes_be_to_limbs(digests))

    words_be = np.ascontiguousarray(digests).view(">u4").astype(np.uint32)
    got = np.asarray(bigint.digest_words_be_to_limbs(jnp.asarray(words_be)))
    np.testing.assert_array_equal(got, bigint.bytes_be_to_limbs(digests))


# admit_batch dispatches native-vs-device by batch size and backend
# (device.dispatch.use_native_batch); both legs must satisfy the same contract
@pytest.fixture(params=["native", "device"])
def admit_path(request, monkeypatch):
    if request.param == "device":
        monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    else:
        monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
        from fisco_bcos_tpu import native_bind

        if native_bind.load() is None:
            pytest.skip("native library unavailable; native leg not testable")
    return request.param


def test_admission_matches_cpu_reference(admit_path):
    payloads = [b"tx %d " % i + b"z" * (i * 37 % 200) for i in range(6)]
    sigs, pubs = _signed(payloads)
    addr, ok, pubs_dev, hashes_dev = admission.admit_batch(payloads, sigs)
    assert ok.all()
    for j, (x, y) in enumerate(pubs):
        pub_bytes = x.to_bytes(32, "big") + y.to_bytes(32, "big")
        assert bytes(pubs_dev[j]) == pub_bytes
        assert bytes(addr[j]) == keccak256(pub_bytes)[12:]
        assert bytes(hashes_dev[j]) == keccak256(payloads[j])


def test_admission_native_device_bit_identity(monkeypatch):
    """The two admit_batch legs must agree bit-for-bit on every output for
    valid lanes, and on the ok mask everywhere — a divergence would fork
    consensus between a CPU-routed node and a TPU-routed node."""
    from fisco_bcos_tpu import native_bind

    if native_bind.load() is None:
        pytest.skip("native library unavailable")
    payloads = [b"bit-identity %d" % i for i in range(5)]
    sigs, _ = _signed(payloads)
    sigs[3, 32:64] = 0  # one malformed lane
    monkeypatch.delenv("FISCO_FORCE_DEVICE_ADMISSION", raising=False)
    nat = admission._admit_batch_native(payloads, sigs)
    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    dev = admission.admit_batch(payloads, sigs)
    np.testing.assert_array_equal(nat[1], dev[1])  # ok mask
    for lane in np.flatnonzero(nat[1]):
        assert bytes(nat[0][lane]) == bytes(dev[0][lane])  # sender
        assert bytes(nat[2][lane]) == bytes(dev[2][lane])  # pubkey
        assert bytes(nat[3][lane]) == bytes(dev[3][lane])  # tx hash


def test_admission_rejects_corruption():
    # ECDSA recover succeeds for almost any well-formed (r, s) — like the
    # reference's recover path, corruption shows up as a *different* recovered
    # sender, not a hard failure (unless the candidate x is off-curve).
    payloads = [b"corrupt me", b"leave me alone"]
    sigs, pubs = _signed(payloads)
    x, y = pubs[0]
    honest_addr = keccak256(x.to_bytes(32, "big") + y.to_bytes(32, "big"))[12:]
    sigs[0, 5] ^= 0xFF  # flip a byte of r
    addr, ok, _, _ = admission.admit_batch(payloads, sigs)
    assert (not ok[0]) or bytes(addr[0]) != honest_addr
    assert ok[1]
    # malformed: s = 0 must hard-fail range checks
    sigs[1, 32:64] = 0
    _, ok, _, _ = admission.admit_batch(payloads, sigs)
    assert not ok[1]


def _limb_operand_from_ints(rows, bb):
    """What an operand is, said with Python integers: row i the 16 limbs of
    the big-endian value, zero rows behind the batch."""
    out = np.zeros((bb, 16), dtype=np.uint32)
    out[: len(rows)] = bigint.ints_to_limbs(int.from_bytes(bytes(r), "big") for r in rows)
    return out


def test_marshal_builds_the_operands_python_integers_give():
    """A batch that is no bucket size: blocks and nblocks are pad_keccak's, r
    and s are the signature's two big-endian values as limbs and v its last
    byte, each bucket-sized with zero rows behind the batch, dtype and shape
    those the program was compiled for."""
    from fisco_bcos_tpu.ops.hash_common import bucket_batch, pad_keccak

    n = 5
    bb = bucket_batch(n)
    assert bb > n
    sigs = np.random.default_rng(29).integers(0, 256, (n, 65), dtype=np.uint8)
    sigs[0, :64] = 0xFF  # every limb at its largest
    sigs[1, :64] = 0
    payloads = [b"marshal %d " % i + b"m" * (i * 53) for i in range(n)]
    blocks, nblocks, r, s, v = admission._marshal_secp(payloads, sigs, bb)
    want_blocks, want_nblocks = pad_keccak(payloads)
    np.testing.assert_array_equal(blocks, want_blocks)
    np.testing.assert_array_equal(nblocks, want_nblocks)
    for got, lo in ((r, 0), (s, 32)):
        assert got.dtype == np.uint32 and got.shape == (bb, 16) and got.flags.c_contiguous
        np.testing.assert_array_equal(got, _limb_operand_from_ints(sigs[:, lo : lo + 32], bb))
    assert v.dtype == np.int32 and v.shape == (bb,)
    assert v[:n].tolist() == [int(x) for x in sigs[:, 64]] and not v[n:].any()


def test_graft_entry_single_chip():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    addr, ok, *_rest = fn(*args)
    assert np.asarray(ok).all()
    assert addr.shape == (128, 20)


def test_graft_entry_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_device_leg_phases_are_measured_and_tile_the_span(monkeypatch):
    """The admission call's phases are ``marshal`` / ``enqueue`` / ``sync`` /
    ``unpack``, live spans with timestamps read from the clock, in that
    order, that tile the ``device.admission`` span to within 5 %; ``/device``
    ``phase_ms`` holds only them (and the ledger's compile on a first call):
    no remainder, no ``execute``."""
    from fisco_bcos_tpu.observability import TRACER
    from fisco_bcos_tpu.observability.device import LEDGER

    monkeypatch.setenv("FISCO_FORCE_DEVICE_ADMISSION", "1")
    payloads = [b"phase-%d " % i + b"q" * (i * 11 % 90) for i in range(6)]
    sigs, _ = _signed(payloads)
    admission.admit_batch(payloads, sigs)  # the shape's first call may compile
    # by the spans' own clock and not by the ring's length: a worker whose
    # earlier files filled the ring (65,536 records) keeps that length
    t_mark = time.perf_counter()
    _addr, ok, _pubs, _hashes = admission.admit_batch(payloads, sigs)
    assert ok.all()
    mine = [r for r in TRACER.spans()
            if r.ts >= t_mark and r.name.startswith("device.admission")]
    by_name = {r.name: r for r in mine}
    order = ["marshal", "enqueue", "sync", "unpack"]
    assert set(by_name) == {"device.admission"} | {f"device.admission.{p}" for p in order}
    span = by_name["device.admission"]
    phases = [by_name[f"device.admission.{p}"] for p in order]
    cursor = span.ts
    for ph in phases:
        assert ph.parent_id == span.span_id and not ph.derived
        assert cursor <= ph.ts <= ph.ts + ph.dur <= span.ts + span.dur
        cursor = ph.ts + ph.dur
    assert sum(ph.dur for ph in phases) >= 0.95 * span.dur
    # the plane's dispatch op is "admission" too: its queue segment sits beside
    totals = LEDGER.phase_totals()["admission"]
    assert set(order) <= set(totals) <= set(order) | {"compile", "queue"}
