"""Native EC core (fisco_native.cpp) vs the pure-Python golden reference.

The native single-item paths are the wedpr-FFI analog (reference:
bcos-crypto/signature/secp256k1/Secp256k1Crypto.cpp:32-136,
signature/sm2/SM2Crypto.cpp:29-91): every PBFT packet and single-tx RPC
admission goes through them, so they must be bit-identical to crypto/ref —
any divergence forks a chain.
"""

import secrets

import pytest

from fisco_bcos_tpu import native_bind
from fisco_bcos_tpu.crypto import suite as suite_mod
from fisco_bcos_tpu.crypto.ref import ecdsa as ref

pytestmark = pytest.mark.skipif(
    native_bind.load() is None, reason="native toolchain unavailable"
)


def _pub_bytes(pub) -> bytes:
    return pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")


def test_secp256k1_sign_verify_recover_identity():
    for _ in range(8):
        d = secrets.randbelow(ref.SECP256K1.n - 1) + 1
        z = secrets.token_bytes(32)
        golden = ref.ecdsa_sign(z, d)
        assert native_bind.secp256k1_sign(z, d) == golden
        r, s, v = golden
        pub = ref.privkey_to_pubkey(ref.SECP256K1, d)
        pb = _pub_bytes(pub)
        assert native_bind.ec_pubkey("secp256k1", d) == pb
        assert native_bind.secp256k1_verify(z, r, s, pb) is True
        assert native_bind.secp256k1_recover(z, r, s, v) == pb
        # v+27 encoding accepted, same as the reference (:106-108)
        assert native_bind.secp256k1_recover(z, r, s, v + 27) == pb


def test_secp256k1_rejects_invalid():
    d = secrets.randbelow(ref.SECP256K1.n - 1) + 1
    z = secrets.token_bytes(32)
    r, s, v = ref.ecdsa_sign(z, d)
    pb = _pub_bytes(ref.privkey_to_pubkey(ref.SECP256K1, d))
    n = ref.SECP256K1.n
    assert native_bind.secp256k1_verify(z, 0, s, pb) is False
    assert native_bind.secp256k1_verify(z, n, s, pb) is False
    assert native_bind.secp256k1_verify(z, r, 0, pb) is False
    assert native_bind.secp256k1_verify(z, r, n + 1, pb) is False
    # off-curve pubkey
    bad = bytearray(pb)
    bad[63] ^= 1
    assert native_bind.secp256k1_verify(z, r, s, bytes(bad)) is False
    # flipped message
    z2 = bytearray(z)
    z2[0] ^= 1
    assert native_bind.secp256k1_verify(bytes(z2), r, s, pb) is False
    assert native_bind.secp256k1_recover(z, r, s, 4) == b""


def test_secp256k1_recover_matches_python_on_mutations():
    d = secrets.randbelow(ref.SECP256K1.n - 1) + 1
    z = secrets.token_bytes(32)
    r, s, v = ref.ecdsa_sign(z, d)
    for v_try in range(4):
        golden = ref.ecdsa_recover(z, r, s, v_try)
        native = native_bind.secp256k1_recover(z, r, s, v_try)
        if golden is None:
            assert native == b""
        else:
            assert native == _pub_bytes(golden)


def test_sm2_sign_verify_identity():
    for _ in range(4):
        d = secrets.randbelow(ref.SM2_CURVE.n - 1) + 1
        pub = ref.privkey_to_pubkey(ref.SM2_CURVE, d)
        pb = _pub_bytes(pub)
        msg = secrets.token_bytes(32)
        e = ref.sm2_e(msg, pub).to_bytes(32, "big")
        assert native_bind.sm2_sign(e, d) == ref.sm2_sign(msg, d)
        r, s = ref.sm2_sign(msg, d)
        assert native_bind.sm2_verify(e, r, s, pb) is True
        assert native_bind.sm2_verify(e, r, (s + 1) % ref.SM2_CURVE.n, pb) is False
        assert native_bind.ec_pubkey("sm2", d) == pb
    # t = (r+s) mod n == 0 rejected
    assert native_bind.sm2_verify(e, 5, ref.SM2_CURVE.n - 5, pb) is False


def test_suite_single_item_paths_use_native_consistently():
    """The CryptoSuite single-item API must give identical bytes whether or
    not the native core is loaded (FISCO_NO_NATIVE covers the other leg in
    test_native.py; here we cross-check suite output against crypto/ref)."""
    for make, curve in (
        (suite_mod.ecdsa_suite, ref.SECP256K1),
        (suite_mod.sm_suite, ref.SM2_CURVE),
    ):
        suite = make()
        kp = suite.signature_impl.generate_keypair(12345678901234567)
        x, y = ref.privkey_to_pubkey(curve, 12345678901234567)
        assert kp.pub == x.to_bytes(32, "big") + y.to_bytes(32, "big")
        msg = bytes(range(32))
        sig = suite.signature_impl.sign(kp, msg)
        if curve is ref.SECP256K1:
            r, s, v = ref.ecdsa_sign(msg, kp.secret)
            assert sig == r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])
        else:
            r, s = ref.sm2_sign(msg, kp.secret)
            assert sig == r.to_bytes(32, "big") + s.to_bytes(32, "big") + kp.pub
        assert suite.signature_impl.verify(kp.pub, msg, sig)
        assert suite.signature_impl.recover(msg, sig) == kp.pub
        bad = bytearray(sig)
        bad[40] ^= 0xFF
        assert not suite.signature_impl.verify(kp.pub, msg, bytes(bad))


def test_native_batch_loops_match_single():
    n = 16
    zs, rs, ss, pubs, vs = b"", b"", b"", b"", b""
    expect = []
    for i in range(n):
        d = secrets.randbelow(ref.SECP256K1.n - 1) + 1
        z = secrets.token_bytes(32)
        r, s, v = ref.ecdsa_sign(z, d)
        pb = _pub_bytes(ref.privkey_to_pubkey(ref.SECP256K1, d))
        if i % 5 == 4:  # poison lane
            s ^= 1
        zs += z
        rs += r.to_bytes(32, "big")
        ss += s.to_bytes(32, "big")
        pubs += pb
        vs += bytes([v])
        expect.append(ref.ecdsa_verify(z, r, s, ref.privkey_to_pubkey(ref.SECP256K1, d)))
    got = native_bind.secp256k1_verify_batch(zs, rs, ss, pubs, n)
    assert got == expect
    pubs_out, oks = native_bind.secp256k1_recover_batch(zs, rs, ss, vs, n)
    for i in range(n):
        golden = ref.ecdsa_recover(
            zs[32 * i : 32 * i + 32],
            int.from_bytes(rs[32 * i : 32 * i + 32], "big"),
            int.from_bytes(ss[32 * i : 32 * i + 32], "big"),
            vs[i],
        )
        if golden is None:
            assert not oks[i]
        else:
            assert oks[i] and pubs_out[64 * i : 64 * i + 64] == _pub_bytes(golden)


def test_ed25519_native_identity():
    import hashlib

    from fisco_bcos_tpu.crypto.ref import ed25519 as ref_ed

    for i in range(4):
        seed = hashlib.sha256(b"ned %d" % i).digest()
        msg = b"packet %d" % i
        pub = ref_ed.seed_to_pubkey(seed)
        assert native_bind.ed25519_pubkey(seed) == pub
        sig = ref_ed.sign(seed, msg)
        assert native_bind.ed25519_sign(seed, msg) == sig
        assert native_bind.ed25519_verify(pub, msg, sig) is True
        assert native_bind.ed25519_verify(pub, msg + b"!", sig) is False
    # RFC 8032 §5.1.7 malleability guard: s >= L rejected
    s_big = (int.from_bytes(sig[32:], "little") + ref_ed.L).to_bytes(32, "little")
    assert native_bind.ed25519_verify(pub, msg, sig[:32] + s_big) is False
    # non-canonical compressed y >= P rejected
    assert native_bind.ed25519_verify((ref_ed.P + 1).to_bytes(32, "little"), msg, sig) is False


def test_ed25519_suite_single_item_uses_native():
    import hashlib

    from fisco_bcos_tpu.crypto.ref import ed25519 as ref_ed

    impl = suite_mod.Ed25519Crypto()
    kp = impl.generate_keypair(secret=424242)
    seed = (424242).to_bytes(32, "little")
    assert kp.pub == ref_ed.seed_to_pubkey(seed)
    msg = hashlib.sha256(b"suite-ed").digest()
    sig = impl.sign(kp, msg)
    assert sig == ref_ed.sign(seed, msg) + kp.pub
    assert impl.verify(kp.pub, msg, sig)
    assert impl.recover(msg, sig) == kp.pub
    bad = bytearray(sig)
    bad[5] ^= 1
    assert not impl.verify(kp.pub, msg, bytes(bad))


def test_ed25519_batch_routes_native_and_agrees():
    """QC-sized ed25519 batches must ride the native host loop on CPU
    backends (device.dispatch.use_native_batch — review r5: the XLA program
    re-introduced per-block latency the routing was built to remove) and
    agree with the device-path semantics."""
    import numpy as np

    from fisco_bcos_tpu import native_bind
    from fisco_bcos_tpu.crypto.suite import Ed25519Crypto

    if native_bind.load() is None:
        import pytest

        pytest.skip("native library unavailable")
    impl = Ed25519Crypto()
    kps = [impl.generate_keypair(secret=0xED25 + i) for i in range(4)]
    hashes = [bytes([i]) * 32 for i in range(4)]
    sigs = [impl.sign(kp, h) for kp, h in zip(kps, hashes)]
    pubs = [kp.pub[:32] for kp in kps]
    ok = impl.batch_verify(hashes, pubs, sigs)
    assert bool(np.asarray(ok).all())
    # one corrupted lane lowers only its bit
    bad = list(sigs)
    bad[2] = bytes([bad[2][0] ^ 1]) + bad[2][1:]
    ok2 = np.asarray(impl.batch_verify(hashes, pubs, bad))
    assert list(ok2) == [True, True, False, True]
