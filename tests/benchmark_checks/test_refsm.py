"""``benchmark/refsm.py`` against the standards' own examples, and against
``crypto/ref/`` as a second, independent writing of the same standards (the
program's host leg: neither imports the other).

The examples are written here from memory of the standards (GB/T 32905-2016
Annex A; GB/T 32918.2-2016 Annex A, the recommended curve) and could not be
checked against a copy offline; that two independent writings reproduce every
one of them, down to r and s under the standard's k, is the check there is."""

import random

import pytest

from benchmark import refsm
from fisco_bcos_tpu.crypto.ref import ecdsa as ref
from fisco_bcos_tpu.crypto.ref.sm3 import sm3 as ref_sm3

SM3_EXAMPLES = [
    (b"abc", "66c7f0f462eeedd9d1f2d46bdc10e4e24167c4875cf2f7a2297da02b8f4ba8e0"),
    (b"abcd" * 16, "debe9ff92275b8a138604889c18e5a4d6fdb70e5387e5765293dcba39c0c5732"),
]
# GB/T 32918.2-2016 Annex A: M = "message digest", ID = "1234567812345678"
D_A = 0x3945208F7B2144B13F36E38AC6D39F95889393692860B51A42FB81EF4DF7C5B8
X_A = 0x09F9DF311E5421A150DD7D161E4BC5C672179FAD1833FC076BB08FF356F35020
Y_A = 0xCCEA490CE26775A52DC6EA718CC1AA600AED05FBF35E084A6632F6072DA9AD13
Z_A = "b2e14c5c79c6df5b85f4fe7ed8db7a262b9da7e07ccb0ea9f4747b8ccda8a4f3"
E = 0xF0B43E94BA45ACCAACE692ED534382EB17E6AB5A19CE7B31F4486FDFC0D28640
K = 0x59276E27D506861A16680F3AD9C02DCCEF3CC1FA3CDBE4CE6D54B80DEAC1BC21
R = 0xF5A03B0648D2C4630EEAC513E1BB81A15944DA3827D5B74143AC7EACEEE720B3
S = 0xB1B6AA29DF212FD8763182BC0D421CA1BB9038FD1F7F42D4840B69C485BBC1AA
MESSAGE = b"message digest"


@pytest.mark.parametrize("message,digest", SM3_EXAMPLES, ids=["abc", "64_bytes"])
def test_sm3_examples_of_the_standard(message, digest):
    assert refsm.sm3(message).hex() == digest
    assert ref_sm3(message).hex() == digest


@pytest.mark.parametrize("n", [0, 1, 55, 56, 63, 64, 65, 119, 120, 210, 1000])
def test_sm3_padding_edges_agree_with_the_other_writing(n):
    message = bytes(random.Random(n).randrange(256) for _ in range(n))
    assert refsm.sm3(message) == ref_sm3(message)


def test_curve_is_the_recommended_one():
    c = ref.SM2_CURVE
    assert (refsm.P, refsm.A, refsm.B, refsm.N, *refsm.G) == (c.p, c.a, c.b, c.n, c.gx, c.gy)
    assert refsm.on_curve(refsm.G) and refsm._mul(refsm.N, refsm.G) is None
    assert refsm.DEFAULT_ID == ref.SM2_DEFAULT_ID == b"1234567812345678"


def test_annex_a_key_za_and_e():
    pub = refsm.pubkey(D_A)
    assert pub == (X_A, Y_A) == ref.privkey_to_pubkey(ref.SM2_CURVE, D_A)
    assert refsm.za(pub).hex() == Z_A == ref.sm2_za(pub).hex()
    assert refsm.e_of(pub, MESSAGE) == E == ref.sm2_e(MESSAGE, pub)


def test_annex_a_signature_under_the_standards_k():
    assert refsm.sign(MESSAGE, D_A, k=K) == (R, S)
    assert refsm.verify(MESSAGE, R, S, (X_A, Y_A))
    assert ref.sm2_verify(MESSAGE, R, S, (X_A, Y_A))


@pytest.mark.parametrize("seed", range(4))
def test_each_writing_verifies_what_the_other_signs(seed):
    rng = random.Random(seed)
    d = rng.randrange(1, refsm.N)
    digest = refsm.sm3(b"cross-check %d" % seed)
    pub = refsm.pubkey(d)
    assert pub == ref.privkey_to_pubkey(ref.SM2_CURVE, d)
    r, s = refsm.sign(digest, d)
    assert (r, s) == refsm.sign(digest, d), "the same inputs sign the same way"
    assert ref.sm2_verify(digest, r, s, pub)
    r2, s2 = ref.sm2_sign(digest, d)
    assert refsm.verify(digest, r2, s2, pub)
    assert refsm.address(refsm.pubkey_bytes(d)) == ref_sm3(refsm.pubkey_bytes(d))[12:]


@pytest.mark.parametrize("what", ["r=0", "s=0", "r=n", "s=n", "t=0", "off_curve", "other_key",
                                  "other_message"])
def test_verify_rejects(what):
    d = 0x1234567
    pub, other = refsm.pubkey(d), refsm.pubkey(d + 1)
    digest = refsm.sm3(b"reject me")
    r, s = refsm.sign(digest, d)
    case = {
        "r=0": (digest, 0, s, pub), "s=0": (digest, r, 0, pub),
        "r=n": (digest, refsm.N, s, pub), "s=n": (digest, r, refsm.N, pub),
        "t=0": (digest, r, refsm.N - r, pub),
        "off_curve": (digest, r, s, (pub[0] ^ 1, pub[1])),
        "other_key": (digest, r, s, other),
        "other_message": (refsm.sm3(b"another"), r, s, pub),
    }[what]
    assert refsm.verify(digest, r, s, pub)
    assert not refsm.verify(*case)
    assert ref.sm2_verify(*case) is False or what == "off_curve"  # the host leg checks the curve elsewhere


def test_the_chains_conventions():
    d = 0xC0FFEE
    payload = b"a transaction's signed payload"
    sig = refsm.sign_tx(payload, d)
    assert len(sig) == 128 and sig[64:] == refsm.pubkey_bytes(d)
    ok, sender, pub, digest = refsm.admit(payload, sig)
    assert ok and pub == sig[64:] and digest == refsm.sm3(payload)
    assert sender == refsm.sm3(pub)[12:] and len(sender) == 20
    ok, sender, pub, digest = refsm.admit(payload + b"!", sig)
    assert not ok and sender == bytes(20) and pub == bytes(64)
    assert digest == refsm.sm3(payload + b"!")
