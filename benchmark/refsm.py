"""Plain national-crypto reference for ``correct``: SM3 (GB/T 32905) and SM2
signatures over the 256-bit recommended curve (GB/T 32918) in Python integers,
with the chain's conventions (FISCO-BCOS ``sm_crypto=true``,
bcos-crypto signature/sm2/SM2Crypto.cpp): e = SM3(ZA ‖ M) under the default
user id, a 128-byte signature r ‖ s ‖ public key, address = right160(SM3(pub)).

Imports nothing of the program and nothing of ``crypto/ref/`` (the program's
own host leg); ``tests/benchmark_checks/test_refsm.py`` holds it to the
standards' examples and cross-checks it against ``crypto/ref/`` as a second
writing of the same standards."""

from __future__ import annotations

# -- SM3 ------------------------------------------------------------------------

_IV = (0x7380166F, 0x4914B2B9, 0x172442D7, 0xDA8A0600,
       0xA96F30BC, 0x163138AA, 0xE38DEE4D, 0xB0FB0E4E)
_M32 = 0xFFFFFFFF


def _rotl(x: int, n: int) -> int:
    n %= 32
    return ((x << n) | (x >> (32 - n))) & _M32


def _p0(x: int) -> int:
    return x ^ _rotl(x, 9) ^ _rotl(x, 17)


def _p1(x: int) -> int:
    return x ^ _rotl(x, 15) ^ _rotl(x, 23)


def _compress(v: tuple, block: bytes) -> tuple:
    w = [int.from_bytes(block[4 * i:4 * i + 4], "big") for i in range(16)]
    for j in range(16, 68):
        w.append(_p1(w[j - 16] ^ w[j - 9] ^ _rotl(w[j - 3], 15)) ^ _rotl(w[j - 13], 7) ^ w[j - 6])
    a, b, c, d, e, f, g, h = v
    for j in range(64):
        t = 0x79CC4519 if j < 16 else 0x7A879D8A
        a12 = _rotl(a, 12)
        ss1 = _rotl((a12 + e + _rotl(t, j)) & _M32, 7)
        ss2 = ss1 ^ a12
        if j < 16:
            ff, gg = a ^ b ^ c, e ^ f ^ g
        else:
            ff, gg = (a & b) | (a & c) | (b & c), (e & f) | (~e & g & _M32)
        tt1 = (ff + d + ss2 + (w[j] ^ w[j + 4])) & _M32
        tt2 = (gg + h + ss1 + w[j]) & _M32
        a, b, c, d, e, f, g, h = tt1, a, _rotl(b, 9), c, _p0(tt2), e, _rotl(f, 19), g
    return tuple(x ^ y for x, y in zip(v, (a, b, c, d, e, f, g, h)))


def sm3(data: bytes) -> bytes:
    padded = data + b"\x80" + bytes((55 - len(data)) % 64) + (8 * len(data)).to_bytes(8, "big")
    v = _IV
    for i in range(0, len(padded), 64):
        v = _compress(v, padded[i:i + 64])
    return b"".join(x.to_bytes(4, "big") for x in v)


# -- the recommended curve y^2 = x^3 + ax + b over F_p ---------------------------

P = 0xFFFFFFFEFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF00000000FFFFFFFFFFFFFFFF
A = 0xFFFFFFFEFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF00000000FFFFFFFFFFFFFFFC
B = 0x28E9FA9E9D9F5E344D5A9E4BCF6509A7F39789F515AB8F92DDBCBD414D940E93
N = 0xFFFFFFFEFFFFFFFFFFFFFFFFFFFFFFFF7203DF6B21C6052B53BBF40939D54123
G = (0x32C4AE2C1F1981195F9904466A39C9948FE30BBFF2660BE1715A4589334C74C7,
     0xBC3736A2F4F6779C59BDCEE36B692153D0A9877CC62A474002DF32E52139F0A0)
DEFAULT_ID = b"1234567812345678"


def on_curve(pt) -> bool:
    x, y = pt
    return 0 <= x < P and 0 <= y < P and (y * y - (x * x * x + A * x + B)) % P == 0


def _add(p, q):
    """Affine addition; None is the point at infinity."""
    if p is None:
        return q
    if q is None:
        return p
    (x1, y1), (x2, y2) = p, q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = (3 * x1 * x1 + A) * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _mul(k: int, pt):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def _b32(x: int) -> bytes:
    return x.to_bytes(32, "big")


# -- SM2 signatures (GB/T 32918.2) ------------------------------------------------


def pubkey(secret: int):
    return _mul(secret, G)


def pubkey_bytes(secret: int) -> bytes:
    x, y = pubkey(secret)
    return _b32(x) + _b32(y)


def za(pub, user_id: bytes = DEFAULT_ID) -> bytes:
    """ZA = SM3(ENTL ‖ ID ‖ a ‖ b ‖ Gx ‖ Gy ‖ Px ‖ Py)."""
    entl = (8 * len(user_id)).to_bytes(2, "big")
    return sm3(entl + user_id + b"".join(_b32(v) for v in (A, B, *G, *pub)))


def e_of(pub, message: bytes, user_id: bytes = DEFAULT_ID) -> int:
    return int.from_bytes(sm3(za(pub, user_id) + message), "big")


def sign(message: bytes, secret: int, k: int | None = None, user_id: bytes = DEFAULT_ID):
    """-> (r, s). ``k`` is the standard's per-signature secret; left out, it is
    derived from the secret and e, so the same inputs sign the same way."""
    e = e_of(pubkey(secret), message, user_id)
    counter = 0
    while True:
        kk = k if k is not None else int.from_bytes(
            sm3(_b32(secret) + _b32(e) + counter.to_bytes(4, "big")), "big") % N
        counter += 1
        if kk == 0:
            continue
        x1, _y1 = _mul(kk, G)
        r = (e + x1) % N
        if r == 0 or r + kk == N:
            if k is not None:
                raise ValueError("sm2: this k gives no signature")
            continue
        s = pow(1 + secret, -1, N) * (kk - r * secret) % N
        if s == 0:
            if k is not None:
                raise ValueError("sm2: this k gives no signature")
            continue
        return r, s


def verify(message: bytes, r: int, s: int, pub, user_id: bytes = DEFAULT_ID) -> bool:
    if not (1 <= r < N and 1 <= s < N) or not on_curve(pub):
        return False
    t = (r + s) % N
    if t == 0:
        return False
    pt = _add(_mul(s, G), _mul(t, pub))
    return pt is not None and (e_of(pub, message, user_id) + pt[0]) % N == r


# -- the chain's conventions ------------------------------------------------------


def sign_tx(payload: bytes, secret: int) -> bytes:
    """The 128-byte signature a national-crypto chain carries: r ‖ s ‖ pub,
    over the transaction's SM3 digest as the message."""
    r, s = sign(sm3(payload), secret)
    return _b32(r) + _b32(s) + pubkey_bytes(secret)


def admit(payload: bytes, sig128: bytes):
    """What admission owes for one transaction -> (ok, sender, pub, digest):
    parse the carried key, verify, sender = right160(SM3(pub)). A rejected
    lane keeps its digest and gives zeros for sender and key."""
    digest = sm3(payload)
    r, s = int.from_bytes(sig128[:32], "big"), int.from_bytes(sig128[32:64], "big")
    pub = sig128[64:128]
    pt = (int.from_bytes(pub[:32], "big"), int.from_bytes(pub[32:], "big"))
    if len(sig128) == 128 and verify(digest, r, s, pt):
        return True, address(pub), pub, digest
    return False, bytes(20), bytes(64), digest


def address(pub64: bytes) -> bytes:
    return sm3(pub64)[12:]
