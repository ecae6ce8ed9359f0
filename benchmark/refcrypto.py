"""Plain reference crypto for the benchmark's `correct`: keccak256 and
secp256k1 in straightforward Python integers. Imports nothing of the program
under test and takes nothing it has made. Slow on purpose (no tables, no
native code): it checks a few dozen keys and a seeded sample of transactions
after the window, never the bulk."""

from __future__ import annotations

_MASK = (1 << 64) - 1
_RC = []
_r = 1
for _ in range(24):
    rc = 0
    for j in range(7):
        _r = ((_r << 1) ^ ((_r >> 7) * 0x71)) & 0xFF
        if _r & 2:
            rc ^= 1 << ((1 << j) - 1)
    _RC.append(rc)
_ROT = [
    [0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56], [27, 20, 39, 8, 14],
]


def _rol(x: int, n: int) -> int:
    n %= 64
    return ((x << n) | (x >> (64 - n))) & _MASK if n else x


def _keccak_f(a: list[list[int]]) -> None:
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
        a[0][0] ^= rc


def keccak256(data: bytes) -> bytes:
    """Original Keccak padding (0x01), rate 136 — Ethereum's, not SHA3-256."""
    rate = 136
    msg = bytearray(data)
    msg.append(0x01)
    msg.extend(b"\x00" * (-len(msg) % rate))
    msg[-1] |= 0x80
    a = [[0] * 5 for _ in range(5)]
    for off in range(0, len(msg), rate):
        for i in range(rate // 8):
            a[i % 5][i // 5] ^= int.from_bytes(msg[off + 8 * i:off + 8 * i + 8], "little")
        _keccak_f(a)
    return b"".join(a[i % 5][i // 5].to_bytes(8, "little") for i in range(4))


# -- secp256k1 ---------------------------------------------------------------
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    if p[0] == q[0]:
        if (p[1] + q[1]) % P == 0:
            return None
        lam = 3 * p[0] * p[0] * pow(2 * p[1], -1, P) % P
    else:
        lam = (q[1] - p[1]) * pow(q[0] - p[0], -1, P) % P
    x = (lam * lam - p[0] - q[0]) % P
    return x, (lam * (p[0] - x) - p[1]) % P


def _mul(k: int, p):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, p)
        p = _add(p, p)
        k >>= 1
    return acc


def pubkey(secret: int) -> tuple[int, int]:
    return _mul(secret % N, G)


def pubkey_bytes(secret: int) -> bytes:
    x, y = pubkey(secret)
    return x.to_bytes(32, "big") + y.to_bytes(32, "big")


def address(pub64: bytes) -> bytes:
    """right160(keccak256(x ‖ y)) — the chain's account address."""
    return keccak256(pub64)[12:]


def sign(digest: bytes, secret: int) -> bytes:
    """Deterministic (nonce from keccak of secret ‖ digest) 65-byte r‖s‖v,
    low-s. Any valid nonce gives a valid signature; this one repeats."""
    z = int.from_bytes(digest, "big")
    k = int.from_bytes(keccak256(secret.to_bytes(32, "big") + digest), "big") % N or 1
    x, y = _mul(k, G)
    r = x % N
    s = pow(k, -1, N) * (z + r * secret) % N
    v = (y & 1) | (2 if x >= N else 0)
    if s > N // 2:
        s, v = N - s, v ^ 1
    if r == 0 or s == 0:
        raise ValueError("degenerate nonce")
    return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])


def verify(digest: bytes, sig65: bytes, pub64: bytes) -> bool:
    r = int.from_bytes(sig65[:32], "big")
    s = int.from_bytes(sig65[32:64], "big")
    if not (0 < r < N and 0 < s < N):
        return False
    q = (int.from_bytes(pub64[:32], "big"), int.from_bytes(pub64[32:], "big"))
    w = pow(s, -1, N)
    z = int.from_bytes(digest, "big")
    pt = _add(_mul(z * w % N, G), _mul(r * w % N, q))
    return pt is not None and pt[0] % N == r
