"""Plain reference for block sync's `correct`: what a replica that catches up
owes for a backlog of downloaded blocks, worked out from their bytes alone.

Given the backlog as the serving peers encode it (header with the committee's
signatures, wire transactions) and the committee's public keys, it says for
every block whether the header carries a quorum of the committee's
signatures, whether plain secp256k1 / keccak256 (``refcrypto.py``; on a
national-crypto chain plain SM2 / SM3, ``refsm.py``) admit every transaction,
what each transaction's hash and sender are, and replays DagTransfer
``userAdd`` into a dict. It imports the two plain references and nothing of
the program: the byte layouts below are written out again from the wire
format (little-endian lengths, ``codec/flat.py``), not taken from it.

Slow on purpose: a recovery is two scalar multiplications in Python integers.
The tests judge whole tiny backlogs; the benchmark cell replays every
transaction's ``userAdd`` (parsing only) and judges a seeded sample."""

from __future__ import annotations

import struct

from benchmark import refcrypto, refsm

DAG_TRANSFER = bytes.fromhex("000000000000000000000000000000000000100c")
USER_ADD = b"userAdd(string,uint256)"


# -- the two suites -------------------------------------------------------------


def _secp_recover(digest: bytes, sig65: bytes) -> bytes | None:
    """The public key a 65-byte r ‖ s ‖ v names, or None where plain ECDSA
    recovers none: r or s outside [1, n), v outside 0..3, r (+ n) no abscissa
    of the curve, or the point at infinity."""
    P, N = refcrypto.P, refcrypto.N
    r, s, v = int.from_bytes(sig65[:32], "big"), int.from_bytes(sig65[32:64], "big"), sig65[64]
    if not (0 < r < N and 0 < s < N) or v > 3:
        return None
    x = r + N * (v >> 1)
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    if (y & 1) != (v & 1):
        y = P - y
    z = int.from_bytes(digest, "big")
    w = pow(r, -1, N)
    q = refcrypto._add(
        refcrypto._mul(s * w % N, (x, y)), refcrypto._mul(-z * w % N, refcrypto.G)
    )
    if q is None:
        return None
    return q[0].to_bytes(32, "big") + q[1].to_bytes(32, "big")


class Secp:
    """secp256k1 + keccak256: 65-byte signatures, the key recovered."""

    sig_len = 65
    hash = staticmethod(refcrypto.keccak256)

    @staticmethod
    def admit(data: bytes, sig: bytes):
        """-> (admitted, hash, sender) of one transaction's signed bytes."""
        digest = refcrypto.keccak256(data)
        pub = _secp_recover(digest, sig) if len(sig) == 65 else None
        if pub is None or not refcrypto.verify(digest, sig, pub):
            return False, digest, b""
        return True, digest, refcrypto.address(pub)

    @staticmethod
    def signed_by(digest: bytes, sig: bytes, pub64: bytes) -> bool:
        return len(sig) == 65 and refcrypto.verify(digest, sig, pub64)


class Sm:
    """SM2 + SM3: 128-byte signatures r ‖ s ‖ pub, the key carried."""

    sig_len = 128
    hash = staticmethod(refsm.sm3)

    @staticmethod
    def admit(data: bytes, sig: bytes):
        if len(sig) != 128:
            return False, refsm.sm3(data), b""
        ok, sender, _pub, digest = refsm.admit(data, sig)
        return ok, digest, sender if ok else b""

    @staticmethod
    def signed_by(digest: bytes, sig: bytes, pub64: bytes) -> bool:
        if len(sig) != 128 or sig[64:] != pub64:
            return False
        pt = (int.from_bytes(pub64[:32], "big"), int.from_bytes(pub64[32:], "big"))
        return refsm.verify(
            digest, int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:64], "big"), pt
        )


# -- the wire format, read again ------------------------------------------------


class _Reader:
    def __init__(self, buf: bytes):
        self.buf, self.off = buf, 0

    def take(self, n: int) -> bytes:
        if self.off + n > len(self.buf):
            raise ValueError("truncated")
        out = self.buf[self.off:self.off + n]
        self.off += n
        return out

    def u32(self) -> int:
        return struct.unpack("<I", self.take(4))[0]

    def i64(self) -> int:
        return struct.unpack("<q", self.take(8))[0]

    def blob(self) -> bytes:
        return self.take(self.u32())


def split_block(raw: bytes) -> tuple[bytes, list[bytes]]:
    """An encoded block -> its encoded header and its wire transactions."""
    r = _Reader(raw)
    header = r.blob()
    return header, [r.blob() for _ in range(r.u32())]


def split_header(header: bytes) -> dict:
    """-> number, parent hash, the three roots, the sealer list, the hash
    preimage and the (index, signature) list."""
    r = _Reader(header)
    preimage = r.blob()
    signatures = [(r.i64(), r.blob()) for _ in range(r.u32())]
    p = _Reader(preimage)
    p.u32()  # version
    parents = [(p.i64(), p.take(32)) for _ in range(p.u32())]
    txs_root, receipts_root, state_root = p.take(32), p.take(32), p.take(32)
    number = p.i64()
    p.take(8 + 8 + 8)  # gas used, timestamp, proposer
    sealers = [p.blob() for _ in range(p.u32())]
    return {
        "number": number, "parent": parents[0][1] if parents else None,
        "txs_root": txs_root, "receipts_root": receipts_root, "state_root": state_root,
        "sealers": sealers, "preimage": preimage, "signatures": signatures,
    }


def split_tx(wire: bytes) -> tuple[bytes, bytes]:
    """A wire transaction -> its signed bytes and its signature."""
    r = _Reader(wire)
    return r.blob(), r.blob()


def call_of(data: bytes) -> tuple[bytes, bytes]:
    """A transaction's signed bytes -> (to, input)."""
    r = _Reader(data)
    r.u32()  # version
    r.blob(), r.blob()  # chain, group
    r.i64()  # block limit
    r.blob()  # nonce
    return r.blob(), r.blob()


def user_add_of(data: bytes, suite) -> tuple[str, int] | None:
    """(user, amount) where the transaction calls DagTransfer
    ``userAdd(string,uint256)``, else None."""
    to, call = call_of(data)
    if to != DAG_TRANSFER or call[:4] != suite.hash(USER_ADD)[:4]:
        return None
    args = call[4:]
    at = int.from_bytes(args[:32], "big")
    amount = int.from_bytes(args[32:64], "big")
    size = int.from_bytes(args[at:at + 32], "big")
    return args[at + 32:at + 32 + size].decode(), amount


# -- the judgement --------------------------------------------------------------


def quorum_signed(header: bytes, committee: list[bytes], suite) -> bool:
    """The header names exactly the committee (sorted by key, weight 1 each)
    and carries valid signatures of distinct members on its hash, more than
    two thirds of them."""
    h = split_header(header)
    sealers = sorted(committee)
    if h["sealers"] != sealers:
        return False
    digest = suite.hash(h["preimage"])
    seen = set()
    for index, sig in h["signatures"]:
        if index in seen or not 0 <= index < len(sealers):
            return False
        if not suite.signed_by(digest, sig, sealers[index]):
            return False
        seen.add(index)
    return len(seen) >= 2 * len(sealers) // 3 + 1


def replay_user_add(wire_txs: list[bytes], suite, balances: dict[str, int]) -> None:
    """``userAdd`` as the precompiled contract defines it: the first write of
    a user wins, a second one changes nothing."""
    for wire in wire_txs:
        call = user_add_of(split_tx(wire)[0], suite)
        if call is not None:
            balances.setdefault(call[0], call[1])


def judge(backlog: list[bytes], committee: list[bytes], suite=Secp) -> dict:
    """The whole backlog, block by block as a replica must take it: a block
    is applied when its header is quorum-signed, it continues the chain and
    every transaction of it is admitted; the first block that is not ends the
    catch-up (sync applies nothing past it). -> per block ``number``,
    ``qc``, ``admits``, ``hashes``, ``senders``, ``applied``; and the
    balances and height after the applied prefix."""
    blocks, balances = [], {}
    going, parent, height = True, None, None
    for raw in backlog:
        header, txs = split_block(raw)
        h = split_header(header)
        answers = [suite.admit(*split_tx(wire)) for wire in txs]
        row = {
            "number": h["number"],
            "qc": quorum_signed(header, committee, suite),
            "admits": all(a[0] for a in answers),
            "hashes": [a[1] for a in answers],
            "senders": [a[2] for a in answers],
            "state_root": h["state_root"],
        }
        linked = parent is None or (h["parent"] == parent and h["number"] == height + 1)
        going = going and row["qc"] and row["admits"] and linked
        row["applied"] = going
        if going:
            replay_user_add(txs, suite, balances)
            parent, height = suite.hash(h["preimage"]), h["number"]
        blocks.append(row)
    return {"blocks": blocks, "balances": balances, "height": height}
