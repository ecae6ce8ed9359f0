"""Traffic generator for the admission cell: one block of ``lanes`` signed
payloads after another, closed loop. ``signers`` distinct keys and payloads,
signed by the benchmark's own plain secp256k1 and tiled to the block (the
upstream TPS harness duplicates one signed transaction the same way); the
blocks differ by a seeded rotation of the lanes. Parameters
(``benchmark/traffic/*.json``): ``lanes``, ``signers``, ``rotations``."""

from __future__ import annotations

import random

import numpy as np

from benchmark import refcrypto


class Corpus:
    def __init__(self, traffic: dict, seed: int):
        self.lanes = int(traffic["lanes"])
        self.seed = seed
        signers = int(traffic["signers"])
        rng = random.Random(seed)
        secrets = [rng.randrange(1, refcrypto.N) for _ in range(signers)]
        payloads = [
            b"bench parallel-transfer tx %08x %06d" % (seed & 0xFFFFFFFF, i) + b"\xab" * 64
            for i in range(signers)
        ]
        digests = [refcrypto.keccak256(p) for p in payloads]
        sigs = [refcrypto.sign(d, s) for d, s in zip(digests, secrets)]
        pubs = [refcrypto.pubkey_bytes(s) for s in secrets]
        # what a right admission returns, lane for lane, known by construction
        self.unique = {
            "payload": payloads,
            "sig": np.frombuffer(b"".join(sigs), np.uint8).reshape(signers, 65),
            "sender": np.frombuffer(b"".join(refcrypto.address(p) for p in pubs), np.uint8).reshape(signers, 20),
            "pub": np.frombuffer(b"".join(pubs), np.uint8).reshape(signers, 64),
            "digest": np.frombuffer(b"".join(digests), np.uint8).reshape(signers, 32),
        }
        self.blocks = [
            self._block(rng.randrange(signers)) for _ in range(int(traffic["rotations"]))
        ]

    def _block(self, shift: int) -> dict:
        signers = len(self.unique["payload"])
        idx = (np.arange(self.lanes) + shift) % signers
        return {
            "idx": idx,
            "payloads": [self.unique["payload"][i] for i in idx],
            "sigs": np.ascontiguousarray(self.unique["sig"][idx]),
        }

    def corrupted(self) -> tuple[dict, list[int]]:
        """A block with four seeded lanes that any ECDSA rejects by its range
        check (r = 0, s = 0, r = n, s = n) -> (block, the broken lanes)."""
        block = self._block(0)
        lanes = sorted(random.Random(self.seed ^ 0xC0881).sample(range(self.lanes), 4))
        order = np.frombuffer(refcrypto.N.to_bytes(32, "big"), np.uint8)
        sigs = block["sigs"]
        sigs[lanes[0], :32] = 0
        sigs[lanes[1], 32:64] = 0
        sigs[lanes[2], :32] = order
        sigs[lanes[3], 32:64] = order
        return block, lanes
