"""Traffic generator for the national-crypto admission cell: the mix of
``signed_payloads`` (one block of ``lanes`` signed payloads after another,
closed loop; ``signers`` distinct keys and payloads tiled to the block; blocks
differing by a seeded rotation of the lanes), signed the way an
``sm_crypto=true`` chain signs: SM2 over the payload's SM3 digest, a 128-byte
signature r ‖ s ‖ public key, by the benchmark's own plain reference
(``benchmark/refsm.py``). Reads the same parameters
(``benchmark/traffic/*.json``): ``lanes``, ``signers``, ``rotations``."""

from __future__ import annotations

import random

import numpy as np

from benchmark import refsm
from benchmark.generators import signed_payloads

BROKEN = ("r = 0", "s = 0", "r = n", "s = n", "carried key off the curve",
          "carried key is the neighbouring signer's")


class Corpus(signed_payloads.Corpus):
    """``signed_payloads.Corpus`` (its blocks and rotations) over SM-signed
    lanes and with SM's broken lanes."""

    def __init__(self, traffic: dict, seed: int):
        self.lanes = int(traffic["lanes"])
        self.seed = seed
        signers = int(traffic["signers"])
        rng = random.Random(seed)
        secrets = [rng.randrange(1, refsm.N) for _ in range(signers)]
        payloads = [
            b"bench parallel-transfer tx %08x %06d" % (seed & 0xFFFFFFFF, i) + b"\xab" * 64
            for i in range(signers)
        ]
        sigs = [refsm.sign_tx(p, s) for p, s in zip(payloads, secrets)]
        pubs = [sig[64:] for sig in sigs]
        # what a right admission returns, lane for lane, known by construction
        self.unique = {
            "payload": payloads,
            "sig": np.frombuffer(b"".join(sigs), np.uint8).reshape(signers, 128),
            "sender": np.frombuffer(b"".join(refsm.address(p) for p in pubs), np.uint8).reshape(signers, 20),
            "pub": np.frombuffer(b"".join(pubs), np.uint8).reshape(signers, 64),
            "digest": np.frombuffer(b"".join(refsm.sm3(p) for p in payloads), np.uint8).reshape(signers, 32),
        }
        self.blocks = [
            self._block(rng.randrange(signers)) for _ in range(int(traffic["rotations"]))
        ]

    def corrupted(self) -> tuple[dict, list[int]]:
        """A block with six seeded broken lanes, in the order of ``BROKEN``:
        four that SM2 rejects by its range check, one whose carried key has a
        bit of Px flipped and so is no point of the curve, one that carries
        the next signer's key (a valid point, the wrong one) -> (block, the
        broken lanes). The plain reference rejects each of them."""
        block = self._block(0)
        lanes = sorted(random.Random(self.seed ^ 0x5C0881).sample(range(self.lanes), 6))
        order = np.frombuffer(refsm.N.to_bytes(32, "big"), np.uint8)
        sigs = block["sigs"]
        sigs[lanes[0], :32] = 0
        sigs[lanes[1], 32:64] = 0
        sigs[lanes[2], :32] = order
        sigs[lanes[3], 32:64] = order
        sigs[lanes[4], 64 + 31] ^= 0x01
        signers = len(self.unique["payload"])
        sigs[lanes[5], 64:] = self.unique["pub"][(block["idx"][lanes[5]] + 1) % signers]
        for lane in lanes:
            assert not refsm.admit(block["payloads"][lane], bytes(sigs[lane]))[0], lane
        return block, lanes
