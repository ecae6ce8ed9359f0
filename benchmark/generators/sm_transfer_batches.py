"""Traffic generator for the national-crypto chain cell: ``transfer_batches``'
corpus (fixed-size batches of signed DagTransfer ``userAdd`` transactions,
every one on a fresh user, from ``senders`` keys, ``attribute`` 0, as they
arrive on the wire with no hash or sender cached; the same parameters of
``benchmark/traffic/*.json``, the same schedule) signed the way an
``sm_crypto=true`` chain signs: SM2 over the transaction's SM3 digest, a
128-byte signature r ‖ s ‖ public key, function selectors SM3's first four
bytes.

The corpus is signed by the program's own SM2 signer (the native core): a
window's 124,000 signatures in plain Python do not fit a set-up. What that
signer produced is held to the plain reference afterwards: ``correct``
re-derives a sample of the committed transactions through ``benchmark/refsm.py``
(hash, sender and carried key), and every corrupted lane is refused by
``refsm.admit`` itself before it is offered."""

from __future__ import annotations

import random

from benchmark import refsm
from benchmark.generators import transfer_batches
from benchmark.generators.sm_signed_payloads import BROKEN


class Corpus(transfer_batches.Corpus):
    """``transfer_batches.Corpus`` (its batches, records and ``sign_until``)
    under the SM suite, with SM's six broken lanes."""

    def __init__(self, traffic: dict, seed: int, block_limit: int):
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import sm_suite
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        super().__init__(traffic, seed, block_limit)  # the sizes, the amounts, the empty corpus
        rng = random.Random(seed)
        self.secrets = [rng.randrange(1, refsm.N) for _ in range(int(traffic["senders"]))]
        suite = sm_suite()
        self._keys = [suite.signature_impl.generate_keypair(secret=s) for s in self.secrets]
        self._factory = TransactionFactory(suite)
        self._codec = ABICodec(suite.hash)

    def corrupt(self, k: int) -> list[int]:
        """Break batch ``k`` in place on six seeded lanes, in the order of
        ``sm_signed_payloads.BROKEN``: four that SM2 rejects by its range
        check, one whose carried key has a bit of Px flipped and so is no
        point of the curve, one that carries the next sender's key (a valid
        point, the wrong one) -> the broken lanes, ascending. The plain
        reference refuses each of them."""
        lanes = sorted(random.Random(self.seed ^ 0x5C0881).sample(range(self.batch_txs), len(BROKEN)))
        zero, order = bytes(32), refsm.N.to_bytes(32, "big")
        for which, lane in enumerate(lanes):
            tx = self.batches[k][lane]
            sig = bytes(tx.signature)
            r, s, pub = sig[:32], sig[32:64], sig[64:128]
            if which < 4:
                r, s = ((zero, s), (r, zero), (order, s), (r, order))[which]
            elif which == 4:
                pub = pub[:31] + bytes([pub[31] ^ 0x01]) + pub[32:]
            else:
                who = self.records[k][lane][2]
                pub = bytes(self._keys[(who + 1) % len(self._keys)].pub)
            tx.signature = r + s + pub
            tx.sender = b""
            tx._wire = None
            assert not refsm.admit(tx.encode_data(), tx.signature)[0], BROKEN[which]
        return lanes
