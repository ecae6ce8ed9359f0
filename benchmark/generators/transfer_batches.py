"""Traffic generator for the chain cells: fixed-size batches of signed
DagTransfer ``userAdd`` transactions, every one on a fresh user (no
conflicts), from ``senders`` keys, on a fixed schedule. One generator reads
every mix of this kind; a mix is its parameters (``benchmark/traffic/*.json``):

- ``batch_txs``  transactions in a batch (one batch becomes one block)
- ``tick_s``     a batch falls due every ``tick_s`` seconds; 0 = back to back
- ``senders``    distinct signing keys
- ``corpus_batches``  batches signed in set-up when ``tick_s`` is 0 (the
                 window ends early if a faster chain spends them)

Everything is drawn from the seed: keys, user names, amounts. The schedule is
not random: every seed offers the same sizes at the same instants."""

from __future__ import annotations

import random

from benchmark import refcrypto

SECP_N = refcrypto.N


def due_offsets(traffic: dict, seconds: float) -> list[float] | None:
    """Seconds from the window's start at which each batch falls due; None
    for a back-to-back mix, whose batches are due as the chain frees."""
    tick = float(traffic["tick_s"])
    if tick <= 0:
        return None
    n = int(seconds / tick - 1e-9) + 1
    return [k * tick for k in range(n)]


class Corpus:
    """Signed batches plus what the plain reference needs to replay them:
    ``records[k][i] = (user, amount, sender index)``."""

    def __init__(self, traffic: dict, seed: int, block_limit: int):
        from fisco_bcos_tpu.codec.abi import ABICodec
        from fisco_bcos_tpu.crypto.suite import ecdsa_suite
        from fisco_bcos_tpu.executor.precompiled import DAG_TRANSFER_ADDRESS
        from fisco_bcos_tpu.protocol.transaction import TransactionFactory

        self.batch_txs = int(traffic["batch_txs"])
        self.seed = seed
        self.block_limit = block_limit
        rng = random.Random(seed)
        self.secrets = [rng.randrange(1, SECP_N) for _ in range(int(traffic["senders"]))]
        self._amounts = random.Random(seed ^ 0x5A5A5A5A)
        suite = ecdsa_suite()
        self._keys = [suite.signature_impl.generate_keypair(secret=s) for s in self.secrets]
        self._factory = TransactionFactory(suite)
        self._codec = ABICodec(suite.hash)
        self._to = DAG_TRANSFER_ADDRESS
        self.batches: list[list] = []
        self.records: list[list[tuple[str, int, int]]] = []

    def sign_until(self, n_batches: int) -> None:
        while len(self.batches) < n_batches:
            k = len(self.batches)
            txs, recs = [], []
            for i in range(self.batch_txs):
                j = k * self.batch_txs + i
                user = f"u{self.seed:x}-{j}"
                amount = self._amounts.randrange(1, 1_000_000)
                who = j % len(self._keys)
                signed = self._factory.create_signed(
                    self._keys[who], chain_id="chain0", group_id="group0",
                    block_limit=self.block_limit, nonce=f"b{self.seed:x}-{j}",
                    to=self._to,
                    input=self._codec.encode_call("userAdd(string,uint256)", user, amount),
                )
                # as it arrives on the wire: no hash or sender cached, so what
                # the node acknowledges is what its admission computed
                txs.append(self._factory.decode(signed.encode()))
                recs.append((user, amount, who))
            self.batches.append(txs)
            self.records.append(recs)

    def corrupt(self, k: int) -> list[int]:
        """Break batch ``k`` in place on four seeded lanes, each a signature
        that any ECDSA rejects by its range check alone (r = 0, s = 0, r = n,
        s = n), so the expectation needs no engine. -> the broken lanes."""
        lanes = random.Random(self.seed ^ 0xC0881).sample(range(self.batch_txs), 4)
        zero, order = bytes(32), SECP_N.to_bytes(32, "big")
        for lane, (r, s) in zip(lanes, ((zero, None), (None, zero), (order, None), (None, order))):
            tx = self.batches[k][lane]
            sig = bytes(tx.signature)
            tx.signature = (r or sig[:32]) + (s or sig[32:64]) + sig[64:]
            tx.sender = b""
            tx._wire = None
        return sorted(lanes)
