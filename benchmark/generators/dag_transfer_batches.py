"""Traffic generator for the parallel-transfer configuration: batches of
signed DagTransfer ``userTransfer(string,string,uint256)`` transactions
between accounts that exist, every one carrying ``TransactionAttribute.DAG``,
so the scheduler hands the whole block to the conflict-DAG runner.

The mix (``benchmark/traffic/*.json``) gives the sizes and the schedule, as
for ``transfer_batches`` (``batch_txs``, ``tick_s``, ``senders``,
``corpus_batches``); the configuration gives what is drawn:

- ``user_batches``  opening batches: accounts = ``user_batches`` x ``batch_txs``,
                    each opened by one ``userAdd`` signed with ``attribute`` 0.
                    They are set-up's and kept apart from the corpus
                    (``opening``, ``opening_records``), so the corpus, the
                    window and the DAG counters hold transfer blocks only;
- ``zipf_theta``    payer and payee are drawn independently from the accounts'
                    ranks with weight 1 / (rank + 1) ** theta (a cumulative
                    table and a bisect), the payee again while it equals the
                    payer;
- ``amount``, ``opening_balance``  inclusive ranges, drawn uniformly.

Everything is drawn from the seed: keys, names, balances, pairs, amounts."""

from __future__ import annotations

import random
from bisect import bisect_right
from itertools import accumulate

from benchmark.generators import transfer_batches

USER_ADD = "userAdd(string,uint256)"
USER_TRANSFER = "userTransfer(string,string,uint256)"


class ZipfRanks:
    """Ranks 0..n-1 with probability proportional to 1 / (rank + 1) ** theta."""

    def __init__(self, n: int, theta: float):
        self.cumulative = list(accumulate((r + 1) ** -theta for r in range(n)))
        self.total = self.cumulative[-1]

    def draw(self, rng: random.Random) -> int:
        return min(bisect_right(self.cumulative, rng.random() * self.total),
                   len(self.cumulative) - 1)


class Corpus(transfer_batches.Corpus):
    """``batches[k]`` are transfer batches with ``records[k][i] = (payer,
    payee, amount, sender index)``; ``opening[b]`` the ``userAdd`` batches of
    set-up with ``opening_records[b][i] = (user, balance, sender index)``.
    ``corrupt`` is ``transfer_batches``': four range-check lanes."""

    def __init__(self, config: dict, traffic: dict, seed: int, block_limit: int):
        super().__init__(traffic, seed, block_limit)
        from fisco_bcos_tpu.protocol.transaction import TransactionAttribute

        self._dag = int(TransactionAttribute.DAG)
        self.accounts = int(config["user_batches"]) * self.batch_txs
        self.names = [f"a{seed:x}-{r}" for r in range(self.accounts)]
        self._ranks = ZipfRanks(self.accounts, float(config["zipf_theta"]))
        self._pairs = random.Random(seed ^ 0x21BF0)
        self._amount = tuple(config["amount"])
        self._opening_balance = tuple(config["opening_balance"])
        self.opening: list[list] = []
        self.opening_records: list[list[tuple[str, int, int]]] = []

    def _signed(self, who: int, nonce: str, attribute: int, sig: str, *args):
        signed = self._factory.create_signed(
            self._keys[who], chain_id="chain0", group_id="group0",
            block_limit=self.block_limit, nonce=nonce, to=self._to,
            input=self._codec.encode_call(sig, *args), attribute=attribute,
        )
        # as it arrives on the wire: no hash or sender cached, so what the
        # node acknowledges is what its admission computed
        return self._factory.decode(signed.encode())

    def sign_opening(self, n_batches: int | None = None) -> None:
        """The ``userAdd`` batches of set-up, ``attribute`` 0: rank by rank."""
        want = self.accounts // self.batch_txs if n_batches is None else n_batches
        while len(self.opening) < want:
            b = len(self.opening)
            txs, recs = [], []
            for i in range(self.batch_txs):
                rank = b * self.batch_txs + i
                balance = self._amounts.randint(*self._opening_balance)
                who = rank % len(self._keys)
                txs.append(self._signed(who, f"o{self.seed:x}-{rank}", 0,
                                        USER_ADD, self.names[rank], balance))
                recs.append((self.names[rank], balance, who))
            self.opening.append(txs)
            self.opening_records.append(recs)

    def draw_pair(self) -> tuple[int, int]:
        payer = self._ranks.draw(self._pairs)
        payee = self._ranks.draw(self._pairs)
        while payee == payer:
            payee = self._ranks.draw(self._pairs)
        return payer, payee

    def sign_until(self, n_batches: int) -> None:
        while len(self.batches) < n_batches:
            k = len(self.batches)
            txs, recs = [], []
            for i in range(self.batch_txs):
                j = k * self.batch_txs + i
                payer, payee = self.draw_pair()
                amount = self._pairs.randint(*self._amount)
                who = j % len(self._keys)
                txs.append(self._signed(
                    who, f"t{self.seed:x}-{j}", self._dag,
                    USER_TRANSFER, self.names[payer], self.names[payee], amount))
                recs.append((self.names[payer], self.names[payee], amount, who))
            self.batches.append(txs)
            self.records.append(recs)
