"""Tx validation: chain/group checks, nonce checkers, signature admission.

Reference: bcos-txpool/txpool/validator/TxValidator.cpp:27-69 (group/chain
check → nonce checkers → ``tx->verify()``), TxPoolNonceChecker.cpp (in-pool
nonce dedup) and LedgerNonceChecker.cpp (committed-nonce window keyed by block
number, pruned by block_limit). The signature step is the #1 hot loop; here
`batch_admit` runs a whole batch through one device program — the suite's
fused admission (keccak→recover→address, or SM3→SM2 verify→address on a
national-crypto chain), or the generic hash_batch→batch_recover pipeline for
a suite that has none — instead of the reference's per-tx CPU call under tbb
(TransactionSync.cpp:521-553).
"""

from __future__ import annotations

import threading

import numpy as np

from ..crypto.suite import CryptoSuite
from ..protocol.transaction import Transaction
from ..utils.error import ErrorCode


class TxPoolNonceChecker:
    """Nonces of txs currently in the pool (TxPoolNonceChecker.cpp)."""

    def __init__(self) -> None:
        self._nonces: set[str] = set()
        self._lock = threading.Lock()

    def exists(self, nonce: str) -> bool:
        with self._lock:
            return nonce in self._nonces

    def insert(self, nonce: str) -> None:
        with self._lock:
            self._nonces.add(nonce)

    def remove(self, nonce: str) -> None:
        with self._lock:
            self._nonces.discard(nonce)


class LedgerNonceChecker:
    """Nonces committed in the block-limit window (LedgerNonceChecker.cpp):
    a tx whose nonce appears in any of the last `block_limit` blocks is a
    replay; a tx whose block_limit is behind the chain head is expired."""

    def __init__(self, block_limit: int = 600):
        self.block_limit = block_limit
        self._block_nonces: dict[int, set[str]] = {}
        self._nonces: set[str] = set()
        self._block_number = 0
        self._lock = threading.Lock()

    def check(self, tx: Transaction) -> ErrorCode:
        with self._lock:
            if tx.block_limit <= self._block_number or tx.block_limit > (
                self._block_number + self.block_limit
            ):
                return ErrorCode.BLOCK_LIMIT_CHECK_FAIL
            if tx.nonce in self._nonces:
                return ErrorCode.TX_ALREADY_IN_CHAIN
        return ErrorCode.SUCCESS

    def commit_block(self, number: int, nonces: list[str]) -> None:
        with self._lock:
            self._block_number = max(self._block_number, number)
            s = set(nonces)
            self._block_nonces[number] = s
            self._nonces.update(s)
            expired = [
                n for n in self._block_nonces if n <= self._block_number - self.block_limit
            ]
            for n in expired:
                self._nonces.difference_update(self._block_nonces.pop(n))


class TxValidator:
    """Admission pipeline for a single transaction (TxValidator.cpp:27-69)."""

    def __init__(
        self,
        suite: CryptoSuite,
        chain_id: str,
        group_id: str,
        pool_nonces: TxPoolNonceChecker,
        ledger_nonces: LedgerNonceChecker,
    ):
        self.suite = suite
        self.chain_id = chain_id
        self.group_id = group_id
        self.pool_nonces = pool_nonces
        self.ledger_nonces = ledger_nonces

    def check_static(self, tx: Transaction) -> ErrorCode:
        """Everything except the signature (cheap, CPU)."""
        if tx.chain_id != self.chain_id:
            return ErrorCode.INVALID_CHAIN_ID
        if tx.group_id != self.group_id:
            return ErrorCode.INVALID_GROUP_ID
        if self.pool_nonces.exists(tx.nonce):
            return ErrorCode.ALREADY_IN_TX_POOL
        return self.ledger_nonces.check(tx)

    def verify(self, tx: Transaction) -> ErrorCode:
        code = self.check_static(tx)
        if code != ErrorCode.SUCCESS:
            return code
        if not tx.signature or not tx.verify(self.suite):
            return ErrorCode.INVALID_SIGNATURE
        return ErrorCode.SUCCESS


def batch_admit(txs: list[Transaction], suite: CryptoSuite) -> np.ndarray:
    """Signature-verify + sender-recover a whole batch in one device pipeline,
    filling each tx's sender cache. Returns ok bool[B] (lanes, not exceptions).

    Dispatch: the suite's fused admission where it has one (secp256k1 +
    keccak256, SM2 + SM3); any other suite (ed25519) takes hash_batch →
    batch_recover → address-batch (still three device programs, not B CPU
    calls).
    """
    if not txs:
        return np.zeros(0, dtype=bool)
    sig_len = suite.signature_impl.sig_len
    sigs = np.zeros((len(txs), sig_len), dtype=np.uint8)
    well_formed = np.ones(len(txs), dtype=bool)
    for i, t in enumerate(txs):
        if len(t.signature) == sig_len:
            sigs[i] = np.frombuffer(t.signature, dtype=np.uint8)
        else:
            well_formed[i] = False

    fused = suite.fused_admission()
    if fused is not None:
        payloads = [t.encode_data() for t in txs]
        senders, ok, _pubs, digests = fused(payloads, sigs)
        # the fused program computed the tx hashes; fill caches from them
        for t, d in zip(txs, digests):
            if t._hash is None:
                t._hash = bytes(d)
    else:
        from ..protocol.transaction import hash_transactions_batch

        hashes = hash_transactions_batch(txs, suite)
        hs = np.frombuffer(b"".join(hashes), dtype=np.uint8).reshape(-1, 32)
        pubs, ok = suite.signature_impl.batch_recover(hs, sigs)
        senders = suite.calculate_address_batch(pubs)

    ok = np.asarray(ok) & well_formed
    for i, t in enumerate(txs):
        if ok[i]:
            t.force_sender(bytes(senders[i]))
    return ok
