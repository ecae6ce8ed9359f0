"""Transaction sync — gossip + missing-tx fetch.

Reference: bcos-txpool/sync/TransactionSync.cpp (maintainTransactions:78
broadcast, onReceiveTxsRequest:165, requestMissedTxs:204,
importDownloadedTxs:521 — the tbb-parallel verify loop that is one device
batch here via TxPool.submit_batch).
"""

from __future__ import annotations

import threading
from enum import IntEnum

from ..codec.flat import FlatReader, FlatWriter
from ..front.front import FrontService, ModuleID
from ..observability import TRACER
from ..protocol.transaction import Transaction
from ..txpool import TxPool
from ..utils.log import get_logger, note_swallowed

_log = get_logger("tx-sync")


class TxsPacket(IntEnum):
    PUSH = 0
    REQUEST = 1
    RESPONSE = 2


def _encode_txs(pkt: TxsPacket, txs: list[bytes]) -> bytes:
    w = FlatWriter()
    w.u8(int(pkt))
    w.seq(txs, lambda w2, b: w2.bytes_(b))
    return w.out()


def _encode_request(hashes: list[bytes]) -> bytes:
    w = FlatWriter()
    w.u8(int(TxsPacket.REQUEST))
    w.seq(hashes, lambda w2, h: w2.fixed(h, 32))
    return w.out()


class TransactionSync:
    def __init__(self, txpool: TxPool, front: FrontService, fetch_timeout: float = 3.0):
        self.txpool = txpool
        self.front = front
        self.suite = txpool.suite
        self.fetch_timeout = fetch_timeout
        self._broadcasted: set[bytes] = set()
        self._responses: dict[bytes, Transaction] = {}
        self._lock = threading.RLock()
        self._response_cv = threading.Condition(self._lock)
        front.register_module(ModuleID.TXS_SYNC, self._on_message)

    # -- gossip (maintainTransactions:78) ------------------------------------

    def maintain(self) -> None:
        """Broadcast txs not yet gossiped (called on a timer / after RPC
        submissions)."""
        with TRACER.span("txsync.maintain") as sp:
            to_send: list[bytes] = []
            with self._lock:
                with self.txpool._lock:
                    items = list(self.txpool._txs.items())
                for h, tx in items:
                    if h not in self._broadcasted:
                        self._broadcasted.add(h)
                        to_send.append(tx.encode())
                # forget hashes that already left the pool
                if len(self._broadcasted) > 4 * max(1, len(items)):
                    live = {h for h, _ in items}
                    self._broadcasted &= live
            if not to_send:
                sp.discard()  # an idle timer tick leaves no record
                return
            sp.set(txs=len(to_send))
            self.front.broadcast(
                ModuleID.TXS_SYNC, _encode_txs(TxsPacket.PUSH, to_send)
            )

    # -- missing-tx fetch (requestMissedTxs:204) -----------------------------

    def fetch_missing(self, hashes: list[bytes], from_node: bytes) -> list[Transaction | None]:
        """Synchronously request missing txs from a peer (the proposal-verify
        fetch hook). Responses arrive on transport threads; block until every
        requested hash is answered or `fetch_timeout` passes. The response
        cache is append-only during the wait, so concurrent fetches can
        coexist (each waits for its own hash set)."""
        wanted = set(hashes)
        self.front.send_message(ModuleID.TXS_SYNC, from_node, _encode_request(hashes))
        import time as _time

        deadline = _time.monotonic() + self.fetch_timeout
        with self._response_cv:
            while not wanted.issubset(self._responses):
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                self._response_cv.wait(remaining)
            out = [self._responses.get(h) for h in hashes]
            # prune answered entries once consumed (bounded cache)
            for h in hashes:
                self._responses.pop(h, None)
            return out

    # -- inbound -------------------------------------------------------------

    def _on_message(self, src: bytes, payload: bytes) -> None:
        try:
            r = FlatReader(payload)
            pkt = TxsPacket(r.u8())
            if pkt == TxsPacket.PUSH:
                raw = r.seq(lambda r2: r2.bytes_())
                r.done()
                self._on_push(raw, src)
            elif pkt == TxsPacket.REQUEST:
                hashes = r.seq(lambda r2: r2.fixed(32))
                r.done()
                self._on_request(src, hashes)
            elif pkt == TxsPacket.RESPONSE:
                raw = r.seq(lambda r2: r2.bytes_())
                r.done()
                self._on_response(raw)
        except Exception as e:
            _log.warning("bad tx-sync message from %s: %s", src.hex()[:8], e)

    def _on_push(self, raw: list[bytes], src: bytes = b"") -> None:
        with TRACER.span("txsync.push", txs=len(raw)) as sp:
            txs = []
            for b in raw:
                try:
                    txs.append(Transaction.decode(b))
                except Exception as e:
                    # a peer pushing undecodable txs is worth counting
                    note_swallowed("tx_sync.push_decode", e)
                    continue
            sp.stage("decode")
            if txs:
                # device batch verify + admission (importDownloadedTxs:521);
                # gossip rides the plane's lowest-priority lane, and the peer
                # id is the strike source — a peer spamming invalid signatures
                # gets demoted at this pool's door
                self.txpool.submit_batch(
                    txs, lane="sync", source=f"peer:{src.hex()[:16]}"
                )

    def _on_request(self, src: bytes, hashes: list[bytes]) -> None:
        found = [t.encode() for t in self.txpool.fetch_txs(hashes) if t is not None]
        self.front.send_message(
            ModuleID.TXS_SYNC, src, _encode_txs(TxsPacket.RESPONSE, found)
        )

    def _on_response(self, raw: list[bytes]) -> None:
        with self._response_cv:
            for b in raw:
                try:
                    tx = Transaction.decode(b)
                except Exception as e:
                    note_swallowed("tx_sync.response_decode", e)
                    continue
                self._responses[tx.hash(self.suite)] = tx
            self._response_cv.notify_all()
