"""Block sync — download, verify, execute, commit.

Reference: bcos-sync/bcos-sync/BlockSync.cpp (peer status registry
state/SyncPeerStatus.cpp, download queue state/DownloadingQueue.cpp) with the
commit path DownloadingQueue::applyBlock:260 → scheduler executeBlock(verify)
:281 → BlockValidator QC check :407 → commitBlock:483.

What a replica commits through sync it has checked itself, as upstream's
decode with ``checkSig = true`` does one transaction after another
(``Transaction::verify()``: recover, ``forceSender``), and here in batches
across blocks. Decoded blocks wait in a download queue (upstream's
DownloadingQueue) and leave it in **gathers**: the longest run of whole
blocks that fits ``VERIFY_LANES_MAX`` lanes, ten 1,000-tx blocks. A gather
is taken when the queue holds more than fits, or when nothing more is to be
had from any peer (the tail); otherwise the next range is asked for first, so
the calls stay full across responses. For a gather:

- the sealer signatures of all its headers go to the dispatch seam as ONE
  ``batch_verify`` (``BlockValidator.check_blocks``; at n = 4 that is 30-40
  signatures, which the seam's policy sends down the native host loop);
- all its transactions go through ONE ``batch_admit`` under
  ``device_lane("sync")``: the suite's fused admission program (secp256k1 +
  keccak256, or SM2 + SM3 on a national-crypto chain) through
  ``device/dispatch``, breaker and host fallback included. The answer fills
  every transaction's sender and hash, so execution sees the senders the
  sealing replicas saw and neither ``tx_hashes`` nor the transaction root
  hashes anything again;
- the blocks are then executed and committed one after another. A block
  with a refused lane (or a failed QC, parent or root) is not executed:
  nothing of it reaches the ledger, the blocks before it stay applied, the
  rest of the queue is dropped, the peer that served it takes a strike and
  the range is asked for again from the best remaining peer.

Verification of one gather and execution of the one before it run one after
the other (ROADMAP Queue 1 has the overlap).

Protocol (over ModuleID.BLOCK_SYNC): nodes broadcast their status on commit
and on `maintain()`; a node behind a peer requests a block range; responses
carry full blocks (header + QC + txs). Timers live in the node runtime —
`maintain()` is the explicit tick, keeping multi-node tests deterministic.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from enum import IntEnum

import numpy as np

from ..codec.flat import FlatReader, FlatWriter
from ..consensus.block_validator import BlockValidator
from ..front.front import FrontService, ModuleID
from ..ledger import Ledger
from ..observability.tracer import TRACER
from ..protocol.block import Block
from ..resilience.crashpoints import InjectedCrash
from ..scheduler.scheduler import Scheduler, SchedulerError
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY

_log = get_logger("block-sync")

MAX_BLOCKS_PER_REQUEST = 32
# the download queue's bound: blocks with few transactions are gathered by
# count once this many wait (recalled from upstream's DownloadingQueue, 256)
MAX_QUEUED_BLOCKS = 256

# Lanes of one re-verification call: the bucket of the north star's block on
# the batch ladder (ops/hash_common._bucket), which ten blocks at the Air
# default tx_count_limit = 1000 fill to 10,000. A gather is whole blocks, so
# its size follows from this and the blocks at hand.
VERIFY_LANES_MAX = 10_240

# a peer that times out this many requests in a row is demoted: the best-peer
# choice skips it until it answers again (or every candidate is demoted, in
# which case the strike board resets — degraded progress beats a stall).
# Reference: bcos-sync's SyncPeerStatus drops idle peers from the download
# queue choice rather than re-asking the same silent one forever.
MAX_PEER_STRIKES = 3


@contextmanager
def _stage(name: str, **attrs):
    """One stage of the catch-up: the span ``sync.<name>`` for a trace, and
    its seconds in ``fisco_sync_stage_seconds_total{stage}`` for an operator
    (where a replica that catches up spends its time)."""
    t0 = time.perf_counter()
    with TRACER.span(f"sync.{name}", **attrs) as sp:
        try:
            yield sp
        finally:
            REGISTRY.counter_add(
                f'fisco_sync_stage_seconds_total{{stage="{name}"}}',
                time.perf_counter() - t0,
                help="seconds block sync spent, by stage: decode, qc, verify "
                "on the replica that catches up, serve on the peer it asks",
            )


class SyncPacket(IntEnum):
    STATUS = 0
    REQUEST = 1
    RESPONSE = 2


@dataclass
class SyncStatus:
    number: int
    block_hash: bytes
    genesis_hash: bytes
    # sender's UTC clock (ms) — feeds NodeTimeMaintenance's median offset
    utc_ms: int = 0


def _encode_status(s: SyncStatus) -> bytes:
    w = FlatWriter()
    w.u8(int(SyncPacket.STATUS))
    w.i64(s.number)
    w.fixed(s.block_hash, 32)
    w.fixed(s.genesis_hash, 32)
    w.i64(s.utc_ms)
    return w.out()


def _encode_request(start: int, count: int) -> bytes:
    w = FlatWriter()
    w.u8(int(SyncPacket.REQUEST))
    w.i64(start)
    w.i64(count)
    return w.out()


def _encode_response(blocks: list[bytes]) -> bytes:
    w = FlatWriter()
    w.u8(int(SyncPacket.RESPONSE))
    w.seq(blocks, lambda w2, b: w2.bytes_(b))
    return w.out()


class BlockSync:
    def __init__(
        self,
        ledger: Ledger,
        scheduler: Scheduler,
        front: FrontService,
        consensus=None,  # PBFTEngine, notified on synced commits
        validator: BlockValidator | None = None,
        group_id: str = "",  # the plane's tenant tag of this chain's batches
    ):
        self.ledger = ledger
        self.scheduler = scheduler
        self.front = front
        self.consensus = consensus
        self.suite = ledger.suite
        self.validator = validator or BlockValidator(self.suite)
        self.group_id = group_id
        self._peers: dict[bytes, SyncStatus] = {}
        self._requested_to: int = 0
        self._requested_at: float = 0.0
        self._requested_peer: bytes | None = None
        # ADAPTIVE request timeout (was: fixed 10 s — one slow peer stalled
        # the download queue for the whole window): per-peer response-time
        # EWMA drives the decay window, clamped to
        # [request_timeout_floor, request_timeout]
        self.request_timeout: float = 10.0  # cap / no-sample default ceiling
        self.request_timeout_floor: float = 0.5
        self.request_timeout_initial: float = 2.0  # before any RTT sample
        self._rtt_ewma: dict[bytes, float] = {}
        self._strikes: dict[bytes, int] = {}
        # the download queue: (block, the peer that served it), decoded and
        # not yet applied, heights contiguous from the ledger's next
        self._queue: list[tuple[Block, bytes]] = []
        self._draining = False  # one thread at a time takes gathers
        self._applying_to = 0  # the last height of the gather being applied
        # a request was abandoned on its window: what is queued is applied
        # as it stands, not held back for a full gather that may never come
        self._tail_now = False
        # median peer clock tracking (bcos-tool NodeTimeMaintenance)
        from ..utils.time_sync import NodeTimeMaintenance

        self.time_maintenance = NodeTimeMaintenance()
        self._lock = threading.RLock()
        # injected-crash containment (resilience/crashpoints.py): the sync
        # commit path reaches the same scheduler seams as consensus; once
        # a crash point fires ANYWHERE in this node it is dead — stop
        # syncing (a halted engine must not keep durably committing via
        # sync), and never unwind the transport's delivery loop
        self._crashed = False
        self._genesis_hash = ledger.block_hash_by_number(0) or b"\x00" * 32
        front.register_module(ModuleID.BLOCK_SYNC, self._on_message)

    def peer_ids(self) -> list[bytes]:
        with self._lock:
            return list(self._peers)

    def peer_statuses(self) -> list[SyncStatus]:
        with self._lock:
            return list(self._peers.values())

    # -- outbound ------------------------------------------------------------

    def broadcast_status(self) -> None:
        from ..utils.time_sync import utc_ms

        num = self.ledger.block_number()
        st = SyncStatus(
            number=num,
            block_hash=self.ledger.block_hash_by_number(num) or b"\x00" * 32,
            genesis_hash=self._genesis_hash,
            utc_ms=utc_ms(),
        )
        self.front.broadcast(ModuleID.BLOCK_SYNC, _encode_status(st))

    def _node_dead(self) -> bool:
        """Whole-node halt state: this sync's own crash flag OR the
        engine's (one injected crash anywhere kills the node; sync must
        not keep writing durable state for a halted consensus)."""
        if self._crashed:
            return True
        return self.consensus is not None and getattr(
            self.consensus, "_crashed", False
        )

    def maintain(self) -> None:
        """One sync tick: advertise status, request missing blocks from the
        best peer (maintainDownloadingQueue analog)."""
        if self._node_dead():
            return  # a crash point fired: this node is dead until reboot
        self.broadcast_status()
        self._drain()

    def _timeout_for(self, nid: bytes | None) -> float:
        """The decay window for an outstanding request to this peer:
        4x its response-time EWMA, clamped — a fast peer's loss is noticed
        in under a second instead of the old fixed 10 s."""
        ewma = self._rtt_ewma.get(nid) if nid is not None else None
        if ewma is None:
            return min(self.request_timeout_initial, self.request_timeout)
        return max(
            self.request_timeout_floor, min(self.request_timeout, 4.0 * ewma)
        )

    def _request_missing(self) -> None:
        with self._lock:
            # the next height to download lies past what is queued
            my_number = self._tip_locked()
            now = time.monotonic()
            if self._requested_to >= my_number + 1:
                # an unanswered request must not stall sync forever: decay
                # it on the ADAPTIVE window and demote the silent peer
                if now - self._requested_at < self._timeout_for(self._requested_peer):
                    return
                # ABANDON the request before anything else: one lost
                # request strikes exactly once — idle ticks with no better
                # peer must not keep re-striking (and re-counting) it
                lag = self._requested_peer
                window = self._timeout_for(lag)
                self._requested_to = 0
                self._requested_at = 0.0
                self._requested_peer = None
                self._tail_now = bool(self._queue)
                if lag is not None and lag in self._peers:
                    strikes = self._strikes.get(lag, 0) + 1
                    self._strikes[lag] = strikes
                    _log.warning(
                        "peer %s missed a block request (%.2fs window, "
                        "strike %d/%d)", lag.hex()[:8],
                        window, strikes, MAX_PEER_STRIKES,
                    )
                    REGISTRY.counter_add(
                        "fisco_sync_request_timeouts_total", 1.0,
                        help="block requests abandoned on the adaptive window",
                    )
            candidates = [
                (nid, st)
                for nid, st in self._peers.items()
                if st.genesis_hash == self._genesis_hash and st.number > my_number
            ]
            if not candidates:
                return
            healthy = [
                c for c in candidates
                if self._strikes.get(c[0], 0) < MAX_PEER_STRIKES
            ]
            if not healthy:
                # every candidate is demoted: reset the board and take the
                # whole set again — degraded progress beats a stall
                _log.warning(
                    "all %d sync candidates demoted — resetting strikes",
                    len(candidates),
                )
                self._strikes.clear()
                healthy = candidates
            # the highest peer; among equals the one with the fewest strikes,
            # so a range a peer served badly goes to another next
            nid, st = max(
                healthy, key=lambda c: (c[1].number, -self._strikes.get(c[0], 0))
            )
            start = my_number + 1
            count = min(st.number - my_number, MAX_BLOCKS_PER_REQUEST)
            self._requested_to = start + count - 1
            self._requested_at = now
            self._requested_peer = nid
        _log.info("requesting blocks [%d, %d) from %s", start, start + count, nid.hex()[:8])
        self.front.send_message(ModuleID.BLOCK_SYNC, nid, _encode_request(start, count))

    # -- inbound -------------------------------------------------------------

    def _on_message(self, src: bytes, payload: bytes) -> None:
        if self._node_dead():
            return  # a crash point fired: this node is dead until reboot
        try:
            r = FlatReader(payload)
            pkt = SyncPacket(r.u8())
            if pkt == SyncPacket.STATUS:
                st = SyncStatus(r.i64(), r.fixed(32), r.fixed(32), r.i64())
                r.done()
                self._on_status(src, st)
            elif pkt == SyncPacket.REQUEST:
                start, count = r.i64(), r.i64()
                r.done()
                self._on_request(src, start, count)
            elif pkt == SyncPacket.RESPONSE:
                blocks = r.seq(lambda r2: r2.bytes_())
                r.done()
                self._on_response(src, blocks)
        except InjectedCrash:
            self._halt_injected()
        except Exception as e:
            _log.warning("bad sync message from %s: %s", src.hex()[:8], e)

    def prune_peers(self, live: set[bytes]) -> None:
        """Drop sync/clock state for departed peers (the runtime feeds the
        gateway's live-peer set; a dead node's stale clock sample must not
        skew the NodeTimeMaintenance median forever)."""
        with self._lock:
            dead = [nid for nid in self._peers if nid not in live]
            for nid in dead:
                del self._peers[nid]
                self._strikes.pop(nid, None)
                self._rtt_ewma.pop(nid, None)
        for nid in dead:
            self.time_maintenance.remove_peer(nid)

    def _on_status(self, src: bytes, st: SyncStatus) -> None:
        with self._lock:
            self._peers[src] = st
        if self.time_maintenance is not None:
            self.time_maintenance.on_peer_time(src, st.utc_ms)
        if st.number > self.ledger.block_number():
            self._drain()

    def _on_request(self, src: bytes, start: int, count: int) -> None:
        count = max(0, min(count, MAX_BLOCKS_PER_REQUEST))
        blocks: list[bytes] = []
        with _stage("serve_request", start=start, count=count) as sp:
            for n in range(start, start + count):
                blk = self.ledger.block_by_number(n, with_txs=True)
                if blk is None:
                    break
                blocks.append(blk.encode())
            response = _encode_response(blocks) if blocks else b""
            sp.set(blocks=len(blocks), bytes=len(response))
        if blocks:
            self.front.send_message(ModuleID.BLOCK_SYNC, src, response)

    def _on_response(self, src: bytes, raw_blocks: list[bytes]) -> None:
        with self._lock:
            # an answer feeds the adaptive window; the outstanding-request
            # markers are consumed HERE so a duplicate or late second
            # response cannot record a bogus RTT sample. What redeems a
            # peer's strikes is a block of its that applies (_apply_gather)
            if src == self._requested_peer and self._requested_at:
                rtt = max(1e-3, time.monotonic() - self._requested_at)
                prev = self._rtt_ewma.get(src)
                self._rtt_ewma[src] = (
                    rtt if prev is None else 0.7 * prev + 0.3 * rtt
                )
                self._requested_peer = None
                self._requested_at = 0.0
        self._enqueue(src, raw_blocks)
        with self._lock:
            self._requested_to = 0  # allow the next request round
        self._drain()

    # -- the download queue (DownloadingQueue.cpp) ---------------------------

    def _tip_locked(self) -> int:
        """The height the queue reaches, past the ledger's head and the gather
        being applied. Blocks the ledger has passed meanwhile (this node also
        commits through consensus) are dropped, and a queue that no longer
        continues from there is dropped whole."""
        head = max(self.ledger.block_number(), self._applying_to)
        while self._queue and self._queue[0][0].header.number <= head:
            self._queue.pop(0)
        if self._queue and self._queue[0][0].header.number != head + 1:
            self._queue.clear()
        return head + len(self._queue)

    def _enqueue(self, src: bytes, raw_blocks: list[bytes]) -> None:
        """Decode a response into the queue: the blocks that continue it, in
        order. The first undecodable block ends the response, counts as a
        refused block and strikes the peer, as a refused signature does."""
        blocks: list[Block] = []
        with _stage(
            "decode", blocks=len(raw_blocks),
            bytes=sum(len(raw) for raw in raw_blocks),
        ):
            for raw in raw_blocks:
                try:
                    blocks.append(Block.decode(raw))
                except Exception:
                    self._refuse(src, None, "decode")
                    break
        with self._lock:
            tip = self._tip_locked()
            for block in blocks:
                if block.header.number <= tip:
                    continue  # a duplicate of what is applied or queued
                if block.header.number != tip + 1:
                    break
                self._queue.append((block, src))
                tip += 1

    def _take_gather_locked(self, tail: bool) -> list[tuple[Block, bytes]]:
        """Pop the next gather: the longest run of whole queued blocks within
        ``VERIFY_LANES_MAX`` lanes, one block at least. A gather the queue
        ends inside could still grow; it is taken only as the ``tail``: when
        no request is on its way, or one was just abandoned."""
        self._tip_locked()
        lanes = n = 0
        for block, _src in self._queue:
            if n and lanes + len(block.transactions) > VERIFY_LANES_MAX:
                break
            lanes += len(block.transactions)
            n += 1
        full = n < len(self._queue) or n >= MAX_QUEUED_BLOCKS
        if not n or not (
            full or (tail and (self._tail_now or not self._requested_to))
        ):
            return []
        self._tail_now = False
        gather, self._queue = self._queue[:n], self._queue[n:]
        self._applying_to = gather[-1][0].header.number
        return gather

    def _drain(self) -> None:
        """Apply what is queued, gather by gather, asking for the next range
        whenever the queue ends inside a gather, until neither moves. One
        thread at a time: a response that arrives meanwhile (under an
        in-process gateway: inside the request's own send) only queues its
        blocks, and this loop finds them."""
        while True:
            with self._lock:
                if self._draining or self._node_dead():
                    return
                self._draining = True
            try:
                while not self._node_dead():
                    with self._lock:
                        gather = self._take_gather_locked(tail=False)
                    if not gather:
                        with self._lock:
                            tip = self._tip_locked()
                        self._request_missing()
                        with self._lock:
                            if self._tip_locked() > tip:
                                continue  # the answer came inline
                            gather = self._take_gather_locked(tail=True)
                    if not gather:
                        break
                    try:
                        applied = self._apply_gather(gather)
                    finally:
                        with self._lock:
                            self._applying_to = 0
                    if applied:
                        self.broadcast_status()
            except InjectedCrash:
                self._halt_injected()
            finally:
                with self._lock:
                    self._draining = False
            with self._lock:
                # a response that came between the loop's last look and the
                # flag's release found the door shut: look once more
                if not self._queue or self._requested_to:
                    return

    def _halt_injected(self) -> None:
        """A crash point fired on the sync-commit path (the same scheduler
        seams consensus hits): halt this node wholesale. Absorbed here and at
        the transport boundary: one node's death must never unwind the
        gateway's delivery to its peers."""
        self._crashed = True
        if self.consensus is not None:
            self.consensus._crashed = True
        _log.error(
            "injected crash while syncing — node halted (reboot to recover)"
        )

    def _refuse(self, src: bytes, number: int | None, reason: str) -> None:
        """A block that is not applied: counted by reason, the queue behind
        it dropped, its peer struck, the range free to be asked for again
        (from the best remaining peer: the strike board decides)."""
        REGISTRY.counter_add(
            f'fisco_sync_blocks_refused_total{{reason="{reason}"}}', 1.0,
            help="downloaded blocks refused by block sync, by what failed",
        )
        with self._lock:
            self._queue.clear()
            self._requested_to = 0
            strikes = 0
            if src:
                strikes = self._strikes[src] = self._strikes.get(src, 0) + 1
        _log.warning(
            "block %s from %s refused (%s), strike %d/%d",
            "?" if number is None else number, src.hex()[:8] or "-", reason,
            strikes, MAX_PEER_STRIKES,
        )

    # -- the commit path (applyBlock:260) ------------------------------------

    def _apply_block(self, block: Block) -> bool:
        """One block through the whole path, a gather of its own."""
        if block.header.number != self.ledger.block_number() + 1:
            return False
        return self._apply_gather([(block, b"")]) == 1

    def _apply_gather(self, gather: list[tuple[Block, bytes]]) -> int:
        """QC of every header as one batch, every transaction through one
        admission call, then execute and commit block by block up to the
        first that fails anything. -> blocks applied."""
        from ..device.plane import device_group, device_lane
        from ..txpool.validator import batch_admit

        blocks = [b for b, _src in gather]
        committee = self.ledger.consensus_nodes()
        with _stage(
            "qc", headers=len(blocks),
            signatures=sum(len(b.header.signature_list) for b in blocks),
        ):
            # QC first: a forged block must not reach admission or execution
            qc_ok = self.validator.check_blocks(
                [b.header for b in blocks], committee
            )
        sound = qc_ok.index(False) if False in qc_ok else len(blocks)
        txs = [t for b in blocks[:sound] for t in b.transactions]
        ok = np.zeros(0, dtype=bool)
        if txs:
            with _stage("verify", blocks=sound, lanes=len(txs)):
                with device_group(self.group_id), device_lane("sync"):
                    # fills every admitted transaction's sender and hash
                    ok = batch_admit(txs, self.suite)
            REGISTRY.counter_add(
                "fisco_sync_verify_lanes_total", float(len(txs)),
                help="transactions of downloaded blocks re-verified by block sync",
            )
            REGISTRY.counter_add(
                "fisco_sync_verify_calls_total", 1.0,
                help="admission calls block sync made, one a gather of blocks",
            )
        applied = lo = 0
        for k, (block, src) in enumerate(gather):
            hi = lo + len(block.transactions)
            if k >= sound:
                reason = "qc"
            elif not ok[lo:hi].all():
                reason = "signature"
            else:
                reason = self._execute_and_commit(block)
            lo = hi
            if reason is not None:
                self._refuse(src, block.header.number, reason)
                break
            applied += 1
            if src:
                with self._lock:
                    self._strikes.pop(src, None)  # a block that applies redeems
            if k + 1 < len(gather) and self.ledger.consensus_nodes() != committee:
                # this block changed the committee: what follows is held to
                # the one that signed it, in a gather of its own
                with self._lock:
                    self._applying_to = 0
                    self._queue[:0] = gather[k + 1:]
                break
        return applied

    def _execute_and_commit(self, block: Block) -> str | None:
        """-> None when the block is committed, else why it was not."""
        number = block.header.number
        if number != self.ledger.block_number() + 1:
            return "parent"
        parent = self.ledger.block_hash_by_number(number - 1)
        if block.header.parent_info and block.header.parent_info[0].hash != parent:
            _log.warning("block %d: parent hash mismatch", number)
            return "parent"
        with TRACER.span(
            "sync.apply_block", block=number, txs=len(block.transactions)
        ):
            try:
                header = self.scheduler.execute_block(block, verify=True)
                self.scheduler.commit_block(header)
            except SchedulerError as e:
                _log.warning("block %d: apply failed: %s", number, e)
                return "root"
        REGISTRY.counter_add(
            "fisco_sync_blocks_applied_total", 1.0,
            help="downloaded blocks executed and committed by block sync",
        )
        if self.consensus is not None:
            self.consensus.on_synced_block(number)
        _log.info("synced block %d (%d txs)", number, len(block.transactions))
        return None
