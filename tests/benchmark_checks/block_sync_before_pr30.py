# A copy of fisco_bcos_tpu/sync/block_sync.py as it stood before PR 30 (commit
# d780dde), kept for one rehearsal: test_catchup_rehearsal.py runs the catch-up
# cell on it to show that the cell's comparison catches what this code does (it
# applies a downloaded block whose signatures nobody on the node checked).
# Loaded as a module of the fisco_bcos_tpu.sync package; nothing else uses it.
"""Block sync — download, verify, execute, commit.

Reference: bcos-sync/bcos-sync/BlockSync.cpp (peer status registry
state/SyncPeerStatus.cpp, download queue state/DownloadingQueue.cpp) with the
commit path DownloadingQueue::applyBlock:260 → scheduler executeBlock(verify)
:281 → BlockValidator QC check :407 → commitBlock:483. The QC check — every
sealer signature on the header — is one device batch here (the #2 hot loop).

Protocol (over ModuleID.BLOCK_SYNC): nodes broadcast their status on commit
and on `maintain()`; a node behind a peer requests a block range; responses
carry full blocks (header + QC + txs). Timers live in the node runtime —
`maintain()` is the explicit tick, keeping multi-node tests deterministic.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from enum import IntEnum

from ..codec.flat import FlatReader, FlatWriter
from ..consensus.block_validator import BlockValidator
from ..front.front import FrontService, ModuleID
from ..ledger import Ledger
from ..protocol.block import Block
from ..resilience.crashpoints import InjectedCrash
from ..scheduler.scheduler import Scheduler, SchedulerError
from ..utils.log import get_logger

_log = get_logger("block-sync")

MAX_BLOCKS_PER_REQUEST = 32

# a peer that times out this many requests in a row is demoted: the best-peer
# choice skips it until it answers again (or every candidate is demoted, in
# which case the strike board resets — degraded progress beats a stall).
# Reference: bcos-sync's SyncPeerStatus drops idle peers from the download
# queue choice rather than re-asking the same silent one forever.
MAX_PEER_STRIKES = 3


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
    ):
        self.ledger = ledger
        self.scheduler = scheduler
        self.front = front
        self.consensus = consensus
        self.suite = ledger.suite
        self.validator = validator or BlockValidator(self.suite)
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
        self._request_missing()

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
        import time as _time

        my_number = self.ledger.block_number()
        with self._lock:
            now = _time.monotonic()
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
                if lag is not None and lag in self._peers:
                    strikes = self._strikes.get(lag, 0) + 1
                    self._strikes[lag] = strikes
                    _log.warning(
                        "peer %s missed a block request (%.2fs window, "
                        "strike %d/%d)", lag.hex()[:8],
                        window, strikes, MAX_PEER_STRIKES,
                    )
                    from ..utils.metrics import REGISTRY

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
            nid, st = max(healthy, key=lambda c: c[1].number)
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
            # a crash point fired on the sync-commit path (the same
            # scheduler seams consensus hits): absorb at the transport
            # boundary — one node's death must never unwind the gateway's
            # delivery to its peers — and halt this node wholesale
            self._crashed = True
            if self.consensus is not None:
                self.consensus._crashed = True
            _log.error(
                "injected crash while syncing — node halted (reboot to "
                "recover)"
            )
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
            self._request_missing()

    def _on_request(self, src: bytes, start: int, count: int) -> None:
        count = max(0, min(count, MAX_BLOCKS_PER_REQUEST))
        blocks: list[bytes] = []
        for n in range(start, start + count):
            blk = self.ledger.block_by_number(n, with_txs=True)
            if blk is None:
                break
            blocks.append(blk.encode())
        if blocks:
            self.front.send_message(ModuleID.BLOCK_SYNC, src, _encode_response(blocks))

    def _on_response(self, src: bytes, raw_blocks: list[bytes]) -> None:
        import time as _time

        with self._lock:
            # an answer redeems the peer and feeds the adaptive window; the
            # outstanding-request markers are consumed HERE so a duplicate
            # or late second response cannot record a bogus RTT sample
            if src == self._requested_peer and self._requested_at:
                rtt = max(1e-3, _time.monotonic() - self._requested_at)
                prev = self._rtt_ewma.get(src)
                self._rtt_ewma[src] = (
                    rtt if prev is None else 0.7 * prev + 0.3 * rtt
                )
                self._requested_peer = None
                self._requested_at = 0.0
                self._strikes.pop(src, None)
        applied = 0
        for raw in raw_blocks:
            try:
                block = Block.decode(raw)
            except Exception:
                _log.warning("undecodable block from %s", src.hex()[:8])
                break
            if not self._apply_block(block):
                break
            applied += 1
        with self._lock:
            self._requested_to = 0  # allow the next request round
        if applied:
            self.broadcast_status()
            self._request_missing()

    # -- the commit path (applyBlock:260) ------------------------------------

    def _apply_block(self, block: Block) -> bool:
        number = block.header.number
        if number != self.ledger.block_number() + 1:
            return False
        # QC first: a forged block must not reach execution
        committee = self.ledger.consensus_nodes()
        if not self.validator.check_block(block.header, committee):
            _log.warning("block %d: QC validation failed", number)
            return False
        parent = self.ledger.block_hash_by_number(number - 1)
        if block.header.parent_info and block.header.parent_info[0].hash != parent:
            _log.warning("block %d: parent hash mismatch", number)
            return False
        try:
            header = self.scheduler.execute_block(block, verify=True)
            self.scheduler.commit_block(header)
        except SchedulerError as e:
            _log.warning("block %d: apply failed: %s", number, e)
            return False
        if self.consensus is not None:
            self.consensus.on_synced_block(number)
        _log.info("synced block %d (%d txs)", number, len(block.transactions))
        return True
