"""Distributed storage — sharded KV over multiple storage service processes.

Reference: bcos-storage/bcos-storage/TiKVStorage.{h,cpp}: the Pro/Max
deployments back the chain on a distributed KV store (TiKV regions +
two-phase commit via a primary lock, connection-loss switch handler :582).
This analog reaches the same capability TPU-natively cheap: N independent
StorageService processes are the "regions", a deterministic hash partition
(table, key) → shard replaces PD placement, and the chain's own block-number
2PC (prepare/commit/rollback fan-out, primary-first) replaces Percolator.

Semantics:
- `get_row`/`set_row` route by ``shard_of(table, key)``; whole-table scans
  (`get_primary_keys`) fan out and merge.
- `prepare(params, writes)` partitions the write set and prepares every
  shard — shard 0 is the PRIMARY (TiKV's primary-lock role): it is prepared
  first and committed first; a crash between phases leaves secondaries
  recoverable by re-driving the same block number (prepare is idempotent,
  keyed on number).
- Any transport loss fires ``switch_handler`` (once per outage episode)
  before the error propagates — the same scheduler term-switch seam as
  :class:`fisco_bcos_tpu.service.storage_service.RemoteStorage`.

System tables (s_*) are small and hot; they shard like any other row — reads
are one round trip either way, and one routing rule means a restarted node
finds every row exactly where it wrote it (placement is per-node plumbing;
consensus state roots are computed from overlay contents upstream of this
layer, so shard layout never leaks into them).
"""

from __future__ import annotations

import hashlib
import time

from ..resilience import HEALTH
from ..service.rpc import ServiceConnectionError, ServiceRemoteError
from ..service.storage_service import RemoteStorage
from ..storage.entry import Entry
from ..storage.interfaces import (
    RowsView,
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
    staged_rows,
)
from ..utils.log import get_logger
from ..utils.metrics import REGISTRY

_log = get_logger("dist-storage")

# per-shard 2PC legs: sub-ms local sqlite staging up to multi-second
# remote-shard round trips under faults
SHARD_2PC_BUCKETS_MS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 100.0, 500.0, 2000.0,
)


def _observe_leg(op: str, shard: int, t0: float) -> None:
    REGISTRY.observe(
        "fisco_storage_shard_2pc_ms",
        (time.perf_counter() - t0) * 1e3,
        buckets=SHARD_2PC_BUCKETS_MS,
        op=op,
        shard=str(shard),
        help="per-shard 2PC leg wall latency (shard attribution)",
    )


class DistributedStorage(TransactionalStorage):
    """TransactionalStorage over N sharded StorageService endpoints."""

    # health-registry component for the whole backend (GET /health)
    _COMPONENT = "storage"

    def __init__(self, endpoints: list[tuple[str, int]], timeout: float = 60.0):
        if not endpoints:
            raise ValueError("DistributedStorage needs at least one endpoint")
        self.shards = [RemoteStorage(h, p, timeout) for h, p in endpoints]
        self.switch_handler = None
        self._down: set[int] = set()  # shard idxs in a live outage episode
        # rollback re-drive ledger: number -> task idxs that could not be
        # reached when the number was declared dead (shard idx, or -1 for
        # the primary's witness retirement). A revived shard must re-run
        # these before any witness-based roll-forward, or it could
        # resurrect a dead block number.
        self._rolled_back: dict[int, set[int]] = {}
        # rollback listeners: cb(number) fired on EVERY rollback attempt of
        # a declared-dead number — initial drive and re-drives alike — so
        # read-side caches (the ProofPlane's frozen trees) evict the height
        # eagerly instead of waiting for their serve-time identity checks
        self.on_rollback: list = []
        for i, sh in enumerate(self.shards):
            # every shard loss funnels into ONE switch seam; RemoteStorage
            # dedups per-shard episodes, this layer scopes them by index
            sh.set_switch_handler(lambda i=i: self._on_shard_loss(i))
            sh.set_heal_handler(lambda i=i: self._on_shard_heal(i))

    def set_switch_handler(self, fn) -> None:
        self.switch_handler = fn

    def _on_shard_loss(self, idx: int) -> None:
        self._down.add(idx)
        HEALTH.degrade(
            self._COMPONENT,
            f"shard {idx} unreachable ({len(self.shards) - len(self._down)}"
            f"/{len(self.shards)} up)",
        )
        # an outage can strand prepared-but-unresolved slots: arm the
        # recovery pass so the next 2PC op resolves them before new work
        self.mark_needs_recovery()
        handler = self.switch_handler
        if handler is not None:
            handler()

    def _on_shard_heal(self, idx: int) -> None:
        self._down.discard(idx)
        if not self._down:
            HEALTH.ok(self._COMPONENT, f"shard {idx} back, all shards up")

    # -- routing ------------------------------------------------------------

    def shard_of(self, table: str, key: bytes) -> int:
        """Deterministic placement: blake2b of (table, key) mod N — stable
        across restarts for a fixed shard count (resharding is a migration,
        not a runtime event; TiKV's PD does it live, out of scope)."""
        h = hashlib.blake2b(
            table.encode() + b"\x00" + bytes(key), digest_size=8
        ).digest()
        return int.from_bytes(h, "big") % len(self.shards)

    # -- KV surface ---------------------------------------------------------

    def get_row(self, table: str, key: bytes) -> Entry | None:
        return self.shards[self.shard_of(table, key)].get_row(table, key)

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        self.shards[self.shard_of(table, key)].set_row(table, key, entry)

    def set_rows(self, table: str, items) -> None:
        by_shard: dict[int, list] = {}
        for k, e in items:
            by_shard.setdefault(self.shard_of(table, k), []).append((k, e))
        for idx, part in by_shard.items():
            self.shards[idx].set_rows(table, part)

    def get_primary_keys(self, table: str) -> list[bytes]:
        keys: list[bytes] = []
        for sh in self.shards:
            keys.extend(sh.get_primary_keys(table))
        return sorted(set(keys))

    # -- 2PC (TiKVStorage asyncPrepare/asyncCommit/asyncRollback) -----------

    # the primary's commit WITNESS row: staged with the primary's slot so it
    # lands atomically with the primary commit; recovery reads it to decide
    # roll-forward vs roll-back (TiKV: secondary locks resolve by checking
    # the primary lock/commit record)
    _WITNESS_TABLE = "s_2pc_witness"

    @staticmethod
    def _witness_key(number: int) -> bytes:
        return b"commit-%d" % number

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> None:
        # recovery may freely resolve params.number here: we are about to
        # RE-stage it, so an abandoned old slot rolling back is the point
        self.recover_in_flight_if_needed()
        # a re-prepare supersedes an earlier dead-number declaration: the
        # slot (and witness) about to be staged belong to the NEW decision,
        # so a leftover re-drive task must not kill them later
        self._rolled_back.pop(params.number, None)
        parts: dict[int, list] = {i: [] for i in range(len(self.shards))}
        for (t, k), e in staged_rows(writes)[0].items():  # read and re-sent: no copy
            parts[self.shard_of(t, k)].append((t, k, e))
        # primary (shard 0) first — its prepared slot carries the commit
        # witness, so the witness becomes durable exactly when the primary
        # commits (the point of no return, like TiKV's primary lock)
        parts[0].append(
            (
                self._WITNESS_TABLE,
                self._witness_key(params.number),
                Entry().set(b"1"),
            )
        )
        for idx in range(len(self.shards)):
            t0 = time.perf_counter()
            self.shards[idx].prepare(params, RowsView(parts[idx]))
            _observe_leg("prepare", idx, t0)

    def commit(self, params: TwoPCParams) -> None:
        # NEVER let recovery touch the number being committed: its slot is
        # legitimately pending RIGHT NOW and has no witness yet — an armed
        # recovery pass would roll it back and this commit would "succeed"
        # with empty slots, silently losing the block's writes
        self.recover_in_flight_if_needed(exclude=params.number)
        for idx in range(len(self.shards)):  # primary first
            t0 = time.perf_counter()
            self.shards[idx].commit(params)
            _observe_leg("commit", idx, t0)
        # retire the PREVIOUS block's witness: a commit of N proves N-1 is
        # fully resolved, so at most one live witness row remains instead
        # of one per block forever
        if params.number > 0:
            from .entry import EntryStatus

            self.shards[0].set_row(
                self._WITNESS_TABLE,
                self._witness_key(params.number - 1),
                Entry(status=EntryStatus.DELETED),
            )

    # -- in-flight 2PC recovery (the re-replay across a switch) -------------

    def mark_needs_recovery(self) -> None:
        """Arm a recovery pass for the next 2PC operation — wired to the
        same outage episodes that fire the switch handler."""
        self._needs_recovery = True

    def recover_in_flight_if_needed(self, exclude: int | None = None) -> None:
        if getattr(self, "_needs_recovery", False):
            self._needs_recovery = False
            try:
                self.recover_in_flight(exclude=exclude)
            except ServiceConnectionError:
                # a shard is still down: stay armed, retry on next 2PC op
                self._needs_recovery = True
                raise
            if self._rolled_back:
                # some dead-number re-drives still face unreachable shards:
                # stay armed so the next 2PC op tries again
                self._needs_recovery = True

    def recover_in_flight(self, exclude: int | None = None) -> None:
        """Resolve prepared-but-unresolved slots left by a crash/outage
        between phases: a slot whose number has the primary's commit
        witness rolls FORWARD (the coordinator had passed the point of no
        return), anything else rolls back — then consensus re-drives the
        block (TiKVStorage.cpp:582's switch handler + lock resolution).

        Numbers explicitly declared dead by :meth:`rollback` while some
        shards were unreachable are re-driven FIRST and never roll forward
        off a stale witness — a revived shard cannot resurrect them."""
        self._retry_unresolved_rollbacks(exclude=exclude)
        pending: set[int] = set()
        for sh in self.shards:
            pending.update(sh.pending_numbers())
        pending.discard(exclude)  # the caller owns that number's decision
        for n in sorted(pending):
            if n in self._rolled_back:
                continue  # declared dead; its re-drive is still unreachable
            witness = self.shards[0].get_row(
                self._WITNESS_TABLE, self._witness_key(n)
            )
            params = TwoPCParams(number=n)
            if witness is not None:
                _log.warning("2PC recovery: rolling FORWARD block %d", n)
                for sh in self.shards:
                    sh.commit(params)
            else:
                _log.warning("2PC recovery: rolling back block %d", n)
                for sh in self.shards:
                    sh.rollback(params)

    def _retry_unresolved_rollbacks(self, exclude: int | None = None) -> None:
        """Re-drive rollbacks that skipped unreachable shards (the recorded
        skip set), so a revived shard's stale slot/witness dies before it
        can influence witness-based recovery."""
        for n in sorted(self._rolled_back):
            if n == exclude:
                continue  # the caller is re-deciding this number right now
            _log.warning("re-driving rollback of block %d on revived shards", n)
            self.rollback(TwoPCParams(number=n))

    def rollback(self, params: TwoPCParams) -> None:
        number = params.number
        # resume from the recorded skip set when this is a re-drive; task
        # -1 is the primary's witness retirement, ordered FIRST so the
        # number loses roll-forward eligibility before anything else. The
        # record is only REPLACED at the end, never popped up front: an
        # unexpected exception mid-loop must not lose the dead-number
        # declaration (the whole point of recording it)
        todo = self._rolled_back.get(number)
        if todo is None:
            todo = {-1} | set(range(len(self.shards)))
        failed: set[int] = set()
        for idx in sorted(todo):
            try:
                if idx < 0:
                    # an explicit rollback declares the number DEAD: retire
                    # any witness a partial commit attempt may have left, or
                    # a later crash would roll a never-decided re-prepare
                    # forward off the stale marker
                    from .entry import EntryStatus

                    self.shards[0].set_row(
                        self._WITNESS_TABLE,
                        self._witness_key(number),
                        Entry(status=EntryStatus.DELETED),
                    )
                else:
                    self.shards[idx].rollback(params)
            except (ServiceRemoteError, OSError):
                # unreachable OR erroring shard (handler error, corrupt
                # reply): either way the task did not land — keep it
                failed.add(idx)
        if failed:
            # remember the skip set (was: logged and forgotten — a revived
            # shard could then resurrect the dead number via its stale
            # witness/slot) and arm recovery to re-drive it
            self._rolled_back[number] = failed
            self.mark_needs_recovery()
            _log.warning(
                "rollback of block %d skipped unreachable shard tasks %s — "
                "recorded for re-drive on recovery", number, sorted(failed),
            )
        else:
            self._rolled_back.pop(number, None)
        # fire AFTER the drive attempt: listeners see the number already
        # declared dead (witness retired first), and they fire again on
        # every re-drive — idempotent evictions by contract
        for cb in list(self.on_rollback):
            try:
                cb(number)
            except Exception as e:  # a listener must not break the 2PC
                from ..utils.log import note_swallowed

                note_swallowed("storage.distributed.on_rollback", e)

    def unresolved_rollbacks(self) -> dict[int, set[int]]:
        """Observability/test surface: numbers declared dead whose rollback
        has not yet reached every shard (task -1 = witness retirement)."""
        return {n: set(s) for n, s in self._rolled_back.items()}

    def pending_numbers(self) -> list[int]:
        out: set[int] = set()
        for sh in self.shards:
            out.update(sh.pending_numbers())
        return sorted(out)

    def close(self) -> None:
        for sh in self.shards:
            sh.close()
