"""Storage service — the backend KV store as its own process.

Reference: the Pro/Max StorageService servant (fisco-bcos-tars-service) over
bcos-storage: other services reach durable state through service RPC.
`StorageService` exposes a TransactionalStorage over service/rpc.py;
`RemoteStorage` implements the same interface as a client, so a node (or a
remote executor) can mount a storage process exactly where it would mount
sqlite.
"""

from __future__ import annotations

from ..codec.flat import FlatReader, FlatWriter
from ..resilience import RetryPolicy
from ..storage.entry import Entry
from ..utils.log import note_swallowed
from ..storage.interfaces import (
    RowsView,
    TransactionalStorage,
    TraversableStorage,
    TwoPCParams,
)
from .rpc import ServiceClient, ServiceConnectionError, ServiceServer

# every storage verb is idempotent (blind puts + number-keyed 2PC), so a
# transient shard blip heals inside the call instead of surfacing as a term
# switch; a genuinely dead shard exhausts ~0.2s of backoff and still raises
_STORAGE_RETRY = dict(max_attempts=3, base_delay=0.05, max_delay=0.5)


class StorageService:
    def __init__(self, backend: TransactionalStorage, host: str = "127.0.0.1", port: int = 0):
        self.backend = backend
        self.server = ServiceServer("storage", host, port)
        s = self.server
        s.register("get_row", self._get_row)
        s.register("set_row", self._set_row)
        s.register("set_rows", self._set_rows)
        s.register("get_primary_keys", self._get_primary_keys)
        s.register("prepare", self._prepare)
        s.register("commit", self._commit)
        s.register("rollback", self._rollback)
        s.register("pending_2pc", self._pending_2pc)
        self.host, self.port = s.host, s.port

    def start(self) -> None:
        self.server.start()

    def stop(self) -> None:
        self.server.stop()

    # -- handlers -------------------------------------------------------------

    def _get_row(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        table, key = r.str_(), r.bytes_()
        r.done()
        e = self.backend.get_row(table, key)
        w = FlatWriter()
        w.u8(0 if e is None else 1)
        if e is not None:
            w.bytes_(e.encode())
        return w.out()

    def _set_row(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        table, key, data = r.str_(), r.bytes_(), r.bytes_()
        r.done()
        self.backend.set_row(table, key, Entry.decode(data))
        return b""

    def _set_rows(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        table = r.str_()
        items = r.seq(lambda r2: (r2.bytes_(), Entry.decode(r2.bytes_())))
        r.done()
        self.backend.set_rows(table, items)
        return b""

    def _get_primary_keys(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        table = r.str_()
        r.done()
        w = FlatWriter()
        w.seq(self.backend.get_primary_keys(table), lambda w2, k: w2.bytes_(k))
        return w.out()

    def _prepare(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        number = r.u64()
        rows = r.seq(
            lambda r2: (r2.str_(), r2.bytes_(), Entry.decode(r2.bytes_()))
        )
        r.done()
        self.backend.prepare(TwoPCParams(number=number), RowsView(rows))
        return b""

    def _commit(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        number = r.u64()
        r.done()
        self.backend.commit(TwoPCParams(number=number))
        return b""

    def _rollback(self, payload: bytes) -> bytes:
        r = FlatReader(payload)
        number = r.u64()
        r.done()
        self.backend.rollback(TwoPCParams(number=number))
        return b""

    def _pending_2pc(self, payload: bytes) -> bytes:
        # interface method (TransactionalStorage.pending_numbers): every
        # backend must answer truthfully or recovery skips its stuck slots
        nums = self.backend.pending_numbers()
        w = FlatWriter()
        w.seq(nums, lambda w2, n: w2.u64(n))
        return w.out()


class RemoteStorage(TransactionalStorage):
    """TransactionalStorage client over a StorageService.

    Failover seam (TiKVStorage.cpp:582 ``setSwitchHandler`` →
    libinitializer/Initializer.cpp:225-235 → SchedulerManager term switch):
    a transport-level loss fires ``switch_handler`` once per outage episode
    before the error propagates, so the scheduler can drop its in-flight
    term instead of wedging on half-committed state; the underlying
    ServiceClient redials on the next call, which ends the episode.
    """

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self.client = ServiceClient(
            host,
            port,
            timeout,
            connect_timeout=min(3.0, timeout),
            retry=RetryPolicy(**_STORAGE_RETRY),
        )
        self.switch_handler = None  # callable() | None
        self.heal_handler = None  # callable() | None — outage-episode END
        self._outage = False

    def set_switch_handler(self, fn) -> None:
        self.switch_handler = fn

    def set_heal_handler(self, fn) -> None:
        """Fires once per outage episode, on the first successful call after
        the loss — the degraded→ok edge (tars reconnect's 'alive again')."""
        self.heal_handler = fn

    def _healed(self) -> None:
        if self._outage:
            self._outage = False
            handler = self.heal_handler
            if handler is not None:
                try:
                    handler()
                except Exception as e:
                    # reporting must never break the storage path
                    note_swallowed("storage_service.heal_handler", e)

    def _call(self, method: str, payload: bytes = b"") -> bytes:
        try:
            out = self.client.call(method, payload)
        except ServiceConnectionError:
            if not self._outage:
                self._outage = True
                handler = self.switch_handler
                if handler is not None:
                    try:
                        handler()
                    except Exception as e:
                        # the switch must never mask the storage error
                        note_swallowed("storage_service.switch_handler", e)
            raise
        except Exception:
            # a reply frame arrived — the transport healed, so the outage
            # episode is over even though the HANDLER failed; otherwise the
            # next real outage would be silently swallowed
            self._healed()
            raise
        self._healed()
        return out

    def get_row(self, table: str, key: bytes) -> Entry | None:
        w = FlatWriter()
        w.str_(table)
        w.bytes_(bytes(key))
        out = self._call("get_row", w.out())
        r = FlatReader(out)
        if not r.u8():
            r.done()
            return None
        e = Entry.decode(r.bytes_())
        r.done()
        return None if e.deleted else e

    def set_row(self, table: str, key: bytes, entry: Entry) -> None:
        w = FlatWriter()
        w.str_(table)
        w.bytes_(bytes(key))
        w.bytes_(entry.encode())
        self._call("set_row", w.out())

    def set_rows(self, table: str, items) -> None:
        w = FlatWriter()
        w.str_(table)
        w.seq(
            list(items),
            lambda w2, kv: (w2.bytes_(bytes(kv[0])), w2.bytes_(kv[1].encode())),
        )
        self._call("set_rows", w.out())

    def get_primary_keys(self, table: str) -> list[bytes]:
        w = FlatWriter()
        w.str_(table)
        out = self._call("get_primary_keys", w.out())
        r = FlatReader(out)
        keys = r.seq(lambda r2: r2.bytes_())
        r.done()
        return keys

    def prepare(self, params: TwoPCParams, writes: TraversableStorage) -> None:
        w = FlatWriter()
        w.u64(params.number)
        w.seq(
            [(t, k, e) for t, k, e in writes.traverse()],
            lambda w2, row: (
                w2.str_(row[0]),
                w2.bytes_(bytes(row[1])),
                w2.bytes_(row[2].encode()),
            ),
        )
        self._call("prepare", w.out())

    def commit(self, params: TwoPCParams) -> None:
        w = FlatWriter()
        w.u64(params.number)
        self._call("commit", w.out())

    def rollback(self, params: TwoPCParams) -> None:
        w = FlatWriter()
        w.u64(params.number)
        self._call("rollback", w.out())

    def pending_numbers(self) -> list[int]:
        r = FlatReader(self._call("pending_2pc"))
        nums = r.seq(lambda r2: r2.u64())
        r.done()
        return [int(n) for n in nums]

    def close(self) -> None:
        self.client.close()
