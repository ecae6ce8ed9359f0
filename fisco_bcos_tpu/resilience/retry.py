"""Retry, backoff and deadline semantics — the shared contract for every
remote call in the Air/Pro/Max splits.

Reference analogs: tars proxy reconnect-with-backoff (the service clients in
bcos-tars-protocol retry through the tars runtime), TarsRemoteExecutorManager's
bounded wait loops, and the per-call timeouts every servant declares. The
reproduction previously scattered ad-hoc ``except (ServiceRemoteError,
OSError)`` blocks and fixed sleeps across service/storage/sync; this module
is the single place those semantics live:

- :class:`RetryPolicy` — capped exponential backoff with deterministic
  jitter (seeded per policy, so fault-injected tests replay identically).
- :class:`Deadline` — an absolute time budget threaded through nested
  calls; ``DeadlineExceeded`` subclasses ``TimeoutError`` (hence
  ``OSError``), so existing transport-failure handling absorbs it.
- Idempotency classification per service-RPC method name: retrying a
  non-idempotent method after a connection loss could double-execute it
  (the request may have been applied before the reply was lost), so only
  classified-idempotent methods are ever auto-retried by the RPC client.
"""

from __future__ import annotations

import random
import time


class DeadlineExceeded(TimeoutError):
    """A per-call deadline ran out (TimeoutError -> OSError subclass: the
    transports' connection-loss handling applies unchanged)."""


class Deadline:
    """An absolute deadline carried through nested remote calls."""

    __slots__ = ("at",)

    def __init__(self, at: float):
        self.at = at

    @classmethod
    def after(cls, seconds: float) -> "Deadline":
        return cls(time.monotonic() + seconds)

    def remaining(self) -> float:
        return self.at - time.monotonic()

    def expired(self) -> bool:
        return self.remaining() <= 0

    def check(self, what: str = "call") -> None:
        if self.expired():
            raise DeadlineExceeded(f"{what}: deadline exceeded")

    def clamp(self, timeout: float) -> float:
        """A socket/sleep timeout bounded by what is left of the budget."""
        return max(0.001, min(timeout, self.remaining()))


class RetryPolicy:
    """Capped exponential backoff with deterministic jitter.

    ``delay(attempt)`` = min(max_delay, base * multiplier**attempt) plus a
    jitter drawn from the policy's own seeded RNG — two policies built with
    the same seed produce the same delay sequence, which keeps
    fault-injected tests reproducible while still de-synchronizing real
    fleets (every client constructs its policy with the default entropy
    seed).
    """

    __slots__ = (
        "max_attempts", "base_delay", "max_delay", "multiplier",
        "jitter", "retry_on", "_rng",
    )

    def __init__(
        self,
        max_attempts: int = 3,
        base_delay: float = 0.05,
        max_delay: float = 2.0,
        multiplier: float = 2.0,
        jitter: float = 0.25,
        retry_on: tuple = (ConnectionError, TimeoutError),
        seed: int | None = None,
    ):
        self.max_attempts = max(1, int(max_attempts))
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.multiplier = float(multiplier)
        self.jitter = float(jitter)
        self.retry_on = retry_on
        self._rng = random.Random(seed)

    def delay(self, attempt: int) -> float:
        d = min(self.max_delay, self.base_delay * (self.multiplier ** attempt))
        if self.jitter > 0:
            d *= 1.0 + self.jitter * self._rng.random()
        return d

    def run(
        self,
        fn,
        *args,
        retry_on: tuple | None = None,
        deadline: Deadline | None = None,
        on_retry=None,
        **kwargs,
    ):
        """Call ``fn(*args, **kwargs)``, retrying classified errors with
        backoff until attempts or the deadline run out. The LAST error is
        re-raised (not a wrapper: failover seams key on error types).

        Re-attempts (attempt >= 1) run inside a ``retry.attempt`` child
        span carrying the attempt number, so a fault-injected trace shows
        the retries instead of an unexplained gap; the first attempt stays
        span-free (the callee's own spans cover the happy path)."""
        from ..observability.tracer import TRACER

        classify = retry_on if retry_on is not None else self.retry_on
        last: BaseException | None = None
        name = getattr(fn, "__name__", "call")
        for attempt in range(self.max_attempts):
            if deadline is not None:
                deadline.check(name)
            try:
                if attempt == 0:
                    return fn(*args, **kwargs)
                with TRACER.span(
                    "retry.attempt", attempt=attempt, fn=name
                ) as sp:
                    try:
                        return fn(*args, **kwargs)
                    except classify as e:  # type: ignore[misc]
                        sp.set(error=type(e).__name__)
                        raise
            except classify as e:  # type: ignore[misc]
                last = e
                if attempt + 1 >= self.max_attempts:
                    break
                d = self.delay(attempt)
                if deadline is not None:
                    if deadline.remaining() <= d:
                        break  # sleeping would blow the budget: fail now
                    d = deadline.clamp(d)
                if on_retry is not None:
                    on_retry(attempt, e)
                time.sleep(d)
        assert last is not None
        raise last


# -- idempotency classification per service-RPC method -----------------------

# A method is idempotent when re-sending the same request after a lost reply
# cannot change durable state beyond the first application. The 2PC verbs
# are idempotent BY DESIGN (keyed on block number — re-preparing/committing/
# rolling back the same number is a no-op, which 2PC recovery already relies
# on). Execution verbs are NOT: execute_transactions mutates the in-flight
# block context cumulatively, and `handle` may carry a sendTransaction.
IDEMPOTENT_METHODS: set[str] = {
    # storage service
    "get_row", "set_row", "set_rows", "get_primary_keys",
    "prepare", "commit", "rollback", "pending_2pc",
    # executor service (read/2PC surface)
    "get_hash", "call", "get_code", "get_abi", "known_callee",
    "next_block_header", "get_storage", "ctx_floor",
    # registry / telemetry / health
    "register", "heartbeat", "metrics", "trace", "trace_tx", "trace_spans",
    "health", "pipeline", "profile", "device",
    # fleet observatory (ISSUE 16): pure reads — the facade's merged
    # cluster/round docs and the 4007 peer telemetry pull (a re-pulled
    # snapshot/ledger/probe only re-reads the peer's in-memory state)
    "fleet", "round", "rounds", "fleet_pull",
    # key center (pure transforms of the payload under the master key)
    "encDataKey", "decDataKey",
    # gateway read/connect surface (re-connecting to a live peer is a no-op)
    "peers", "connect_peer",
    # succinct state plane (ISSUE 18): pure reads off frozen per-height
    # snapshots — a re-served batch rebuilds at most a cached page tree
    "getStateProof", "state_proof", "state_proof_batch",
}

NON_IDEMPOTENT_METHODS: set[str] = {
    "execute_transactions", "dag_execute_transactions",
    "dmc_execute", "dmc_cancel", "dmc_commit_ctx", "dmc_set_ownership",
    "align", "handle", "send", "broadcast", "register_front",
    # frame delivery to the node: replaying re-dispatches module handlers
    "on_receive",
    # quota grant: a retry after a lost reply double-spends the permits
    "acquire",
}


def is_idempotent(method: str) -> bool:
    """Unknown methods default to NOT idempotent — auto-retry must be
    opted into, never inferred."""
    return method in IDEMPOTENT_METHODS


def mark_idempotent(method: str, flag: bool = True) -> None:
    """Extend the classification (new servants register their methods)."""
    if flag:
        NON_IDEMPOTENT_METHODS.discard(method)
        IDEMPOTENT_METHODS.add(method)
    else:
        IDEMPOTENT_METHODS.discard(method)
        NON_IDEMPOTENT_METHODS.add(method)
