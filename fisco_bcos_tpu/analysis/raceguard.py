"""Sampling lockset race recorder — the dynamic half of "tpu-tsan".

The static guarded-state checker sees lexical ``with self._lock:`` blocks;
it cannot see a field guarded by a caller's lock three frames up, a guard
taken in another module, or a field that is *never* guarded because every
author assumed someone else held the lock. This module watches real field
traffic the way the PR 5 lock-factory patch watches real lock traffic:

- :meth:`RaceGuard.watch` instruments a class's ``__setattr__`` /
  ``__getattribute__`` so every read/write of the *watched fields* reports
  to the guard (everything else pays one set-membership test);
- each access records the per-thread **lockset** — by default the
  instrumented-lock chain the :mod:`.lockorder` recorder already tracks,
  so the two runtime tools share one notion of "what this thread holds";
- per (instance, field) the guard runs the classic Eraser state machine:
  *exclusive* while a single thread owns the field (construction,
  hand-off), *shared* once a second thread reads it, *shared-modified*
  once writes race in — in the modified states the candidate lockset is
  intersected on every access, and an **empty intersection means no
  single lock protected every access**: a data-race candidate, reported
  once per ``Class.field`` with the access site that emptied the set.

**Sampling**: ``sample_every=N`` records one access in N (plus every
write) — the recorder is meant to ride whole test suites, where field
reads are hot; lockset soundness degrades gracefully (a missed access can
only *miss* a race, never invent one... except via the also-classic
Eraser false positives: ad-hoc synchronization, write-once-publish.
Those get waivers in the watch-list, not silence).

Suite-wide use: ``FISCO_RACEGUARD=1`` makes ``tests/conftest.py`` call
:func:`install` (default **off** — the tier-1 timing budget), watching
:data:`DEFAULT_WATCHLIST` — the hot shared-state classes named by the
concurrency roadmap item. The interleave explorer builds its own private
:class:`RaceGuard` per schedule with ``access_hook`` as its preemption
point, so every watched access is also a forced context switch.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass

from .lockorder import _REAL_LOCK, RECORDER

# Eraser states
_EXCLUSIVE = "exclusive"
_SHARED = "shared"  # read by >1 thread, no second-thread write yet
_SHARED_MOD = "shared-modified"  # racing writes: lockset intersection live

_PKG_MARKER = f"fisco_bcos_tpu{os.sep}"


_TOOLING = ("raceguard.py", "interleave.py", "lockorder.py")


def _access_site() -> str:
    """repo-style file:line of the package frame performing the access
    (the race tooling's own frames are skipped, harness frames are not)."""
    import sys

    f = sys._getframe(3)
    while f is not None:
        fn = f.f_code.co_filename
        i = fn.rfind(_PKG_MARKER)
        if i >= 0 and not fn.endswith(_TOOLING):
            return fn[i:].replace(os.sep, "/") + f":{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


@dataclass
class _FieldState:
    __slots__ = ("state", "owner", "lockset", "writers")
    state: str
    owner: int  # owning thread ident while exclusive
    lockset: frozenset | None  # candidate lockset (None until shared)
    writers: int


@dataclass
class Race:
    """One confirmed lockset violation (reported once per Class.field)."""

    cls: str
    fld: str
    kind: str  # "write" | "read"
    site: str  # package file:line of the access that emptied the lockset
    threads: tuple[str, str]  # (example earlier thread, racing thread)

    def render(self) -> str:
        return (
            f"{self.cls}.{self.fld}: no common lock across threads "
            f"{self.threads[0]!r}/{self.threads[1]!r} — lockset emptied by a "
            f"{self.kind} at {self.site}"
        )


class RaceGuard:
    """Watches field traffic on instrumented classes and runs the lockset
    state machine. One process-wide instance (:data:`RACEGUARD`) for the
    suite-wide recorder; the interleave explorer builds private ones."""

    def __init__(self, lockset_fn=None, sample_every: int = 1,
                 thread_filter=None):
        self._mu = _REAL_LOCK()
        self.lockset_fn = lockset_fn or RECORDER.held_sites
        self.sample_every = max(1, int(sample_every))
        # None = record every thread; else a () -> bool gate (the explorer
        # restricts recording to its managed workers so unrelated daemon
        # threads from earlier tests cannot pollute a schedule)
        self.thread_filter = thread_filter
        # called (cls_name, field, is_write) AFTER recording, outside _mu —
        # the interleave explorer's preemption point
        self.access_hook = None
        # the interleave explorer pauses the suite-wide guard during its
        # runs: harness traffic rides cooperative locks the lockorder
        # recorder cannot see, so its locksets would read empty here
        self.paused = False
        self._patched: dict[type, tuple] = {}
        self._states: dict[tuple[int, str], _FieldState] = {}
        self._owner_names: dict[int, str] = {}
        self.races: dict[tuple[str, str], Race] = {}
        self._tick = 0  # sampling counter (racy on purpose: it IS a sampler)

    # -- instrumentation -------------------------------------------------------

    def watch(self, cls: type, fields) -> None:
        """Patch ``cls`` so reads/writes of ``fields`` report here.
        Idempotent per class (fields merge into the watched set).

        A ``"?name"`` field is a **write-once-publish waiver** (the classic
        Eraser false positive the module docstring promises a waiver for):
        lock-free *reads* of a field assigned once at construction are by
        design — e.g. the flight ring's GIL-atomic ``deque.append`` rides
        an attribute read — so only *writes* report; a post-publication
        reassignment from a second thread still flags."""
        fields = frozenset(fields)
        waived = frozenset(f[1:] for f in fields if f.startswith("?"))
        fields = frozenset(f for f in fields if not f.startswith("?")) | waived
        with self._mu:
            if cls in self._patched:
                orig_set, orig_get, fs, wv = self._patched[cls]
                self._patched[cls] = (orig_set, orig_get, fs | fields, wv | waived)
                return
            orig_set = cls.__setattr__
            orig_get = cls.__getattribute__
            self._patched[cls] = (orig_set, orig_get, fields, waived)
        guard = self

        def __setattr__(obj, name, value):
            entry = guard._patched.get(cls)
            if entry is not None and name in entry[2]:
                guard._on_access(obj, cls.__name__, name, True)
            orig_set(obj, name, value)

        def __getattribute__(obj, name):
            entry = guard._patched.get(cls)
            if entry is not None and name in entry[2] and name not in entry[3]:
                guard._on_access(obj, cls.__name__, name, False)
            return orig_get(obj, name)

        cls.__setattr__ = __setattr__
        cls.__getattribute__ = __getattribute__

    def unwatch_all(self) -> None:
        with self._mu:
            patched, self._patched = self._patched, {}
        for cls, (orig_set, orig_get, _fields, _waived) in patched.items():
            cls.__setattr__ = orig_set
            cls.__getattribute__ = orig_get

    # -- the lockset state machine --------------------------------------------

    def _on_access(self, obj, cls_name: str, fld: str, is_write: bool) -> None:
        if self.paused:
            return
        if self.thread_filter is not None and not self.thread_filter():
            return
        if not is_write and self.sample_every > 1:
            self._tick += 1
            if self._tick % self.sample_every:
                return
        tid = threading.get_ident()
        held = frozenset(self.lockset_fn())
        key = (id(obj), fld)
        race: Race | None = None
        with self._mu:
            self._owner_names.setdefault(tid, threading.current_thread().name)
            st = self._states.get(key)
            if st is None:
                self._states[key] = _FieldState(
                    _EXCLUSIVE, tid, None, 1 if is_write else 0
                )
            elif st.state == _EXCLUSIVE:
                if tid == st.owner:
                    st.writers += 1 if is_write else 0
                else:
                    # second thread: the hand-off point — candidate lockset
                    # starts at THIS access's locks (first-thread accesses
                    # were construction)
                    st.state = _SHARED_MOD if (is_write or st.writers) else _SHARED
                    st.lockset = held
                    if is_write:
                        st.writers += 1
                    race = self._check_locked(st, cls_name, fld, is_write, tid)
            else:
                if is_write:
                    st.state = _SHARED_MOD
                    st.writers += 1
                st.lockset = (
                    held if st.lockset is None else st.lockset & held
                )
                race = self._check_locked(st, cls_name, fld, is_write, tid)
        hook = self.access_hook
        if hook is not None:
            hook(cls_name, fld, is_write)
        if race is not None:
            self._note_race(race)

    def _check_locked(self, st, cls_name, fld, is_write, tid) -> Race | None:
        if st.state != _SHARED_MOD or st.lockset:
            return None
        if (cls_name, fld) in self.races:
            return None
        other = next(
            (n for t, n in self._owner_names.items() if t != tid), "?"
        )
        return Race(
            cls_name, fld, "write" if is_write else "read", _access_site(),
            (other, self._owner_names.get(tid, "?")),
        )

    def _note_race(self, race: Race) -> None:
        with self._mu:
            self.races.setdefault((race.cls, race.fld), race)

    # -- reporting -------------------------------------------------------------

    def report(self) -> list[str]:
        with self._mu:
            return [r.render() for _, r in sorted(self.races.items())]

    def reset(self) -> None:
        with self._mu:
            self._states.clear()
            self.races.clear()
            self._owner_names.clear()


RACEGUARD = RaceGuard()

# the hot shared-state classes from the concurrency roadmap item, with the
# fields whose guard discipline the recorder checks. Dict-valued fields
# report attr-level loads (the read before .setdefault/[]) — enough for the
# lockset intersection to see which lock was held at the touch.
DEFAULT_WATCHLIST: tuple[tuple[str, str, tuple[str, ...]], ...] = (
    ("fisco_bcos_tpu.device.plane", "DevicePlane",
     ("requests", "dispatches", "merged_requests", "items", "_busy",
      "_deficit", "_drr_rotor")),
    ("fisco_bcos_tpu.proofs.plane", "ProofPlane",
     ("requests", "hits", "misses", "builds_commit", "builds_lazy",
      "coalesced_builds")),
    ("fisco_bcos_tpu.txpool.quota", "AdmissionQuotas", ("_groups",)),
    ("fisco_bcos_tpu.scheduler.scheduler", "Scheduler",
     ("term", "_committing_thread")),
    ("fisco_bcos_tpu.utils.metrics", "MetricsRegistry",
     ("_counters", "_gauges", "_histograms")),
    # the pipeline observatory's always-on shared state (ISSUE 9): stage
    # stat maps and per-stage accumulators, hit concurrently by every
    # pipeline worker plus the watermark sampler thread
    ("fisco_bcos_tpu.observability.pipeline", "PipelineRecorder",
     ("_stages", "_probes", "_marks")),
    ("fisco_bcos_tpu.observability.pipeline", "StageStats",
     ("busy_ms", "intervals", "blocked_intervals", "n_busy", "n_blocked",
      "state")),
    # the fleet observatory's shared state (ISSUE 16): the round ledger is
    # written by the engine worker + transport threads and snapshotted by
    # the federation aggregator; the flight ring is append-only from every
    # subsystem and drained by crash-flush
    ("fisco_bcos_tpu.observability.roundlog", "RoundLedger",
     ("_rounds", "_view_changes")),
    # "?": lock-free ring reads are the design (GIL-atomic deque.append);
    # only a post-publication reassignment of the ring itself may flag
    ("fisco_bcos_tpu.observability.flight", "FlightRecorder", ("?_ring",)),
)

_installed = False


def install(watchlist=None, sample_every: int | None = None) -> None:
    """Watch the default hot-class list on the process-wide guard.
    Idempotent. ``FISCO_RACEGUARD_SAMPLE`` tunes the read-sampling rate."""
    global _installed
    if _installed:
        return
    _installed = True
    if sample_every is None:
        try:
            sample_every = int(os.environ.get("FISCO_RACEGUARD_SAMPLE", "1"))
        except ValueError:
            sample_every = 1
    RACEGUARD.sample_every = max(1, sample_every)
    import importlib

    for mod_name, cls_name, fields in (watchlist or DEFAULT_WATCHLIST):
        mod = importlib.import_module(mod_name)
        RACEGUARD.watch(getattr(mod, cls_name), fields)


def uninstall() -> None:
    global _installed
    RACEGUARD.unwatch_all()
    _installed = False
