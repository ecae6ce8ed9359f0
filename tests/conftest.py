"""Test configuration.

Tests are CPU tests: they run on an 8-device virtual CPU platform so
multi-chip sharding (jax.sharding.Mesh) is exercised without TPU hardware,
exactly as ``__graft_entry__.dryrun_multichip`` does. The pin itself
(platform, fast-compile XLA flags, the one 32-lane batch bucket, the shared
compile cache) is ``fisco_bcos_tpu.utils.jaxenv.pin_cpu`` — applied below,
after the lock-order recorder is installed and before any backend exists.
"""

import os

# Device-plane coalescing window off for tests: the 2 ms production window
# adds idle latency to every sequential batch call (thousands across the
# suite on this 1-core host) and buys nothing for correctness — bursts
# still coalesce while the worker is busy, which is what the dedicated
# plane tests pin with explicit windows.
os.environ.setdefault("FISCO_DEVICE_WINDOW_MS", "0")
# Flight-recorder dumps (observability/flight.py) land in FISCO_FLIGHT_DIR
# (default cwd). Every Node.stop() across the suite flushes one — point
# them at a per-session temp dir so test runs don't litter the repo.
if "FISCO_FLIGHT_DIR" not in os.environ:
    import tempfile as _tempfile

    os.environ["FISCO_FLIGHT_DIR"] = _tempfile.mkdtemp(prefix="fisco-flight-")

import pytest  # noqa: E402

# Runtime lock-order recording (analysis/lockorder.py): every lock the
# package creates during the suite records per-thread acquisition chains;
# the session fails on ordering cycles or RPC IO held under a foreign lock.
# Installed BEFORE any fisco_bcos_tpu import so module-level locks are
# wrapped too. Disable with FISCO_LOCKORDER=0 (e.g. when bisecting timing).
_LOCKORDER = os.environ.get("FISCO_LOCKORDER", "1") != "0"
if _LOCKORDER:
    from fisco_bcos_tpu.analysis import lockorder as _lockorder

    _lockorder.install()
    _lockorder.install_io_guards()
    # Runtime accepted debt (the dynamic analog of tool/analysis_baseline
    # .json): locks these files create MAY be held across service-RPC IO by
    # design; anything else held across a frame send/recv fails the session.
    _lockorder.RECORDER.allowed_blocking = {
        # the consensus RLock IS the PBFT serialization: the engine holds it
        # across execute/commit/broadcast for one message end-to-end (the
        # commit 2PC included — commit_block runs under the engine lock)
        "fisco_bcos_tpu/consensus/engine.py": "consensus serialization lock",
        # execute_block holds the scheduler lock across remote execution on
        # purpose (shared executor block context); the commit-path 2PC was
        # moved OUTSIDE this lock in r10, so the forbid list re-catches
        # exactly that regression class — 2PC verbs under the scheduler
        # lock — while the broad, evolving execute-path RPC surface
        # (next_block_header/execute/DAG/DMC/get_hash) stays waived
        "fisco_bcos_tpu/scheduler/scheduler.py": _lockorder.Waiver(
            "executor block context (execute path only)",
            forbid=("/prepare", "/commit", "/rollback"),
        ),
    }

# Sampling lockset race recorder (analysis/raceguard.py): watches the hot
# shared-state classes' field traffic suite-wide and fails the session on
# lockset violations. Default OFF — the __getattribute__ instrumentation
# costs real time and tier-1 already runs against its timeout (see the
# tier1-timing-budget note); enable locally with FISCO_RACEGUARD=1.
_RACEGUARD = os.environ.get("FISCO_RACEGUARD", "0") == "1"
if _RACEGUARD:
    if not _LOCKORDER:
        # the guard's locksets COME FROM the lockorder recorder: without
        # the factory patch every access reads as lock-free and the whole
        # session fails on false races — refuse loudly instead
        raise RuntimeError(
            "FISCO_RACEGUARD=1 requires the lockorder recorder "
            "(unset FISCO_LOCKORDER=0)"
        )
    from fisco_bcos_tpu.analysis import raceguard as _raceguard

    _raceguard.install()

from fisco_bcos_tpu.utils.jaxenv import pin_cpu  # noqa: E402

pin_cpu(virtual_devices=8)


@pytest.fixture(autouse=True)
def _reset_admission_quotas():
    """The per-group admission policer is a process singleton (txpool/
    quota.py); strike/demotion state must not leak across tests."""
    yield
    from fisco_bcos_tpu.txpool import quota

    if quota._QUOTAS is not None:
        quota._QUOTAS.reset()


@pytest.fixture(scope="session", autouse=True)
def _lockorder_enforcement():
    """Fail the session if the suite's REAL lock traffic produced an
    ordering cycle or blocking RPC IO under a foreign lock (the runtime
    half of the lock-order analyzer — see docs/static_analysis.md)."""
    yield
    if not _LOCKORDER:
        return
    rec = _lockorder.RECORDER
    cycles = rec.cycles()
    assert not cycles, (
        "lock-order cycles recorded during the test suite (threads took "
        f"these locks in conflicting orders): {cycles}\nedges: "
        f"{rec.report()['edges']}"
    )
    viol = rec.blocking_violations
    assert not viol, (
        "blocking RPC IO performed while holding a lock during the test "
        f"suite: {viol}"
    )


@pytest.fixture(scope="session", autouse=True)
def _raceguard_enforcement():
    """When FISCO_RACEGUARD=1, fail the session on any lockset violation
    the suite's real field traffic produced (the dynamic complement of the
    guarded-state checker — see docs/static_analysis.md)."""
    yield
    if not _RACEGUARD:
        return
    races = _raceguard.RACEGUARD.report()
    assert not races, (
        "raceguard lockset violations recorded during the test suite "
        "(no single lock protected every access):\n" + "\n".join(races)
    )


_EXIT_STATUS = [None]


def pytest_sessionfinish(session, exitstatus):
    _EXIT_STATUS[0] = int(exitstatus)


def pytest_unconfigure(config):
    """Skip interpreter finalization: jaxlib's C++ static destructors race
    daemon threads that touched XLA during the suite (device-plane worker,
    engine workers of harnesses the tests leave running) and flakily call
    std::terminate AFTER the summary is printed — turning a fully green
    run into rc=134. By unconfigure time every report is flushed; exiting
    here hands the real pytest status to the caller deterministically."""
    if _EXIT_STATUS[0] is None:
        return  # the session never ran (usage error): normal teardown
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_EXIT_STATUS[0])


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-wall-clock end-to-end tests"
    )
