"""Project-native invariant analyzers + runtime lock-order recorder.

Two enforcement halves:

1. the package itself must be CLEAN against the checked-in baseline
   (``test_repo_has_no_new_findings`` IS the tier-1 gate every future PR
   lands against), and
2. each checker must demonstrably FIRE on its fixture violation under
   ``tests/fixtures/analysis/`` (a checker that never fires is a decoration,
   not a gate) while the ``clean.py`` control produces nothing.

Plus unit coverage for the framework (waivers, baseline diff, jit
inventory) and the runtime recorder (edge recording, cycle detection,
reentrancy, Condition round-trip, IO-under-lock guard, factory filter).

Everything here is pure AST + plain threading — no jax tracing, so the
whole module stays well inside the 30 s tier-1 budget on a cold process.
"""

from __future__ import annotations

import ast
import json
import os
import threading

import pytest

from fisco_bcos_tpu.analysis import (
    Finding,
    Source,
    check_repo,
    diff_findings,
    jitmap,
    load_sources,
    run_all,
)
from fisco_bcos_tpu.analysis.checkers import (
    ALL_CHECKERS,
    AtomicityChecker,
    ContractChecker,
    DeviceDispatchChecker,
    ExceptionHygieneChecker,
    GuardedStateChecker,
    JitPurityChecker,
    LockOrderChecker,
    ShapeBucketChecker,
)
from fisco_bcos_tpu.analysis.lockorder import (
    InstrumentedLock,
    InstrumentedRLock,
    LockOrderRecorder,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


def _src(text: str, relpath: str = "fisco_bcos_tpu/x.py") -> Source:
    return Source(relpath, relpath, text, ast.parse(text))


@pytest.fixture(scope="module")
def fixture_sources():
    return load_sources(FIXTURES)


@pytest.fixture(scope="module")
def fixture_findings(fixture_sources):
    return run_all(sources=fixture_sources)


# -- the tier-1 gate ----------------------------------------------------------


def test_repo_has_no_new_findings():
    """THE enforcement: zero non-baselined findings over the package, and
    no stale baseline entries (paid debt must leave the ledger)."""
    new, stale = check_repo()
    assert not new, "new analyzer findings:\n" + "\n".join(
        f.render() for f in new
    )
    assert not stale, f"stale baseline entries (debt paid? remove): {stale}"


def test_baseline_keys_are_current_format():
    with open(os.path.join(REPO, "tool", "analysis_baseline.json")) as f:
        data = json.load(f)
    names = {c.name for c in ALL_CHECKERS}
    for entry in data["findings"]:
        checker = entry["key"].split(":", 1)[0]
        assert checker in names, f"baseline references unknown checker: {entry}"
        assert entry.get("note"), f"baseline entry without a note: {entry}"


# -- each checker fires on its fixture ---------------------------------------


def _keys(findings, checker: str) -> set[str]:
    return {f.key for f in findings if f.checker == checker}


def test_fixture_device_dispatch(fixture_findings):
    assert (
        "device-dispatch:tests/fixtures/analysis/bad_device.py::import-secp256k1"
        in _keys(fixture_findings, "device-dispatch")
    )


def test_fixture_layering_under_the_seams(fixture_findings):
    """device/, ops/ and parallel/ import nothing of crypto/ but the plain
    reference; the rule reads every spelling of the import."""
    got = _keys(fixture_findings, "device-dispatch")
    base = "device-dispatch:tests/fixtures/analysis/device/bad_layering.py::"
    assert {k for k in got if k.startswith(base)} == {base + "imports-up-crypto.suite"}
    for text in (
        "from ..crypto.suite import device_backend_is_cpu\n",
        "from ..crypto import admission\n",
        "from .. import crypto\n",
        "import fisco_bcos_tpu.crypto.bls\n",
        "def f():\n    from fisco_bcos_tpu.crypto.admission import admission_core\n",
    ):
        for layer in ("device/plane.py", "ops/merkle.py", "parallel/sharding.py"):
            assert DeviceDispatchChecker().run([_src(text, "fisco_bcos_tpu/" + layer)]), text
        assert not DeviceDispatchChecker().run([_src(text, "fisco_bcos_tpu/crypto/x.py")])
    leaf = "from ..crypto.ref.ecdsa import SECP256K1\nfrom ..crypto.ref import sm3\n"
    assert not DeviceDispatchChecker().run([_src(leaf, "fisco_bcos_tpu/ops/ec.py")])


def _imported_modules(src: Source) -> set[str]:
    """Every module a file imports, as absolute dotted names; a
    ``from x import a`` counts ``x`` and ``x.a`` (``a`` may be a module)."""
    package = src.relpath[: -len(".py")].split("/")[:-1]
    out: set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("layer", ["storage", "codec", "protocol", "ledger"])
def test_the_lowest_host_layers_import_nothing_of_observability(layer):
    """Rows, codecs, protocol objects and the ledger know no recorder above
    them: what is measured of them is measured where they are called."""
    sources = [
        s for s in load_sources()
        if s.relpath.startswith(f"fisco_bcos_tpu/{layer}/")
    ]
    assert sources, layer
    up = {
        f"{s.relpath}: {m}"
        for s in sources
        for m in _imported_modules(s)
        if (m + ".").startswith("fisco_bcos_tpu.observability.")
    }
    assert not up, sorted(up)


def test_fixture_shape_bucket(fixture_findings):
    assert (
        "shape-bucket:tests/fixtures/analysis/bad_shape.py:feed:unbucketed-kernel"
        in _keys(fixture_findings, "shape-bucket")
    )


def test_fixture_jit_purity(fixture_findings):
    assert (
        "jit-purity:tests/fixtures/analysis/bad_jit_purity.py:stamped:"
        "impure-time.time" in _keys(fixture_findings, "jit-purity")
    )


def test_fixture_lock_cycle(fixture_findings):
    assert (
        "lock-order:tests/fixtures/analysis/bad_lock_order.py::cycle-A-B"
        in _keys(fixture_findings, "lock-order")
    )


def test_fixture_blocking_under_lock(fixture_findings):
    assert (
        "lock-order:tests/fixtures/analysis/bad_blocking.py:slow:"
        "blocking-sleep-under-L" in _keys(fixture_findings, "lock-order")
    )


def test_fixture_except_hygiene(fixture_findings):
    # the key carries a content hash of the guarded try body (not an
    # index): recompute it from the fixture the same way the checker does,
    # proving the key is derived from WHAT is guarded, not where it sits
    import hashlib

    fixture = os.path.join(FIXTURES, "bad_except.py")
    with open(fixture, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    (try_node,) = [n for n in ast.walk(tree) if isinstance(n, ast.Try)]
    digest = hashlib.sha1(
        "\n".join(ast.dump(s) for s in try_node.body).encode()
    ).hexdigest()[:8]
    assert (
        "except-hygiene:tests/fixtures/analysis/bad_except.py:risky:"
        f"silent-swallow@{digest}" in _keys(fixture_findings, "except-hygiene")
    )


def test_fixture_contracts(fixture_findings):
    got = _keys(fixture_findings, "contract")
    base = "contract:tests/fixtures/analysis/bad_contract.py:Servant.setup:"
    assert base + "rpc-unclassified-totally_unclassified" in got
    assert base + "span-not-closed-span" in got
    assert base + "adhoc-latency-buckets-fixture_latency_ms" in got


def test_fixture_guarded_state(fixture_findings):
    got = _keys(fixture_findings, "guarded-state")
    base = "guarded-state:tests/fixtures/analysis/bad_guarded_state.py:"
    assert base + "Stats.racy_write:unguarded-write-count" in got
    assert base + "Stats.racy_rmw:unguarded-rmw-total" in got
    assert base + "Stats.escape:escape-_items" in got


def test_fixture_atomicity(fixture_findings):
    got = _keys(fixture_findings, "atomicity")
    base = "atomicity:tests/fixtures/analysis/bad_atomicity.py:"
    assert base + "Cache.check_then_act:check-then-act-_cache" in got
    assert base + "Cache.start:racy-lazy-init-_started" in got
    assert base + "get_singleton:unlocked-lazy-init-_SINGLETON" in got


def test_fixture_host_sync(fixture_findings):
    got = _keys(fixture_findings, "host-sync")
    base = "host-sync:tests/fixtures/analysis/bad_host_sync.py:wrapper:"
    assert base + "asarray-out" in got
    assert base + "float-out" in got


def test_fixture_dtype_drift(fixture_findings):
    got = _keys(fixture_findings, "dtype-drift")
    base = "dtype-drift:tests/fixtures/analysis/bad_dtype_drift.py:"
    assert base + "drifty:x64-float64" in got
    assert base + "drifty:astype-float" in got
    assert base + "feed:weak-arg-drifty-float-literal-2.0" in got


def test_fixture_program_coherence(fixture_findings):
    got = _keys(fixture_findings, "program-coherence")
    base = "program-coherence:tests/fixtures/analysis/bad_coherence.py:"
    assert base + "orphan:missing-spec-orphan" in got
    assert base + ":pad-off-ladder-100" in got


def test_clean_fixture_has_no_findings(fixture_findings):
    noise = [
        f for f in fixture_findings if f.file.endswith("/clean.py")
    ]
    assert not noise, [f.render() for f in noise]


def test_every_checker_fires_somewhere(fixture_findings):
    """A checker producing nothing over the violation fixtures is broken."""
    fired = {f.checker for f in fixture_findings}
    assert fired == {c.name for c in ALL_CHECKERS}


# -- framework mechanics ------------------------------------------------------


def test_waiver_suppresses_on_line_and_above():
    flagged = _src(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert ExceptionHygieneChecker().run([flagged])
    waived_above = _src(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # analysis: allow(except-hygiene, fixture)\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert not ExceptionHygieneChecker().run([waived_above])
    waived_all = _src(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    # analysis: allow(all, fixture)\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert not ExceptionHygieneChecker().run([waived_all])


def test_baseline_diff_new_and_stale():
    f1 = Finding("c", "a.py", 3, "f", "d1", "m")
    f2 = Finding("c", "a.py", 9, "g", "d2", "m")
    baseline = {f1.key: "accepted", "c:gone.py:h:d3": "paid off"}
    new, stale = diff_findings([f1, f2], baseline)
    assert [f.key for f in new] == [f2.key]
    assert stale == ["c:gone.py:h:d3"]


def test_finding_key_is_line_independent():
    a = Finding("c", "a.py", 3, "f", "d", "m")
    b = Finding("c", "a.py", 300, "f", "d", "m")
    assert a.key == b.key


def test_jitmap_collects_all_three_idioms():
    src = _src(
        "import jax\n"
        "@jax.jit\n"
        "def direct(x):\n"
        "    return x\n"
        "def wrapped_core(x):\n"
        "    return x\n"
        "wrapped = jax.jit(wrapped_core)\n"
        "def maker():\n"
        "    def local(x):\n"
        "        return x\n"
        "    return jax.jit(local)\n"
    )
    jits = jitmap.collect([src])
    names = jitmap.callable_names(jits)
    assert {"direct", "wrapped", "wrapped_core", "local"} <= names


def test_repo_jit_inventory_is_substantial():
    """The package really does carry a fleet of jitted functions — the
    purity/shape checkers must be walking a non-trivial inventory."""
    jits = jitmap.collect(load_sources())
    assert len(jits) >= 15, [j.qualname for j in jits]


# The pinned jit inventory, by NAME (sorted ``file:qualname``). A count
# pin (the previous form) tells a reader "something changed" without
# saying WHAT; the name pin makes the failure self-explanatory and — the
# ISSUE 20 point — is exactly the key set tool/jaxpr_baseline.json must
# cover, so progaudit's coverage/stale diff and this test agree on the
# universe. A new jitted program must be added here AND get a PROGSPEC
# entry (progaudit) AND a tool/warm_cache.py warmer.
PINNED_JIT_PROGRAMS = [
    "fisco_bcos_tpu/crypto/admission.py:_admission_packed",
    "fisco_bcos_tpu/crypto/admission.py:_sm_admission_packed",
    "fisco_bcos_tpu/crypto/admission.py:admission_core",
    "fisco_bcos_tpu/ops/address.py:sender_address_device",
    "fisco_bcos_tpu/ops/bls12_381.py:_multi_pairing_xla",
    "fisco_bcos_tpu/ops/bls12_381.py:_pairing_check_xla",
    "fisco_bcos_tpu/ops/ed25519.py:_verify_xla",
    "fisco_bcos_tpu/ops/keccak.py:keccak256_blocks",
    "fisco_bcos_tpu/ops/merkle.py:_device_root_fn.run",
    "fisco_bcos_tpu/ops/merkle.py:_device_tree_fn.tree",
    "fisco_bcos_tpu/ops/poseidon.py:poseidon_blocks",
    "fisco_bcos_tpu/ops/secp256k1.py:_recover_xla",
    "fisco_bcos_tpu/ops/secp256k1.py:_verify_xla",
    "fisco_bcos_tpu/ops/sha256.py:sha256_blocks",
    "fisco_bcos_tpu/ops/sm2.py:_e_xla",
    "fisco_bcos_tpu/ops/sm2.py:_verify_xla",
    "fisco_bcos_tpu/ops/sm3.py:sm3_blocks",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_admission.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_admission_packed.admission_shard",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_ed25519_verify.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_merkle_root.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_qc_check.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_sm2_verify.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_state_root.local",
    "fisco_bcos_tpu/parallel/sharding.py:sharded_verify.local",
]


def test_repo_jit_inventory_pinned_and_covers_bls():
    """ISSUE 13 satellite, upgraded by ISSUE 20: the inventory is PINNED
    by sorted program NAMES, not a bare count — on drift the assertion
    names exactly which programs appeared and which vanished."""
    progs = jitmap.inventory()
    got = sorted(f"{p['file']}:{p['qualname']}" for p in progs)
    unexpected = sorted(set(got) - set(PINNED_JIT_PROGRAMS))
    vanished = sorted(set(PINNED_JIT_PROGRAMS) - set(got))
    assert got == PINNED_JIT_PROGRAMS, (
        f"jit inventory drifted: +{unexpected} -{vanished} "
        "(update PINNED_JIT_PROGRAMS, the program's PROGSPEC, "
        "tool/jaxpr_baseline.json and tool/warm_cache.py together)"
    )
    bls = [p for p in progs if p["file"] == "fisco_bcos_tpu/ops/bls12_381.py"]
    assert [p["qualname"] for p in bls] == [
        "_pairing_check_xla", "_multi_pairing_xla"
    ]
    pos = [p for p in progs if p["file"] == "fisco_bcos_tpu/ops/poseidon.py"]
    assert [p["qualname"] for p in pos] == ["poseidon_blocks"]
    # every record is CLI-printable (the --list-jit contract)
    for p in progs:
        assert p["line"] > 0 and p["names"], p


def test_exception_checker_accepts_observing_handlers():
    ok = _src(
        "def f(log):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        log.warning('boom %s', e)\n"
    )
    assert not ExceptionHygieneChecker().run([ok])


def test_device_dispatch_seams_are_exempt():
    seam = _src(
        "from ..ops import secp256k1\n", "fisco_bcos_tpu/crypto/suite.py"
    )
    assert not DeviceDispatchChecker().run([seam])
    outside = _src(
        "from ..ops import secp256k1\n", "fisco_bcos_tpu/rpc/api.py"
    )
    assert DeviceDispatchChecker().run([outside])


def test_shape_bucket_passthrough_is_exempt():
    # no array construction -> the shape decision was made upstream
    src = _src(
        "import jax\n"
        "@jax.jit\n"
        "def k(x):\n"
        "    return x\n"
        "def passthrough(arr):\n"
        "    return k(arr)\n"
    )
    assert not ShapeBucketChecker().run([src])


def test_lock_checker_no_cycle_for_consistent_order():
    src = _src(
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            return 1\n"
        "def g():\n"
        "    with A:\n"
        "        with B:\n"
        "            return 2\n"
    )
    assert not [
        f for f in LockOrderChecker().run([src]) if f.detail.startswith("cycle")
    ]


def test_contract_checker_accepts_named_buckets_and_with_spans():
    src = _src(
        "def f(TRACER, REGISTRY, LATENCY_BUCKETS_MS):\n"
        "    with TRACER.span('ok'):\n"
        "        REGISTRY.observe('x_ms', 1.0, buckets=LATENCY_BUCKETS_MS)\n"
    )
    assert not ContractChecker().run([src])


def test_jit_purity_pure_body_passes():
    src = _src(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def k(x):\n"
        "    y = jnp.sum(x)\n"
        "    return y * 2\n"
    )
    assert not JitPurityChecker().run([src])


def test_guarded_state_locked_suffix_and_init_exempt():
    src = _src(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0\n"  # init writes never flag
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "    def _bump_locked(self):\n"
        "        self.n += 1\n"  # caller-holds-the-lock convention
    )
    assert not GuardedStateChecker().run([src])


def test_guarded_state_condition_aliases_its_lock():
    src = _src(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "        self.n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "    def b(self):\n"
        "        with self._cv:\n"  # holding the cv IS holding the lock
        "            self.n += 1\n"
    )
    assert not GuardedStateChecker().run([src])


def test_guarded_state_copy_return_passes_reference_fails():
    base = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._d = {}\n"
        "    def put(self, k):\n"
        "        with self._lock:\n"
        "            self._d[k] = k\n"
    )
    leaky = _src(base + "    def snap(self):\n        return self._d\n")
    found = GuardedStateChecker().run([leaky])
    assert any(f.detail == "escape-_d" for f in found), found
    copied = _src(base + "    def snap(self):\n        return dict(self._d)\n")
    assert not GuardedStateChecker().run([copied])


def test_atomicity_double_checked_locking_passes():
    src = _src(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._x = None\n"
        "    def get(self):\n"
        "        if self._x is None:\n"
        "            with self._lock:\n"
        "                if self._x is None:\n"
        "                    self._x = object()\n"
        "        return self._x\n"
    )
    assert not AtomicityChecker().run([src])
    racy = _src(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._x = None\n"
        "    def get(self):\n"
        "        if self._x is None:\n"
        "            self._x = object()\n"
        "        return self._x\n"
    )
    assert [f.detail for f in AtomicityChecker().run([racy])] == [
        "racy-lazy-init-_x"
    ]


def test_atomicity_module_singleton_double_checked_passes():
    src = _src(
        "import threading\n"
        "_X = None\n"
        "_L = threading.Lock()\n"
        "def get():\n"
        "    global _X\n"
        "    if _X is None:\n"
        "        with _L:\n"
        "            if _X is None:\n"
        "                _X = object()\n"
        "    return _X\n"
    )
    assert not AtomicityChecker().run([src])


def test_cli_list_and_checker_filter(capsys):
    from fisco_bcos_tpu.analysis.__main__ import main

    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for c in ALL_CHECKERS:
        assert c.name in out
        assert getattr(c, "description", "")  # every checker documents itself
    # filtered run: clean, and other checkers' baselined debt is NOT stale
    assert main(["--checker", "guarded-state,atomicity"]) == 0
    assert main(["--checker", "nope"]) == 2


# -- runtime lock-order recorder ---------------------------------------------


def _locks(rec: LockOrderRecorder):
    return (
        InstrumentedLock("fisco_bcos_tpu/m.py:1", rec),
        InstrumentedLock("fisco_bcos_tpu/m.py:2", rec),
    )


def test_recorder_consistent_order_no_cycle():
    rec = LockOrderRecorder()
    a, b = _locks(rec)
    for _ in range(2):
        with a:
            with b:
                pass
    assert rec.cycles() == []
    assert rec.edges[("fisco_bcos_tpu/m.py:1", "fisco_bcos_tpu/m.py:2")][1] == 2


def test_recorder_detects_inversion_cycle():
    rec = LockOrderRecorder()
    a, b = _locks(rec)
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert rec.cycles() == [["fisco_bcos_tpu/m.py:1", "fisco_bcos_tpu/m.py:2"]]


def test_recorder_cross_thread_inversion():
    """The real deadlock shape: each order taken by a DIFFERENT thread."""
    rec = LockOrderRecorder()
    a, b = _locks(rec)

    def t1():
        with a:
            with b:
                pass

    def t2():
        with b:
            with a:
                pass

    th1 = threading.Thread(target=t1)
    th1.start()
    th1.join()
    th2 = threading.Thread(target=t2)
    th2.start()
    th2.join()
    assert rec.cycles() == [["fisco_bcos_tpu/m.py:1", "fisco_bcos_tpu/m.py:2"]]


def test_recorder_rlock_reentry_records_nothing():
    rec = LockOrderRecorder()
    r = InstrumentedRLock("fisco_bcos_tpu/m.py:9", rec)
    with r:
        with r:
            pass
    assert rec.edges == {}
    assert rec.held_sites() == ()


def test_recorder_condition_roundtrip_keeps_chain_exact():
    rec = LockOrderRecorder()
    r = InstrumentedRLock("fisco_bcos_tpu/m.py:5", rec)
    cv = threading.Condition(r)
    with cv:
        assert rec.held_sites() == ("fisco_bcos_tpu/m.py:5",)
        cv.wait(timeout=0.01)  # _release_save / _acquire_restore round-trip
        assert rec.held_sites() == ("fisco_bcos_tpu/m.py:5",)
    assert rec.held_sites() == ()


def test_recorder_blocking_guard_excludes_own_file():
    rec = LockOrderRecorder()
    own = InstrumentedLock("fisco_bcos_tpu/service/rpc.py:300", rec)
    foreign = InstrumentedLock("fisco_bcos_tpu/txpool/txpool.py:78", rec)
    with own:
        rec.note_blocking("rpc.send", exclude_file="fisco_bcos_tpu/service/rpc.py")
    assert rec.blocking_violations == []
    with foreign:
        rec.note_blocking("rpc.send", exclude_file="fisco_bcos_tpu/service/rpc.py")
    assert len(rec.blocking_violations) == 1
    what, held, _thread = rec.blocking_violations[0]
    assert what == "rpc.send" and held == ("fisco_bcos_tpu/txpool/txpool.py:78",)


def test_recorder_waiver_forbid_scopes_the_hold():
    from fisco_bcos_tpu.analysis.lockorder import Waiver

    rec = LockOrderRecorder()
    sched = InstrumentedRLock("fisco_bcos_tpu/scheduler/scheduler.py:82", rec)
    rec.allowed_blocking = {
        "fisco_bcos_tpu/scheduler/scheduler.py": Waiver(
            "execute path only", forbid=("/prepare", "/commit")
        )
    }
    with sched:
        # execute-path RPC under the waived lock: allowed
        rec.note_blocking("rpc.send_frame:h:1/execute_transactions")
        assert rec.blocking_violations == []
        # a forbidden 2PC verb under the same lock: violation despite waiver
        rec.note_blocking("rpc.send_frame:h:1/prepare")
    assert len(rec.blocking_violations) == 1
    what, held, _thread = rec.blocking_violations[0]
    assert what == "rpc.send_frame:h:1/prepare"
    assert held == ("fisco_bcos_tpu/scheduler/scheduler.py:82",)
    # plain-string entries keep waiving unconditionally
    rec2 = LockOrderRecorder()
    lock = InstrumentedLock("fisco_bcos_tpu/consensus/engine.py:50", rec2)
    rec2.allowed_blocking = {"fisco_bcos_tpu/consensus/engine.py": "pbft"}
    with lock:
        rec2.note_blocking("rpc.send_frame:h:1/prepare")
    assert rec2.blocking_violations == []


def test_recorder_nonblocking_acquire_failure_not_recorded():
    rec = LockOrderRecorder()
    a, b = _locks(rec)
    a.acquire()
    try:
        got = a._inner.acquire(False)  # simulate: someone else holds it
        assert not got
        with b:
            assert not a._inner.acquire(False)
        # failed tries must not have pushed anything
        assert rec.held_sites() == ("fisco_bcos_tpu/m.py:1",)
    finally:
        a.release()


def test_factory_filter_instruments_only_package_code():
    from fisco_bcos_tpu.analysis import lockorder

    installed_before = lockorder._installed
    lockorder.install()
    try:
        # a caller whose compiled filename lies inside the package tree
        ns: dict = {}
        code = compile(
            "import threading\nL = threading.Lock()\nR = threading.RLock()\n",
            os.path.join("fisco_bcos_tpu", "fake", "mod.py"),
            "exec",
        )
        exec(code, ns)
        assert isinstance(ns["L"], InstrumentedLock)
        assert isinstance(ns["R"], InstrumentedRLock)
        assert ns["L"]._site.startswith("fisco_bcos_tpu/fake/mod.py:")
        # this test file is NOT package code -> raw lock
        raw = threading.Lock()
        assert not isinstance(raw, InstrumentedLock)
    finally:
        if not installed_before:
            lockorder.uninstall()


def test_cli_json_clean(capsys):
    from fisco_bcos_tpu.analysis.__main__ import main

    assert main(["--format=json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["new"] == []
    assert out["total_findings"] >= 2  # the baselined by-design debt
