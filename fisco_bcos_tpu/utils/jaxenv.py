"""JAX process set-up shared by every entry point: where the persistent
compile cache lives, which device the process landed on, and the CPU pin
the test tier and the ``tool/check_*.py`` smokes run under.

One process owns a chip: whoever initialises a JAX backend first holds the
device until it exits, and a child that needs it then fails or hangs. So
orchestrating parents (``chip_smoke.py``,
``__graft_entry__.dryrun_multichip``) never call anything here that touches
a backend — only :func:`configure_compile_cache`, which is config-only.
"""

from __future__ import annotations

import os

from .log import get_logger

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")

# tests and CPU smokes check results, not speed: the EC programs are
# ~148k-equation graphs, and LLVM at full optimisation spends minutes on
# each where level 0 spends a third of that
_CPU_FAST_COMPILE_FLAGS = (
    "--xla_backend_optimization_level=0 "
    "--xla_llvm_disable_expensive_passes=true"
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one home and return
    the directory in effect. Call before the first compile; initialises no
    backend.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of the variable
    stands and no directory is set from code. Unset:
    ``<checkout>/.jax_cache`` — a fixed path, because a cache that moves
    between runs never hits. Every program is persisted, however fast it
    compiled: the point of the cache is that the second process compiles
    nothing (a node recompiling at each boot sits inside its consensus
    timeout while it does)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


_IDENTITY: dict | None = None


def device_identity() -> dict:
    """``{"platform", "device_kind", "count"}`` as JAX reports the default
    backend. Initialises the backend (and so takes the chip); a backend that
    fails to initialise raises — "no accelerator" is an error the caller
    sees, never a quiet CPU. Cached: identity cannot change in a process."""
    global _IDENTITY
    # analysis: allow(atomicity, idempotent memo — racing initialisers both
    # compute the same immutable backend identity, last write wins harmlessly)
    if _IDENTITY is None:
        import jax

        devices = jax.devices()
        _IDENTITY = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
        }
        get_logger("device").info(
            "jax backend up: platform=%s device_kind=%s count=%d",
            _IDENTITY["platform"], _IDENTITY["device_kind"], _IDENTITY["count"],
        )
    return dict(_IDENTITY)


def device_backend_is_cpu() -> bool:
    """True when the jax device plane is CPU XLA (no accelerator): batch
    dispatchers then prefer the native host loops at every size
    (device/dispatch.py), tree levels hash on the host (ops/merkle.py) and
    the plane keeps no coalescing window. Rides :func:`device_identity`'s
    memo; a backend that fails to initialise raises — "no chip" must never
    read as "CPU"."""
    return device_identity()["platform"] == "cpu"


def pin_cpu(virtual_devices: int | None = None) -> None:
    """Hold this process to the CPU backend for correctness runs (the test
    tier, ``tool/check_*.py``): CPU platform, fast-compile XLA flags, the
    shared compile cache and the one 32-lane batch bucket that keeps the EC
    compiles to a single shape. Must run before the first backend
    initialisation; ``virtual_devices`` splits the host into that many
    devices so mesh code has something to shard over."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if virtual_devices and "xla_force_host_platform_device_count" not in flags:
        flags += f" --xla_force_host_platform_device_count={virtual_devices}"
    if "xla_backend_optimization_level" not in flags:
        flags += " " + _CPU_FAST_COMPILE_FLAGS
    os.environ["XLA_FLAGS"] = flags.strip()
    os.environ.setdefault("FISCO_TEST_BUCKET", "32")
    import jax

    jax.config.update("jax_platforms", "cpu")
    configure_compile_cache()
