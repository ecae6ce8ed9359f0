"""Structured logging with METRIC-style key/value stage lines.

Reference: bcos-utilities/Log.h LOG_BADGE/LOG_KV/LOG_DESC macros and the METRIC
badge (bcos-framework/Common.h:24) that the mtail sidecar scrapes into Prometheus
gauges. We emit the same shape: ``[badge] desc|k1=v1|k2=v2``.
"""

from __future__ import annotations

import logging
import threading
from typing import Any

_FORMAT = "%(asctime)s %(levelname)s [%(name)s] %(message)s"
_configured = False
_CONFIGURE_LOCK = threading.Lock()


def _configure() -> None:
    global _configured
    if not _configured:
        # double-checked: basicConfig is NOT idempotent when two threads
        # race it before the root logger has handlers (duplicate handlers
        # double every log line from then on)
        with _CONFIGURE_LOCK:
            if not _configured:
                logging.basicConfig(level=logging.INFO, format=_FORMAT)
                _configured = True


def get_logger(name: str) -> logging.Logger:
    _configure()
    return logging.getLogger(name)


def kv_line(badge: str, desc: str, **kvs: Any) -> str:
    parts = [f"[{badge}]", desc]
    for k, v in kvs.items():
        parts.append(f"{k}={v}")
    return "|".join(parts)


def metric(logger: logging.Logger, desc: str, **kvs: Any) -> None:
    """Emit a METRIC line (scrapeable, mirrors the reference's mtail contract)."""
    logger.info(kv_line("METRIC", desc, **kvs))


def note_swallowed(site: str, exc: BaseException | None = None) -> None:
    """Observe an intentionally-swallowed error instead of erasing it.

    The except-hygiene analyzer (``fisco_bcos_tpu.analysis``) forbids broad
    handlers whose body does nothing; every tolerated failure routes through
    here so operators can see error *mass* per site even at INFO level:
    a debug log line plus ``fisco_swallowed_errors_total{site=...}``.
    """
    try:
        from .metrics import REGISTRY

        REGISTRY.counter_add(
            f'fisco_swallowed_errors_total{{site="{site}"}}',
            1.0,
            help="errors intentionally swallowed (tolerated), by site",
        )
    # analysis: allow(except-hygiene, the swallow observer itself must never raise)
    except Exception:
        pass
    if exc is not None:
        logging.getLogger("fisco.swallowed").debug(
            "swallowed at %s: %r", site, exc
        )
