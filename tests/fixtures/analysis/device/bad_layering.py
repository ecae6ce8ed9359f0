"""Fixture: a module below the crypto seams (its directory is ``device``)
importing crypto/ — the arrow that points up. The plain reference passes."""

from fisco_bcos_tpu.crypto.ref import ecdsa  # noqa: F401  (exempt: a leaf)
from fisco_bcos_tpu.crypto.suite import ecdsa_suite  # noqa: F401  (device-dispatch)
