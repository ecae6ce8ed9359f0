"""Device verification plane — shared, shape-bucketed batch scheduling for
all device crypto (see :mod:`.plane`, :mod:`.dispatch` — the one place
that decides where a batch runs — and docs/device_plane.md)."""

from .plane import (  # noqa: F401
    DEFAULT_LANE,
    LANES,
    DevicePlane,
    PlaneRequest,
    current_lane,
    device_lane,
    get_plane,
    in_plane_executor,
)
