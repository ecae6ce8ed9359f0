"""The benchmark's own spans around its calls into each layer: host clock in
memory, and the same name as a ``jax.profiler.TraceAnnotation`` so a traced
run puts them on the device trace's clock."""

from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.rows: list[tuple[str, float, float]] = []  # name, start, end

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float, hi: float) -> list[float]:
        """Seconds of each ``name`` span that started inside [lo, hi)."""
        return [b - a for n, a, b in self.rows if n == name and lo <= a < hi]
