"""Named host spans on the profiler's clock.

``span(name)`` is a context manager: it enters a
``jax.profiler.TraceAnnotation(name)``, so under ``jax.profiler`` the span
lands on the host plane of the trace, on the same clock as the device's
operations; it also times itself (``.seconds``, from
``time.perf_counter_ns()``) whether or not a profiler is running.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class span:
    """Time a host step under ``name``."""

    __slots__ = ("name", "start_ns", "end_ns", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9
