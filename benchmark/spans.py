"""Host spans the benchmark puts round its own calls into the program.

Each span is kept in memory on the host clock (``program_span`` metrics read
these) and, through ``jax.profiler.TraceAnnotation``, written into the
profiler's trace as ``bench:<name>`` so that the trace reduction can name
what the host was doing in every idle gap of the device.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.closed: list[tuple[str, float, float]] = []  # name, start, end
        self._open: dict[str, tuple[float, object]] = {}

    def begin(self, name: str) -> None:
        import jax

        note = jax.profiler.TraceAnnotation(f"bench:{name}")
        note.__enter__()
        self._open[name] = (time.perf_counter(), note)

    def end(self, name: str) -> None:
        start, note = self._open.pop(name)
        note.__exit__(None, None, None)
        self.closed.append((name, start, time.perf_counter()))

    def is_open(self, name: str) -> bool:
        return name in self._open

    @contextlib.contextmanager
    def span(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)

    def durations(self, name: str, since: float = 0.0) -> list[float]:
        return [e - s for n, s, e in self.closed if n == name and s >= since]
