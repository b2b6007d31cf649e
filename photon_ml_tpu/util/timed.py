"""Wall-clock profiling of named blocks.

Reference parity: photon-lib util/Timed.scala:33-77 — ``Timed("name"){...}``
logs the duration of the block; used pervasively by the drivers and the
coordinate-descent loop. Here a context manager / decorator; durations feed
the process-wide metrics registry (telemetry/registry.py histograms under
``timing/<name>``) so drivers can print a phase summary with distribution
stats. The histogram and the log line are always on; the block's place in a
timeline comes from the one span seam (telemetry/tracing.py ``span``): one
event per block, in the profiler's trace while a session is active and in
the run tracer's ring when one is installed, so driver phases frame the
finer seam spans. A bare phase label (``"read training data"``) is filed as
the span ``phase/<label>``; a name that already stands in a namespace
(``pack/group_entities``) keeps it. ``begin <name>`` is logged at DEBUG
when a block opens, so that the last line of a hung run names the open
phase.
"""

from __future__ import annotations

import contextlib
import logging
import time
from functools import wraps

from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.telemetry.registry import default_registry

logger = logging.getLogger("photon_ml_tpu.timing")

#: registry namespace for phase timings
_TIMING_PREFIX = "timing/"


class Timed(contextlib.AbstractContextManager):
    """``with Timed("read training data"): ...`` — logs and records.
    ``attrs`` go to the block's span."""

    def __init__(self, name: str, log_level: int = logging.INFO, **attrs):
        self.name = name
        self.log_level = log_level
        self.attrs = attrs
        self.duration: float | None = None

    def __enter__(self):
        logger.debug("begin %s", self.name)
        name = self.name if "/" in self.name else "phase/" + self.name
        self._span = tracing.span(name, cat="phase", **self.attrs)
        self._span.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.duration = time.perf_counter() - self._start
        self._span.__exit__(exc_type, exc, tb)
        default_registry().histogram(_TIMING_PREFIX + self.name).observe(
            self.duration
        )
        logger.log(self.log_level, "%s took %.3f s", self.name, self.duration)
        return False


def timed(name: str | None = None):
    """Decorator form of Timed."""

    def decorate(fn):
        label = name or fn.__qualname__

        @wraps(fn)
        def wrapper(*args, **kwargs):
            with Timed(label):
                return fn(*args, **kwargs)

        return wrapper

    return decorate


@contextlib.contextmanager
def profile_trace(log_dir: str | None):
    """Capture a jax.profiler device trace for the enclosed block.

    ``with profile_trace("/tmp/trace"): train()`` writes a TensorBoard-
    loadable trace (XLA op timeline, HBM usage) — the TPU-native upgrade of
    the reference's wall-clock-only Timed blocks (util/Timed.scala:33-77;
    it had no device-level tracing, SURVEY.md §5). A None/empty ``log_dir``
    disables tracing so drivers can pass their flag through unconditionally.
    """
    if not log_dir:
        yield
        return
    import jax.profiler

    with jax.profiler.trace(str(log_dir)):
        yield
    logger.info("jax profiler trace written to %s", log_dir)


def timing_summary() -> dict[str, dict[str, float]]:
    """name -> {count, total, mean, min, max, p50, p95} over everything
    timed so far (the ``timing/`` histograms of the metrics registry)."""
    return {
        name[len(_TIMING_PREFIX):]: hist.summary()
        for name, hist in default_registry().histograms(_TIMING_PREFIX).items()
        if hist.count
    }


def reset_timings() -> None:
    default_registry().remove_prefix(_TIMING_PREFIX)
