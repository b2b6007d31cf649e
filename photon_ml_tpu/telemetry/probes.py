"""Device/runtime probes: compile counts, HBM bytes, marginal timing.

Reference parity: no reference analogue — Photon-ML leaned on the Spark UI
for executor/runtime attribution (SURVEY.md §5). The measurement helpers
live here as a library instead of inside ``bench.py``:

- ``MarginalTimer`` / ``scan_step_marginal``: K_hi-vs-K_lo differencing of
  K evaluations inside ONE jit, ending on a host read. The difference
  cancels every fixed per-call cost (dispatch, launch, the host read) so
  what remains is device time per evaluation.
- ``stream_calibration``: a same-run one-X-read matvec probe
  (``fe_hot_loop_stream_gbps``) as a callable, so an experiment can state
  its hot loop as a fraction of what this chip streamed in this process.
- ``install_compile_listener`` / ``CompileMonitor``: jax.monitoring hook
  counting backend compiles (recompilation storms are a classic silent
  perf pathology under vmap/jit churn) and, beside them, what a program
  cost before it could run: seconds tracing, seconds lowering, seconds
  loading executables from the persistent cache, and that cache's hits and
  misses.
- ``device_memory_stats`` / ``live_buffer_bytes`` /
  ``device_memory_report``: allocator statistics per device. The ``cpu``
  platform reports none (``memory_stats()`` is None there); on ``tpu`` a
  missing statistic is an error, not a None.
- ``runtime_stamp``: platform, device kind and count, jax / jaxlib / libtpu
  versions and the compile-cache directory — what every driver writes into
  its run summary so a reader of the summary knows what ran it.

Everything imports jax lazily so this module is safe to import before the
platform is chosen (bench.py / driver startup order).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable

import numpy as np

from photon_ml_tpu.telemetry.registry import default_registry

#: median-of-K reps for gate metrics: a one-chip machine shares its host's
#: cores, so single-shot host-clock numbers spread
GATE_REPS = 3


def median_spread(measure_once: Callable[[], float], reps: int = GATE_REPS):
    """Run a marginal measurement ``reps`` times; return
    (median, [min, max]) — the spread is the error bar to quote with it."""
    vals = [measure_once() for _ in range(reps)]
    return statistics.median(vals), [min(vals), max(vals)]


def read_scalar(x) -> float:
    """Host-read synchronization point: returns float(x), which waits for
    the device to produce it (same wait as ``block_until_ready``, plus the
    copy of one scalar)."""
    return float(np.asarray(x).ravel()[0])


@dataclasses.dataclass
class MarginalResult:
    median: float  # marginal seconds per unit of work
    spread: list  # [min, max] across reps


@dataclasses.dataclass
class MarginalTimer:
    """K_hi-vs-K_lo marginal differencing over an arbitrary timed unit.

    ``measure(timed_k)`` calls ``timed_k(k)`` — which must run ``k`` units
    of work and return elapsed seconds, ending on a host read (use
    :func:`read_scalar`) — and returns the per-unit marginal
    ``(t(k_hi) - t(k_lo)) / (k_hi - k_lo)`` as a median-of-``reps`` with
    [min, max] spread. Differencing cancels the fixed per-call cost;
    ``k_hi - k_lo`` must be large enough that device time dwarfs the
    call-to-call jitter of that fixed cost, or marginals can come out
    negative."""

    k_lo: int = 1
    k_hi: int = 5
    reps: int = GATE_REPS
    floor: float = 1e-6

    def __post_init__(self):
        if self.k_hi <= self.k_lo:
            raise ValueError(f"k_hi ({self.k_hi}) must exceed k_lo ({self.k_lo})")

    def measure(self, timed_k: Callable[[int], float]) -> MarginalResult:
        def once() -> float:
            lo = timed_k(self.k_lo)
            hi = timed_k(self.k_hi)
            return max((hi - lo) / (self.k_hi - self.k_lo), self.floor)

        median, spread = median_spread(once, self.reps)
        return MarginalResult(median=median, spread=spread)


def scan_step_marginal(
    step_fn,
    operand,
    dim: int,
    *,
    k_lo: int = 16,
    k_hi: int = 256,
    reps: int = GATE_REPS,
    warmups: int = 4,
    rng=None,
) -> tuple[float, list]:
    """Marginal seconds per evaluation of ``step_fn(w, operand) -> (w', v)``.

    K evaluations run inside ONE jit via ``lax.scan`` (so the K_hi-K_lo
    delta is pure device time), every step consumes the carry (XLA hoists
    loop-invariant work such as ``X @ w0`` out of the scan otherwise),
    warm starts are perturbed per rep, and timing ends on a host read. Returns ``(median, [min, max])`` like :func:`median_spread`."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7) if rng is None else rng

    def timed(k: int) -> float:
        @jax.jit
        def run(w0, op):
            w, vs = jax.lax.scan(
                lambda w, _: step_fn(w, op), w0, None, length=k
            )
            return vs.sum() + w.sum()

        float(run(jnp.zeros(dim, jnp.float32), operand))  # compile + sync
        best = None
        for _ in range(warmups):
            w0 = jnp.asarray(rng.normal(size=dim).astype(np.float32)) * 0.01
            t0 = time.perf_counter()
            float(run(w0, operand))
            el = time.perf_counter() - t0
            best = el if best is None or el < best else best
        return best

    return median_spread(
        lambda: max((timed(k_hi) - timed(k_lo)) / (k_hi - k_lo), 1e-6), reps
    )


def stream_calibration(
    features,
    *,
    k_lo: int = 16,
    k_hi: int = 256,
    reps: int = GATE_REPS,
    rng=None,
) -> dict:
    """Same-run calibration: achieved GB/s of one [n, d] matvec X read per
    step, so hot-loop times can be stated as fractions of a one-pass
    stream measured in the same process. The probe is an XLA matvec, not
    a bandwidth ceiling: a fraction above 1.0 is possible."""
    import jax.numpy as jnp

    n, d = features.shape
    xbytes = n * d * features.dtype.itemsize

    def step(w, x):
        return w + jnp.sum(x @ w) * 1e-30, jnp.float32(0)

    marginal, spread = scan_step_marginal(
        step, features, d, k_lo=k_lo, k_hi=k_hi, reps=reps, rng=rng
    )
    return {
        "gbps": xbytes / marginal / 1e9,
        "spread_gbps": [xbytes / s / 1e9 for s in spread[::-1]],
        "marginal_sec": marginal,
        "spread_sec": spread,
        "bytes_per_eval": xbytes,
        "n": int(n),
        "d": int(d),
    }


# --- compile-event monitoring (jax.monitoring) ------------------------------

#: registry names of the backend-compile counter/histogram the listener
#: feeds — public so the program ledger (telemetry/program_ledger.py) can
#: take scoped deltas against them and heartbeats can snapshot the count
COMPILE_COUNT_METRIC = "jax/backend_compile_count"
COMPILE_SECONDS_METRIC = "jax/backend_compile_seconds"
_COMPILE_COUNTER = COMPILE_COUNT_METRIC
_COMPILE_SECONDS = COMPILE_SECONDS_METRIC
#: histograms of seconds (``.total`` is the process's sum so far): tracing
#: Python into jaxprs, lowering jaxprs to MLIR modules, and reading +
#: deserialising executables from the persistent compile cache
TRACE_SECONDS_METRIC = "jax/trace_seconds"
LOWER_SECONDS_METRIC = "jax/lower_seconds"
CACHE_LOAD_SECONDS_METRIC = "jax/cache_load_seconds"
#: counters of persistent-cache look-ups that were answered / that compiled
#: and wrote an entry
CACHE_HITS_METRIC = "jax/cache_hits"
CACHE_MISSES_METRIC = "jax/cache_misses"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_DURATION_EVENTS = {
    _TRACE_EVENT: TRACE_SECONDS_METRIC,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER_SECONDS_METRIC,
    "/jax/core/compile/backend_compile_duration": COMPILE_SECONDS_METRIC,
    "/jax/compilation_cache/cache_retrieval_time_sec":
        CACHE_LOAD_SECONDS_METRIC,
}
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": CACHE_HITS_METRIC,
    "/jax/compilation_cache/cache_misses": CACHE_MISSES_METRIC,
}
#: registries that already have a listener feeding them (the listener holds
#: a strong reference, so the id() stays unique for the registry's lifetime)
_installed_registry_ids: set[int] = set()


def install_compile_listener(registry=None) -> None:
    """Idempotently (per registry) install the jax.monitoring listeners
    that file JAX's compile-path events in the metrics registry: backend
    compiles (count and seconds), trace / lower / cache-load seconds, cache
    hits and misses. jax.monitoring has no targeted unregister, so each
    listener installs once per (process, registry) and stays.

    JAX reports a trace's seconds when the trace ends, and a function
    traced INSIDE another trace (a nested jit, every ``jnp`` primitive
    wrapper) reports its own event too, inside the outer one's time: only
    events that arrive with no trace in flight (the outermost) are added,
    so ``jax/trace_seconds`` counts no second twice. Lowering, compiling
    and cache look-ups happen once per dispatched program and do not nest."""
    reg = registry or default_registry()
    if id(reg) in _installed_registry_ids:
        return
    import jax.core
    import jax.monitoring

    def _on_duration(name: str, secs: float, **kw) -> None:
        metric = _DURATION_EVENTS.get(name)
        if metric is None or (
            name == _TRACE_EVENT and not jax.core.trace_ctx.is_top_level()
        ):
            return
        reg.histogram(metric).observe(secs)
        if metric == COMPILE_SECONDS_METRIC:
            reg.counter(_COMPILE_COUNTER).inc()

    def _on_event(name: str, **kw) -> None:
        metric = _COUNT_EVENTS.get(name)
        if metric is not None:
            reg.counter(metric).inc()

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _installed_registry_ids.add(id(reg))
    # every metric exists from here on: a reader tells "none yet" (0) from
    # "no listener in this program" (absent)
    for metric in _DURATION_EVENTS.values():
        reg.histogram(metric)
    for metric in (_COMPILE_COUNTER, *_COUNT_EVENTS.values()):
        reg.counter(metric)


def compile_count(registry=None) -> int:
    """Backend compiles observed since :func:`install_compile_listener`."""
    reg = registry or default_registry()
    return reg.counter(_COMPILE_COUNTER).value


class CompileMonitor:
    """``with CompileMonitor() as cm: ...; cm.count`` — compiles (and compile
    seconds) attributable to the enclosed block."""

    def __init__(self, registry=None):
        self.registry = registry or default_registry()
        # snapshot at construction too, so count/seconds are well-defined
        # even when read from a finally block after __enter__ failed
        self._count0 = self.registry.counter(_COMPILE_COUNTER).value
        self._secs0 = self.registry.histogram(_COMPILE_SECONDS).total

    def __enter__(self) -> "CompileMonitor":
        install_compile_listener(self.registry)
        self._count0 = self.registry.counter(_COMPILE_COUNTER).value
        self._secs0 = self.registry.histogram(_COMPILE_SECONDS).total
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @property
    def count(self) -> int:
        return self.registry.counter(_COMPILE_COUNTER).value - self._count0

    @property
    def seconds(self) -> float:
        return self.registry.histogram(_COMPILE_SECONDS).total - self._secs0


def device_memory_stats(device=None) -> "dict | None":
    """``device.memory_stats()``: the allocator's counters on ``tpu``, None
    on ``cpu`` (whose client keeps none). A ``tpu`` device without them is
    an error — a summary that silently lost its HBM numbers reads like one
    that never had any."""
    import jax

    dev = device or jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats is None and dev.platform == "tpu":
        raise RuntimeError(f"{dev} reports no memory_stats()")
    return stats


def live_buffer_bytes(device=None) -> int:
    """Live device-buffer bytes: allocator ``bytes_in_use`` on ``tpu``, the
    sum over ``jax.live_arrays()`` on the virtual CPU mesh."""
    import jax

    stats = device_memory_stats(device)
    if stats is not None:
        return int(stats["bytes_in_use"])
    return int(sum(a.nbytes for a in jax.live_arrays()))


def device_memory_limit_bytes(device=None) -> "int | None":
    """Allocator ``bytes_limit`` on ``tpu``; None on ``cpu`` — the budget the
    program ledger's HBM-overcommit forecast is judged against."""
    stats = device_memory_stats(device)
    return None if stats is None else int(stats["bytes_limit"])


def device_memory_report() -> list:
    """One row per local device — id, ``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_limit`` (None on ``cpu``) — so a mesh run
    shows whether every chip held data, not only device 0."""
    import jax

    rows = []
    for dev in jax.local_devices():
        stats = device_memory_stats(dev) or {}
        rows.append({
            "id": int(dev.id),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return rows


def runtime_stamp() -> dict:
    """What ran this process, as the run summaries record it."""
    import importlib.metadata

    import jax
    import jaxlib

    from photon_ml_tpu.util.compile_cache import cache_dir_in_use

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "process_count": jax.process_count(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "libtpu_version": libtpu,
        "compile_cache_dir": cache_dir_in_use(),
        "device_memory": device_memory_report(),
    }
