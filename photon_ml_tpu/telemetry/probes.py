"""Device/runtime probes: compile counts, HBM bytes, the runtime stamp.

Reference parity: no reference analogue — Photon-ML leaned on the Spark UI
for executor/runtime attribution (SURVEY.md §5).

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
platform is chosen (driver startup order).
"""

from __future__ import annotations

from photon_ml_tpu.telemetry.registry import default_registry

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
