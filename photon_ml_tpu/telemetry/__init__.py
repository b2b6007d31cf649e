"""Structured run telemetry: metrics registry, JSONL run journal, solver
convergence tracing, device/runtime probes.

Reference parity: the PhotonLogger / OptimizationStatesTracker /
PhotonOptimizationLogEvent triple (photon-lib util/PhotonLogger.scala:34-90,
OptimizationStatesTracker.scala:82-101, photon-client event/ emitted from
Driver.scala:120-393) rebuilt as one subsystem the whole stack emits
through — see each submodule's docstring for its slice of the map.
"""

from photon_ml_tpu.telemetry.journal import (
    JOURNAL_FILENAME,
    JOURNAL_PARTIAL_SUFFIX,
    RunJournal,
    json_safe,
    read_journal,
)
from photon_ml_tpu.telemetry.layout import (
    LAYOUT_METRIC_PREFIX,
    record_hybrid_layout,
    record_tail_layout,
    reset_layout_metrics,
)
from photon_ml_tpu.telemetry.probes import (
    CompileMonitor,
    compile_count,
    install_compile_listener,
    live_buffer_bytes,
)
from photon_ml_tpu.telemetry.program_ledger import (
    ProgramLedger,
    current_ledger,
    install_ledger,
    ledger_active,
    ledger_jit,
    uninstall_ledger,
)
from photon_ml_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from photon_ml_tpu.telemetry.tracing import (
    Tracer,
    current_tracer,
    exchange_wait_tables,
    finalize_trace,
    flush_trace_best_effort,
    gather_straggler_report,
    install_tracer,
    publish_trace,
    span,
    straggler_report,
    tracing_active,
    uninstall_tracer,
)
# solver_trace pulls jax/flax (via optim.common); load it lazily so that
# importing the registry/journal/probes side of telemetry — which util.timed
# does on every import — stays jax-free (the drivers/conftest configure the
# platform before jax ever loads).
_LAZY = {
    "SolverTelemetry": "photon_ml_tpu.telemetry.solver_trace",
    "lane_rows": "photon_ml_tpu.telemetry.solver_trace",
    "lane_summary": "photon_ml_tpu.telemetry.solver_trace",
    "solver_result_row": "photon_ml_tpu.telemetry.solver_trace",
}


def __getattr__(name: str):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(module), name)


__all__ = [
    "JOURNAL_FILENAME",
    "JOURNAL_PARTIAL_SUFFIX",
    "RunJournal",
    "json_safe",
    "read_journal",
    "LAYOUT_METRIC_PREFIX",
    "record_hybrid_layout",
    "record_tail_layout",
    "reset_layout_metrics",
    "CompileMonitor",
    "compile_count",
    "install_compile_listener",
    "live_buffer_bytes",
    "ProgramLedger",
    "current_ledger",
    "install_ledger",
    "ledger_active",
    "ledger_jit",
    "uninstall_ledger",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "SolverTelemetry",
    "lane_rows",
    "lane_summary",
    "solver_result_row",
    "Tracer",
    "current_tracer",
    "exchange_wait_tables",
    "finalize_trace",
    "flush_trace_best_effort",
    "gather_straggler_report",
    "install_tracer",
    "publish_trace",
    "span",
    "straggler_report",
    "tracing_active",
    "uninstall_tracer",
]
