"""Solver telemetry adapter: SolverResult / LaneTrace -> events + journal.

Reference parity: photon-client event/PhotonOptimizationLogEvent (per-
coordinate-update optimization telemetry emitted from Driver.scala:120-393)
+ photon-lib OptimizationStatesTracker.scala:82-101 (the per-iteration state
table reported across coordinates). This module closes that parity gap for
every solve shape in the stack:

- a single un-vmapped solve (the fixed-effect coordinate, sequential
  ``train_glm`` λ steps) → one ``convergence`` row with iteration count,
  convergence reason, value and gradient norm, plus the trimmed
  per-iteration value history;
- vmapped lanes (λ-grid lanes, random-effect entity buckets) → per-lane
  rows (capped) and a ``convergence_lanes`` tally of reasons across lanes,
  so pathologies like "every lane pays max_iter" (CLAUDE.md) show up as
  ``reasons: {"MAX_ITERATIONS": <all lanes>}`` instead of staying silent.

The adapter fans out to any of: a RunJournal (JSONL rows), an EventEmitter
(OptimizationLogEvent per update), and a MetricsRegistry (iteration
histograms / convergence counters). All sinks are optional.
"""

from __future__ import annotations

import numpy as np

from photon_ml_tpu.optim.common import (
    ConvergenceReason,
    LaneTrace,
    LaneTraces,
    SolverResult,
)
from photon_ml_tpu.util.events import EventEmitter, OptimizationLogEvent

#: per-lane rows written to the journal before falling back to tally-only
MAX_LANE_ROWS = 128

#: registry namespace for solver convergence metrics
SOLVER_METRIC_PREFIX = "solver/"

#: cross-coordinate per-lane iteration-count histogram: the lane-iteration
#: DISTRIBUTION the lane scheduler (algorithm/lane_scheduler.py) exists to
#: exploit — fed here for vmapped traces and by the scheduler itself
LANE_ITERS_METRIC = "solver/lane_iters"


def reset_solver_metrics(registry=None) -> None:
    """Drop per-run solver/* AND scheduler/* counters and histograms —
    drivers call this at run start (next to ``reset_timings``) so a sweep
    invoking ``run()`` repeatedly journals per-run tallies, not cross-run
    accumulations."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    reg.remove_prefix(SOLVER_METRIC_PREFIX)
    # literal, not imported: lane_scheduler pulls jax in, and this helper
    # must stay importable/callable before the backend is chosen
    reg.remove_prefix("scheduler/")


def _reason_name(code) -> str:
    try:
        return ConvergenceReason(int(code)).name
    except ValueError:
        return f"UNKNOWN_{int(code)}"


def solver_result_row(
    result: SolverResult,
    *,
    max_history: int = 64,
) -> dict:
    """One journal-ready dict from a scalar (un-vmapped) SolverResult."""
    iterations = int(result.iterations)
    values = np.asarray(result.value_history)
    history = [
        float(v) for v in values[: min(iterations + 1, max_history, len(values))]
        if np.isfinite(v)
    ]
    return {
        "iterations": iterations,
        "reason": _reason_name(result.reason),
        "converged": bool(result.converged),
        "value": float(result.value),
        "gradient_norm": float(result.gradient_norm),
        "value_history": history,
    }


def tron_counts(result: SolverResult) -> dict:
    """What a scalar TRON solve's ``SolverResult`` says of its inner loop
    (optim/tron.py fills the line-search fields with it): the Hessian-vector
    products of all rounds, the rounds that were rejected (a round whose value
    AND gradient-norm slots repeat the slots before them: a kept step moves
    the gradient even where float32 leaves the value as it was), and the
    rounds the float's floor ended."""
    n = int(result.iterations) + 1
    values = np.asarray(result.value_history)[:n]
    grads = np.asarray(result.grad_norm_history)[:n]
    return {
        "tron_hv_products": int(np.sum(np.asarray(result.line_search_trials))),
        "tron_rejected_rounds": int(np.sum(
            (values[1:] == values[:-1]) & (grads[1:] == grads[:-1]))),
        "tron_floor_exits": int(result.floor_exits),
    }


def _as_host_trace(trace: LaneTrace | LaneTraces | SolverResult) -> LaneTrace:
    """Normalize to one LaneTrace whose fields are host numpy arrays — ONE
    device-to-host transfer per field (per-bucket LaneTraces merge here, in
    numpy), so the summary/rows consumers below never trigger repeated
    device-to-host reads (each one waits for the device queue to drain)."""
    if isinstance(trace, SolverResult):
        from photon_ml_tpu.optim.common import lane_trace_of

        trace = lane_trace_of(trace)
    if isinstance(trace, LaneTraces):
        parts = trace.buckets
        return LaneTrace(
            iterations=np.concatenate([np.asarray(t.iterations) for t in parts]),
            reason=np.concatenate([np.asarray(t.reason) for t in parts]),
            value=np.concatenate([np.asarray(t.value) for t in parts]),
            gradient_norm=np.concatenate(
                [np.asarray(t.gradient_norm) for t in parts]
            ),
            valid=np.concatenate([np.asarray(t.valid) for t in parts]),
            scheduled=any(t.scheduled for t in parts),
        )
    if isinstance(trace.iterations, np.ndarray):
        return trace
    return LaneTrace(
        iterations=np.asarray(trace.iterations),
        reason=np.asarray(trace.reason),
        value=np.asarray(trace.value),
        gradient_norm=np.asarray(trace.gradient_norm),
        valid=np.asarray(trace.valid),
        scheduled=trace.scheduled,
    )


def lane_summary(trace: LaneTrace | SolverResult) -> dict:
    """Convergence-reason tallies + iteration stats across vmapped lanes.

    Accepts either a LaneTrace (the RE-bucket shape) or a vmapped
    SolverResult with a leading lane axis (the λ-grid shape).
    """
    trace = _as_host_trace(trace)
    valid = np.asarray(trace.valid).astype(bool)
    iterations = np.asarray(trace.iterations)[valid]
    reasons = np.asarray(trace.reason)[valid]
    values = np.asarray(trace.value)[valid]
    n = int(valid.sum())
    if n == 0:
        return {"num_lanes": 0, "reasons": {}, "lanes_at_max_iterations": 0}
    codes, counts = np.unique(reasons, return_counts=True)
    tallies = {_reason_name(c): int(k) for c, k in zip(codes, counts)}
    return {
        "num_lanes": n,
        "iterations_min": int(iterations.min()),
        "iterations_mean": float(iterations.mean()),
        "iterations_max": int(iterations.max()),
        "iterations_total": int(iterations.sum()),
        "reasons": tallies,
        "lanes_at_max_iterations": int(
            (reasons == int(ConvergenceReason.MAX_ITERATIONS)).sum()
        ),
        "lanes_not_converged": int(
            (reasons == int(ConvergenceReason.NOT_CONVERGED)).sum()
        ),
        "value_mean": float(values.mean()),
        "value_max": float(values.max()),
    }


def lane_rows(trace: LaneTrace | SolverResult, keys=None, limit: int = MAX_LANE_ROWS):
    """Per-lane convergence dicts (valid lanes only), ``keys[i]`` merged in
    when given (e.g. ``{"lambda": 0.1}`` per λ-grid lane)."""
    trace = _as_host_trace(trace)
    valid = np.asarray(trace.valid).astype(bool)
    iterations = np.asarray(trace.iterations)
    reasons = np.asarray(trace.reason)
    values = np.asarray(trace.value)
    grads = np.asarray(trace.gradient_norm)
    rows = []
    for i in np.flatnonzero(valid)[:limit]:
        row = {
            "lane": int(i),
            "iterations": int(iterations[i]),
            "reason": _reason_name(reasons[i]),
            "value": float(values[i]),
            "gradient_norm": float(grads[i]),
        }
        if keys is not None and i < len(keys):
            key = keys[i]
            row.update(key if isinstance(key, dict) else {"key": key})
        rows.append(row)
    return rows


class SolverTelemetry:
    """Fan-out sink for solver/coordinate convergence telemetry.

    ``journal``/``emitter``/``registry`` are each optional; drivers build one
    of these and thread it through estimators into the coordinate-descent
    loop and the GLM training paths.
    """

    def __init__(
        self,
        journal=None,
        emitter: EventEmitter | None = None,
        registry=None,
        max_lane_rows: int = MAX_LANE_ROWS,
    ):
        self.journal = journal
        self.emitter = emitter
        self.registry = registry
        self.max_lane_rows = max_lane_rows

    def _has_sink(self) -> bool:
        """False when no sink would consume a record — building rows costs
        real device-to-host reads (each a sync point that stalls the async
        dispatch queue), so producers skip the work entirely when the
        journal is absent/inert (worker ranks drop every record), the
        registry is absent, and no event listener is registered."""
        if self.journal is not None and getattr(self.journal, "active", True):
            return True
        if self.registry is not None:
            return True
        return self.emitter is not None and self.emitter.has_listeners

    def _journal(self, kind: str, row: dict) -> None:
        if self.journal is not None:
            self.journal.record(kind, **row)

    def heartbeat(self, stage: str, **cursor) -> None:
        """Periodic liveness row (ISSUE 12): training loops call this at
        sweep/epoch/λ boundaries so ``dev/doctor.py --live`` can read a
        wedged run's progress cursor + registry counter deltas out of the
        crash-durable journal stage. Observe-only and inert without an
        active journal (worker ranks, journal-less runs)."""
        if self.journal is None or not getattr(self.journal, "active", False):
            return
        self.journal.heartbeat(registry=self.registry, stage=stage, **cursor)

    def _emit(self, coordinate_id: str, iteration: int, metrics: dict) -> None:
        if self.emitter is not None:
            self.emitter.send(OptimizationLogEvent(
                coordinate_id=coordinate_id,
                iteration=iteration,
                metrics=metrics,
            ))

    def _count(self, coordinate_id: str, iterations: int, converged: bool) -> None:
        if self.registry is None:
            return
        self.registry.histogram(
            f"{SOLVER_METRIC_PREFIX}{coordinate_id}/iterations"
        ).observe(iterations)
        self.registry.counter(f"{SOLVER_METRIC_PREFIX}{coordinate_id}/solves").inc()
        if not converged:
            self.registry.counter(f"{SOLVER_METRIC_PREFIX}{coordinate_id}/not_converged").inc()

    def record_solve(
        self,
        coordinate_id: str,
        result: SolverResult,
        *,
        outer_iteration: int = 0,
        extra: dict | None = None,
    ) -> dict:
        """One un-vmapped solve (FE coordinate, sequential λ step). A solve
        whose ``extra`` names ``optimizer`` "TRON" also reports
        :func:`tron_counts`, in its row and as ``solver/tron_*`` counters."""
        if not self._has_sink():
            return {}
        row = solver_result_row(result)
        extra = extra or {}
        if extra.get("optimizer") == "TRON":
            counts = tron_counts(result)
            row.update(counts)
            if self.registry is not None:
                for name, count in counts.items():
                    self.registry.counter(SOLVER_METRIC_PREFIX + name).inc(count)
        row.update(extra)
        row.update(coordinate=coordinate_id, outer_iteration=outer_iteration)
        self._journal("convergence", row)
        self._emit(coordinate_id, outer_iteration, row)
        self._count(coordinate_id, row["iterations"], row["converged"])
        return row

    def record_lanes(
        self,
        coordinate_id: str,
        trace: LaneTrace | SolverResult,
        *,
        outer_iteration: int = 0,
        keys=None,
        extra: dict | None = None,
    ) -> dict:
        """Vmapped lanes (λ grid, RE buckets): per-lane rows + reason tally."""
        if not self._has_sink():
            return {}
        trace = _as_host_trace(trace)  # one transfer feeds summary AND rows
        summary = lane_summary(trace)
        summary.update(extra or {})
        summary.update(coordinate=coordinate_id, outer_iteration=outer_iteration)
        for row in lane_rows(trace, keys=keys, limit=self.max_lane_rows):
            row.update(coordinate=coordinate_id, outer_iteration=outer_iteration)
            self._journal("convergence", row)
        self._journal("convergence_lanes", summary)
        self._emit(coordinate_id, outer_iteration, summary)
        if self.registry is not None and summary.get("num_lanes", 0) > 0:
            self.registry.histogram(
                f"{SOLVER_METRIC_PREFIX}{coordinate_id}/iterations"
            ).observe(summary["iterations_mean"])
            # per-lane iteration DISTRIBUTION across coordinates — p50/p95
            # vs max is the headroom the lane scheduler compacts away.
            # Scheduler-produced traces are skipped: the scheduler already
            # observed them (counting twice would double count/total)
            if not trace.scheduled:
                valid = np.asarray(trace.valid).astype(bool)
                self.registry.histogram(LANE_ITERS_METRIC).observe_many(
                    np.asarray(trace.iterations)[valid].tolist()
                )
            self.registry.counter(f"{SOLVER_METRIC_PREFIX}{coordinate_id}/solves").inc(
                summary["num_lanes"]
            )
            self.registry.counter(
                f"{SOLVER_METRIC_PREFIX}{coordinate_id}/lanes_at_max_iterations"
            ).inc(summary["lanes_at_max_iterations"])
        return summary

    def record_coordinate(
        self,
        coordinate_id: str,
        outer_iteration: int,
        info,
        *,
        metrics: dict | None = None,
    ) -> None:
        """Per-coordinate, per-outer-iteration hook for the GAME block-
        coordinate-descent loop: dispatches on what the coordinate's
        ``update_model`` returned (SolverResult for the fixed effect,
        LaneTrace(s) for vmapped random-effect buckets, None for locked/MF)."""
        if not self._has_sink():
            return
        extra = {"evaluation": metrics} if metrics else None
        if isinstance(info, SolverResult):
            self.record_solve(
                coordinate_id, info, outer_iteration=outer_iteration, extra=extra
            )
        elif isinstance(info, (LaneTrace, LaneTraces)):
            self.record_lanes(
                coordinate_id, info, outer_iteration=outer_iteration, extra=extra
            )
        elif metrics:
            row = dict(coordinate=coordinate_id, outer_iteration=outer_iteration,
                       evaluation=metrics)
            self._journal("coordinate_update", row)
            self._emit(coordinate_id, outer_iteration, row)
