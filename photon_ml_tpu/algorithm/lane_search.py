"""Vmapped config-tournament lanes: one solve program, many hyperparameter
configurations.

Reference parity: the reference evaluates hyperparameter candidates as a
sequential outer loop of full driver fits (photon-client hyperparameter/
HyperparameterTuning.scala-style glue around RandomSearch.scala:33-50);
there is no reference analogue of the vmapped tournament itself — it
generalizes this repo's λ-grid machinery (estimators._jitted_grid_solve,
lane-varying L2 only) to full per-lane config VECTORS: (l2, l1, solver
tolerance, optional per-lane box bounds) as traced per-lane arrays through
one vmapped LBFGS/OWLQN solve. Branch structure stays static and shared
across lanes (`use_owlqn` / `use_box` resolve once per tournament — Snap ML
arXiv:1803.06333's "keep the accelerator saturated by batching many small
solves into one resident program").

Invariants:
- A uniform-config tournament (λ lanes only: per-lane l2/l1 from one
  elastic-net α, uniform tolerance == the optimizer's, no box, cold zero
  warm starts) is BITWISE identical to `estimators.train_glm_grid`
  (tests/test_lane_search.py pins it) — tolerance and w0 become traced
  per-lane arguments but feed only exact IEEE comparisons/multiplies, and
  a runtime zero vector margins identically to the inlined constant.
- Per-lane boxes ride the projected-gradient L-BFGS path; a tournament
  with NO box lane passes bounds=None so the unprojected convergence test
  (‖g‖, not ‖P(w-g)-w‖) is preserved exactly — ±inf bounds arrays are NOT
  bitwise-equivalent to bounds=None and must never be the no-box encoding
  at the tournament level.
- Tournament evaluation stays on device: per-lane validation margins +
  the exact sharded metric (evaluation/sharded.py) reduce on-mesh and only
  the [L] metric scalars cross to the host.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import LabeledPointBatch, compute_margins
from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    OptimizerType,
    resolve_auto_optimizer,
)
from photon_ml_tpu.telemetry.program_ledger import ledger_jit
from photon_ml_tpu.types import TaskType

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LaneConfigs:
    """Per-lane hyperparameter vectors for one tournament round.

    l2 / l1 / tolerance: [L] float arrays (one lane per configuration).
    lower_bounds / upper_bounds: optional [L, d] per-lane box; lanes without
    a box carry ±inf rows. Leave BOTH None when no lane uses a box — that
    selects the exact unprojected L-BFGS path (see module invariants).
    """

    l2: np.ndarray
    l1: np.ndarray
    tolerance: np.ndarray
    lower_bounds: np.ndarray | None = None
    upper_bounds: np.ndarray | None = None

    def __post_init__(self):
        l2 = np.asarray(self.l2, np.float64)
        l1 = np.asarray(self.l1, np.float64)
        tol = np.asarray(self.tolerance, np.float64)
        if not (l2.shape == l1.shape == tol.shape and l2.ndim == 1):
            raise ValueError(
                "LaneConfigs needs matching [L] vectors, got "
                f"l2{l2.shape} l1{l1.shape} tolerance{tol.shape}"
            )
        if (self.lower_bounds is None) != (self.upper_bounds is None):
            raise ValueError(
                "per-lane boxes need BOTH lower_bounds and upper_bounds "
                "([L, d]; ±inf rows for box-off lanes)"
            )

    @property
    def num_lanes(self) -> int:
        return int(np.asarray(self.l2).shape[0])

    @property
    def has_box(self) -> bool:
        return self.lower_bounds is not None

    def needs_owlqn(self) -> bool:
        return bool(np.any(np.asarray(self.l1) > 0.0))


@dataclasses.dataclass
class TournamentResult:
    """One vmapped tournament: per-lane solver results + model-space models."""

    #: vmapped SolverResult — every leaf has a leading [L] lane axis
    results: object
    #: model-space GLMs, lane order
    models: list[GeneralizedLinearModel]
    #: the configs that trained them (for trajectory bookkeeping)
    configs: LaneConfigs


def run_lane_tournament(
    batch: LabeledPointBatch,
    task: TaskType,
    configs: LaneConfigs,
    *,
    optimizer: OptimizerConfig | None = None,
    warm_start: Array | np.ndarray | None = None,
    normalization=None,
    intercept_index: int | None = None,
    telemetry=None,
) -> TournamentResult:
    """Train every lane of ``configs`` in ONE vmapped solve.

    ``warm_start``: optional [L, d] per-lane initial coefficients in
    NORMALIZED (solver) space — the search driver supplies
    nearest-evaluated-config starts; None = cold zeros, which is the
    train_glm_grid-identical path. ``optimizer``: AUTO resolves ONCE here
    for the whole tournament (never per lane); only LBFGS/OWLQN vmap.
    """
    optimizer = resolve_auto_optimizer(optimizer or OptimizerConfig())
    if optimizer.optimizer_type not in (OptimizerType.LBFGS, OptimizerType.OWLQN):
        raise ValueError(
            "lane tournaments support LBFGS/OWLQN lanes; got "
            f"{optimizer.optimizer_type.name}"
        )
    use_owlqn = (
        configs.needs_owlqn()
        or optimizer.optimizer_type == OptimizerType.OWLQN
    )
    if use_owlqn and configs.has_box:
        raise ValueError(
            "box constraints cannot combine with OWL-QN / L1 lanes"
        )
    loss = loss_for_task(task)
    # deferred: estimators imports algorithm/* at module load
    from photon_ml_tpu.estimators import _objective_for_batch

    objective = _objective_for_batch(batch, loss, 0.0, normalization)
    dtype = batch.solve_dtype
    num_lanes = configs.num_lanes
    l2v = jnp.asarray(np.asarray(configs.l2), dtype)
    l1v = jnp.asarray(np.asarray(configs.l1), dtype)
    tolv = jnp.asarray(np.asarray(configs.tolerance), dtype)
    if warm_start is None:
        w0v = jnp.zeros((num_lanes, batch.dim), dtype)
    else:
        w0v = jnp.asarray(warm_start, dtype)
        if w0v.shape != (num_lanes, batch.dim):
            raise ValueError(
                f"warm_start must be [{num_lanes}, {batch.dim}], "
                f"got {w0v.shape}"
            )
    bounds = None
    if configs.has_box:
        bounds = (
            jnp.asarray(configs.lower_bounds, dtype),
            jnp.asarray(configs.upper_bounds, dtype),
        )
    results = _jitted_lane_solve(
        objective, use_owlqn, optimizer.history, optimizer.max_iterations,
        optimizer.rel_function_tolerance, batch, l2v, l1v, tolv, w0v,
        bounds,
    )
    if telemetry is not None:
        telemetry.record_lanes(
            "lane-search", results,
            keys=[
                {"l2": float(np.asarray(configs.l2)[i]),
                 "l1": float(np.asarray(configs.l1)[i])}
                for i in range(num_lanes)
            ],
        )
    norm = objective.normalization
    models = []
    for i in range(num_lanes):
        means = norm.to_model_space(results.coefficients[i], intercept_index)
        models.append(
            GeneralizedLinearModel(Coefficients(means=means), task)
        )
    return TournamentResult(results=results, models=models, configs=configs)


@functools.partial(ledger_jit, label="search/lane_solve",
                   static_argnums=(0, 1, 2, 3, 4))
def _jitted_lane_solve(objective, use_owlqn, history, max_iter,
                       rel_function_tolerance, batch, l2v, l1v, tolv, w0v,
                       bounds=None):
    """Module-level jit: one compiled tournament program per
    (objective, optimizer statics) pair; the batch and every per-lane
    config vector enter as ARGUMENTS (a closed-over batch would be baked
    into the program as a constant — lint check 9).
    Mirrors estimators._jitted_grid_solve with per-lane tolerance, warm
    starts and (optionally) per-lane [L, d] boxes vmapped in; the
    objective stays use_pallas=False because these lanes are vmapped."""
    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs
    from photon_ml_tpu.optim.owlqn import minimize_owlqn

    bound = objective.bind(batch)

    def solve_one(l2, l1, tol, w0, *lane_bounds):
        def vg(w):
            v, g = bound.value_and_grad(w)
            return v + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w

        if use_owlqn:
            return minimize_owlqn(
                vg, w0, l1_weight=l1,
                max_iter=max_iter, tolerance=tol, history=history,
                rel_function_tolerance=rel_function_tolerance,
            )
        lo, hi = lane_bounds if lane_bounds else (None, None)
        return minimize_lbfgs(
            vg, w0, max_iter=max_iter, tolerance=tol, history=history,
            rel_function_tolerance=rel_function_tolerance,
            lower_bounds=lo, upper_bounds=hi,
        )

    if bounds is None:
        return jax.vmap(solve_one)(l2v, l1v, tolv, w0v)
    return jax.vmap(solve_one)(l2v, l1v, tolv, w0v, bounds[0], bounds[1])


@functools.partial(ledger_jit, label="search/lane_metrics",
                   static_argnums=(0, 1, 2))
def _jitted_lane_metrics(objective, metric_fn, intercept_index, batch,
                         coefficients, consts):
    """Per-lane validation metrics WITHOUT a host score round-trip: map each
    lane's solver-space coefficients to model space, margin against the
    validation batch, reduce with the exact device metric
    (evaluation/sharded.py) — only the [L] scalars leave the mesh."""
    norm = objective.normalization

    def one(w):
        wm = norm.to_model_space(w, intercept_index)
        scores = compute_margins(batch, wm)
        return metric_fn(scores, consts)

    return jax.vmap(one)(coefficients)


def evaluate_tournament_on_device(
    objective,
    metric_fn,
    val_batch: LabeledPointBatch,
    coefficients: Array,
    consts: dict,
    intercept_index: int | None = None,
) -> Array:
    """[L] on-device metric values for a tournament's coefficient stack
    (solver space). ``metric_fn``/``consts`` come from a prepared
    evaluation.sharded.DeviceEvaluator (callers keep its ``better_than``).
    Returns the DEVICE array — dispatch is async, so callers overlap host
    work (the GP fit) before reading it; ``np.asarray`` is the sync point.
    """
    if isinstance(val_batch, SparseLabeledPointBatch):
        raise TypeError(
            "tournament evaluation needs a dense validation batch "
            "(per-lane margins are one [n, d] @ [d] per lane)"
        )
    return _jitted_lane_metrics(
        objective, metric_fn, intercept_index, val_batch, coefficients,
        consts,
    )
