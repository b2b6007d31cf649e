"""Optimizer facade: config objects + dispatch to the jittable solvers.

Reference parity: photon-lib optimization/Optimizer.scala (template method +
convergence config), OptimizerFactory.scala, and the per-optimizer config in
OptimizerConfig/GLMOptimizationConfiguration. The reference's optimizer
objects are stateful; here an Optimizer is a frozen config whose ``solve``
is a pure function, so one compiled program serves every coordinate-descent
iteration, λ-grid point, and (vmapped) every random-effect entity.
"""

from __future__ import annotations

import dataclasses
import enum

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.objective import BoundObjective
from photon_ml_tpu.optim.common import SolverResult
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs
from photon_ml_tpu.optim.newton import minimize_newton
from photon_ml_tpu.optim.owlqn import minimize_owlqn
from photon_ml_tpu.optim.tron import minimize_tron

Array = jax.Array


class OptimizerType(enum.Enum):
    """Reference: photon-lib optimization/OptimizerType.scala. NEWTON is a
    TPU-first extension with no reference analogue (optim/newton.py): the
    op-minimal solver for small-d vmapped per-entity solves. AUTO picks
    the fastest safe solver per coordinate KIND (resolve_auto_optimizer):
    NEWTON on eligible small-d dense vmapped solves (RE/MF buckets; on
    the chip in the cell game-ymusic-r2.sweeps, PERF.md 5), LBFGS everywhere else.
    Explicit LBFGS stays the reference-parity default."""

    LBFGS = "LBFGS"
    OWLQN = "OWLQN"
    LBFGSB = "LBFGSB"
    TRON = "TRON"
    NEWTON = "NEWTON"
    AUTO = "AUTO"


@dataclasses.dataclass(frozen=True)
class LaneSchedulerConfig:
    """Converged-lane scheduling for vmapped random-effect solves
    (algorithm/lane_scheduler.py; no reference analogue — the reference's
    per-entity RDD solves are independently scheduled by Spark's task
    scheduler, while vmapped lanes advance in lock-step to the worst lane).

    probe_iterations: short probe budget — every lane solves this many
        iterations, then only lanes that are still at MAX_ITERATIONS are
        host-compacted into power-of-two-padded rescue blocks and re-run
        with the remaining ``max_iterations - probe_iterations`` budget.
    freeze_coefficient_tolerance / freeze_gradient_tolerance: cross-sweep
        active sets — when BOTH are > 0, entities whose relative coefficient
        delta and final gradient norm fall below these thresholds after a
        sweep are frozen (skipped by later sweeps' solves, still rescored);
        the final sweep always runs everyone.
    """

    probe_iterations: int = 2
    freeze_coefficient_tolerance: float = 0.0
    freeze_gradient_tolerance: float = 0.0

    @property
    def freezes(self) -> bool:
        return (
            self.freeze_coefficient_tolerance > 0.0
            and self.freeze_gradient_tolerance > 0.0
        )


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static solver configuration (reference OptimizerConfig.scala).

    ``box_constraints``: optional (lower, upper) arrays for LBFGSB / the
    reference's constraint-map projection (LBFGS.scala:70-76).

    ``rel_function_tolerance`` (None = reference behavior): separate live
    function-decrease stop threshold — the knob that lets warm-started
    vmapped lanes exit before max_iter (optim/common.check_convergence).

    ``scheduler`` (None = off, bitwise-identical to the unscheduled path):
    probe/rescue lane scheduling for vmapped random-effect solves. Consumed
    ABOVE :func:`solve` by algorithm/lane_scheduler.py; the solver dispatch
    below ignores it.
    """

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = 100
    tolerance: float = 1e-7
    history: int = 10  # L-BFGS memory m
    max_cg_iterations: int = 20  # TRON inner loop cap
    l1_weight: float = 0.0  # OWLQN only; set by the elastic-net path
    rel_function_tolerance: float | None = None
    scheduler: LaneSchedulerConfig | None = None

    def with_l1(self, l1_weight: float) -> "OptimizerConfig":
        return dataclasses.replace(self, l1_weight=l1_weight)


def solve(
    config: OptimizerConfig,
    objective: BoundObjective,
    w0: Array,
    *,
    lower_bounds: Array | None = None,
    upper_bounds: Array | None = None,
    host_loop: bool = False,
    state_observer=None,
    resume_state=None,
) -> SolverResult:
    """Run the configured solver on a bound objective. Pure; jit/vmap-safe.

    ``host_loop=True`` drives the solver's identical per-iteration math
    from Python loops so the objective may be a host-level chunked-epoch
    accumulator (algorithm/streaming.py); LBFGS/OWLQN/TRON only — NEWTON
    needs a dense [d, d] Hessian no streaming objective materializes.

    ``state_observer`` / ``resume_state`` (host_loop only): the solver-
    state checkpoint hooks (io/checkpoint.SolverCheckpointer) — the
    observer sees the solver's state struct after every outer iteration,
    ``resume_state`` re-enters from a restored one. The matching state
    class is ``solver_state_class(config)``.
    """
    t = config.optimizer_type
    if t == OptimizerType.AUTO:
        # AUTO is a coordinate-layer concept: the safe/fast choice depends
        # on the SOLVE SHAPE (vmapped small-d dense vs big-d streamed),
        # which this dispatch cannot see — the coordinate call sites
        # resolve it before building jitted programs
        raise ValueError(
            "OptimizerType.AUTO must be resolved before solve() — call "
            "resolve_auto_optimizer(config, loss=..., small_dense=...) at "
            "the coordinate layer (estimators/coordinates/programs do this "
            "for their own specs)"
        )
    if (state_observer is not None or resume_state is not None) and (
        not host_loop or t == OptimizerType.NEWTON
    ):
        raise ValueError(
            "state_observer/resume_state cover the host-loop LBFGS/OWLQN/"
            "TRON solvers only (streaming solver checkpointing)"
        )
    if host_loop and t == OptimizerType.NEWTON:
        raise ValueError(
            "NEWTON has no host-loop (streaming) mode — it needs the dense "
            "[d, d] Hessian; use TRON for streamed second-order solves"
        )
    if (lower_bounds is not None or upper_bounds is not None) and t not in (
        OptimizerType.LBFGS, OptimizerType.LBFGSB
    ):
        raise ValueError(
            f"box constraints are only supported by the LBFGS family, not "
            f"{t.name} (the reference projects in LBFGS, LBFGS.scala:70-76)"
        )
    if t == OptimizerType.LBFGS:
        # a constraint map makes plain LBFGS project onto the box after each
        # step, exactly like the reference (LBFGS.scala:70-76)
        return minimize_lbfgs(
            objective.value_and_grad,
            w0,
            max_iter=config.max_iterations,
            history=config.history,
            tolerance=config.tolerance,
            rel_function_tolerance=config.rel_function_tolerance,
            lower_bounds=lower_bounds,
            upper_bounds=upper_bounds,
            host_loop=host_loop,
            state_observer=state_observer,
            resume_state=resume_state,
        )
    if t == OptimizerType.LBFGSB:
        if lower_bounds is None and upper_bounds is None:
            raise ValueError("LBFGSB requires box constraints")
        return minimize_lbfgs(
            objective.value_and_grad,
            w0,
            max_iter=config.max_iterations,
            history=config.history,
            tolerance=config.tolerance,
            rel_function_tolerance=config.rel_function_tolerance,
            lower_bounds=lower_bounds,
            upper_bounds=upper_bounds,
            host_loop=host_loop,
            state_observer=state_observer,
            resume_state=resume_state,
        )
    if t == OptimizerType.OWLQN:
        return minimize_owlqn(
            objective.value_and_grad,
            w0,
            l1_weight=config.l1_weight,
            max_iter=config.max_iterations,
            history=config.history,
            tolerance=config.tolerance,
            rel_function_tolerance=config.rel_function_tolerance,
            host_loop=host_loop,
            state_observer=state_observer,
            resume_state=resume_state,
        )
    if t == OptimizerType.TRON:
        loss = objective.objective.loss
        if not loss.twice_differentiable:
            raise ValueError(
                f"TRON requires a twice-differentiable loss, got {type(loss).__name__}"
                " (reference restricts smoothed-hinge to the LBFGS family)"
            )
        return minimize_tron(
            objective.value_and_grad,
            objective.hessian_vector,
            w0,
            max_iter=config.max_iterations,
            tolerance=config.tolerance,
            rel_function_tolerance=config.rel_function_tolerance,
            max_cg_iter=config.max_cg_iterations,
            host_loop=host_loop,
            state_observer=state_observer,
            resume_state=resume_state,
        )
    if t == OptimizerType.NEWTON:
        loss = objective.objective.loss
        if not loss.twice_differentiable:
            raise ValueError(
                f"NEWTON requires a twice-differentiable loss, got "
                f"{type(loss).__name__} (same restriction as TRON)"
            )
        # the generic BoundObjective always has the method; what matters is
        # whether the UNDERLYING objective can produce a dense [d, d] H
        inner = getattr(objective, "objective", objective)
        if not hasattr(inner, "hessian_matrix"):
            raise ValueError(
                "NEWTON needs an explicit [d, d] Hessian; "
                f"{type(inner).__name__} does not expose one — NEWTON is "
                "meant for small-d dense (per-entity) solves"
            )
        return minimize_newton(
            objective.value_and_grad,
            objective.hessian_matrix,
            w0,
            value_fn=objective.value,
            max_iter=config.max_iterations,
            tolerance=config.tolerance,
            rel_function_tolerance=config.rel_function_tolerance,
        )
    raise ValueError(f"Unknown optimizer type {t}")


def resolve_auto_optimizer(
    config: OptimizerConfig,
    *,
    loss=None,
    small_dense: bool = False,
) -> OptimizerConfig:
    """Resolve ``OptimizerType.AUTO`` into a concrete solver for one solve
    site; non-AUTO configs pass through untouched.

    ``small_dense=True`` marks the vmapped small-d dense per-entity solve
    shape (RE/MF buckets): there AUTO promotes to NEWTON — the op-minimal
    solver for that shape (on the chip: the ridge lanes of the cell
    game-ymusic-r2.sweeps, PERF.md 5, PR 50; against L-BFGS on the same
    logistic lanes it is not measured, PERF.md 7 row 3; the "18 vs 48 ms" once
    quoted here predates the chip's benchmark, BASELINE.md) — exactly when
    the dispatch guards in :func:`solve`
    would accept it (twice-differentiable ``loss``, no L1 term; box
    constraints are an LBFGS-family feature and AUTO never carries them
    here). Everything else (big-d FE solves, streamed host-loop
    objectives) resolves to LBFGS, the reference-parity default — except
    a config already carrying ``l1_weight`` > 0, which resolves to OWLQN
    directly: plain LBFGS never reads ``l1_weight``, so mapping AUTO+L1
    to LBFGS at a call site without its own ``uses_owlqn`` flip (the spec
    paths) would silently drop the penalty. Callers whose elastic-net
    flip runs later (``_solve_config``/``with_l1``) see the same end
    state either way.
    """
    if config.optimizer_type != OptimizerType.AUTO:
        return config
    if config.l1_weight > 0.0:
        resolved = OptimizerType.OWLQN
    else:
        eligible = (
            small_dense
            and loss is not None
            and getattr(loss, "twice_differentiable", False)
        )
        resolved = (
            OptimizerType.NEWTON if eligible else OptimizerType.LBFGS
        )
    return dataclasses.replace(config, optimizer_type=resolved)


def solver_state_class(config: OptimizerConfig):
    """The flax-struct state class ``solve(config, ..., host_loop=True)``
    hands to a ``state_observer`` — the (de)serialization contract of
    io/checkpoint.SolverCheckpointer. The effective solver for an
    elastic-net λ is OWLQN whenever ``l1_weight`` > 0 (estimators'
    per-λ switch), which this lookup mirrors via ``optimizer_type``."""
    from photon_ml_tpu.optim.lbfgs import _LBFGSState
    from photon_ml_tpu.optim.owlqn import _OWLQNState
    from photon_ml_tpu.optim.tron import _TRONState

    t = config.optimizer_type
    if t in (OptimizerType.LBFGS, OptimizerType.LBFGSB):
        return _LBFGSState
    if t == OptimizerType.OWLQN:
        return _OWLQNState
    if t == OptimizerType.TRON:
        return _TRONState
    raise ValueError(
        f"{t.name} has no host-loop (streaming) mode, so no checkpointable "
        "solver state"
    )


def default_config_for(optimizer_type: OptimizerType) -> OptimizerConfig:
    """Reference defaults: LBFGS maxIter=100 tol=1e-7 (LBFGS.scala:152-157);
    TRON maxIter=15 tol=1e-5 (TRON.scala:257-262)."""
    if optimizer_type == OptimizerType.TRON:
        return OptimizerConfig(
            optimizer_type=optimizer_type, max_iterations=15, tolerance=1e-5
        )
    return OptimizerConfig(optimizer_type=optimizer_type)
