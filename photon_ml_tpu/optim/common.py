"""Shared optimizer machinery: results, convergence, line search.

Reference parity: photon-lib optimization/Optimizer.scala (template loop,
convergence by max-iter / loss-delta / gradient-norm, Optimizer.scala:135-149)
and OptimizationStatesTracker.scala (per-iteration state history).

Everything here is jit- and vmap-safe: fixed shapes, lax control flow, no
data-dependent python branching. ``vmap(minimize_*)`` over per-entity
objectives is the TPU replacement for the reference's per-entity RDD solves.
"""

from __future__ import annotations

import enum

import flax.struct
import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


class ConvergenceReason(enum.IntEnum):
    """Why an optimizer stopped (reference util/ConvergenceReason.scala)."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_WITHIN_TOLERANCE = 2
    GRADIENT_WITHIN_TOLERANCE = 3
    LINE_SEARCH_FAILED = 4


def run_while(cond, body, init, *, host: bool = False, observer=None):
    """``lax.while_loop`` — or, with ``host=True``, the IDENTICAL loop body
    driven from Python with concrete arrays.

    The host mode exists for out-of-core streaming solves
    (algorithm/streaming.py): there ``value_and_grad_fn`` is a HOST
    function (one chunked epoch over data that never fits on device), so
    it cannot be traced into a ``lax.while_loop`` body — tracing would
    both consume the chunk stream at trace time and bake every chunk into
    the program as constants (a program the size of the data, recompiled
    per stream). Every per-iteration
    operation is the same jax code either way; only the control-flow
    driver changes, so the host loop follows the in-core solve's
    arithmetic step for step (differences come only from the chunked
    summation order inside the objective, i.e. float round-off).

    ``observer`` (host mode only): called with the state after every body
    step — the epoch-boundary hook solver-state checkpointing rides
    (io/checkpoint.SolverCheckpointer). It observes, never rewrites: the
    state it receives is the state the loop continues with, so a solve
    with an observer is bitwise the solve without one.

    The default (``host=False``) compiles to the exact same
    ``lax.while_loop`` call as before this parameter existed.
    """
    if not host:
        if observer is not None:
            raise ValueError(
                "run_while(observer=...) requires host=True — a compiled "
                "lax.while_loop body cannot call back to the host"
            )
        return lax.while_loop(cond, body, init)
    from photon_ml_tpu.telemetry import tracing

    state = init
    i = 0
    while bool(cond(state)):
        # per-iteration host wall-clock span (a streaming solve's iteration
        # IS an epoch or several); observes only — the body/observer
        # sequence is identical with tracing off
        with tracing.span("solver/iteration", cat="solver", i=i):
            state = body(state)
        if observer is not None:
            observer(state)
        i += 1
    return state


@flax.struct.dataclass
class SolverResult:
    """Final state + per-iteration history of one solve.

    ``value_history`` / ``grad_norm_history`` are fixed-size [max_iter + 1]
    arrays padded with NaN past ``iterations`` — the jittable analogue of
    OptimizationStatesTracker's bounded state queue.

    ``line_search_trials[i]`` counts the objective-side evaluations INSIDE
    iteration ``i`` (int32 [max_iter + 1]; slot 0 and slots past ``iterations``
    hold 0): a line search's trial points, TRON's Hessian-vector products;
    ``floor_exits`` the searches, or TRON's rounds, the float's floor ended
    (:data:`LINE_SEARCH_FLOOR_K`).
    Newton (optim/newton.py) counts a round's step-shrink candidates as its
    trials, 1 in ``floor_exits`` where the floor ended the solve, and its
    rounds that accepted no candidate in ``rejected_rounds`` (None elsewhere).
    """

    coefficients: Array
    value: Array
    gradient_norm: Array
    iterations: Array  # int32 scalar
    reason: Array  # int32 scalar, ConvergenceReason code
    value_history: Array
    grad_norm_history: Array
    line_search_trials: Array  # int32 [max_iter + 1]
    floor_exits: Array  # int32 scalar
    rejected_rounds: Array | None = None  # int32 scalar; Newton only

    @property
    def converged(self) -> Array:
        return self.reason != ConvergenceReason.NOT_CONVERGED

    def states_table(self) -> str:
        """Printable per-iteration state table (reference
        OptimizationStatesTracker.toString, OptimizationStatesTracker.scala:
        82-101): iteration | objective value | gradient norm, ending with
        the convergence reason."""
        import numpy as np

        values = np.asarray(self.value_history)
        grads = np.asarray(self.grad_norm_history)
        n = int(self.iterations)
        lines = [f"{'iter':>6} {'value':>16} {'|gradient|':>16}"]
        for i in range(min(n + 1, len(values))):
            if np.isnan(values[i]):
                break
            lines.append(f"{i:>6} {values[i]:>16.8g} {grads[i]:>16.8g}")
        reason = ConvergenceReason(int(self.reason)).name
        lines.append(f"converged after {n} iterations: {reason}")
        return "\n".join(lines)


@flax.struct.dataclass
class LaneTrace:
    """Per-lane convergence scalars of a vmapped solve (one entry per solver
    lane: a λ-grid point or a random-effect entity).

    The jittable skeleton of the reference's per-problem
    OptimizationStatesTracker reporting (OptimizationStatesTracker.scala:
    82-101): vmapped solves cannot keep per-iteration host-side state, but
    XLA computes each lane's final iteration count / reason / value anyway.
    ``valid`` masks padding lanes (OOB-sentinel entity rows solve
    all-zero-weight batches and must not pollute convergence tallies). Read
    by telemetry/solver_trace.py (reason tallies across lanes) and by the
    fused sweep's counts (:func:`bucket_count_parts`): the "every lane pays
    max_iter" pathology (CLAUDE.md) made visible.
    """

    iterations: Array  # [lanes] int32
    reason: Array  # [lanes] int32 ConvergenceReason codes
    value: Array  # [lanes] final objective values
    gradient_norm: Array  # [lanes]
    valid: Array  # [lanes] bool; False = padding lane
    #: True when the lane scheduler (algorithm/lane_scheduler.py) produced this
    #: trace: it has already observed these lanes into the solver/lane_iters
    #: histogram, so telemetry must not count them again (static, not a leaf)
    scheduled: bool = flax.struct.field(pytree_node=False, default=False)
    #: line-search work, filled by :func:`lane_trace_of`; None elsewhere
    line_search_trials: Array | None = None  # [lanes] int32, a lane's own trials
    floor_exits: Array | None = None  # [lanes] int32
    #: int32 scalar: sum over iterations of the max over ALL lanes (padding
    #: lanes too) — the trips the vmapped search loop actually ran
    lockstep_trials: Array | None = None
    #: [lanes, max_iter + 1] int32, the solve's own history: a lane's trials
    #: by iteration (what :func:`bucket_count_parts` takes its maxima from)
    search_trials: Array | None = None
    #: [lanes] int32, a Newton lane's rejected rounds; None for other solvers
    rejected_rounds: Array | None = None


class LaneTraces:
    """Per-bucket LaneTraces held AS the device arrays the solves returned.

    Deliberately not a pytree and never merged on device: each eager
    ``jnp.concatenate`` is one more dispatched program (and compile, per
    bucket shape) whose only consumer is the host, so the merge happens
    host-side in numpy — and only when a telemetry consumer actually reads
    the traces
    (telemetry/solver_trace.py). A coordinate update with no telemetry
    attached pays nothing for carrying this object.
    """

    def __init__(self, buckets):
        self.buckets: tuple[LaneTrace, ...] = tuple(buckets)


def lane_trace_of(result: SolverResult, valid: Array | None = None) -> LaneTrace:
    """Build a LaneTrace from a (vmapped) SolverResult, dropping the value
    and gradient histories that padding lanes would make meaningless (the
    line-search history stays, with each lane's total and the bucket's
    lock-step total beside it)."""
    iterations = jnp.atleast_1d(result.iterations)
    valid = jnp.ones(iterations.shape, bool) if valid is None else valid
    trials = jnp.atleast_2d(result.line_search_trials)  # [lanes, max_iter + 1]
    return LaneTrace(
        iterations=iterations,
        reason=jnp.atleast_1d(result.reason),
        value=jnp.atleast_1d(result.value),
        gradient_norm=jnp.atleast_1d(result.gradient_norm),
        valid=jnp.atleast_1d(valid),
        line_search_trials=jnp.sum(trials, axis=1, dtype=jnp.int32),
        floor_exits=jnp.atleast_1d(result.floor_exits),
        # iteration by iteration the vmapped search loop runs until its
        # slowest lane is done: the sum of those maxima is what the device ran
        lockstep_trials=jnp.sum(jnp.max(trials, axis=0), dtype=jnp.int32),
        search_trials=trials,
        rejected_rounds=(None if result.rejected_rounds is None
                         else jnp.atleast_1d(result.rejected_rounds)),
    )


#: the thirteen registry counters (``solver/<name>``) a fused sweep has
#: reported since PR 50, each a sum over the rows of the step's count array
#: (:data:`BUCKET_COUNT_NAMES`, end of this file): four of the random-effect
#: buckets, the fixed-effect solves' trials and floor exits, the same four of
#: the matrix-factorization half-steps' buckets (``mf_*``) and three of the
#: buckets Newton solves (``newton_*``); zero where a program has no such solve
SOLVER_COUNT_NAMES = (
    "lockstep_trials", "lane_trials", "floor_exits", "line_searches",
    "fe_trials", "fe_floor_exits",
    "mf_lockstep_trials", "mf_lane_trials", "mf_floor_exits", "mf_line_searches",
    "newton_lockstep_rounds", "newton_lane_rounds", "newton_rejected_rounds",
)


#: a row of the fused step's count array, one row a solve (a bucket of lanes,
#: or a fixed-effect solve as a bucket of one lane), built by
#: :func:`bucket_count_parts` and :func:`bucket_counts` at the end of this
#: file: what the device ran (``lockstep_trials``: iteration by iteration
#: the slowest lane's trials, summed; ``lockstep_iterations``: the slowest
#: lane's outer trips; both over ALL lanes, padding too: every lane waits),
#: what the VALID lanes needed each by itself (``lane_trials``;
#: ``line_searches``: one search, or Newton round, an iteration), the valid
#: lanes (``lane_solves``) and how many of them each reason stopped (the four
#: sum to it), the searches the float's floor ended, and the Newton lanes'
#: rounds that accepted no candidate (zero under every other solver)
BUCKET_COUNT_NAMES = (
    "lockstep_trials", "lockstep_iterations",
    "lane_trials", "floor_exits", "line_searches", "lane_solves",
    "lanes_max_iterations", "lanes_function_tolerance",
    "lanes_gradient_tolerance", "lanes_search_failed", "rejected_rounds",
)


def check_convergence(
    *,
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    initial_grad_norm: Array,
    tolerance: float,
    rel_function_tolerance: float | None = None,
) -> Array:
    """Return a ConvergenceReason code (0 if not converged).

    Matches the reference's dual test (Optimizer.scala:135-149): relative
    change in objective value below tolerance, or gradient norm below
    tolerance relative to the initial gradient norm.

    ``rel_function_tolerance`` (default None = use ``tolerance``, the
    reference behavior) sets a SEPARATE threshold for the function-decrease
    test. This is the live stop that actually fires in f32 for warm-started
    vmapped lanes: an exact step leaves ‖g‖ at rounding scale, which a large
    warm-start g0 never maps below the relative gradient tolerance, and at
    the 1e-7 default the relative value delta sits at f32 rounding scale too
    — without a looser live function stop every lane pays max_iter
    (CLAUDE.md; the ~87% RE-solve share of the fused sweep, BASELINE.md r5).
    """
    rel_delta = jnp.abs(value - prev_value) / jnp.maximum(
        jnp.maximum(jnp.abs(value), jnp.abs(prev_value)), 1.0
    )
    ftol = tolerance if rel_function_tolerance is None else rel_function_tolerance
    func_ok = rel_delta <= ftol
    grad_ok = grad_norm <= tolerance * jnp.maximum(initial_grad_norm, 1.0)
    return jnp.where(
        grad_ok,
        jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
        jnp.where(
            func_ok,
            jnp.int32(ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
        ),
    )


@flax.struct.dataclass
class LineSearchResult:
    step: Array
    value: Array
    gradient: Array
    success: Array  # bool
    trials: Array  # int32: trial points evaluated
    floor_exit: Array  # bool: ended by the float's floor, not by the tests


#: A search that has failed Armijo at step ``t`` is over once the decrease it
#: could still claim, ``|t * dg0|``, is no larger than this many ulps of the
#: function's own value: ``t`` only shrinks from there, so no later trial can
#: show a decrease that ``f0``'s floating-point value resolves. One constant,
#: not an option (why this value: PERF.md §6, PR 25).
LINE_SEARCH_FLOOR_K = 1.0


def wolfe_line_search(
    value_and_grad_fn,
    w: Array,
    f0: Array,
    g0: Array,
    direction: Array,
    t_init: Array,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_steps: int = 25,
    host_loop: bool = False,
    active: Array | bool = True,
) -> LineSearchResult:
    """Weak-Wolfe bisection line search, fully jittable.

    ``active`` (False = the caller has no use for this search): the loop
    condition is false from the first trip, so under ``vmap`` a lane whose
    solve has already stopped adds no trial to the bucket's lock-step loop.
    The loop also ends at the float's floor (:data:`LINE_SEARCH_FLOOR_K`),
    returning what running out of ``max_steps`` returns: the best Armijo
    point if one was seen, else failure.

    ``host_loop=True`` drives the same trial-step body from Python (see
    :func:`run_while`) so a host-level chunked ``value_and_grad_fn`` can be
    searched over; the default stays the one ``lax.while_loop``.

    Bracketing bisection: shrink on Armijo failure, expand (or bisect within
    the bracket) on curvature failure. Each trial costs one value_and_grad —
    cheap once jitted, since the whole optimizer step lives in one XLA
    program (SURVEY.md §7 "keep the whole optimizer step inside one jit").

    Replaces breeze's StrongWolfeLineSearch used by the reference's LBFGS
    (optimization/LBFGS.scala:97-107).
    """
    dg0 = jnp.vdot(g0, direction)
    # the floor and its test are OWL-QN's search's too: the end of this file
    floor = line_search_floor(f0)

    def body(state):
        i, t, lo, hi, t_best, f_best, g_best, has_best, _done, _floored = state
        f_t, g_t = value_and_grad_fn(w + t * direction)
        bad = jnp.isnan(f_t) | jnp.isinf(f_t)
        armijo = (f_t <= f0 + c1 * t * dg0) & ~bad
        curv = jnp.vdot(g_t, direction) >= c2 * dg0
        done = armijo & curv
        floored = at_line_search_floor(~armijo, t * dg0, floor)
        # Remember the best Armijo-satisfying point seen so far: if curvature
        # never holds within max_steps, we still return a genuine decrease
        # step instead of reporting a spurious line-search failure.
        better = armijo & (~has_best | (f_t < f_best))
        t_best = jnp.where(better, t, t_best)
        f_best = jnp.where(better, f_t, f_best)
        g_best = jax.tree.map(lambda a, b: jnp.where(better, a, b), g_t, g_best)
        has_best = has_best | armijo
        # Armijo failed -> step too long: shrink bracket from above.
        new_hi = jnp.where(~armijo, t, hi)
        # Armijo ok but curvature failed -> step too short: raise lower edge.
        new_lo = jnp.where(armijo & ~curv, t, lo)
        new_t = jnp.where(
            ~armijo,
            0.5 * (new_lo + new_hi),
            jnp.where(
                ~curv,
                jnp.where(jnp.isinf(new_hi), 2.0 * t, 0.5 * (new_lo + new_hi)),
                t,
            ),
        )
        return (i + 1, new_t, new_lo, new_hi, t_best, f_best, g_best, has_best,
                done, floored)

    def cond(state):
        i, *_rest, done, floored = state
        return (i < max_steps) & ~done & ~floored & active

    inf = jnp.asarray(jnp.inf, dtype=f0.dtype)
    zero = jnp.zeros((), dtype=f0.dtype)
    init = (
        jnp.int32(0),
        t_init.astype(f0.dtype),
        zero,
        inf,
        zero,
        f0,
        g0,
        jnp.asarray(False),
        jnp.asarray(False),
        jnp.asarray(False),
    )
    trials, _, _, _, t_best, f_best, g_best, has_best, _done, floored = run_while(
        cond, body, init, host=host_loop
    )
    success = has_best & (f_best < f0)
    return LineSearchResult(
        step=t_best, value=f_best, gradient=g_best, success=success,
        trials=trials, floor_exit=floored,
    )


def line_search_floor(f0: Array) -> Array:
    """The smallest decrease ``f0``'s floating-point value resolves:
    :data:`LINE_SEARCH_FLOOR_K` ulps of it. (Below ``wolfe_line_search``: a
    line that moves above its call of the objective re-keys every compiled
    program that holds the Pallas kernel, PERF.md 6, PR 24.)"""
    finfo = jnp.finfo(f0.dtype)
    return LINE_SEARCH_FLOOR_K * finfo.eps * jnp.maximum(jnp.abs(f0), finfo.tiny)


def at_line_search_floor(failed: Array, claimable: Array, floor: Array) -> Array:
    """True where a trial ``failed`` its decrease test and the decrease it
    could still claim is within ``floor``: later trials only claim less, so
    the search is over (``wolfe_line_search``, OWL-QN's backtracking)."""
    return failed & (jnp.abs(claimable) <= floor)


#: the reasons that can end a lane, in the order of ``lanes_max_iterations``
#: ... ``lanes_search_failed`` (a solve never returns NOT_CONVERGED)
_STOP_REASONS = (
    ConvergenceReason.MAX_ITERATIONS,
    ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE,
    ConvergenceReason.GRADIENT_WITHIN_TOLERANCE,
    ConvergenceReason.LINE_SEARCH_FAILED,
)


def bucket_count_parts(trace: LaneTrace, parts: int = 1) -> tuple[Array, Array]:
    """A solve's counts over each of ``parts`` runs of neighbouring lanes,
    before anything crosses a part: (maxima ``[parts, max_iter + 2]`` over
    ALL lanes of a part, padding too: its largest ``iterations``, then by
    iteration its slowest search's trials; sums ``[parts, 9]`` over a part's
    VALID lanes, in the order of :data:`BUCKET_COUNT_NAMES` from
    ``lane_trials`` on). int32. Where the lane axis lies over the chips of a
    mesh and ``parts`` is their number, a part is one chip's own lanes and
    nothing here crosses a chip; :func:`bucket_counts` brings the parts
    together, for one solve or for a stack of them at once (the fused step:
    one reduction across the chips a sweep, not one a bucket). ``parts`` has
    to divide the lanes."""
    lanes = trace.iterations.shape[0]
    if lanes % parts:
        raise ValueError(f"{parts} parts do not divide a bucket of {lanes} lanes")

    def by_part(per_lane):  # [lanes, ...] -> [parts, lanes / parts, ...]
        return per_lane.reshape((parts, lanes // parts) + per_lane.shape[1:])

    per_lane = [
        trace.line_search_trials, trace.floor_exits, trace.iterations,
        jnp.ones_like(trace.iterations),
        *(trace.reason == reason for reason in _STOP_REASONS),
        (jnp.zeros_like(trace.iterations) if trace.rejected_rounds is None
         else trace.rejected_rounds),
    ]
    # each [lanes] count reduced by itself, the results stacked: stacked first
    # and reduced once, the four-chip step's offsets gather of the widest
    # coordinate ran a quarter slower (PERF.md 6, PR 52, call 6)
    sums = jnp.stack([
        jnp.sum(by_part(jnp.where(trace.valid, count, 0)), axis=1, dtype=jnp.int32)
        for count in per_lane], axis=1)
    maxima = jnp.concatenate([
        jnp.max(by_part(trace.iterations), axis=1)[:, None],
        jnp.max(by_part(trace.search_trials), axis=1)], axis=1)
    return maxima.astype(jnp.int32), sums


def bucket_counts(maxima: Array, sums: Array) -> Array:
    """``[..., len(BUCKET_COUNT_NAMES)]`` int32 from :func:`bucket_count_parts`'
    pair (``[parts, ..., max_iter + 2]``, ``[parts, ..., 9]``; the solves of a
    stack between the two axes, their trips zero-padded to one width): the
    parts' largest and their sums, and the search trips summed over the
    iterations into ``lockstep_trials``."""
    largest = jnp.max(maxima, axis=0)
    return jnp.concatenate([
        jnp.sum(largest[..., 1:], axis=-1, keepdims=True, dtype=jnp.int32),
        largest[..., :1],
        jnp.sum(sums, axis=0, dtype=jnp.int32),
    ], axis=-1)
