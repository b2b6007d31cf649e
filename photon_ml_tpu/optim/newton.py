"""Damped Newton (IRLS) with an explicit elimination solve — the small-d solver.

No reference analogue: the reference solves every per-entity random-effect
subproblem with the iterative LBFGS/TRON family (RandomEffectOptimizationProblem
+ Optimizer.scala template loop), which is the right call on a JVM executor.
On TPU those vmapped iterative solves are bound by their op COUNT, not by
bandwidth: the two-loop recursion plus a Wolfe line search whose batched
while_loop runs every lane until the WORST lane satisfies the conditions
(``glmix-ml20m.sweeps``: the lanes' searches alone 30 % of the device's busy
seconds, 838 lock-step trials a sweep; PERF.md 5).

For the small dense dimensions where per-entity solves live (d ≲ a few
hundred), Newton's method is the op-minimal shape, a round under four scopes:
``newton/hessian`` (a batched ``[e, d, cap] x [e, cap, d]`` contraction, on a
TPU an MXU convolution, at precision "highest":
ops/objective._weighted_gram), ``newton/solve`` (one d-step Gauss-Jordan
elimination, NOT an XLA cholesky: batched small decompositions serialize per
matrix on TPU), ``newton/shrink`` (one fixed 4-point step-shrink: a vmapped
value evaluation that shares the feature read across the candidates, no
divergent line-search loop), ``newton/gradient`` (one value-and-gradient pass
at an accepted point). For the squared loss one full step is EXACT (ridge
normal equations), and a lane that can gain no more than the float resolves
stops (the floor in ``body``), so a ridge lane costs the exact step and one
check: two rounds. On the chip (a TPU v5e, the cell ``game-ymusic-r2.sweeps``:
155,674 ridge lanes in 14 buckets; PERF.md 5, PR 50) a sweep runs 30.67
lock-step rounds, 2.19 a bucket solve, where the same lanes without the
floor run 140, and of an episode's 1.27 busy seconds the elimination takes
0.35 (the lanes lie on the MAJOR axis of its ``[e, 16, 17]`` system: tiles
seven eighths padding), the Hessian pass 0.10 at 7.5 % of its HBM roofline,
the candidates' pass 0.04 and the gradient pass 0.04. This
file's earlier timings (a hand-rolled elimination at 0.09 ms against 3.4 ms
for cholesky at [2000, 16, 16], "~2 ms per RE coordinate per L-BFGS
iteration") were taken before the chip's benchmark on a plug-in that no
longer exists (BASELINE.md) and are NOT re-measured: Newton against L-BFGS
on the same logistic lanes has no cell yet (PERF.md 7 row 3).

GLM Hessians are PSD and every RE coordinate carries l2 > 0, so H + l2·I is
PD; a trace-scaled Levenberg jitter plus a gradient-direction fallback guard
the elimination against degenerate all-padding entities (their H is l2·I,
which eliminates cleanly — the fallback only fires on non-finite input).

Opt-in via ``OptimizerType.NEWTON``, or chosen by ``OptimizerType.AUTO`` for
the small dense vmapped solves (optim/optimizer.resolve_auto_optimizer); LBFGS
stays the default everywhere, so reference-parity solver behavior is unchanged
unless asked for. A result counts its work (SolverResult): a round's trials
are its five candidates, ``floor_exits`` 1 where the floor ended the solve,
``rejected_rounds`` the rounds that accepted no candidate.
"""

from __future__ import annotations

from typing import Callable

import flax.struct
import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (
    ConvergenceReason,
    SolverResult,
    line_search_floor,
)

Array = jax.Array

#: fixed step-shrink candidates: the current point (alpha=0 — the baseline
#: every accept/convergence decision compares against, through the same
#: value path), a full Newton step, and three shrinks for over-shooting
#: steps. Evaluated with one vmapped value pass (the candidates share
#: every feature read). Overshoots beyond the 16x shrink range are handled
#: by the adaptive LM damping, not by more candidates.
_ALPHAS = (0.0, 1.0, 0.5, 0.25, 0.0625)


def _solve_pd(h: Array, g: Array) -> Array:
    """Solve H p = g for PD H by unpivoted Gauss-Jordan elimination,
    vectorized over any batch dims with a fori over columns.

    XLA's native decompositions are the wrong tool for BATCHED small
    systems on TPU (their row-sequential inner loops serialize per matrix;
    the timings that first said so predate the chip's benchmark and are not
    re-measured: the module's docstring). PD systems need no pivoting (every
    pivot is a positive Schur complement diagonal; the caller's Levenberg
    jitter keeps them away from zero under f32)."""
    d = h.shape[-1]
    a = jnp.concatenate([h, g[..., None]], axis=-1)  # [..., d, d+1]

    def elim(i, a):
        piv = a[..., i, :] / a[..., i, i][..., None]  # [..., d+1]
        factors = a[..., :, i]  # [..., d]
        a = a - factors[..., None] * piv[..., None, :]
        return a.at[..., i, :].set(piv)

    a = lax.fori_loop(0, d, elim, a)
    return a[..., :, d]


@flax.struct.dataclass
class _NewtonState:
    w: Array
    f: Array
    g: Array
    #: Levenberg-Marquardt damping as a FRACTION of trace(H)/d: grows x64
    #: on a rejected round (a Newton step overshooting by more than the
    #: fixed alphas' 16x range — reachable from flat regions of Poisson /
    #: weakly-regularized logistic), decays x0.25 on acceptance. The
    #: fixed-shape replacement for an unbounded backtracking loop.
    damping: Array
    iteration: Array
    reason: Array
    value_history: Array
    grad_norm_history: Array
    #: int32: rounds that accepted no candidate; 1 where the floor ended the solve
    rejected: Array
    floor_exit: Array


def minimize_newton(
    value_and_grad_fn: Callable[[Array], tuple[Array, Array]],
    hessian_matrix_fn: Callable[[Array], Array],
    w0: Array,
    *,
    value_fn: Callable[[Array], Array] | None = None,
    max_iter: int = 15,
    tolerance: float = 1e-7,
    rel_function_tolerance: float | None = None,
) -> SolverResult:
    """Minimize a twice-differentiable convex objective by damped Newton
    (Levenberg-Marquardt safeguarded).

    ``hessian_matrix_fn(w)`` returns the full [d, d] Hessian INCLUDING any
    regularizer (GLMObjective.hessian_matrix semantics). Convergence when
    ‖g‖ <= tolerance * max(‖g0‖, 1) (the LBFGS/TRON relative test) or on a
    clean round whose best step changes the value by <= tolerance
    relative (the test that actually fires in f32). A round where even the
    16x-shrunk step fails to improve — a Newton overshoot from a flat
    region (Poisson, weakly-regularized logistic) — grows the LM damping
    x64 and retries rather than terminating, so the solver always makes
    progress instead of silently returning w0. jit- and vmap-safe (fixed
    shapes, no divergent inner loops).

    ``rel_function_tolerance`` (None = use ``tolerance``, unchanged
    behavior): a separate threshold for the function-decrease stop — the
    live-stop knob the LBFGS/OWLQN family adopted from this solver's
    pattern (optim/common.check_convergence).
    """
    dtype = w0.dtype
    w0 = jnp.asarray(w0, dtype)
    d = w0.shape[-1]
    if value_fn is None:
        value_fn = lambda w: value_and_grad_fn(w)[0]
    with jax.named_scope("newton/gradient"):
        f0, g0 = value_and_grad_fn(w0)
    g0_norm = jnp.linalg.norm(g0)
    alphas = jnp.asarray(_ALPHAS, dtype)
    ftol = tolerance if rel_function_tolerance is None else rel_function_tolerance

    nan_hist = jnp.full((max_iter + 1,), jnp.nan, dtype)
    init = _NewtonState(
        w=w0,
        f=f0,
        g=g0,
        damping=jnp.asarray(0.0, dtype),
        iteration=jnp.int32(0),
        # warm starts arrive already-stationary: stop before the first solve
        reason=jnp.where(
            g0_norm <= tolerance,
            jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
        ),
        value_history=nan_hist.at[0].set(f0),
        grad_norm_history=nan_hist.at[0].set(g0_norm),
        rejected=jnp.int32(0),
        floor_exit=jnp.int32(0),
    )

    def cond(state: _NewtonState):
        return (state.iteration < max_iter) & (
            state.reason == ConvergenceReason.NOT_CONVERGED
        )

    def body(state: _NewtonState):
        with jax.named_scope("newton/hessian"):
            h = hessian_matrix_fn(state.w)
        with jax.named_scope("newton/solve"):
            # trace-scaled Levenberg jitter (f32 PD safety) + the adaptive LM
            # damping carried in the state. The scale is floored so the
            # damping still regularizes a zero-trace Hessian (all-zero H with
            # l2=0, reachable outside the RE path): without the floor the
            # jitter collapses to 1e-30 and damping growth multiplies zero,
            # leaving the gradient fallback's 1e-12 divisor to produce huge
            # steps.
            scale = jnp.maximum(jnp.trace(h) / d, 1e-12)
            jitter = (1e-7 + state.damping) * scale + 1e-30
            p = -_solve_pd(h + jitter * jnp.eye(d, dtype=h.dtype), state.g)
            # degenerate Hessian (non-finite solve): steepest descent scaled
            # by the largest curvature — only reachable on non-finite input
            ok = jnp.all(jnp.isfinite(p))
            p_fallback = -state.g / jnp.maximum(jnp.max(jnp.diag(h)), 1e-12)
            p = jnp.where(ok, p, p_fallback)

        # fixed step-shrink: ONE vmapped value pass over all candidates,
        # alpha=0 included so every accept/convergence comparison below is
        # between evaluations of the SAME value path (value_fn) — state.f
        # may come from the Pallas kernel, whose ~5e-6 relative delta vs
        # the autodiff value would otherwise decide accepts near optimum
        with jax.named_scope("newton/shrink"):
            vals = jax.vmap(lambda a: value_fn(state.w + a * p))(alphas)
        vals = jnp.where(jnp.isfinite(vals), vals, jnp.inf)
        best = jnp.argmin(vals[1:]) + 1  # best NONZERO step
        improved = vals[best] < vals[0]
        # nothing at solver tolerance left to gain in this direction: the
        # function-decrease test is what actually fires in f32 (an exact
        # Newton step leaves ‖g‖ at rounding scale, which warm-started RE
        # solves' large g0 never map below the relative gradient
        # tolerance, and without a live stop every vmapped lane pays
        # max_iter full iterations)
        f_delta_small = jnp.abs(vals[0] - vals[best]) <= ftol * (
            jnp.abs(vals[0]) + 1e-30
        )
        # the float's floor (optim/common.line_search_floor, L-BFGS's): the
        # objective is convex, so no candidate can lie further below vals[0]
        # than the slope claims for the full step, |g . p|. Where that is
        # within an ulp of the value no candidate's value can SHOW a
        # decrease: whether one reads lower is rounding, in this round and in
        # every later one (same g, same H). After the one exact step of a
        # ridge lane g is rounding and the claim some 1e-9 of an ulp, while
        # the candidates' values scatter by an ulp or two, strictly below
        # vals[0] for some lanes and not for others: the measured values
        # cannot carry the rule (PERF.md 6, PR 50; TRON's floor reads the
        # predicted decrease for the same reason, PR 40). Under heavy damping
        # the step, and so the claim, is artificially small: no stop there,
        # as for a flat round.
        at_floor = (jnp.abs(jnp.vdot(state.g, p)) <= line_search_floor(vals[0])) & (
            state.damping <= 1e-3
        )
        w_new = jnp.where(improved, state.w + alphas[best] * p, state.w)
        # rejected round: w_new == state.w, so the value+grad it carries is
        # already exact — reuse it. lax.cond skips the pass entirely on
        # un-vmapped solves; vmapped lanes lower to a select-both-branches
        # (no worse than the unconditional recompute this replaces).
        with jax.named_scope("newton/gradient"):
            f_new, g_new = lax.cond(
                improved,
                lambda: value_and_grad_fn(w_new),
                lambda: (state.f, state.g),
            )

        # LM damping: a rejected round means the step overshot past the
        # alphas' 16x range — damp hard and retry; acceptance decays the
        # damping back toward pure Newton
        damping = jnp.where(
            improved,
            state.damping * 0.25,
            jnp.maximum(state.damping * 64.0, 1e-6),
        )

        gnorm = jnp.linalg.norm(g_new)
        g0n = state.grad_norm_history[0]
        # converged only on a clean (undamped-ish) ACCEPTED flat round:
        # heavy damping makes steps artificially tiny, and a rejected-but-
        # flat round (best nonzero step within tolerance but slightly
        # worse) must take one damped — more gradient-like — retry before
        # declaring convergence, in case only the undamped Newton direction
        # was poor
        flat_round = f_delta_small & improved & (state.damping <= 1e-3)
        reason = jnp.where(
            gnorm <= tolerance * jnp.maximum(g0n, 1.0),
            jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
            jnp.where(
                flat_round | at_floor,
                jnp.int32(ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE),
                jnp.int32(ConvergenceReason.NOT_CONVERGED),
            ),
        )
        it = state.iteration + 1
        return _NewtonState(
            w=w_new,
            f=f_new,
            g=g_new,
            damping=damping,
            iteration=it,
            reason=reason,
            value_history=state.value_history.at[it].set(f_new),
            grad_norm_history=state.grad_norm_history.at[it].set(gnorm),
            rejected=state.rejected + (~improved).astype(jnp.int32),
            floor_exit=(
                at_floor & (reason == ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE)
            ).astype(jnp.int32),
        )

    final = lax.while_loop(cond, body, init)
    reason = jnp.where(
        final.reason == ConvergenceReason.NOT_CONVERGED,
        jnp.int32(ConvergenceReason.MAX_ITERATIONS),
        final.reason,
    )
    rounds = jnp.arange(max_iter + 1, dtype=jnp.int32)
    return SolverResult(
        coefficients=final.w,
        value=final.f,
        gradient_norm=jnp.linalg.norm(final.g),
        iterations=final.iteration,
        reason=reason,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        # a round's trials are its step-shrink candidates, one value pass
        line_search_trials=jnp.where(
            (rounds >= 1) & (rounds <= final.iteration), jnp.int32(len(_ALPHAS)), 0
        ),
        floor_exits=final.floor_exit,
        rejected_rounds=final.rejected,
    )
