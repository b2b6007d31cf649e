"""OWL-QN: Orthant-Wise Limited-memory Quasi-Newton for L1 regularization.

Reference parity: photon-lib optimization/OWLQN.scala:40-86 (breeze OWLQN
wrapper; mutable l1RegularizationWeight for the elastic-net regularization
path). The L2 part of elastic net stays in the smooth objective; this solver
adds λ₁‖w‖₁ via the pseudo-gradient and orthant projection (Andrew & Gao 2007).

Jittable: one lax.while_loop, fixed-shape ordered L-BFGS history, masked
projection — vmaps over entities like the plain L-BFGS solver, and by
``minimize_lbfgs``'s two rules for lock-step lanes: a lane whose solve has
stopped adds no trial to the block's search loop, and a search ends at the
float's floor (optim/common.LINE_SEARCH_FLOOR_K). ``SolverResult`` counts what
L-BFGS counts: a search's trial points by iteration, the searches the floor
ended.
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
    at_line_search_floor,
    check_convergence,
    line_search_floor,
    run_while,
)
from photon_ml_tpu.optim.lbfgs import (
    empty_history,
    push_pair,
    require_history_form,
    two_loop_direction,
)

Array = jax.Array


def pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """Pseudo-gradient of f(w) = L(w) + l1*‖w‖₁ (Andrew & Gao 2007, eq. 4)."""
    right = g + l1
    left = g - l1
    return jnp.where(
        w > 0.0,
        right,
        jnp.where(
            w < 0.0,
            left,
            jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0)),
        ),
    )


@flax.struct.dataclass
class _OWLQNState:
    w: Array
    f: Array  # smooth + L1 value
    g: Array  # smooth gradient
    s_hist: Array
    y_hist: Array
    rho: Array
    count: Array
    iteration: Array
    reason: Array
    g0_norm: Array
    value_history: Array
    grad_norm_history: Array
    line_search_trials: Array  # int32 [max_iter + 1]: trials of iteration i's search
    floor_exits: Array  # int32: searches the float's floor ended


def minimize_owlqn(
    value_and_grad_fn: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    *,
    l1_weight: float,
    max_iter: int = 100,
    history: int = 10,
    tolerance: float = 1e-7,
    rel_function_tolerance: float | None = None,
    max_line_search_steps: int = 30,
    host_loop: bool = False,
    state_observer=None,
    resume_state: "_OWLQNState | None" = None,
) -> SolverResult:
    """Minimize smooth(w) + l1_weight * ‖w‖₁.

    ``value_and_grad_fn`` covers only the smooth part (loss + optional L2).
    ``rel_function_tolerance``: live function-decrease stop for warm-started
    vmapped lanes (None = use ``tolerance``; optim/common.check_convergence).
    ``host_loop=True``: identical body math driven from Python so
    ``value_and_grad_fn`` may be a host-level streaming epoch accumulator
    (optim/common.run_while).

    ``state_observer`` / ``resume_state`` (host_loop only): per-iteration
    state hook + checkpointed re-entry for crash-safe streaming solves —
    same contract as optim/lbfgs.minimize_lbfgs.
    """
    if (state_observer is not None or resume_state is not None) and not host_loop:
        raise ValueError(
            "state_observer/resume_state require host_loop=True (solver-"
            "state checkpointing exists for host-driven streaming solves)"
        )
    dtype = w0.dtype
    d = w0.shape[0]
    m = history
    l1 = jnp.asarray(l1_weight, dtype)

    def full_value(w, smooth_f):
        return smooth_f + l1 * jnp.sum(jnp.abs(w))

    if resume_state is not None:
        require_history_form(resume_state, m, d)
        init = resume_state
    else:
        w0 = jnp.asarray(w0, dtype)
        sf0, g0 = value_and_grad_fn(w0)
        f0 = full_value(w0, sf0)
        with jax.named_scope("owlqn/pseudo_gradient"):
            pg0 = pseudo_gradient(w0, g0, l1)
            g0_norm = jnp.linalg.norm(pg0)

        nan_hist = jnp.full((max_iter + 1,), jnp.nan, dtype)
        s_hist, y_hist, rho, count = empty_history(m, d, dtype)
        init = _OWLQNState(
            w=w0,
            f=f0,
            g=g0,
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            iteration=jnp.int32(0),
            reason=jnp.where(
                g0_norm <= tolerance,
                jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
                jnp.int32(ConvergenceReason.NOT_CONVERGED),
            ),
            g0_norm=g0_norm,
            value_history=nan_hist.at[0].set(f0),
            grad_norm_history=nan_hist.at[0].set(g0_norm),
            line_search_trials=jnp.zeros((max_iter + 1,), jnp.int32),
            floor_exits=jnp.int32(0),
        )

    def cond(state: _OWLQNState):
        return (state.iteration < max_iter) & (
            state.reason == ConvergenceReason.NOT_CONVERGED
        )

    def body(state: _OWLQNState):
        # False only under vmap, where a stopped lane's body still runs (and
        # is thrown away): its line search must not hold the block's
        # lock-step loop open. Un-vmapped, ``cond`` guarantees it.
        live = state.reason == ConvergenceReason.NOT_CONVERGED
        with jax.named_scope("owlqn/pseudo_gradient"):
            pg = pseudo_gradient(state.w, state.g, l1)
        direction = two_loop_direction(
            pg, state.s_hist, state.y_hist, state.rho, state.count
        )
        with jax.named_scope("owlqn/pseudo_gradient"):
            # Constrain direction to the descent orthant of -pg.
            direction = jnp.where(direction * (-pg) > 0.0, direction, 0.0)
            # Fall back to steepest descent on the pseudo-gradient if degenerate.
            degenerate = jnp.vdot(direction, pg) >= 0.0
            direction = jnp.where(degenerate, -pg, direction)

            # Orthant of the search: sign(w), or sign(-pg) where w == 0.
            xi = jnp.where(state.w != 0.0, jnp.sign(state.w), jnp.sign(-pg))

        t_init = jnp.where(
            state.count == 0,
            1.0 / jnp.maximum(jnp.linalg.norm(pg), 1.0),
            jnp.ones((), dtype),
        )

        # Projected backtracking: evaluate the full (smooth + L1) objective at
        # the orthant-projected trial point; Armijo decrease measured against
        # actual displacement dotted with the pseudo-gradient.
        c1 = 1e-4
        floor = line_search_floor(state.f)

        def ls_body(ls_state):
            i, t, _w, _f, _g, _done, _floored = ls_state
            cand = state.w + t * direction
            cand = jnp.where(cand * xi > 0.0, cand, 0.0)  # orthant projection
            sf, sg = value_and_grad_fn(cand)
            f_t = full_value(cand, sf)
            decrease = jnp.vdot(pg, cand - state.w)
            ok = (
                (f_t <= state.f + c1 * decrease)
                & ~(jnp.isnan(f_t) | jnp.isinf(f_t))
                & (f_t < state.f)
            )
            # ``|decrease|`` only shrinks as t halves (a coordinate's move is
            # t * direction, or its clip at zero): at the floor no later trial
            # can show a decrease that ``state.f`` resolves
            floored = at_line_search_floor(~ok, decrease, floor)
            return (i + 1, t * 0.5, cand, f_t, sg, ok, floored)

        def ls_cond(ls_state):
            i, _t, _w, _f, _g, done, floored = ls_state
            return (i < max_line_search_steps) & ~done & ~floored & live

        with jax.named_scope("owlqn/line_search"):
            ls_trials, _, w_new, f_new, g_new, ls_ok, ls_floored = run_while(
                ls_cond,
                ls_body,
                (jnp.int32(0), t_init, state.w, state.f, state.g,
                 jnp.asarray(False), jnp.asarray(False)),
                host=host_loop,
            )

        s_hist, y_hist, rho, count = push_pair(
            state.s_hist,
            state.y_hist,
            state.rho,
            state.count,
            w_new - state.w,
            g_new - state.g,  # smooth gradients, per Andrew & Gao
            ls_ok,
        )

        with jax.named_scope("owlqn/pseudo_gradient"):
            pg_new = pseudo_gradient(w_new, g_new, l1)
            gnorm = jnp.linalg.norm(pg_new)
        reason = jnp.where(
            ls_ok,
            check_convergence(
                value=f_new,
                prev_value=state.f,
                grad_norm=gnorm,
                initial_grad_norm=state.g0_norm,
                tolerance=tolerance,
                rel_function_tolerance=rel_function_tolerance,
            ),
            jnp.int32(ConvergenceReason.LINE_SEARCH_FAILED),
        )

        it = state.iteration + 1
        return _OWLQNState(
            w=jnp.where(ls_ok, w_new, state.w),
            f=jnp.where(ls_ok, f_new, state.f),
            g=jnp.where(ls_ok, g_new, state.g),
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            iteration=it,
            reason=reason,
            g0_norm=state.g0_norm,
            value_history=state.value_history.at[it].set(jnp.where(ls_ok, f_new, state.f)),
            grad_norm_history=state.grad_norm_history.at[it].set(gnorm),
            line_search_trials=state.line_search_trials.at[it].set(ls_trials),
            floor_exits=state.floor_exits + ls_floored.astype(jnp.int32),
        )

    final = run_while(cond, body, init, host=host_loop, observer=state_observer)
    reason = jnp.where(
        final.reason == ConvergenceReason.NOT_CONVERGED,
        jnp.int32(ConvergenceReason.MAX_ITERATIONS),
        final.reason,
    )
    with jax.named_scope("owlqn/pseudo_gradient"):
        pg_final = pseudo_gradient(final.w, final.g, l1)
    return SolverResult(
        coefficients=final.w,
        value=final.f,
        gradient_norm=jnp.linalg.norm(pg_final),
        iterations=final.iteration,
        reason=reason,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        line_search_trials=final.line_search_trials,
        floor_exits=final.floor_exits,
    )
