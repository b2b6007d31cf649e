"""TRON: trust-region Newton method with truncated conjugate-gradient inner loop.

Reference parity: photon-lib optimization/TRON.scala (a LIBLINEAR port):
outer trust-region loop with eta/sigma update rules (TRON.scala:152-253),
inner truncated CG calling hessianVector per step (TRON.scala:278-338),
defaults maxIter=15, tolerance=1e-5, maxNumImprovementFailures — here the CG
cap defaults to 20 like the reference (TRON.scala:257-262).

TPU-native: outer loop and CG are nested lax.while_loops in one XLA program;
each CG step is one Hessian-vector product, whatever ``hessian_vector_fn`` is.
For a dense GLM on the chip, un-vmapped, it is ONE Pallas custom call that reads
X once (ops/pallas_glm.fused_hessian_vector, by ``GLMObjective``'s own rule:
4.60 ms at 400,000 x 2,000 float32 on a v5e, PERF.md 5, PR 41); elsewhere a
jvp-of-grad, which compiled for that size is two multiply-reduce fusions that
each read X once, on the vector unit in float32, and a third the compiler
hoists out of the CG loop, the margins ``X w``, once a round (PERF.md 6, PR 40).
TRON needs only O(4) work vectors vs L-BFGS's 2m, which is why the reference
positions it for high-dimensional L2 problems — the same argument holds for
sharded 1B-coefficient vectors (SURVEY.md §7).

Three ``jax.named_scope``s mark where a round's device time goes (metadata
only): ``tron/cg`` round the truncated CG, ``tron/hv`` round every product
inside it, ``tron/update`` round the ratio test and the radius update. The
round's one value-and-gradient evaluation stands outside all three.
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
    run_while,
)

Array = jax.Array

# LIBLINEAR trust-region constants (TRON.scala:168-175)
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


def _truncated_cg(hv_fn, g: Array, delta: Array, max_cg: int, cg_tol: Array,
                  host_loop: bool = False):
    """Solve H z ≈ -g within the trust region ‖z‖ <= delta.

    Returns (z, r, hit_boundary, cg_iters): ``r = -g - H z`` is the CG's own
    residual at the step returned (LIBLINEAR keeps it for the predicted
    reduction) and ``cg_iters`` the Hessian-vector products taken, one a CG
    step. Steihaug-Toint truncated CG
    (reference TRON.truncatedConjugateGradientMethod, TRON.scala:278-338).
    ``host_loop=True`` drives the same CG body from Python so ``hv_fn`` may
    be a host-level streaming epoch accumulator (optim/common.run_while).
    """
    d0 = -g
    r0 = -g

    def boundary_tau(z, dvec):
        # tau >= 0 with ‖z + tau*d‖ = delta
        zz = jnp.vdot(z, z)
        zd = jnp.vdot(z, dvec)
        dd = jnp.maximum(jnp.vdot(dvec, dvec), 1e-30)
        rad = jnp.sqrt(jnp.maximum(zd * zd + dd * (delta * delta - zz), 0.0))
        return (-zd + rad) / dd

    def body(state):
        z, r, dvec, i, _hit, _done = state
        with jax.named_scope("tron/hv"):
            hd = hv_fn(dvec)
        dhd = jnp.vdot(dvec, hd)
        rr = jnp.vdot(r, r)
        # Negative curvature (non-convex edge case): go to the boundary.
        neg_curv = dhd <= 0.0
        alpha = rr / jnp.maximum(dhd, 1e-30)
        z_try = z + alpha * dvec
        outside = jnp.linalg.norm(z_try) >= delta
        take_boundary = neg_curv | outside
        # the residual follows the step TAKEN, so that at the boundary too
        # it is -g - H z (the outer round reads z . r)
        taken = jnp.where(take_boundary, boundary_tau(z, dvec), alpha)
        z_new = jnp.where(take_boundary, z + taken * dvec, z_try)
        r_new = r - taken * hd
        rr_new = jnp.vdot(r_new, r_new)
        converged = jnp.sqrt(rr_new) <= cg_tol
        beta = rr_new / jnp.maximum(rr, 1e-30)
        d_new = r_new + beta * dvec
        done = take_boundary | converged
        return (z_new, r_new, d_new, i + 1, take_boundary, done)

    def cond(state):
        _z, _r, _d, i, _hit, done = state
        return (i < max_cg) & ~done

    z0 = jnp.zeros_like(g)
    z, r, _d, iters, hit, _done = run_while(
        cond, body,
        (z0, r0, d0, jnp.int32(0), jnp.asarray(False), jnp.asarray(False)),
        host=host_loop,
    )
    return z, r, hit, iters


@flax.struct.dataclass
class _TRONState:
    w: Array
    f: Array
    g: Array
    delta: Array
    iteration: Array
    reason: Array
    value_history: Array
    grad_norm_history: Array
    hv_products: Array  # int32 [max_iter + 1]: round i's Hessian-vector products
    floor_exits: Array  # int32: rounds the float's floor ended (0 or 1)


#: A round is at the float's floor once the decrease its model predicts lies
#: within this many ulps of the objective's own value: no evaluation of the
#: objective can then confirm the step or speak against it, so the measured
#: decrease is not consulted, the step is kept and the round is the solve's
#: last. The measured decrease is NOT part of the test: a float32 sum over
#: 400,000 rows, accumulated tile by tile, differs between two evaluations a
#: step of 1e-3 apart by up to some ten ulps of its value (PERF.md 6, PR 40:
#: with it in the test, rounds predicting 0.1 to 1 ulp were rejected on a
#: measured -4 to -9). One constant, not an option: the rule
#: ``LINE_SEARCH_FLOOR_K`` gives the line search (optim/common.py).
TRON_FLOOR_K = 4.0


def minimize_tron(
    value_and_grad_fn: Callable[[Array], tuple[Array, Array]],
    hessian_vector_fn: Callable[[Array, Array], Array],
    w0: Array,
    *,
    max_iter: int = 15,
    tolerance: float = 1e-5,
    rel_function_tolerance: float | None = None,
    max_cg_iter: int = 20,
    cg_forcing: float = 0.1,
    host_loop: bool = False,
    state_observer=None,
    resume_state: "_TRONState | None" = None,
) -> SolverResult:
    """Minimize a twice-differentiable convex objective with TRON.

    ``hessian_vector_fn(w, v)`` returns H(w) @ v. Convergence when
    ‖g‖ <= tolerance * ‖g0‖ (LIBLINEAR's test, TRON.scala:208), or at the
    float's floor (:data:`TRON_FLOOR_K`): a round whose predicted decrease
    is rounding of the objective's value keeps its step, whatever decrease
    was measured, and ends the solve with
    ``FUNCTION_VALUES_WITHIN_TOLERANCE``; no step is rejected on a measured
    decrease that cannot be told from noise. In float64 that floor lies far
    under what the gradient test leaves.

    What the result counts (:class:`SolverResult` has no field of TRON's
    own): ``line_search_trials[i]`` holds the Hessian-vector products of
    round ``i``, which are its CG steps and nothing else (the predicted
    reduction comes from the CG's residual, LIBLINEAR's
    ``-0.5 (g.s - s.r)``); ``floor_exits`` the rounds the floor ended. A
    REJECTED round is one whose ``value_history`` and ``grad_norm_history``
    slots both repeat the slots before them.

    ``host_loop=True``: the identical outer/CG body math driven from
    Python loops so both callbacks may be host-level streaming epoch
    accumulators (optim/common.run_while).

    ``rel_function_tolerance`` (None = reference behavior, no function
    test): live relative function-decrease stop on accepted rounds — the
    same warm-start exit the LBFGS/OWLQN/NEWTON family gained
    (optim/common.check_convergence semantics).

    ``state_observer`` / ``resume_state`` (host_loop only): per-outer-
    iteration state hook + checkpointed re-entry for crash-safe streaming
    solves — same contract as optim/lbfgs.minimize_lbfgs. The inner CG
    loop is never observed or resumed mid-flight: an outer iteration is
    the atomic (epoch-boundary) unit.
    """
    if (state_observer is not None or resume_state is not None) and not host_loop:
        raise ValueError(
            "state_observer/resume_state require host_loop=True (solver-"
            "state checkpointing exists for host-driven streaming solves)"
        )
    dtype = w0.dtype
    if resume_state is not None:
        init = resume_state
    else:
        w0 = jnp.asarray(w0, dtype)
        f0, g0 = value_and_grad_fn(w0)
        g0_norm = jnp.linalg.norm(g0)

        nan_hist = jnp.full((max_iter + 1,), jnp.nan, dtype)
        init = _TRONState(
            w=w0,
            f=f0,
            g=g0,
            delta=g0_norm,
            iteration=jnp.int32(0),
            # Warm starts arrive already-stationary: stop before paying a
            # CG loop. (The in-loop test is relative to g0; at iteration 0
            # only an absolute test is meaningful.)
            reason=jnp.where(
                g0_norm <= tolerance,
                jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
                jnp.int32(ConvergenceReason.NOT_CONVERGED),
            ),
            value_history=nan_hist.at[0].set(f0),
            grad_norm_history=nan_hist.at[0].set(g0_norm),
            hv_products=jnp.zeros((max_iter + 1,), jnp.int32),
            floor_exits=jnp.int32(0),
        )
    finfo = jnp.finfo(dtype)

    def cond(state: _TRONState):
        return (state.iteration < max_iter) & (
            state.reason == ConvergenceReason.NOT_CONVERGED
        )

    def body(state: _TRONState):
        gnorm = jnp.linalg.norm(state.g)
        hv = lambda v: hessian_vector_fn(state.w, v)
        with jax.named_scope("tron/cg"):
            step, residual, hit_boundary, cg_iters = _truncated_cg(
                hv, state.g, state.delta, max_cg_iter, cg_forcing * gnorm,
                host_loop=host_loop,
            )
        with jax.named_scope("tron/update"):
            gs = jnp.vdot(state.g, step)
            # s.H.s = -(g.s + s.r): no product beyond the CG's own
            prered = -0.5 * (gs - jnp.vdot(step, residual))
        f_new, g_new = value_and_grad_fn(state.w + step)
        with jax.named_scope("tron/update"):
            actred = state.f - f_new
            finite = ~(jnp.isnan(f_new) | jnp.isinf(f_new))
            floor = TRON_FLOOR_K * finfo.eps * jnp.maximum(jnp.abs(state.f), 1.0)
            at_floor = finite & (prered <= floor)

            snorm = jnp.linalg.norm(step)
            # Trust-region radius update (LIBLINEAR-style, TRON.scala:214-236)
            delta = state.delta
            # alpha interpolation factor for severe failures
            alpha = jnp.where(
                f_new - state.f - gs <= 0.0,
                SIGMA3,
                jnp.maximum(SIGMA1, -0.5 * (gs / jnp.minimum(f_new - state.f - gs, -1e-30))),
            )
            delta = jnp.where(
                actred < ETA0 * prered,
                jnp.minimum(jnp.maximum(alpha, SIGMA1) * snorm, SIGMA2 * delta),
                jnp.where(
                    actred < ETA1 * prered,
                    jnp.maximum(SIGMA1 * delta, jnp.minimum(alpha * snorm, SIGMA2 * delta)),
                    jnp.where(
                        actred < ETA2 * prered,
                        jnp.maximum(SIGMA1 * delta, jnp.minimum(alpha * snorm, SIGMA3 * delta)),
                        jnp.where(
                            hit_boundary,
                            jnp.minimum(SIGMA3 * delta, jnp.maximum(delta, snorm)),
                            jnp.maximum(delta, jnp.minimum(alpha * snorm, SIGMA3 * delta)),
                        ),
                    ),
                ),
            )
            # at the floor the measured decrease says nothing of the step:
            # neither a rejection nor a smaller region follows from it
            delta = jnp.where(at_floor, state.delta, delta)
            accept = ((actred > ETA0 * prered) | at_floor) & finite
        w_acc = jnp.where(accept, state.w + step, state.w)
        f_acc = jnp.where(accept, f_new, state.f)
        g_acc = jnp.where(accept, g_new, state.g)

        gnorm_acc = jnp.linalg.norm(g_acc)
        g0n = state.grad_norm_history[0]
        reason = jnp.where(
            gnorm_acc <= tolerance * jnp.maximum(g0n, 1e-30),
            jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
        )
        # A collapsed trust region means no further progress is possible;
        # nor does a round at the float's floor leave any to observe.
        floor_exit = at_floor & (reason == ConvergenceReason.NOT_CONVERGED)
        reason = jnp.where(
            (delta < 1e-12) | floor_exit,
            jnp.int32(ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE),
            reason,
        )
        if rel_function_tolerance is not None:
            # live stop: an ACCEPTED round whose relative decrease is below
            # threshold (same test as optim/common.check_convergence)
            rel_delta = jnp.abs(f_acc - state.f) / jnp.maximum(
                jnp.maximum(jnp.abs(f_acc), jnp.abs(state.f)), 1.0
            )
            reason = jnp.where(
                accept
                & (rel_delta <= rel_function_tolerance)
                & (reason == ConvergenceReason.NOT_CONVERGED),
                jnp.int32(ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE),
                reason,
            )

        it = state.iteration + 1
        return _TRONState(
            w=w_acc,
            f=f_acc,
            g=g_acc,
            delta=delta,
            iteration=it,
            reason=reason,
            value_history=state.value_history.at[it].set(f_acc),
            grad_norm_history=state.grad_norm_history.at[it].set(gnorm_acc),
            hv_products=state.hv_products.at[it].set(cg_iters),
            floor_exits=state.floor_exits + floor_exit.astype(jnp.int32),
        )

    final = run_while(cond, body, init, host=host_loop, observer=state_observer)
    reason = jnp.where(
        final.reason == ConvergenceReason.NOT_CONVERGED,
        jnp.int32(ConvergenceReason.MAX_ITERATIONS),
        final.reason,
    )
    return SolverResult(
        coefficients=final.w,
        value=final.f,
        gradient_norm=jnp.linalg.norm(final.g),
        iterations=final.iteration,
        reason=reason,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        line_search_trials=final.hv_products,
        floor_exits=final.floor_exits,
    )
