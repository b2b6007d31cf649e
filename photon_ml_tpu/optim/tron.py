"""TRON: trust-region Newton method with truncated conjugate-gradient inner loop.

Reference parity: photon-lib optimization/TRON.scala (a LIBLINEAR port):
outer trust-region loop with eta/sigma update rules (TRON.scala:152-253),
inner truncated CG calling hessianVector per step (TRON.scala:278-338),
defaults maxIter=15, tolerance=1e-5, maxNumImprovementFailures — here the CG
cap defaults to 20 like the reference (TRON.scala:257-262).

TPU-native: outer loop and CG are nested lax.while_loops in one XLA program;
each CG step is one Hessian-vector product (a jvp-of-grad — two fused passes
over the data block on the MXU). TRON needs only O(4) work vectors vs
L-BFGS's 2m, which is why the reference positions it for high-dimensional
L2 problems — the same argument holds for sharded 1B-coefficient vectors
(SURVEY.md §7).
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
    no_line_search_counts,
    run_while,
)

Array = jax.Array

# LIBLINEAR trust-region constants (TRON.scala:168-175)
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


def _truncated_cg(hv_fn, g: Array, delta: Array, max_cg: int, cg_tol: Array,
                  host_loop: bool = False):
    """Solve H z ≈ -g within the trust region ‖z‖ <= delta.

    Returns (z, hit_boundary, cg_iters). Steihaug-Toint truncated CG
    (reference TRON.truncatedConjugateGradientMethod, TRON.scala:278-338).
    ``host_loop=True`` drives the same CG body from Python so ``hv_fn`` may
    be a host-level streaming epoch accumulator (optim/common.run_while).
    """
    d0 = -g
    r0 = -g

    def boundary_step(z, dvec):
        # tau >= 0 with ‖z + tau*d‖ = delta
        zz = jnp.vdot(z, z)
        zd = jnp.vdot(z, dvec)
        dd = jnp.maximum(jnp.vdot(dvec, dvec), 1e-30)
        rad = jnp.sqrt(jnp.maximum(zd * zd + dd * (delta * delta - zz), 0.0))
        tau = (-zd + rad) / dd
        return z + tau * dvec

    def body(state):
        z, r, dvec, i, _hit, _done = state
        hd = hv_fn(dvec)
        dhd = jnp.vdot(dvec, hd)
        rr = jnp.vdot(r, r)
        # Negative curvature (non-convex edge case): go to the boundary.
        neg_curv = dhd <= 0.0
        alpha = rr / jnp.maximum(dhd, 1e-30)
        z_try = z + alpha * dvec
        outside = jnp.linalg.norm(z_try) >= delta
        z_bound = boundary_step(z, dvec)
        take_boundary = neg_curv | outside
        z_new = jnp.where(take_boundary, z_bound, z_try)
        r_new = r - alpha * hd
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
    z, _r, _d, iters, hit, _done = run_while(
        cond, body,
        (z0, r0, d0, jnp.int32(0), jnp.asarray(False), jnp.asarray(False)),
        host=host_loop,
    )
    return z, hit, iters


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
    ‖g‖ <= tolerance * ‖g0‖ (LIBLINEAR's test, TRON.scala:208).

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
        )

    def cond(state: _TRONState):
        return (state.iteration < max_iter) & (
            state.reason == ConvergenceReason.NOT_CONVERGED
        )

    def body(state: _TRONState):
        gnorm = jnp.linalg.norm(state.g)
        hv = lambda v: hessian_vector_fn(state.w, v)
        step, hit_boundary, _cg_iters = _truncated_cg(
            hv, state.g, state.delta, max_cg_iter, cg_forcing * gnorm,
            host_loop=host_loop,
        )

        gs = jnp.vdot(state.g, step)
        shs = jnp.vdot(step, hv(step))
        prered = -(gs + 0.5 * shs)
        f_new, g_new = value_and_grad_fn(state.w + step)
        actred = state.f - f_new

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

        accept = (actred > ETA0 * prered) & ~(jnp.isnan(f_new) | jnp.isinf(f_new))
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
        # A collapsed trust region means no further progress is possible.
        reason = jnp.where(
            delta < 1e-12,
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
        **no_line_search_counts(max_iter),
    )
