"""Pure-JAX L-BFGS with weak-Wolfe line search and optional box projection.

Reference parity: photon-lib optimization/LBFGS.scala (breeze LBFGS wrapper,
defaults maxIter=100, m=10, tol=1e-7, LBFGS.scala:152-157; box-constraint
projection after each step, LBFGS.scala:70-76).

TPU-native design: the whole solve — two-loop recursion, line search,
convergence tests — is one ``lax.while_loop`` inside one XLA program. State
is a pytree with fixed shapes, so the solver jits once, reuses the compiled
program across coordinate-descent iterations and λ-grid points, and vmaps
over entities for random-effect coordinates (replacing
RandomEffectCoordinate.scala:104-153's per-entity breeze solves).

The pair history (m slots each of ``s_hist`` and ``y_hist``) is kept IN
ORDER, newest pair at slot 0: every slot the two-loop recursion reads or
writes is its loop counter, the same for every lane of a vmapped solve, so a
read is one slice of all the lanes' histories and never a gather with an
index a lane (a circular buffer's newest slot differs lane by lane; PERF.md
§6, PR 28). Keeping a pair costs one dense shift of the history.

A slot is STORED in one of two shapes, by one rule on the static d
(``history_slot_shape``, ``SLAB_MIN_DIM``): below the edge the history is
``[m, d]``, the lanes' and every small solve's program since PR 28; from the
edge on it is ``[m, R, 128]`` with ``R = 8 * ceil(d / 1024)``, each slot
whole (8, 128) tiles, d padded with zeros to them. The TPU tiles an array's
last two dimensions: in ``[m, d]`` the m slots lie along the sublanes, ten
slots are stored as sixteen and reading ONE of them moves the whole tiles it
shares with seven others (2.0 ms a visit at d = 20 million where the slot's
own bytes take 0.3; PERF.md §6, PR 45); in ``[m, R, 128]`` m is an untiled
major dimension and slot k is one contiguous slab, so each of the recursion's
2·m slot visits costs what the slot's bytes cost (4·m·d floats read an
iteration). The shift, 2·m·d floats read and written by the count, is in
both forms one out-of-place pass the compiler feeds from a copy of
``hist[:-1]``: 2.4 times those bytes at d = 20 million, nothing that shows below
the edge (PERF.md §7 row 4 h). The algorithm is one:
``two_loop_direction`` and ``push_pair`` fold the flat vectors they are given
to the slot's shape on the way in and unfold the direction on the way out;
``w``, ``g`` and all the objective and the line search see stay flat ``[d]``.
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
    check_convergence,
    run_while,
    wolfe_line_search,
)
from photon_ml_tpu.telemetry.registry import default_registry

Array = jax.Array

DEFAULT_MAX_ITER = 100
DEFAULT_HISTORY = 10
DEFAULT_TOLERANCE = 1e-7

#: THE rule of the history's stored form: a slot of this many floats or more
#: is whole (8, 128) tiles (``[m, R, 128]``), a shorter one a row of ``[m, d]``.
#: 16 tiles: from here on the pad to whole tiles is under a sixteenth of a
#: slot and falling, and the history stops being small enough for the compiler
#: to keep in fast memory wherever its loop wants it (the lanes' d = 16 and
#: 32, the fixed effects' 256 and 2,000: 0.26 MB at most, their compiled
#: programs untouched) and becomes memory traffic that has to lie contiguous.
SLAB_MIN_DIM = 16 * 1024

_SUBLANES, _LANES = 8, 128


def history_slot_shape(d: int) -> tuple[int, ...]:
    """The shape in which one history slot of a d-vector is stored."""
    if d < SLAB_MIN_DIM:
        return (d,)
    tile = _SUBLANES * _LANES
    return (_SUBLANES * -(-d // tile), _LANES)


def _record_history_form(slot: tuple[int, ...], dtype) -> None:
    """Trace-time gauges of the last L-BFGS / OWL-QN solve traced: the slot's
    stored shape (``slot_rows`` x ``slot_lanes``; one row of d for ``[m, d]``)
    and the bytes of one slot as stored, the slab's pad included."""
    rows, lanes = slot if len(slot) == 2 else (1,) + slot
    reg = default_registry()
    reg.gauge("solver/history/slot_rows").set(rows)
    reg.gauge("solver/history/slot_lanes").set(lanes)
    reg.gauge("solver/history/slot_bytes").set(rows * lanes * jnp.dtype(dtype).itemsize)


def empty_history(m: int, d: int, dtype) -> tuple[Array, Array, Array, Array]:
    """(s_hist, y_hist, rho, count) of a solve over d coefficients that has
    kept no pair yet, in the form the rule gives d."""
    slot = history_slot_shape(d)
    _record_history_form(slot, dtype)
    return (
        jnp.zeros((m,) + slot, dtype),
        jnp.zeros((m,) + slot, dtype),
        jnp.zeros((m,), dtype),
        jnp.int32(0),
    )


def require_history_form(state, m: int, d: int) -> None:
    """Refuse a state to resume from whose history is not in the form the
    rule gives d (a snapshot written under the other form, or another m): it
    is never folded, and never read as if it fitted."""
    want = (m,) + history_slot_shape(d)
    for name in ("s_hist", "y_hist"):
        got = tuple(getattr(state, name).shape)
        if got != want:
            raise ValueError(
                f"resume_state.{name} has shape {got} where a history of "
                f"{m} slots over d = {d} is stored as {want}: it was written "
                "under another form of the L-BFGS history; use a fresh "
                "checkpoint directory"
            )
    _record_history_form(want[1:], state.s_hist.dtype)


def _fold(v: Array, slot: tuple[int, ...]) -> Array:
    """A flat d-vector in a slot's stored shape (zeros in the slab's pad)."""
    if v.shape == slot:
        return v
    rows, lanes = slot
    return jnp.pad(v, (0, rows * lanes - v.shape[0])).reshape(slot)


def _dot(a: Array, b: Array) -> Array:
    """aᵀb. Over a slab one multiply-reduce of the tiles as they lie
    (``vdot`` would flatten both operands first)."""
    return jnp.vdot(a, b) if a.ndim == 1 else jnp.sum(a * b)


def two_loop_direction(
    g: Array, s_hist: Array, y_hist: Array, rho: Array, count: Array
) -> Array:
    """L-BFGS two-loop recursion over an ordered history.

    g: [d]; s_hist/y_hist: [m, d] or [m, R, 128] (``history_slot_shape``);
    rho: [m] (1/sᵀy); slot 0 holds the newest pair, slot k the pair k steps
    back; count: number of valid pairs. Slots from ``count`` on are masked by
    zeroing their alpha/beta contributions, keeping shapes static for jit.
    Every slot index is a loop counter: under ``vmap`` it is the same for all
    lanes, a slice of the lanes' histories. Returns the flat [d] direction.
    """
    with jax.named_scope("lbfgs/direction"):
        m = s_hist.shape[0]
        d = g.shape[0]
        g = _fold(g, s_hist.shape[1:])

        def slot(x, k):
            return lax.dynamic_index_in_dim(x, k, keepdims=False)

        def backward(k, carry):
            # newest to oldest
            q, alphas = carry
            alpha = jnp.where(k < count, slot(rho, k) * _dot(slot(s_hist, k), q), 0.0)
            q = q - alpha * slot(y_hist, k)
            return q, lax.dynamic_update_index_in_dim(alphas, alpha, k, 0)

        q, alphas = lax.fori_loop(0, m, backward, (g, jnp.zeros((m,), dtype=g.dtype)))

        gamma = jnp.where(
            count > 0,
            _dot(s_hist[0], y_hist[0])
            / jnp.maximum(_dot(y_hist[0], y_hist[0]), 1e-30),
            1.0,
        )
        r = gamma * q

        def forward(i, r):
            # oldest to newest: the empty slots come first and add nothing
            k = m - 1 - i
            beta = slot(rho, k) * _dot(slot(y_hist, k), r)
            return r + jnp.where(k < count, slot(alphas, k) - beta, 0.0) * slot(s_hist, k)

        r = lax.fori_loop(0, m, forward, r)
        # the slab's pad stayed zero throughout and is cut off here
        return -r if r.ndim == 1 else (-r).reshape(-1)[:d]


def push_pair(
    s_hist: Array,
    y_hist: Array,
    rho: Array,
    count: Array,
    s: Array,
    y: Array,
    accepted: Array,
) -> tuple[Array, Array, Array, Array]:
    """The history after a step (s, y), both flat [d]: where the step was
    accepted and its curvature sᵀy is positive, every pair moves one slot back
    (the oldest falls off) and the new one takes slot 0; else the history
    stays."""
    with jax.named_scope("lbfgs/history"):
        m = s_hist.shape[0]
        sy = jnp.vdot(s, y)
        keep_pair = accepted & (sy > 1e-10)

        def pushed(hist, new):
            return jnp.where(keep_pair, jnp.concatenate([new[None], hist[:-1]]), hist)

        return (
            pushed(s_hist, _fold(s, s_hist.shape[1:])),
            pushed(y_hist, _fold(y, y_hist.shape[1:])),
            pushed(rho, 1.0 / jnp.maximum(sy, 1e-30)),
            jnp.where(keep_pair, jnp.minimum(count + 1, m), count),
        )


@flax.struct.dataclass
class _LBFGSState:
    w: Array
    f: Array
    g: Array
    s_hist: Array
    y_hist: Array
    rho: Array
    count: Array
    iteration: Array
    reason: Array
    prev_f: Array
    g0_norm: Array
    value_history: Array
    grad_norm_history: Array
    line_search_trials: Array  # int32 [max_iter + 1]: trials of iteration i's search
    floor_exits: Array  # int32: searches the float's floor ended


def minimize_lbfgs(
    value_and_grad_fn: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    *,
    max_iter: int = DEFAULT_MAX_ITER,
    history: int = DEFAULT_HISTORY,
    tolerance: float = DEFAULT_TOLERANCE,
    rel_function_tolerance: float | None = None,
    lower_bounds: Array | None = None,
    upper_bounds: Array | None = None,
    max_line_search_steps: int = 25,
    host_loop: bool = False,
    state_observer=None,
    resume_state: "_LBFGSState | None" = None,
) -> SolverResult:
    """Minimize a smooth function with L-BFGS. Jit- and vmap-safe.

    ``host_loop=True`` runs the identical per-iteration body from a Python
    loop (optim/common.run_while) so ``value_and_grad_fn`` may be a HOST
    function — the out-of-core streaming epoch accumulator
    (algorithm/streaming.py). The default compiles exactly as before.

    ``state_observer`` / ``resume_state`` (host_loop only — crash-safe
    streaming solves, io/checkpoint.SolverCheckpointer): the observer sees
    the full ``_LBFGSState`` after every outer iteration (an epoch
    boundary — each iteration is an integral number of chunked epochs);
    ``resume_state`` re-enters the loop from a checkpointed state WITHOUT
    re-evaluating the initial point (the whole saving — the skipped
    iterations each cost epochs). Both default to None, which is bitwise
    the pre-existing solve.

    With ``lower_bounds``/``upper_bounds`` set, iterates are projected onto
    the box after every accepted step and convergence is tested on the
    projected gradient — the gradient-projection scheme the reference applies
    (LBFGS.scala:70-76); the dedicated LBFGSB entry point builds on this.

    ``rel_function_tolerance`` (None = reference behavior, use
    ``tolerance``): a separate live function-decrease stop inside the
    while_loop condition, so warm-started vmapped lanes can actually exit
    instead of paying max_iter (optim/common.check_convergence).
    """
    if (state_observer is not None or resume_state is not None) and not host_loop:
        raise ValueError(
            "state_observer/resume_state require host_loop=True (solver-"
            "state checkpointing exists for host-driven streaming solves)"
        )
    dtype = w0.dtype
    d = w0.shape[0]
    m = history

    has_box = lower_bounds is not None or upper_bounds is not None
    lo = jnp.full((d,), -jnp.inf, dtype) if lower_bounds is None else jnp.asarray(lower_bounds, dtype)
    hi = jnp.full((d,), jnp.inf, dtype) if upper_bounds is None else jnp.asarray(upper_bounds, dtype)

    def project(w):
        return jnp.clip(w, lo, hi) if has_box else w

    def projected_grad_norm(w, g):
        if not has_box:
            return jnp.linalg.norm(g)
        # norm of P(w - g) - w: zero iff w is box-stationary
        return jnp.linalg.norm(project(w - g) - w)

    if resume_state is not None:
        # checkpointed re-entry: the saved state already holds f/g/history
        # for its iterate — re-evaluating w0 would cost an epoch for
        # numbers the checkpoint carries
        require_history_form(resume_state, m, d)
        init = resume_state
    else:
        w0 = project(jnp.asarray(w0, dtype))
        f0, g0 = value_and_grad_fn(w0)
        g0_norm = projected_grad_norm(w0, g0)

        nan_hist = jnp.full((max_iter + 1,), jnp.nan, dtype)
        s_hist, y_hist, rho, count = empty_history(m, d, dtype)
        init = _LBFGSState(
            w=w0,
            f=f0,
            g=g0,
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            iteration=jnp.int32(0),
            reason=jnp.int32(ConvergenceReason.NOT_CONVERGED),
            prev_f=jnp.asarray(jnp.inf, dtype),
            g0_norm=g0_norm,
            value_history=nan_hist.at[0].set(f0),
            grad_norm_history=nan_hist.at[0].set(g0_norm),
            line_search_trials=jnp.zeros((max_iter + 1,), jnp.int32),
            floor_exits=jnp.int32(0),
        )

        # Already stationary at the initial point?
        init = init.replace(
            reason=jnp.where(
                g0_norm <= tolerance,
                jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
                init.reason,
            )
        )

    def cond(state: _LBFGSState):
        return (state.iteration < max_iter) & (
            state.reason == ConvergenceReason.NOT_CONVERGED
        )

    def body(state: _LBFGSState):
        # False only under vmap, where a stopped lane's body still runs (and
        # is thrown away): its line search must not hold the bucket's
        # lock-step loop open. Un-vmapped, ``cond`` guarantees it.
        live = state.reason == ConvergenceReason.NOT_CONVERGED
        direction = two_loop_direction(
            state.g, state.s_hist, state.y_hist, state.rho, state.count
        )
        if has_box:
            # Active-set masking: don't push into an active bound
            # (projected L-BFGS; reference projects per step, LBFGS.scala:70-76).
            eps_b = 1e-10
            active = ((state.w <= lo + eps_b) & (direction < 0.0)) | (
                (state.w >= hi - eps_b) & (direction > 0.0)
            )
            direction = jnp.where(active, 0.0, direction)
            sd = -state.g
            sd = jnp.where(
                ((state.w <= lo + eps_b) & (sd < 0.0))
                | ((state.w >= hi - eps_b) & (sd > 0.0)),
                0.0,
                sd,
            )
            direction = jnp.where(jnp.vdot(state.g, direction) >= 0.0, sd, direction)
        else:
            # Guard: fall back to steepest descent if not a descent direction.
            direction = jnp.where(jnp.vdot(state.g, direction) >= 0.0, -state.g, direction)

        t_init = jnp.where(
            state.count == 0,
            1.0 / jnp.maximum(jnp.linalg.norm(state.g), 1.0),
            jnp.ones((), dtype),
        )

        if has_box:
            # Projected Armijo backtracking: trial points stay feasible, the
            # sufficient-decrease test uses the actual displacement.
            c1 = 1e-4

            def ls_body(s):
                i, t, _w, _f, _g, _ok = s
                cand = project(state.w + t * direction)
                f_t, g_t = value_and_grad_fn(cand)
                decrease = jnp.vdot(state.g, cand - state.w)
                ok = (
                    (f_t <= state.f + c1 * decrease)
                    & ~(jnp.isnan(f_t) | jnp.isinf(f_t))
                    & (f_t < state.f)
                )
                return (i + 1, t * 0.5, cand, f_t, g_t, ok)

            def ls_cond(s):
                i, _t, _w, _f, _g, ok = s
                return (i < max_line_search_steps) & ~ok & live

            with jax.named_scope("lbfgs/line_search"):
                ls_trials, _, w_new, f_new, g_new, ls_ok = run_while(
                    ls_cond,
                    ls_body,
                    (jnp.int32(0), t_init, state.w, state.f, state.g, jnp.asarray(False)),
                    host=host_loop,
                )
            ls_success = ls_ok
            ls_floor_exit = jnp.asarray(False)
        else:
            with jax.named_scope("lbfgs/line_search"):
                ls = wolfe_line_search(
                    value_and_grad_fn,
                    state.w,
                    state.f,
                    state.g,
                    direction,
                    t_init,
                    max_steps=max_line_search_steps,
                    host_loop=host_loop,
                    active=live,
                )
            w_new = state.w + ls.step * direction
            f_new, g_new = ls.value, ls.gradient
            ls_success = ls.success
            ls_trials, ls_floor_exit = ls.trials, ls.floor_exit

        s_hist, y_hist, rho, count = push_pair(
            state.s_hist,
            state.y_hist,
            state.rho,
            state.count,
            w_new - state.w,
            g_new - state.g,
            ls_success,
        )

        gnorm = projected_grad_norm(w_new, g_new)
        reason = jnp.where(
            ls_success,
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
        return _LBFGSState(
            w=jnp.where(ls_success, w_new, state.w),
            f=jnp.where(ls_success, f_new, state.f),
            g=jnp.where(ls_success, g_new, state.g),
            s_hist=s_hist,
            y_hist=y_hist,
            rho=rho,
            count=count,
            iteration=it,
            reason=reason,
            prev_f=state.f,
            g0_norm=state.g0_norm,
            value_history=state.value_history.at[it].set(jnp.where(ls_success, f_new, state.f)),
            grad_norm_history=state.grad_norm_history.at[it].set(gnorm),
            line_search_trials=state.line_search_trials.at[it].set(ls_trials),
            floor_exits=state.floor_exits + ls_floor_exit.astype(jnp.int32),
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
        gradient_norm=projected_grad_norm(final.w, final.g),
        iterations=final.iteration,
        reason=reason,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        line_search_trials=final.line_search_trials,
        floor_exits=final.floor_exits,
    )
