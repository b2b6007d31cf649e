"""OWL-QN as lock-step lanes (PR 47): ``minimize_lbfgs``'s two rules for a
vmapped solve, and the counts they are read by.

Held here: under ``vmap`` a lane whose solve has stopped adds no trial to the
block's search loop (the objective is evaluated, trip by trip, as often as the
slowest LIVE lane asks, counted by a callback that fires once a lock-step
evaluation); a search ends at the float's floor and returns what running out
of steps returns; wherever no search reached the floor an un-vmapped solve is
the parent's bit for bit (the parent's loop is kept in this file); and
``line_search_trials`` / ``floor_exits`` add up to the evaluations made.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import common, owlqn
from photon_ml_tpu.optim.common import ConvergenceReason, check_convergence
from photon_ml_tpu.optim.lbfgs import empty_history, push_pair, two_loop_direction
from photon_ml_tpu.optim.owlqn import minimize_owlqn, pseudo_gradient

from tests.conftest import make_classification


def _parent_minimize_owlqn(value_and_grad_fn, w0, *, l1_weight, max_iter=100,
                           history=10, tolerance=1e-7, rel_function_tolerance=None,
                           max_line_search_steps=30):
    """PR 46's ``minimize_owlqn`` (its ``lax.while_loop`` form): no ``live``
    rule, no floor, no counts. Returns (w, f, gradient norm, iterations,
    reason, value_history, grad_norm_history)."""
    dtype = w0.dtype
    d = w0.shape[0]
    l1 = jnp.asarray(l1_weight, dtype)

    def full_value(w, smooth_f):
        return smooth_f + l1 * jnp.sum(jnp.abs(w))

    sf0, g0 = value_and_grad_fn(w0)
    f0 = full_value(w0, sf0)
    g0_norm = jnp.linalg.norm(pseudo_gradient(w0, g0, l1))
    nan_hist = jnp.full((max_iter + 1,), jnp.nan, dtype)
    s_hist, y_hist, rho, count = empty_history(history, d, dtype)
    init = dict(
        w=w0, f=f0, g=g0, s_hist=s_hist, y_hist=y_hist, rho=rho, count=count,
        iteration=jnp.int32(0),
        reason=jnp.where(g0_norm <= tolerance,
                         jnp.int32(ConvergenceReason.GRADIENT_WITHIN_TOLERANCE),
                         jnp.int32(ConvergenceReason.NOT_CONVERGED)),
        value_history=nan_hist.at[0].set(f0),
        grad_norm_history=nan_hist.at[0].set(g0_norm))

    def cond(s):
        return (s["iteration"] < max_iter) & (
            s["reason"] == ConvergenceReason.NOT_CONVERGED)

    def body(s):
        pg = pseudo_gradient(s["w"], s["g"], l1)
        direction = two_loop_direction(pg, s["s_hist"], s["y_hist"], s["rho"], s["count"])
        direction = jnp.where(direction * (-pg) > 0.0, direction, 0.0)
        direction = jnp.where(jnp.vdot(direction, pg) >= 0.0, -pg, direction)
        xi = jnp.where(s["w"] != 0.0, jnp.sign(s["w"]), jnp.sign(-pg))
        t_init = jnp.where(s["count"] == 0,
                           1.0 / jnp.maximum(jnp.linalg.norm(pg), 1.0),
                           jnp.ones((), dtype))

        def ls_body(ls):
            i, t, _w, _f, _g, _done = ls
            cand = s["w"] + t * direction
            cand = jnp.where(cand * xi > 0.0, cand, 0.0)
            sf, sg = value_and_grad_fn(cand)
            f_t = full_value(cand, sf)
            decrease = jnp.vdot(pg, cand - s["w"])
            ok = ((f_t <= s["f"] + 1e-4 * decrease)
                  & ~(jnp.isnan(f_t) | jnp.isinf(f_t)) & (f_t < s["f"]))
            return (i + 1, t * 0.5, cand, f_t, sg, ok)

        _, _, w_new, f_new, g_new, ls_ok = lax.while_loop(
            lambda ls: (ls[0] < max_line_search_steps) & ~ls[5], ls_body,
            (jnp.int32(0), t_init, s["w"], s["f"], s["g"], jnp.asarray(False)))
        s_hist, y_hist, rho, count = push_pair(
            s["s_hist"], s["y_hist"], s["rho"], s["count"],
            w_new - s["w"], g_new - s["g"], ls_ok)
        gnorm = jnp.linalg.norm(pseudo_gradient(w_new, g_new, l1))
        reason = jnp.where(
            ls_ok,
            check_convergence(value=f_new, prev_value=s["f"], grad_norm=gnorm,
                              initial_grad_norm=g0_norm, tolerance=tolerance,
                              rel_function_tolerance=rel_function_tolerance),
            jnp.int32(ConvergenceReason.LINE_SEARCH_FAILED))
        it = s["iteration"] + 1
        return dict(
            w=jnp.where(ls_ok, w_new, s["w"]), f=jnp.where(ls_ok, f_new, s["f"]),
            g=jnp.where(ls_ok, g_new, s["g"]), s_hist=s_hist, y_hist=y_hist,
            rho=rho, count=count, iteration=it, reason=reason,
            value_history=s["value_history"].at[it].set(
                jnp.where(ls_ok, f_new, s["f"])),
            grad_norm_history=s["grad_norm_history"].at[it].set(gnorm))

    final = lax.while_loop(cond, body, init)
    reason = jnp.where(final["reason"] == ConvergenceReason.NOT_CONVERGED,
                       jnp.int32(ConvergenceReason.MAX_ITERATIONS), final["reason"])
    return (final["w"], final["f"],
            jnp.linalg.norm(pseudo_gradient(final["w"], final["g"], l1)),
            final["iteration"], reason, final["value_history"],
            final["grad_norm_history"])


def _logistic(rng, n, d, dtype=np.float64):
    x, y, _ = make_classification(rng, n=n, d=d)
    batch = LabeledPointBatch.create(x.astype(dtype), y.astype(dtype), dtype=dtype)
    return GLMObjective(LogisticLoss(), use_pallas=False).bind(batch), x, y


# -- (1) a stopped lane adds no trial -------------------------------------------


class _LockstepCounter:
    """An objective whose every LOCK-STEP evaluation (one call of the vmapped
    ``value_and_grad_fn``, whatever the number of lanes) is counted on the
    host: the callback takes the whole block at once."""

    def __init__(self, x, y):
        self.x, self.y, self.calls = np.asarray(x), np.asarray(y), 0

    def _host(self, w):  # w: [lanes, d]; [d] at the lanes' shared start
        self.calls += 1
        m = w @ self.x.T
        value = np.sum(np.logaddexp(0.0, m) - self.y * m, axis=-1)
        grad = (1.0 / (1.0 + np.exp(-m)) - self.y) @ self.x
        return value.astype(w.dtype), grad.astype(w.dtype)

    def value_and_grad(self, w):
        return jax.pure_callback(
            self._host, (jax.ShapeDtypeStruct((), w.dtype),
                         jax.ShapeDtypeStruct(w.shape, w.dtype)),
            w, vmap_method="broadcast_all")


def _lane_l1s(x, y, lanes):
    """L1 weights from past the largest gradient at zero (a lane that stops
    before its first iteration) down three decades."""
    at_zero = np.abs(x.T @ (0.5 - y)).max()
    return np.concatenate([[1.5 * at_zero], at_zero * np.logspace(-0.2, -3, lanes - 1)])


def test_under_vmap_a_stopped_lane_adds_no_trial(rng):
    _, x, y = _logistic(rng, 200, 8)
    counter = _LockstepCounter(x, y)
    l1s = _lane_l1s(x, y, 6)
    result = jax.vmap(lambda l1: minimize_owlqn(
        counter.value_and_grad, jnp.zeros(8), l1_weight=l1, max_iter=40,
        rel_function_tolerance=1e-6))(jnp.asarray(l1s))
    trials = np.asarray(result.line_search_trials)  # [lanes, max_iter + 1]
    iterations = np.asarray(result.iterations)
    assert iterations[0] == 0 and not np.asarray(result.coefficients)[0].any()
    assert iterations.max() > iterations[1:].min() > 0  # the lanes stop apart
    # trip by trip the block's loop runs as long as its slowest LIVE lane asks:
    # a lane past its last iteration holds zeros, so the maximum over lanes is
    # the maximum over the live ones; one more evaluation at the start
    lockstep = 1 + int(np.sum(np.max(trials, axis=0)))
    assert counter.calls == lockstep
    for lane, n in enumerate(iterations):
        assert not trials[lane, n + 1:].any() and (trials[lane, 1:n + 1] >= 1).all()
    # without the rule the first lane alone (direction zero, candidate w, no
    # decrease) would have held every trip's search open to its last step
    assert lockstep < 30 * iterations.max()


def test_without_the_live_rule_the_block_pays_thirty_trials_a_trip(rng):
    """The control of the test above: the same block through the parent's
    loop evaluates the objective ``max_line_search_steps`` times in every
    outer trip."""
    _, x, y = _logistic(rng, 200, 8)
    counter = _LockstepCounter(x, y)
    l1s = _lane_l1s(x, y, 6)
    out = jax.vmap(lambda l1: _parent_minimize_owlqn(
        counter.value_and_grad, jnp.zeros(8), l1_weight=l1, max_iter=40,
        rel_function_tolerance=1e-6))(jnp.asarray(l1s))
    trips = int(np.max(np.asarray(out[3])))
    assert counter.calls == 1 + 30 * trips


def test_every_lane_of_a_block_is_the_solve_it_would_be_alone(rng):
    bound, x, y = _logistic(rng, 150, 6)
    l1s = _lane_l1s(x, y, 5)
    solve = lambda l1: minimize_owlqn(  # noqa: E731
        bound.value_and_grad, jnp.zeros(6), l1_weight=l1, max_iter=40,
        rel_function_tolerance=1e-6)
    block = jax.vmap(solve)(jnp.asarray(l1s))
    for lane, l1 in enumerate(l1s):
        alone = jax.jit(solve)(jnp.asarray(l1))
        assert int(alone.iterations) == int(block.iterations[lane])
        assert int(alone.reason) == int(block.reason[lane])
        np.testing.assert_array_equal(np.asarray(alone.line_search_trials),
                                      np.asarray(block.line_search_trials[lane]))
        np.testing.assert_allclose(np.asarray(alone.coefficients),
                                   np.asarray(block.coefficients[lane]),
                                   rtol=1e-9, atol=1e-12)


# -- (2) a search ends at the float's floor -------------------------------------


def _offset_quadratic(offset):
    """A float32 bowl whose value is dominated by a constant: ``eps |f|`` is
    some 0.06, so near the minimizer no decrease is resolved."""
    scales = jnp.asarray([1.0, 0.5, 0.25, 2.0], jnp.float32)
    target = jnp.asarray([1.0, -2.0, 0.0, 0.5], jnp.float32)

    def value_and_grad(w):
        r = w - target
        return jnp.float32(offset) + 0.5 * jnp.sum(scales * r * r), scales * r

    return value_and_grad


def test_a_search_ends_at_the_floor_and_returns_failure():
    vg = _offset_quadratic(5e5)
    w0 = jnp.zeros(4, jnp.float32)
    floored = jax.jit(lambda w: minimize_owlqn(
        vg, w, l1_weight=0.1, max_iter=30, tolerance=1e-12))(w0)
    n = int(floored.iterations)
    assert int(floored.floor_exits) == 1
    assert int(floored.reason) == ConvergenceReason.LINE_SEARCH_FAILED
    assert 1 <= int(floored.line_search_trials[n]) < 10
    # what running out of steps returns: the parent's loop halves thirty times
    # in its last iteration and ends in the same state
    w, f, gnorm, iterations, reason, values, gnorms = jax.jit(
        lambda w: _parent_minimize_owlqn(
            vg, w, l1_weight=0.1, max_iter=30, tolerance=1e-12))(w0)
    assert int(iterations) == n and int(reason) == ConvergenceReason.LINE_SEARCH_FAILED
    np.testing.assert_array_equal(np.asarray(floored.coefficients), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(floored.value), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(floored.value_history), np.asarray(values))
    np.testing.assert_array_equal(np.asarray(floored.grad_norm_history)[:n],
                                  np.asarray(gnorms)[:n])


def test_the_floor_is_wolfe_line_searchs_own():
    """One constant and one test, in optim/common.py: OWL-QN imports both."""
    assert owlqn.line_search_floor is common.line_search_floor
    assert owlqn.at_line_search_floor is common.at_line_search_floor
    f0 = jnp.float32(2e5)
    floor = float(common.line_search_floor(f0))
    assert floor == pytest.approx(
        common.LINE_SEARCH_FLOOR_K * float(np.finfo(np.float32).eps) * 2e5)
    assert bool(common.at_line_search_floor(True, jnp.float32(-0.5 * floor), floor))
    assert not bool(common.at_line_search_floor(True, jnp.float32(-2 * floor), floor))
    assert not bool(common.at_line_search_floor(False, jnp.float32(0.0), floor))


# -- (3) un-vmapped results are the parent's bit for bit -------------------------

#: the existing OWL-QN cases (tests/test_optimizers.py, the elastic-net path
#: of tests/test_train_glm_path.py) and the benchmark cell's stop
_CASES = [
    pytest.param(dict(n=150, d=10, l1=20.0), {}, id="strong-l1"),
    pytest.param(dict(n=150, d=10, l1=0.01), {}, id="weak-l1"),
    pytest.param(dict(n=120, d=6, l1=3.0), dict(tolerance=1e-10), id="tight-stop"),
    pytest.param(dict(n=300, d=12, l1=1.5),
                 dict(max_iter=50, rel_function_tolerance=1e-6), id="the-cells-stop"),
    pytest.param(dict(n=300, d=12, l1=1e4), dict(max_iter=50), id="stopped-at-zero"),
]


@pytest.mark.parametrize("dtype", [np.float64, np.float32], ids=["f64", "f32"])
@pytest.mark.parametrize("case,options", _CASES)
def test_an_unvmapped_solve_is_the_parents_bit_for_bit(rng, case, options, dtype):
    bound, _, _ = _logistic(rng, case["n"], case["d"], dtype)
    w0 = jnp.zeros(case["d"], dtype)
    new = jax.jit(lambda w: minimize_owlqn(
        bound.value_and_grad, w, l1_weight=case["l1"], **options))(w0)
    old = jax.jit(lambda w: _parent_minimize_owlqn(
        bound.value_and_grad, w, l1_weight=case["l1"], **options))(w0)
    if int(new.floor_exits):
        # float32 at a stop below its rounding: the solve's LAST search ended at
        # the floor (the parent halves on, and may find a decrease that the
        # value's last bit carries); every iteration before it is the parent's
        n = int(new.iterations)
        assert dtype == np.float32 and int(new.floor_exits) == 1 and n > 1
        assert int(new.reason) == ConvergenceReason.LINE_SEARCH_FAILED
        assert int(old[3]) >= n
        np.testing.assert_array_equal(np.asarray(new.value_history)[:n],
                                      np.asarray(old[5])[:n])
        np.testing.assert_array_equal(np.asarray(new.grad_norm_history)[:n],
                                      np.asarray(old[6])[:n])
        return
    got = (new.coefficients, new.value, new.gradient_norm, new.iterations, new.reason,
           new.value_history, new.grad_norm_history)
    for a, b in zip(got, old):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (4) the counts add up -------------------------------------------------------


def test_the_counts_add_up(rng):
    bound, _, _ = _logistic(rng, 200, 8)
    evaluations = []

    def counted(w):
        jax.debug.callback(lambda: evaluations.append(1))
        return bound.value_and_grad(w)

    result = jax.jit(lambda w: minimize_owlqn(
        counted, w, l1_weight=2.0, max_iter=25, rel_function_tolerance=1e-6))(
            jnp.zeros(8))
    jax.effects_barrier()
    trials = np.asarray(result.line_search_trials)
    n = int(result.iterations)
    assert trials.shape == (26,) and trials.dtype == np.int32
    assert trials[0] == 0 and not trials[n + 1:].any() and (trials[1:n + 1] >= 1).all()
    assert len(evaluations) == 1 + int(trials.sum())
    assert 0 <= int(result.floor_exits) <= n
    # a lane trace sums them as it does L-BFGS's
    trace = common.lane_trace_of(jax.tree.map(lambda a: a[None], result))
    assert int(trace.line_search_trials[0]) == int(trials.sum())
    assert int(trace.lockstep_trials) == int(trials.sum())
