"""The line search of a vmapped L-BFGS pays for no trial that cannot change a
lane's result (PERF.md §6, PR 25): a lane whose solve has stopped leaves the
search loop (exact), and a search ends at the float's floor
(``optim/common.LINE_SEARCH_FLOOR_K``; changes float32 results at rounding
scale, float64 results not at all)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.optim import common, lbfgs
from photon_ml_tpu.optim.common import ConvergenceReason
from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

LANES, ROWS, DIM = 256, 8, 16
#: as the benchmark's random-effect lanes run: ten iterations, live stop 1e-6
SOLVE = dict(max_iter=10, rel_function_tolerance=1e-6)


def logistic_value_and_grad(x, y, l2=1.0):
    def value(w):
        margin = x @ w
        return jnp.sum(jnp.logaddexp(0.0, margin) - y * margin) \
            + 0.5 * l2 * jnp.sum(w * w)

    return jax.value_and_grad(value)


def bucket_data(dtype, lanes=LANES, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    x = (scale * rng.normal(size=(lanes, ROWS, DIM))).astype(dtype)
    x[..., -1] = 1.0  # the intercept
    y = (rng.random((lanes, ROWS)) < 0.5).astype(dtype)
    return jnp.asarray(x), jnp.asarray(y)


def vmapped_solve(**kwargs):
    """A FRESH jit of the vmapped solve: the floor constant and a patched
    search are read while tracing."""
    return jax.jit(jax.vmap(
        lambda x, y, w0: minimize_lbfgs(
            logistic_value_and_grad(x, y), w0, **{**SOLVE, **kwargs})))


def optima(x, y):
    """Every lane's optimum, solved in float64 and cast to the data's type."""
    solved = jax.jit(jax.vmap(lambda x, y, w0: minimize_lbfgs(
        logistic_value_and_grad(x, y), w0, max_iter=200)))(
            x.astype(jnp.float64), y.astype(jnp.float64),
            jnp.zeros((x.shape[0], DIM), jnp.float64))
    return solved.coefficients.astype(x.dtype)


def quarter_warm_starts(x, y):
    """Zero starts, but every fourth lane starts at its own optimum: those
    lanes stop within an iteration or two."""
    warm = np.arange(x.shape[0]) % 4 == 0
    return jnp.where(warm[:, None], optima(x, y), 0.0)


def counting(evaluations):
    """The lane objective, bumping ``evaluations[0]`` once a lane every time
    the device evaluates it (under ``vmap``: lanes x lock-step trips)."""

    def objective(x, y):
        value_and_grad = logistic_value_and_grad(x, y)

        def fn(w):
            jax.debug.callback(lambda _: evaluations.__setitem__(
                0, evaluations[0] + 1), w[0])
            return value_and_grad(w)

        return fn

    return objective


def assert_bitwise_equal(a, b):
    for name in a.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


@pytest.fixture(scope="module")
def bucket():
    x, y = bucket_data(np.float32)
    return x, y, quarter_warm_starts(x, y)


@pytest.fixture
def floor_off(monkeypatch):
    monkeypatch.setattr(common, "LINE_SEARCH_FLOOR_K", 0.0)


def test_the_mask_alone_changes_no_bit(bucket, floor_off, monkeypatch):
    """(a) With the floor constant at 0, handing ``active`` to the search
    gives bitwise what the parent's loop gives (the same search with the
    argument dropped): ``vmap`` already throws a stopped lane's body away."""
    masked = vmapped_solve()(*bucket)
    search = common.wolfe_line_search
    monkeypatch.setattr(
        lbfgs, "wolfe_line_search",
        lambda *args, active=True, **kwargs: search(*args, **kwargs))
    assert_bitwise_equal(masked, vmapped_solve()(*bucket))
    assert np.any(np.asarray(masked.iterations) < 3)  # lanes did stop early
    assert int(jnp.sum(masked.floor_exits)) == 0


def test_lock_step_trials_follow_the_lanes_own_need(bucket):
    """(b) What the device runs for the bucket, iteration by iteration the
    slowest live lane's trials, stays near what a lane needs by itself. The
    parent reads 25 here in most iterations: a lane that has stopped starts
    a search from its converged point again in every later iteration, and a
    live lane at the float32 floor runs one, each to ``max_steps``. The
    history is what really ran: the objective is evaluated once at the start
    and once a lock-step trial, for every lane."""
    x, y, w0 = bucket
    evaluations = [0]
    counted = counting(evaluations)
    result = jax.jit(jax.vmap(lambda x, y, w0: minimize_lbfgs(
        counted(x, y), w0, **SOLVE)))(x, y, w0)
    jax.effects_barrier()
    trials = np.asarray(result.line_search_trials)  # [lanes, max_iter + 1]
    lockstep = trials.max(axis=0)
    assert lockstep.max() <= 8
    assert lockstep.sum() <= 3 * trials.sum(axis=1).max()
    assert evaluations[0] == LANES * (1 + lockstep.sum())
    assert int(jnp.sum(result.floor_exits)) > 0  # the floor did end searches


def test_float64_never_reaches_the_floor(monkeypatch):
    """(c) In float64 the Wolfe test ends every search long before the
    floor: no search ends there, and the result is bitwise the floor-less
    one."""
    x, y = bucket_data(np.float64, lanes=64, seed=3)
    w0 = jnp.zeros((64, DIM), jnp.float64)
    with_floor = vmapped_solve(max_iter=30)(x, y, w0)
    assert int(jnp.sum(with_floor.floor_exits)) == 0
    monkeypatch.setattr(common, "LINE_SEARCH_FLOOR_K", 0.0)
    assert_bitwise_equal(with_floor, vmapped_solve(max_iter=30)(x, y, w0))


PANEL = {  # (feature scale: 8 makes the curvature range wide, warm start)
    "well-cold": (1.0, False), "well-warm": (1.0, True),
    "ill-cold": (8.0, False), "ill-warm": (8.0, True),
}


@pytest.mark.parametrize("kind", sorted(PANEL))
def test_float32_panel_loses_nothing_the_objective_resolves(kind, monkeypatch):
    """(d) 16 un-vmapped float32 problems a kind (64 in all), solved to the
    end: against the floor-less solve the objective at the result (its true
    value: evaluated in float64, since the float32 value the solver reports
    carries tens of eps of its own noise on the ill-conditioned kinds, which
    the floor-less solve goes on chasing) is no worse than by float32
    rounding of the objective, and the coefficients agree to 1e-3 relative."""
    scale, warm = PANEL[kind]
    x, y = bucket_data(np.float32, lanes=16, seed=11, scale=scale)
    w0 = optima(x, y) if warm else jnp.zeros((16, DIM), jnp.float32)

    def solve_each():
        one = jax.jit(lambda x, y, w0: minimize_lbfgs(
            logistic_value_and_grad(x, y), w0, max_iter=100))
        return [one(x[i], y[i], w0[i]) for i in range(16)]

    with_floor = solve_each()
    monkeypatch.setattr(common, "LINE_SEARCH_FLOOR_K", 0.0)
    without = solve_each()
    eps = float(jnp.finfo(jnp.float32).eps)
    for i, (got, ref) in enumerate(zip(with_floor, without)):
        true_value = logistic_value_and_grad(
            x[i].astype(jnp.float64), y[i].astype(jnp.float64))
        f_got, f_ref = (float(true_value(r.coefficients.astype(jnp.float64))[0])
                        for r in (got, ref))
        assert f_got <= f_ref + 4 * eps * abs(f_ref)
        gap = np.linalg.norm(np.asarray(got.coefficients - ref.coefficients))
        assert gap <= 1e-3 * np.linalg.norm(np.asarray(ref.coefficients))
        assert int(got.iterations) <= int(ref.iterations)
    if warm:
        assert sum(int(r.floor_exits) for r in with_floor) > 0


def host_and_compiled(x, y, w0, lane):
    fn = logistic_value_and_grad(x[lane], y[lane])
    return (minimize_lbfgs(fn, w0[lane], max_iter=30, host_loop=True),
            jax.jit(lambda w: minimize_lbfgs(fn, w, max_iter=30))(w0[lane]))


def test_host_loop_runs_the_same_search():
    """(e) A streaming solve drives the same bodies from Python: in float64
    (where op-by-op and fused rounding cannot change a decision) the same
    trials iteration by iteration, the same stop, the same result."""
    x, y = bucket_data(np.float64, lanes=4, seed=5)
    w0 = quarter_warm_starts(x, y)  # lane 0 starts at its optimum
    for lane in range(4):
        hosted, compiled = host_and_compiled(x, y, w0, lane)
        np.testing.assert_array_equal(
            np.asarray(hosted.line_search_trials),
            np.asarray(compiled.line_search_trials))
        assert int(hosted.floor_exits) == int(compiled.floor_exits) == 0
        assert int(hosted.reason) == int(compiled.reason)
        np.testing.assert_allclose(
            np.asarray(hosted.coefficients), np.asarray(compiled.coefficients),
            rtol=1e-9, atol=1e-12)


def test_host_loop_stops_paying_for_searches_at_the_float32_floor():
    """(e) In float32, from its optimum, either driver ends a solve within a
    few trials, some of them at the floor (the parent pays 26 or 27 in one
    such solve of four: every trial of a streaming solve is an epoch)."""
    x, y = bucket_data(np.float32, lanes=4, seed=6)
    pairs = [host_and_compiled(x, y, optima(x, y), lane) for lane in range(4)]
    for results in zip(*pairs):  # the hosted solves, then the compiled ones
        assert max(int(jnp.sum(r.line_search_trials)) for r in results) <= 6
        assert sum(int(r.floor_exits) for r in results) > 0


def test_box_path_finished_lanes_add_no_trials(bucket):
    """(f) The projected-Armijo loop of the box path takes the same mask:
    the objective is evaluated once at the start and once a lock-step trial
    of the LIVE lanes, though every fourth lane stops at once."""
    x, y, w0 = bucket
    x, y, w0 = x[:64], y[:64], jnp.clip(w0[:64], -0.25, 0.25)
    evaluations = [0]
    counted = counting(evaluations)
    bounds = dict(lower_bounds=jnp.full((DIM,), -0.25, jnp.float32),
                  upper_bounds=jnp.full((DIM,), 0.25, jnp.float32))
    result = jax.jit(jax.vmap(lambda x, y, w0: minimize_lbfgs(
        counted(x, y), w0, **SOLVE, **bounds)))(x, y, w0)
    jax.effects_barrier()
    trials = np.asarray(result.line_search_trials)
    stopped_first = np.asarray(result.iterations).min()
    assert stopped_first < np.asarray(result.iterations).max()
    assert evaluations[0] == 64 * (1 + trials.max(axis=0).sum())
    assert int(jnp.sum(result.floor_exits)) == 0  # the box loop has its own test
    assert set(np.asarray(result.reason)) <= {
        int(r) for r in ConvergenceReason if r != ConvergenceReason.NOT_CONVERGED}
