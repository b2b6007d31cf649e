"""The L-BFGS pair history is kept in order, newest pair first (PR 28).

The circular layout that ``optim/lbfgs.py`` and ``optim/owlqn.py`` had until
then lives on HERE, copied as it stood, as the oracle: the ordered layout must
give the same direction BIT FOR BIT from the same stream of pairs, in one
process, un-vmapped and under ``vmap`` (where the circular layout's slots
differed lane by lane and every read was a gather). Whole solves are held to
what the parent commit returned, recorded below. A structural guard keeps
gathers and scatters with an index a lane out of the vmapped recursion, and
every gather and scatter out of the vmapped write.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from photon_ml_tpu.optim.lbfgs import minimize_lbfgs, push_pair, two_loop_direction
from photon_ml_tpu.optim.owlqn import minimize_owlqn

M = 10
D = 16


# -- the oracle: the circular layout, as optim/lbfgs.py had it ---------------


def _circular_two_loop_direction(g, s_hist, y_hist, rho, count, head):
    m = s_hist.shape[0]

    def backward(i, carry):
        q, alphas = carry
        idx = (head - i) % m
        valid = i < count
        alpha = jnp.where(valid, rho[idx] * jnp.vdot(s_hist[idx], q), 0.0)
        q = q - alpha * y_hist[idx]
        return q, alphas.at[idx].set(alpha)

    q, alphas = lax.fori_loop(0, m, backward, (g, jnp.zeros((m,), dtype=g.dtype)))

    gamma = jnp.where(
        count > 0,
        jnp.vdot(s_hist[head], y_hist[head])
        / jnp.maximum(jnp.vdot(y_hist[head], y_hist[head]), 1e-30),
        1.0,
    )
    r = gamma * q

    def forward(i, r):
        # oldest-to-newest among valid entries
        idx = (head - (count - 1 - i)) % m
        valid = i < count
        beta = rho[idx] * jnp.vdot(y_hist[idx], r)
        return r + jnp.where(valid, (alphas[idx] - beta), 0.0) * s_hist[idx]

    r = lax.fori_loop(0, m, forward, r)
    return -r


def _circular_write(s_hist, y_hist, rho, count, head, s, y, ls_success):
    m = s_hist.shape[0]
    sy = jnp.vdot(s, y)
    keep_pair = ls_success & (sy > 1e-10)

    new_head = jnp.where(keep_pair, (head + 1) % m, head)
    # count==0 means head slot 0 is where the first pair goes
    write_head = jnp.where(count == 0, jnp.int32(0), new_head)
    new_head = jnp.where(count == 0, jnp.int32(0), new_head)
    s_hist = jnp.where(keep_pair, s_hist.at[write_head].set(s), s_hist)
    y_hist = jnp.where(keep_pair, y_hist.at[write_head].set(y), y_hist)
    rho = jnp.where(
        keep_pair,
        rho.at[write_head].set(1.0 / jnp.maximum(sy, 1e-30)),
        rho,
    )
    count = jnp.where(keep_pair, jnp.minimum(count + 1, m), count)
    return s_hist, y_hist, rho, count, new_head


# -- one stream of pairs through both layouts --------------------------------


def _empty():
    return (
        jnp.zeros((M, D), jnp.float32),
        jnp.zeros((M, D), jnp.float32),
        jnp.zeros((M,), jnp.float32),
        jnp.int32(0),
    )


def _stream(seed, n_pairs):
    """n_pairs steps (s, y) of positive curvature and a gradient to turn after
    each: float32, nothing exactly zero."""
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(n_pairs, D)).astype(np.float32)
    scale = rng.uniform(0.5, 2.0, size=(n_pairs, 1)).astype(np.float32)
    y = scale * s + 0.1 * rng.normal(size=(n_pairs, D)).astype(np.float32)
    g = rng.normal(size=(n_pairs + 1, D)).astype(np.float32)
    return jnp.asarray(s), jnp.asarray(y), jnp.asarray(g)


def _run_both(s, y, g, accepted):
    """Feed the stream to both layouts; the direction after every step (and
    before the first) from each, and the final counts."""

    def step(carry, xs):
        ordered, circular = carry
        s_t, y_t, g_t, ok = xs
        ordered = push_pair(*ordered, s_t, y_t, ok)
        circular = _circular_write(*circular, s_t, y_t, ok)
        return (ordered, circular), (
            two_loop_direction(g_t, *ordered),
            _circular_two_loop_direction(g_t, *circular),
        )

    init = (_empty(), _empty() + (jnp.int32(0),))
    first = (
        two_loop_direction(g[0], *init[0]),
        _circular_two_loop_direction(g[0], *init[1]),
    )
    (ordered, circular), rest = lax.scan(step, init, (s, y, g[1:], accepted))
    return first, rest, ordered[3], circular[3]


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _assert_same_bits(ordered, circular):
    assert np.asarray(ordered).dtype == np.float32
    assert np.isfinite(np.asarray(ordered)).all()
    assert np.array_equal(_bits(ordered), _bits(circular))


# count 0 (no pair at all), part full, full and wrapped, pairs skipped
CASES = {
    "count0": (0, ()),
    "count1": (1, ()),
    "count3": (3, ()),
    "count9": (9, ()),
    "wrapped15": (15, ()),
    "wrapped23": (23, ()),
    "skipped_part_full": (8, (2, 3, 6)),
    "skipped_wrapped": (19, (0, 7, 8, 12, 18)),
}


@pytest.mark.parametrize("name", CASES)
def test_direction_is_bitwise_the_circular_layouts(name):
    n_pairs, skipped = CASES[name]
    s, y, g = _stream(seed=28 + n_pairs, n_pairs=n_pairs)
    accepted = jnp.ones((n_pairs,), bool).at[jnp.asarray(skipped, jnp.int32)].set(False)
    first, rest, count, count_circular = jax.jit(_run_both)(s, y, g, accepted)
    _assert_same_bits(*first)
    # no history yet: the direction is the negated gradient itself
    assert np.array_equal(_bits(first[0]), _bits(-g[0]))
    if n_pairs:
        _assert_same_bits(*rest)
    assert int(count) == int(count_circular) == min(n_pairs - len(skipped), M)


def test_zero_curvature_pair_is_dropped_in_both_layouts():
    s, y, g = _stream(seed=5, n_pairs=6)
    y = y.at[2].set(-y[2]).at[4].set(0.0)  # sᵀy < 0, sᵀy = 0: neither is kept
    first, rest, count, count_circular = jax.jit(_run_both)(
        s, y, g, jnp.ones((6,), bool)
    )
    _assert_same_bits(*rest)
    assert int(count) == int(count_circular) == 4


LANES = 7


def test_direction_is_bitwise_under_vmap_over_lanes_that_differ():
    """Seven lanes, each at another count and with other steps refused: the
    circular layout's newest slot differs lane by lane, the ordered one's
    never does."""
    n_pairs = 14
    streams = [_stream(seed=100 + lane, n_pairs=n_pairs) for lane in range(LANES)]
    s, y, g = (jnp.stack(x) for x in zip(*streams))
    rng = np.random.default_rng(7)
    # lane 0 refuses every step, lane 6 none
    accepted = rng.uniform(size=(LANES, n_pairs)) < np.linspace(0.0, 1.0, LANES)[:, None]
    first, rest, count, count_circular = jax.jit(jax.vmap(_run_both))(
        s, y, g, jnp.asarray(accepted)
    )
    _assert_same_bits(*first)
    _assert_same_bits(*rest)
    assert np.array_equal(np.asarray(count), np.asarray(count_circular))
    assert np.array_equal(np.asarray(count), np.minimum(accepted.sum(axis=1), M))
    assert len(set(np.asarray(count).tolist())) >= 5  # the lanes really differ


# -- no gather, no scatter with an index a lane -------------------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _indexed(jaxpr):
    """(primitive, shape of its indices) of every gather and scatter."""
    return [
        (eqn.primitive.name, eqn.invars[1].aval.shape)
        for eqn in _eqns(jaxpr)
        if "gather" in eqn.primitive.name or "scatter" in eqn.primitive.name
    ]


def _lane_indexed(jaxpr):
    """The gathers and scatters whose INDICES have a lane axis. ``vmap`` writes
    a ``dynamic_slice`` at the loop counter as a ``gather`` too, with the one
    index all lanes share (shape [1]; XLA makes it a dynamic-slice again): that
    is a slice. An index a lane (shape [LANES, 1]) is what the TPU runs lane by
    lane."""
    return [name for name, shape in _indexed(jaxpr) if LANES in shape]


def _lane_history():
    s_hist, y_hist, rho, _ = _empty()
    tile = lambda x: jnp.broadcast_to(x, (LANES,) + x.shape)
    return tile(s_hist), tile(y_hist), tile(rho), jnp.arange(LANES, dtype=jnp.int32)


def test_vmapped_recursion_indexes_nothing_by_lane():
    assert LANES not in (M, D, 1)
    g = jnp.ones((LANES, D), jnp.float32)
    ordered = jax.make_jaxpr(jax.vmap(two_loop_direction))(g, *_lane_history())
    assert _lane_indexed(ordered.jaxpr) == []
    # the guard sees what it guards against: the circular layout under vmap
    circular = jax.make_jaxpr(jax.vmap(_circular_two_loop_direction))(
        g, *_lane_history(), jnp.arange(LANES, dtype=jnp.int32)
    )
    assert {"gather", "scatter"} <= set(_lane_indexed(circular.jaxpr))


def test_vmapped_write_has_no_gather_and_no_scatter():
    step = jnp.ones((LANES, D), jnp.float32)
    accepted = jnp.arange(LANES) % 2 == 0
    ordered = jax.make_jaxpr(jax.vmap(push_pair))(*_lane_history(), step, step, accepted)
    assert "concatenate" in {eqn.primitive.name for eqn in _eqns(ordered.jaxpr)}
    assert _indexed(ordered.jaxpr) == []
    circular = jax.make_jaxpr(jax.vmap(_circular_write))(
        *_lane_history(), jnp.arange(LANES, dtype=jnp.int32), step, step, accepted
    )
    assert "scatter" in _lane_indexed(circular.jaxpr)


# -- whole solves against what the parent commit returned --------------------


def _logistic_lanes(n_lanes, n=120, d=12):
    """Seeded logistic problems in float64, one a lane: lanes differ in their
    rows, labels and L2 weight, so they stop at different iterations."""
    rng = np.random.default_rng(2028)
    x = rng.normal(size=(n_lanes, n, d))
    w_true = rng.normal(size=(n_lanes, d))
    p = 1.0 / (1.0 + np.exp(-np.einsum("lnd,ld->ln", x, w_true)))
    labels = (rng.uniform(size=p.shape) < p).astype(np.float64)
    l2 = np.geomspace(0.05, 5.0, n_lanes)
    return jnp.asarray(x), jnp.asarray(labels), jnp.asarray(l2)


def _value_and_grad(x, labels, l2):
    def value(w):
        z = x @ w
        return jnp.sum(jnp.logaddexp(0.0, z) - labels * z) + 0.5 * l2 * jnp.vdot(w, w)

    return jax.value_and_grad(value)


def _solve(solver, x, labels, l2):
    fn = _value_and_grad(x, labels, l2)
    w0 = jnp.zeros((x.shape[-1],), x.dtype)
    if solver == "lbfgs":
        # history 4 of up to 30 iterations: the history wraps
        return minimize_lbfgs(fn, w0, max_iter=30, history=4, tolerance=1e-9)
    if solver == "box":
        return minimize_lbfgs(
            fn, w0, max_iter=30, history=4, tolerance=1e-9,
            lower_bounds=jnp.full_like(w0, -0.25), upper_bounds=jnp.full_like(w0, 0.4),
        )
    return minimize_owlqn(fn, w0, l1_weight=1.5, max_iter=30, history=4, tolerance=1e-9)


def solve_case(solver, vmapped):
    """What a recorded case runs (also run, as it stands, on the parent commit
    to make RECORDED)."""
    x, labels, l2 = _logistic_lanes(5)
    if vmapped:
        result = jax.jit(jax.vmap(lambda *a: _solve(solver, *a)))(x, labels, l2)
    else:
        result = jax.jit(lambda *a: _solve(solver, *a))(x[1], labels[1], l2[1])
    return {
        "iterations": np.asarray(result.iterations).tolist(),
        "reason": np.asarray(result.reason).tolist(),
        "line_search_trials": np.asarray(result.line_search_trials).tolist(),
        "value": np.asarray(result.value).tolist(),
        "coefficients": np.asarray(result.coefficients).tolist(),
    }


# what commit f1295b6 (the circular layout) returned for solve_case, floats to 10 digits
RECORDED = {
    ("lbfgs", False): {
        "iterations": (
            15
        ),
        "reason": (
            2
        ),
        "line_search_trials": (
            [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0]
        ),
        "value": (
            23.32312693
        ),
        "coefficients": (
            [-0.4442309378, 3.585110936, 0.7193695533, 0.1420932564, 0.8882843489, 1.411568668,
            -2.124847279, 0.6406504515, 1.966784272, -0.2647399166, -0.5818763533, 1.576440227]
        ),
    },
    ("lbfgs", True): {
        "iterations": (
            [13, 15, 10, 11, 9]
        ),
        "reason": (
            [2, 2, 2, 2, 2]
        ),
        "line_search_trials": (
            [[0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]]
        ),
        "value": (
            [30.44281828, 23.32312693, 57.19552105, 37.39466765, 51.73512756]
        ),
        "coefficients": (
            [[0.6041376361, -1.776887374, -0.1215241969, 1.755860469, 1.035289719, 1.719384174,
            -1.182626382, 3.021746049, 0.3439701535, 1.255446293, -3.014626718, -0.09691391714],
            [-0.4442309378, 3.585110936, 0.7193695533, 0.1420932564, 0.8882843489, 1.411568668,
            -2.124847279, 0.6406504515, 1.966784272, -0.2647399166, -0.5818763533, 1.576440227],
            [1.157317559, 0.7058404219, -0.1113305862, 0.04050028883, 0.9160379886,
            -1.157615465, -0.2066163949, -0.2327451568, 0.1385588073, -0.2208566987,
            0.3097706949, 0.2038803996], [1.411695303, 0.6019723477, -0.7026082262,
            -0.8159868732, 0.6037108551, -1.528102515, -1.192109584, -0.3692539088,
            0.6115884939, 0.5244347593, -0.2568350486, 0.2802906389], [-0.7416923232,
            -0.2593214433, -0.004046605727, 0.1737039312, 0.2166393305, 0.4883590279,
            0.4742750125, 0.01787436697, 0.5244681751, -0.6574004535, 0.6447987407,
            0.9325748971]]
        ),
    },
    ("box", False): {
        "iterations": (
            14
        ),
        "reason": (
            2
        ),
        "line_search_trials": (
            [0, 1, 1, 5, 5, 12, 6, 7, 8, 5, 7, 5, 7, 5, 14, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0]
        ),
        "value": (
            54.12860842
        ),
        "coefficients": (
            [-0.1747162087, 0.4, 0.1422345555, 0.05126737721, 0.2945044449, 0.4, -0.25,
            0.1695029966, 0.4, -0.08253139006, -0.25, 0.4]
        ),
    },
    ("box", True): {
        "iterations": (
            [30, 14, 13, 14, 10]
        ),
        "reason": (
            [1, 2, 2, 2, 2]
        ),
        "line_search_trials": (
            [[0, 1, 1, 5, 2, 5, 3, 5, 4, 5, 4, 5, 5, 5, 5, 5, 6, 5, 6, 5, 6, 5, 6, 5, 7, 5, 6,
            5, 7, 5, 7], [0, 1, 1, 5, 5, 12, 6, 7, 8, 5, 7, 5, 7, 5, 14, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 5, 3, 5, 5, 6, 8, 10, 6, 8, 6, 5, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 5, 2, 5, 4, 5, 6, 5, 8, 6, 9, 5, 12,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 3, 6, 5, 6, 5, 9, 6, 10,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]]
        ),
        "value": (
            [57.10551539, 54.12860842, 66.02228135, 55.05719513, 58.57506463]
        ),
        "coefficients": (
            [[0.2210007648, -0.25, -0.05006556924, 0.4, 0.4, 0.4, -0.25, 0.4, -0.01428752713,
            0.1441454775, -0.25, -0.25], [-0.1747162087, 0.4, 0.1422345555, 0.05126737721,
            0.2945044449, 0.4, -0.25, 0.1695029966, 0.4, -0.08253139006, -0.25, 0.4], [0.4, 0.4,
            -0.0304851782, 0.09629035687, 0.4, -0.25, -0.1387787383, -0.1678131425,
            -0.05675768506, 0.02730203859, 0.1768172452, 0.1929074163], [0.4, 0.2030891992,
            -0.25, -0.25, 0.4, -0.25, -0.25, -0.2455012007, 0.4, 0.3734974631, -0.1852409561,
            0.3263550911], [-0.25, -0.25, 0.0657941773, 0.0905697296, 0.1214392244, 0.398598218,
            0.3414745715, -0.01141974643, 0.4, -0.25, 0.4, 0.4]]
        ),
    },
    # OWL-QN's ``line_search_trials`` are PR 47's counts (one trial an
    # iteration here); until then the solver reported zeros. Every other
    # number of the two cases is the circular layout's commit's.
    ("owlqn", False): {
        "iterations": (
            12
        ),
        "reason": (
            2
        ),
        "line_search_trials": (
            [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0]
        ),
        "value": (
            38.80467021
        ),
        "coefficients": (
            [-0.1384859378, 2.231668023, 0.2213493952, 0, 0.4350019785, 0.7348067381,
            -1.33558716, 0.2068371255, 1.179715077, -0.01140307416, -0.3092588253, 0.8384910573]
        ),
    },
    ("owlqn", True): {
        "iterations": (
            [11, 12, 10, 10, 8]
        ),
        "reason": (
            [2, 2, 2, 2, 2]
        ),
        "line_search_trials": (
            [[0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0,
            0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0, 1, 1, 1, 1, 1, 1,
            1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [0,
            1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
            0, 0, 0, 0, 0]]
        ),
        "value": (
            [46.81191916, 38.80467021, 63.87764191, 48.78892798, 58.63684273]
        ),
        "coefficients": (
            [[0.2366406368, -0.8134898908, 0, 0.8438512838, 0.5709183485, 0.9344725559,
            -0.6254081266, 1.648836226, 0.07179361629, 0.5033549244, -1.601630429,
            -0.09968446865], [-0.1384859378, 2.231668023, 0.2213493952, 0, 0.4350019785,
            0.7348067381, -1.33558716, 0.2068371255, 1.179715077, -0.01140307416, -0.3092588253,
            0.8384910573], [0.8958599412, 0.5104864138, -0.02313192709, 0.01248186896,
            0.7304609864, -0.8749213817, -0.09556961002, -0.105885383, 0.009132316987,
            -0.03032677572, 0.2058785389, 0.1261248294], [1.106747032, 0.3489382852,
            -0.4703584935, -0.6489348036, 0.4173377965, -1.181123687, -0.9323279533,
            -0.2059132187, 0.4371639893, 0.3712567836, -0.1607433396, 0.1945615903],
            [-0.6344267408, -0.1669343627, 0, 0.08178545727, 0.1406529615, 0.3972154923,
            0.3659559473, 0, 0.4498434216, -0.5438777386, 0.5320530284, 0.8091640479]]
        ),
    },
}


@pytest.mark.parametrize("vmapped", [False, True], ids=["one_solve", "vmapped"])
@pytest.mark.parametrize("solver", ["lbfgs", "box", "owlqn"])
def test_whole_solve_is_the_parent_commits(solver, vmapped):
    got = solve_case(solver, vmapped)
    want = RECORDED[solver, vmapped]
    for exact in ("iterations", "reason", "line_search_trials"):
        assert got[exact] == want[exact], exact
    np.testing.assert_allclose(got["value"], want["value"], rtol=1e-6)
    np.testing.assert_allclose(
        got["coefficients"], want["coefficients"], rtol=1e-6, atol=1e-12
    )


# -- a slot stored as whole tiles where d is large (PR 45) --------------------
#
# ``history_slot_shape`` is the one rule: below ``SLAB_MIN_DIM`` a slot is a row
# of ``[m, d]``, from it on a slab ``[R, 128]`` of ``[m, R, 128]``. The two
# functions read the form off the history they are handed, so both forms of
# one d can be fed the same pairs here.

from photon_ml_tpu.optim import lbfgs as lbfgs_mod  # noqa: E402
from photon_ml_tpu.optim.lbfgs import (  # noqa: E402
    SLAB_MIN_DIM,
    empty_history,
    history_slot_shape,
)
from photon_ml_tpu.telemetry.registry import default_registry  # noqa: E402
from photon_ml_tpu.telemetry.solver_trace import reset_solver_metrics  # noqa: E402

SLAB_D = SLAB_MIN_DIM + 3_616  # 20,000: over the edge, no multiple of 1,024


def _slab_shape(d):
    return (8 * -(-d // 1024), 128)


def test_the_rule_has_one_edge_and_whole_tiles_above_it():
    for d in (16, 32, 256, 2_000, SLAB_MIN_DIM - 1):
        assert history_slot_shape(d) == (d,)
    assert history_slot_shape(SLAB_MIN_DIM) == (SLAB_MIN_DIM // 128, 128)
    assert history_slot_shape(SLAB_D) == (160, 128)
    assert history_slot_shape(20_216_830) == (157_944, 128)  # 20,216,832 floats
    for d in (SLAB_MIN_DIM, SLAB_D, 20_216_830):
        rows, lanes = history_slot_shape(d)
        assert rows % 8 == 0 and 0 <= rows * lanes - d < 1024


def _wide_stream(seed, n_pairs, d, dtype):
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(n_pairs, d))
    y = rng.uniform(0.5, 2.0, size=(n_pairs, 1)) * s + 0.1 * rng.normal(size=(n_pairs, d))
    if n_pairs > 4:
        y[2] = -y[2]  # negative and zero curvature: neither pair is kept
        y[4] = 0.0
    g = rng.normal(size=(n_pairs + 1, d))
    return tuple(jnp.asarray(x, dtype) for x in (s, y, g))


def _run_form(slot, s, y, g, accepted):
    """The stream through a history whose slots have shape ``slot``: the
    direction before the first step and after every one, and the final
    history."""
    m, dtype = M, s.dtype
    init = (jnp.zeros((m,) + slot, dtype), jnp.zeros((m,) + slot, dtype),
            jnp.zeros((m,), dtype), jnp.int32(0))

    def step(history, xs):
        s_t, y_t, g_t, ok = xs
        history = push_pair(*history, s_t, y_t, ok)
        return history, two_loop_direction(g_t, *history)

    history, rest = lax.scan(step, init, (s, y, g[1:], accepted))
    return two_loop_direction(g[0], *init), rest, history


# pairs fed: none, part full, exactly full, wrapped (two of them dropped for
# their curvature, two refused)
FORM_CASES = {"count0": 0, "count3": 3, "full": M + 2, "wrapped": 2 * M + 5}


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("name", FORM_CASES)
@pytest.mark.parametrize("d", [1_000, SLAB_MIN_DIM, SLAB_D],
                         ids=["below", "at_the_edge", "above"])
def test_slab_form_gives_the_row_forms_direction_and_keeps_its_pairs(d, name, dtype):
    n_pairs = FORM_CASES[name]
    s, y, g = _wide_stream(45 + n_pairs, n_pairs, d, dtype)
    accepted = np.ones((n_pairs,), bool)
    if n_pairs > M:
        accepted[[7, 9]] = False
    accepted = jnp.asarray(accepted)
    run = jax.jit(_run_form, static_argnums=0)
    row_first, row_rest, row_hist = run((d,), s, y, g, accepted)
    slab_first, slab_rest, slab_hist = run(_slab_shape(d), s, y, g, accepted)

    assert slab_first.shape == (d,) and slab_rest.shape == (n_pairs, d)
    assert np.array_equal(np.asarray(slab_first), np.asarray(-g[0]))
    # another order of the same sums over d floats, 2 m of them a direction:
    # a rounding of each sum (some sqrt(d) ulps) of the direction's norm
    ulps = 4 * np.sqrt(d) * np.finfo(dtype).eps if dtype == "float32" else 1e-12
    for slab, row in zip(np.asarray(slab_rest), np.asarray(row_rest)):
        assert np.isfinite(slab).all()
        assert np.linalg.norm(slab - row) <= ulps * np.linalg.norm(row)

    kept = int(row_hist[3])
    dropped = (2 if n_pairs > 4 else 0) + (2 if n_pairs > M else 0)
    assert int(slab_hist[3]) == kept == min(n_pairs - dropped, M)
    # the same pairs in the same slots (s.y is taken on the flat vectors in both)
    assert np.array_equal(np.asarray(slab_hist[2]), np.asarray(row_hist[2]))
    for slab, row in zip(slab_hist[:2], row_hist[:2]):
        flat = np.asarray(slab).reshape(M, -1)
        assert np.array_equal(flat[:, :d], np.asarray(row))
        # the pad's floats stay zero through every push
        assert not flat[:, d:].any()


def test_the_slabs_pad_never_reaches_the_direction():
    """d = 20,000 leaves 480 floats of pad a slot: zero after every push, and
    the direction is the [d] floats alone, whatever the gradient holds."""
    d = SLAB_D
    s, y, g = _wide_stream(7, M + 3, d, "float32")
    first, rest, history = jax.jit(_run_form, static_argnums=0)(
        _slab_shape(d), s, y, g, jnp.ones((M + 3,), bool))
    rows, lanes = _slab_shape(d)
    assert rows * lanes - d == 480
    for hist in history[:2]:
        assert hist.shape == (M, rows, lanes)
        assert not np.asarray(hist).reshape(M, -1)[:, d:].any()
    assert first.shape == (d,) and rest.shape == (M + 3, d)
    assert np.isfinite(np.asarray(rest)).all() and np.asarray(rest[-1]).all()


# -- below the edge the traced program is the parent's ------------------------


def _parent_two_loop_direction(g, s_hist, y_hist, rho, count):
    """``two_loop_direction`` as commit 79e1951 had it."""
    with jax.named_scope("lbfgs/direction"):
        m = s_hist.shape[0]

        def slot(x, k):
            return lax.dynamic_index_in_dim(x, k, keepdims=False)

        def backward(k, carry):
            q, alphas = carry
            alpha = jnp.where(k < count, slot(rho, k) * jnp.vdot(slot(s_hist, k), q), 0.0)
            q = q - alpha * slot(y_hist, k)
            return q, lax.dynamic_update_index_in_dim(alphas, alpha, k, 0)

        q, alphas = lax.fori_loop(0, m, backward, (g, jnp.zeros((m,), dtype=g.dtype)))
        gamma = jnp.where(
            count > 0,
            jnp.vdot(s_hist[0], y_hist[0])
            / jnp.maximum(jnp.vdot(y_hist[0], y_hist[0]), 1e-30),
            1.0,
        )
        r = gamma * q

        def forward(i, r):
            k = m - 1 - i
            beta = slot(rho, k) * jnp.vdot(slot(y_hist, k), r)
            return r + jnp.where(k < count, slot(alphas, k) - beta, 0.0) * slot(s_hist, k)

        r = lax.fori_loop(0, m, forward, r)
        return -r


def _parent_push_pair(s_hist, y_hist, rho, count, s, y, accepted):
    """``push_pair`` as commit 79e1951 had it."""
    with jax.named_scope("lbfgs/history"):
        m = s_hist.shape[0]
        sy = jnp.vdot(s, y)
        keep_pair = accepted & (sy > 1e-10)

        def pushed(hist, new):
            return jnp.where(keep_pair, jnp.concatenate([new[None], hist[:-1]]), hist)

        return (
            pushed(s_hist, s),
            pushed(y_hist, y),
            pushed(rho, 1.0 / jnp.maximum(sy, 1e-30)),
            jnp.where(keep_pair, jnp.minimum(count + 1, m), count),
        )


@pytest.mark.parametrize("vmapped", [False, True], ids=["one_solve", "vmapped"])
@pytest.mark.parametrize("d", [16, 32, 256])
def test_below_the_edge_the_jaxprs_are_the_parents(d, vmapped):
    """The lanes' d (16, 32) and the GLMix fixed effect's (256): the history
    the rule gives them is ``[m, d]`` and both functions trace to the parent
    commit's equations, variable for variable: no reshape, no pad."""
    s_hist, y_hist, rho, count = empty_history(M, d, jnp.float32)
    assert s_hist.shape == y_hist.shape == (M, d)
    g = jnp.ones((d,), jnp.float32)
    direction_args = (g, s_hist, y_hist, rho, count)
    push_args = (s_hist, y_hist, rho, count, g, g, jnp.asarray(True))
    wrap = (lambda f: f)
    if vmapped:
        tile = lambda x: jnp.broadcast_to(x, (LANES,) + x.shape)
        direction_args = tuple(tile(x) for x in direction_args)
        push_args = tuple(tile(x) for x in push_args)
        wrap = jax.vmap
    for ours, parents, args in (
        (two_loop_direction, _parent_two_loop_direction, direction_args),
        (push_pair, _parent_push_pair, push_args),
    ):
        got = jax.make_jaxpr(wrap(ours))(*args)
        assert str(got) == str(jax.make_jaxpr(wrap(parents))(*args))
        names = {eqn.primitive.name for eqn in _eqns(got.jaxpr)}
        assert not names & {"reshape", "pad", "reduce_sum"}
    # and above it the slab is what both are handed
    assert empty_history(M, SLAB_D, jnp.float32)[0].shape == (M,) + _slab_shape(SLAB_D)


# -- whole solves above the edge against the same solve forced below it -------


def _wide_problem(n=48, d=SLAB_D):
    rng = np.random.default_rng(4500)
    x = rng.normal(size=(n, d)) / np.sqrt(d)
    w_true = rng.normal(size=(d,)) * 4.0
    p = 1.0 / (1.0 + np.exp(-(x @ w_true)))
    labels = (rng.uniform(size=p.shape) < p).astype(np.float64)
    return jnp.asarray(x), jnp.asarray(labels), jnp.asarray(0.05)


def _wide_solve(solver):
    """Traced anew at every call: the rule is read while tracing."""
    x, labels, l2 = _wide_problem()
    fn = _value_and_grad(x, labels, l2)
    w0 = jnp.zeros((x.shape[-1],), x.dtype)
    if solver == "lbfgs":
        run = lambda: minimize_lbfgs(fn, w0, max_iter=30, history=4, tolerance=1e-9)
    elif solver == "box":
        run = lambda: minimize_lbfgs(
            fn, w0, max_iter=30, history=4, tolerance=1e-9,
            lower_bounds=jnp.full_like(w0, -0.02), upper_bounds=jnp.full_like(w0, 0.03))
    else:
        run = lambda: minimize_owlqn(
            fn, w0, l1_weight=0.002, max_iter=30, history=4, tolerance=1e-9)
    return jax.jit(run)()


@pytest.mark.parametrize("solver", ["lbfgs", "box", "owlqn"])
def test_whole_solve_above_the_edge_is_the_solve_forced_below_it(solver, monkeypatch):
    reset_solver_metrics()
    slab = _wide_solve(solver)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["solver/history/slot_rows"] == 160  # the slab engaged
    monkeypatch.setattr(lbfgs_mod, "SLAB_MIN_DIM", 10 ** 9)
    row = _wide_solve(solver)
    assert default_registry().snapshot()["gauges"]["solver/history/slot_rows"] == 1
    assert int(slab.iterations) == int(row.iterations) > 4  # the history wrapped
    assert int(slab.reason) == int(row.reason)
    assert np.array_equal(np.asarray(slab.line_search_trials),
                          np.asarray(row.line_search_trials))
    np.testing.assert_allclose(float(slab.value), float(row.value), rtol=1e-9)
    gap = np.linalg.norm(np.asarray(slab.coefficients) - np.asarray(row.coefficients))
    assert gap <= 1e-5 * np.linalg.norm(np.asarray(row.coefficients))
    assert np.linalg.norm(np.asarray(row.coefficients)) > 0.0


# -- the gauges that say which form the last traced solve stored --------------


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
@pytest.mark.parametrize("d,rows,lanes", [(SLAB_D, 160, 128), (16, 1, 16)],
                         ids=["d20000_slab", "d16_row"])
def test_a_traced_solve_records_its_historys_form(d, rows, lanes, solver):
    def fn(w):
        return 0.5 * jnp.vdot(w - 1.0, w - 1.0), w - 1.0

    reset_solver_metrics()
    assert not [k for k in default_registry().snapshot()["gauges"] if k.startswith("solver/")]
    w0 = jnp.zeros((d,), jnp.float32)
    if solver == "lbfgs":
        jax.jit(lambda: minimize_lbfgs(fn, w0, max_iter=3))()
    else:
        jax.jit(lambda: minimize_owlqn(fn, w0, l1_weight=0.1, max_iter=3))()
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["solver/history/slot_rows"] == rows
    assert gauges["solver/history/slot_lanes"] == lanes
    assert gauges["solver/history/slot_bytes"] == rows * lanes * 4  # the pad included
    reset_solver_metrics()
    assert "solver/history/slot_bytes" not in default_registry().snapshot()["gauges"]
