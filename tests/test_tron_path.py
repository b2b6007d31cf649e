"""Trust-region Newton (``optim/tron.py``) on the estimator's normal path.

Held here: (a) ``train_glm`` under TRON against the plain reference's exact
minimizer for every λ of a path; (b) in float64 the iterates are those of a
plain Python replay of LIBLINEAR's method (the parent's arithmetic: the
predicted reduction from an explicit ``s.H.s``), and what the solve counts
(``line_search_trials`` = a round's Hessian-vector products, ``floor_exits``,
rejected rounds) is what the replay counts; (c) a float32 sum objective of 1e5
ends at the float's floor, before ``max_iterations``, with no rejected round
after its last accepted one; (d) the jitted path equals the eager loop; (e)
``H v`` against float64 numpy, and a product with bfloat16 operands beside it;
(f) an L-BFGS solve's ``SolverResult`` has the leaves it had; (g) the scopes
and the telemetry adapter's counts exist for a TRON solve and for no other;
(h) with the one-pass product kernel in (interpreted) the path takes the rounds
and products it takes on the jvp, and the kernel's call stands under
``tron/hv`` by a name the benchmark's reduction does not take for the
gradient kernel's.
"""

import contextlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import tron
from photon_ml_tpu.optim.common import ConvergenceReason, SolverResult
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType, solve
from photon_ml_tpu.optim.tron import minimize_tron
from photon_ml_tpu.telemetry import program_ledger
from photon_ml_tpu.telemetry.program_ledger import compiled_scopes
from photon_ml_tpu.telemetry.registry import MetricsRegistry
from photon_ml_tpu.telemetry.solver_trace import SolverTelemetry, tron_counts
from photon_ml_tpu.types import TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAMBDAS = (0.1, 1.0, 10.0, 100.0)
TRON = OptimizerConfig(OptimizerType.TRON, max_iterations=15, tolerance=1e-5,
                       max_cg_iterations=20)
TASKS = {"logistic": TaskType.LOGISTIC_REGRESSION,
         "poisson": TaskType.POISSON_REGRESSION,
         "linear": TaskType.LINEAR_REGRESSION}


def _reference():
    path = os.path.join(ROOT, "benchmark", "references", "logistic-epsilon-tron.py")
    spec = importlib.util.spec_from_file_location("reference_tron", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rows(task: str, n: int, d: int, seed: int = 0, dtype=np.float64,
          signal: float = 4.0):
    """Seeded rows: correlated unit-scale columns, labels of a seeded model."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, d)) + rng.normal(size=(n, 1))) / np.sqrt(2 * d)
    margin = signal * (x @ rng.normal(size=d))
    if task == "logistic":
        y = rng.random(n) < 1.0 / (1.0 + np.exp(-margin))
    elif task == "poisson":
        y = rng.poisson(np.exp(np.clip(margin, -3.0, 2.0)))
    else:
        y = margin + rng.normal(size=n)
    return x.astype(dtype), np.asarray(y, dtype)


def _batch(x, y):
    return LabeledPointBatch.create(jnp.asarray(x), jnp.asarray(y))


class _Recorder:
    def __init__(self):
        self.solves = {}

    def record_solve(self, _coordinate, result, *, extra=None, **_):
        self.solves[extra["lambda"]] = result

    def heartbeat(self, *_args, **_cursor):
        return None


def _rejected(result) -> int:
    return tron_counts(result)["tron_rejected_rounds"]


# -- (a) the path against the reference's exact minimizer ---------------------


@pytest.fixture(scope="module")
def float32_path():
    x, y = _rows("logistic", 6000, 40, dtype=np.float32)
    recorder = _Recorder()
    models = train_glm(_batch(x, y), TASKS["logistic"], optimizer=TRON,
                       regularization_weights=LAMBDAS, telemetry=recorder)
    exact = _reference().fit({"x": x, "y": y}, {"lambdas": list(LAMBDAS)},
                             jax.devices()[:1])
    return models, recorder.solves, exact


@pytest.mark.parametrize("k", range(len(LAMBDAS)), ids=[f"lambda{lam:g}" for lam in LAMBDAS])
def test_the_path_ends_at_the_references_minimizer(float32_path, k):
    models, solves, exact = float32_path
    lam = LAMBDAS[k]
    w = np.asarray(models[lam].coefficients.means, np.float64)
    # an L-BFGS path at its live stop is held to 5e-3 here (tests/benchmark):
    # TRON ends where float32 does
    assert np.linalg.norm(w - exact[k]) / np.linalg.norm(exact[k]) < 2e-4
    solve_ = solves[lam]
    assert int(solve_.reason) in (ConvergenceReason.GRADIENT_WITHIN_TOLERANCE,
                                  ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE)
    assert int(solve_.iterations) < TRON.max_iterations


# -- (b) float64: LIBLINEAR's iterates, and the counts of a Python replay -----


def _replay_tron(value_and_grad, hessian_vector, w0, *, max_iter=15, tolerance=1e-5,
                 max_cg=20, forcing=0.1):
    """LIBLINEAR's TRON in plain numpy float64 as the parent ran it (the
    predicted reduction from one more product, ``s.H.s``); returns the
    iterates' values, the CG steps of every round and the rejected rounds."""
    eta0, eta1, eta2 = 1e-4, 0.25, 0.75
    sigma1, sigma2, sigma3 = 0.25, 0.5, 4.0
    w = np.array(w0, np.float64)
    f, g = value_and_grad(w)
    g0 = np.linalg.norm(g)
    delta = g0
    values, cg_steps, rejected = [f], [], 0
    for _ in range(max_iter):
        if np.linalg.norm(g) <= tolerance * g0:
            break
        # Steihaug's truncated CG
        z, r, dvec = np.zeros_like(g), -g, -g
        tol = forcing * np.linalg.norm(g)
        steps, hit = 0, False
        while steps < max_cg:
            hd = hessian_vector(w, dvec)
            steps += 1
            alpha = (r @ r) / max(dvec @ hd, 1e-30)
            if dvec @ hd <= 0 or np.linalg.norm(z + alpha * dvec) >= delta:
                zd, dd = z @ dvec, max(dvec @ dvec, 1e-30)
                rad = np.sqrt(max(zd * zd + dd * (delta * delta - z @ z), 0.0))
                z = z + (-zd + rad) / dd * dvec
                hit = True
                break
            z = z + alpha * dvec
            r_new = r - alpha * hd
            if np.sqrt(r_new @ r_new) <= tol:
                break
            dvec = r_new + (r_new @ r_new) / max(r @ r, 1e-30) * dvec
            r = r_new
        cg_steps.append(steps)
        gs = g @ z
        prered = -(gs + 0.5 * (z @ hessian_vector(w, z)))
        f_new, g_new = value_and_grad(w + z)
        actred = f - f_new
        snorm = np.linalg.norm(z)
        alpha = sigma3 if f_new - f - gs <= 0 else max(
            sigma1, -0.5 * gs / min(f_new - f - gs, -1e-30))
        if actred < eta0 * prered:
            delta = min(max(alpha, sigma1) * snorm, sigma2 * delta)
        elif actred < eta1 * prered:
            delta = max(sigma1 * delta, min(alpha * snorm, sigma2 * delta))
        elif actred < eta2 * prered:
            delta = max(sigma1 * delta, min(alpha * snorm, sigma3 * delta))
        elif hit:
            delta = min(sigma3 * delta, max(delta, snorm))
        else:
            delta = max(delta, min(alpha * snorm, sigma3 * delta))
        if actred > eta0 * prered:
            w, f, g = w + z, f_new, g_new
        else:
            rejected += 1
        values.append(f)
    return w, np.asarray(values), cg_steps, rejected


def _numpy_objective(task: str, x, y, l2):
    """(value_and_grad, hessian_vector) in float64 numpy."""
    from scipy.special import expit

    def parts(w):
        m = x @ w
        if task == "logistic":
            p = expit(m)
            return np.sum(np.logaddexp(0.0, m) - y * m), p - y, p * (1 - p)
        if task == "poisson":
            e = np.exp(m)
            return np.sum(e - y * m), e - y, e
        return 0.5 * np.sum((m - y) ** 2), m - y, np.ones_like(m)

    def value_and_grad(w):
        value, d1, _ = parts(w)
        return value + 0.5 * l2 * (w @ w), x.T @ d1 + l2 * w

    def hessian_vector(w, v):
        return x.T @ (parts(w)[2] * (x @ v)) + l2 * v

    return value_and_grad, hessian_vector


@pytest.fixture(scope="module", params=sorted(TASKS))
def float64_solve(request):
    task = request.param
    x, y = _rows(task, 3000, 24, seed=3)
    l2 = 0.5
    bound = GLMObjective(loss_for_task(TASKS[task]), l2_weight=l2,
                         use_pallas=False).bind(_batch(x, y))
    result = jax.jit(lambda w0: minimize_tron(
        bound.value_and_grad, bound.hessian_vector, w0))(jnp.zeros(24))
    replay = _replay_tron(*_numpy_objective(task, x, y, l2), np.zeros(24))
    return task, result, replay


def test_float64_iterates_are_the_replays(float64_solve):
    _, result, (w, values, _, _) = float64_solve
    n = int(result.iterations)
    assert n == len(values) - 1
    np.testing.assert_allclose(np.asarray(result.value_history)[:n + 1], values,
                               rtol=1e-11)
    np.testing.assert_allclose(np.asarray(result.coefficients), w,
                               rtol=1e-8, atol=1e-10)
    assert int(result.reason) == ConvergenceReason.GRADIENT_WITHIN_TOLERANCE


def test_products_rejected_rounds_and_floor_exits_are_the_replays_counts(float64_solve):
    task, result, (_, _, cg_steps, rejected) = float64_solve
    n = int(result.iterations)
    trials = np.asarray(result.line_search_trials)
    assert trials.dtype == np.int32 and trials.shape == (16,)
    # a round's products are its CG steps and nothing else
    assert trials[1:n + 1].tolist() == cg_steps
    assert trials[0] == 0 and not trials[n + 1:].any()
    counts = tron_counts(result)
    assert counts == {"tron_hv_products": sum(cg_steps),
                      "tron_rejected_rounds": rejected, "tron_floor_exits": 0}
    if task == "poisson":  # from zero the first full step overshoots
        assert rejected >= 1


def test_a_round_costs_its_cg_steps_and_no_more():
    x, y = _rows("logistic", 500, 8, seed=5)
    bound = GLMObjective(loss_for_task(TASKS["logistic"]), l2_weight=1.0,
                         use_pallas=False).bind(_batch(x, y))
    calls = []

    def counted(w, v):
        calls.append(1)
        return bound.hessian_vector(w, v)

    result = minimize_tron(bound.value_and_grad, counted, jnp.zeros(8),
                           host_loop=True)
    assert len(calls) == int(np.sum(np.asarray(result.line_search_trials))) > 0


# -- (c) the float32 floor ----------------------------------------------------


@pytest.fixture(scope="module")
def floor_solve():
    x, y = _rows("logistic", 240_000, 12, seed=7, dtype=np.float32, signal=1.0)
    bound = GLMObjective(loss_for_task(TASKS["logistic"]), l2_weight=1.0,
                         use_pallas=False).bind(_batch(x, y))
    # a gradient test no float32 gradient can meet: only the floor can end it
    return jax.jit(lambda w0: minimize_tron(
        bound.value_and_grad, bound.hessian_vector, w0, tolerance=1e-12,
        max_iter=15))(jnp.zeros(12, jnp.float32))


def test_a_float32_sum_of_1e5_ends_at_the_floor(floor_solve):
    assert float(floor_solve.value) > 1e5
    assert int(floor_solve.reason) == ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE
    assert int(floor_solve.floor_exits) == 1
    assert 2 <= int(floor_solve.iterations) < 15


def test_no_round_is_rejected_after_the_last_accepted_one(floor_solve):
    n = int(floor_solve.iterations)
    grads = np.asarray(floor_solve.grad_norm_history)[:n + 1]
    # the floor's round kept its step: the gradient moved in the last round
    assert grads[n] != grads[n - 1]
    assert _rejected(floor_solve) == 0
    # and it ended near the minimizer: the gradient fell by four orders
    assert grads[n] < 1e-4 * grads[0]


def test_the_floor_is_the_dtypes_own():
    """In float64 the same rows run on to the gradient test: the floor reads
    the dtype of ``w`` and the value it was handed, no option."""
    x, y = _rows("logistic", 20_000, 12, seed=7)
    bound = GLMObjective(loss_for_task(TASKS["logistic"]), l2_weight=1.0,
                         use_pallas=False).bind(_batch(x, y))
    result = minimize_tron(bound.value_and_grad, bound.hessian_vector,
                           jnp.zeros(12), tolerance=1e-9)
    assert int(result.reason) == ConvergenceReason.GRADIENT_WITHIN_TOLERANCE
    assert int(result.floor_exits) == 0


# -- (d) eager = jitted -------------------------------------------------------


@pytest.mark.parametrize("task", sorted(TASKS))
def test_the_jitted_path_is_the_eager_loop(task):
    x, y = _rows(task, 400, 7, seed=11)
    batch = _batch(x, y)
    recorder = _Recorder()
    models = train_glm(batch, TASKS[task], optimizer=TRON,
                       regularization_weights=LAMBDAS, telemetry=recorder)
    w = jnp.zeros(7)
    for lam in LAMBDAS:
        eager = solve(TRON, GLMObjective(loss_for_task(TASKS[task]),
                                         l2_weight=lam).bind(batch), w)
        w = eager.coefficients
        np.testing.assert_allclose(np.asarray(models[lam].coefficients.means),
                                   np.asarray(w), rtol=1e-7, atol=1e-9)
        jitted = recorder.solves[lam]
        assert int(jitted.iterations) == int(eager.iterations)
        assert (np.asarray(jitted.line_search_trials).tolist()
                == np.asarray(eager.line_search_trials).tolist())


# -- (e) the product ----------------------------------------------------------


def test_the_product_is_float32_true_and_a_bfloat16_one_is_not():
    x, y = _rows("logistic", 20_000, 64, seed=13, dtype=np.float32)
    rng = np.random.default_rng(1)
    w = rng.normal(size=64).astype(np.float32)
    v = rng.normal(size=64).astype(np.float32)
    lam = 1.0
    objective = GLMObjective(loss_for_task(TASKS["logistic"]))
    product = np.asarray(jax.jit(objective.hessian_vector)(
        jnp.asarray(w), jnp.asarray(v), _batch(x, y))) + lam * v
    exact = _reference().hessian_vector({"x": x}, w[None], v[None], [lam])[0]
    assert exact.dtype == np.float64

    def gap(a):
        return np.linalg.norm(a - exact) / np.linalg.norm(exact)

    assert gap(product) < 1e-5
    xb = jnp.asarray(x, jnp.bfloat16)

    def rounded(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    p = jax.nn.sigmoid(rounded(xb, jnp.asarray(w)))
    control = np.asarray(rounded(p * (1 - p) * rounded(xb, jnp.asarray(v)), xb)) + lam * v
    assert 2e-4 < gap(control) < 2e-2


# -- (f) the output pytree of every solver's program --------------------------


def test_an_lbfgs_solves_result_has_the_leaves_it_had():
    x, y = _rows("logistic", 300, 6, seed=17, dtype=np.float32)
    recorder = _Recorder()
    train_glm(_batch(x, y), TASKS["logistic"],
              optimizer=OptimizerConfig(OptimizerType.LBFGS, max_iterations=20),
              regularization_weights=(1.0,), telemetry=recorder)
    result = recorder.solves[1.0]
    assert isinstance(result, SolverResult)
    leaves = {jax.tree_util.keystr(path): (leaf.shape, str(leaf.dtype))
              for path, leaf in jax.tree_util.tree_flatten_with_path(result)[0]}
    assert leaves == {
        ".coefficients": ((6,), "float32"), ".value": ((), "float32"),
        ".gradient_norm": ((), "float32"), ".iterations": ((), "int32"),
        ".reason": ((), "int32"), ".value_history": ((21,), "float32"),
        ".grad_norm_history": ((21,), "float32"),
        ".line_search_trials": ((21,), "int32"), ".floor_exits": ((), "int32"),
    }


# -- (g) scopes and the adapter's counts --------------------------------------


def _scope_paths(optimizer) -> set:
    x, y = _rows("logistic", 64, 5, seed=19, dtype=np.float32)
    train_glm(_batch(x, y), TASKS["logistic"], optimizer=optimizer,
              regularization_weights=(1.0,))
    record = compiled_scopes("glm/path_solve")
    return {op_name.rpartition("/")[0] for _, op_name in record.instructions.values()}


def _holds(paths: set, *scopes: str) -> bool:
    pattern = re.compile(".*".join(
        r"(?<![^/(])" + re.escape(scope) + r"(?![^/)])" for scope in scopes))
    return any(pattern.search(path) for path in paths)


@pytest.mark.parametrize("scopes", [("tron/cg",), ("tron/cg", "tron/hv"),
                                    ("tron/update",)], ids="/".join)
def test_a_tron_program_carries_the_scope(scopes):
    paths = _scope_paths(TRON)
    assert _holds(paths, *scopes)
    # every product is inside the CG: none is left outside it
    assert all("tron/cg" in path for path in paths if "tron/hv" in path)
    assert not _holds(paths, "lbfgs/line_search")


def test_no_other_program_carries_a_tron_scope():
    paths = _scope_paths(OptimizerConfig(OptimizerType.LBFGS, max_iterations=5))
    assert paths and not any("tron/" in path for path in paths)


def test_a_tron_scope_is_never_an_instruction(monkeypatch):
    texts = []
    parse = program_ledger.scopes_of_text
    monkeypatch.setattr(program_ledger, "scopes_of_text",
                        lambda text: texts.append(text) or parse(text))

    def stripped(text: str) -> str:
        head, _, rest = text.partition("\nFileNames\n")
        body = re.sub(r"\A.*?\nStackFrames\n(?:\d+ [^\n]*\n)*", "", "\n" + rest,
                      flags=re.DOTALL) if rest else ""
        return re.sub(r", metadata=\{[^}]*\}", "", head + "\n" + body)

    def text_of_a_fresh_program():
        # a static OptimizerConfig no other test uses: traced here, not before
        _scope_paths(OptimizerConfig(OptimizerType.TRON, max_iterations=7,
                                     tolerance=1e-5))
        return texts[-1]

    scoped = text_of_a_fresh_program()
    assert "tron/hv" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()  # the same statics: traced anew only without jit's cache
    bare = text_of_a_fresh_program()
    assert "tron/" not in bare
    assert stripped(bare) == stripped(scoped)


# -- (h) the one-pass product on the path ------------------------------------


@pytest.fixture(scope="module")
def kernel_and_jvp_paths():
    """The λ path through ``glm/path_solve`` twice, the objective built with
    the kernels forced (interpreted here) and with them off, and the record of
    the program that holds the kernels."""
    from photon_ml_tpu.estimators import _jitted_path_solve

    x, y = _rows("logistic", 3000, 40, seed=23, dtype=np.float32)
    batch = _batch(x, y)
    paths = {}
    for use_pallas in (True, False):
        objective = GLMObjective(loss_for_task(TASKS["logistic"]),
                                 use_pallas=use_pallas)
        w = jnp.zeros(40, jnp.float32)
        paths[use_pallas] = []
        for lam in LAMBDAS:
            result = _jitted_path_solve(objective, TRON, batch, w,
                                        np.float32(lam), None, None)
            w = result.coefficients
            paths[use_pallas].append(result)
        if use_pallas:  # the record is of the label's LAST traced program
            record = compiled_scopes("glm/path_solve")
    return paths, record


@pytest.mark.parametrize("k", range(len(LAMBDAS)), ids=[f"lambda{lam:g}" for lam in LAMBDAS])
def test_the_kernels_path_takes_the_jvps_rounds_and_products(kernel_and_jvp_paths, k):
    paths, _ = kernel_and_jvp_paths
    kernel, jvp = paths[True][k], paths[False][k]
    assert int(kernel.iterations) == int(jvp.iterations)
    assert (np.asarray(kernel.line_search_trials).tolist()
            == np.asarray(jvp.line_search_trials).tolist())
    assert int(kernel.reason) == int(jvp.reason)
    w, expected = np.asarray(kernel.coefficients), np.asarray(jvp.coefficients)
    assert np.linalg.norm(w - expected) / np.linalg.norm(expected) < 2e-5


def test_the_products_call_stands_under_tron_hv_by_a_name_of_its_own(kernel_and_jvp_paths):
    """``benchmark/path_scopes.py`` files an instruction whose NAME matches
    ``trace_reduce.KERNEL`` under the gradient kernel's category before it
    looks at the scope, and on the chip a Pallas custom call is named after
    the jitted wrapper round it: the product's wrapper must not match."""
    from benchmark import trace_reduce

    _, record = kernel_and_jvp_paths
    # the scopes each kernel's jitted wrapper was traced under (interpreted,
    # the wrapper holds the grid's loop; on the chip, the one custom call)
    scopes = {name: {op_name.partition(f"/jit({name})")[0]
                     for _, op_name in record.instructions.values()
                     if f"/jit({name})" in op_name}
              for name in ("_hv_one_pass", "_fused_padded")}
    assert scopes["_hv_one_pass"] and scopes["_fused_padded"]
    assert all(_holds({path}, "tron/cg", "tron/hv") for path in scopes["_hv_one_pass"])
    assert not any("tron/" in path for path in scopes["_fused_padded"])
    assert trace_reduce.KERNEL.search("_fused_padded")
    assert not trace_reduce.KERNEL.search("_hv_one_pass")


def test_the_adapter_reports_tron_counts_for_a_tron_solve_only(float64_solve):
    _, result, (_, _, cg_steps, rejected) = float64_solve
    rows = []

    class _Journal:
        active = True

        def record(self, kind, **row):
            rows.append((kind, row))

        def heartbeat(self, **_):
            return None

    registry = MetricsRegistry()
    telemetry = SolverTelemetry(journal=_Journal(), registry=registry)
    telemetry.record_solve("glm", result, extra={"lambda": 1.0, "optimizer": "TRON"})
    telemetry.record_solve("glm", result, extra={"lambda": 1.0, "optimizer": "LBFGS"})
    (_, tron_row), (_, other_row) = rows
    assert tron_row["tron_hv_products"] == sum(cg_steps)
    assert tron_row["tron_rejected_rounds"] == rejected
    assert tron_row["tron_floor_exits"] == 0
    assert tron_row["iterations"] == int(result.iterations) and "reason" in tron_row
    assert not any(key.startswith("tron_") for key in other_row)
    counters = registry.snapshot()["counters"]
    assert counters["solver/tron_hv_products"] == sum(cg_steps)
    assert counters["solver/tron_rejected_rounds"] == rejected
    assert counters["solver/tron_floor_exits"] == 0


def test_train_glm_names_the_optimizer_to_its_telemetry():
    x, y = _rows("logistic", 200, 5, seed=23, dtype=np.float32)
    seen = []

    class _Telemetry(_Recorder):
        def record_solve(self, coordinate, result, *, extra=None, **kw):
            seen.append(dict(extra))

    train_glm(_batch(x, y), TASKS["logistic"], optimizer=TRON,
              regularization_weights=(1.0, 10.0), telemetry=_Telemetry())
    assert seen == [{"lambda": 1.0, "optimizer": "TRON"},
                    {"lambda": 10.0, "optimizer": "TRON"}]


def test_the_floor_constant_is_a_few_ulps():
    assert 1.0 <= tron.TRON_FLOOR_K <= 8.0
