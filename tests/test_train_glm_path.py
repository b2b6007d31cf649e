"""``train_glm`` solves its λ path through ONE cached program whose inputs are
arguments (``estimators._jitted_path_solve``, label ``glm/path_solve``).

Held here: (a) the models equal those of the eager per-λ loop the program
replaced, written out below as the plain reference of the change; (b) a second
fit on the same shapes traces, lowers and compiles nothing; (c) an L2 path
compiles one program whatever its length; (d) the program takes the feature
block as an input and holds no constant of its size.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import estimators
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType, solve
from photon_ml_tpu.telemetry.program_ledger import (
    ProgramLedger,
    install_ledger,
    uninstall_ledger,
)
from photon_ml_tpu.telemetry.registry import MetricsRegistry, default_registry
from photon_ml_tpu.types import TaskType

TASK = TaskType.LOGISTIC_REGRESSION
LAMBDAS = (0.1, 1.0, 10.0, 100.0)
N, D = 240, 9

#: (regularization, box, optimizer): every branch of train_glm's loop
PATHS = [
    pytest.param("l2", False, "LBFGS", id="l2-LBFGS"),
    pytest.param("l2", True, "LBFGS", id="l2-box-LBFGS"),
    pytest.param("l2", False, "TRON", id="l2-TRON"),
    pytest.param("elastic-net", False, "LBFGS", id="elastic-net-LBFGS"),
]
LAYOUTS = ["dense", "sparse"]


def _batch(layout, seed=0, n=N, d=D, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.6)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ rng.normal(size=d))))
    y = y.astype(dtype)
    if layout == "dense":
        return LabeledPointBatch.create(jnp.asarray(x, dtype), jnp.asarray(y))
    rows, cols = np.nonzero(x)
    return SparseLabeledPointBatch.from_coo(
        rows, cols, x[rows, cols], y, dim=d, dtype=dtype)


def _fit_arguments(reg, box, optimizer, d=D):
    kw = dict(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType[optimizer], max_iterations=40),
        regularization_weights=LAMBDAS,
        elastic_net_alpha=0.5 if reg == "elastic-net" else 0.0,
    )
    if box:
        kw["lower_bounds"] = np.full(d, -0.25)
        kw["upper_bounds"] = np.full(d, 0.4)
    return kw


class _Recorder:
    def __init__(self):
        self.solves = {}

    def record_solve(self, _coordinate, result, *, extra=None, **_):
        self.solves[extra["lambda"]] = result

    def heartbeat(self, *_args, **_cursor):
        return None


def _eager_path(batch, *, optimizer, regularization_weights,
                elastic_net_alpha=0.0, normalization=None, lower_bounds=None,
                upper_bounds=None):
    """The loop ``train_glm`` ran before: one un-jitted ``solve`` for each λ,
    the λ's own L2 inside the objective, warm-started in ascending order."""
    loss = loss_for_task(TASK)
    kind = (SparseGLMObjective if isinstance(batch, SparseLabeledPointBatch)
            else GLMObjective)
    w = jnp.zeros((batch.dim,), batch.solve_dtype)
    means, results = {}, {}
    for lam in sorted(regularization_weights):
        l1 = elastic_net_alpha * lam
        objective = kind(loss, l2_weight=(1.0 - elastic_net_alpha) * lam,
                         normalization=normalization)
        opt = optimizer
        if l1 > 0.0:
            opt = dataclasses.replace(
                optimizer.with_l1(l1), optimizer_type=OptimizerType.OWLQN)
        results[lam] = solve(
            opt, objective.bind(batch), w,
            lower_bounds=None if lower_bounds is None
            else jnp.asarray(lower_bounds, batch.dtype),
            upper_bounds=None if upper_bounds is None
            else jnp.asarray(upper_bounds, batch.dtype))
        w = results[lam].coefficients
        means[lam] = objective.normalization.to_model_space(w, None)
    return means, results


# -- (a) the same models as the eager loop -----------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("reg, box, optimizer", PATHS)
def test_models_are_the_eager_loops(layout, reg, box, optimizer):
    batch = _batch(layout)
    kw = _fit_arguments(reg, box, optimizer)
    recorder = _Recorder()
    models = train_glm(batch, TASK, telemetry=recorder, **kw)
    means, results = _eager_path(batch, **kw)
    assert sorted(models) == sorted(LAMBDAS)
    for lam in LAMBDAS:
        got = np.asarray(models[lam].coefficients.means)
        want = np.asarray(means[lam])
        scale = max(np.linalg.norm(want), 1e-12)
        assert np.linalg.norm(got - want) / scale <= 1e-6, lam
        assert int(recorder.solves[lam].iterations) == int(results[lam].iterations)
        assert int(recorder.solves[lam].reason) == int(results[lam].reason)
        if box:
            assert got.min() >= -0.25 - 1e-12 and got.max() <= 0.4 + 1e-12


def test_variances_carry_each_lambdas_own_l2():
    """``coefficient_variances`` still sees the λ's L2: 1 / (diag(H) + l2)."""
    batch = _batch("dense")
    models = train_glm(batch, TASK, regularization_weights=(1.0, 100.0),
                       compute_variance=True, variance_mode="diagonal")
    for lam, model in models.items():
        data = GLMObjective(loss_for_task(TASK)).hessian_diagonal(
            model.coefficients.means, batch)
        np.testing.assert_allclose(
            np.asarray(model.coefficients.variances),
            1.0 / (np.asarray(data) + lam), rtol=1e-9)


# -- (b) a second fit is a dispatch ------------------------------------------


def _compile_work():
    snap = default_registry().snapshot()
    return (snap["histograms"]["jax/trace_seconds"]["total"],
            snap["histograms"]["jax/lower_seconds"]["total"],
            snap["counters"]["jax/backend_compile_count"])


def _factors_context(d=D):
    return NormalizationContext(
        factors=jnp.asarray(np.linspace(0.5, 2.0, d)), shifts=None)


@pytest.mark.parametrize("normalized", [False, True],
                         ids=["no_normalization", "one_context_reused"])
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("reg, box, optimizer", PATHS)
def test_second_fit_on_the_same_shapes_compiles_nothing(
        layout, reg, box, optimizer, normalized):
    kw = _fit_arguments(reg, box, optimizer)
    if normalized:
        kw["normalization"] = _factors_context()  # built once, as a job does
    first = _batch(layout, seed=1)
    jax.block_until_ready(
        [m.coefficients.means for m in train_glm(first, TASK, **kw).values()])
    if layout == "dense":
        second = _batch(layout, seed=2)  # other values, the same shapes
    else:
        second = first  # a sparse batch's shapes follow its non-zeros
    before = _compile_work()
    models = train_glm(second, TASK, **kw)
    jax.block_until_ready([m.coefficients.means for m in models.values()])
    assert _compile_work() == before


def test_a_rebuilt_context_is_another_program():
    """The objective keys on ``id(normalization)``: the jit cache is warm for
    the caller that keeps ONE context, which is what the test above holds;
    this one says that the key is the object, not its values."""
    batch = _batch("dense", seed=3)
    kw = _fit_arguments("l2", False, "LBFGS")
    train_glm(batch, TASK, normalization=_factors_context(), **kw)
    before = _compile_work()[2]
    train_glm(batch, TASK, normalization=_factors_context(), **kw)
    assert _compile_work()[2] > before


# -- (c) one program for the whole L2 path -----------------------------------


@pytest.fixture
def ledger():
    led = install_ledger(ProgramLedger(registry=MetricsRegistry()))
    try:
        yield led
    finally:
        uninstall_ledger()


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
def test_an_l2_path_compiles_one_program(ledger, layout, optimizer):
    # shapes no other test of this file uses, so the program is new here
    batch = _batch(layout, seed=4, n=N + 8, d=D + 2)
    train_glm(batch, TASK, **_fit_arguments("l2", False, optimizer, d=D + 2))
    row = ledger.snapshot()["glm/path_solve"]
    assert row["calls"] == len(LAMBDAS)
    assert row["signatures"] == 1 and row["recompiles"] == 0
    assert row["compiles"] == 1


def test_an_elastic_net_path_compiles_one_program_a_lambda(ledger):
    """L1 lives in the static ``OptimizerConfig``: one OWL-QN program per λ."""
    batch = _batch("dense", seed=5, n=N + 16, d=D + 3)
    train_glm(batch, TASK,
              **_fit_arguments("elastic-net", False, "LBFGS", d=D + 3))
    row = ledger.snapshot()["glm/path_solve"]
    assert row["calls"] == len(LAMBDAS)
    assert row["signatures"] == len(LAMBDAS)


# -- (d) the feature block is an input, not a constant ------------------------


def _constants(closed):
    """Every constant of a closed jaxpr and of the closed jaxprs inside it."""
    found = list(closed.consts)
    stack = [closed.jaxpr]
    while stack:
        for eqn in stack.pop().eqns:
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) else (value,):
                    if hasattr(inner, "consts"):
                        found.extend(inner.consts)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        stack.append(inner)
    return found


def _leaf_avals(tree):
    return sorted((tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_program_takes_the_batch_as_input_and_bakes_in_none_of_it(layout):
    batch = _batch(layout, seed=6)
    objective = estimators._objective_for_batch(
        batch, loss_for_task(TASK), 0.0, None, use_pallas=None)
    opt = OptimizerConfig(max_iterations=5)
    w0 = jnp.zeros((batch.dim,), batch.solve_dtype)
    l2 = np.asarray(1.0, batch.solve_dtype)
    largest = max(leaf.size for leaf in jax.tree_util.tree_leaves(batch))
    assert largest >= N

    traced = estimators._jitted_path_solve.trace(
        objective, opt, batch, w0, l2, None, None).jaxpr
    inputs = sorted((tuple(v.aval.shape), str(v.aval.dtype))
                    for v in traced.jaxpr.invars)
    for aval in _leaf_avals(batch):
        assert aval in inputs
    assert ((), str(l2.dtype)) in inputs  # λ too: one program for the path
    assert all(np.size(c) < N for c in _constants(traced))

    # the guard discriminates: the eager solve closes over the batch
    eager = jax.make_jaxpr(
        lambda w: solve(opt, objective.bind(batch), w))(w0)
    assert any(np.size(c) == largest for c in _constants(eager))


# -- (e) the kernel reads the feature block as it lies ------------------------


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, but a Pallas
    kernel's body: what happens there happens on a tile in VMEM."""
    stack = [jaxpr]
    while stack:
        for eqn in stack.pop().eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        stack.append(inner)


@pytest.mark.parametrize("n,d,ragged", [
    pytest.param(1000, 200, 1, id="ragged"),  # neither whole tiles nor whole lanes
    pytest.param(1024, 256, 0, id="whole-tiles"),
])
def test_no_copy_of_the_feature_block_is_made_for_the_kernel(n, d, ragged):
    """No equation of the path's program builds an array of the feature
    block's size or more by padding, concatenating or updating (the kernel
    wrapper used to pad X to whole tiles inside EVERY evaluation: 3.28 GB
    written and read again, 55 times a fit of the dense benchmark cell), and
    the kernel's trace counts itself as ragged exactly when it masks."""
    from photon_ml_tpu.ops import pallas_glm

    batch = _batch("dense", seed=7, n=n, d=d, dtype=np.float32)
    objective = estimators._objective_for_batch(
        batch, loss_for_task(TASK), 0.0, None, use_pallas=True)
    opt = OptimizerConfig(max_iterations=5)
    counter = default_registry().counter(pallas_glm.TRACES_RAGGED)
    before = counter.value
    traced = estimators._jitted_path_solve.trace(
        objective, opt, batch, jnp.zeros((d,), jnp.float32),
        np.asarray(1.0, np.float32), None, None).jaxpr
    assert counter.value == before + ragged

    equations = list(_equations(traced.jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in equations)
    builders = {"pad", "concatenate", "dynamic_update_slice"}
    assert not [
        (e.primitive.name, v.aval.shape) for e in equations
        if e.primitive.name in builders
        for v in e.outvars if np.prod(v.aval.shape) >= n * d]
