"""What the sparse fixed effect's first cell forced in the program (PR 43): a
model scores a sparse block through the block's own layout, the hybrid head's
dots say their precision, the sparse work runs under named scopes, and an
already row-major triple is not sorted again.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import sparse_batch
from photon_ml_tpu.data.sparse_batch import (
    HybridPolicy,
    SparseLabeledPointBatch,
    coalesce_coo,
    sparse_margins,
    sparse_product,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.types import TaskType

N, D = 300, 2000
LAYOUTS = {
    "flat": dict(ell=False),
    "ell": dict(),
    "ell_narrow": dict(ell=3),  # rows wider than 3 spill into the flat overflow
    "hybrid": dict(hybrid=HybridPolicy(hot_cols=16, label="t")),
    "hybrid_narrow": dict(ell=2, hybrid=HybridPolicy(hot_cols=16, label="t")),
    "hybrid_no_ell": dict(ell=False, hybrid=HybridPolicy(hot_cols=16, label="t")),
}


def triple(seed=0):
    rng = np.random.default_rng(seed)
    per_row = rng.integers(3, 12, N)
    rows = np.repeat(np.arange(N), per_row)
    # a hot head (ids under 40) and a cold tail, as power-law columns give
    cols = np.where(rng.random(len(rows)) < 0.5, rng.integers(0, 40, len(rows)),
                    rng.integers(0, D, len(rows)))
    vals = rng.standard_normal(len(rows))
    labels = (rng.random(N) < 0.5).astype(np.float64)
    return rows, cols, vals, labels


def dense_of(rows, cols, vals):
    x = np.zeros((N, D))
    np.add.at(x, (rows, cols), vals)
    return x


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_model_scores_a_sparse_block_through_its_own_layout(layout):
    rows, cols, vals, labels = triple()
    offsets = np.random.default_rng(1).standard_normal(N)
    batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals, labels, dim=D, dtype=np.float64, offsets=offsets,
        **LAYOUTS[layout])
    w = np.random.default_rng(2).standard_normal(D)
    model = GeneralizedLinearModel(Coefficients(jnp.asarray(w)),
                                   TaskType.LOGISTIC_REGRESSION)
    expected = dense_of(rows, cols, vals) @ w
    # the batch's offsets are no part of a score; the caller's are added
    assert np.allclose(model.score(batch), expected, rtol=1e-12, atol=1e-12)
    assert np.allclose(model.score(batch, jnp.asarray(offsets)), expected + offsets,
                       rtol=1e-12, atol=1e-12)
    assert np.allclose(model.predict(batch), 1 / (1 + np.exp(-expected)), atol=1e-12)
    assert np.array_equal(np.asarray(sparse_margins(batch, jnp.asarray(w))),
                          np.asarray(sparse_product(batch, jnp.asarray(w)) + batch.offsets))


def test_a_dense_block_is_scored_as_before():
    x = np.random.default_rng(0).standard_normal((5, 7))
    w = np.random.default_rng(1).standard_normal(7)
    assert np.array_equal(np.asarray(Coefficients(jnp.asarray(w)).compute_score(
        jnp.asarray(x))), np.asarray(jnp.asarray(x) @ jnp.asarray(w)))


def _dots(jaxpr, out=None):
    """Every dot_general of a jaxpr, sub-jaxprs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dots(inner, out)
    return out


@pytest.mark.parametrize("what", ["margins", "gradient", "hessian_vector"])
def test_the_heads_dots_say_highest_precision(what):
    """A TPU's DEFAULT matmul precision rounds float32 operands to bfloat16;
    the three products over the dense head must not leave it to the default."""
    rows, cols, vals, labels = triple()
    batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals.astype(np.float32), labels.astype(np.float32), dim=D,
        hybrid=HybridPolicy(hot_cols=16, label="t"))
    objective = SparseGLMObjective(LogisticLoss())
    w = jnp.zeros(D, jnp.float32)
    fn = {"margins": lambda w: sparse_product(batch, w),
          "gradient": lambda w: objective.value_and_gradient(w, batch)[1],
          "hessian_vector": lambda w: objective.hessian_vector(w, w + 1.0, batch)}[what]
    k_hot = batch.hot_vals.shape[1]  # 16 padded to the head's lane multiple
    head = [eqn for eqn in _dots(jax.make_jaxpr(fn)(w).jaxpr)
            if k_hot in eqn.invars[0].aval.shape + eqn.invars[1].aval.shape]
    assert head, "no dot over the [n, k_hot] head"
    for eqn in head:
        assert eqn.params["precision"] is not None
        assert all(p == jax.lax.Precision.HIGHEST for p in eqn.params["precision"])


def test_a_bfloat16_batch_accumulates_its_gradient_in_float32():
    rows, cols, vals, labels = triple()
    batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals, labels, dim=D, dtype=jnp.bfloat16,
        hybrid=HybridPolicy(hot_cols=16, label="t"))
    value, gradient = SparseGLMObjective(LogisticLoss()).value_and_gradient(
        jnp.zeros(D, jnp.float32), batch)
    assert gradient.dtype == jnp.float32 and value.dtype == jnp.float32


def test_the_path_solves_record_holds_the_three_sparse_scopes():
    from photon_ml_tpu.estimators import train_glm
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.telemetry import program_ledger

    rows, cols, vals, labels = triple()
    batch = SparseLabeledPointBatch.from_coo(
        rows, cols, vals.astype(np.float32), labels.astype(np.float32), dim=D,
        ell=2, hybrid=HybridPolicy(hot_cols=16, label="t"))
    train_glm(batch, TaskType.LOGISTIC_REGRESSION,
              optimizer=OptimizerConfig(max_iterations=3),
              regularization_weights=[1.0])
    record = program_ledger.compiled_scopes("glm/path_solve")
    op_names = " ".join(op or "" for _, op in record.instructions.values())
    for scope in ("sparse/head", "sparse/tail_margins", "sparse/tail_gradient",
                  "lbfgs/line_search"):
        assert f"/{scope}/" in op_names, scope
    # the sparse scopes stand INSIDE the line search's: the innermost decides
    assert "lbfgs/line_search/while/body/sparse/tail_gradient/" in op_names


@pytest.mark.parametrize("what", ["sorted unique", "a pair twice", "rows out of order",
                                  "columns out of order"])
def test_a_row_major_unique_triple_is_not_sorted_again(what, monkeypatch):
    rows = np.array([0, 0, 1, 1, 1, 3])
    cols = np.array([2, 5, 0, 4, 9, 1])
    vals = np.arange(1.0, 7.0)
    if what == "a pair twice":
        cols[4] = 4
    elif what == "rows out of order":
        rows[2] = 2
        rows[3] = 1
    elif what == "columns out of order":
        cols[0], cols[1] = 5, 2
    sorts = []
    lexsort = np.lexsort
    monkeypatch.setattr(sparse_batch.np, "lexsort",
                        lambda keys: sorts.append(1) or lexsort(keys))
    r, c, v = coalesce_coo(rows, cols, vals)
    assert bool(sorts) == (what != "sorted unique")
    # what comes out is row-major, unique, and sums what went in
    key = r * 100 + c
    assert (np.diff(key) > 0).all() and v.sum() == vals.sum()
    dense = np.zeros((4, 10))
    np.add.at(dense, (rows, cols), vals)
    again = np.zeros((4, 10))
    again[r, c] = v
    assert np.array_equal(dense, again)
