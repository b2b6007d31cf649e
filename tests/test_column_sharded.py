"""Column-sharded (model-parallel) giant-d sparse FE training.

VERDICT r2 #5: the 1B-coefficient story needs the coefficient axis sharded
over "model" with nothing of size d replicated. These tests pin the
shard_map program (parallel/column_sharded.py) against the single-device
sparse objective on the 8-device virtual mesh (reference scale machinery:
feature-space partitioning + treeAggregate,
ValueAndGradientAggregator.scala:133-154).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch, SparseShard
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType, solve
from photon_ml_tpu.parallel.column_sharded import (
    _RUN_CHUNK,
    ColumnShardedGLMObjective,
    _sorted_run_sums,
    build_column_sharded_batch,
    init_column_sharded_coefficients,
    shard_column_batch,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType


def _problem(seed=0, n=120, d=37, nnz=600):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, size=nnz)
    cols = rng.integers(0, d, size=nnz)
    vals = rng.normal(size=nnz)
    y = (rng.random(n) < 0.5).astype(np.float64)
    offsets = rng.normal(scale=0.1, size=n)
    weights = rng.uniform(0.5, 2.0, size=n)
    shard = SparseShard(rows=rows, cols=cols, vals=vals,
                        num_samples=n, feature_dim=d)
    return shard, y, offsets, weights


def _put_model(mesh, x):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("model")))


@pytest.mark.parametrize("nnz,runs", [
    (300, 37),                    # inside one chunk
    (3 * _RUN_CHUNK + 17, 50),    # runs that span chunk edges
    (2 * _RUN_CHUNK, 5000),       # more runs than entries in places: empty runs
    (1, 3),                       # one entry, two of three runs empty
])
def test_sorted_run_sums_equal_the_runs_own_sums(nnz, runs):
    """The gradient's scatter-free reduction: every contiguous run of the
    column-sorted contributions sums to what numpy adds up, empty runs to 0."""
    rng = np.random.default_rng(nnz + runs)
    contrib = rng.normal(size=nnz)
    cuts = np.sort(rng.integers(0, nnz + 1, size=runs - 1))
    bounds = np.concatenate([[0], cuts, [nnz]])
    want = np.array([contrib[a:b].sum() for a, b in zip(bounds[:-1], bounds[1:])])
    got = _sorted_run_sums(
        jnp.asarray(contrib), jnp.asarray(bounds, dtype=jnp.int32)
    )
    assert got.shape == (runs,)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-9, atol=1e-11)
    assert np.all(np.asarray(got)[np.diff(bounds) == 0] == 0.0)


class TestColumnShardedObjective:
    @pytest.fixture(scope="class")
    def setup(self):
        shard, y, off, wt = _problem()
        mesh = make_mesh(data=1, model=8)
        cb = shard_column_batch(
            build_column_sharded_batch(shard, y, 8, offsets=off, weights=wt),
            mesh,
        )
        obj = ColumnShardedGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), mesh, l2_weight=0.4
        )
        ref_batch = SparseLabeledPointBatch.from_shard(
            shard, jnp.asarray(y), jnp.asarray(off), jnp.asarray(wt)
        )
        ref = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.4
        )
        return mesh, cb, obj, ref_batch, ref, shard.feature_dim

    def test_value_and_gradient_match_single_device(self, setup):
        mesh, cb, obj, ref_batch, ref, d = setup
        rng = np.random.default_rng(1)
        w = rng.normal(scale=0.1, size=d)
        wp = np.zeros(cb.padded_dim)
        wp[:d] = w
        v1, g1 = obj.value_and_gradient(_put_model(mesh, wp), cb)
        v2, g2 = ref.value_and_gradient(jnp.asarray(w), ref_batch)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(g1)[:d], np.asarray(g2), rtol=1e-9)
        # padding coefficient lanes see only the L2 term
        np.testing.assert_allclose(np.asarray(g1)[d:], 0.4 * wp[d:], rtol=1e-12)

    def test_hessian_vector_matches_single_device(self, setup):
        mesh, cb, obj, ref_batch, ref, d = setup
        rng = np.random.default_rng(2)
        w, v = rng.normal(scale=0.1, size=d), rng.normal(size=d)
        wp, vp = np.zeros(cb.padded_dim), np.zeros(cb.padded_dim)
        wp[:d], vp[:d] = w, v
        hv1 = obj.hessian_vector(_put_model(mesh, wp), _put_model(mesh, vp), cb)
        hv2 = ref.hessian_vector(jnp.asarray(w), jnp.asarray(v), ref_batch)
        np.testing.assert_allclose(np.asarray(hv1)[:d], np.asarray(hv2), rtol=1e-8)

    @pytest.mark.parametrize("opt", [OptimizerType.LBFGS, OptimizerType.TRON])
    def test_solver_equivalence(self, setup, opt):
        """LBFGS and TRON run UNCHANGED over the sharded vectors and land on
        the single-device solution."""
        mesh, cb, obj, ref_batch, ref, d = setup
        cfg = OptimizerConfig(optimizer_type=opt, max_iterations=40)
        w0 = init_column_sharded_coefficients(cb, mesh)
        r = jax.jit(lambda w: solve(cfg, obj.bind(cb), w))(w0)
        rr = solve(cfg, ref.bind(ref_batch), jnp.zeros(d))
        np.testing.assert_allclose(
            np.asarray(r.coefficients)[:d], np.asarray(rr.coefficients),
            atol=2e-5,
        )
        # solver work vectors live sharded over "model", coefficients too
        assert not r.coefficients.sharding.is_fully_replicated

    def test_mesh_invariance(self, setup):
        """4-block and 8-block partitions agree (the partitioner never
        changes the math — reference partition-count invariance)."""
        mesh, cb, obj, ref_batch, ref, d = setup
        shard, y, off, wt = _problem()
        mesh4 = make_mesh(data=1, model=4)
        cb4 = shard_column_batch(
            build_column_sharded_batch(shard, y, 4, offsets=off, weights=wt),
            mesh4,
        )
        obj4 = ColumnShardedGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), mesh4, l2_weight=0.4
        )
        rng = np.random.default_rng(3)
        w = rng.normal(scale=0.1, size=d)
        wp8 = np.zeros(cb.padded_dim); wp8[:d] = w
        wp4 = np.zeros(cb4.padded_dim); wp4[:d] = w
        v8, g8 = obj.value_and_gradient(_put_model(mesh, wp8), cb)
        v4, g4 = obj4.value_and_gradient(_put_model(mesh4, wp4), cb4)
        np.testing.assert_allclose(float(v8), float(v4), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g8)[:d], np.asarray(g4)[:d], rtol=1e-9
        )

    def test_block_mesh_mismatch_rejected(self, setup):
        """A batch partitioned into more blocks than mesh devices would
        silently drop entries (each device consumes ONE block) — must
        raise."""
        mesh, cb, obj, ref_batch, ref, d = setup
        shard, y, off, wt = _problem()
        cb16 = build_column_sharded_batch(shard, y, 16, offsets=off, weights=wt)
        w = _put_model(mesh, np.zeros(cb16.padded_dim))
        with pytest.raises(ValueError, match="column blocks"):
            obj.value_and_gradient(w, cb16)

    def test_block_padding_lanes_stay_zero_through_solve(self, setup):
        mesh, cb, obj, ref_batch, ref, d = setup
        cfg = OptimizerConfig(max_iterations=25)
        r = solve(cfg, obj.bind(cb), init_column_sharded_coefficients(cb, mesh))
        np.testing.assert_array_equal(np.asarray(r.coefficients)[d:], 0.0)
