"""Sparse/giant-FE data path tests.

The reference keeps feature vectors sparse end to end
(AvroDataReader.scala:165-200) and scales fixed effects to "hundreds of
billions of coefficients" (README.md:77). These tests pin the TPU-native
flat-COO equivalent: numerical equivalence to the dense path at small d,
and a d=10⁷ fixed-effect solve that would be impossible densified
(n·d = 4·10¹¹ floats).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import LabeledPointBatch, summarize
from photon_ml_tpu.data.sparse_batch import (
    SparseLabeledPointBatch,
    SparseShard,
    sparse_margins,
    summarize_sparse,
)
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.types import TaskType


def _random_coo(n, d, nnz, seed, duplicates=False):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, size=nnz)
    cols = rng.integers(0, d, size=nnz)
    vals = rng.normal(size=nnz)
    if duplicates:
        # force some duplicate (row, col) pairs to pin the accumulation rule
        rows[: nnz // 8] = rows[nnz // 2 : nnz // 2 + nnz // 8]
        cols[: nnz // 8] = cols[nnz // 2 : nnz // 2 + nnz // 8]
    return rows, cols, vals


def _dense_from_coo(n, d, rows, cols, vals):
    x = np.zeros((n, d))
    np.add.at(x, (rows, cols), vals)
    return x


def _pair(n=64, d=12, nnz=300, seed=0, task=TaskType.LOGISTIC_REGRESSION):
    """(sparse batch, dense batch) over identical data with duplicates."""
    rng = np.random.default_rng(seed + 1)
    rows, cols, vals = _random_coo(n, d, nnz, seed, duplicates=True)
    x = _dense_from_coo(n, d, rows, cols, vals)
    if task == TaskType.LOGISTIC_REGRESSION:
        labels = (rng.random(n) < 0.5).astype(np.float64)
    else:
        labels = x @ rng.normal(size=d) + rng.normal(scale=0.1, size=n)
    offsets = rng.normal(scale=0.1, size=n)
    weights = rng.uniform(0.5, 2.0, size=n)
    sb = SparseLabeledPointBatch.from_coo(
        rows, cols, vals, labels, dim=d, offsets=offsets, weights=weights,
        dtype=np.float64,
    )
    db = LabeledPointBatch(
        features=jnp.asarray(x), labels=jnp.asarray(labels),
        offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
    )
    return sb, db


class TestSparseBatch:
    def test_margins_match_dense(self):
        sb, db = _pair()
        w = jnp.asarray(np.random.default_rng(2).normal(size=12))
        np.testing.assert_allclose(
            np.asarray(sparse_margins(sb, w)),
            np.asarray(db.features @ w + db.offsets),
            rtol=1e-10,
        )

    def test_nnz_padding_is_inert(self):
        """Flat-COO entry padding contributes nothing (ell=False isolates
        the flat layout; the batch's .values hold ONLY the overflow tail
        when the ELL view is on)."""
        rows, cols, vals = _random_coo(64, 12, 300, 0, duplicates=True)
        labels = np.random.default_rng(1).random(64)
        common = dict(dim=12, dtype=np.float64, ell=False)
        sb = SparseLabeledPointBatch.from_coo(rows, cols, vals, labels, **common)
        padded = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, pad_nnz_to=sb.nnz + 57, **common
        )
        assert padded.nnz == sb.nnz + 57
        w = jnp.asarray(np.random.default_rng(3).normal(size=sb.dim))
        np.testing.assert_allclose(
            np.asarray(sparse_margins(padded, w)),
            np.asarray(sparse_margins(sb, w)),
            rtol=1e-12,
        )

    def test_ell_view_matches_flat_and_dense(self):
        """The default ELL view (incl. overflow tail at a forced tiny
        width) computes identical margins/column-sums to flat COO."""
        from photon_ml_tpu.data.sparse_batch import sparse_column_sum

        rows, cols, vals = _random_coo(64, 12, 300, 5, duplicates=True)
        labels = np.random.default_rng(1).random(64)
        flat = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=12, dtype=np.float64, ell=False
        )
        w = jnp.asarray(np.random.default_rng(3).normal(size=12))
        rw = jnp.asarray(np.random.default_rng(4).uniform(0.5, 2.0, size=64))
        for ell in ("auto", 2):  # 2 forces a large overflow tail
            eb = SparseLabeledPointBatch.from_coo(
                rows, cols, vals, labels, dim=12, dtype=np.float64, ell=ell
            )
            assert eb.has_ell_view
            if ell == 2:
                assert eb.values.shape[0] > 0  # tail exercised
            np.testing.assert_allclose(
                np.asarray(sparse_margins(eb, w)),
                np.asarray(sparse_margins(flat, w)), rtol=1e-12,
            )
            for sq in (False, True):
                np.testing.assert_allclose(
                    np.asarray(sparse_column_sum(eb, rw, square_values=sq)),
                    np.asarray(sparse_column_sum(flat, rw, square_values=sq)),
                    rtol=1e-12,
                )

    @pytest.mark.parametrize("task", [
        TaskType.LOGISTIC_REGRESSION, TaskType.POISSON_REGRESSION,
    ])
    def test_tiered_ell_view_matches_dense(self, task):
        """An auto-built ELL view over rows of 1 to 60 entries (and a few of
        200) is a list of width tiers; margins, value and gradient (autodiff
        through the tiers' gathers and the adds at their row ids), the
        Hessian-vector product and the Hessian diagonal match the DENSE
        matrix of the same entries."""
        rng = np.random.default_rng(17)
        n, d = 3000, 256
        counts = 1 + (rng.random(n) ** 2 * 60).astype(np.int64)
        counts[:8] = 200
        rows = np.repeat(np.arange(n), counts)
        cols = np.concatenate(
            [rng.choice(d, size=c, replace=False) for c in counts]
        )
        vals = rng.normal(size=len(rows))
        labels = (rng.random(n) < 0.5).astype(np.float64)
        x = np.zeros((n, d))
        x[rows, cols] = vals
        dense = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(labels),
            offsets=jnp.zeros(n), weights=jnp.ones(n),
        )
        tiered = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float64
        )
        assert len(tiered.ell_tiers) >= 2 and tiered.nnz > 0
        slots = tiered.ell_vals.size + sum(t.vals.size for t in tiered.ell_tiers)
        one_block = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float64,
            ell=int(counts.max()),
        )
        assert slots < one_block.ell_vals.size  # the slots follow the counts
        loss = loss_for_task(task)
        so = SparseGLMObjective(loss, l2_weight=0.2)
        do = GLMObjective(loss, l2_weight=0.2)
        w = jnp.asarray(rng.normal(scale=0.05, size=d))
        v = jnp.asarray(rng.normal(size=d))
        np.testing.assert_allclose(
            np.asarray(sparse_margins(tiered, w)), x @ np.asarray(w),
            rtol=1e-10, atol=1e-12,
        )
        sv, sg = so.value_and_gradient(w, tiered)
        dv, dg = do.value_and_gradient(w, dense)
        np.testing.assert_allclose(float(sv), float(dv), rtol=1e-10)
        np.testing.assert_allclose(np.asarray(sg), np.asarray(dg),
                                   rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(
            np.asarray(so.hessian_vector(w, v, tiered)),
            np.asarray(do.hessian_vector(w, v, dense)), rtol=1e-8, atol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(so.hessian_diagonal(w, tiered)),
            np.asarray(do.hessian_diagonal(w, dense)), rtol=1e-8, atol=1e-10,
        )

    def test_out_of_range_indices_rejected(self):
        with pytest.raises(ValueError, match="dim"):
            SparseLabeledPointBatch.from_coo(
                [0], [5], [1.0], [1.0], dim=5
            )

    def test_negative_indices_rejected(self):
        with pytest.raises(ValueError, match="negative"):
            SparseLabeledPointBatch.from_coo(
                [-1], [0], [1.0], [1.0], dim=3
            )
        with pytest.raises(ValueError, match="negative"):
            SparseShard(
                rows=np.array([0]), cols=np.array([-2]),
                vals=np.array([1.0]), num_samples=1, feature_dim=3,
            )

    def test_dim_beyond_int32_rejected(self):
        # device indices are int32; a silent wrap would corrupt gathers at
        # exactly the giant-d scale this layer exists for
        with pytest.raises(ValueError, match="int32"):
            SparseLabeledPointBatch.from_coo(
                [0], [0], [1.0], [1.0], dim=2**31
            )
        with pytest.raises(ValueError, match="int32"):
            SparseShard(
                rows=np.array([0]), cols=np.array([0]),
                vals=np.array([1.0]), num_samples=1, feature_dim=2**31,
            )

    def test_validation_failures_aggregate(self):
        # sparse NaN + bad logistic labels must surface in ONE report
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            validate_game_dataset,
        )

        shard = SparseShard(
            rows=np.array([0, 1]), cols=np.array([0, 1]),
            vals=np.array([1.0, np.nan]), num_samples=2, feature_dim=3,
        )
        ds = build_game_dataset(
            labels=np.array([0.0, 7.0]), feature_shards={"g": shard}
        )
        with pytest.raises(DataValidationError) as e:
            validate_game_dataset(ds, TaskType.LOGISTIC_REGRESSION)
        assert "NaN" in str(e.value) and "binary labels" in str(e.value)

    def test_summarize_matches_dense(self):
        # duplicates included: they must accumulate into one cell before
        # any squaring/extremum, exactly like the dense scatter
        n, d = 40, 7
        rows, cols, vals = _random_coo(n, d, 120, seed=4, duplicates=True)
        weights = np.random.default_rng(5).uniform(0.5, 2.0, size=n)
        x = _dense_from_coo(n, d, rows, cols, vals)
        want = summarize(x, weights)
        got = summarize_sparse(rows, cols, vals, n=n, dim=d, weights=weights)
        for key in ("mean", "variance", "max", "min", "max_magnitude",
                    "norm_l1", "norm_l2", "num_nonzeros"):
            np.testing.assert_allclose(got[key], want[key], rtol=1e-9,
                                       atol=1e-12, err_msg=key)

    def test_padding_keeps_row_ids_sorted(self):
        sb = SparseLabeledPointBatch.from_coo(
            [0, 2, 1], [1, 0, 2], [1.0, 2.0, 3.0], [0.0, 1.0, 0.0],
            dim=3, pad_nnz_to=8,
        )
        ids = np.asarray(sb.row_ids)
        assert np.all(np.diff(ids) >= 0)  # indices_are_sorted promise
        assert np.all(np.asarray(sb.values)[3:] == 0.0)

    def test_validator_checks_sparse_values(self):
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            DataValidationType,
            validate_game_dataset,
        )

        def dataset(vals):
            shard = SparseShard(
                rows=np.array([0, 1]), cols=np.array([0, 1]),
                vals=np.asarray(vals), num_samples=2, feature_dim=3,
            )
            return build_game_dataset(
                labels=np.zeros(2), feature_shards={"g": shard}
            )

        validate_game_dataset(
            dataset([1.0, 2.0]), TaskType.LINEAR_REGRESSION,
            DataValidationType.VALIDATE_FULL,
        )
        with pytest.raises(DataValidationError, match="NaN"):
            validate_game_dataset(
                dataset([1.0, np.nan]), TaskType.LINEAR_REGRESSION,
                DataValidationType.VALIDATE_FULL,
            )


class TestSparseObjective:
    @pytest.mark.parametrize("task", [
        TaskType.LOGISTIC_REGRESSION,
        TaskType.LINEAR_REGRESSION,
        TaskType.POISSON_REGRESSION,
    ])
    def test_value_and_gradient_match_dense(self, task):
        sb, db = _pair(task=task, seed=7)
        loss = loss_for_task(task)
        so = SparseGLMObjective(loss, l2_weight=0.3)
        do = GLMObjective(loss, l2_weight=0.3)
        w = jnp.asarray(np.random.default_rng(8).normal(scale=0.1, size=sb.dim))
        sv, sg = so.value_and_gradient(w, sb)
        dv, dg = do.value_and_gradient(w, db)
        np.testing.assert_allclose(float(sv), float(dv), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(sg), np.asarray(dg), rtol=1e-8)

    def test_hessian_vector_matches_dense(self):
        sb, db = _pair(seed=9)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        so, do = SparseGLMObjective(loss, l2_weight=0.1), GLMObjective(loss, l2_weight=0.1)
        rng = np.random.default_rng(10)
        w = jnp.asarray(rng.normal(scale=0.1, size=sb.dim))
        v = jnp.asarray(rng.normal(size=sb.dim))
        np.testing.assert_allclose(
            np.asarray(so.hessian_vector(w, v, sb)),
            np.asarray(do.hessian_vector(w, v, db)),
            rtol=1e-8,
        )

    def test_hessian_diagonal_matches_dense(self):
        sb, db = _pair(seed=11)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        so, do = SparseGLMObjective(loss, l2_weight=0.2), GLMObjective(loss, l2_weight=0.2)
        w = jnp.asarray(np.random.default_rng(12).normal(scale=0.1, size=sb.dim))
        np.testing.assert_allclose(
            np.asarray(so.hessian_diagonal(w, sb)),
            np.asarray(do.hessian_diagonal(w, db)),
            rtol=1e-8,
        )

    def test_normalization_algebra_matches_dense(self):
        # factors + shifts (standardization): the margin-shift algebra must
        # keep the sparse data sparse yet agree with the dense transform
        sb, db = _pair(seed=13)
        rng = np.random.default_rng(14)
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, size=sb.dim)),
            shifts=jnp.asarray(rng.normal(scale=0.2, size=sb.dim)),
        )
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        so = SparseGLMObjective(loss, l2_weight=0.1, normalization=norm)
        do = GLMObjective(loss, l2_weight=0.1, normalization=norm)
        w = jnp.asarray(rng.normal(scale=0.1, size=sb.dim))
        sv, sg = so.value_and_gradient(w, sb)
        dv, dg = do.value_and_gradient(w, db)
        np.testing.assert_allclose(float(sv), float(dv), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(sg), np.asarray(dg), rtol=1e-7)
        np.testing.assert_allclose(
            np.asarray(so.hessian_diagonal(w, sb)),
            np.asarray(do.hessian_diagonal(w, db)),
            rtol=1e-7,
        )


class TestSparseTraining:
    @pytest.mark.parametrize("opt_type", ["LBFGS", "TRON"])
    def test_train_glm_matches_dense(self, opt_type):
        from photon_ml_tpu.estimators import train_glm
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

        sb, db = _pair(n=200, d=10, nnz=1500, seed=15)
        kw = dict(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType[opt_type], max_iterations=60,
            ),
            regularization_weights=[1.0],
            compute_variance=True,  # auto resolves to diagonal on sparse
        )
        ms = train_glm(sb, TaskType.LOGISTIC_REGRESSION, **kw)
        md = train_glm(db, TaskType.LOGISTIC_REGRESSION, **kw)
        np.testing.assert_allclose(
            np.asarray(ms[1.0].coefficients.means),
            np.asarray(md[1.0].coefficients.means),
            atol=2e-5,
        )
        assert ms[1.0].coefficients.variances is not None

    def test_train_glm_grid_matches_dense(self):
        from photon_ml_tpu.estimators import train_glm_grid

        sb, db = _pair(n=200, d=10, nnz=1500, seed=16)
        lams = [0.1, 1.0]
        gs = train_glm_grid(sb, TaskType.LOGISTIC_REGRESSION,
                            regularization_weights=lams)
        gd = train_glm_grid(db, TaskType.LOGISTIC_REGRESSION,
                            regularization_weights=lams)
        for lam in lams:
            np.testing.assert_allclose(
                np.asarray(gs[lam].coefficients.means),
                np.asarray(gd[lam].coefficients.means),
                atol=2e-5,
            )

    def test_explicit_full_variance_raises_on_sparse(self):
        from photon_ml_tpu.estimators import train_glm

        sb, _ = _pair(n=50, d=5, nnz=200, seed=17)
        with pytest.raises(ValueError, match="dense Hessian"):
            train_glm(sb, TaskType.LOGISTIC_REGRESSION,
                      compute_variance=True, variance_mode="full")

    def test_giant_dimension_fixed_effect(self):
        """The VERDICT #3 gate: d=10⁷ FE trains single-chip with no [n, d]
        anywhere. Dense would need n·d = 3·10¹⁰ floats (120 GB f32)."""
        from photon_ml_tpu.estimators import train_glm
        from photon_ml_tpu.optim.optimizer import OptimizerConfig

        n, d = 3000, 10_000_000
        noise_per_row, signal_per_row = 8, 4
        rng = np.random.default_rng(18)
        # each sample: a few signal columns (drawn from a small recurring
        # support, so each support column is observed ~n·4/64 ≈ 190 times —
        # a learnable density) plus noise columns scattered over all of d
        # (each observed ~once — unlearnable filler, like real long tails)
        support = rng.choice(d, size=64, replace=False)
        w_true_support = rng.normal(size=64) * 3.0
        sig_pick = rng.integers(0, 64, size=(n, signal_per_row))
        sig_vals = rng.normal(size=(n, signal_per_row))
        noise_cols = rng.integers(0, d, size=(n, noise_per_row))
        noise_vals = rng.normal(size=(n, noise_per_row))
        rows = np.repeat(np.arange(n), noise_per_row + signal_per_row)
        cols = np.concatenate([support[sig_pick], noise_cols], axis=1).ravel()
        vals = np.concatenate([sig_vals, noise_vals], axis=1).ravel()
        margins = (sig_vals * w_true_support[sig_pick]).sum(axis=1)
        labels = (margins + 0.1 * rng.normal(size=n) > 0).astype(np.float64)

        sb = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float32
        )
        models = train_glm(
            sb, TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerConfig(max_iterations=15),
            regularization_weights=[0.1],
        )
        w = models[0.1].coefficients.means
        assert w.shape == (d,)
        assert bool(jnp.all(jnp.isfinite(w)))
        # training signal reached the planted support: its learned mass
        # dominates other *observed* columns' (unobserved columns are
        # exactly 0 under pure L2, so compare against real competitors)
        learned = np.asarray(w)
        observed_noise = np.setdiff1d(np.unique(noise_cols), support)
        assert np.abs(learned[support]).mean() > 5 * np.abs(
            learned[observed_noise]
        ).mean()
        # learned support weights track the planted truth
        corr = np.corrcoef(learned[support], w_true_support)[0, 1]
        assert corr > 0.8, corr


class TestShardIntegration:
    def _sparse_records(self, n=300, d=6, seed=19):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + rng.normal(scale=0.1, size=n)
        users = [f"u{rng.integers(0, 8)}" for _ in range(n)]
        records = [
            {
                "uid": str(i),
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(d)
                ],
                "weight": 1.0,
                "offset": 0.0,
                "foldId": None,
                "metadataMap": {"userId": users[i]},
            }
            for i in range(n)
        ]
        return records, x, y

    def test_reader_builds_sparse_shard_with_intercept(self):
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            build_index_maps,
            records_to_game_dataset,
        )

        records, x, _ = self._sparse_records()
        cfgs = {
            "g": FeatureShardConfiguration(
                feature_bags=("features",), has_intercept=True, sparse=True
            )
        }
        imaps = build_index_maps(records, cfgs)
        result = records_to_game_dataset(
            records, cfgs, imaps, random_effect_id_columns=("userId",),
            dtype=np.float64,
        )
        shard = result.dataset.feature_shards["g"]
        assert isinstance(shard, SparseShard)
        assert shard.shape == (300, imaps["g"].size)
        # intercept present as explicit entries
        assert "g" in result.intercept_indices
        ii = result.intercept_indices["g"]
        ones = shard.vals[shard.cols == ii]
        assert len(ones) == 300 and np.all(ones == 1.0)

    def test_sparse_fe_coordinate_and_scoring_match_dense(self):
        from photon_ml_tpu.algorithm.coordinates import (
            CoordinateOptimizationConfig,
            FixedEffectCoordinate,
        )
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.optim.optimizer import OptimizerConfig

        rng = np.random.default_rng(20)
        n, d = 250, 7
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + rng.normal(scale=0.1, size=n)
        rows, cols = np.nonzero(x)
        shard = SparseShard(
            rows=rows, cols=cols, vals=x[rows, cols].astype(np.float64),
            num_samples=n, feature_dim=d,
        )
        ds_sparse = build_game_dataset(
            labels=y, feature_shards={"g": shard}, dtype=np.float64
        )
        ds_dense = build_game_dataset(
            labels=y, feature_shards={"g": x}, dtype=np.float64
        )
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=50), l2_weight=1.0,
        )
        results = {}
        for name, ds in (("sparse", ds_sparse), ("dense", ds_dense)):
            coord = FixedEffectCoordinate(
                coordinate_id="fe", dataset=ds, feature_shard_id="g",
                task=TaskType.LINEAR_REGRESSION, config=cfg,
            )
            model, _ = coord.update_model(coord.initial_model())
            results[name] = (model, np.asarray(coord.score(model)))
        np.testing.assert_allclose(
            np.asarray(results["sparse"][0].glm.coefficients.means),
            np.asarray(results["dense"][0].glm.coefficients.means),
            atol=1e-6,
        )
        np.testing.assert_allclose(
            results["sparse"][1], results["dense"][1], atol=1e-6
        )

    def test_sparse_fe_full_variance_fails_before_solve(self):
        from photon_ml_tpu.algorithm.coordinates import (
            CoordinateOptimizationConfig,
            FixedEffectCoordinate,
        )
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.optim.optimizer import OptimizerConfig

        shard = SparseShard(
            rows=np.array([0, 1]), cols=np.array([0, 1]),
            vals=np.array([1.0, 2.0]), num_samples=2, feature_dim=3,
        )
        ds = build_game_dataset(labels=np.zeros(2), feature_shards={"g": shard})
        coord = FixedEffectCoordinate(
            coordinate_id="fe", dataset=ds, feature_shard_id="g",
            task=TaskType.LINEAR_REGRESSION,
            config=CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=5),
                compute_variance=True, variance_mode="full",
            ),
        )
        with pytest.raises(ValueError, match="dense Hessian"):
            coord.update_model(coord.initial_model())

    def test_random_effect_on_sparse_shard_builds_compact(self):
        """r3: sparse RE shards build the compact per-entity representation
        instead of raising (full coverage in test_sparse_random_effects)."""
        from photon_ml_tpu.data.game_data import (
            build_game_dataset,
            build_random_effect_dataset,
        )

        rng = np.random.default_rng(21)
        n, d = 60, 5
        x = rng.normal(size=(n, d))
        rows, cols = np.nonzero(x)
        shard = SparseShard(
            rows=rows, cols=cols, vals=x[rows, cols],
            num_samples=n, feature_dim=d,
        )
        ds = build_game_dataset(
            labels=np.zeros(n), feature_shards={"g": shard},
            entity_keys={"user": np.array([f"u{i % 4}" for i in range(n)])},
        )
        red = build_random_effect_dataset(ds, "user", "g", bucket_sizes=(32,))
        assert red.is_compact and red.num_entities == 4

    def test_driver_end_to_end_sparse_shard(self, tmp_path):
        from photon_ml_tpu.cli import game_training_driver
        from photon_ml_tpu.io import avro as avro_io
        from photon_ml_tpu.io import photon_schemas as schemas

        records, _, _ = self._sparse_records()
        data_dir = tmp_path / "train"
        os.makedirs(data_dir)
        avro_io.write_container(
            str(data_dir / "part-00000.avro"),
            schemas.TRAINING_EXAMPLE_AVRO, records,
        )
        out = tmp_path / "out"
        summary = game_training_driver.main([
            "--input-data-path", str(data_dir),
            "--root-output-dir", str(out),
            "--feature-shard-configurations",
            "name=g,feature.bags=features,intercept=true,sparse=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=g,reg.weights=1.0,max.iter=40",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ])
        assert summary["num_configurations"] == 1
        assert (out / "best" / "fixed-effect" / "fe" / "id-info").exists()
        assert (out / "feature-stats" / "g" / "part-00000.avro").exists()


class TestShardedCoefficients:
    def test_model_axis_sharded_solve_matches_replicated(self):
        """Giant-FE mesh story: the coefficient vector shards over "model";
        the gather/scatter lower to collectives under jit and the solve
        matches the unsharded result."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        sb, _ = _pair(n=128, d=16, nnz=800, seed=22)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        so = SparseGLMObjective(loss, l2_weight=0.5)
        w = jnp.asarray(np.random.default_rng(23).normal(scale=0.1, size=16))
        want_v, want_g = so.value_and_gradient(w, sb)

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("model",))
        w_sharded = jax.device_put(w, NamedSharding(mesh, P("model")))
        got_v, got_g = jax.jit(so.value_and_gradient)(w_sharded, sb)
        np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=1e-6
        )
