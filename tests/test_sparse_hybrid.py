"""Hybrid dense-head / sparse-tail layout tests (ISSUE 5).

The reference keeps name-term feature bags sparse end to end
(AvroDataReader.scala:165-200); those bags are power-law distributed, so a
small hot-column head carries most nonzeros. These tests pin the hybrid
view's contract: every sparse view of the same shard (flat COO, ELL,
hybrid) computes identical value/gradient/hessian_vector; hybrid OFF is bitwise-identical to the pre-existing
layouts; the pad/offsets lifecycle keeps all views in lockstep; the
column-sharded hot head is sharding-invariant (1-device == 8-device); and
the CLI grammar + partitioned-io guard behave.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.sparse_batch import (
    HybridPolicy,
    SparseLabeledPointBatch,
    SparseShard,
    resolve_hybrid_policy,
    sparse_column_sum,
    sparse_margins,
)
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.types import TaskType


def _skewed_coo(n, d, nnz, seed, gamma=6.0):
    """Power-law columns (the regime the hybrid layout targets) with forced
    duplicate (row, col) pairs to pin the accumulation rule."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, size=nnz)
    cols = (rng.random(nnz) ** gamma * d).astype(np.int64)
    vals = rng.normal(size=nnz)
    rows[: nnz // 8] = rows[nnz // 2 : nnz // 2 + nnz // 8]
    cols[: nnz // 8] = cols[nnz // 2 : nnz // 2 + nnz // 8]
    return rows, cols, vals


def _data(n=80, d=40, nnz=600, seed=0):
    rng = np.random.default_rng(seed + 1)
    rows, cols, vals = _skewed_coo(n, d, nnz, seed)
    labels = (rng.random(n) < 0.5).astype(np.float64)
    offsets = rng.normal(scale=0.1, size=n)
    weights = rng.uniform(0.5, 2.0, size=n)
    return rows, cols, vals, labels, offsets, weights


def _views(seed=0, n=80, d=40, nnz=600):
    """Every view of the same shard, keyed by name."""
    rows, cols, vals, labels, offsets, weights = _data(n, d, nnz, seed)
    common = dict(dim=d, offsets=offsets, weights=weights, dtype=np.float64)
    build = lambda **kw: SparseLabeledPointBatch.from_coo(
        rows, cols, vals, labels, **common, **kw
    )
    return {
        "flat": build(ell=False),
        "ell": build(),
        "ell_narrow": build(ell=2),  # forces a large overflow tail
        "hybrid": build(hybrid=HybridPolicy(coverage=0.6, pad_multiple=4)),
        "hybrid_budget": build(
            hybrid=HybridPolicy(hot_cols=3, pad_multiple=8)
        ),
        "hybrid_flat_tail": build(
            ell=False, hybrid=HybridPolicy(coverage=0.5, pad_multiple=4)
        ),
    }


def _assert_same_leaves(got, want):
    """Two batches equal leaf for leaf: same tree, dtypes, shapes, bits."""
    got_leaves, got_tree = jax.tree_util.tree_flatten_with_path(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten_with_path(want)
    assert got_tree == want_tree
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


class TestViewContract:
    """Flat-COO vs ELL vs hybrid views of the same shard
    agree on value/gradient/hessian_vector (ISSUE 5 property test)."""

    @pytest.mark.parametrize("seed", [0, 7, 23])
    @pytest.mark.parametrize("task", [
        TaskType.LOGISTIC_REGRESSION, TaskType.POISSON_REGRESSION,
    ])
    def test_value_gradient_hessian_vector_agree(self, seed, task):
        views = _views(seed=seed)
        so = SparseGLMObjective(loss_for_task(task), l2_weight=0.3)
        d = views["flat"].dim
        rng = np.random.default_rng(seed + 100)
        w = jnp.asarray(rng.normal(scale=0.1, size=d))
        v = jnp.asarray(rng.normal(size=d))
        want_val, want_grad = so.value_and_gradient(w, views["flat"])
        want_hv = so.hessian_vector(w, v, views["flat"])
        want_diag = so.hessian_diagonal(w, views["flat"])
        for name, batch in views.items():
            val, grad = so.value_and_gradient(w, batch)
            np.testing.assert_allclose(
                float(val), float(want_val), rtol=1e-11, err_msg=name
            )
            np.testing.assert_allclose(
                np.asarray(grad), np.asarray(want_grad),
                rtol=1e-9, atol=1e-12, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(so.hessian_vector(w, v, batch)),
                np.asarray(want_hv), rtol=1e-8, atol=1e-12, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(so.hessian_diagonal(w, batch)),
                np.asarray(want_diag), rtol=1e-8, atol=1e-12, err_msg=name,
            )

    def test_hybrid_view_shapes(self):
        views = _views()
        hyb = views["hybrid"]
        assert hyb.has_hybrid_view and hyb.has_ell_view
        k_pad = hyb.hot_vals.shape[1]
        assert k_pad % 4 == 0  # lane-friendly padding
        assert hyb.hot_col_ids.shape == (k_pad,)
        # the head actually absorbed entries: the tail is strictly smaller
        # than the full ELL view's footprint
        assert hyb.ell_vals.shape[1] <= views["ell"].ell_vals.shape[1]
        budget = views["hybrid_budget"]
        assert budget.hot_vals.shape[1] == 8  # 3 hot cols padded to 8
        # pad head ids repeat the LAST hot id over all-zero columns
        ids = np.asarray(budget.hot_col_ids)
        assert np.all(ids[3:] == ids[2])
        assert np.all(np.asarray(budget.hot_vals)[:, 3:] == 0.0)

    def test_margins_and_column_sums_agree(self):
        views = _views(seed=3)
        rng = np.random.default_rng(4)
        d, n = views["flat"].dim, views["flat"].num_samples
        w = jnp.asarray(rng.normal(size=d))
        rw = jnp.asarray(rng.uniform(0.5, 2.0, size=n))
        want_m = np.asarray(sparse_margins(views["flat"], w))
        for name, batch in views.items():
            np.testing.assert_allclose(
                np.asarray(sparse_margins(batch, w)), want_m,
                rtol=1e-11, err_msg=name,
            )
            for sq in (False, True):
                np.testing.assert_allclose(
                    np.asarray(sparse_column_sum(batch, rw, sq)),
                    np.asarray(sparse_column_sum(views["flat"], rw, sq)),
                    rtol=1e-9, atol=1e-12, err_msg=f"{name} sq={sq}",
                )

    def test_normalization_algebra_agrees(self):
        """Factors + shifts through the fused hybrid path; with shifts the
        Hv falls back to autodiff and must still agree."""
        views = _views(seed=5)
        rng = np.random.default_rng(6)
        d = views["flat"].dim
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d)),
            shifts=jnp.asarray(rng.normal(scale=0.2, size=d)),
        )
        so = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.2,
            normalization=norm,
        )
        w = jnp.asarray(rng.normal(scale=0.1, size=d))
        v = jnp.asarray(rng.normal(size=d))
        want_v, want_g = so.value_and_gradient(w, views["flat"])
        for name in ("hybrid", "hybrid_budget", "hybrid_flat_tail"):
            val, grad = so.value_and_gradient(w, views[name])
            np.testing.assert_allclose(float(val), float(want_v), rtol=1e-11)
            np.testing.assert_allclose(
                np.asarray(grad), np.asarray(want_g),
                rtol=1e-9, atol=1e-12, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(so.hessian_vector(w, v, views[name])),
                np.asarray(so.hessian_vector(w, v, views["flat"])),
                rtol=1e-8, atol=1e-12, err_msg=name,
            )
        # factors only: the split Hv path (no fallback) still agrees
        so_f = SparseGLMObjective(
            loss_for_task(TaskType.POISSON_REGRESSION), l2_weight=0.7,
            normalization=NormalizationContext(
                factors=norm.factors, shifts=None
            ),
        )
        np.testing.assert_allclose(
            np.asarray(so_f.hessian_vector(w, v, views["hybrid"])),
            np.asarray(so_f.hessian_vector(w, v, views["flat"])),
            rtol=1e-8, atol=1e-12,
        )

    def test_matches_dense(self):
        rows, cols, vals, labels, offsets, weights = _data(seed=9)
        n, d = len(labels), 40
        x = np.zeros((n, d))
        np.add.at(x, (rows, cols), vals)
        db = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(labels),
            offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
        )
        hyb = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, offsets=offsets,
            weights=weights, dtype=np.float64,
            hybrid=HybridPolicy(coverage=0.7, pad_multiple=4),
        )
        from photon_ml_tpu.ops.objective import GLMObjective

        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        so = SparseGLMObjective(loss, l2_weight=0.3)
        do = GLMObjective(loss, l2_weight=0.3)
        w = jnp.asarray(np.random.default_rng(10).normal(scale=0.1, size=d))
        sv, sg = so.value_and_gradient(w, hyb)
        dv, dg = do.value_and_gradient(w, db)
        np.testing.assert_allclose(float(sv), float(dv), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(sg), np.asarray(dg), rtol=1e-8)


class TestHybridOffBitwise:
    """``hybrid`` off must be bitwise-identical to the pre-existing
    ELL/flat paths (ISSUE 5 acceptance)."""

    @pytest.mark.parametrize("off", [None, False])
    def test_builder_arrays_identical(self, off):
        rows, cols, vals, labels, offsets, weights = _data(seed=11)
        common = dict(
            dim=40, offsets=offsets, weights=weights, dtype=np.float64
        )
        base = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, **common
        )
        off_batch = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, hybrid=off, **common
        )
        assert not off_batch.has_hybrid_view
        assert off_batch.hot_vals is None and off_batch.hot_col_ids is None
        _assert_same_leaves(off_batch, base)

    def test_objective_outputs_bitwise_identical(self):
        rows, cols, vals, labels, offsets, weights = _data(seed=12)
        common = dict(
            dim=40, offsets=offsets, weights=weights, dtype=np.float64
        )
        base = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, **common
        )
        off_batch = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, hybrid=False, **common
        )
        so = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.4
        )
        w = jnp.asarray(np.random.default_rng(13).normal(size=40))
        v1, g1 = jax.jit(so.value_and_gradient)(w, base)
        v2, g2 = jax.jit(so.value_and_gradient)(w, off_batch)
        assert float(v1) == float(v2)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    def test_shard_without_policy_stays_plain(self):
        rows, cols, vals, labels, _, _ = _data(seed=14)
        shard = SparseShard(
            rows=rows, cols=cols, vals=vals, num_samples=80, feature_dim=40
        )
        b = SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros(80), np.ones(80)
        )
        assert not b.has_hybrid_view


class TestLifecycleLockstep:
    """pad_nnz -> with_offsets -> add_scores_to_offsets keeps every view in
    lockstep: pads are weight-0 / value-0 / clamped ids and all views still
    agree after the full residual-update cycle."""

    @pytest.mark.parametrize("name", [
        "flat", "ell", "ell_narrow", "hybrid", "hybrid_flat_tail",
    ])
    def test_round_trip_keeps_views_in_lockstep(self, name):
        views = _views(seed=17)
        batch = views[name]
        rng = np.random.default_rng(18)
        n, d = batch.num_samples, batch.dim
        scores = jnp.asarray(rng.normal(scale=0.1, size=n))
        offsets2 = jnp.asarray(rng.normal(scale=0.1, size=n))

        def cycle(b):
            padded = b.pad_nnz(b.nnz + 13)
            assert padded.nnz == b.nnz + 13
            # hybrid head and ELL block are not on the entry axis: lockstep
            # means they are UNTOUCHED while the flat tail pads inertly
            if b.has_hybrid_view:
                np.testing.assert_array_equal(
                    np.asarray(padded.hot_vals), np.asarray(b.hot_vals)
                )
                np.testing.assert_array_equal(
                    np.asarray(padded.hot_col_ids), np.asarray(b.hot_col_ids)
                )
            if b.has_ell_view:
                np.testing.assert_array_equal(
                    np.asarray(padded.ell_vals), np.asarray(b.ell_vals)
                )
            assert np.all(np.asarray(padded.values)[b.nnz:] == 0.0)
            assert np.all(np.diff(np.asarray(padded.row_ids)) >= 0)
            return padded.with_offsets(offsets2).add_scores_to_offsets(scores)

        got = cycle(batch)
        want = cycle(views["flat"])
        np.testing.assert_allclose(
            np.asarray(got.offsets), np.asarray(want.offsets), rtol=1e-12
        )
        so = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.2
        )
        w = jnp.asarray(rng.normal(scale=0.1, size=d))
        v1, g1 = so.value_and_gradient(w, got)
        v2, g2 = so.value_and_gradient(w, want)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-11)
        np.testing.assert_allclose(
            np.asarray(g1), np.asarray(g2), rtol=1e-9, atol=1e-12
        )


class TestTraining:
    def test_train_glm_hybrid_matches_dense(self):
        from photon_ml_tpu.estimators import train_glm
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

        rng = np.random.default_rng(20)
        n, d = 200, 10
        rows, cols, vals = _skewed_coo(n, d, 1500, seed=21, gamma=3.0)
        x = np.zeros((n, d))
        np.add.at(x, (rows, cols), vals)
        labels = (x @ rng.normal(size=d) > 0).astype(np.float64)
        hyb = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float64,
            hybrid=HybridPolicy(hot_cols=3, pad_multiple=2),
        )
        db = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(labels),
            offsets=jnp.zeros(n), weights=jnp.ones(n),
        )
        for opt in ("LBFGS", "TRON"):
            kw = dict(
                optimizer=OptimizerConfig(
                    optimizer_type=OptimizerType[opt], max_iterations=60
                ),
                regularization_weights=[1.0],
            )
            ms = train_glm(hyb, TaskType.LOGISTIC_REGRESSION, **kw)
            md = train_glm(db, TaskType.LOGISTIC_REGRESSION, **kw)
            np.testing.assert_allclose(
                np.asarray(ms[1.0].coefficients.means),
                np.asarray(md[1.0].coefficients.means),
                atol=2e-5, err_msg=opt,
            )


class TestColumnShardedHybrid:
    def _shard(self, seed=30, n=96, d=48, nnz=700):
        rows, cols, vals = _skewed_coo(n, d, nnz, seed)
        labels = (np.random.default_rng(seed).random(n) < 0.5).astype(
            np.float64
        )
        shard = SparseShard(
            rows=rows, cols=cols, vals=vals, num_samples=n, feature_dim=d,
            hybrid_policy=HybridPolicy(coverage=0.5, pad_multiple=4),
        )
        return shard, labels

    def test_sharding_invariance_1_vs_8_devices(self):
        """Hybrid path 1-device == 8-device on the virtual CPU mesh — the
        "model"-sharded tail AND the hot head (ISSUE 5 satellite)."""
        from jax.sharding import Mesh

        from photon_ml_tpu.parallel.column_sharded import (
            ColumnShardedGLMObjective,
            build_column_sharded_batch,
            shard_column_batch,
        )

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        shard, labels = self._shard()
        n, d = shard.shape
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        flat = SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros(n), np.ones(n), ell=False, hybrid=False
        )
        so = SparseGLMObjective(loss, l2_weight=0.4)
        rng = np.random.default_rng(31)
        w = jnp.asarray(rng.normal(scale=0.1, size=d))
        v = jnp.asarray(rng.normal(size=d))
        want_v, want_g = so.value_and_gradient(w, flat)
        want_hv = so.hessian_vector(w, v, flat)
        for num_devices in (1, 8):
            mesh = Mesh(
                np.asarray(jax.devices()[:num_devices]).reshape(num_devices),
                ("model",),
            )
            batch = build_column_sharded_batch(shard, labels, num_devices)
            assert batch.has_hot_head  # inherited from the shard's policy
            batch = shard_column_batch(batch, mesh)
            obj = ColumnShardedGLMObjective(loss, mesh, l2_weight=0.4)
            pad = batch.padded_dim
            wp = jnp.zeros(pad, dtype=w.dtype).at[:d].set(w)
            vp = jnp.zeros(pad, dtype=w.dtype).at[:d].set(v)
            val = obj.value(wp, batch)
            v2, g2 = obj.value_and_gradient(wp, batch)
            hv2 = obj.hessian_vector(wp, vp, batch)
            msg = f"devices={num_devices}"
            np.testing.assert_allclose(
                float(val), float(want_v), rtol=1e-10, err_msg=msg
            )
            np.testing.assert_allclose(float(v2), float(want_v), rtol=1e-10)
            np.testing.assert_allclose(
                np.asarray(g2)[:d], np.asarray(want_g),
                rtol=1e-9, atol=1e-12, err_msg=msg,
            )
            # padding coefficient lanes beyond dim stay untouched (zero grad
            # contribution from zero data, before L2)
            np.testing.assert_allclose(
                np.asarray(hv2)[:d], np.asarray(want_hv),
                rtol=1e-9, atol=1e-12, err_msg=msg,
            )

    def test_hybrid_off_column_sharded_identical(self):
        """hybrid=False on a policy-carrying shard forces the pre-existing
        layout — no hot head, same results."""
        from jax.sharding import Mesh

        from photon_ml_tpu.parallel.column_sharded import (
            ColumnShardedGLMObjective,
            build_column_sharded_batch,
            shard_column_batch,
        )

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        shard, labels = self._shard(seed=33)
        n, d = shard.shape
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("model",))
        off = build_column_sharded_batch(shard, labels, 8, hybrid=False)
        assert not off.has_hot_head
        on = build_column_sharded_batch(shard, labels, 8)
        assert on.has_hot_head
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        obj = ColumnShardedGLMObjective(loss, mesh, l2_weight=0.1)
        rng = np.random.default_rng(34)
        w_full = rng.normal(scale=0.1, size=d)
        results = []
        for batch in (off, on):
            batch = shard_column_batch(batch, mesh)
            wp = jnp.zeros(batch.padded_dim).at[:d].set(jnp.asarray(w_full))
            _, g = obj.value_and_gradient(wp, batch)
            results.append(np.asarray(g)[:d])
        np.testing.assert_allclose(
            results[0], results[1], rtol=1e-9, atol=1e-12
        )


def _tiered_coo(n=4000, d=300, seed=0):
    """Rows that hold 1 to 60 entries (skewed low) and a few of 150 to 200:
    counts spread enough that the width rule takes several tiers, with an
    overflow beyond the last. Unique (row, col) pairs, row-major."""
    rng = np.random.default_rng(seed)
    counts = 1 + (rng.random(n) ** 2 * 60).astype(np.int64)
    counts[rng.choice(n, size=12, replace=False)] = rng.integers(150, 201, 12)
    rows = np.repeat(np.arange(n), counts)
    cols = np.concatenate(
        [np.sort(rng.choice(d, size=c, replace=False)) for c in counts]
    )
    # a column law with a hot head, so a hybrid policy has something to take
    cols = (cols.astype(np.float64) ** 2 / d).astype(np.int64)
    keep = np.ones(len(rows), bool)
    keep[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
    rows, cols = rows[keep], cols[keep]
    vals = rng.normal(size=len(rows))
    labels = (rng.random(n) < 0.5).astype(np.float64)
    offsets = rng.normal(scale=0.1, size=n)
    weights = rng.uniform(0.5, 2.0, size=n)
    return rows, cols, vals, labels, offsets, weights


@functools.lru_cache(maxsize=None)
def _tiered_views():
    """flat / tiered ELL / tiered hybrid views of ``_tiered_coo``, built once."""
    rows, cols, vals, labels, offsets, weights = _tiered_coo()
    build = lambda **kw: SparseLabeledPointBatch.from_coo(
        rows, cols, vals, labels, dim=300, offsets=offsets,
        weights=weights, dtype=np.float64, **kw
    )
    return dict(
        flat=build(ell=False),
        ell=build(),
        hybrid=build(hybrid=HybridPolicy(hot_cols=8, pad_multiple=8,
                                         label="t_tiers")),
    )


def _tiered_norm(kind, d=300):
    rng = np.random.default_rng(77)
    factors = jnp.asarray(rng.uniform(0.5, 2.0, size=d))
    shifts = jnp.asarray(rng.normal(scale=0.2, size=d))
    return {
        "plain": None,
        "factors": NormalizationContext(factors=factors, shifts=None),
        "factors_shifts": NormalizationContext(factors=factors, shifts=shifts),
    }[kind]


def _view_entries(batch):
    """Every (row, col, value) a batch's ELL tiers and flat triple hold,
    pad slots (value 0) apart, row-major sorted."""
    blocks = [(np.arange(batch.num_samples), batch.ell_vals, batch.ell_cols)]
    # a further tier lies rows-minor: [width, n_k]
    blocks += [(np.asarray(t.row_ids), t.vals.T, t.cols.T)
               for t in batch.ell_tiers]
    r, c, v = [], [], []
    for row_ids, vals, cols in blocks:
        vals, cols = np.asarray(vals), np.asarray(cols)
        real = vals != 0.0
        r.append(np.broadcast_to(row_ids[:, None], vals.shape)[real])
        c.append(cols[real])
        v.append(vals[real])
    real = np.asarray(batch.values) != 0.0
    r.append(np.asarray(batch.row_ids)[real])
    c.append(np.asarray(batch.col_indices)[real])
    v.append(np.asarray(batch.values)[real])
    r, c, v = (np.concatenate(x) for x in (r, c, v))
    order = np.lexsort((c, r))
    return r[order], c[order], v[order]


class TestEllTiers:
    """The auto-built ELL view is a short list of width tiers read off the
    rows' counts (ISSUE 44): same entries, same sums, fewer slots."""

    def test_fixture_is_tiered_with_an_overflow(self):
        views = _tiered_views()
        for name in ("ell", "hybrid"):
            batch = views[name]
            assert len(batch.ell_tiers) >= 2, name
            assert batch.nnz > 0, name  # entries beyond the last width
            assert batch.ell_vals.shape[0] == batch.num_samples

    @pytest.mark.parametrize("view", ["ell", "hybrid"])
    @pytest.mark.parametrize("norm", ["plain", "factors", "factors_shifts"])
    @pytest.mark.parametrize("quantity", [
        "value_and_gradient", "hessian_vector", "hessian_diagonal",
    ])
    def test_objective_agrees_with_flat_coo(self, view, norm, quantity):
        views = _tiered_views()
        so = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.3,
            normalization=_tiered_norm(norm),
        )
        rng = np.random.default_rng(5)
        w = jnp.asarray(rng.normal(scale=0.1, size=300))
        v = jnp.asarray(rng.normal(size=300))

        def read(batch):
            if quantity == "value_and_gradient":
                # the flat view takes the autodiff path
                val, grad = so.value_and_gradient(w, batch)
                return np.concatenate([np.asarray(val)[None], np.asarray(grad)])
            if quantity == "hessian_vector":
                return np.asarray(so.hessian_vector(w, v, batch))
            return np.asarray(so.hessian_diagonal(w, batch))

        np.testing.assert_allclose(
            read(views[view]), read(views["flat"]), rtol=1e-9, atol=1e-11
        )

    @pytest.mark.parametrize("view", ["ell", "hybrid"])
    @pytest.mark.parametrize("square", [False, True])
    def test_product_and_column_sums_agree_with_flat_coo(self, view, square):
        from photon_ml_tpu.data.sparse_batch import sparse_product

        views = _tiered_views()
        rng = np.random.default_rng(6)
        w = jnp.asarray(rng.normal(size=300))
        rw = jnp.asarray(rng.uniform(0.5, 2.0, size=views["flat"].num_samples))
        np.testing.assert_allclose(
            np.asarray(sparse_product(views[view], w)),
            np.asarray(sparse_product(views["flat"], w)),
            rtol=1e-11, atol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(sparse_column_sum(views[view], rw, square)),
            np.asarray(sparse_column_sum(views["flat"], rw, square)),
            rtol=1e-10, atol=1e-12,
        )

    def test_float32_agrees_to_rounding(self):
        """The cell's precision: float32 values and sums; only the order of
        the additions differs from the flat path."""
        rows, cols, vals, labels, offsets, weights = _tiered_coo(seed=3)
        build = lambda **kw: SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=300, offsets=offsets,
            weights=weights, dtype=np.float32, **kw
        )
        flat = build(ell=False)
        tiered = build(hybrid=HybridPolicy(hot_cols=8, pad_multiple=8))
        assert tiered.ell_tiers and tiered.ell_vals.dtype == jnp.float32
        so = SparseGLMObjective(
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.3
        )
        w = jnp.asarray(
            np.random.default_rng(8).normal(scale=0.1, size=300), jnp.float32
        )
        want_v, want_g = so.value_and_gradient(w, flat)
        got_v, got_g = so.value_and_gradient(w, tiered)
        assert got_g.dtype == jnp.float32
        np.testing.assert_allclose(float(got_v), float(want_v), rtol=2e-6)
        scale = float(jnp.abs(want_g).max())
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), atol=2e-5 * scale
        )

    @pytest.mark.parametrize("view", ["ell", "hybrid"])
    def test_every_entry_stands_in_exactly_one_slot(self, view):
        from photon_ml_tpu.data.sparse_batch import coalesce_coo

        rows, cols, vals, *_ = _tiered_coo()
        batch = _tiered_views()[view]
        if view == "hybrid":
            # the head took its columns: the tail is what is left
            cold = ~np.isin(cols, np.asarray(batch.hot_col_ids))
            rows, cols, vals = rows[cold], cols[cold], vals[cold]
        want = coalesce_coo(rows, cols, vals)
        got = _view_entries(batch)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # and the slots are counted: pads are what is not an entry
        slots = batch.ell_vals.size + sum(t.vals.size for t in batch.ell_tiers)
        assert slots + batch.nnz >= len(want[0])

    @pytest.mark.parametrize("view", ["ell", "hybrid"])
    def test_tier_row_ids_ascend_and_nest(self, view):
        batch = _tiered_views()[view]
        holders = np.arange(batch.num_samples)
        for tier in batch.ell_tiers:
            ids = np.asarray(tier.row_ids)
            assert ids.dtype == np.int32
            assert (np.diff(ids) > 0).all()  # ascending and unique
            assert np.isin(ids, holders).all()  # rows of the tier before
            # rows along the minor axis
            assert tier.vals.shape == tier.cols.shape == (tier.width, len(ids))
            # a row is in a tier because it has an entry there
            assert (np.asarray(tier.vals)[0] != 0.0).all()
            holders = ids

    def test_uniform_counts_build_one_tier_equal_to_the_explicit_width(self):
        rng = np.random.default_rng(11)
        n, d, width = 50, 64, 7
        rows = np.repeat(np.arange(n), width)
        cols = np.concatenate(
            [np.sort(rng.choice(d, size=width, replace=False)) for _ in range(n)]
        )
        vals = rng.normal(size=n * width)
        labels = np.zeros(n)
        auto = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float64
        )
        fixed = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=d, dtype=np.float64, ell=width
        )
        assert auto.ell_tiers == () and auto.ell_vals.shape == (n, width)
        assert auto.nnz == 0
        for a, b in zip(jax.tree_util.tree_leaves(auto),
                        jax.tree_util.tree_leaves(fixed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("how", ["ell_int", "shard_ell_width",
                                     "shard_ell_int"])
    def test_an_explicit_width_is_one_block_and_nothing_else(self, how):
        rows, cols, vals, labels, offsets, weights = _tiered_coo()
        n, width = len(labels), 9
        if how == "ell_int":
            batch = SparseLabeledPointBatch.from_coo(
                rows, cols, vals, labels, dim=300, dtype=np.float64, ell=width
            )
        else:
            shard = SparseShard(
                rows=rows, cols=cols, vals=vals, num_samples=n,
                feature_dim=300,
                ell_width=width if how == "shard_ell_width" else None,
            )
            batch = SparseLabeledPointBatch.from_shard(
                shard, labels, offsets, weights,
                ell="auto" if how == "shard_ell_width" else width,
            )
        assert batch.ell_tiers == ()
        assert batch.ell_vals.shape == batch.ell_cols.shape == (n, width)
        counts = np.bincount(rows, minlength=n)
        assert batch.nnz == int(np.maximum(counts - width, 0).sum())

    def test_one_ell_width_is_the_agreed_width_or_the_one_width_rule(self):
        from photon_ml_tpu.data.sparse_batch import _ell_auto_width

        rows, cols, vals, labels, *_ = _tiered_coo()
        n = len(labels)
        shard = SparseShard(rows=rows, cols=cols, vals=vals, num_samples=n,
                            feature_dim=300)
        counts = np.bincount(rows, minlength=n)
        assert shard.one_ell_width() == _ell_auto_width(counts, n, len(rows))
        agreed = dataclasses.replace(shard, ell_width=5)
        assert agreed.one_ell_width() == 5
        # under a hybrid policy the rule reads the COLD tail's counts
        policy = HybridPolicy(hot_cols=8, pad_multiple=8)
        hyb = dataclasses.replace(shard, hybrid_policy=policy)
        tail_rows = hyb.hybrid_split(policy)[2]
        assert hyb.one_ell_width() == _ell_auto_width(
            np.bincount(tail_rows, minlength=n), n, len(tail_rows)
        )


def _tier_cost(freq, widths):
    """What ``_ell_tier_widths`` minimises, counted the slow way."""
    from photon_ml_tpu.data import sparse_batch as sb

    counts = np.repeat(np.arange(len(freq)), freq)
    cost, lower = 0.0, 0
    for upper in widths:
        holders = len(counts) if lower == 0 else int((counts > lower).sum())
        cost += holders * (upper - lower) + sb._TIER_LAUNCH_COST
        if lower:
            cost += sb._TIER_ROW_COST * holders
        lower = upper
    return cost + sb._OVERFLOW_ENTRY_COST * np.maximum(counts - lower, 0).sum()


class TestTierWidthRule:
    def test_same_histogram_same_widths(self):
        from photon_ml_tpu.data.sparse_batch import _ell_tier_widths

        rows, cols, vals, labels, *_ = _tiered_coo()
        n = len(labels)
        counts = np.bincount(rows, minlength=n)
        widths = _ell_tier_widths(np.bincount(counts))
        assert widths == _ell_tier_widths(np.bincount(counts))
        # another order of the same rows is the same histogram
        shuffled = np.random.default_rng(0).permutation(counts)
        assert widths == _ell_tier_widths(np.bincount(shuffled))
        # and the builder takes exactly these widths
        batch = _tiered_views()["ell"]
        built = np.cumsum(
            [batch.ell_vals.shape[1]] + [t.width for t in batch.ell_tiers]
        )
        assert tuple(built) == widths

    @pytest.mark.parametrize("seed", range(6))
    def test_widths_are_the_cheapest_over_every_choice(self, seed, monkeypatch):
        """Exhaustive over a small histogram: no choice of up to three
        widths among 1..max costs less than the rule's."""
        import itertools

        from photon_ml_tpu.data import sparse_batch as sb

        monkeypatch.setattr(sb, "_MAX_TIERS", 3)
        monkeypatch.setattr(sb, "_TIER_LAUNCH_COST", 5.0)
        rng = np.random.default_rng(seed)
        freq = rng.integers(0, 30, size=11)  # counts 0..10
        freq[-1] = max(freq[-1], 1)
        widths = sb._ell_tier_widths(freq)
        assert list(widths) == sorted(set(widths)) and 1 <= len(widths) <= 3
        cheapest = min(
            _tier_cost(freq, choice)
            for k in (1, 2, 3)
            for choice in itertools.combinations(range(1, 11), k)
        )
        assert _tier_cost(freq, widths) == pytest.approx(cheapest)

    @pytest.mark.parametrize("freq,want", [
        ([0, 0, 0, 9], (3,)),          # every row holds three entries
        ([4], (1,)),                   # no entry at all
        ([], (1,)),                    # no row
        ([2, 0, 0, 0, 0, 5], (5,)),    # empty rows beside one count
    ])
    def test_degenerate_histograms_take_one_tier(self, freq, want):
        from photon_ml_tpu.data.sparse_batch import _ell_tier_widths

        assert _ell_tier_widths(np.asarray(freq, np.int64)) == want

    def test_many_distinct_counts_stay_within_the_tier_cap(self):
        from photon_ml_tpu.data import sparse_batch as sb

        rng = np.random.default_rng(2)
        counts = rng.integers(1, 3000, size=20000)
        widths = sb._ell_tier_widths(np.bincount(counts))
        assert 1 <= len(widths) <= sb._MAX_TIERS
        assert list(widths) == sorted(set(widths))
        assert widths[-1] <= counts.max()


class TestMeshKeepsOneWidth:
    def _tiered_shard_dataset(self):
        from photon_ml_tpu.data.game_data import build_game_dataset

        rows, cols, vals, labels, *_ = _tiered_coo(n=2048)
        shard = SparseShard(rows=rows, cols=cols, vals=vals,
                            num_samples=len(labels), feature_dim=300)
        return shard, build_game_dataset(
            labels=labels, feature_shards={"global": shard},
            entity_keys={}, dtype=np.float64,
        )

    def _program(self):
        from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
        from photon_ml_tpu.parallel.distributed import (
            FixedEffectStepSpec,
            GameTrainProgram,
        )

        opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS,
                              max_iterations=2)
        return GameTrainProgram(
            TaskType.LOGISTIC_REGRESSION,
            FixedEffectStepSpec("global", opt, l2_weight=0.1), (),
        )

    def test_prepare_inputs_builds_one_block(self):
        shard, dataset = self._tiered_shard_dataset()
        data, _ = self._program().prepare_inputs(dataset, {})
        sb_ = data["fe_sparse_batch"]
        assert sb_.ell_tiers == ()
        assert sb_.ell_vals.shape == (2048, shard.one_ell_width())

    def test_shard_inputs_refuses_a_tiered_batch(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs four CPU devices")
        from photon_ml_tpu.parallel.mesh import make_mesh

        shard, dataset = self._tiered_shard_dataset()
        program = self._program()
        data, buckets = program.prepare_inputs(dataset, {})
        tiered = SparseLabeledPointBatch.from_shard(
            shard, data["labels"], data["offsets"], data["weights"]
        )
        assert tiered.ell_tiers
        data["fe_sparse_batch"] = tiered
        mesh = make_mesh(data=4, model=1)
        with pytest.raises(ValueError, match=r"one_ell_width.*ell_width"):
            program._shard_data(mesh, data)

    def test_partitioned_assembly_refuses_a_tiered_batch(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs four CPU devices")
        from photon_ml_tpu.parallel.distributed import _assemble_sparse_fe
        from photon_ml_tpu.parallel.mesh import make_mesh

        shard, dataset = self._tiered_shard_dataset()
        labels = np.asarray(dataset.host_array("labels"))
        tiered = SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros_like(labels), np.ones_like(labels)
        )
        prepared = {r: ({"fe_sparse_batch": tiered}, None) for r in (0, 1)}
        with pytest.raises(ValueError, match=r"one_ell_width.*ell_width"):
            _assemble_sparse_fe(
                prepared, [0, 1], make_mesh(data=4, model=1), 2, jax.device_put
            )


class TestLayoutTelemetry:
    def test_tail_gauges_equal_the_built_arrays(self):
        from photon_ml_tpu.telemetry import default_registry
        from photon_ml_tpu.telemetry.layout import reset_layout_metrics

        reset_layout_metrics()
        rows, cols, vals, labels, *_ = _tiered_coo(seed=9)
        batch = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=300, dtype=np.float64,
            hybrid=HybridPolicy(hot_cols=8, pad_multiple=8, label="t_built"),
        )
        g = {k.rsplit("/", 1)[1]: v
             for k, v in default_registry().snapshot()["gauges"].items()
             if k.startswith("layout/t_built/")}
        blocks = [batch.ell_vals] + [t.vals for t in batch.ell_tiers]
        slots = sum(b.size for b in blocks) + batch.nnz
        assert len(blocks) >= 3 and batch.nnz > 0
        assert g["tail_slots"] == slots
        assert g["tail_tiers"] == len(blocks)
        assert g["tail_width"] == batch.ell_vals.shape[1] + sum(
            t.width for t in batch.ell_tiers)
        # tail_nnz stays the tail's ENTRIES, padding apart
        entries = len(_view_entries(batch)[0])
        assert g["tail_nnz"] == entries
        assert g["tail_pad_share"] == pytest.approx(1.0 - entries / slots)
        arrays = [batch.hot_vals, batch.ell_vals, batch.ell_cols, batch.values,
                  batch.col_indices, batch.row_ids]
        for t in batch.ell_tiers:
            arrays += [t.vals, t.cols, t.row_ids]
        assert g["hybrid_bytes"] == sum(a.size * a.dtype.itemsize for a in arrays)
        # a flat tail (ell=False) has no tier and pads nothing
        SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=300, dtype=np.float64, ell=False,
            hybrid=HybridPolicy(hot_cols=8, pad_multiple=8, label="t_flat"),
        )
        gauges = default_registry().snapshot()["gauges"]
        assert gauges["layout/t_flat/tail_tiers"] == 0
        assert gauges["layout/t_flat/tail_slots"] == gauges["layout/t_flat/tail_nnz"]
        assert gauges["layout/t_flat/tail_pad_share"] == 0.0
        reset_layout_metrics()


    def test_hybrid_build_records_gauges_and_resets(self):
        from photon_ml_tpu.telemetry import default_registry
        from photon_ml_tpu.telemetry.layout import reset_layout_metrics

        reset_layout_metrics()
        rows, cols, vals, labels, _, _ = _data(seed=40)
        SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=40, dtype=np.float64,
            hybrid=HybridPolicy(coverage=0.5, label="t_shard"),
        )
        snap = default_registry().snapshot()
        gauges = snap["gauges"]
        for key in ("k_hot", "k_hot_padded", "hot_coverage", "hot_nnz",
                    "tail_nnz", "tail_width", "hybrid_bytes", "tail_slots",
                    "tail_tiers", "tail_pad_share"):
            assert f"layout/t_shard/{key}" in gauges, key
        assert 0.0 < gauges["layout/t_shard/hot_coverage"] <= 1.0
        assert snap["counters"]["layout/t_shard/builds"] == 1
        # per-run reset (drivers call this next to reset_solver_metrics)
        reset_layout_metrics()
        snap = default_registry().snapshot()
        assert not any(k.startswith("layout/") for k in snap["gauges"])
        assert not any(k.startswith("layout/") for k in snap["counters"])

    def test_column_sharded_build_records_block_head_gauges(self):
        from photon_ml_tpu.parallel.column_sharded import (
            build_column_sharded_batch,
        )
        from photon_ml_tpu.telemetry import default_registry
        from photon_ml_tpu.telemetry.layout import reset_layout_metrics

        reset_layout_metrics()
        rows, cols, vals = _skewed_coo(64, 48, 500, seed=42)
        labels = np.zeros(64)
        shard = SparseShard(
            rows=rows, cols=cols, vals=vals, num_samples=64, feature_dim=48,
            hybrid_policy=HybridPolicy(
                coverage=0.5, pad_multiple=4, label="cs"
            ),
        )
        build_column_sharded_batch(shard, labels, 8)
        gauges = default_registry().snapshot()["gauges"]
        assert gauges["layout/cs/block_head_width"] >= 1
        # replication 1.0 = perfectly spread head; ~num_blocks = clustered
        assert gauges["layout/cs/block_head_replication"] >= 1.0
        reset_layout_metrics()


class TestCliGrammar:
    def test_parse_hybrid_keys(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        name, cfg = parse_feature_shard_config(
            "name=g,feature.bags=features,sparse=true,hybrid=true,"
            "hybrid.hot.cols=512"
        )
        assert name == "g" and cfg.hybrid
        assert cfg.hybrid_hot_cols == 512
        policy = cfg.hybrid_policy(label="g")
        assert isinstance(policy, HybridPolicy)
        assert policy.hot_cols == 512 and policy.label == "g"
        _, cfg = parse_feature_shard_config(
            "name=g,feature.bags=features,sparse=true,hybrid=true,"
            "hybrid.coverage=0.9"
        )
        assert cfg.hybrid_policy().coverage == 0.9

    def test_budget_and_coverage_mutually_exclusive(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        with pytest.raises(ValueError, match="mutually exclusive"):
            parse_feature_shard_config(
                "name=g,feature.bags=features,sparse=true,hybrid=true,"
                "hybrid.hot.cols=512,hybrid.coverage=0.9"
            )
        with pytest.raises(ValueError, match="mutually exclusive"):
            HybridPolicy(hot_cols=64, coverage=0.9)

    def test_hybrid_defaults_off(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        _, cfg = parse_feature_shard_config(
            "name=g,feature.bags=features,sparse=true"
        )
        assert not cfg.hybrid and cfg.hybrid_policy() is None

    def test_hybrid_requires_sparse(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        with pytest.raises(ValueError, match="sparse"):
            parse_feature_shard_config(
                "name=g,feature.bags=features,hybrid=true"
            )

    def test_hybrid_knobs_require_hybrid(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        with pytest.raises(ValueError, match="hybrid=true"):
            parse_feature_shard_config(
                "name=g,feature.bags=features,sparse=true,"
                "hybrid.hot.cols=128"
            )

    def test_bad_ranges_rejected(self):
        from photon_ml_tpu.cli.configs import parse_feature_shard_config

        with pytest.raises(ValueError, match="coverage"):
            parse_feature_shard_config(
                "name=g,feature.bags=features,sparse=true,hybrid=true,"
                "hybrid.coverage=1.5"
            )
        with pytest.raises(ValueError, match="hot_cols"):
            parse_feature_shard_config(
                "name=g,feature.bags=features,sparse=true,hybrid=true,"
                "hybrid.hot.cols=0"
            )

    def test_resolve_policy_forms(self):
        assert resolve_hybrid_policy(None) is None
        assert resolve_hybrid_policy(False) is None
        assert resolve_hybrid_policy(True) == HybridPolicy()
        p = HybridPolicy(hot_cols=7)
        assert resolve_hybrid_policy(p) is p
        with pytest.raises(TypeError):
            resolve_hybrid_policy("yes")

    def test_reader_attaches_policy(self):
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            build_index_maps,
            records_to_game_dataset,
        )

        records = [
            {
                "uid": str(i),
                "label": float(i % 2),
                "features": [
                    {"name": f"f{j}", "term": "", "value": 1.0}
                    for j in range(3)
                ],
            }
            for i in range(6)
        ]
        cfgs = {
            "g": FeatureShardConfiguration(
                feature_bags=("features",), sparse=True, hybrid=True,
                hybrid_coverage=0.8,
            )
        }
        imaps = build_index_maps(records, cfgs)
        result = records_to_game_dataset(records, cfgs, imaps)
        shard = result.dataset.feature_shards["g"]
        assert isinstance(shard, SparseShard)
        assert shard.hybrid_policy is not None
        assert shard.hybrid_policy.coverage == 0.8
        assert shard.hybrid_policy.label == "g"
        batch = result.dataset.fixed_effect_batch("g")
        assert batch.has_hybrid_view  # inherited through from_shard


class TestHybridSplitCache:
    def test_from_shard_reuses_split_across_rebuilds(self):
        """GAME CD rebuilds the FE batch every sweep; the (shard, policy)
        split — an O(nnz log nnz) ranking + dense host fill — must compute
        once, not per sweep (builds counter pins it)."""
        from photon_ml_tpu.telemetry import default_registry
        from photon_ml_tpu.telemetry.layout import reset_layout_metrics

        reset_layout_metrics()
        rows, cols, vals, labels, _, _ = _data(seed=50)
        shard = SparseShard(
            rows=rows, cols=cols, vals=vals, num_samples=80, feature_dim=40,
            hybrid_policy=HybridPolicy(coverage=0.5, label="cache"),
        )
        b1 = SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros(80), np.ones(80)
        )
        b2 = SparseLabeledPointBatch.from_shard(
            shard, labels, np.ones(80), np.ones(80)  # offsets differ
        )
        assert b1.has_hybrid_view and b2.has_hybrid_view
        counters = default_registry().snapshot()["counters"]
        assert counters["layout/cache/builds"] == 1
        np.testing.assert_array_equal(
            np.asarray(b1.hot_vals), np.asarray(b2.hot_vals)
        )
        # a different policy recomputes
        SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros(80), np.ones(80),
            hybrid=HybridPolicy(hot_cols=2, label="cache"),
        )
        counters = default_registry().snapshot()["counters"]
        assert counters["layout/cache/builds"] == 2
        reset_layout_metrics()


_ONE_BUILDER_LAYOUTS = {
    "flat": dict(ell=False),
    "ell_auto": dict(),
    "ell_width_overflow": dict(ell=4),
    "hybrid_hot_cols": dict(
        hybrid=HybridPolicy(hot_cols=8, pad_multiple=8, label="t_one")),
    "hybrid_coverage": dict(
        hybrid=HybridPolicy(coverage=0.4, pad_multiple=8, label="t_one")),
    "hybrid_flat_tail": dict(
        ell=False,
        hybrid=HybridPolicy(hot_cols=8, pad_multiple=8, label="t_one")),
}


class TestOneBuilder:
    """``from_shard`` and ``from_coo`` decide a batch's layout in one place
    (``_build_batch``, ISSUE 46): the same triples give the same leaves."""

    @staticmethod
    def _both(dtype=np.float64, shard_kw=None, coo_kw=None, **kw):
        rows, cols, vals, labels, offsets, weights = _tiered_coo()
        from_coo = SparseLabeledPointBatch.from_coo(
            rows, cols, vals, labels, dim=300, offsets=offsets,
            weights=weights, dtype=dtype, **kw, **(coo_kw or {}),
        )
        shard = SparseShard(
            rows, cols, vals.astype(dtype), 4000, 300, **(shard_kw or {})
        )
        from_shard = SparseLabeledPointBatch.from_shard(
            shard, labels.astype(dtype), offsets.astype(dtype),
            weights.astype(dtype), **kw,
        )
        return from_shard, from_coo

    @pytest.mark.parametrize("dtype", [np.float32, np.float64],
                             ids=["float32", "float64"])
    @pytest.mark.parametrize("layout", sorted(_ONE_BUILDER_LAYOUTS))
    def test_from_shard_and_from_coo_build_the_same_leaves(self, layout, dtype):
        from_shard, from_coo = self._both(dtype, **_ONE_BUILDER_LAYOUTS[layout])
        _assert_same_leaves(from_shard, from_coo)
        assert from_coo.dtype == jnp.dtype(dtype)
        # the case is the layout its name says
        assert from_coo.has_hybrid_view == layout.startswith("hybrid")
        assert from_coo.has_ell_view == (
            layout not in ("flat", "hybrid_flat_tail")
        )
        if layout == "ell_auto":
            assert len(from_coo.ell_tiers) >= 2
        if layout == "ell_width_overflow":
            assert from_coo.ell_vals.shape == (4000, 4)
            assert not from_coo.ell_tiers and from_coo.nnz > 0

    @pytest.mark.parametrize("case", [
        "same_length_same_pad", "an_agreed_block_too_short_raises",
        "pad_nnz_to_below_the_count_pads_nothing",
    ])
    def test_one_pad_rule_keeps_what_each_caller_sees(self, case):
        """A shard's agreed ``flat_block_nnz`` and ``from_coo``'s
        ``pad_nnz_to`` pad the flat triple under one contract (the last row
        id, column 0, value 0); the agreed length is exact, the other a
        floor."""
        free = self._both(ell=4)[1]
        assert free.nnz > 37
        if case == "same_length_same_pad":
            target = free.nnz + 37
            from_shard, from_coo = self._both(
                ell=4, coo_kw=dict(pad_nnz_to=target),
                shard_kw=dict(flat_block_nnz=target),
            )
            _assert_same_leaves(from_shard, from_coo)
            assert from_coo.nnz == target
            np.testing.assert_array_equal(
                np.asarray(from_coo.values)[: free.nnz], np.asarray(free.values)
            )
            assert np.all(np.asarray(from_coo.values)[free.nnz:] == 0.0)
            assert np.all(np.asarray(from_coo.col_indices)[free.nnz:] == 0)
            assert np.all(
                np.asarray(from_coo.row_ids)[free.nnz:]
                == np.asarray(free.row_ids)[-1]
            )
        elif case == "an_agreed_block_too_short_raises":
            with pytest.raises(ValueError, match="agreed flat_block_nnz"):
                self._both(ell=4, shard_kw=dict(flat_block_nnz=free.nnz - 37))
        else:
            from_coo = self._both(
                ell=4, coo_kw=dict(pad_nnz_to=free.nnz - 37)
            )[1]
            _assert_same_leaves(from_coo, free)


class TestPartitionedIoComposition:
    def test_hybrid_plus_partitioned_io_accepted(self):
        """hybrid + --partitioned-io is a LEGAL composition since ISSUE 6:
        the partitioned reader resolves one GLOBAL hot head over the
        metadata exchange, so validate() no longer rejects the pair."""
        from photon_ml_tpu.cli.configs import CoordinateCliConfig
        from photon_ml_tpu.cli.game_training_driver import GameTrainingParams
        from photon_ml_tpu.io.data_reader import FeatureShardConfiguration

        def params(partitioned_io):
            return GameTrainingParams(
                input_data_path="/nonexistent",
                root_output_dir="/nonexistent-out",
                feature_shards={
                    "g": FeatureShardConfiguration(
                        feature_bags=("features",), sparse=True, hybrid=True
                    )
                },
                coordinates={
                    "fe": CoordinateCliConfig(name="fe", feature_shard="g")
                },
                task_type=TaskType.LINEAR_REGRESSION,
                partitioned_io=partitioned_io,
            )

        params(True).validate()
        params(False).validate()

    def test_global_hot_ids_policy(self):
        """A policy carrying pre-resolved hot_ids (the partitioned
        reader's global ranking) builds exactly those columns — even ones
        the local block never observed, and even on an empty block — so
        the head SHAPE agrees across ranks."""
        from photon_ml_tpu.data.sparse_batch import _hybrid_arrays

        rows = np.array([0, 0, 1, 2])
        cols = np.array([3, 7, 3, 9])
        vals = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
        policy = HybridPolicy(
            hot_ids=(3, 5), pad_multiple=2, label="gids"
        )
        hot, ids, tr, tc, tv = _hybrid_arrays(rows, cols, vals, 3, 16, policy)
        np.testing.assert_array_equal(ids, [3, 5])
        np.testing.assert_array_equal(
            hot, [[1.0, 0.0], [3.0, 0.0], [0.0, 0.0]]
        )
        np.testing.assert_array_equal(tc, [7, 9])  # cold tail preserved
        # an empty local block still builds the agreed head shape
        hot0, ids0, *_tail = _hybrid_arrays(
            np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, np.float32), 3, 16, policy,
        )
        assert hot0.shape == (3, 2)
        np.testing.assert_array_equal(ids0, [3, 5])

    def test_hot_ids_validation(self):
        with pytest.raises(ValueError, match="sorted"):
            HybridPolicy(hot_ids=(5, 3))
        with pytest.raises(ValueError, match="at least one"):
            HybridPolicy(hot_ids=())

    def test_shard_ell_width_fixes_signature(self):
        """SparseShard.ell_width (the partitioned reader's agreed width)
        overrides the auto rule so every rank's batch block shares one
        shape, with an empty flat overflow tail when wide enough."""
        rows, cols, vals, labels, _, _ = _data(seed=51)
        shard = SparseShard(
            rows=rows, cols=cols, vals=vals, num_samples=80, feature_dim=40,
            ell_width=int(np.bincount(rows).max()),
        )
        b = SparseLabeledPointBatch.from_shard(
            shard, labels, np.zeros(80), np.ones(80)
        )
        assert b.has_ell_view
        assert b.ell_vals.shape == (80, int(np.bincount(rows).max()))
        assert b.nnz == 0  # wide enough: no overflow entries
