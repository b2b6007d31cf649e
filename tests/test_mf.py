"""Matrix-factorization coordinate tests.

The reference declares MF (README.md:92-95, LatentFactorAvro.avsc) but never
implemented it; these tests cover our implementation of the promised
capability: scoring semantics, bucketing, alternating training (rank
recovery), estimator integration, and LatentFactorAvro round-trip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.algorithm.mf_coordinate import (
    MatrixFactorizationCoordinate,
    build_mf_dataset,
)
from photon_ml_tpu.algorithm.coordinates import CoordinateOptimizationConfig
from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.estimators import (
    FixedEffectCoordinateConfig,
    GameEstimator,
    MatrixFactorizationCoordinateConfig,
)
from photon_ml_tpu.io.model_io import load_game_model, save_game_model
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.models.matrix_factorization import (
    MatrixFactorizationModel,
    init_factors,
    score_matrix_factorization,
)
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.types import TaskType


def _mf_problem(rng, n=600, n_rows=12, n_cols=9, k=2, noise=0.05):
    """Low-rank regression data: y = u_r . v_c + noise."""
    u = rng.normal(size=(n_rows, k))
    v = rng.normal(size=(n_cols, k))
    r = rng.integers(0, n_rows, size=n)
    c = rng.integers(0, n_cols, size=n)
    y = np.einsum("nk,nk->n", u[r], v[c]) + noise * rng.normal(size=n)
    rows = np.array([f"u{i}" for i in r])
    cols = np.array([f"v{i}" for i in c])
    return rows, cols, y.astype(np.float64)


def test_score_semantics_missing_entities(rng):
    row_f = jnp.asarray(rng.normal(size=(4, 3)))
    col_f = jnp.asarray(rng.normal(size=(5, 3)))
    row_idx = jnp.asarray(np.array([0, 1, -1, 2], dtype=np.int32))
    col_idx = jnp.asarray(np.array([0, -1, 2, 4], dtype=np.int32))
    s = np.asarray(score_matrix_factorization(row_f, col_f, row_idx, col_idx))
    assert s[1] == 0.0 and s[2] == 0.0  # either side missing -> 0
    np.testing.assert_allclose(s[0], np.dot(row_f[0], col_f[0]), rtol=1e-6)
    np.testing.assert_allclose(s[3], np.dot(row_f[2], col_f[4]), rtol=1e-6)


def test_init_factors_nonzero_and_deterministic():
    r1, c1 = init_factors(7, 5, 3, seed=42)
    r2, c2 = init_factors(7, 5, 3, seed=42)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert np.abs(np.asarray(r1)).max() > 0
    assert r1.shape == (7, 3) and c1.shape == (5, 3)


def test_build_mf_dataset_buckets(rng):
    rows, cols, y = _mf_problem(rng, n=100)
    # knock out some col entities from the vocab to exercise weight zeroing
    ds = build_game_dataset(
        labels=y,
        feature_shards={},
        entity_keys={"user": rows, "item": cols},
        entity_vocabs={"item": np.unique(cols)[:-2]},
        dtype=np.float64,
    )
    mf = build_mf_dataset(ds, "user", "item")
    assert mf.num_row_entities == len(np.unique(rows))
    # samples whose item is unseen cannot contribute a factor-feature and
    # are excluded from the row-side buckets entirely (they must not crowd
    # usable samples out of reservoir caps)
    item_idx = np.asarray(ds.entity_idx["item"])
    usable = int((item_idx >= 0).sum())
    assert usable < 100  # the vocab knockout actually removed some
    total = sum(int((np.asarray(b.sample_rows) >= 0).sum()) for b in mf.row_buckets)
    assert total == usable
    for b in mf.row_buckets:
        sr = np.asarray(b.sample_rows)
        w = np.asarray(b.weights)
        assert np.all(w[sr >= 0] > 0)  # every bucketed slot is trainable


def test_mf_coordinate_recovers_low_rank(rng):
    rows, cols, y = _mf_problem(rng, n=800, k=2, noise=0.05)
    ds = build_game_dataset(
        labels=y,
        feature_shards={},
        entity_keys={"user": rows, "item": cols},
        dtype=np.float64,
    )
    coord = MatrixFactorizationCoordinate(
        coordinate_id="mf",
        dataset=ds,
        mf_dataset=build_mf_dataset(ds, "user", "item"),
        task=TaskType.LINEAR_REGRESSION,
        config=CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=20
            ),
            l2_weight=1e-3,
        ),
        num_latent_factors=2,
        num_alternations=6,
    )
    model = coord.initial_model()
    rmse0 = float(np.sqrt(np.mean((np.asarray(coord.score(model)) - y) ** 2)))
    model, _ = coord.update_model(model)
    rmse = float(np.sqrt(np.mean((np.asarray(coord.score(model)) - y) ** 2)))
    assert rmse < 0.35, f"MF failed to fit rank-2 structure: rmse {rmse0} -> {rmse}"
    assert rmse < rmse0 / 3


def test_mf_newton_matches_lbfgs(rng):
    """optimizer=NEWTON drives the MF alternating half-steps too (they go
    through the same solve() facade as RE buckets): equal fit quality at
    a fraction of the per-iteration op count (optim/newton.py)."""
    rows, cols, y = _mf_problem(rng, n=800, k=2, noise=0.05)
    ds = build_game_dataset(
        labels=y,
        feature_shards={},
        entity_keys={"user": rows, "item": cols},
        dtype=np.float64,
    )

    def fit(opt_type):
        coord = MatrixFactorizationCoordinate(
            coordinate_id="mf",
            dataset=ds,
            mf_dataset=build_mf_dataset(ds, "user", "item"),
            task=TaskType.LINEAR_REGRESSION,
            config=CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(
                    optimizer_type=opt_type, max_iterations=20
                ),
                l2_weight=1e-3,
            ),
            num_latent_factors=2,
            num_alternations=6,
        )
        model, _ = coord.update_model(coord.initial_model())
        return float(np.sqrt(np.mean((np.asarray(coord.score(model)) - y) ** 2)))

    rmse_newton = fit(OptimizerType.NEWTON)
    rmse_lbfgs = fit(OptimizerType.LBFGS)
    assert rmse_newton < 0.35
    assert abs(rmse_newton - rmse_lbfgs) < 0.02, (rmse_newton, rmse_lbfgs)


def test_mf_l1_rejected(rng):
    rows, cols, y = _mf_problem(rng, n=50)
    ds = build_game_dataset(
        labels=y, feature_shards={}, entity_keys={"user": rows, "item": cols},
        dtype=np.float64,
    )
    coord = MatrixFactorizationCoordinate(
        coordinate_id="mf",
        dataset=ds,
        mf_dataset=build_mf_dataset(ds, "user", "item"),
        task=TaskType.LINEAR_REGRESSION,
        config=CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(), l1_weight=0.1
        ),
        num_latent_factors=2,
    )
    with pytest.raises(ValueError, match="L1"):
        coord.update_model(coord.initial_model())


def test_estimator_with_mf_coordinate(rng):
    # fixed effect + MF residual structure
    n, d, k = 700, 4, 2
    w_true = rng.normal(size=d)
    x = rng.normal(size=(n, d))
    rows, cols, y_mf = _mf_problem(rng, n=n, k=k, noise=0.0)
    y = x @ w_true + 0.7 * y_mf + 0.05 * rng.normal(size=n)
    ds = build_game_dataset(
        labels=y,
        feature_shards={"global": x},
        entity_keys={"user": rows, "item": cols},
        dtype=np.float64,
    )
    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=20),
        l2_weight=1e-3,
    )
    est = GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig("global", opt),
            "mf": MatrixFactorizationCoordinateConfig(
                "user", "item", num_latent_factors=k, optimization=opt,
                num_alternations=2,
            ),
        },
        num_iterations=4,
        check_finite=True,
    )
    result = est.fit(ds)
    scores = np.asarray(result.model.score_dataset(ds))
    rmse = float(np.sqrt(np.mean((scores - y) ** 2)))
    # FE alone leaves the 0.7*mf residual (std ~ 0.7*|u.v| ~ 1); joint fit
    # must capture most of it
    assert rmse < 0.4, f"joint FE+MF fit too weak: rmse={rmse}"
    assert isinstance(result.model.get("mf"), MatrixFactorizationModel)


def test_mf_checkpoint_round_trip(rng):
    from photon_ml_tpu.io.checkpoint import (
        game_model_from_arrays,
        game_model_to_arrays,
    )

    model = MatrixFactorizationModel(
        row_factors=jnp.asarray(rng.normal(size=(3, 2))),
        col_factors=jnp.asarray(rng.normal(size=(4, 2))),
        row_effect_type="user",
        col_effect_type="item",
        row_keys=np.array(["u0", "u1", "u2"]),
        col_keys=np.array(["i0", "i1", "i2", "i3"]),
        task=TaskType.LINEAR_REGRESSION,
    )
    arrays, meta = game_model_to_arrays(GameModel(models={"mf": model}))
    restored = game_model_from_arrays(arrays, meta).get("mf")
    assert isinstance(restored, MatrixFactorizationModel)
    np.testing.assert_allclose(
        np.asarray(restored.row_factors), np.asarray(model.row_factors)
    )
    np.testing.assert_array_equal(restored.col_keys, model.col_keys)
    assert restored.task == TaskType.LINEAR_REGRESSION


def test_mf_cli_config_partial_spec_rejected():
    from photon_ml_tpu.cli.configs import parse_coordinate_config

    cfg = parse_coordinate_config(
        "name=mf,mf.row.effect.type=u,mf.col.effect.type=i,mf.latent.factors=4"
    )
    assert cfg.is_matrix_factorization and cfg.mf_latent_factors == 4
    # partial MF specs must fail loudly, not silently train a fixed effect
    with pytest.raises(ValueError, match="matrix-.*factorization coordinate"):
        parse_coordinate_config(
            "name=x,feature.shard=g,mf.col.effect.type=i,mf.latent.factors=2"
        )
    with pytest.raises(ValueError, match="mf.latent.factors"):
        parse_coordinate_config(
            "name=x,mf.row.effect.type=u,mf.col.effect.type=i"
        )


def test_mf_cli_config_conflicts_rejected():
    from photon_ml_tpu.cli.configs import parse_coordinate_config

    with pytest.raises(ValueError, match="either a random effect or"):
        parse_coordinate_config(
            "name=x,feature.shard=g,random.effect.type=u,"
            "mf.row.effect.type=u,mf.col.effect.type=i,mf.latent.factors=2"
        )
    with pytest.raises(ValueError, match="L1"):
        parse_coordinate_config(
            "name=x,mf.row.effect.type=u,mf.col.effect.type=i,"
            "mf.latent.factors=2,reg.alpha=0.5"
        )


def test_mf_untrained_vocab_entities_score_zero(rng):
    """Vocab entities with zero samples must score 0, not random-init noise
    (random-effect missing-entity semantics)."""
    rows, cols, y = _mf_problem(rng, n=60, n_rows=5, n_cols=4)
    vocab_rows = np.concatenate([np.unique(rows), ["ghost-user"]])
    ds = build_game_dataset(
        labels=y,
        feature_shards={},
        entity_keys={"user": rows, "item": cols},
        entity_vocabs={"user": vocab_rows},
        dtype=np.float64,
    )
    coord = MatrixFactorizationCoordinate(
        coordinate_id="mf",
        dataset=ds,
        mf_dataset=build_mf_dataset(ds, "user", "item"),
        task=TaskType.LINEAR_REGRESSION,
        config=CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=5), l2_weight=1e-3
        ),
        num_latent_factors=2,
        num_alternations=1,
    )
    model, _ = coord.update_model(coord.initial_model())
    ghost = int(np.nonzero(np.asarray(model.row_keys) == "ghost-user")[0][0])
    np.testing.assert_array_equal(np.asarray(model.row_factors)[ghost], 0.0)


def test_mf_model_avro_round_trip(tmp_path, rng):
    rows = np.array(["u0", "u1", "u2"])
    cols = np.array(["i0", "i1"])
    model = MatrixFactorizationModel(
        row_factors=jnp.asarray(rng.normal(size=(3, 4))),
        col_factors=jnp.asarray(rng.normal(size=(2, 4))),
        row_effect_type="user",
        col_effect_type="item",
        row_keys=rows,
        col_keys=cols,
        task=TaskType.LINEAR_REGRESSION,
    )
    game = GameModel(models={"mf": model})
    save_game_model(tmp_path / "model", game, index_maps={})
    loaded = load_game_model(tmp_path / "model", index_maps={}, dtype=np.float64)
    lm = loaded.get("mf")
    assert isinstance(lm, MatrixFactorizationModel)
    assert lm.row_effect_type == "user" and lm.col_effect_type == "item"
    np.testing.assert_array_equal(lm.row_keys, rows)
    np.testing.assert_allclose(
        np.asarray(lm.row_factors), np.asarray(model.row_factors), rtol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(lm.col_factors), np.asarray(model.col_factors), rtol=1e-12
    )
    # scoring equivalence on a dataset built against the saved vocabs
    ds = build_game_dataset(
        labels=np.zeros(4),
        feature_shards={},
        entity_keys={
            "user": np.array(["u1", "u0", "zz", "u2"]),
            "item": np.array(["i0", "i1", "i0", "zz"]),
        },
        entity_vocabs={"user": rows, "item": cols},
        dtype=np.float64,
    )
    np.testing.assert_allclose(
        np.asarray(lm.score_dataset(ds)),
        np.asarray(model.score_dataset(ds)),
        rtol=1e-6,
    )


def test_mf_reservoir_cap_ignores_unusable_samples(rng):
    """Samples whose other-side entity is unseen must not crowd usable
    samples out of the reservoir cap."""
    n_usable, n_dead = 6, 40
    rows = np.array(["r0"] * (n_usable + n_dead))
    cols = np.array(["c0"] * n_usable + ["GONE"] * n_dead)
    y = rng.normal(size=n_usable + n_dead)
    ds = build_game_dataset(
        labels=y, feature_shards={},
        entity_keys={"user": rows, "item": cols},
        entity_vocabs={"item": np.array(["c0"])},
        dtype=np.float64,
    )
    mf = build_mf_dataset(ds, "user", "item", bucket_sizes=(8,),
                          active_data_upper_bound=8)
    # all 6 usable samples must survive the cap with nonzero weight
    kept = sum(float((np.asarray(b.weights) > 0).sum()) for b in mf.row_buckets)
    assert kept == n_usable


def _assert_fused_sweep_equals_coordinate_path(ds, x, mf_dataset, *, iterations, seed):
    """One sweep of the fused step (fixed effect ``global``, then the
    factorization) against ``MatrixFactorizationCoordinate.update_model`` on
    the fixed effect's margin: the same starting factors, the same two tables."""
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
        MatrixFactorizationStepSpec,
    )

    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=iterations)
    program = GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("global", opt, l2_weight=0.5),
        mf_specs=(MatrixFactorizationStepSpec(
            "mf", "user", "item", 3, opt, l2_weight=0.7, seed=seed),))
    data, buckets = program.prepare_inputs(ds, {}, {"mf": mf_dataset})
    state = program.init_state(ds, {}, {"mf": mf_dataset})
    new_state, _loss = program.step(data, buckets, state)

    coord = MatrixFactorizationCoordinate(
        coordinate_id="mf", dataset=ds, mf_dataset=mf_dataset,
        task=TaskType.LOGISTIC_REGRESSION,
        config=CoordinateOptimizationConfig(optimizer=opt, l2_weight=0.7),
        num_latent_factors=3, num_alternations=1, seed=seed)
    model = coord.initial_model()
    np.testing.assert_array_equal(np.asarray(model.row_factors),
                                  np.asarray(state.mf_rows["mf"]))
    fe_margin = jnp.asarray(x) @ new_state.fe_coefficients
    model, _ = coord.update_model(model, extra_offsets=fe_margin)
    np.testing.assert_allclose(np.asarray(model.row_factors),
                               np.asarray(new_state.mf_rows["mf"]), rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(model.col_factors),
                               np.asarray(new_state.mf_cols["mf"]), rtol=1e-9, atol=1e-12)
    assert np.abs(np.asarray(model.row_factors) - np.asarray(state.mf_rows["mf"])).max() > 1e-2


def test_one_fused_sweep_equals_the_coordinate_path(rng):
    """The fused step's factorization coordinate is the
    MatrixFactorizationCoordinate's update: from the same starting factors,
    against the fixed effect the sweep has just solved, one alternation gives
    the same two tables."""
    rows, cols, y = _mf_problem(rng, n=500)
    x = rng.normal(size=(500, 5))
    labels = (y + x @ rng.normal(size=5) > 0).astype(np.float64)
    ds = build_game_dataset(
        labels=labels, feature_shards={"global": x},
        entity_keys={"user": rows, "item": cols}, dtype=np.float64)
    mf_dataset = build_mf_dataset(ds, "user", "item", bucket_sizes=(16, 64, 256))
    _assert_fused_sweep_equals_coordinate_path(ds, x, mf_dataset, iterations=7, seed=11)


def test_the_packer_stays_on_the_host_times_itself_and_records_its_padding():
    """Seven users of 1 to 7 rows and two items of 14 on a ladder of (4, 8):
    every block is a host array, the packing is timed, and the gauges read
    the padding the ladder costs each side."""
    from photon_ml_tpu.telemetry.registry import default_registry

    users = np.repeat(np.arange(7), np.arange(1, 8))  # 28 rows
    items = np.arange(28) % 2  # two items of 14 rows: past the top rung
    ds = build_game_dataset(
        labels=np.zeros(28), feature_shards={},
        entity_keys={"user": users.astype(str), "item": items.astype(str)},
        dtype=np.float64)
    before = default_registry().histogram("timing/pack/mf_side_buckets").count
    mf = build_mf_dataset(ds, "user", "item", bucket_sizes=(4, 8))
    for b in mf.row_buckets + mf.col_buckets:
        for block in (b.labels, b.weights, b.entity_rows, b.sample_rows):
            assert isinstance(block, np.ndarray)
    assert default_registry().histogram("timing/pack/mf_side_buckets").count == before + 1
    # users: sizes 1..4 in cap 4 (16 slots, 10 rows), 5..7 in cap 8 (24, 18)
    # items: two of 14 rows capped to the top rung, 8 (16 slots, 16 rows)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["mf/user_x_item/row_pad_fraction"] == pytest.approx(1 - 28 / 40)
    assert gauges["mf/user_x_item/col_pad_fraction"] == 0.0
    assert mf.pad_fractions() == (pytest.approx(0.3), 0.0)


# -- the half-step's layout: slots minor where ``cap`` is whole vectors -------


def _logistic_objective(l2_weight=1.0):
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective

    return GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION),
                        l2_weight=l2_weight, use_pallas=False)


def _side_bucket(rng, e, cap, k, n_other=23, n_this=40):
    """One side's bucket as the packer would leave it, with everything a
    half-step has to mask: padding slots (``sample_rows`` -1, weight 0),
    slots whose other-side entity is unseen (``other_idx`` -1, weight 0) and
    padding LANES (``entity_rows`` past the table: gathers clamp, scatters
    drop). float32 throughout."""
    n = e * cap
    sample_rows = rng.permutation(n).reshape(e, cap).astype(np.int32)
    fill = rng.integers(max(1, cap // 2), cap + 1, size=e)
    sample_rows[np.arange(cap)[None, :] >= fill[:, None]] = -1
    other_idx = rng.integers(0, n_other, size=n).astype(np.int32)
    other_idx[rng.random(n) < 0.1] = -1
    weights = ((sample_rows >= 0)
               & (other_idx[np.maximum(sample_rows, 0)] >= 0)).astype(np.float32)
    entity_rows = rng.permutation(n_this)[:e].astype(np.int32)
    entity_rows[-2:] = n_this  # two padding lanes
    return dict(
        labels=(rng.random((e, cap)) < 0.5).astype(np.float32), weights=weights,
        entity_rows=entity_rows, sample_rows=sample_rows, other_idx_full=other_idx,
        other_factors=(rng.normal(size=(n_other, k)) / np.sqrt(k)).astype(np.float32),
        full_offsets=rng.normal(size=n).astype(np.float32),
        table=(rng.normal(size=(n_this, k)) / np.sqrt(k)).astype(np.float32))


def _plain_half_step(objective, opt, labels, weights, entity_rows, sample_rows,
                     other_idx_full, other_factors, full_offsets, table):
    """The half-step written out in ``jax.numpy``: gather the other side's
    factor rows, mask, solve every lane by the same ``solve`` on ``[cap, k]``
    features, scatter."""
    import jax

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.optim.optimizer import solve

    live = (sample_rows >= 0) & (other_idx_full[jnp.maximum(sample_rows, 0)] >= 0)
    oidx = jnp.maximum(other_idx_full[jnp.maximum(sample_rows, 0)], 0)
    feats = jnp.where(live[..., None], other_factors[oidx], 0.0)        # [e, cap, k]
    offsets = jnp.where(sample_rows >= 0,
                        full_offsets[jnp.maximum(sample_rows, 0)], 0.0)

    def lane(f, l, o, w, w0):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=w)
        return solve(opt, objective.bind(batch), w0)

    result = jax.vmap(lane)(feats, labels, offsets, weights, table[entity_rows])
    valid = (entity_rows >= 0) & (entity_rows < table.shape[0])
    return table.at[entity_rows].set(result.coefficients), result, valid


_HALF_STEP_CASES = [
    pytest.param(cap, k, "LBFGS", dtype, id=f"cap{cap}-k{k}-LBFGS-{dtype}")
    for cap in (8, 32, 128, 256) for k in (4, 32) for dtype in ("float32", "float64")
] + [pytest.param(128, 4, solver, "float64", id=f"cap128-k4-{solver}-float64")
     for solver in ("NEWTON", "TRON")]


@pytest.mark.parametrize("cap,k,solver,dtype", _HALF_STEP_CASES)
def test_half_step_equals_the_plain_one_at_every_cap(rng, cap, k, solver, dtype):
    """Whatever layout the rule gives the lanes' features, the half-step is
    the plain one, with the same lanes valid: in float64 step for step, to
    1e-9 after six iterations; in float32, the benchmark's precision, to 5e-3
    of the table's largest entry (a float32 fit follows its rounding, another
    order of a sum and another trial is accepted: 1.6e-3 was read, where a
    block read the wrong way round is off by the table's own size)."""
    import jax

    from photon_ml_tpu.algorithm.mf_coordinate import solve_mf_side_bucket

    objective = _logistic_objective()
    opt = OptimizerConfig(optimizer_type=OptimizerType[solver], max_iterations=6)
    bucket = {
        name: jnp.asarray(a, dtype if a.dtype == np.float32 else None)
        for name, a in _side_bucket(rng, 9, cap, k).items()}
    table, trace = jax.jit(solve_mf_side_bucket, static_argnums=(0, 1))(
        objective, opt, *bucket.values())
    want, result, valid = jax.jit(_plain_half_step, static_argnums=(0, 1))(
        objective, opt, *bucket.values())
    assert table.dtype == jnp.dtype(dtype)
    exact = dtype == "float64"
    np.testing.assert_allclose(
        np.asarray(table), np.asarray(want), rtol=1e-9 if exact else 0.0,
        atol=(1e-9 if exact else 5e-3) * float(np.abs(np.asarray(want)).max()))
    np.testing.assert_array_equal(np.asarray(trace.valid), np.asarray(valid))
    assert not np.asarray(trace.valid)[-2:].any() and np.asarray(trace.valid)[:-2].all()
    if exact:
        np.testing.assert_array_equal(np.asarray(trace.iterations)[:-2],
                                      np.asarray(result.iterations)[:-2])
    # the lanes moved: the comparison is not of two untouched tables
    rows = np.asarray(bucket["entity_rows"])[:-2]
    assert np.abs(np.asarray(table)[rows] - np.asarray(bucket["table"])[rows]).max() > 1e-2


def _float_shapes_in(jaxpr, found: set) -> set:
    """The shapes of the float arrays among the variables of a jaxpr and of
    the jaxprs its equations hold."""
    import jax

    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if hasattr(aval, "shape") and jnp.issubdtype(aval.dtype, jnp.floating):
                found.add(tuple(aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _float_shapes_in(sub, found)
    return found


@pytest.mark.parametrize("cap", [8, 32, 128, 256, 384, 2048])
def test_the_lanes_read_slots_minor_exactly_where_the_rule_says(rng, cap):
    """No chip needed: in the half-step's jaxpr the solver's loops hold the
    ``[e, k, cap]`` block, slots last, for the buckets ``slots_minor`` names,
    and the gathered ``[e, cap, k]`` block for the others; neither holds
    both, and nothing but the static shape chose."""
    import jax

    from photon_ml_tpu.algorithm.mf_coordinate import slots_minor, solve_mf_side_bucket

    e, k = 5, 3
    objective = _logistic_objective()
    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=3)
    bucket = _side_bucket(rng, e, cap, k)
    jaxpr = jax.make_jaxpr(
        lambda *arrays: solve_mf_side_bucket(objective, opt, *arrays))(*bucket.values())
    in_loops: set = set()
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "while":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _float_shapes_in(sub, in_loops)
    assert in_loops, "the solver's loops were not found"
    assert slots_minor(cap) == (cap % 128 == 0)
    assert ((e, k, cap) in in_loops) == slots_minor(cap)
    assert ((e, cap, k) in in_loops) == (not slots_minor(cap))


def test_the_packer_records_the_share_of_slots_that_go_slots_minor():
    """Users of 1 to 4 rows (cap 4) and of 100 (cap 128), items of 130 rows
    capped to the top rung: the gauges and ``slots_minor_fractions`` read the
    share of each side's slots in buckets the half-step lays slots minor."""
    from photon_ml_tpu.telemetry.registry import default_registry

    users = np.concatenate([np.repeat(np.arange(4), np.arange(1, 5)),   # 10 rows
                            np.repeat(np.arange(4, 7), 100)])           # 300 rows
    items = np.arange(310) % 2   # two items of 155 rows: past the top rung
    ds = build_game_dataset(
        labels=np.zeros(310), feature_shards={},
        entity_keys={"user": users.astype(str), "item": items.astype(str)},
        dtype=np.float64)
    mf = build_mf_dataset(ds, "user", "item", bucket_sizes=(4, 128))
    # users: 4 lanes of cap 4 (16 slots), 3 lanes of cap 128 (384 slots)
    # items: 2 lanes of cap 128 (256 slots)
    assert mf.slots_minor_fractions() == (pytest.approx(384 / 400), 1.0)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["mf/user_x_item/row_slots_minor_fraction"] == pytest.approx(0.96)
    assert gauges["mf/user_x_item/col_slots_minor_fraction"] == 1.0
    none = build_mf_dataset(ds, "user", "item", bucket_sizes=(4, 200))
    assert none.slots_minor_fractions() == (0.0, 0.0)
    assert default_registry().snapshot()["gauges"][
        "mf/user_x_item/row_slots_minor_fraction"] == 0.0


@pytest.mark.parametrize("ladder", [(8, 128), (16, 256), (8, 32, 64)],
                         ids=lambda ladder: "ladder" + "-".join(map(str, ladder)))
def test_one_fused_sweep_equals_the_coordinate_path_on_whole_vector_buckets(rng, ladder):
    """As ``test_one_fused_sweep_equals_the_coordinate_path``, on ladders whose
    upper rungs are whole vectors (both layouts in one half-step) and on one
    with none: the fused step's ``_solve_mf`` and ``update_model`` take the
    layout from the same function and give the same two tables."""
    n = 900
    rows, cols, y = _mf_problem(rng, n=n, n_rows=14, n_cols=6, k=3)
    # eight light users of 5 rows and three of 20, so that every rung is filled
    rows[:40] = np.array([f"w{i}" for i in range(8)])[np.arange(40) % 8]
    rows[40:100] = np.array([f"m{i}" for i in range(3)])[np.arange(60) % 3]
    x = rng.normal(size=(n, 4))
    labels = (y + x @ rng.normal(size=4) > 0).astype(np.float64)
    ds = build_game_dataset(
        labels=labels, feature_shards={"global": x},
        entity_keys={"user": rows, "item": cols}, dtype=np.float64)
    mf_dataset = build_mf_dataset(ds, "user", "item", bucket_sizes=ladder)
    caps = {int(b.sample_rows.shape[1])
            for b in mf_dataset.row_buckets + mf_dataset.col_buckets}
    assert caps == set(ladder)
    _assert_fused_sweep_equals_coordinate_path(ds, x, mf_dataset, iterations=6, seed=5)


@pytest.mark.parametrize(
    "method", ["value_and_gradient", "hessian_vector", "hessian_matrix", "hessian_diagonal"])
def test_the_slots_minor_objective_is_the_shared_one_on_the_transposed_block(rng, method):
    """Everything a solver can ask of the lanes' objective over ``[k, cap]``
    features equals the shared objective's answer over ``[cap, k]``."""
    import jax

    from photon_ml_tpu.algorithm.mf_coordinate import _SlotsMinorObjective
    from photon_ml_tpu.data.batch import LabeledPointBatch

    cap, k = 24, 5
    shared = _logistic_objective(0.3)
    slots_minor = _SlotsMinorObjective(shared)
    assert slots_minor != shared and slots_minor == _SlotsMinorObjective(shared)
    batch = LabeledPointBatch(
        features=jnp.asarray(rng.normal(size=(cap, k))),
        labels=jnp.asarray((rng.random(cap) < 0.5).astype(np.float64)),
        offsets=jnp.asarray(rng.normal(size=cap)),
        weights=jnp.asarray(rng.random(cap)))
    w = jnp.asarray(rng.normal(size=k))
    args = (w, jnp.asarray(rng.normal(size=k))) if method == "hessian_vector" else (w,)
    want = getattr(shared, method)(*args, batch)
    got = getattr(slots_minor, method)(*args, batch.replace(features=batch.features.T))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12, atol=1e-14)
