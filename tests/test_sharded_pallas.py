"""The single-pass GLM kernel under mesh sharding (VERDICT r4 #1).

The reference's one-pass seqOp runs on every executor and merges with
treeAggregate (ValueAndGradientAggregator.scala:133-154, :236-251); here the
same composition is a shard_map running the Pallas kernel per device with a
psum combine (parallel/sharded_dense.py). These tests pin, on the 8-device
virtual CPU mesh (kernel in interpret mode):

- objective agreement: sharded value/grad/Hv == the unsharded objective,
  for both the kernel and the autodiff local path, with normalization;
- solver agreement: LBFGS and TRON through the sharded objective match the
  unsharded solve;
- program agreement: the fused GAME sweep on a multi-device mesh with the
  kernel active matches the single-device sweep (the r4 gate that hard-
  disabled the kernel under sharding is gone);
- the non-divisible-rows padding path.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tests.conftest import make_classification
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import (
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    OptimizerType,
    solve,
)
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainProgram,
    RandomEffectStepSpec,
    train_distributed,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.parallel.sharded_dense import ShardedDenseGLMObjective
from photon_ml_tpu.types import TaskType


def _batch(rng, n=64, d=16, dtype=np.float32):
    x, y, _ = make_classification(rng, n=n, d=d, dtype=dtype)
    return LabeledPointBatch(
        features=jnp.asarray(x, dtype),
        labels=jnp.asarray(y, dtype),
        offsets=jnp.asarray(rng.normal(size=n) * 0.1, dtype),
        weights=jnp.asarray(rng.uniform(0.5, 1.5, size=n), dtype),
    )


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("normalized", [False, True])
def test_sharded_objective_matches_unsharded(rng, use_pallas, normalized):
    d = 16
    batch = _batch(rng, n=64, d=d)
    norm = None
    if normalized:
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d), jnp.float32),
            shifts=jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32),
        )
    mesh = make_mesh(data=8, model=1)
    ref = GLMObjective(LogisticLoss(), l2_weight=0.3, normalization=norm,
                       use_pallas=False)
    sharded = ShardedDenseGLMObjective(
        LogisticLoss(), mesh, l2_weight=0.3, normalization=norm,
        use_pallas=use_pallas,
    )
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    v = jnp.asarray(rng.normal(size=d), jnp.float32)

    v_ref, g_ref = ref.value_and_gradient(w, batch)
    v_sh, g_sh = sharded.value_and_gradient(w, batch)
    # interpret-mode kernel is f32 with a different reduction order
    tol = dict(rtol=2e-4, atol=2e-5) if use_pallas else dict(rtol=1e-5)
    np.testing.assert_allclose(float(v_sh), float(v_ref), **tol)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref), **tol)

    np.testing.assert_allclose(
        float(sharded.value(w, batch)), float(ref.value(w, batch)), **tol
    )
    # Hv follows the local objective's path as value_and_gradient does: the
    # one-pass product kernel per device, or the jvp (TRON's CG ladder)
    np.testing.assert_allclose(
        np.asarray(sharded.hessian_vector(w, v, batch)),
        np.asarray(ref.hessian_vector(w, v, batch)),
        **tol,
    )


def test_sharded_objective_bf16_block(rng):
    """A bf16 feature block through the per-device kernel (the product
    path wired by dtype=bf16): accuracy within the BASELINE.md bf16 table
    scale."""
    import ml_dtypes

    x, y, _ = make_classification(rng, n=64, d=16, dtype=np.float32)
    batch32 = LabeledPointBatch(
        features=jnp.asarray(x), labels=jnp.asarray(y),
        offsets=jnp.zeros(64, jnp.float32), weights=jnp.ones(64, jnp.float32),
    )
    batch16 = batch32.replace(
        features=jnp.asarray(x.astype(ml_dtypes.bfloat16))
    )
    mesh = make_mesh(data=8, model=1)
    ref = GLMObjective(LogisticLoss(), l2_weight=0.2, use_pallas=False)
    sharded = ShardedDenseGLMObjective(
        LogisticLoss(), mesh, l2_weight=0.2, use_pallas=True
    )
    w = jnp.asarray(rng.normal(size=16), jnp.float32)
    v_ref, g_ref = ref.value_and_gradient(w, batch32)
    v_sh, g_sh = sharded.value_and_gradient(w, batch16)
    assert g_sh.dtype == jnp.float32  # accumulation stays f32
    np.testing.assert_allclose(float(v_sh), float(v_ref), rtol=5e-3)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                               rtol=5e-2, atol=5e-3)


def test_sharded_objective_pads_non_divisible_rows(rng):
    """61 rows over 8 devices: the wrapper pads with zero-weight rows."""
    batch = _batch(rng, n=61, d=8)
    mesh = make_mesh(data=8, model=1)
    ref = GLMObjective(LogisticLoss(), l2_weight=0.1, use_pallas=False)
    sharded = ShardedDenseGLMObjective(
        LogisticLoss(), mesh, l2_weight=0.1, use_pallas=True
    )
    w = jnp.asarray(rng.normal(size=8), jnp.float32)
    v_ref, g_ref = ref.value_and_gradient(w, batch)
    v_sh, g_sh = sharded.value_and_gradient(w, batch)
    np.testing.assert_allclose(float(v_sh), float(v_ref), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "opt_type", [OptimizerType.LBFGS, OptimizerType.TRON]
)
def test_sharded_solve_matches_unsharded(rng, opt_type):
    batch = _batch(rng, n=128, d=8)
    mesh = make_mesh(data=8, model=1)
    cfg = OptimizerConfig(optimizer_type=opt_type, max_iterations=12)
    ref = GLMObjective(LogisticLoss(), l2_weight=0.5, use_pallas=False)
    sharded = ShardedDenseGLMObjective(
        LogisticLoss(), mesh, l2_weight=0.5, use_pallas=True
    )
    w0 = jnp.zeros(8, jnp.float32)
    w_ref = solve(cfg, ref.bind(batch), w0).coefficients
    w_sh = solve(cfg, sharded.bind(batch), w0).coefficients
    np.testing.assert_allclose(np.asarray(w_sh), np.asarray(w_ref),
                               rtol=5e-3, atol=5e-4)


def test_fused_sweep_kernel_active_on_mesh_matches_single_device(rng):
    """The r4 gate is lifted: a multi-device fused program with
    use_pallas_fe=True runs the kernel per-shard (interpret mode here) and
    must reproduce the single-device autodiff sweep."""
    n, d_fe, d_re = 128, 16, 4
    users = np.array([f"u{i}" for i in rng.integers(0, 10, size=n)])
    x_fe = rng.normal(size=(n, d_fe)).astype(np.float32)
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ds = build_game_dataset(
        labels=y, feature_shards={"global": x_fe, "per": x_re},
        entity_keys={"user": users},
    )
    opt = OptimizerConfig(max_iterations=8)

    def run(mesh, use_pallas_fe):
        re_ds = {"user": build_random_effect_dataset(ds, "user", "per",
                                                     bucket_sizes=(32,))}
        program = GameTrainProgram(
            TaskType.LOGISTIC_REGRESSION,
            FixedEffectStepSpec("global", opt, l2_weight=0.5),
            (RandomEffectStepSpec("user", "per", opt, l2_weight=0.5),),
            use_pallas_fe=use_pallas_fe,
            mesh=mesh,
        )
        state, losses = train_distributed(
            program, ds, re_ds, mesh=mesh, num_iterations=2
        )
        return np.asarray(state.fe_coefficients), np.asarray(losses)

    fe1, losses1 = run(None, False)
    mesh = make_mesh(data=8, model=1)
    fe8, losses8 = run(mesh, True)
    np.testing.assert_allclose(fe8, fe1, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(losses8, losses1, rtol=1e-4)


def test_program_builds_sharded_objective_only_when_eligible(rng):
    opt = OptimizerConfig(max_iterations=2)
    fe = FixedEffectStepSpec("global", opt, l2_weight=0.1)
    mesh = make_mesh(data=8, model=1)

    p = GameTrainProgram(TaskType.LOGISTIC_REGRESSION, fe, (), mesh=mesh)
    assert p._fe_sharded_objective is not None

    # feature-sharded FE: the column-sharded/sparse path owns it
    p = GameTrainProgram(TaskType.LOGISTIC_REGRESSION, fe, (), mesh=mesh,
                         fe_feature_sharded=True)
    assert p._fe_sharded_objective is None

    # explicit off
    p = GameTrainProgram(TaskType.LOGISTIC_REGRESSION, fe, (), mesh=mesh,
                         use_pallas_fe=False)
    assert p._fe_sharded_objective is None

    # no mesh: conservative default (batches may be GSPMD-sharded later)
    p = GameTrainProgram(TaskType.LOGISTIC_REGRESSION, fe, ())
    assert p._fe_sharded_objective is None
    assert p._fe_objective.use_pallas is False
