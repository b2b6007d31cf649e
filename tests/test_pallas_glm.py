"""Fused Pallas GLM kernel vs autodiff reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import (
    LogisticLoss,
    PoissonLoss,
    SmoothedHingeLoss,
    SquaredLoss,
)
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.pallas_glm import fused_value_and_gradient


def _batch(n, d, seed=0, binary=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (
        (rng.uniform(size=n) < 0.5).astype(np.float32)
        if binary
        else rng.normal(size=n).astype(np.float32)
    )
    offsets = rng.normal(scale=0.1, size=n).astype(np.float32)
    weights = rng.uniform(0.2, 2.0, size=n).astype(np.float32)
    return LabeledPointBatch.create(x, y, offsets=offsets, weights=weights)


LOSSES = [
    (SquaredLoss(), False),
    (LogisticLoss(), True),
    (PoissonLoss(), False),
    (SmoothedHingeLoss(), True),
]


@pytest.mark.parametrize("loss,binary", LOSSES, ids=lambda p: type(p).__name__ if not isinstance(p, bool) else "")
def test_matches_autodiff(loss, binary):
    batch = _batch(300, 20, binary=binary)  # odd shapes force padding
    w = jnp.asarray(np.random.default_rng(1).normal(size=20).astype(np.float32)) * 0.3
    objective = GLMObjective(loss, l2_weight=0.7)
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, batch)
    v, g = fused_value_and_gradient(loss, w, batch, l2_weight=0.7, interpret=True)
    np.testing.assert_allclose(float(v), float(ref_v), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=2e-4, atol=2e-4)


def test_aligned_shapes():
    batch = _batch(512, 128)
    w = jnp.zeros(128, jnp.float32)
    objective = GLMObjective(SquaredLoss())
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, batch)
    v, g = fused_value_and_gradient(SquaredLoss(), w, batch, interpret=True)
    np.testing.assert_allclose(float(v), float(ref_v), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=1e-4, atol=1e-4)


def test_zero_weight_rows_ignored():
    batch = _batch(64, 8)
    zeroed = batch.replace(weights=batch.weights.at[32:].set(0.0))
    truncated = LabeledPointBatch(
        features=batch.features[:32],
        labels=batch.labels[:32],
        offsets=batch.offsets[:32],
        weights=batch.weights[:32],
    )
    w = jnp.asarray(np.random.default_rng(2).normal(size=8).astype(np.float32))
    v1, g1 = fused_value_and_gradient(SquaredLoss(), w, zeroed, interpret=True)
    v2, g2 = fused_value_and_gradient(SquaredLoss(), w, truncated, interpret=True)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


def test_objective_use_pallas_flag_in_solver():
    """End-to-end: L-BFGS over the pallas objective converges to the same
    solution as the autodiff objective."""
    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

    batch = _batch(256, 16, binary=True)
    w0 = jnp.zeros(16, jnp.float32)
    sols = []
    for use_pallas in (False, True):
        objective = GLMObjective(LogisticLoss(), l2_weight=0.5, use_pallas=use_pallas)
        bound = objective.bind(batch)
        result = minimize_lbfgs(bound.value_and_grad, w0, max_iter=40)
        sols.append(np.asarray(result.coefficients))
    np.testing.assert_allclose(sols[0], sols[1], rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("norm_type", ["SCALE_WITH_STANDARD_DEVIATION", "STANDARDIZATION"])
def test_pallas_normalized_matches_autodiff(norm_type):
    """The kernel supports the normalization algebra (effective coefficients
    + margin shift + Σr chain rule) — same numbers as the autodiff path."""
    from photon_ml_tpu.ops.normalization import NormalizationType, build_normalization

    rng = np.random.default_rng(3)
    batch = _batch(200, 12, binary=True)
    norm = build_normalization(
        NormalizationType[norm_type],
        mean=jnp.asarray(rng.normal(size=12).astype(np.float32)),
        variance=jnp.asarray(rng.uniform(0.5, 4.0, size=12).astype(np.float32)),
        max_magnitude=jnp.ones(12),
        intercept_index=0,
    )
    objective = GLMObjective(LogisticLoss(), l2_weight=0.3,
                             normalization=norm, use_pallas=True)
    w = jnp.asarray(rng.normal(size=12).astype(np.float32)) * 0.4
    v, g = objective.value_and_gradient(w, batch)
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, batch)
    np.testing.assert_allclose(float(v), float(ref_v), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=2e-4, atol=2e-4)


def test_pallas_auto_mode_off_tpu_uses_autodiff():
    """use_pallas=None is 'auto': off-TPU it must resolve to the autodiff
    path (exact f64 numbers on the CPU test mesh)."""
    batch = _batch(64, 8)
    objective = GLMObjective(SquaredLoss(), l2_weight=0.1, use_pallas=None)
    w = jnp.asarray(np.random.default_rng(4).normal(size=8))
    assert not objective._pallas_enabled(w, batch)
    v, g = objective.value_and_gradient(w, batch)
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, batch)
    np.testing.assert_allclose(float(v), float(ref_v), rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=0, atol=0)


def test_bf16_feature_block_matches_f32(monkeypatch):
    """bf16 X with f32 accumulation (VERDICT r3 #2): kernel path parity vs
    the f32 autodiff reference within bf16 rounding tolerance."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(300, 20)).astype(np.float32)
    y = (rng.uniform(size=300) < 0.5).astype(np.float32)
    b32 = LabeledPointBatch.create(x, y)
    bbf = LabeledPointBatch.create(jnp.asarray(x, jnp.bfloat16), y)
    assert bbf.features.dtype == jnp.bfloat16
    # aux columns stay f32 (bf16 applies to the feature block only)
    assert bbf.labels.dtype == jnp.float32
    assert bbf.weights.dtype == jnp.float32
    assert bbf.solve_dtype == jnp.float32
    w = jnp.asarray(rng.normal(size=20).astype(np.float32)) * 0.3
    objective = GLMObjective(LogisticLoss(), l2_weight=0.4)
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, b32)
    v, g = fused_value_and_gradient(
        LogisticLoss(), w, bbf, l2_weight=0.4, interpret=True
    )
    assert g.dtype == jnp.float32
    np.testing.assert_allclose(float(v), float(ref_v), rtol=5e-3)
    # bf16 products: ~0.4% relative rounding per entry, summed over 300
    # rows — scale the tolerance to the gradient's magnitude
    scale = float(np.max(np.abs(np.asarray(ref_g))))
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g),
                               rtol=3e-2, atol=3e-2 * scale)


def test_bf16_autodiff_margins_match_f32():
    """The autodiff path's bf16 matmul (f32 accumulation via
    preferred_element_type) agrees with the f32 objective to bf16
    tolerance, and its value/grad dtypes stay f32."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(200, 12)).astype(np.float32)
    y = rng.normal(size=200).astype(np.float32)
    b32 = LabeledPointBatch.create(x, y)
    bbf = LabeledPointBatch.create(jnp.asarray(x, jnp.bfloat16), y)
    w = jnp.asarray(rng.normal(size=12).astype(np.float32)) * 0.3
    objective = GLMObjective(SquaredLoss(), l2_weight=0.2, use_pallas=False)
    v32, g32 = objective.value_and_gradient(w, b32)
    vbf, gbf = objective.value_and_gradient(w, bbf)
    assert vbf.dtype == jnp.float32 and gbf.dtype == jnp.float32
    np.testing.assert_allclose(float(vbf), float(v32), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(gbf), np.asarray(g32),
                               rtol=5e-2, atol=5e-2)


def test_auto_mode_falls_back_under_vmap(monkeypatch):
    """use_pallas auto/True under vmap must take the autodiff path: vmapped
    lanes (the λ-grid) share X reads in one XLA matmul, and the kernel has
    no lane axis. Pretend we're on TPU so 'auto' would otherwise engage."""
    import photon_ml_tpu.ops.objective as objective_mod

    monkeypatch.setattr(
        objective_mod.jax, "default_backend", lambda: "tpu"
    )
    calls = {"pallas": 0}
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    real = kernel_mod.fused_value_and_gradient

    def spy(*a, **k):
        calls["pallas"] += 1
        return real(*a, **k, interpret=True) if "interpret" not in k else real(*a, **k)

    monkeypatch.setattr(kernel_mod, "fused_value_and_gradient", spy)
    batch = _batch(64, 8)
    objective = GLMObjective(SquaredLoss(), use_pallas=None)
    ws = jnp.asarray(np.random.default_rng(5).normal(size=(3, 8)).astype(np.float32))
    vs, gs = jax.vmap(lambda w: objective.value_and_gradient(w, batch))(ws)
    assert calls["pallas"] == 0  # vmapped: autodiff
    ref_v, ref_g = jax.vmap(lambda w: jax.value_and_grad(objective.value)(w, batch))(ws)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(ref_v), rtol=1e-6)
    # un-vmapped on (pretend) TPU: the kernel engages
    v, g = objective.value_and_gradient(ws[0], batch)
    assert calls["pallas"] == 1


def test_vmap_detection_canary():
    """_under_vmap leans on the private jax._src BatchTracer (a plain
    import: a jax that moves it breaks the build instead of quietly
    switching the one-pass kernel off). Pin that it still discriminates."""
    import photon_ml_tpu.ops.objective as objective_mod

    batch = _batch(16, 4)
    w = jnp.zeros(4)
    assert not objective_mod._under_vmap(w, batch.features)
    seen = []
    jax.vmap(
        lambda w_: seen.append(objective_mod._under_vmap(w_, batch.features))
        or jnp.sum(w_)
    )(jnp.zeros((2, 4)))
    assert seen == [True]


def test_row_tile_fits_budget_and_sublane_packing():
    """Every width the auto rule routes to the kernel gets a row tile that
    is a multiple of the dtype's sublane packing (8 f32 / 16 bf16 — Mosaic
    refuses others) and keeps ONE X tile within the 4 MiB budget whose
    double buffer fits the explicit VMEM limit (the widths from 2048 up did
    not compile on the v5e before)."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    for d_pad in (128, 256, 512, 2048, 4096, 12800, kernel_mod.MAX_KERNEL_DIM):
        for itemsize, sublane in ((4, 8), (2, 16)):
            tile = kernel_mod._row_tile(d_pad, itemsize)
            assert tile % sublane == 0 and tile >= sublane
            assert tile * d_pad * itemsize <= kernel_mod._X_TILE_BYTES
    assert kernel_mod._row_tile(512, 4) == 1024
    assert kernel_mod._row_tile(512, 2) == 2048


def test_kernel_width_limit(monkeypatch):
    """Past MAX_KERNEL_DIM the auto rule keeps the XLA path and a forced
    kernel raises — never a silent fallback."""
    import photon_ml_tpu.ops.objective as objective_mod
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    monkeypatch.setattr(kernel_mod, "MAX_KERNEL_DIM", 128)
    monkeypatch.setattr(objective_mod.jax, "default_backend", lambda: "tpu")
    batch = _batch(32, 200)  # pads to 256 lanes > 128
    w = jnp.zeros(200, jnp.float32)
    assert not GLMObjective(SquaredLoss())._pallas_enabled(w, batch)
    with pytest.raises(ValueError, match="use_pallas=True"):
        GLMObjective(SquaredLoss(), use_pallas=True).value_and_gradient(w, batch)
    assert GLMObjective(SquaredLoss())._pallas_enabled(
        jnp.zeros(8, jnp.float32), _batch(32, 8))


def test_interpret_only_on_cpu(monkeypatch):
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    assert kernel_mod._should_interpret()  # the suite runs on cpu
    monkeypatch.setattr(kernel_mod.jax, "default_backend", lambda: "tpu")
    assert not kernel_mod._should_interpret()
    monkeypatch.setattr(kernel_mod.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kernel_mod._should_interpret()


def test_kernel_traces_are_counted():
    """The registry counters chip_smoke.py reads from a run's journal."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod
    from photon_ml_tpu.telemetry.registry import default_registry

    counter = default_registry().counter(kernel_mod.TRACES_INTERPRETED)
    before = counter.value
    batch = _batch(16, 4)
    kernel_mod.fused_value_and_gradient(SquaredLoss(), jnp.zeros(4), batch)
    assert counter.value == before + 1
