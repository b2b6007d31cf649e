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
    """Every width the auto rule routes to the kernel gets a row tile in whole
    128s: whole sublane packings (8 f32 / 16 bf16; Mosaic refuses others) and,
    since PR 53, a ``(3, tile)`` aux block that ends on a lane boundary (the
    Pallas lowering refuses any other). ONE X tile stays within the 4 MiB
    budget wherever 128 rows do, and its double buffer fits the explicit VMEM
    limit with the widest float32 tile's 8 MiB (tests/test_tpu_compile.py
    compiles those for a described v5e)."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    for d_pad in (128, 256, 512, 1280, 2048, 4096, 8192, 12800, kernel_mod.MAX_KERNEL_DIM):
        for itemsize, sublane in ((4, 8), (2, 16)):
            tile = kernel_mod._row_tile(d_pad, itemsize)
            assert tile % 128 == 0 and tile % sublane == 0 and tile >= 128
            assert tile == 128 or tile * d_pad * itemsize <= kernel_mod._X_TILE_BYTES
            assert 3 * tile * d_pad * itemsize <= kernel_mod._VMEM_LIMIT_BYTES
    assert kernel_mod._row_tile(512, 4) == 1024
    assert kernel_mod._row_tile(512, 2) == 2048
    assert kernel_mod._row_tile(2048, 4) == 512  # the dense cells' tile
    assert kernel_mod._row_tile(12800, 2) == 128  # 163 rows fit: whole 128s
    assert kernel_mod._row_tile(kernel_mod.MAX_KERNEL_DIM, 4) == 128  # 64 fit


def test_kernel_width_limit(monkeypatch):
    """Past MAX_KERNEL_DIM the auto rule keeps the XLA path and a forced
    kernel raises — never a silent fallback."""
    import photon_ml_tpu.data.batch as batch_mod
    import photon_ml_tpu.ops.objective as objective_mod

    monkeypatch.setattr(batch_mod, "MAX_KERNEL_DIM", 128)  # where the rule reads it
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


# -- X as it lies: the last row tile and the last lanes are masked in the kernel


def _ragged_shapes(tile):
    return [
        pytest.param(tile + 300, 256, id="rows"),        # n % tile != 0 only
        pytest.param(tile, 200, id="lanes"),             # d % 128 != 0 only
        pytest.param(tile + 300, 200, id="rows+lanes"),
        pytest.param(300, 20, id="n<tile"),
        pytest.param(tile + 1, 130, id="n=tile+1"),
    ]


RAGGED = [
    pytest.param(*shape.values, dtype, normalized,
                 id=f"{shape.id}-{dtype}{'-normalized' if normalized else ''}")
    for dtype, tile in (("float32", 1024), ("bfloat16", 2048))
    for shape in _ragged_shapes(tile)
    for normalized in (False, True)
]


@pytest.mark.parametrize("n,d,dtype,normalized", RAGGED)
def test_ragged_shapes_match_autodiff_and_the_zero_padded_call(n, d, dtype, normalized):
    """A partial block's out-of-bounds part is undefined on read (the
    interpreter fills it with NaN). Held here: value and gradient against
    autodiff on the same stored features, and BIT FOR BIT against the same
    call on a batch explicitly padded to whole tiles (zero rows of weight 0,
    zero columns with zero coefficients): what the wrapper's ``jnp.pad`` of X
    built before, which the masks have to reproduce exactly."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod
    from photon_ml_tpu.ops.normalization import NormalizationType, build_normalization

    dtype = jnp.dtype(dtype)
    d_pad = kernel_mod._round_up(d, 128)
    tile = kernel_mod._row_tile(d_pad, dtype.itemsize)
    n_pad = kernel_mod._round_up(n, tile)
    assert (n % tile, d % 128) != (0, 0)

    rng = np.random.default_rng(n + d)
    batch = _batch(n, d, seed=n, binary=True)
    batch = batch.replace(features=batch.features.astype(dtype))
    w = jnp.asarray(rng.normal(size=d).astype(np.float32)) * 0.3
    mean = rng.normal(size=d).astype(np.float32)
    variance = rng.uniform(0.5, 4.0, size=d).astype(np.float32)

    def context(width):
        if not normalized:
            return None
        return build_normalization(
            NormalizationType.STANDARDIZATION,
            mean=jnp.pad(jnp.asarray(mean), (0, width - d)),
            variance=jnp.pad(jnp.asarray(variance), (0, width - d),
                             constant_values=1.0),
            max_magnitude=jnp.ones(width), intercept_index=0)

    loss = LogisticLoss()
    v, g = fused_value_and_gradient(
        loss, w, batch, l2_weight=0.7, normalization=context(d), interpret=True)

    stored = batch.replace(features=batch.features.astype(jnp.float32))
    objective = GLMObjective(
        loss, l2_weight=0.7, normalization=context(d), use_pallas=False)
    ref_v, ref_g = jax.value_and_grad(objective.value)(w, stored)
    np.testing.assert_allclose(float(v), float(ref_v), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=2e-4, atol=2e-4)

    rows, cols = (0, n_pad - n), (0, d_pad - d)
    padded = LabeledPointBatch(
        features=jnp.pad(batch.features, (rows, cols)),
        labels=jnp.pad(batch.labels, rows), offsets=jnp.pad(batch.offsets, rows),
        weights=jnp.pad(batch.weights, rows))
    pv, pg = fused_value_and_gradient(
        loss, jnp.pad(w, cols), padded, l2_weight=0.7,
        normalization=context(d_pad), interpret=True)
    assert float(v) == float(pv)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(pg)[:d])
    assert not np.any(np.asarray(pg)[d:])


def _kernel_body(n, d):
    """The jaxpr Mosaic is handed for an [n, d] float32 feature block."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    closed = jax.make_jaxpr(
        lambda x, aux, w: kernel_mod._fused_padded(SquaredLoss(), x, aux, True, w))(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((3, n), jnp.float32),
        jax.ShapeDtypeStruct((kernel_mod._round_up(d, 128),), jnp.float32))
    (call,) = [e for e in closed.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    return str(call.params["jaxpr"])


@pytest.mark.parametrize("n,d,bodies,iotas", [
    pytest.param(2048, 256, 1, 0, id="whole-tiles"),  # the GLMix cells' kind
    pytest.param(2048, 200, 1, 1, id="lanes"),  # a lane mask, on every step
    # rows, in the last step's body: of X along the sublanes, of the per-row values along the lanes
    pytest.param(2000, 256, 2, 2, id="rows"),
    pytest.param(2000, 200, 2, 4, id="rows+lanes"),  # lanes in both, rows in the last
])
def test_masks_follow_from_the_static_shape(n, d, bodies, iotas):
    """No option decides what is masked: ``n % tile`` and ``d % 128`` do, at
    trace time. On whole tiles the kernel holds no mask at all (it is the
    kernel the padded wrapper ran); rows past ``n`` are masked in a second
    body, which only the last grid step runs."""
    body = _kernel_body(n, d)
    assert body.count("reduce_sum[axes=(1,)") == bodies  # one margin sum a body
    assert body.count(" iota[") == iotas  # every mask is a comparison on an iota


@pytest.mark.parametrize("n,d,ragged", [
    pytest.param(1024, 128, 0, id="whole-tiles"),
    pytest.param(1000, 128, 1, id="rows"),
    pytest.param(1024, 100, 1, id="lanes"),
])
def test_ragged_traces_are_counted(n, d, ragged):
    """``traces_ragged`` rises once for every trace of the kernel in which a
    masked body was emitted, and never on whole tiles."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod
    from photon_ml_tpu.telemetry.registry import default_registry

    counter = default_registry().counter(kernel_mod.TRACES_RAGGED)
    kernel_mod._fused_padded.clear_cache()  # a cached trace emits nothing anew
    before = counter.value
    fused_value_and_gradient(PoissonLoss(), jnp.zeros(d), _batch(n, d), interpret=True)
    assert counter.value == before + ragged


def test_an_empty_batch_is_zero():
    batch = _batch(0, 8)
    v, g = fused_value_and_gradient(SquaredLoss(), jnp.ones(8), batch, interpret=True)
    assert float(v) == 0.0 and not np.any(np.asarray(g))


# -- the aux block lies [3, n]: the same body fed the parent's [tile, 3] block


class _Turned:
    """A ``[tile, 3]`` block read as the ``[3, tile]`` one the body takes its
    three rows from."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, index):
        return self._ref[index].T


def _fed_row_wise(loss, x, aux_rows, w):
    """The parent's form, kept here alone: ``aux_rows`` [n, 3] blocked
    ``(tile, 3)`` along the rows beside X, and ``_kernel``'s own body."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod
    from jax.experimental import pallas as pl

    (n, d), d_pad = x.shape, w.shape[0]
    tile = kernel_mod._row_tile(d_pad, x.dtype.itemsize)

    def body(x_ref, aux_ref, *refs):
        kernel_mod._kernel(loss, n, d, x_ref, _Turned(aux_ref), *refs)

    row, scalar = pl.BlockSpec((1, d_pad), lambda i: (0, 0)), pl.BlockSpec((1, 1), lambda i: (0, 0))
    value, grad, rsum = pl.pallas_call(
        body, grid=(pl.cdiv(n, tile),),
        in_specs=[pl.BlockSpec((tile, d_pad), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 3), lambda i: (i, 0)), row],
        out_specs=[scalar, row, scalar],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=True)(x, aux_rows, w.reshape(1, d_pad))
    return value[0, 0], grad[0], rsum[0, 0]


@pytest.mark.parametrize("n,d,dtype", [
    pytest.param(4096, 256, "float32", id="whole-tiles"),
    pytest.param(3000, 200, "float32", id="rows+lanes"),
    pytest.param(300, 20, "float32", id="n<tile"),
    pytest.param(1025, 130, "float32", id="n=tile+1"),
    pytest.param(4096, 256, "bfloat16", id="whole-tiles-bfloat16"),
    pytest.param(3000, 200, "bfloat16", id="rows+lanes-bfloat16"),
    pytest.param(520, 12800, "float32", id="d12800-tile-128"),
])
def test_the_lane_wise_block_gives_the_row_wise_blocks_sums_bit_for_bit(n, d, dtype):
    """Value, gradient and Σr of ``_fused_padded`` on the ``[3, n]`` block
    against the same body fed the ``[n, 3]`` block in ``(tile, 3)`` pieces:
    the orientation moves bytes, no arithmetic. A ragged last block is NaN
    past the array's edge along the lanes now, along the rows then. (That the
    pointwise work on ``[1, tile]`` rows gives the parent's bits, whose body
    worked on ``[tile, 1]`` columns, is the chip's to show: PERF.md 6, PR 53.)"""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod

    batch = _batch(n, d, seed=n + d, binary=True)
    x = batch.features.astype(jnp.dtype(dtype))
    w = jnp.pad(jnp.asarray(np.random.default_rng(d).normal(size=d).astype(np.float32)) * 0.3,
                (0, kernel_mod._round_up(d, 128) - d))
    aux_rows = jnp.stack([batch.labels, batch.offsets, batch.weights], axis=1)
    new = kernel_mod._fused_padded(LogisticLoss(), x, aux_rows.T, True, w)
    old = _fed_row_wise(LogisticLoss(), x, aux_rows, w)
    assert np.all(np.isfinite(np.asarray(new[1])))
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_operands_are_prepared_in_one_place_and_hold_no_row_wise_block():
    """Both public functions go through ``_operands``: the aux block of either
    is ``[3, n]`` float32 with the offsets moved by the shift term, and no
    ``[n, 3]`` array is built on the way to either kernel."""
    import photon_ml_tpu.ops.pallas_glm as kernel_mod
    from photon_ml_tpu.ops.normalization import NormalizationContext
    from photon_ml_tpu.ops.pallas_glm import fused_hessian_vector

    n, d = 300, 20
    batch = _batch(n, d, binary=True)
    rng = np.random.default_rng(9)
    context = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d).astype(np.float32)),
        shifts=jnp.asarray(rng.normal(size=d).astype(np.float32)))
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    x, aux, (eff,), factors, shifts = kernel_mod._operands(batch, context, w)
    assert x is batch.features and aux.shape == (3, n) and aux.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(aux[0]), np.asarray(batch.labels))
    np.testing.assert_array_equal(np.asarray(aux[2]), np.asarray(batch.weights))
    np.testing.assert_array_equal(
        np.asarray(aux[1]), np.asarray(batch.offsets - jnp.dot(w * factors, shifts)))
    np.testing.assert_array_equal(np.asarray(eff), np.asarray(w * factors))
    programs = [
        jax.make_jaxpr(lambda w_: fused_value_and_gradient(
            LogisticLoss(), w_, batch, normalization=context, interpret=True))(w),
        jax.make_jaxpr(lambda w_: fused_hessian_vector(
            LogisticLoss(), w_, w_, batch, normalization=context, interpret=True))(w)]
    for program in programs:
        shapes = {tuple(v.aval.shape) for eqn in program.jaxpr.eqns for v in eqn.outvars}
        assert (3, n) in shapes and (n, 3) not in shapes
