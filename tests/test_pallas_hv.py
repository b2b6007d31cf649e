"""The one-pass Hessian-vector kernel (ops/pallas_glm.fused_hessian_vector) in
the interpreter: against the jvp of the gradient and a float64 numpy product,
its masks, its gate in GLMObjective.hessian_vector and its counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu.data.batch as batch_mod
import photon_ml_tpu.ops.objective as objective_mod
import photon_ml_tpu.ops.pallas_glm as kernel_mod
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.pallas_glm import fused_hessian_vector
from photon_ml_tpu.telemetry.registry import default_registry

L2 = 0.7
#: the loss's second derivative in float64 numpy
D2 = {
    LogisticLoss: lambda m: 1 / (1 + np.exp(-m)) * (1 - 1 / (1 + np.exp(-m))),
    SquaredLoss: np.ones_like,
    PoissonLoss: np.exp,
}
#: (tile, d) pairs: one 1024-row tile of float32 at 256 lanes, 2048 of bfloat16
SHAPES = {
    "whole": lambda tile: (tile, 256),
    "rows": lambda tile: (tile + 300, 256),       # n % tile != 0 only
    "lanes": lambda tile: (tile, 200),            # d % 128 != 0 only
    "rows+lanes": lambda tile: (tile + 300, 200),
    "n<tile": lambda tile: (300, 20),
}
NORMALIZATIONS = ("none", "factors", "shifts", "both")


def _problem(n, d, dtype="float32", seed=0, zero_weights=True):
    """A batch with offsets, and weights a fifth of which are zero; counts for
    the labels so that every loss takes them; ``w`` small enough for exp."""
    rng = np.random.default_rng(seed + n + d)
    x = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    y = rng.integers(0, 2, size=n).astype(np.float32)
    weights = rng.uniform(0.2, 2.0, size=n).astype(np.float32)
    if zero_weights:
        weights[rng.random(n) < 0.2] = 0.0
    batch = LabeledPointBatch.create(
        jnp.asarray(x, jnp.dtype(dtype)), y,
        offsets=rng.normal(scale=0.1, size=n).astype(np.float32), weights=weights)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    return batch, w, v


def _normalization(kind, d, seed=1):
    rng = np.random.default_rng(seed + d)
    factors = jnp.asarray(rng.uniform(0.5, 2.0, size=d).astype(np.float32))
    shifts = jnp.asarray((rng.normal(size=d) / np.sqrt(d)).astype(np.float32))
    return {"none": None,
            "factors": NormalizationContext(factors=factors),
            "shifts": NormalizationContext(shifts=shifts),
            "both": NormalizationContext(factors=factors, shifts=shifts)}[kind]


def _float64_product(loss, batch, w, v, normalization, l2):
    """``factors * (X - shifts)' D (X - shifts) factors v + l2 v`` on the
    features as stored, every sum in float64."""
    f64 = lambda a: np.asarray(a, np.float64)
    x = f64(batch.features.astype(jnp.float32))
    if normalization is not None and normalization.shifts is not None:
        x = x - f64(normalization.shifts)
    if normalization is not None and normalization.factors is not None:
        x = x * f64(normalization.factors)
    d2 = f64(batch.weights) * D2[type(loss)](x @ f64(w) + f64(batch.offsets))
    return x.T @ (d2 * (x @ f64(v))) + l2 * f64(v)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


PARITY = [
    pytest.param(shape, dtype, LogisticLoss(), normalization,
                 id=f"{shape}-{dtype}-logistic-{normalization}")
    for dtype in ("float32", "bfloat16")
    for shape in SHAPES
    for normalization in ("none", "both")
] + [
    pytest.param("rows+lanes", "float32", loss, normalization,
                 id=f"rows+lanes-float32-{type(loss).__name__}-{normalization}")
    for loss in (LogisticLoss(), SquaredLoss(), PoissonLoss())
    for normalization in NORMALIZATIONS
    if not (isinstance(loss, LogisticLoss) and normalization in ("none", "both"))
]


@pytest.mark.parametrize("shape,dtype,loss,normalization", PARITY)
def test_the_product_is_the_jvps_and_float64s(shape, dtype, loss, normalization):
    """Offsets, zero weights and ``l2_weight`` > 0 in every case. Against
    float64 on the stored features the kernel's float32 sums stand at 1e-6
    whatever the block's dtype (a bfloat16 block is multiplied as stored);
    the jvp path rounds ``w`` and ``v`` to bfloat16 for such a block
    (ops/objective.py ``margins``), so against IT the band is bfloat16's."""
    tile = 1024 if dtype == "float32" else 2048
    n, d = SHAPES[shape](tile)
    batch, w, v = _problem(n, d, dtype)
    context = _normalization(normalization, d)
    hv = fused_hessian_vector(loss, w, v, batch, l2_weight=L2,
                              normalization=context, interpret=True)
    assert hv.shape == (d,) and hv.dtype == w.dtype
    assert _rel(hv, _float64_product(loss, batch, w, v, context, L2)) < 2e-6
    reference = GLMObjective(loss, l2_weight=L2, normalization=context,
                             use_pallas=False)
    band = 2e-6 if dtype == "float32" else 2e-2
    assert _rel(hv, reference.hessian_vector(w, v, batch)) < band


RAGGED = [pytest.param(shape, dtype, normalization, id=f"{shape}-{dtype}-{normalization}")
          for dtype in ("float32", "bfloat16")
          for shape in SHAPES if shape != "whole"
          for normalization in ("none", "both")]


@pytest.mark.parametrize("shape,dtype,normalization", RAGGED)
def test_a_ragged_product_is_the_zero_padded_calls_bit_for_bit(shape, dtype, normalization):
    """A partial block's out-of-bounds part is NaN in the interpreter: a mask
    that is missing, or a multiply by zero in a select's place, shows as NaN.
    The masks put zeros exactly where padding the batch to whole tiles would
    (zero rows of weight 0, zero columns under zero coefficients)."""
    dtype = jnp.dtype(dtype)
    n, d = SHAPES[shape](1024 if dtype == jnp.float32 else 2048)
    d_pad = kernel_mod._round_up(d, 128)
    n_pad = kernel_mod._round_up(n, kernel_mod._row_tile(d_pad, dtype.itemsize))
    batch, w, v = _problem(n, d, dtype.name)
    hv = fused_hessian_vector(
        LogisticLoss(), w, v, batch, l2_weight=L2,
        normalization=_normalization(normalization, d), interpret=True)
    assert np.all(np.isfinite(np.asarray(hv)))

    rows, cols = (0, n_pad - n), (0, d_pad - d)
    padded = LabeledPointBatch(
        features=jnp.pad(batch.features, (rows, cols)),
        labels=jnp.pad(batch.labels, rows), offsets=jnp.pad(batch.offsets, rows),
        weights=jnp.pad(batch.weights, rows))
    context = _normalization(normalization, d)
    if context is not None:
        context = NormalizationContext(
            factors=jnp.pad(context.factors, cols, constant_values=1.0),
            shifts=jnp.pad(context.shifts, cols))
    padded_hv = fused_hessian_vector(
        LogisticLoss(), jnp.pad(w, cols), jnp.pad(v, cols), padded, l2_weight=L2,
        normalization=context, interpret=True)
    np.testing.assert_array_equal(np.asarray(hv), np.asarray(padded_hv)[:d])
    assert not np.any(np.asarray(padded_hv)[d:])


def _kernel_body(n, d):
    """The jaxpr Mosaic is handed for an [n, d] float32 feature block."""
    d_pad = kernel_mod._round_up(d, 128)
    closed = jax.make_jaxpr(
        lambda x, aux, w, v, z: kernel_mod._hv_one_pass(
            SquaredLoss(), x, aux, True, w, v, z))(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((3, n), jnp.float32),
        jax.ShapeDtypeStruct((d_pad,), jnp.float32),
        jax.ShapeDtypeStruct((d_pad,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32))
    (call,) = [e for e in closed.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    return str(call.params["jaxpr"])


@pytest.mark.parametrize("n,d,bodies,iotas", [
    pytest.param(2048, 256, 1, 0, id="whole-tiles"),
    pytest.param(2048, 200, 1, 1, id="lanes"),  # a lane mask, on every step
    # rows, in the last step's body: of X along the sublanes, of the per-row values along the lanes
    pytest.param(2000, 256, 2, 2, id="rows"),
    pytest.param(2000, 200, 2, 4, id="rows+lanes"),  # lanes in both, rows in the last
])
def test_the_products_masks_follow_from_the_static_shape(n, d, bodies, iotas):
    """The gradient kernel's rule: ``n % tile`` and ``d % 128`` decide at trace
    time, whole tiles hold no mask, rows past ``n`` are masked in a second
    body. A body reduces along the lanes twice (``x w`` and ``x v``) and reads
    X from its block once."""
    body = _kernel_body(n, d)
    assert body.count("reduce_sum[axes=(1,)") == 2 * bodies
    assert body.count(" iota[") == iotas


def test_rows_of_weight_zero_and_an_empty_batch_add_nothing():
    batch, w, v = _problem(64, 8, zero_weights=False)
    zeroed = batch.replace(weights=batch.weights.at[32:].set(0.0))
    head = LabeledPointBatch(batch.features[:32], batch.labels[:32],
                             batch.offsets[:32], batch.weights[:32])
    product = lambda b: np.asarray(
        fused_hessian_vector(PoissonLoss(), w, v, b, interpret=True))
    np.testing.assert_allclose(product(zeroed), product(head), rtol=1e-6)
    empty = LabeledPointBatch(batch.features[:0], batch.labels[:0],
                              batch.offsets[:0], batch.weights[:0])
    assert not np.any(product(empty))
    np.testing.assert_array_equal(
        np.asarray(fused_hessian_vector(PoissonLoss(), w, v, empty, l2_weight=L2,
                                        interpret=True)), L2 * np.asarray(v))


# -- the gate: GLMObjective.hessian_vector under value_and_gradient's rule


@pytest.fixture
def kernel_calls(monkeypatch):
    """Counts the calls that reach the kernel, and lets them through."""
    calls = []
    real = kernel_mod.fused_hessian_vector

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return real(*args, **kwargs, interpret=True)

    monkeypatch.setattr(kernel_mod, "fused_hessian_vector", spy)
    return calls


GATE = [
    # (id, backend, objective's keywords, vmapped operand, kernel taken)
    pytest.param("tpu", {}, None, True, id="auto-on-tpu"),
    pytest.param("cpu", {}, None, False, id="auto-off-tpu"),
    pytest.param("cpu", {"use_pallas": True}, None, True, id="forced-on-cpu"),
    pytest.param("tpu", {"use_pallas": False}, None, False, id="use_pallas=False"),
    pytest.param("tpu", {"axis_name": "data"}, None, False, id="axis_name"),
    pytest.param("tpu", {}, "coefficients", False, id="vmap-over-coefficients"),
    pytest.param("tpu", {}, "vector", False, id="vmap-over-the-vector"),
    pytest.param("tpu", {"use_pallas": True}, "vector", False, id="forced-under-vmap"),
]


@pytest.mark.parametrize("backend,keywords,vmapped,taken", GATE)
def test_the_product_takes_the_kernel_where_the_gradient_does(
        monkeypatch, kernel_calls, backend, keywords, vmapped, taken):
    monkeypatch.setattr(objective_mod.jax, "default_backend", lambda: backend)
    batch, w, v = _problem(64, 8)
    objective = GLMObjective(LogisticLoss(), l2_weight=L2, **keywords)
    reference = GLMObjective(LogisticLoss(), l2_weight=L2, use_pallas=False)
    if "axis_name" in keywords:  # a named axis without a vmap: traced is enough
        jax.make_jaxpr(lambda w_, v_: objective.hessian_vector(w_, v_, batch),
                       axis_env=[("data", 1)])(w, v)
        assert not kernel_calls
        return
    if vmapped is None:
        hv = objective.hessian_vector(w, v, batch)
        expected = reference.hessian_vector(w, v, batch)
    else:
        ws = jnp.stack([w, 2 * w]) if vmapped == "coefficients" else w
        vs = jnp.stack([v, 2 * v]) if vmapped == "vector" else v
        axes = (0 if vmapped == "coefficients" else None,
                0 if vmapped == "vector" else None)
        hv = jax.vmap(lambda w_, v_: objective.hessian_vector(w_, v_, batch), axes)(ws, vs)
        expected = jax.vmap(
            lambda w_, v_: reference.hessian_vector(w_, v_, batch), axes)(ws, vs)
    assert bool(kernel_calls) is taken
    assert _rel(hv, expected) < 2e-6
    if taken:
        assert kernel_calls[0] == {"l2_weight": L2,
                                   "normalization": objective.normalization}


def test_the_product_follows_the_kernels_width_limit(monkeypatch, kernel_calls):
    """Past MAX_KERNEL_DIM the auto rule keeps the jvp and a forced kernel
    raises: ``value_and_gradient``'s rule, through the same ``_pallas_enabled``."""
    monkeypatch.setattr(batch_mod, "MAX_KERNEL_DIM", 128)  # where the rule reads it
    monkeypatch.setattr(objective_mod.jax, "default_backend", lambda: "tpu")
    batch, w, v = _problem(32, 200)  # pads to 256 lanes > 128
    hv = GLMObjective(SquaredLoss()).hessian_vector(w, v, batch)
    assert not kernel_calls
    assert _rel(hv, GLMObjective(SquaredLoss(), use_pallas=False)
                .hessian_vector(w, v, batch)) == 0.0
    with pytest.raises(ValueError, match="use_pallas=True"):
        GLMObjective(SquaredLoss(), use_pallas=True).hessian_vector(w, v, batch)
    batch, w, v = _problem(32, 8)
    GLMObjective(SquaredLoss()).hessian_vector(w, v, batch)
    assert len(kernel_calls) == 1


COUNTED = [
    # (n, d, interpret, counter that rises, ragged traces)
    pytest.param(1024, 128, True, kernel_mod.HV_TRACES_INTERPRETED, 0, id="whole-tiles"),
    pytest.param(1000, 128, True, kernel_mod.HV_TRACES_INTERPRETED, 1, id="rows"),
    pytest.param(1024, 100, None, kernel_mod.HV_TRACES_INTERPRETED, 1, id="lanes-auto"),
]


@pytest.mark.parametrize("n,d,interpret,rises,ragged", COUNTED)
def test_product_traces_are_counted_apart_from_the_gradients(n, d, interpret, rises, ragged):
    """``hv_traces_interpreted`` / ``hv_traces_compiled`` once an evaluation
    site traced, ``hv_traces_ragged`` once a trace of the kernel that masked;
    the gradient kernel's three stay where they were."""
    names = [kernel_mod.HV_TRACES_COMPILED, kernel_mod.HV_TRACES_INTERPRETED,
             kernel_mod.HV_TRACES_RAGGED, kernel_mod.TRACES_COMPILED,
             kernel_mod.TRACES_INTERPRETED, kernel_mod.TRACES_RAGGED]
    read = lambda: {name: default_registry().counter(name).value for name in names}
    kernel_mod._hv_one_pass.clear_cache()  # a cached trace emits nothing anew
    before = read()
    batch, w, v = _problem(n, d)
    fused_hessian_vector(PoissonLoss(), w, v, batch, interpret=interpret)
    expected = dict(before)
    expected[rises] += 1
    expected[kernel_mod.HV_TRACES_RAGGED] += ragged
    assert read() == expected


def test_a_compiled_trace_is_counted_as_compiled(monkeypatch):
    """On ``tpu`` the kernel is never interpreted: the counter a run's journal
    shows. Traced only (Mosaic does not lower for the CPU)."""
    monkeypatch.setattr(kernel_mod.jax, "default_backend", lambda: "tpu")
    counter = default_registry().counter(kernel_mod.HV_TRACES_COMPILED)
    before = counter.value
    batch, w, v = _problem(64, 8)
    jax.make_jaxpr(lambda w_, v_: fused_hessian_vector(LogisticLoss(), w_, v_, batch))(w, v)
    assert counter.value == before + 1


# -- the aux block lies [3, n]: the same body fed the parent's [tile, 3] block


class _Turned:
    """A ``[tile, 3]`` block read as the ``[3, tile]`` one the body takes its
    three rows from."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, index):
        return self._ref[index].T


def _fed_row_wise(loss, x, aux_rows, w, v, zshift):
    """The parent's form, kept here alone: ``aux_rows`` [n, 3] blocked
    ``(tile, 3)`` along the rows beside X, and ``_hv_kernel``'s own body."""
    from jax.experimental import pallas as pl

    (n, d), d_pad = x.shape, w.shape[0]
    tile = kernel_mod._row_tile(d_pad, x.dtype.itemsize)

    def body(zshift_ref, x_ref, aux_ref, *refs):
        kernel_mod._hv_kernel(loss, n, d, zshift_ref, x_ref, _Turned(aux_ref), *refs)

    row, scalar = pl.BlockSpec((1, d_pad), lambda i: (0, 0)), pl.BlockSpec((1, 1), lambda i: (0, 0))
    acc, usum = pl.pallas_call(
        body, grid=(pl.cdiv(n, tile),),
        in_specs=[scalar, pl.BlockSpec((tile, d_pad), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 3), lambda i: (i, 0)), row, row],
        out_specs=[row, scalar],
        out_shape=[jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=True)(zshift.reshape(1, 1), x, aux_rows, w.reshape(1, d_pad),
                        v.reshape(1, d_pad))
    return acc[0], usum[0, 0]


@pytest.mark.parametrize("n,d,dtype", [
    pytest.param(4096, 256, "float32", id="whole-tiles"),
    pytest.param(3000, 200, "float32", id="rows+lanes"),
    pytest.param(300, 20, "float32", id="n<tile"),
    pytest.param(4096, 256, "bfloat16", id="whole-tiles-bfloat16"),
    pytest.param(3000, 200, "bfloat16", id="rows+lanes-bfloat16"),
    pytest.param(520, 12800, "float32", id="d12800-tile-128"),
])
def test_the_lane_wise_block_gives_the_row_wise_blocks_product_bit_for_bit(n, d, dtype):
    """``X'u`` and Σu of ``_hv_one_pass`` on the ``[3, n]`` block against the
    same body fed the ``[n, 3]`` block in ``(tile, 3)`` pieces."""
    batch, w, v = _problem(n, d, dtype)
    lanes = (0, kernel_mod._round_up(d, 128) - d)
    w, v = jnp.pad(w, lanes), jnp.pad(v, lanes)
    zshift = jnp.float32(0.25)
    aux_rows = jnp.stack([batch.labels, batch.offsets, batch.weights], axis=1)
    new = kernel_mod._hv_one_pass(
        LogisticLoss(), batch.features, aux_rows.T, True, w, v, zshift)
    old = _fed_row_wise(LogisticLoss(), batch.features, aux_rows, w, v, zshift)
    assert np.all(np.isfinite(np.asarray(new[0])))
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
