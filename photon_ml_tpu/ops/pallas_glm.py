"""Pallas TPU kernel: fused GLM value + gradient in one pass over X.

This is the reference's hot loop (ValueAndGradientAggregator.scala:133-177 —
per-sample margin dot product, pointwise loss, axpy accumulation, merged
tree-wise) as a single Pallas kernel: each row tile streams through VMEM
once; the margin matvec, the pointwise loss/derivative, and the gradient
accumulation all consume the tile while it is resident, so X crosses HBM
once per evaluation where the autodiff/XLA path reads it twice (forward
margin matvec + backward transpose matvec — XLA does not fuse them into one
read, as the timings below show).

Measured on one TPU v5e under jax 0.9.0 (chip run of PR 21; ms per
evaluation inside one jitted 64-step scan, gradient error against an f64
numpy recomputation):

- f32, d=512, n=262144: 0.92 ms (583 GB/s of X) vs 1.63 ms for the
  autodiff path (two X passes) — 1.8x; d=4096, n=32768: 0.74 vs 1.45 ms.
- Both matvecs are VPU multiply + lane/sublane reductions with f32
  products, for f32 and bf16 tiles alike. An MXU variant ([tile,d]@[d,1]
  margins, [1,tile]@[tile,d] gradient) was no faster at d >= 512 (0.92 vs
  0.92 ms) and, at Mosaic's default contraction precision, rounds the f32
  operands to one bf16 pass: its gradient sat 1.7e-3 (relative) off the f64
  value where this path is at 2.5e-7 and XLA's autodiff at 4e-6. With
  ``Precision.HIGHEST`` the MXU variant matched the accuracy at 1.8x the
  time. So there is one path.
- The per-sample columns ride as ONE [tile, 3] block (labels | offsets |
  weights): three separate [tile, 1] inputs each cost a narrow DMA per grid
  step that outweighed the X stream.
- From d_pad = 2048 up the double-buffered X tile plus the lane-padded aux
  block passed v5e's 16 MiB default scoped VMEM ("scoped allocation 16.01M,
  limit 16.00M"); the tile budget and the explicit limit below are what
  made d_pad in {2048, 4096, 12800, 16384} x {f32, bf16} compile.

Accumulator outputs (value, gradient, Σr) map to the same block every grid
step, making them sequential accumulators (TPU grids are serialized),
initialized at step 0. Padding rows carry weight 0 and padded feature /
coefficient columns are 0, so they contribute nothing.

On the ``cpu`` platform the kernel runs in Pallas interpret mode so the
same code path is testable there; on ``tpu`` it is always compiled by
Mosaic; any other platform is an error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.telemetry.registry import default_registry

Array = jax.Array

_LANE = 128  # TPU lane width: last dim of every tile
_X_TILE_BYTES = 4 * 1024 * 1024  # target VMEM footprint for ONE X tile
#: scoped-VMEM limit handed to Mosaic. The pipeline double-buffers the X
#: tile (2 x 4 MiB), the [tile, 3] aux block pads to 128 lanes, the
#: products materialize f32 [tile, d_pad] temporaries, and w / grad ride as
#: sublane-padded [8, d_pad] blocks — together past v5e's 16 MiB default
#: from d_pad = 2048 up (measured: "scoped allocation 16.01M, limit
#: 16.00M"). 32 MiB covers every width up to MAX_KERNEL_DIM with room;
#: v5e has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: widest (lane-padded) feature block the kernel takes. Past it the
#: resident w / grad blocks alone crowd the row tile down to a few
#: sublanes; the auto rule (ops/objective.py) keeps wider dense blocks on
#: the XLA path, and forcing the kernel there raises. Every width at or
#: under it is compiled on the chip by chip_smoke.py's kernel leg.
MAX_KERNEL_DIM = 16384
#: registry counters bumped once per TRACE of the kernel into a program —
#: how a run's journal shows that its FE solve held the Mosaic-compiled
#: kernel (and never the interpreter) without anyone reading HLO
TRACES_COMPILED = "ops/pallas_glm/traces_compiled"
TRACES_INTERPRETED = "ops/pallas_glm/traces_interpreted"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def kernel_supports(num_features: int) -> bool:
    """Whether a dense block this wide is in the kernel's compiled range."""
    return _round_up(num_features, _LANE) <= MAX_KERNEL_DIM


def _row_tile(d_pad: int, itemsize: int) -> int:
    """Rows per grid step: the measured optima with the packed-aux layout
    at d=512 (1024 rows f32 / 2048 bf16), shrunk to keep one X tile within
    ``_X_TILE_BYTES`` for wide feature blocks. Always a multiple of the
    dtype's sublane packing — (8, 128) f32, (16, 128) bf16."""
    sublane = 32 // itemsize
    cap = 1024 if itemsize >= 4 else 2048
    rows = _X_TILE_BYTES // (itemsize * d_pad)
    return int(np.clip(rows // sublane * sublane, sublane, cap))


def _kernel(loss: PointwiseLoss, x_ref, aux_ref, w_ref,
            val_ref, grad_ref, rsum_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        val_ref[0, 0] = jnp.float32(0.0)
        rsum_ref[0, 0] = jnp.float32(0.0)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    x = x_ref[:].astype(jnp.float32)  # [tile, d_pad], streamed f32 or bf16
    w = w_ref[:]  # [1, d_pad], f32
    aux = aux_ref[:]  # [tile, 3]: labels | offsets | weights
    y, o, ws = aux[:, 0:1], aux[:, 1:2], aux[:, 2:3]
    margins = jnp.sum(x * w, axis=1, keepdims=True) + o
    l, dz = loss.loss_and_dz(margins, y)
    r = ws * dz  # [tile, 1] f32
    val_ref[0, 0] += jnp.sum(ws * l)
    # Σr feeds the normalized-space chain rule (grad shift term) for free
    rsum_ref[0, 0] += jnp.sum(r)
    grad_ref[:] = grad_ref[:] + jnp.sum(r * x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _fused_padded(loss: PointwiseLoss, x, aux, interpret: bool, w):
    n_pad, d_pad = x.shape
    tile = _row_tile(d_pad, x.dtype.itemsize)
    grid = (n_pad // tile,)

    vmem = {} if interpret else dict(memory_space=pltpu.VMEM)
    smem = {} if interpret else dict(memory_space=pltpu.SMEM)
    value, grad, rsum = pl.pallas_call(
        functools.partial(_kernel, loss),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d_pad), lambda i: (i, 0), **vmem),
            pl.BlockSpec((tile, 3), lambda i: (i, 0), **vmem),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0), **vmem),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), **smem),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0), **vmem),
            pl.BlockSpec((1, 1), lambda i: (0, 0), **smem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, aux, w.reshape(1, d_pad))
    return value[0, 0], grad[0], rsum[0, 0]


def _should_interpret() -> bool:
    """Interpret on ``cpu`` only; ``tpu`` always compiles through Mosaic."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas GLM kernel runs on tpu (compiled) or cpu (interpreted); "
        f"the default backend is {platform!r}"
    )


def fused_value_and_gradient(
    loss: PointwiseLoss,
    coefficients: Array,
    batch: LabeledPointBatch,
    *,
    l2_weight: float = 0.0,
    normalization=None,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Fused (value, gradient) of the weighted GLM objective.

    Numerically equivalent to ``jax.value_and_grad`` of GLMObjective.value,
    including the normalization algebra (effective coefficients + margin
    shift, ValueAndGradientAggregator.scala:36-49): the kernel streams X once
    with ``eff = factors*w`` and a shifted offset column, and the chain rule
    back to ``w`` uses the kernel's Σr output —
    ``grad_w = factors * (X'r - (Σr)*shifts)``. Use inside jit.

    bf16 feature blocks stream as bf16 (half the HBM traffic) with all
    accumulation in f32; coefficients/value/gradient stay f32 throughout.
    Inputs of any shape are zero-padded to (tile-multiple rows, 128m cols);
    padded rows get weight 0 and padded columns 0 coefficients,
    contributing nothing.
    """
    if interpret is None:
        interpret = _should_interpret()
    default_registry().counter(
        TRACES_INTERPRETED if interpret else TRACES_COMPILED
    ).inc()
    x = batch.features
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    tile = _row_tile(_round_up(d, _LANE), x.dtype.itemsize)
    n_pad, d_pad = _round_up(max(n, 1), tile), _round_up(d, _LANE)
    x = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
    factors = shifts = None
    if normalization is not None:
        factors, shifts = normalization.factors, normalization.shifts
    eff = jnp.asarray(coefficients, jnp.float32)
    if factors is not None:
        eff = eff * jnp.asarray(factors, jnp.float32)
    offsets = jnp.asarray(batch.offsets, jnp.float32)
    if shifts is not None:
        offsets = offsets - jnp.dot(eff, jnp.asarray(shifts, jnp.float32))
    w = jnp.pad(eff, (0, d_pad - d))
    aux = jnp.stack([
        jnp.asarray(batch.labels, jnp.float32),
        offsets,
        jnp.asarray(batch.weights, jnp.float32),
    ], axis=1)
    aux = jnp.pad(aux, ((0, n_pad - n), (0, 0)))
    value, grad, rsum = _fused_padded(loss, x, aux, bool(interpret), w)
    grad = grad[:d]
    if shifts is not None:
        grad = grad - rsum * jnp.asarray(shifts, jnp.float32)
    if factors is not None:
        grad = grad * jnp.asarray(factors, jnp.float32)
    grad = grad.astype(coefficients.dtype)
    if l2_weight > 0.0:
        value = value + 0.5 * l2_weight * jnp.vdot(coefficients, coefficients)
        grad = grad + l2_weight * coefficients
    return value.astype(coefficients.dtype), grad
