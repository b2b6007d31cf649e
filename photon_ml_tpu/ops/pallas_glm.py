"""Pallas TPU kernels: fused GLM value + gradient, and H v, one pass over X each.

This is the reference's hot loop (ValueAndGradientAggregator.scala:133-177 —
per-sample margin dot product, pointwise loss, axpy accumulation, merged
tree-wise) as a single Pallas kernel: each row tile streams through VMEM
once; the margin matvec, the pointwise loss/derivative, and the gradient
accumulation all consume the tile while it is resident, so X crosses HBM
once per evaluation where the autodiff/XLA path reads it twice (forward
margin matvec + backward transpose matvec — XLA does not fuse them).

Its sibling (PR 41; HessianVectorAggregator.scala's loop) is the product TRON's
CG takes (``_hv_kernel`` / ``fused_hessian_vector``, at the end of this file):
the same grid, tile, aux block and masks, and per tile ``m = x.w + o``,
``d2 = ws * l''(m, y)``, ``z = x.v``, ``acc += (d2 z)' x``, where the jvp of the
gradient reads X twice a product and once more a round. On a v5e at 400,000 x
2,000 float32 a launch takes 4.60 ms beside the gradient kernel's 4.60 (85 % of
819 GB/s; PERF.md 5, PR 41): two more vector operations an element, the margins
recomputed on the tile, hide under the stream. Its gap to float64 there is 4e-7.

Measured on one TPU v5e under jax 0.9.0 (chip run of PR 21; per evaluation inside
one jitted 64-step scan): f32, d=512, n=262144: 0.92 ms (583 GB/s of X) vs 1.63 ms
for the autodiff path (two X passes) — 1.8x; d=4096, n=32768: 0.74 vs 1.45 ms.

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

Accumulator outputs (value, gradient, Σr) map to the same block every grid step:
sequential accumulators (TPU grids are serialized), initialized at step 0.

The kernels read X ``[n, d]`` and the aux block ``[n, 3]`` AS THEY LIE
(PR 33): the grid is ``cdiv(n, tile)`` and the X block ``(tile, d_pad)``
over the ``d``-wide array, so the last row tile and the last lanes are
partial blocks, whose out-of-bounds part is undefined on read (the
interpreter fills it with NaN; 0 x NaN is NaN, so nothing there may be
multiplied away). What the zero padding did outside is done on the tile in
VMEM, by selects on an iota that follow from the static shape alone:

- ``d % 128 != 0``: lanes at or past ``d`` of the X tile are selected to
  zero before the margin's reduction; ``w`` arrives zero-padded to ``d_pad``
  (a ``d``-float pad) and the gradient leaves ``d_pad`` wide.
- ``n % tile != 0``: a second body, which only the LAST grid step runs
  (``pl.when``), also zeroes the rows at or past ``n`` of the X tile and of
  ``r`` and ``ws * l``; every other step runs the unmasked body.
- whole tiles and whole lanes: no mask is emitted at all.

The zeros stand exactly where a ``jnp.pad`` of X would put them, under the same
tile and ``d_pad``-lane reduction: results are the padded call's BIT FOR BIT
(tests/test_pallas_glm.py, tests/test_pallas_hv.py; on the chip, PR 33). The pad
this replaced cost 9.96 ms beside the kernel's 4.61 ms, 55 times a fit.

On ``cpu`` the kernels run in Pallas interpret mode, so the same code path is
testable there; on ``tpu`` Mosaic compiles them; any other platform is an error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.data.batch import (  # the width rule lives with the batch
    MAX_KERNEL_DIM, LabeledPointBatch, kernel_supports)
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
#: ``MAX_KERNEL_DIM`` (the widest lane-padded block the kernels take) and
#: ``kernel_supports`` (the predicate the auto rule of ops/objective.py
#: reads) are written ONCE, in data/batch.py, and imported back above:
#: since PR 49 the batch's placement asks the same question, and data/ lies
#: under ops/ (tests/test_layering.py). The placement is the other half of
#: "X as it lies": the kernels' X operand is ROW-major, a TPU keeps a
#: ``[400000, 2000]`` float32 array column-major, and XLA put a relayout
#: copy of all of X in front of the kernel in every program that took such
#: an X as an argument. ``data/batch.in_kernel_layout`` places the block
#: row-major once, where the batch is made; no program copies it again.
#: registry counters bumped once per TRACE of the kernel into a program —
#: how a run's journal shows that its FE solve held the Mosaic-compiled
#: kernel (and never the interpreter) without anyone reading HLO
TRACES_COMPILED = "ops/pallas_glm/traces_compiled"
TRACES_INTERPRETED = "ops/pallas_glm/traces_interpreted"
#: bumped once per trace of the kernel in which a masked body was emitted
#: (rows past the last whole tile, or lanes past the last whole 128)
TRACES_RAGGED = "ops/pallas_glm/traces_ragged"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _row_tile(d_pad: int, itemsize: int) -> int:
    """Rows per grid step: the measured optima with the packed-aux layout
    at d=512 (1024 rows f32 / 2048 bf16), shrunk to keep one X tile within
    ``_X_TILE_BYTES`` for wide feature blocks. Always a multiple of the
    dtype's sublane packing — (8, 128) f32, (16, 128) bf16."""
    sublane = 32 // itemsize
    cap = 1024 if itemsize >= 4 else 2048
    rows = _X_TILE_BYTES // (itemsize * d_pad)
    return int(np.clip(rows // sublane * sublane, sublane, cap))


def _kernel(loss: PointwiseLoss, n: int, d: int, x_ref, aux_ref, w_ref,
            val_ref, grad_ref, rsum_ref):
    tile, d_pad = x_ref.shape
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        val_ref[0, 0] = jnp.float32(0.0)
        rsum_ref[0, 0] = jnp.float32(0.0)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    def accumulate(rows):
        """One tile into the accumulators. ``rows`` is None on a whole tile,
        else how many of the tile's rows the array has: what lies past an
        array's edge in a partial block is undefined on read (the interpreter
        fills it with NaN), so it is selected away, never multiplied by 0."""
        x = x_ref[:].astype(jnp.float32)  # [tile, d_pad], streamed f32 or bf16
        keep = live = None
        if d != d_pad:
            keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
        if rows is not None:
            live = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < rows
            keep = live if keep is None else keep & live
        if keep is not None:
            x = jnp.where(keep, x, 0.0)
        w = w_ref[:]  # [1, d_pad], f32, zero past d
        aux = aux_ref[:]  # [tile, 3]: labels | offsets | weights
        y, o, ws = aux[:, 0:1], aux[:, 1:2], aux[:, 2:3]
        margins = jnp.sum(x * w, axis=1, keepdims=True) + o
        l, dz = loss.loss_and_dz(margins, y)

        def alive(v):
            return v if live is None else jnp.where(live, v, 0.0)

        r = alive(ws * dz)  # [tile, 1] f32
        val_ref[0, 0] += jnp.sum(alive(ws * l))
        # Σr feeds the normalized-space chain rule (grad shift term) for free
        rsum_ref[0, 0] += jnp.sum(r)
        grad_ref[:] = grad_ref[:] + jnp.sum(r * x, axis=0, keepdims=True)

    if n % tile == 0:
        accumulate(None)
    else:
        last = pl.num_programs(0) - 1
        pl.when(step != last)(lambda: accumulate(None))
        pl.when(step == last)(lambda: accumulate(n % tile))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _fused_padded(loss: PointwiseLoss, x, aux, interpret: bool, w):
    """``x`` [n, d] and ``aux`` [n, 3] as they lie; ``w`` [d_pad], zero past
    d. The name is the one the device trace knows the kernel by."""
    n, d = x.shape
    d_pad = w.shape[0]
    tile = _row_tile(d_pad, x.dtype.itemsize)
    if n == 0:  # an empty grid would leave the accumulators unwritten
        zero = jnp.float32(0.0)
        return zero, jnp.zeros((d_pad,), jnp.float32), zero
    if n % tile or d != d_pad:
        default_registry().counter(TRACES_RAGGED).inc()

    vmem = {} if interpret else dict(memory_space=pltpu.VMEM)
    smem = {} if interpret else dict(memory_space=pltpu.SMEM)
    value, grad, rsum = pl.pallas_call(
        functools.partial(_kernel, loss, n, d),
        grid=(pl.cdiv(n, tile),),
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
    Inputs of any shape go to the kernel as they are: nothing of size n is
    copied to reach whole tiles (the kernel masks its last row tile and lanes
    itself; only the coefficients are padded to 128m lanes), nor to reach the
    kernel's row-major layout where ``data/batch.in_kernel_layout`` placed X.
    """
    if interpret is None:
        interpret = _should_interpret()
    default_registry().counter(
        TRACES_INTERPRETED if interpret else TRACES_COMPILED
    ).inc()
    x = batch.features
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(x, jnp.float32)
    d = x.shape[1]
    d_pad = _round_up(d, _LANE)
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


# -- the Hessian-vector product: the same stream, two lane reductions a tile --

#: the product kernel's own three, bumped as the gradient kernel's are
HV_TRACES_COMPILED = "ops/pallas_glm/hv_traces_compiled"
HV_TRACES_INTERPRETED = "ops/pallas_glm/hv_traces_interpreted"
HV_TRACES_RAGGED = "ops/pallas_glm/hv_traces_ragged"


def _hv_kernel(loss: PointwiseLoss, n: int, d: int, zshift_ref, x_ref, aux_ref,
               w_ref, v_ref, acc_ref, usum_ref):
    """``acc += X' (d2 * (X v + zshift))`` over one row tile, ``d2`` the loss's
    second derivative at the margins ``X w + o``, which are recomputed on the
    tile: vector work under a DMA-bound stream, and nothing is carried from
    the round's gradient evaluation to its products. Grid, tile, aux block
    and masks are ``_kernel``'s."""
    tile, d_pad = x_ref.shape
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        usum_ref[0, 0] = jnp.float32(0.0)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def accumulate(rows):
        """``rows`` as in ``_kernel``: None on a whole tile, else the rows of
        the tile that the array has; what lies past an edge is selected away."""
        x = x_ref[:].astype(jnp.float32)  # [tile, d_pad], streamed f32 or bf16
        keep = live = None
        if d != d_pad:
            keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
        if rows is not None:
            live = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < rows
            keep = live if keep is None else keep & live
        if keep is not None:
            x = jnp.where(keep, x, 0.0)
        aux = aux_ref[:]  # [tile, 3]: labels | offsets | weights
        y, o, ws = aux[:, 0:1], aux[:, 1:2], aux[:, 2:3]
        margins = jnp.sum(x * w_ref[:], axis=1, keepdims=True) + o
        z = jnp.sum(x * v_ref[:], axis=1, keepdims=True) + zshift_ref[0, 0]
        u = ws * loss.d2z(margins, y) * z  # [tile, 1] f32
        if live is not None:
            u = jnp.where(live, u, 0.0)
        # Σu feeds the normalized-space chain rule, as Σr does the gradient's
        usum_ref[0, 0] += jnp.sum(u)
        acc_ref[:] = acc_ref[:] + jnp.sum(u * x, axis=0, keepdims=True)

    if n % tile == 0:
        accumulate(None)
    else:
        last = pl.num_programs(0) - 1
        pl.when(step != last)(lambda: accumulate(None))
        pl.when(step == last)(lambda: accumulate(n % tile))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _hv_one_pass(loss: PointwiseLoss, x, aux, interpret: bool, w, v, zshift):
    """``x`` [n, d] and ``aux`` [n, 3] as they lie; ``w`` and ``v`` [d_pad],
    zero past d; ``zshift`` the scalar every row's ``X v`` is moved by.
    Returns ``(X'u [d_pad], Σu)``. The device trace knows the custom call by
    this name, and the benchmark's reduction files a name that holds
    ``_fused_padded`` or ``pallas`` under the gradient kernel's category
    whatever scope it was traced under: this one must match neither."""
    n, d = x.shape
    d_pad = w.shape[0]
    tile = _row_tile(d_pad, x.dtype.itemsize)
    if n == 0:  # an empty grid would leave the accumulators unwritten
        return jnp.zeros((d_pad,), jnp.float32), jnp.float32(0.0)
    if n % tile or d != d_pad:
        default_registry().counter(HV_TRACES_RAGGED).inc()

    vmem = {} if interpret else dict(memory_space=pltpu.VMEM)
    smem = {} if interpret else dict(memory_space=pltpu.SMEM)
    row = pl.BlockSpec((1, d_pad), lambda i: (0, 0), **vmem)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0), **smem)
    acc, usum = pl.pallas_call(
        functools.partial(_hv_kernel, loss, n, d),
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            scalar,
            pl.BlockSpec((tile, d_pad), lambda i: (i, 0), **vmem),
            pl.BlockSpec((tile, 3), lambda i: (i, 0), **vmem),
            row,
            row,
        ],
        out_specs=[row, scalar],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(zshift.reshape(1, 1), x, aux, w.reshape(1, d_pad), v.reshape(1, d_pad))
    return acc[0], usum[0, 0]


def fused_hessian_vector(
    loss: PointwiseLoss,
    coefficients: Array,
    vector: Array,
    batch: LabeledPointBatch,
    *,
    l2_weight: float = 0.0,
    normalization=None,
    interpret: bool | None = None,
) -> Array:
    """Fused ``H(coefficients) @ vector`` of the weighted GLM objective: the
    sibling of :func:`fused_value_and_gradient`, one read of X a product.

    Numerically equivalent to ``jax.jvp`` of ``jax.grad`` of
    GLMObjective.value, normalization algebra included: the kernel streams X
    once with ``eff_w = factors*w`` (and the offsets shifted, as for the
    gradient) and ``eff_v = factors*v``; the scalar ``eff_v . shifts`` that
    every row's ``z = x . eff_v`` is moved by is taken off ON THE TILE, before
    ``u = d2 * z``, where it costs one add a row. Folded through afterwards
    (``X'(d2 z) - (eff_v . shifts) X'd2``) it would want a second [d]
    accumulator and cancels AFTER the sums: on standardized columns whose
    means are of their deviation's size the product's gap to float64 read
    1e-6 to 2e-5 folded where 1e-7 on the tile (interpreter, float32; 20,000
    to 50,000 rows). Back to ``w``'s space
    ``Hv = factors * (X'u - (Σu)*shifts) + l2*v``. Use inside jit.

    Feature dtypes, shapes and what is padded are the gradient kernel's.
    """
    if interpret is None:
        interpret = _should_interpret()
    default_registry().counter(
        HV_TRACES_INTERPRETED if interpret else HV_TRACES_COMPILED
    ).inc()
    x = batch.features
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(x, jnp.float32)
    d = x.shape[1]
    d_pad = _round_up(d, _LANE)
    factors = shifts = None
    if normalization is not None:
        factors, shifts = normalization.factors, normalization.shifts
    eff_w = jnp.asarray(coefficients, jnp.float32)
    eff_v = jnp.asarray(vector, jnp.float32)
    if factors is not None:
        factors = jnp.asarray(factors, jnp.float32)
        eff_w, eff_v = eff_w * factors, eff_v * factors
    offsets = jnp.asarray(batch.offsets, jnp.float32)
    zshift = jnp.float32(0.0)
    if shifts is not None:
        shifts = jnp.asarray(shifts, jnp.float32)
        offsets = offsets - jnp.dot(eff_w, shifts)
        zshift = -jnp.dot(eff_v, shifts)
    aux = jnp.stack([
        jnp.asarray(batch.labels, jnp.float32),
        offsets,
        jnp.asarray(batch.weights, jnp.float32),
    ], axis=1)
    hv, usum = _hv_one_pass(
        loss, x, aux, bool(interpret),
        jnp.pad(eff_w, (0, d_pad - d)), jnp.pad(eff_v, (0, d_pad - d)), zshift)
    hv = hv[:d]
    if shifts is not None:
        hv = hv - usum * shifts
    if factors is not None:
        hv = hv * factors
    hv = hv.astype(coefficients.dtype)
    if l2_weight > 0.0:
        hv = hv + l2_weight * vector
    return hv
