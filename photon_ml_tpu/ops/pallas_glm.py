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
2,000 float32 a launch takes 4.34 ms beside the gradient kernel's 4.34 (90 % of
819 GB/s; PERF.md 5, PR 53; 4.60 each until then): two more vector operations
an element, the margins recomputed on the tile, hide under the stream. Its gap
to float64 there is 4e-7.

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
- The per-sample columns ride as ONE ``[3, n]`` float32 block (labels |
  offsets | weights, ROWS ALONG THE LANES; built in ``_operands``, for both
  kernels), blocked ``(3, tile)`` beside the X tile. Three separate
  ``[tile, 1]`` inputs each cost a narrow DMA per grid step that outweighed
  the X stream, and ONE ``[n, 3]`` block (until PR 53) is stored 128 lanes
  wide: 512 bytes a row in HBM and in every DMA where 12 are needed, 2.56 GB
  of a GLMix launch's 7.68 at ``[4999168, 256]``. The device keeps
  ``f32[3,n]`` in ``T(4,128)`` tiles, 16 bytes a row.
- Everything pointwise in a row (the loss, its derivatives, the weights, the
  row masks) is done on ``[1, tile]`` ROWS, whole vregs. A lane reduction of
  the X tile leaves a ``[tile, 1]`` COLUMN, one lane in 128 in use, and the
  products with the X tile and the parent's sums take columns: ``_turned``
  moves between the two on the tile in VMEM, one turn each way a tile. On
  columns (until PR 53) softplus and sigmoid of a 1,024-row tile ran on 128
  vregs where 8 hold them, and at 256 lanes THAT, not the bytes, was the
  launch: 12.24 ms at ``[4999168, 256]``, of which 11.8 moved with no tile and
  no byte (13.35 with the block ``[3, n]`` and the work still on columns).
  Now 8.46 ms there (75 % of 819 GB/s by the count of benchmark/roofline.py),
  the product 8.49 where 10.15, and at ``[400000, 2000]`` 4.34 ms each where
  4.60 (90 %): chip runs of PR 53, a launch's own events in a trace; value,
  gradient, Σr, ``X'u`` and Σu came out the parent's bit for bit on the chip
  at both shapes (the turns move values; every sum is taken as before).
- VMEM: the pipeline double-buffers the X tile, the products materialize
  float32 ``[tile, d_pad]`` temporaries, and w / grad ride as sublane-padded
  ``[8, d_pad]`` blocks: past v5e's 16 MiB default scoped VMEM from
  ``d_pad`` = 2048 up ("scoped allocation 16.01M, limit 16.00M"). The row
  tile never goes under 128 rows (a ``(3, tile)`` block has to end on a lane
  boundary), so the widest float32 tile, 128 x 16,384, is 8 MiB and its
  kernel asks for 23.9 MiB (compiled for a described v5e,
  tests/test_tpu_compile.py): ``_VMEM_LIMIT_BYTES`` covers every
  ``d_pad`` up to ``MAX_KERNEL_DIM`` x {f32, bf16}.

Accumulator outputs (value, gradient, Σr) map to the same block every grid step:
sequential accumulators (TPU grids are serialized), initialized at step 0.

The kernels read X ``[n, d]`` and the aux block ``[3, n]`` AS THEY LIE
(PR 33): the grid is ``cdiv(n, tile)``, the X block ``(tile, d_pad)`` over
the ``d``-wide array and the aux block ``(3, tile)``, so the last row tile
(of the aux block: its last lanes) and X's last lanes are partial blocks,
whose out-of-bounds part is undefined on read (the
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
    _LANE, MAX_KERNEL_DIM, LabeledPointBatch, _round_up, kernel_supports)
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.telemetry.registry import default_registry

Array = jax.Array

_X_TILE_BYTES = 4 * 1024 * 1024  # target VMEM footprint for ONE X tile
#: scoped-VMEM limit handed to Mosaic (the module docstring has the count):
#: the widest float32 tile's kernel takes 23.9 MiB of it; v5e has 128 MiB.
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


def _row_tile(d_pad: int, itemsize: int) -> int:
    """Rows per grid step, one rule of ``(d_pad, itemsize)``: as many as keep
    one X tile within ``_X_TILE_BYTES``, in whole 128s (the aux block's rows
    lie along the lanes, and a ``(3, tile)`` block has to end on a lane
    boundary; 128 is whole sublane packings of float32 and bfloat16 too),
    never under 128 (from ``d_pad`` 8,320 of float32 on that is an X tile
    past the budget: 8 MiB at ``MAX_KERNEL_DIM``, within ``_VMEM_LIMIT_BYTES``)
    and at most 1,024 of float32 / 2,048 of bfloat16. Measured again with
    the ``[3, n]`` block (chip runs of PR 53, a launch's own events, float32):
    at ``d_pad`` 2,048 the launch is flat in the tile (256 / 512 / 1,024 rows:
    4.3386 / 4.3393 / 4.3405 ms); at 256 a grid step costs 0.31 us and 2,048
    / 4,096 rows read 7.31 / 6.88 ms where 1,024 read 8.46 (92 % of the
    roofline where 75), but a tile is also the order of the gradient's sublane
    sums: at 4,096 rows ``glmix-ml20m.sweeps`` took 8 kernel launches a sweep
    where 7 and 870.67 lock-step trials where 838, and its episode came out
    SLOWER (1.0484 s where 1.0244). So the cap stays where it was."""
    cap = 1024 if itemsize >= 4 else 2048
    rows = _X_TILE_BYTES // (itemsize * d_pad)
    return int(np.clip(rows // _LANE * _LANE, _LANE, cap))


def _tile(x_ref, aux_ref, d: int, rows):
    """What both kernels read of one grid step: the X tile in float32 with
    what lies past the array's edges selected to zero; labels, offsets and
    weights as the ``[1, tile]`` rows they arrive as (rows ALONG THE LANES);
    and the lanes of such a row that the array has (None on a whole tile).
    ``rows`` is None on a whole tile, else how many of the tile's rows the
    array has: what lies past an array's edge in a partial block is undefined
    on read (the interpreter fills it with NaN), so it is selected away,
    never multiplied by 0."""
    tile, d_pad = x_ref.shape
    x = x_ref[:].astype(jnp.float32)  # [tile, d_pad], streamed f32 or bf16
    keep = live = None
    if d != d_pad:
        keep = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
    if rows is not None:
        below = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < rows
        keep = below if keep is None else keep & below
        live = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) < rows
    if keep is not None:
        x = jnp.where(keep, x, 0.0)
    aux = aux_ref[:]  # [3, tile]: labels | offsets | weights
    return x, (aux[0:1, :], aux[1:2, :], aux[2:3, :]), live


def _turned(*parts):
    """``[tile, 1]`` columns to ``[1, tile]`` rows, or rows to columns, in ONE
    turn of the stacked parts on the tile in VMEM. A row reduction of the X
    tile leaves a column (one row a sublane, one lane in 128 in use) and the
    products with the X tile take one; everything pointwise between the two
    (the loss, its derivatives, the weights) is done on rows, whole vregs."""
    column = parts[0].shape[1] == 1
    turned = jnp.concatenate(parts, axis=1 if column else 0).T
    return [turned[i:i + 1, :] if column else turned[:, i:i + 1]
            for i in range(len(parts))]


def _each_tile(n: int, tile: int, accumulate) -> None:
    """``accumulate(None)`` on every whole row tile; where ``n`` leaves a
    partial last one, a second body that only the last grid step runs."""
    if n % tile == 0:
        accumulate(None)
    else:
        step, last = pl.program_id(0), pl.num_programs(0) - 1
        pl.when(step != last)(lambda: accumulate(None))
        pl.when(step == last)(lambda: accumulate(n % tile))


def _kernel(loss: PointwiseLoss, n: int, d: int, x_ref, aux_ref, w_ref,
            val_ref, grad_ref, rsum_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        val_ref[0, 0] = jnp.float32(0.0)
        rsum_ref[0, 0] = jnp.float32(0.0)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    def accumulate(rows):
        """One tile into the accumulators (``rows`` as ``_tile`` takes it)."""
        x, (y, o, ws), live = _tile(x_ref, aux_ref, d, rows)
        w = w_ref[:]  # [1, d_pad], f32, zero past d
        (xw,) = _turned(jnp.sum(x * w, axis=1, keepdims=True))
        l, dz = loss.loss_and_dz(xw + o, y)  # [1, tile]: a row a lane

        def alive(v):
            return v if live is None else jnp.where(live, v, 0.0)

        wl, r = _turned(alive(ws * l), alive(ws * dz))  # [tile, 1] f32
        val_ref[0, 0] += jnp.sum(wl)
        # Σr feeds the normalized-space chain rule (grad shift term) for free
        rsum_ref[0, 0] += jnp.sum(r)
        grad_ref[:] = grad_ref[:] + jnp.sum(r * x, axis=0, keepdims=True)

    _each_tile(n, x_ref.shape[0], accumulate)


def _launch(body, ragged_counter: str, interpret: bool, scalars, x, aux, rows, outs):
    """One pass of ``body`` over the row tiles of ``x`` [n, d] beside the
    ``(3, tile)`` pieces of ``aux`` [3, n]: ``scalars`` ride in SMEM ahead of
    X, ``rows`` (each ``[d_pad]``) stay resident behind the aux block, and
    ``outs`` names each accumulator the body is handed, ``"row"`` for a
    ``[d_pad]`` vector and ``"scalar"``. An empty batch launches nothing (an
    empty grid would leave the accumulators unwritten) and returns zeros."""
    (n, d), d_pad = x.shape, rows[0].shape[0]
    blocks = {"row": (1, d_pad), "scalar": (1, 1)}
    if n == 0:
        return [jnp.zeros((d_pad,) if kind == "row" else (), jnp.float32) for kind in outs]
    tile = _row_tile(d_pad, x.dtype.itemsize)
    if n % tile or d != d_pad:
        default_registry().counter(ragged_counter).inc()
    vmem = {} if interpret else dict(memory_space=pltpu.VMEM)
    smem = {} if interpret else dict(memory_space=pltpu.SMEM)
    specs = {"row": pl.BlockSpec(blocks["row"], lambda i: (0, 0), **vmem),
             "scalar": pl.BlockSpec(blocks["scalar"], lambda i: (0, 0), **smem)}
    results = pl.pallas_call(
        functools.partial(body, n, d),
        grid=(pl.cdiv(n, tile),),
        in_specs=[specs["scalar"]] * len(scalars) + [
            pl.BlockSpec((tile, d_pad), lambda i: (i, 0), **vmem),
            pl.BlockSpec((3, tile), lambda i: (0, i), **vmem),
        ] + [specs["row"]] * len(rows),
        out_specs=[specs[kind] for kind in outs],
        out_shape=[jax.ShapeDtypeStruct(blocks[kind], jnp.float32) for kind in outs],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*(s.reshape(1, 1) for s in scalars), x, aux, *(r.reshape(1, d_pad) for r in rows))
    return [r[0] if kind == "row" else r[0, 0] for r, kind in zip(results, outs)]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _fused_padded(loss: PointwiseLoss, x, aux, interpret: bool, w):
    """``x`` [n, d] and ``aux`` [3, n] as they lie; ``w`` [d_pad], zero past
    d. Returns ``(value, X'r [d_pad], Σr)``. The name is the one the device
    trace knows the kernel by, and X stays the custom call's first 2-D
    operand: the benchmark's reduction reads the kernel's bytes off the first
    ``f32[a,b]`` of the instruction's text."""
    return _launch(functools.partial(_kernel, loss), TRACES_RAGGED, interpret,
                   (), x, aux, (w,), ("scalar", "row", "scalar"))


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


def _operands(batch: LabeledPointBatch, normalization, *vectors):
    """What both kernels are handed, prepared in ONE place. Returns ``(x, aux,
    effective, factors, shifts)``: X as it lies (float32 or bfloat16, anything
    else as float32); the per-row columns as ONE float32 ``[3, n]`` block
    (labels | offsets | weights, rows along the lanes: a ``[n, 3]`` block is
    stored 128 lanes wide, 512 bytes a row where these are 12), the offsets
    moved by ``-(effective[0] . shifts)``; every vector of ``vectors`` (the
    coefficients first) in float32 times the factors; and the normalization's
    two vectors in float32, or None."""
    x = batch.features
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(x, jnp.float32)
    factors = shifts = None
    if normalization is not None:
        factors, shifts = normalization.factors, normalization.shifts
    effective = [jnp.asarray(v, jnp.float32) for v in vectors]
    if factors is not None:
        factors = jnp.asarray(factors, jnp.float32)
        effective = [v * factors for v in effective]
    offsets = jnp.asarray(batch.offsets, jnp.float32)
    if shifts is not None:
        shifts = jnp.asarray(shifts, jnp.float32)
        offsets = offsets - jnp.dot(effective[0], shifts)
    aux = jnp.stack([
        jnp.asarray(batch.labels, jnp.float32),
        offsets,
        jnp.asarray(batch.weights, jnp.float32),
    ], axis=0)
    return x, aux, effective, factors, shifts


def _lane_padded(v: Array) -> Array:
    """``[d]`` to ``[d_pad]``, zero past d: the one pad left, d floats."""
    return jnp.pad(v, (0, _round_up(v.shape[0], _LANE) - v.shape[0]))


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
    x, aux, (w,), factors, shifts = _operands(batch, normalization, coefficients)
    value, grad, rsum = _fused_padded(
        loss, x, aux, bool(interpret), _lane_padded(w))
    grad = grad[:x.shape[1]]
    if shifts is not None:
        grad = grad - rsum * shifts
    if factors is not None:
        grad = grad * factors
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
    @pl.when(pl.program_id(0) == 0)
    def _init():
        usum_ref[0, 0] = jnp.float32(0.0)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def accumulate(rows):
        x, (y, o, ws), live = _tile(x_ref, aux_ref, d, rows)
        xw, xv = _turned(jnp.sum(x * w_ref[:], axis=1, keepdims=True),
                         jnp.sum(x * v_ref[:], axis=1, keepdims=True))
        u = ws * loss.d2z(xw + o, y) * (xv + zshift_ref[0, 0])  # [1, tile]
        if live is not None:
            u = jnp.where(live, u, 0.0)
        (u,) = _turned(u)  # [tile, 1] f32
        # Σu feeds the normalized-space chain rule, as Σr does the gradient's
        usum_ref[0, 0] += jnp.sum(u)
        acc_ref[:] = acc_ref[:] + jnp.sum(u * x, axis=0, keepdims=True)

    _each_tile(n, x_ref.shape[0], accumulate)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _hv_one_pass(loss: PointwiseLoss, x, aux, interpret: bool, w, v, zshift):
    """``x`` [n, d] and ``aux`` [3, n] as they lie; ``w`` and ``v`` [d_pad],
    zero past d; ``zshift`` the scalar every row's ``X v`` is moved by.
    Returns ``(X'u [d_pad], Σu)``. The device trace knows the custom call by
    this name, and the benchmark's reduction files a name that holds
    ``_fused_padded`` or ``pallas`` under the gradient kernel's category
    whatever scope it was traced under: this one must match neither."""
    return _launch(functools.partial(_hv_kernel, loss), HV_TRACES_RAGGED, interpret,
                   (zshift,), x, aux, (w, v), ("row", "scalar"))


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
    x, aux, (w, v), factors, shifts = _operands(
        batch, normalization, coefficients, vector)
    zshift = jnp.float32(0.0) if shifts is None else -jnp.dot(v, shifts)
    hv, usum = _hv_one_pass(
        loss, x, aux, bool(interpret), _lane_padded(w), _lane_padded(v), zshift)
    hv = hv[:x.shape[1]]
    if shifts is not None:
        hv = hv - usum * shifts
    if factors is not None:
        hv = hv * factors
    hv = hv.astype(coefficients.dtype)
    if l2_weight > 0.0:
        hv = hv + l2_weight * vector
    return hv
