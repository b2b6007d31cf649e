"""Sample-sharded dense GLM objective: the one-pass kernel on every device.

Reference parity: the reference's hot loop runs its one-pass seqOp *on every
executor* and merges with treeAggregate
(photon-lib function/glm/ValueAndGradientAggregator.scala:133-154 per-sample
add, :236-251 treeAggregate combine) — distribution and the one-pass loop
compose by construction. The GSPMD path here could not do the same: XLA
cannot partition a ``pallas_call``, so mesh-sharded solves used to forfeit
the single-pass kernel (ops/pallas_glm.py) and fall back to two autodiff
passes over X.

This module restores the composition with ``jax.shard_map``: each mesh
device runs the packed single-pass kernel (or the autodiff path off-TPU) on
its local ``[n/K, d]`` rows, and value / gradient / Σr combine with a psum
over the mesh "data" axis — the XLA collective that replaces
``treeAggregate``. Coefficients stay replicated, so the solver's vector
algebra outside the shard_map is unchanged.

The L2 term is added OUTSIDE the psum (each local objective runs with
l2=0): summing per-device values would count the regularizer K times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.ops.objective import GLMObjective, BoundObjective

Array = jax.Array


class ShardedDenseGLMObjective:
    """GLM objective over a sample-sharded dense batch on a device mesh.

    Drop-in for :class:`GLMObjective` at every solver call site
    (``bind(batch)`` feeds ``optim.optimizer.solve``): ``value``,
    ``value_and_gradient``, and ``hessian_vector`` each run as one
    ``shard_map`` over the mesh, with the sample axis split along
    ``data_axis`` and everything else (coefficients, normalization factors)
    replicated. Features sharded over a "model" axis are NOT supported here
    — that is the column-sharded objective's job (parallel/column_sharded.py).

    use_pallas: forwarded to the per-device local objective. ``None``
    (default) = the single-pass kernel on TPU, autodiff elsewhere; ``True``
    forces the kernel (interpret mode off-TPU — how the virtual-mesh tests
    exercise this exact code path); ``False`` forces autodiff. The vmap
    hazard that forbids the kernel elsewhere does not apply: the primary FE
    solve is never vmapped, and inside shard_map the batch is an ordinary
    local array.
    """

    def __init__(
        self,
        loss: PointwiseLoss,
        mesh: Mesh,
        l2_weight: float = 0.0,
        normalization: NormalizationContext | None = None,
        use_pallas: bool | None = None,
        data_axis: str = "data",
    ):
        self.loss = loss
        self.mesh = mesh
        self.data_axis = data_axis
        self.l2_weight = float(l2_weight)
        self.normalization = (
            normalization if normalization is not None else no_normalization()
        )
        # Local objective computes the DATA term only (l2=0, no axis_name):
        # the psum and the once-only L2 happen out here.
        self._local = GLMObjective(
            loss, l2_weight=0.0, normalization=self.normalization,
            use_pallas=use_pallas,
        )

    # Value-based identity so jit static-arg caching works across repeated
    # construction (same contract as GLMObjective._key).
    def _key(self):
        return (type(self.loss), self.l2_weight, self.data_axis,
                id(self.mesh), id(self.normalization), self._local.use_pallas)

    def __eq__(self, other):
        return (
            isinstance(other, ShardedDenseGLMObjective)
            and self._key() == other._key()
        )

    def __hash__(self):
        return hash(self._key())

    # -- plumbing ------------------------------------------------------------

    def _pad(self, batch: LabeledPointBatch) -> LabeledPointBatch:
        """Rows must split evenly over the data axis; zero-weight padding
        rows contribute nothing (train_distributed pads datasets up front,
        so this is a no-op there — it exists for direct callers)."""
        k = int(self.mesh.shape[self.data_axis])
        n = batch.num_samples
        if n % k == 0:
            return batch
        return batch.pad_to(n + (-n) % k)

    def _spec(self):
        da = self.data_axis
        return dict(
            mesh=self.mesh,
            in_specs=(P(), P(da, None), P(da), P(da), P(da)),
            check_vma=False,
        )

    def _args(self, batch: LabeledPointBatch):
        return batch.features, batch.labels, batch.offsets, batch.weights

    def _l2_value(self, w: Array) -> Array:
        return 0.5 * self.l2_weight * jnp.vdot(w, w)

    # -- the objective surface the solvers consume ---------------------------

    def value(self, w: Array, batch: LabeledPointBatch) -> Array:
        batch = self._pad(batch)

        def f(w_, x, y, o, ws):
            local = self._local.value(w_, LabeledPointBatch(x, y, o, ws))
            return jax.lax.psum(local, self.data_axis)

        total = shard_map(f, out_specs=P(), **self._spec())(
            w, *self._args(batch)
        )
        if self.l2_weight > 0.0:
            total = total + self._l2_value(w)
        return total

    def value_and_gradient(
        self, w: Array, batch: LabeledPointBatch
    ) -> tuple[Array, Array]:
        batch = self._pad(batch)

        def f(w_, x, y, o, ws):
            v, g = self._local.value_and_gradient(
                w_, LabeledPointBatch(x, y, o, ws)
            )
            return (
                jax.lax.psum(v, self.data_axis),
                jax.lax.psum(g, self.data_axis),
            )

        value, grad = shard_map(f, out_specs=(P(), P()), **self._spec())(
            w, *self._args(batch)
        )
        if self.l2_weight > 0.0:
            value = value + self._l2_value(w)
            grad = grad + self.l2_weight * w
        return value, grad

    def hessian_vector(
        self, w: Array, v: Array, batch: LabeledPointBatch
    ) -> Array:
        batch = self._pad(batch)

        def f(w_, v_, x, y, o, ws):
            hv = self._local.hessian_vector(
                w_, v_, LabeledPointBatch(x, y, o, ws)
            )
            return jax.lax.psum(hv, self.data_axis)

        spec = self._spec()
        spec["in_specs"] = (P(),) + spec["in_specs"]
        hv = shard_map(f, out_specs=P(), **spec)(
            w, v, *self._args(batch)
        )
        if self.l2_weight > 0.0:
            hv = hv + self.l2_weight * v
        return hv

    def bind(self, batch: LabeledPointBatch) -> BoundObjective:
        return BoundObjective(self, batch)
