"""GLM objective functions: value / gradient / Hessian-vector / Hessian matrix.

This is the TPU-native replacement for the reference's hand-written streaming
aggregators (photon-lib function/glm/ValueAndGradientAggregator.scala,
HessianVectorAggregator.scala, HessianMatrixAggregator.scala) and the
objective-function hierarchy (function/ObjectiveFunction.scala:25-73,
DiffFunction, TwiceDiffFunction, L2Regularization.scala:26-72).

Design: the objective is a *pure scalar function* of the coefficients; the
gradient is ``jax.grad`` and the Hessian-vector product is a ``jax.jvp`` of
the gradient. XLA fuses the entire per-sample seqOp (margin dot product,
pointwise loss, axpy accumulation) into one pass over the feature block —
the fusion the reference implemented by hand, for free, on the MXU.

Normalization is folded in algebraically exactly as the reference does
(effective coefficients + margin shift, ValueAndGradientAggregator.scala:36-49)
so the feature data is never rewritten.

Distribution: there is no Distributed-vs-SingleNode split. Under jit with a
batch sharded along the sample axis, XLA inserts the cross-device reductions
(psum trees) that replace ``RDD.treeAggregate``
(DistributedGLMLossFunction.scala:91-135). The same objective vmaps over
per-entity blocks for random-effect local solves. An explicit ``axis_name``
is supported for shard_map contexts.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext, no_normalization

Array = jax.Array

# Private, because jax has no public "is this value vmapped" query. A jax
# that moves it fails this import — a broken build, not a quiet loss of the
# one-pass kernel (tests/test_pallas_glm.py pins that it still discriminates).
from jax._src.interpreters.batching import BatchTracer as _BatchTracer


def _under_vmap(*arrays) -> bool:
    """True when any input is a vmap batch tracer (the Pallas kernel has no
    batching rule worth using; vmapped lanes stay on the autodiff path)."""
    return any(isinstance(a, _BatchTracer) for a in arrays)


class GLMObjective:
    """Weighted GLM objective: sum_i w_i * l(margin_i, y_i) + (l2/2)‖w‖².

    The L1 term of elastic-net regularization is *not* part of this smooth
    objective — it is handled by OWL-QN's pseudo-gradient, mirroring the
    reference where L1 lives in breeze's OWLQN, not in the loss
    (optimization/OWLQN.scala:40-86).
    """

    def __init__(
        self,
        loss: PointwiseLoss,
        l2_weight: float = 0.0,
        normalization: NormalizationContext | None = None,
        axis_name: str | None = None,
        use_pallas: bool | None = None,
    ):
        self.loss = loss
        self.l2_weight = float(l2_weight)
        self.normalization = normalization if normalization is not None else no_normalization()
        self.axis_name = axis_name
        #: route value_and_gradient through the single-pass Pallas kernel
        #: (ops/pallas_glm.py). None (default) means "auto": the kernel on
        #: TPU whenever the call is not visibly vmapped. The kernel streams
        #: X across HBM once per eval where autodiff reads it twice —
        #: measured ~2x per eval f32 and more with bf16 feature blocks
        #: (BASELINE.md r4 study). False forces autodiff — REQUIRED for
        #: (a) solves that get vmapped (λ-grid lanes, per-entity RE/MF
        #: buckets): `lax.while_loop` bodies trace with UNBATCHED tracers,
        #: so the auto-detection below cannot see a vmap wrapping the
        #: solver loop, and a Pallas call baked into the loop body batches
        #: into a serial per-lane loop (~lanes x slower); and (b) GSPMD
        #: mesh-sharded batches, whose pallas_call XLA cannot partition
        #: (parallel/distributed.py sets it). True forces the kernel where
        #: supported (still falls back on a DIRECTLY visible vmap).
        self.use_pallas = use_pallas

    # Value-based identity so jit static-arg caching works across repeated
    # construction (coordinate-descent iterations reuse compiled programs).
    # Normalization contexts hold arrays, so they compare by object identity;
    # coordinates construct theirs once.
    def _key(self):
        return (type(self.loss), self.l2_weight, self.axis_name,
                id(self.normalization), self.use_pallas)

    def __eq__(self, other):
        return isinstance(other, GLMObjective) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    # -- core scalar function ------------------------------------------------

    def margins(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        eff = self.normalization.effective_coefficients(coefficients)
        shift = self.normalization.margin_shift(eff)
        x = batch.features
        if x.dtype == jnp.bfloat16 and eff.dtype != jnp.bfloat16:
            # bf16 feature blocks: X stays bf16 across HBM, the product's
            # operands are rounded and the MXU accumulates in f32 — the
            # Pallas kernel's bf16 arithmetic. The float32 product's words
            # are ``_feature_product``'s, at the end of this file.
            with jax.named_scope("glm/margins"):
                m = jnp.matmul(x, eff.astype(jnp.bfloat16),
                               preferred_element_type=eff.dtype)
        else:
            m = _feature_product(x, eff)
        return m - shift + batch.offsets

    def _data_value(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        margins = self.margins(coefficients, batch)
        losses = self.loss.loss(margins, batch.labels)
        total = jnp.sum(batch.weights * losses)
        if self.axis_name is not None:
            total = jax.lax.psum(total, self.axis_name)
        return total

    def value(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        total = self._data_value(coefficients, batch)
        if self.l2_weight > 0.0:
            total = total + 0.5 * self.l2_weight * jnp.vdot(coefficients, coefficients)
        return total

    # -- derivatives ---------------------------------------------------------

    def _pallas_enabled(self, coefficients: Array, batch: LabeledPointBatch) -> bool:
        if self.use_pallas is False or self.axis_name is not None:
            return False
        if _under_vmap(coefficients, batch.features):
            # vmapped lanes (λ-grid, per-entity RE solves) share X reads
            # across lanes in one XLA matmul — the kernel has no lane axis
            return False
        from photon_ml_tpu.ops.pallas_glm import MAX_KERNEL_DIM, kernel_supports

        supported = kernel_supports(batch.features.shape[-1])
        if self.use_pallas is None:
            return supported and jax.default_backend() == "tpu"
        if not supported:
            raise ValueError(
                f"use_pallas=True on a {batch.features.shape[-1]}-wide dense "
                f"block: the kernel compiles up to {MAX_KERNEL_DIM} lane-padded "
                "columns; leave use_pallas=None (auto) for wider blocks"
            )
        return True

    def value_and_gradient(
        self, coefficients: Array, batch: LabeledPointBatch
    ) -> tuple[Array, Array]:
        if self._pallas_enabled(coefficients, batch):
            from photon_ml_tpu.ops.pallas_glm import fused_value_and_gradient

            return fused_value_and_gradient(
                self.loss, coefficients, batch,
                l2_weight=self.l2_weight, normalization=self.normalization,
            )
        return jax.value_and_grad(self.value)(coefficients, batch)

    def gradient(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        return self.value_and_gradient(coefficients, batch)[1]

    def hessian_vector(
        self, coefficients: Array, vector: Array, batch: LabeledPointBatch
    ) -> Array:
        """H @ v (TRON's CG step, TRON.scala:298-300): ``_one_pass_hessian_vector``
        (at the end of this file) where its rule admits, else a jvp of the gradient."""
        if self._pallas_enabled(coefficients, batch) and not _under_vmap(vector):
            return _one_pass_hessian_vector(self, coefficients, vector, batch)
        with jax.default_matmul_precision("highest"):
            return jax.jvp(lambda w: jax.grad(self.value)(w, batch),
                           (coefficients,), (vector,))[1]

    def hessian_matrix(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        """Dense Hessian X'ᵀ D X' + l2·I — for variance estimation / diagnostics
        on small dims only (reference HessianMatrixAggregator, used by
        DistributedOptimizationProblem variance computation).
        """
        margins = self.margins(coefficients, batch)
        d2 = self.loss.d2z(margins, batch.labels) * batch.weights
        factors = self.normalization.factors
        x = batch.features
        if factors is not None:
            x = x * factors
        if self.normalization.shifts is not None:
            shift_row = self.normalization.shifts * (
                factors if factors is not None else 1.0
            )
            x = x - shift_row
        h = _weighted_gram(x, d2)
        if self.axis_name is not None:
            h = jax.lax.psum(h, self.axis_name)
        if self.l2_weight > 0.0:
            h = h + self.l2_weight * jnp.eye(h.shape[0], dtype=h.dtype)
        return h

    def hessian_diagonal(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        """diag(H) without materializing H — used for diagonal variance
        approximation at large dims."""
        margins = self.margins(coefficients, batch)
        d2 = self.loss.d2z(margins, batch.labels) * batch.weights
        factors = self.normalization.factors
        x = batch.features
        if factors is not None:
            x = x * factors
        if self.normalization.shifts is not None:
            shift_row = self.normalization.shifts * (
                factors if factors is not None else 1.0
            )
            x = x - shift_row
        diag = jnp.einsum("n,nd,nd->d", d2, x, x)
        if self.axis_name is not None:
            diag = jax.lax.psum(diag, self.axis_name)
        if self.l2_weight > 0.0:
            diag = diag + self.l2_weight
        return diag

    # -- functional views for the optimizers ---------------------------------

    def bind(self, batch: LabeledPointBatch) -> "BoundObjective":
        return BoundObjective(self, batch)


class BoundObjective:
    """Objective closed over a fixed batch: pure functions of coefficients.

    This is what optimizers consume; it is also what gets vmapped over entity
    blocks for random-effect coordinates.
    """

    def __init__(self, objective: GLMObjective, batch: LabeledPointBatch):
        self.objective = objective
        self.batch = batch

    def value(self, w: Array) -> Array:
        return self.objective.value(w, self.batch)

    def value_and_grad(self, w: Array) -> tuple[Array, Array]:
        return self.objective.value_and_gradient(w, self.batch)

    def hessian_vector(self, w: Array, v: Array) -> Array:
        return self.objective.hessian_vector(w, v, self.batch)

    def hessian_matrix(self, w: Array) -> Array:
        return self.objective.hessian_matrix(w, self.batch)


ValueAndGradFn = Callable[[Array], tuple[Array, Array]]
HessianVectorFn = Callable[[Array, Array], Array]


def _one_pass_hessian_vector(
    objective: GLMObjective, coefficients: Array, vector: Array, batch: LabeledPointBatch
) -> Array:
    """``GLMObjective.hessian_vector`` under the rule that gives
    ``value_and_gradient`` the one-pass kernel (``_pallas_enabled``, and a
    ``vector`` that is no vmap tracer either): the kernel's sibling,
    ops/pallas_glm.fused_hessian_vector, one read of X a product. Everywhere
    else the method takes one jvp of the gradient, its contractions at
    precision "highest": float32 stays float32 whatever the backend makes of
    them. Compiled for a v5e at 400,000 x 2,000 that fallback is two
    multiply-reduce passes over X (``X v``, then ``X' u``) and a hoisted third
    a round, none on the MXU.

    Down here, and the method's body at the ten lines it had, because a line
    that moves above a Python caller of the gradient kernel
    (``BoundObjective.value_and_grad`` is one) re-keys every compiled program
    that holds that kernel (PERF.md 6, PR 24)."""
    from photon_ml_tpu.ops.pallas_glm import fused_hessian_vector

    return fused_hessian_vector(
        objective.loss, coefficients, vector, batch,
        l2_weight=objective.l2_weight, normalization=objective.normalization,
    )


def _feature_product(x: Array, eff: Array) -> Array:
    """``x @ eff`` of ``GLMObjective.margins``, under the scope ``glm/margins``
    (the gradient's product carries the same scope inside ``transpose(jvp())``)
    and at precision "highest". Un-vmapped, and over the random effects'
    ``[e, cap, d]`` lanes, XLA lowers it to a float32 multiply-reduce on the
    vector unit whatever the precision says; under a lane axis over ONE X (the
    λ grid's lanes, ``estimators.train_glm_grid``) it is ``[n, d] x [d, L]`` and
    its transpose, true matrix products, which a TPU at default precision feeds
    to the MXU with float32 operands rounded to bfloat16 (PERF.md 6, PR 47).
    Down here for ``_one_pass_hessian_vector``'s reason."""
    with jax.named_scope("glm/margins"):
        return jnp.matmul(x, eff, precision=jax.lax.Precision.HIGHEST)


def _weighted_gram(x: Array, d2: Array) -> Array:
    """``x' diag(d2) x`` of ``GLMObjective.hessian_matrix``, at precision
    "highest". Under the random effects' lanes (``vmap``) it is a batched
    ``[e, d, cap] x [e, cap, d]`` contraction, a true matrix product, which a
    TPU at default precision feeds to the MXU with float32 operands rounded to
    bfloat16 (PERF.md 6, PR 47, of ``_feature_product``; PR 50 for this one):
    for a squared loss the Newton step built on it IS the ridge solution, so a
    rounded ``X'X`` is a rounded model. Down here for
    ``_one_pass_hessian_vector``'s reason."""
    return jnp.matmul(x.T, d2[:, None] * x, precision=jax.lax.Precision.HIGHEST)
