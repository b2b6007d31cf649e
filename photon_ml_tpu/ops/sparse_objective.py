"""GLM objective over flat-COO sparse batches (giant feature spaces).

Reference parity: the same value/gradient/Hessian-vector contract as
GLMObjective (reference function/ObjectiveFunction.scala hierarchy and the
sparse-aware aggregators in function/glm/ValueAndGradientAggregator.scala —
the whole point of their effectiveCoef/marginShift algebra was to keep
sparse vectors sparse; here the algebra is identical and XLA derives the
transpose scatter-add from the forward gather+segment-sum by autodiff).

Memory story: only O(nnz) per-entry arrays and O(d) vectors (coefficients,
gradient, normalization factors) — no [n, d] anywhere. d=10⁷ is a 40 MB f32
coefficient vector; the dense block it replaces would be n·d·4 bytes
(0.5 TB at n=10⁵ already). LBFGS history (m=10 pairs) adds 20·d floats —
at truly giant d prefer TRON (4-5 work vectors), matching the reference's
TRON-for-L2 positioning (SURVEY.md §7).

Mesh story: the coefficient axis shards over "model"
(``NamedSharding(mesh, P("model"))``); the gather at ``w[col_indices]``
and the transpose scatter lower to XLA collectives automatically under
jit. The flat entry arrays shard over "data" like dense sample axes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.sparse_batch import (
    SparseLabeledPointBatch,
    hot_head_dot,
    sparse_column_sum,
    sparse_margins,
    sparse_product,
    tail_transpose_add,
)
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import (
    NormalizationContext,
    no_normalization,
)
from photon_ml_tpu.ops.objective import BoundObjective

Array = jax.Array

class SparseGLMObjective:
    """Sparse twin of GLMObjective: same interface, flat-COO batches.

    Supports the full normalization algebra (factors + shifts): the margin
    uses effective coefficients and the scalar margin shift, so shifted
    (standardized) features never densify the data — autodiff turns the
    shift term into the dense rank-one gradient correction automatically.
    """

    def __init__(
        self,
        loss: PointwiseLoss,
        l2_weight: float = 0.0,
        normalization: NormalizationContext | None = None,
        axis_name: str | None = None,
    ):
        self.loss = loss
        self.l2_weight = float(l2_weight)
        self.normalization = (
            normalization if normalization is not None else no_normalization()
        )
        self.axis_name = axis_name

    # Value-based identity so jit static-arg caching works (same contract as
    # GLMObjective._key).
    def _key(self):
        return (type(self.loss), self.l2_weight, self.axis_name,
                id(self.normalization))

    def __eq__(self, other):
        return isinstance(other, SparseGLMObjective) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    # -- core scalar function ------------------------------------------------

    def margins(self, coefficients: Array, batch: SparseLabeledPointBatch) -> Array:
        eff = self.normalization.effective_coefficients(coefficients)
        shift = self.normalization.margin_shift(eff)
        return sparse_margins(batch, eff) - shift

    def value(self, coefficients: Array, batch: SparseLabeledPointBatch) -> Array:
        margins = self.margins(coefficients, batch)
        losses = self.loss.loss(margins, batch.labels)
        total = jnp.sum(batch.weights * losses)
        if self.axis_name is not None:
            total = jax.lax.psum(total, self.axis_name)
        if self.l2_weight > 0.0:
            total = total + 0.5 * self.l2_weight * jnp.vdot(coefficients, coefficients)
        return total

    # -- derivatives ---------------------------------------------------------

    def value_and_gradient(
        self, coefficients: Array, batch: SparseLabeledPointBatch
    ) -> tuple[Array, Array]:
        if batch.has_hybrid_view:
            return self._value_and_gradient_hybrid(coefficients, batch)
        return jax.value_and_grad(self.value)(coefficients, batch)

    def _tail_gradient_update(
        self, g_eff: Array, dzw: Array, batch: SparseLabeledPointBatch
    ) -> Array:
        """Scatter the cold-tail contributions (the ELL view's tiers + flat
        overflow) into the effective gradient — the same transpose scatters
        autodiff derives for the ELL path (``tail_transpose_add``), written
        out so the hybrid value+gradient shares ONE dz evaluation across
        head and tail (the r4 dense-kernel single-pass discipline)."""
        with jax.named_scope("sparse/tail_gradient"):
            return tail_transpose_add(g_eff, batch, dzw)

    def _head_gradient(
        self, row_terms: Array, batch: SparseLabeledPointBatch
    ) -> Array:
        """The hot head's share of ``X' row_terms`` as a [dim] vector: one
        dense [n]·[n, k_hot] product at float32 (``hot_head_dot``) and a
        k_hot-sized scatter, under the scope ``sparse/head``."""
        with jax.named_scope("sparse/head"):
            # the solve's dtype: bfloat16 feature values still accumulate in float32
            g_eff = jnp.zeros((batch.dim,), dtype=batch.solve_dtype)
            return g_eff.at[batch.hot_col_ids].add(
                hot_head_dot(row_terms, batch.hot_vals)
            )

    def _value_and_gradient_hybrid(
        self, coefficients: Array, batch: SparseLabeledPointBatch
    ) -> tuple[Array, Array]:
        """Hand-fused value+gradient over the hybrid dense-head/sparse-tail
        layout (ISSUE 5 tentpole).

        One forward margin evaluation (hot MXU matmul + ELL/flat tail), one
        dz, then the gradient assembles as
            head:  dzwᵀ X_hot  — a dense [n]·[n, k_hot] matvec (at float32:
                   ``hot_head_dot``) plus a k_hot-sized scatter into [dim]
                   (amortized over n rows; NO per-entry index ops for
                   covered nonzeros)
            tail:  the existing ELL/flat transpose scatters, now over the
                   cold residual only
        with the full normalization algebra (f = factors, dz = w_i·l'_i):
            margin_i = Σ vals·eff[cols] − eff·shifts + offsets
            ∂/∂w     = f ⊙ (Σ dz·x − (Σ dz)·shifts) + λw.
        Verified against the flat autodiff path in tests (the view-contract
        property test)."""
        margins = self.margins(coefficients, batch)
        losses, dz = self.loss.loss_and_dz(margins, batch.labels)
        total = jnp.sum(batch.weights * losses)
        dzw = batch.weights * dz
        g_eff = self._head_gradient(dzw, batch)
        g_eff = self._tail_gradient_update(g_eff, dzw, batch)
        norm = self.normalization
        if norm.shifts is not None:
            g_eff = g_eff - jnp.sum(dzw) * norm.shifts
        grad = g_eff * norm.factors if norm.factors is not None else g_eff
        if self.axis_name is not None:
            total = jax.lax.psum(total, self.axis_name)
            grad = jax.lax.psum(grad, self.axis_name)
        if self.l2_weight > 0.0:
            total = total + 0.5 * self.l2_weight * jnp.vdot(
                coefficients, coefficients
            )
            grad = grad + self.l2_weight * coefficients
        return total, grad

    def gradient(self, coefficients: Array, batch: SparseLabeledPointBatch) -> Array:
        return self.value_and_gradient(coefficients, batch)[1]

    def hessian_vector(
        self, coefficients: Array, vector: Array, batch: SparseLabeledPointBatch
    ) -> Array:
        """H @ v. Hybrid view (and no margin shifts):
            H v = f ⊙ (Xᵀ D X (f ⊙ v)) + λ v,   D = diag(w_i·l''_i)
        with the identical dense-head / sparse-tail split as the gradient —
        forward X(f·v) rides the hot MXU matmul + cold tail, and the
        transpose assembles as the head matvec + k_hot scatter plus the
        tail scatters. This is TRON's CG inner loop at giant d (d=10⁸).
        Otherwise forward-over-reverse jvp of the gradient, same as the
        dense path (TRON calls this per CG step).
        """
        norm = self.normalization
        if batch.has_hybrid_view and norm.shifts is None:
            eff_v = norm.effective_coefficients(vector)
            mv = sparse_product(batch, eff_v)  # pure X @ f·v, no offsets
            margins = self.margins(coefficients, batch)
            d2w = self.loss.d2z(margins, batch.labels) * batch.weights
            t = d2w * mv
            hv_eff = self._head_gradient(t, batch)
            hv_eff = self._tail_gradient_update(hv_eff, t, batch)
            hv = hv_eff * norm.factors if norm.factors is not None else hv_eff
            if self.axis_name is not None:
                hv = jax.lax.psum(hv, self.axis_name)
            if self.l2_weight > 0.0:
                hv = hv + self.l2_weight * vector
            return hv
        grad_fn = lambda w: jax.grad(self.value)(w, batch)
        return jax.jvp(grad_fn, (coefficients,), (vector,))[1]

    def hessian_diagonal(
        self, coefficients: Array, batch: SparseLabeledPointBatch
    ) -> Array:
        """diag(H) = Σ_i w_i l''_i x'_ij² without materializing H.

        With shifts, x'_ij = f_j(x_ij - s_j) expands into sparse, cross, and
        dense terms — all three are one column-sum or one dense vector op.
        """
        margins = self.margins(coefficients, batch)
        d2 = self.loss.d2z(margins, batch.labels) * batch.weights
        f = self.normalization.factors
        s = self.normalization.shifts
        # Σ d2·x², Σ d2·x (per column), Σ d2 (scalar)
        sq = sparse_column_sum(batch, d2, square_values=True)
        if s is not None:
            lin = sparse_column_sum(batch, d2)
            tot = jnp.sum(d2)
            diag = sq - 2.0 * s * lin + s * s * tot
        else:
            diag = sq
        if f is not None:
            diag = diag * f * f
        if self.axis_name is not None:
            diag = jax.lax.psum(diag, self.axis_name)
        if self.l2_weight > 0.0:
            diag = diag + self.l2_weight
        return diag

    # -- functional views ----------------------------------------------------

    def bind(self, batch: SparseLabeledPointBatch) -> BoundObjective:
        """Optimizers consume the same duck-typed BoundObjective as the
        dense path — LBFGS/OWLQN/TRON run unchanged over sparse data."""
        return BoundObjective(self, batch)
