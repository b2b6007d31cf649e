"""Coefficient-variance estimation for trained GLMs.

Reference parity: DistributedOptimizationProblem.computeVariances
(photon-api optimization/DistributedOptimizationProblem.scala:82-96) and
SingleNodeOptimizationProblem.computeVariances (:58-69) — both build the
full Hessian at the optimum and return diag(H⁻¹) via Cholesky inverse
(photon-lib util/Linalg.scala choleskyInverse).

TPU-native: H is one X'ᵀDX' matmul (GLMObjective.hessian_matrix, since PR 50 at
precision "highest": float32 operands stay float32 on the MXU, in several
bfloat16 passes, where the platform's default rounds them to bfloat16 once);
diag(H⁻¹) = column sums of squares of L⁻¹ where H = LLᵀ, i.e. one triangular
solve against I. O(d³) compute / O(d²) memory, so FULL is gated to small d;
above FULL_VARIANCE_MAX_DIM the AUTO mode falls back to the diagonal
approximation 1/diag(H) (exact when H is diagonal, and the only option at
giant-FE scale where H cannot be materialized).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array

#: AUTO uses the reference-fidelity full Cholesky inverse up to this many
#: coefficients (d² Hessian = 64 MB f32 at the boundary), diagonal beyond.
FULL_VARIANCE_MAX_DIM = 4096

_MODES = ("auto", "full", "diagonal")


def validate_variance_mode(mode: str) -> str:
    """Fail fast on typos (called at config-parse time, before any solve)."""
    if mode not in _MODES:
        raise ValueError(f"variance mode must be one of {_MODES}, got {mode!r}")
    return mode


def resolve_variance_mode(mode: str, dim: int, num_problems: int = 1) -> str:
    """Resolve "auto" to a concrete mode.

    num_problems: how many d×d Hessians materialize at once (e.g. vmapped
    λ-grid lanes) — AUTO's memory budget covers the whole stack, not one.
    """
    validate_variance_mode(mode)
    if mode == "auto":
        budget = FULL_VARIANCE_MAX_DIM * FULL_VARIANCE_MAX_DIM
        return "full" if num_problems * dim * dim <= budget else "diagonal"
    return mode


def resolve_variance_mode_for(
    objective, mode: str, dim: int, num_problems: int = 1
) -> str:
    """Like :func:`resolve_variance_mode`, but also accounts for objectives
    that cannot materialize a dense Hessian (sparse/giant-d): AUTO falls
    back to diagonal; an explicit "full" request raises."""
    resolved = resolve_variance_mode(mode, dim, num_problems)
    if resolved == "full" and not hasattr(objective, "hessian_matrix"):
        if mode == "full":
            raise ValueError(
                "variance_mode='full' requires a dense Hessian; this "
                f"objective ({type(objective).__name__}) only supports the "
                "diagonal approximation"
            )
        resolved = "diagonal"
    return resolved


def inverse_of_diagonal(diag: Array) -> Array:
    """The diagonal approximation's clamped inverse — single definition so
    every path (sequential, grid lanes, per-entity) uses the same floor."""
    return 1.0 / jnp.maximum(diag, 1e-12)


def diag_inverse_from_hessian(h: Array) -> Array:
    """diag(H⁻¹) via Cholesky, without forming H⁻¹, with a built-in guard:
    entries where the factorization produced non-finite values (H not
    positive definite — e.g. λ=0 with exactly collinear features, or a
    per-entity block with fewer samples than dimensions) fall back to the
    clamped diagonal approximation 1/diag(H) elementwise, instead of
    persisting NaN into saved models. (The reference's breeze `cholesky`
    throws outright on non-PD input — Linalg.scala choleskyInverse — but a
    traceable elementwise select is the jit/vmap-compatible equivalent.)
    Near-singular-but-factorizable H yields large variances, same as the
    reference.

    H = LLᵀ ⇒ H⁻¹ = L⁻ᵀL⁻¹ ⇒ diag(H⁻¹)ᵢ = Σⱼ (L⁻¹)ⱼᵢ².
    """
    chol = jnp.linalg.cholesky(h)
    eye = jnp.eye(h.shape[0], dtype=h.dtype)
    linv = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    full = jnp.sum(linv * linv, axis=0)
    approx = inverse_of_diagonal(jnp.diagonal(h))
    return jnp.where(jnp.isfinite(full), full, approx)


def full_inverse_from_hessian(h: Array) -> Array:
    """Full H⁻¹ via Cholesky (for covariance PROPAGATION through a
    projection: diag(P H⁻¹ Pᵀ) needs the off-diagonal entries that
    :func:`diag_inverse_from_hessian` never materializes). Non-PD H falls
    back to the clamped diagonal-only inverse, mirroring that function's
    guard."""
    chol = jnp.linalg.cholesky(h)
    eye = jnp.eye(h.shape[0], dtype=h.dtype)
    linv = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    full = linv.T @ linv
    approx = jnp.diag(inverse_of_diagonal(jnp.diagonal(h)))
    return jnp.where(jnp.isfinite(full).all(), full, approx)


@partial(jax.jit, static_argnums=(0,))
def _full_variances(objective, coefficients: Array, batch) -> Array:
    return diag_inverse_from_hessian(
        objective.hessian_matrix(coefficients, batch)
    )


@partial(jax.jit, static_argnums=(0,))
def _diagonal_variances(objective, coefficients: Array, batch) -> Array:
    return inverse_of_diagonal(objective.hessian_diagonal(coefficients, batch))


def coefficient_variances(
    objective, coefficients: Array, batch, mode: str = "auto"
) -> Array:
    """Per-coefficient variances at the optimum, in the objective's space.

    mode: "full" = diag(H⁻¹) (reference fidelity; requires H positive
    definite — guaranteed with l2_weight > 0, generically true for n > d);
    "diagonal" = 1/diag(H); "auto" picks by dimension.
    """
    resolved = resolve_variance_mode_for(
        objective, mode, int(coefficients.shape[-1])
    )
    if resolved == "full":
        return _full_variances(objective, coefficients, batch)
    return _diagonal_variances(objective, coefficients, batch)
