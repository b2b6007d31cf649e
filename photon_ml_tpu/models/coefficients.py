"""Model coefficients: means + optional variances.

Reference parity: photon-lib model/Coefficients.scala — a coefficient vector
with optional per-coefficient variances (from the inverse Hessian diagonal),
persisted as BayesianLinearModelAvro.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch, sparse_product

Array = jax.Array

#: a sparse block scored as ONE program, the block an ARGUMENT (every layout
#: of the batch: the hybrid head, the ELL tail, the flat overflow)
_sparse_score = jax.jit(sparse_product)


@flax.struct.dataclass
class Coefficients:
    means: Array
    variances: Array | None = None

    @property
    def dim(self) -> int:
        return self.means.shape[-1]

    def compute_score(self, features) -> Array:
        """Dot product score (reference Coefficients.computeScore):
        ``features`` a dense [n, d] block, or a ``SparseLabeledPointBatch``
        whose rows are scored through its own layout (``sparse_product``:
        the batch's offsets are not part of a score)."""
        if isinstance(features, SparseLabeledPointBatch):
            return _sparse_score(features, self.means)
        return features @ self.means

    @classmethod
    def zeros(cls, dim: int, dtype=jnp.float32) -> "Coefficients":
        return cls(means=jnp.zeros((dim,), dtype=dtype))
