"""Dense batched training data: the TPU-native LabeledPoint.

Reference parity: photon-lib data/LabeledPoint.scala — per-sample
(label, features, offset, weight). On TPU the unit is not one sample but a
dense [n, d] block: the MXU wants large batched matmuls, so sparse per-sample
vectors become padded dense rows (feature shards are domain-limited, see
SURVEY.md §7 "Sparse features on TPU").

``weights`` double as the padding mask: padded rows carry weight 0 and
therefore contribute nothing to any weighted aggregate — value, gradient,
Hessian-vector, or evaluator. This is how fixed-shape jit programs coexist
with ragged real-world data.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax._src.config import persistent_cache_min_compile_time_secs as _cache_write_floor
from jax.experimental.layout import Format, Layout

from photon_ml_tpu.telemetry.registry import default_registry

Array = jax.Array

_LANE = 128  # TPU lane width: the last dimension of every tile
#: widest (lane-padded) feature block the Pallas GLM kernels take
#: (ops/pallas_glm.py). At it the row tile stands at its floor of 128 rows
#: (8 MiB of float32, 23.9 MiB of the 32 MiB of scoped VMEM the kernels ask
#: for); the auto rule (ops/objective.py) keeps wider dense blocks on the XLA
#: path, and forcing the kernel there raises. chip_smoke.py's kernel leg
#: compiles and runs it on the chip, and tests/test_tpu_compile.py compiles
#: it for a described v5e. It stands here, under ops/, because the batch's placement
#: (``in_kernel_layout``) asks the same question the auto rule does.
MAX_KERNEL_DIM = 16384
#: most a block may grow when its rows are stored whole lanes wide:
#: ``round_up(d, 128) / d``. 2.4 % at d = 2,000, nothing at 256; a
#: ``[n, 16]`` block would be stored eight times over and is left as it lies.
MAX_ROW_MAJOR_GROWTH = 1.125
#: the layout the kernels' X operand has: rows major, features along the lanes
KERNEL_LAYOUT = Layout(major_to_minor=(0, 1))
#: registry counter: dense blocks ``in_kernel_layout`` actually moved, and
#: the bytes the last of them takes as it lies now
DENSE_RELAYOUTS = "data/dense_relayouts"
DENSE_RELAYOUT_BYTES = "data/dense_relayout_bytes"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def kernel_supports(num_features: int) -> bool:
    """Whether a dense block this wide is in the kernels' compiled range."""
    return _round_up(num_features, _LANE) <= MAX_KERNEL_DIM


def _major_to_minor(features) -> tuple[int, ...] | None:
    """How a device array lies, as the runtime reports it; None where it
    reports nothing."""
    layout = features.format.layout
    return None if layout is None else tuple(layout.major_to_minor)


def _row_major(resident: Array) -> Array:
    """``resident`` copied into ``KERNEL_LAYOUT`` on its own devices: a jitted
    identity with the format as ``out_shardings``, which is what
    ``jax.device_put`` to a ``Format`` runs, but compiled in THIS process and
    never written to the persistent compile cache. An executable LOADED from
    that cache hands back arrays that report the platform's default layout
    whatever their buffers hold (chip run, PR 49: the second run of a cell
    died of "expected parameter 0 of size 3200000000 ... but got buffer with
    incompatible size 3276800000"), and ``jit`` compiles for what an array
    reports. 0.1 s of compile a process; nothing else here is cache-shy.
    The floor is raised for THIS thread and this compile alone (a jax config
    state entered as a context): another thread's compiles write as before."""

    def as_the_kernels_read_it(x):  # the program's name, and so its cache key
        return x

    with _cache_write_floor(float("inf")):
        return jax.jit(
            as_the_kernels_read_it,
            out_shardings=Format(KERNEL_LAYOUT, resident.sharding),
        )(resident)


def in_kernel_layout(features):
    """A dense feature block as the Pallas GLM kernels read it: row-major.

    A TPU keeps a ``[400000, 2000]`` float32 array COLUMN-major (that pads
    nothing; row-major pads 2,000 lanes to 2,048), the kernels' operand is
    row-major, and XLA put a relayout of all of X in front of the kernel in
    every program that took such an X as an argument: twice a
    ``glm/path_solve``, eight copies of 9.95 ms a fit (PERF.md 6, PR 33 and
    PR 49). Placed here ONCE, as a committed array in ``KERNEL_LAYOUT``
    (tiling left to the platform), every later ``jit`` is compiled for the
    layout the array has and reads it as it lies.

    The rule reads what it can observe and nothing a caller sets: the
    default backend is ``tpu``; the block is a concrete 2-D float32 or
    bfloat16 array (host rows go to the default device); ``kernel_supports(d)``
    (the auto rule's own predicate, ops/objective.py); whole lanes cost at
    most ``MAX_ROW_MAJOR_GROWTH`` of the block; the array does not already
    lie that way. In every other case the SAME object comes back: on a CPU,
    under a trace, for an integer or float64 block, beyond
    ``MAX_KERNEL_DIM``, for a narrow block, where the platform's default is
    row-major already.
    """
    if jax.default_backend() != "tpu":
        return features
    if isinstance(features, jax.core.Tracer) or not isinstance(
            features, (jax.Array, np.ndarray)):
        return features
    if features.ndim != 2 or features.dtype not in (jnp.float32, jnp.bfloat16):
        return features
    n, d = features.shape
    lanes = _round_up(d, _LANE)
    if not kernel_supports(d) or lanes > MAX_ROW_MAJOR_GROWTH * d:
        return features
    resident = jnp.asarray(features)  # host rows: to the default device first
    if _major_to_minor(resident) == KERNEL_LAYOUT.major_to_minor:
        return features
    placed = _row_major(resident)
    if _major_to_minor(placed) != KERNEL_LAYOUT.major_to_minor:
        # a copy that reports another layout than it was compiled to give
        # would be read transposed, or refused for its size, by the next jit
        raise RuntimeError(
            f"in_kernel_layout: a {features.shape} {features.dtype} block placed "
            f"in {KERNEL_LAYOUT} reports {placed.format.layout}")
    registry = default_registry()
    registry.counter(DENSE_RELAYOUTS).inc()
    registry.gauge(DENSE_RELAYOUT_BYTES).set(n * lanes * features.dtype.itemsize)
    return placed


@flax.struct.dataclass
class LabeledPointBatch:
    """A dense block of labeled samples.

    features: [n, d] float array
    labels:   [n] float array
    offsets:  [n] float array — prior/residual scores added to the margin
              (the residual mechanism of coordinate descent,
              reference data/DataSet.scala addScoresToOffsets)
    weights:  [n] float array — sample weights; 0 marks padding
    """

    features: Array
    labels: Array
    offsets: Array
    weights: Array

    @property
    def num_samples(self) -> int:
        return self.features.shape[0]

    @property
    def dim(self) -> int:
        return self.features.shape[1]

    @property
    def dtype(self):
        return self.features.dtype

    @property
    def solve_dtype(self):
        """Dtype for coefficients/optimizer state: bf16 feature blocks
        (half the HBM traffic on the hot loop) still solve in f32 — only
        the per-product operand is bf16; accumulation, coefficients, and
        every aux column stay f32 (CLAUDE.md: a bf16 block is a no-op
        unless the whole read path is bf16; the solve path must NOT be)."""
        import jax.numpy as _jnp

        return _jnp.float32 if self.features.dtype == _jnp.bfloat16 else self.features.dtype

    def with_offsets(self, offsets: Array) -> "LabeledPointBatch":
        return self.replace(offsets=offsets)

    def add_scores_to_offsets(self, scores: Array) -> "LabeledPointBatch":
        """Residual update used by coordinate descent (DataSet.addScoresToOffsets)."""
        return self.replace(offsets=self.offsets + scores)

    @classmethod
    def create(
        cls,
        features,
        labels,
        offsets=None,
        weights=None,
        dtype=None,
    ) -> "LabeledPointBatch":
        """Build a batch. ``dtype=None`` preserves the input float dtype
        (float64 in x64 test mode, float32 in production). A device array of
        the asked dtype is taken as it is, with ONE exception: on a TPU a
        block the GLM kernels will read is placed row-major here, once
        (``in_kernel_layout``: a relayout copy of the block; the caller's
        array is not touched), so that no fit relayouts it again."""
        features = in_kernel_layout(jnp.asarray(features, dtype=dtype))
        if dtype is None:
            dtype = features.dtype
        if dtype == jnp.bfloat16:
            # bf16 applies to the FEATURE BLOCK only; labels/offsets/weights
            # stay f32 (loss math and accumulation are f32 throughout)
            dtype = jnp.float32
        labels = jnp.asarray(labels, dtype=dtype)
        n = features.shape[0]
        if offsets is None:
            offsets = jnp.zeros((n,), dtype=dtype)
        else:
            offsets = jnp.asarray(offsets, dtype=dtype)
        if weights is None:
            weights = jnp.ones((n,), dtype=dtype)
        else:
            weights = jnp.asarray(weights, dtype=dtype)
        return cls(features=features, labels=labels, offsets=offsets, weights=weights)

    def pad_to(self, n: int) -> "LabeledPointBatch":
        """Pad to n rows with zero-weight rows (fixed shapes for jit)."""
        cur = self.num_samples
        if cur == n:
            return self
        if cur > n:
            raise ValueError(f"cannot pad {cur} rows down to {n}")
        pad = n - cur
        return LabeledPointBatch(
            features=jnp.pad(self.features, ((0, pad), (0, 0))),
            labels=jnp.pad(self.labels, (0, pad)),
            offsets=jnp.pad(self.offsets, (0, pad)),
            weights=jnp.pad(self.weights, (0, pad)),
        )


def solve_dtype_of(feature_dtype) -> jnp.dtype:
    """Coefficient/optimizer-state dtype for a feature-block dtype: bf16
    blocks still solve in f32 (see LabeledPointBatch.solve_dtype)."""
    return (
        jnp.float32 if jnp.dtype(feature_dtype) == jnp.bfloat16
        else jnp.dtype(feature_dtype)
    )


def compute_margins(batch: LabeledPointBatch, coefficients: Array) -> Array:
    """margin_i = x_i . w + offset_i (reference DataPoint.computeMargin)."""
    return batch.features @ coefficients + batch.offsets


def summarize(features: np.ndarray, weights: np.ndarray | None = None) -> dict:
    """Weighted feature summary (reference stat/BasicStatisticalSummary.scala).

    Returns mean, variance (unbiased, weighted), max, min, max_magnitude,
    norm_l1, norm_l2, num_nonzeros per feature column — the statistics the
    reference gets from Spark MLLIB's MultivariateStatisticalSummary.
    """
    features = np.asarray(features, dtype=np.float64)
    n = features.shape[0]
    if weights is None:
        weights = np.ones((n,), dtype=np.float64)
    else:
        weights = np.asarray(weights, dtype=np.float64)
    wsum = weights.sum()
    mean = (weights[:, None] * features).sum(axis=0) / wsum
    centered = features - mean
    var = (weights[:, None] * centered * centered).sum(axis=0) / np.maximum(wsum - 1.0, 1.0)
    return {
        "count": n,
        "weight_sum": wsum,
        "mean": mean,
        "variance": var,
        "max": features.max(axis=0) if n else np.zeros(features.shape[1]),
        "min": features.min(axis=0) if n else np.zeros(features.shape[1]),
        "max_magnitude": np.abs(features).max(axis=0) if n else np.zeros(features.shape[1]),
        "norm_l1": np.abs(features).sum(axis=0),
        "norm_l2": np.sqrt((features * features).sum(axis=0)),
        "num_nonzeros": (features != 0).sum(axis=0).astype(np.float64),
    }
