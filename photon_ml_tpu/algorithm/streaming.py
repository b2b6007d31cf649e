"""Out-of-core streaming epochs: exact chunked GLM objectives.

Reference parity: photon-lib function/glm/DistributedGLMLossFunction.scala
:91-135 — the reference computes value/gradient/Hessian-vector as an
``RDD.treeAggregate`` over partitions that never co-reside in one
machine's memory; ValueAndGradientAggregator.scala /
HessianVectorAggregator.scala are its per-partition seqOps. This module is
the TPU-native equivalent for n beyond device memory: a GLM objective is
a SUM over samples, so one epoch over fixed-shape chunks accumulates the
EXACT value/gradient/Hv (not a stochastic estimate), with host decode of
chunk k+1 double-buffered behind device compute of chunk k
(io/stream_reader.ChunkPrefetcher — the Snap ML compute/ingest overlap,
arXiv:1803.06333).

The batch-as-argument rule, mechanized: every chunk enters the device
through the ARGUMENT list of the ONE module-level jitted step, never as a
closed-over constant — a closed-over batch is baked into the program, so
every chunk would be a new program (a compile per chunk) carrying its own
copy of the bytes — and the accumulator is carry-threaded through that
step so
XLA cannot hoist the per-chunk work. dev/lint_parity.py check 9
statically bans nested ``jax.jit`` in the streaming modules to keep it
that way.

Solvers: LBFGS/OWLQN/TRON consume the accumulated (value, grad, Hv)
through their ``host_loop=True`` mode (optim/common.run_while) — the
IDENTICAL per-iteration body math as the in-core solve, driven from
Python so each objective evaluation can be an epoch. The streamed final
loss/coefficients therefore match the in-core solver to float round-off
(the only difference is the chunked summation order).

Multi-process composition: with a ``MetadataExchange``, each rank streams
only its own block assignment (io/stream_reader.plan_chunks block_subset)
and the per-rank data-part accumulators are summed IN RANK ORDER through
the exchange at every epoch end — deterministic, identical on every rank,
riding the exchange's rank-attributed deadlines. Regularization is added
once, after the cross-rank sum.
"""

from __future__ import annotations

import base64
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.io.stream_reader import (
    DEFAULT_CHUNK_TIMEOUT,
    ChunkPrefetcher,
    ChunkSource,
)
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.telemetry.program_ledger import ledger_jit

Array = jax.Array


# ---------------------------------------------------------------------------
# The one jit signature chunks ride (module scope — lint check 9)
# ---------------------------------------------------------------------------


@partial(ledger_jit, label="streaming/accumulate_value_grad",
         static_argnames=("objective",))
def _accumulate_value_grad(acc_value, acc_grad, coefficients, batch, *, objective):
    """acc += chunk's DATA value/gradient (no regularization — that is
    added once per epoch, after any cross-rank sum). The accumulators are
    the carry; the chunk batch is an argument."""
    value, grad = objective.value_and_gradient(coefficients, batch)
    return acc_value + value, acc_grad + grad


@partial(ledger_jit, label="streaming/accumulate_hessian_vector",
         static_argnames=("objective",))
def _accumulate_hessian_vector(acc_hv, coefficients, vector, batch, *, objective):
    """acc += chunk's DATA Hessian-vector product (TRON's CG inner loop)."""
    return acc_hv + objective.hessian_vector(coefficients, vector, batch)


def _pack_f64(a: np.ndarray) -> str:
    return base64.b64encode(
        np.ascontiguousarray(a, dtype="<f8").tobytes()
    ).decode("ascii")


def _unpack_f64(s: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s), dtype="<f8")


def _pack_i64(a: np.ndarray) -> str:
    return base64.b64encode(
        np.ascontiguousarray(a, dtype="<i8").tobytes()
    ).decode("ascii")


def _unpack_i64(s: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s), dtype="<i8")


class StreamingGLMObjective:
    """A GLM objective whose every evaluation is one chunked epoch.

    Quacks like ``ops.objective.BoundObjective`` (value / value_and_grad /
    hessian_vector) so ``optim.optimizer.solve(..., host_loop=True)``
    drives it directly; ``.objective`` exposes the underlying per-chunk
    dense/sparse objective (solve()'s loss introspection reads it).

    l2_weight lives HERE, not in the chunk objective: the chunk steps
    accumulate the data part only, and the epoch finalizer adds
    ``(l2/2)‖w‖²`` / ``l2·w`` / ``l2·v`` exactly once — after the
    cross-rank sum when an exchange is attached.

    mesh: optional device mesh — dense chunk batches are placed sharded
    along the sample axis (first mesh axis) before accumulation, so the
    chunked epoch reduces across devices exactly like the in-core sharded
    objective (the 1-vs-8 invariance tests pin it).
    """

    def __init__(
        self,
        source: ChunkSource,
        loss,
        *,
        l2_weight: float = 0.0,
        normalization=None,
        mesh=None,
        exchange=None,
        prefetch: bool = True,
        retry_policy=None,
        chunk_timeout: float = DEFAULT_CHUNK_TIMEOUT,
    ):
        self.source = source
        self.l2_weight = float(l2_weight)
        if source.sparse:
            self.objective = SparseGLMObjective(
                loss, 0.0, normalization=normalization
            )
        else:
            self.objective = GLMObjective(loss, 0.0, normalization=normalization)
        self.mesh = mesh
        self.exchange = exchange
        self.prefetch = bool(prefetch)
        self.retry_policy = retry_policy
        self.chunk_timeout = float(chunk_timeout)
        #: epochs run so far (one per objective evaluation) — journal fodder
        self.epochs = 0

    # -- epoch machinery -----------------------------------------------------

    def _prefetcher(self) -> ChunkPrefetcher:
        return ChunkPrefetcher(
            self.source,
            prefetch=self.prefetch,
            retry_policy=self.retry_policy,
            chunk_timeout=self.chunk_timeout,
        )

    def _place(self, batch):
        if self.mesh is None or self.source.sparse:
            return batch
        from jax.sharding import NamedSharding, PartitionSpec

        axis = self.mesh.axis_names[0]
        row = NamedSharding(self.mesh, PartitionSpec(axis))
        row2d = NamedSharding(self.mesh, PartitionSpec(axis, None))
        shardings = type(batch)(
            features=row2d, labels=row, offsets=row, weights=row
        )
        return jax.device_put(batch, shardings)

    def _epoch(self, fold: Callable, carry):
        # host wall-clock spans only: the accumulate step DISPATCHES
        # asynchronously, so its span measures the host-blocking portion
        # (transfer + dispatch), not device time — exactly the overlap
        # seam the prefetcher's decode/wait spans complement
        with tracing.span("stream/epoch", cat="stream", epoch=self.epochs,
                          chunks=self.source.num_chunks):
            with self._prefetcher() as chunks:
                for i, batch in enumerate(chunks):
                    with tracing.span("stream/accumulate", cat="stream",
                                      chunk=i):
                        carry = fold(carry, self._place(batch))
        self.epochs += 1
        return carry

    def _cross_rank_sum(self, arrays: Sequence[Array]) -> list[np.ndarray]:
        """Sum model-sized accumulators across ranks IN RANK ORDER via the
        metadata exchange (deterministic: every rank computes the identical
        f64 sum). Model-sized payloads only — the [n] sample axis never
        crosses this channel."""
        shapes = [np.asarray(a).shape for a in arrays]
        flat = np.concatenate(
            [np.asarray(a, dtype=np.float64).ravel() for a in arrays]
        )
        gathered = self.exchange.allgather(
            "stream_accumulator", {"acc": _pack_f64(flat)}
        )
        total = np.zeros_like(flat)
        for g in gathered:  # rank order — the exchange contract
            total = total + _unpack_f64(g["acc"])
        out, pos = [], 0
        for shape in shapes:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            out.append(total[pos:pos + size].reshape(shape))
            pos += size
        return out

    # -- BoundObjective protocol ---------------------------------------------

    def value_and_grad(self, w: Array) -> tuple[Array, Array]:
        w = jnp.asarray(w)
        init = (jnp.zeros((), w.dtype), jnp.zeros_like(w))
        acc_f, acc_g = self._epoch(
            lambda carry, batch: _accumulate_value_grad(
                carry[0], carry[1], w, batch, objective=self.objective
            ),
            init,
        )
        if self.exchange is not None and self.exchange.num_ranks > 1:
            f_np, g_np = self._cross_rank_sum([acc_f, acc_g])
            acc_f = jnp.asarray(f_np, w.dtype).reshape(())
            acc_g = jnp.asarray(g_np, w.dtype)
        if self.l2_weight > 0.0:
            acc_f = acc_f + 0.5 * self.l2_weight * jnp.vdot(w, w)
            acc_g = acc_g + self.l2_weight * w
        return acc_f, acc_g

    def value(self, w: Array) -> Array:
        return self.value_and_grad(w)[0]

    def hessian_vector(self, w: Array, v: Array) -> Array:
        w = jnp.asarray(w)
        v = jnp.asarray(v)
        acc = self._epoch(
            lambda carry, batch: _accumulate_hessian_vector(
                carry, w, v, batch, objective=self.objective
            ),
            jnp.zeros_like(w),
        )
        if self.exchange is not None and self.exchange.num_ranks > 1:
            (hv_np,) = self._cross_rank_sum([acc])
            acc = jnp.asarray(hv_np, w.dtype)
        if self.l2_weight > 0.0:
            acc = acc + self.l2_weight * v
        return acc


def streaming_summarize(
    source: ChunkSource,
    *,
    prefetch: bool = True,
    retry_policy=None,
    chunk_timeout: float = DEFAULT_CHUNK_TIMEOUT,
) -> dict:
    """Weighted feature statistics from one streaming pass — the chunked
    equivalent of ``data.batch.summarize`` (reference
    stat/BasicStatisticalSummary.scala) for normalization contexts over
    data that never materializes in core. Accumulates f64 weighted sums
    (Σw, Σwx, Σwx², max|x|) host-side; zero-weight chunk padding
    contributes nothing, so the mean/variance/max_magnitude match the
    in-core summary to f64 round-off. Dense sources only."""
    if source.sparse:
        raise ValueError(
            "streaming_summarize covers dense sources; sparse shards keep "
            "their own summary path (data.sparse_batch.summarize_sparse)"
        )
    wsum = 0.0
    count = 0
    sum_wx = np.zeros((source.dim,), np.float64)
    sum_wxx = np.zeros((source.dim,), np.float64)
    max_mag = np.zeros((source.dim,), np.float64)
    with ChunkPrefetcher(
        source, prefetch=prefetch, retry_policy=retry_policy,
        chunk_timeout=chunk_timeout,
    ) as chunks:
        for batch in chunks:
            x = np.asarray(batch.features, dtype=np.float64)
            w = np.asarray(batch.weights, dtype=np.float64)
            wsum += float(w.sum())
            count += int((w != 0).sum())
            sum_wx += w @ x
            sum_wxx += w @ (x * x)
            max_mag = np.maximum(max_mag, np.abs(x).max(axis=0))
    if wsum <= 0.0:
        raise ValueError("streaming_summarize saw no positive-weight samples")
    mean = sum_wx / wsum
    # Σw(x-m)² = Σwx² - 2mΣwx + m²Σw, over wsum-1 like the in-core summary
    var = (sum_wxx - 2.0 * mean * sum_wx + mean * mean * wsum) / max(
        wsum - 1.0, 1.0
    )
    return {
        "count": count,
        "weight_sum": wsum,
        "mean": mean,
        "variance": np.maximum(var, 0.0),
        "max_magnitude": max_mag,
    }
