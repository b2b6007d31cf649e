"""Column-sharded (model-parallel) sparse GLM training for giant d.

The reference's scale claim — "hundreds of billions of coefficients"
(README.md:77) — rests on Spark hash-partitioning feature sub-spaces across
executors and aggregating per-partition gradients
(function/glm/ValueAndGradientAggregator.scala:133-154 is the per-partition
sparse axpy; DistributedObjectiveFunction drives treeAggregate over them).
The TPU-native equivalent: partition the COO entries BY COLUMN BLOCK over
the mesh "model" axis so each device owns a contiguous coefficient range
and exactly the entries that touch it. Per evaluation:

    local partial margins  (gather + row segment-sum over OWN entries)
    -> psum over "model"  (the treeAggregate)
    -> pointwise loss (replicated, O(n))
    -> OWN-column gradient block, scatter-free (sorted-run prefix sums)

Nothing of size d is ever replicated: coefficients, gradient, solver work
vectors, and the per-column run bounds all live sharded P("model"). At
d = 10⁹ the f32 coefficient vector alone is 4 GB — this layout is the only
way it trains on real chips, and it is exactly the scaling-book "shard the
big axis, psum the small one" recipe: the [n] margin psum is the sole
collective, riding ICI.

The ``shard_map`` program reduces each device's own column runs with
:func:`_sorted_run_sums`, and the LBFGS/OWLQN/TRON solvers run UNCHANGED
over the sharded vectors — their dots and axpys lower to per-shard ops +
psums under jit.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial

import flax.struct
import jax
import jax.numpy as jnp

from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.sparse_batch import (
    SparseShard,
    _hybrid_arrays,
    resolve_hybrid_policy,
)
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.telemetry.layout import record_block_head

Array = jax.Array

logger = logging.getLogger(__name__)


#: chunk width of the sorted-run reduction: bounds the magnitude any prefix
#: difference can cancel against (f32 error ~ eps·|within-chunk prefix|) and
#: keeps the [C, B] cumsum VPU-friendly
_RUN_CHUNK = 4096


def _sorted_run_sums(contrib: Array, bounds: Array) -> Array:
    """Sum each contiguous run of a (column-)sorted contribution vector.

    ``bounds`` is the [block+1] int32 run-boundary array of one column
    block (run j = ``contrib[bounds[j]:bounds[j+1]]``, precomputed on host
    by ``build_column_sharded_batch``). TPU-native replacement for
    ``segment_sum(..., num_segments=block)``: a two-level prefix sum over
    [C, B] chunks plus one gather per boundary —
        P(p) = chunk_prefix[p // B] + intra_chunk_cumsum[p]
        run_sum[j] = P(bounds[j+1]-1) - P(bounds[j]-1)
    Everything is cumsum/reshape/gather (bandwidth-bound, compiles in
    seconds at any dim); no scatter appears anywhere. Empty runs subtract
    identical gathers and come out exactly 0. Cross-chunk cancellation only
    touches runs that span a chunk edge, whose sums are large relative to
    the f32 error it introduces.
    """
    nnz = contrib.shape[0]
    pad = (-nnz) % _RUN_CHUNK
    if pad:
        contrib = jnp.pad(contrib, (0, pad))
    c2 = contrib.reshape(-1, _RUN_CHUNK)
    intra = jnp.cumsum(c2, axis=1)
    chunk_prefix = jnp.concatenate(
        [jnp.zeros((1,), intra.dtype), jnp.cumsum(intra[:, -1])]
    )
    intra_flat = intra.reshape(-1)
    end = bounds[1:] - 1
    start = bounds[:-1] - 1

    def parts(pos):
        safe = jnp.maximum(pos, 0)
        valid = pos >= 0
        i = jnp.where(valid, intra_flat[safe], 0.0)
        p = jnp.where(valid, chunk_prefix[safe // _RUN_CHUNK], 0.0)
        return i, p

    i_end, p_end = parts(end)
    i_start, p_start = parts(start)
    # grouped so same-chunk runs cancel the chunk prefix exactly
    return (i_end - i_start) + (p_end - p_start)


@flax.struct.dataclass
class ColumnShardedSparseBatch:
    """Flat-COO entries grouped into per-device column blocks.

    Entry arrays are [K, m]: K column blocks (sharded over "model"), each
    padded to the widest block's m entries (pad entries carry value 0).
    Column ids are LOCAL to the block (col - k·block). Two sorted layouts
    of the same entries: row-sorted (margins) and column-sorted with run
    bounds (gradient/Hv, scatter-free).

    dim is the true coefficient count; block·K >= dim — coefficients beyond
    dim are padding lanes pinned at 0 by zero data + L2.
    """

    values: Array       # [K, m] row-sorted within block
    local_cols: Array   # [K, m] int32
    row_ids: Array      # [K, m] int32
    vals_by_col: Array  # [K, m] column-sorted within block
    rows_by_col: Array  # [K, m] int32
    local_bounds: Array  # [K, block+1] int32 run boundaries
    labels: Array       # [n]
    offsets: Array      # [n]
    weights: Array      # [n]
    dim: int = flax.struct.field(pytree_node=False)
    block: int = flax.struct.field(pytree_node=False)
    #: optional hybrid dense-head view (data/sparse_batch.HybridPolicy
    #: builder rule applied globally): each block's slice of the hot
    #: column set rides a dense [n, h] sub-block with LOCAL column ids —
    #: the head is "model"-sharded by the same contiguous-range rule as
    #: the tail, so each device still owns exactly the entries that touch
    #: its coefficient range. Pad slots carry local col 0 over an all-zero
    #: column (inert in gather and scatter). The COO/column-sorted arrays
    #: then hold ONLY the cold residual tail. None = hybrid off (the
    #: existing layout, bitwise unchanged).
    hot_vals: Array | None = None        # [K, n, h]
    hot_local_cols: Array | None = None  # [K, h] int32

    @property
    def has_hot_head(self) -> bool:
        return self.hot_vals is not None

    @property
    def num_blocks(self) -> int:
        return int(self.values.shape[0])

    @property
    def num_samples(self) -> int:
        return int(self.labels.shape[0])

    @property
    def padded_dim(self) -> int:
        return self.num_blocks * self.block

    @property
    def dtype(self):
        return self.values.dtype


def _block_hot_head(
    hot_block: np.ndarray, hot_ids: np.ndarray, k: int, block: int
) -> tuple[np.ndarray, np.ndarray]:
    """Regroup a global [n, k_hot] hot head into per-block [K, n, h] dense
    sub-blocks with LOCAL column ids — the same contiguous-range rule the
    tail's column blocks follow. Pad slots (h padding, and the global
    head's own lane padding) carry local col 0 over an all-zero column."""
    n = hot_block.shape[0]
    kh = hot_ids.shape[0]
    blk = (hot_ids // block).astype(np.int64)
    local = (hot_ids - blk * block).astype(np.int64)
    counts = np.bincount(blk, minlength=k) if kh else np.zeros(k, np.int64)
    h = max(int(counts.max(initial=0)), 1)
    starts = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    # hot_ids are sorted, so each block's ids are contiguous in the input
    slot = np.arange(kh) - starts[blk]
    out_v = np.zeros((k, n, h), dtype=hot_block.dtype)
    out_c = np.zeros((k, h), dtype=np.int32)
    if kh:
        out_v[blk, :, slot] = hot_block.T
        out_c[blk, slot] = local
    return out_v, out_c


def build_column_sharded_batch(
    shard: SparseShard,
    labels,
    num_blocks: int,
    *,
    offsets=None,
    weights=None,
    hybrid=None,
) -> ColumnShardedSparseBatch:
    """Group a SparseShard's entries into ``num_blocks`` column blocks.

    Host-side analogue of the reference's feature-space hash partitioner —
    except blocks are CONTIGUOUS ranges so each device's run bounds stay a
    dense [block+1] slice and locality survives (hash partitioning would
    randomize columns across devices and kill the sorted-run reduction).

    hybrid: None (default) inherits the shard's attached ``hybrid_policy``;
    False forces it off; a HybridPolicy/True enables the dense hot head —
    selected GLOBALLY by the same nnz ranking as the single-chip builder,
    then "model"-sharded per block alongside the cold tail.
    """
    rows, cols, vals = shard.coalesced()
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    n, dim = shard.shape
    k = int(num_blocks)
    block = -(-dim // k)

    policy = (
        shard.hybrid_policy if hybrid is None else resolve_hybrid_policy(hybrid)
    )
    hot_extra = {}
    if policy is not None:
        # pad=False: lane padding would land every duplicate pad id in the
        # last hot column's block and inflate the per-block width; blocks
        # re-pad to their own widest count below
        hot_block, hot_ids, rows, cols, vals = _hybrid_arrays(
            rows, cols, vals, n, dim, policy, pad=False
        )
        hv3, hc2 = _block_hot_head(hot_block, hot_ids, k, block)
        # every block pads to the widest block's hot count: hot ids
        # clustered into few contiguous blocks (e.g. insertion-ordered
        # index maps) blow the [K, n, h] head up toward K× the global
        # head — surface it instead of silently multiplying HBM/compute
        record_block_head(
            policy.label, width=hv3.shape[2], num_blocks=k,
            k_hot_padded=hot_ids.shape[0],
        )
        if hot_ids.shape[0] and hv3.shape[2] * k > 2 * hot_ids.shape[0]:
            logger.warning(
                "hybrid hot head is clustered across column blocks: "
                "per-block width %d x %d blocks vs %d global hot columns "
                "(%.1fx replicated zeros); a hashed/shuffled feature id "
                "assignment spreads the head",
                hv3.shape[2], k, hot_ids.shape[0],
                hv3.shape[2] * k / hot_ids.shape[0],
            )
        hot_extra = dict(
            hot_vals=jnp.asarray(hv3),
            hot_local_cols=jnp.asarray(hc2, dtype=jnp.int32),
        )

    blk = (cols // block).astype(np.int64)
    local = (cols - blk * block).astype(np.int64)
    counts = np.bincount(blk, minlength=k)
    m = max(int(counts.max(initial=0)), 1)

    def grouped(order_keys) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """[K, m] (vals, other, localcol) laid out by block in the given
        within-block order; pads carry value 0 / index 0."""
        order = np.lexsort(order_keys + (blk,))
        b, r, c, v = blk[order], rows[order], local[order], vals[order]
        starts = np.zeros(k + 1, dtype=np.int64)
        np.cumsum(counts, out=starts[1:])
        pos = np.arange(len(b)) - starts[b]
        out_v = np.zeros((k, m), dtype=vals.dtype)
        # pad slots: value 0 with the LAST row id (keeps per-block row ids
        # sorted for the margins' segment-sum promise) and local col 0
        out_r = np.full((k, m), max(n - 1, 0), dtype=np.int32)
        out_c = np.zeros((k, m), dtype=np.int32)
        out_v[b, pos] = v
        out_r[b, pos] = r
        out_c[b, pos] = c
        return out_v, out_r, out_c

    # row-sorted within block (margins' per-row segment sum wants sorted rows)
    v_row, r_row, c_row = grouped((local, rows))
    # column-sorted within block (gradient's run reduction)
    v_col, r_col, c_col = grouped((rows, local))
    # run bounds per block over local columns, from the TRUE entries only
    # (pad slots carry local col 0 and would corrupt counts): one combined
    # bincount over (block, local) keys instead of a per-block scan
    col_counts = np.bincount(
        blk * block + local, minlength=k * block
    ).reshape(k, block)
    bounds = np.zeros((k, block + 1), dtype=np.int64)
    np.cumsum(col_counts, axis=1, out=bounds[:, 1:])
    dtype = vals.dtype
    labels = np.asarray(labels, dtype=dtype)
    offsets = (
        np.zeros(n, dtype) if offsets is None else np.asarray(offsets, dtype)
    )
    weights = (
        np.ones(n, dtype) if weights is None else np.asarray(weights, dtype)
    )
    return ColumnShardedSparseBatch(
        values=jnp.asarray(v_row),
        local_cols=jnp.asarray(c_row),
        row_ids=jnp.asarray(r_row),
        vals_by_col=jnp.asarray(v_col),
        rows_by_col=jnp.asarray(r_col),
        local_bounds=jnp.asarray(bounds, dtype=jnp.int32),
        labels=jnp.asarray(labels),
        offsets=jnp.asarray(offsets),
        weights=jnp.asarray(weights),
        dim=int(dim),
        block=int(block),
        **hot_extra,
    )


def shard_column_batch(batch: ColumnShardedSparseBatch, mesh: Mesh,
                       put_fn=None) -> ColumnShardedSparseBatch:
    """Place the block axis over "model", per-sample vectors replicated.

    (A 2-D data×model layout would additionally shard [n]; the giant-d
    regime is model-bound — n·4 bytes is small next to d·4 — so replicated
    sample vectors keep the psum a plain ICI all-reduce.)"""
    put = put_fn if put_fn is not None else jax.device_put
    mdl = NamedSharding(mesh, P("model", None))
    rep = NamedSharding(mesh, P())
    hot_extra = {}
    if batch.has_hot_head:
        # the hot head shards over "model" with the tail (each device owns
        # its blocks' hot columns); the sample axis stays unsharded like
        # every other per-sample dimension here
        hot_extra = dict(
            hot_vals=put(batch.hot_vals,
                         NamedSharding(mesh, P("model", None, None))),
            hot_local_cols=put(batch.hot_local_cols, mdl),
        )
    return batch.replace(
        **hot_extra,
        values=put(batch.values, mdl),
        local_cols=put(batch.local_cols, mdl),
        row_ids=put(batch.row_ids, mdl),
        vals_by_col=put(batch.vals_by_col, mdl),
        rows_by_col=put(batch.rows_by_col, mdl),
        local_bounds=put(batch.local_bounds, mdl),
        labels=put(batch.labels, rep),
        offsets=put(batch.offsets, rep),
        weights=put(batch.weights, rep),
    )


class ColumnShardedGLMObjective:
    """BoundObjective-compatible GLM objective over a column-sharded batch.

    value / value_and_grad / hessian_vector run as one ``shard_map`` over
    the mesh "model" axis; coefficients and gradients are [K·block] arrays
    sharded P("model"). Feed ``bind(batch)`` to ``optim.optimizer.solve``
    like any other objective — the solvers' vector algebra stays sharded.
    """

    def __init__(self, loss: PointwiseLoss, mesh: Mesh,
                 l2_weight: float = 0.0):
        self.loss = loss
        self.mesh = mesh
        self.l2_weight = float(l2_weight)

    def _key(self):
        return (type(self.loss), self.l2_weight, id(self.mesh))

    def __eq__(self, other):
        return (
            isinstance(other, ColumnShardedGLMObjective)
            and self._key() == other._key()
        )

    def __hash__(self):
        return hash(self._key())

    def _shard_spec(self, hot: bool = False):
        e = P("model", None)
        hot_specs = (P("model", None, None), e) if hot else ()
        return dict(
            mesh=self.mesh,
            in_specs=(P("model"),) + hot_specs
            + (e, e, e, e, e, e, P(), P(), P()),
            check_vma=False,
        )

    def _check_blocks(self, batch: ColumnShardedSparseBatch) -> None:
        """The shard_map bodies consume exactly ONE block per device
        (``values[0]``); any other blocks-per-device ratio would silently
        drop entries — fail loudly instead."""
        model = int(self.mesh.shape["model"])
        if batch.num_blocks != model:
            raise ValueError(
                f"batch has {batch.num_blocks} column blocks but the mesh "
                f"'model' axis is {model}; build the batch with "
                f"num_blocks={model}"
            )

    # -- margins (the psum'd treeAggregate) ---------------------------------

    @staticmethod
    def _local_margins(w_l, values, local_cols, row_ids, n: int,
                       hot_vals=None, hot_cols=None) -> Array:
        contrib = values * w_l[local_cols]
        partial = jax.ops.segment_sum(
            contrib, row_ids, num_segments=n, indices_are_sorted=True
        )
        if hot_vals is not None:
            # dense hot head: one [n, h] matvec against this block's own
            # coefficient slice (pad columns are zero — inert)
            partial = partial + hot_vals @ w_l[hot_cols]
        return jax.lax.psum(partial, "model")

    @staticmethod
    def _unpack(hot: bool, args):
        """(hot_vals, hot_cols, tail-and-sample args) from a shard_map
        argument list that carries the hot head only when present."""
        if hot:
            return args[0], args[1], args[2:]
        return None, None, args

    def value(self, w: Array, batch: ColumnShardedSparseBatch) -> Array:
        self._check_blocks(batch)
        n = batch.num_samples
        hot = batch.has_hot_head

        def f(w_l, *args):
            hv, hc, (values, local_cols, row_ids, vbc, rbc, bounds,
                     labels, offsets, weights) = self._unpack(hot, args)
            margins = self._local_margins(
                w_l[0], values[0], local_cols[0], row_ids[0], n,
                hot_vals=None if hv is None else hv[0],
                hot_cols=None if hc is None else hc[0],
            ) + offsets
            total = jnp.sum(weights * self.loss.loss(margins, labels))
            if self.l2_weight > 0.0:
                total = total + 0.5 * self.l2_weight * jax.lax.psum(
                    jnp.vdot(w_l, w_l), "model"
                )
            return total

        return shard_map(
            f, out_specs=P(), **self._shard_spec(hot)
        )(w.reshape(batch.num_blocks, batch.block), *self._batch_args(batch))

    def value_and_gradient(
        self, w: Array, batch: ColumnShardedSparseBatch
    ) -> tuple[Array, Array]:
        self._check_blocks(batch)
        n = batch.num_samples
        hot = batch.has_hot_head

        def f(w_l, *args):
            hv, hc, (values, local_cols, row_ids, vbc, rbc, bounds,
                     labels, offsets, weights) = self._unpack(hot, args)
            margins = self._local_margins(
                w_l[0], values[0], local_cols[0], row_ids[0], n,
                hot_vals=None if hv is None else hv[0],
                hot_cols=None if hc is None else hc[0],
            ) + offsets
            losses, dz = self.loss.loss_and_dz(margins, labels)
            total = jnp.sum(weights * losses)
            dzw = weights * dz
            contrib = dzw[rbc[0]] * vbc[0]
            g_l = _sorted_run_sums(contrib, bounds[0])
            if hv is not None:
                # head transpose: ONE [n]·[n, h] matvec + an h-sized
                # scatter into this block's gradient slice
                g_l = g_l.at[hc[0]].add(dzw @ hv[0])
            if self.l2_weight > 0.0:
                total = total + 0.5 * self.l2_weight * jax.lax.psum(
                    jnp.vdot(w_l, w_l), "model"
                )
                g_l = g_l + self.l2_weight * w_l[0]
            return total, g_l[None, :]

        value, grad = shard_map(
            f, out_specs=(P(), P("model", None)), **self._shard_spec(hot)
        )(w.reshape(batch.num_blocks, batch.block), *self._batch_args(batch))
        return value, grad.reshape(-1)

    def hessian_vector(
        self, w: Array, v: Array, batch: ColumnShardedSparseBatch
    ) -> Array:
        """H v = Xᵀ diag(w_i l''_i) X v (+ λv): forward psum'd Jv, then the
        same local sorted-run transpose — TRON's CG ladder at giant d.
        With a hot head, both directions take the dense-head/sparse-tail
        split (the hybrid CG step at d=10⁸)."""
        self._check_blocks(batch)
        n = batch.num_samples
        hot = batch.has_hot_head

        def f(w_l, v_l, *args):
            hv, hc, (values, local_cols, row_ids, vbc, rbc, bounds,
                     labels, offsets, weights) = self._unpack(hot, args)
            hot_kw = dict(
                hot_vals=None if hv is None else hv[0],
                hot_cols=None if hc is None else hc[0],
            )
            margins = self._local_margins(
                w_l[0], values[0], local_cols[0], row_ids[0], n, **hot_kw
            ) + offsets
            jv = self._local_margins(
                v_l[0], values[0], local_cols[0], row_ids[0], n, **hot_kw
            )
            d2w = self.loss.d2z(margins, labels) * weights
            t = d2w * jv
            contrib = t[rbc[0]] * vbc[0]
            hv_l = _sorted_run_sums(contrib, bounds[0])
            if hv is not None:
                hv_l = hv_l.at[hc[0]].add(t @ hv[0])
            if self.l2_weight > 0.0:
                hv_l = hv_l + self.l2_weight * v_l[0]
            return hv_l[None, :]

        spec = self._shard_spec(hot)
        spec["in_specs"] = (P("model"),) + spec["in_specs"]
        k, b = batch.num_blocks, batch.block
        hv = shard_map(f, out_specs=P("model", None), **spec)(
            w.reshape(k, b), v.reshape(k, b), *self._batch_args(batch)
        )
        return hv.reshape(-1)

    @staticmethod
    def _batch_args(batch: ColumnShardedSparseBatch):
        hot = (
            (batch.hot_vals, batch.hot_local_cols)
            if batch.has_hot_head else ()
        )
        return hot + (
            batch.values, batch.local_cols, batch.row_ids,
            batch.vals_by_col, batch.rows_by_col, batch.local_bounds,
            batch.labels, batch.offsets, batch.weights,
        )

    def bind(self, batch: ColumnShardedSparseBatch):
        from photon_ml_tpu.ops.objective import BoundObjective

        return BoundObjective(self, batch)

    # the duck-typed BoundObjective calls value_and_gradient via this alias
    def gradient(self, w: Array, batch) -> Array:
        return self.value_and_gradient(w, batch)[1]


def init_column_sharded_coefficients(
    batch: ColumnShardedSparseBatch, mesh: Mesh, dtype=None
) -> Array:
    """Zero [K·block] coefficient vector laid out P("model") — the solver's
    w0 (and with it every solver work vector) starts sharded."""
    dtype = dtype or batch.dtype
    return jax.device_put(
        jnp.zeros((batch.padded_dim,), dtype=dtype),
        NamedSharding(mesh, P("model")),
    )
