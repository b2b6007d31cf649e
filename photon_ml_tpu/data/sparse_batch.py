"""Sparse batched training data for giant feature spaces.

Reference parity: the reference assembles *sparse* feature vectors from Avro
name-term bags (photon-client data/avro/AvroDataReader.scala:165-200) and
claims fixed-effect models of "hundreds of billions of coefficients"
(README.md:77). A dense [n, d] block caps d at ~10⁴-10⁵ before HBM dies;
this module keeps the reader's COO triples as device-resident flat arrays so
only O(nnz) memory scales with data and only O(d) with the model.

TPU-native layout: one flat (values, col_indices, row_ids) triple sorted by
row. The margin is a gather + segment-sum (MXU-free but bandwidth-friendly:
one pass over nnz); the gradient falls out of autodiff as the transpose
scatter-add — exactly the sparse axpy the reference hand-wrote in
ValueAndGradientAggregator.scala:133-154, generated by XLA instead.
Padding entries carry value 0.0 (and col/row 0), contributing nothing to
either direction.

Two faster views of the same entries, agreeing with the flat triple to
tight tolerance (tests/test_sparse_hybrid.py): ELL (padded rows in width
tiers that follow the rows' counts, the single-chip default; one [n, L]
block at an explicit or agreed width), and the opt-in r6 hybrid
dense-head/sparse-tail split (:class:`HybridPolicy`) — the nnz-hottest
columns of a power-law feature space train as one dense MXU block with zero
per-entry index ops, the cold residual rides the ELL tail. Which of them a
batch gets is decided in one place, :func:`_build_batch`.
"""

from __future__ import annotations

import dataclasses

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


#: gather/scatter indices ride int32 on device; one coefficient axis can
#: carry up to 2^31-1 entries (≈2.1B — covers the reference's 1B-coefficient
#: claim single-axis; beyond that, shard the feature axis first)
MAX_INDEX = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class HybridPolicy:
    """Builder policy for the hybrid dense-head / sparse-tail view.

    Photon-ML's name-term feature bags are power-law distributed: a small
    set of hot columns carries most nonzeros. The hybrid view ranks columns
    by nnz and moves the top ``k_hot`` into a dense [n, k_hot] block
    (column-remapped, zero-filled) whose margin/gradient are ONE MXU matmul
    plus a k_hot-sized coefficient gather/scatter amortized over n rows;
    the residual ELL tail keeps only the cold nonzeros, so the per-index
    ops that bound all sparse work (BASELINE.md: ~7-12 ns/element) shrink
    by the hot-coverage fraction. Strictly opt-in — no policy means the
    existing ELL/flat layouts, bitwise unchanged.

    Exactly like the ELL auto width, head sizing is host-side and
    data-driven: ``hot_cols`` fixes the head budget; else ``coverage``
    picks the smallest head covering that fraction of nonzeros. The head is
    padded up to ``pad_multiple`` (lane-friendly; pad columns are zero with
    the last hot id, contributing nothing in either direction).
    """

    hot_cols: int | None = None
    coverage: float | None = None
    #: pad k_hot up to this multiple (MXU/VPU lane-friendly widths)
    pad_multiple: int = 128
    #: safety cap: the dense head is n·k_hot floats of HBM
    max_hot_cols: int = 1 << 14
    #: telemetry namespace: gauges land under ``layout/<label>/*``
    label: str = "sparse"
    #: PRE-RESOLVED hot head (sorted, unique global column ids): when set,
    #: the builder uses exactly these columns instead of ranking locally.
    #: This is how partitioned ingestion keeps every rank's head identical —
    #: io/partitioned_reader.py sums per-rank nnz histograms over the
    #: metadata exchange and bakes the GLOBAL ranking in here, so a rank's
    #: local (1/P) column statistics never elect a divergent head. The
    #: sizing rules (hot_cols/coverage) were already applied at resolution.
    hot_ids: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.hot_cols is not None and self.hot_cols <= 0:
            raise ValueError(f"hot_cols must be positive, got {self.hot_cols}")
        if self.coverage is not None and not (0.0 < self.coverage <= 1.0):
            raise ValueError(
                f"coverage must be in (0, 1], got {self.coverage}"
            )
        if self.hot_cols is not None and self.coverage is not None:
            # rejected up front, not silently wrong: an explicit budget
            # never consults coverage, so accepting both would quietly
            # drop one of the user's constraints
            raise ValueError(
                "hot_cols and coverage are mutually exclusive head-sizing "
                "rules; set one"
            )
        if self.pad_multiple <= 0:
            raise ValueError("pad_multiple must be positive")
        if self.hot_ids is not None:
            ids = np.asarray(self.hot_ids, dtype=np.int64)
            if len(ids) == 0:
                raise ValueError("hot_ids must name at least one column")
            if (np.diff(ids) <= 0).any() or ids[0] < 0:
                raise ValueError(
                    "hot_ids must be sorted, unique, non-negative column ids"
                )

    @property
    def effective_coverage(self) -> float:
        """Coverage target when neither knob is set explicitly."""
        return 0.5 if self.coverage is None else self.coverage


def resolve_hybrid_policy(hybrid) -> "HybridPolicy | None":
    """Normalize the ``hybrid`` builder argument: None/False off, True the
    default policy, a HybridPolicy as given."""
    if hybrid is None or hybrid is False:
        return None
    if hybrid is True:
        return HybridPolicy()
    if isinstance(hybrid, HybridPolicy):
        return hybrid
    raise TypeError(f"hybrid must be a HybridPolicy or bool, got {hybrid!r}")


def _check_index_ranges(rows, cols, n, dim) -> None:
    if dim > MAX_INDEX or n > MAX_INDEX:
        raise ValueError(
            f"dim={dim}/n={n} exceeds the int32 device-index range "
            f"({MAX_INDEX}); shard the axis before building the batch"
        )
    if rows.size:
        if rows.min(initial=0) < 0 or cols.min(initial=0) < 0:
            raise ValueError("negative row/col index")
        if rows.max(initial=0) >= n:
            raise ValueError("row index out of range")
        if cols.max(initial=0) >= dim:
            raise ValueError(f"column index >= dim={dim}")


def _strictly_row_major(rows: np.ndarray, cols: np.ndarray) -> bool:
    """True when (row, col) strictly increases along the entries: sorted
    row-major and every pair once. Three passes, no sort."""
    same_row = rows[1:] == rows[:-1]
    if not ((rows[1:] > rows[:-1]) | same_row).all():
        return False
    return bool((cols[1:] > cols[:-1])[same_row].all())


def coalesce_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray):
    """Sum duplicate (row, col) entries (scipy sum_duplicates semantics).

    Mandatory before any non-linear use of the values: the dense equivalent
    accumulates duplicates into one cell *before* squaring (Hessian
    diagonals), so x² must be (Σv)², not Σv². Output is row-major sorted —
    exactly what the segment sums want.
    """
    if len(vals) == 0:
        return rows, cols, vals
    if _strictly_row_major(rows, cols):
        # already sorted with no pair twice (a reader or generator that emits
        # row-major unique entries): nothing to sum, and the lexsort it would
        # take to find that out is 20 s at 4e7 entries
        return rows, cols, vals
    order = np.lexsort((cols, rows))
    r, c, v = rows[order], cols[order], vals[order]
    first = np.empty(len(r), dtype=bool)
    first[0] = True
    first[1:] = (r[1:] != r[:-1]) | (c[1:] != c[:-1])
    idx = np.flatnonzero(first)
    return r[idx], c[idx], np.add.reduceat(v, idx)


@dataclasses.dataclass
class SparseShard:
    """Host-side sparse feature shard: COO triples + shape.

    Drop-in for the dense [n, d] shard value in GameDataset.feature_shards
    when d is too large to densify (the reader keeps the Avro name-term
    bags' sparsity, AvroDataReader.scala:165-200). Exposes .shape/.dtype so
    shape-probing callers need no isinstance checks; the device-resident
    flat arrays are built once and cached.
    """

    rows: np.ndarray  # [nnz] sample index
    cols: np.ndarray  # [nnz] feature index
    vals: np.ndarray  # [nnz]
    num_samples: int
    feature_dim: int
    #: builder policy for the hybrid dense-head/sparse-tail view, attached
    #: by the reader from the CLI shard grammar (``hybrid=true`` etc.);
    #: batch builders consuming this shard inherit it unless overridden.
    #: None (default) keeps the existing ELL/flat layouts bitwise unchanged.
    hybrid_policy: "HybridPolicy | None" = None
    #: globally-agreed ELL row width (partitioned ingestion): when set,
    #: ``from_shard``'s ell="auto" uses exactly this width instead of the
    #: local auto rule. io/partitioned_reader.py computes it by applying
    #: the SAME auto rule (``_ell_auto_width``) to the summed per-rank
    #: row-count histograms — i.e. exactly the width the full read would
    #: have picked — so every rank's local batch block shares one
    #: jit/assembly signature AND the composed layout stays bitwise
    #: identical to the unpartitioned one. None (default) keeps the local
    #: auto width, bitwise unchanged.
    ell_width: int | None = None
    #: globally-agreed flat-overflow block length (partitioned ingestion):
    #: when set, ``from_shard`` pads the ELL overflow tail to exactly this
    #: many entries (value 0 / col 0 / LAST real row id — ``pad_nnz``'s
    #: inert-entry contract), so every rank's flat block has one agreed
    #: length and parallel/distributed._assemble_sparse_fe can assemble
    #: the nnz axis across ranks. None (default) leaves the tail at its
    #: natural length, bitwise unchanged.
    flat_block_nnz: int | None = None
    _device: "SparseShardDevice | None" = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _coalesced: "tuple | None" = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _hybrid_cache: "tuple | None" = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self):
        _check_index_ranges(
            np.asarray(self.rows), np.asarray(self.cols),
            self.num_samples, self.feature_dim,
        )
        # The shard lazily caches coalesced triples and device arrays, so
        # mutating the COO arrays after first use would silently serve stale
        # data — freeze them. (Callers needing a modified shard build a new
        # one; dataclasses.replace with _device=None/_coalesced=None works.)
        for name in ("rows", "cols", "vals"):
            arr = getattr(self, name)
            if isinstance(arr, np.ndarray):
                arr.flags.writeable = False

    def coalesced(self) -> tuple:
        """(rows, cols, vals) with duplicates summed — computed once (an
        O(nnz log nnz) lexsort worth caching at giant nnz) and shared by
        device() and summarize()."""
        if self._coalesced is None:
            self._coalesced = coalesce_coo(
                np.asarray(self.rows), np.asarray(self.cols),
                np.asarray(self.vals),
            )
        return self._coalesced

    @property
    def shape(self) -> tuple[int, int]:
        return (self.num_samples, self.feature_dim)

    @property
    def dtype(self):
        return jnp.asarray(self.vals[:0]).dtype  # device dtype for this host dtype

    @property
    def nnz(self) -> int:
        return len(self.vals)

    def device(self) -> "SparseShardDevice":
        if self._device is None:
            rows, cols, vals = self.coalesced()
            self._device = SparseShardDevice(
                values=jnp.asarray(vals),
                col_indices=jnp.asarray(cols, dtype=jnp.int32),
                row_ids=jnp.asarray(rows, dtype=jnp.int32),
                num_samples=int(self.num_samples),
                dim=int(self.feature_dim),
            )
        return self._device

    def hybrid_split(self, policy: "HybridPolicy") -> tuple:
        """The hybrid head/tail arrays for ``policy`` — computed once per
        (shard, policy) and cached like ``coalesced()``: GAME coordinate
        descent rebuilds the FE batch every sweep, and the split (an
        O(nnz log nnz) column ranking plus a dense [n, k_hot] host fill)
        depends only on the entries. The cached tail triple is read-only
        shared state; batch builders copy, never mutate."""
        if self._hybrid_cache is None or self._hybrid_cache[0] != policy:
            rows, cols, vals = self.coalesced()
            self._hybrid_cache = (policy, _hybrid_arrays(
                np.asarray(rows), np.asarray(cols), np.asarray(vals),
                self.num_samples, self.feature_dim, policy,
            ))
        return self._hybrid_cache[1]

    def one_ell_width(self) -> int:
        """The ONE width of this shard's [n, L] ELL block where the layout
        has to be a single block (a mesh shards it ``P("data", None)``):
        the agreed ``ell_width`` where the reader exchanged one, else the
        one-width auto rule on the counts of the rows' entries (of the cold
        tail's, under the attached hybrid policy)."""
        if self.ell_width is not None:
            return int(self.ell_width)
        if self.hybrid_policy is not None:
            rows = self.hybrid_split(self.hybrid_policy)[2]
        else:
            rows = np.asarray(self.coalesced()[0])
        n = self.num_samples
        counts = np.bincount(rows, minlength=n) if len(rows) else np.zeros(n, np.int64)
        return _ell_auto_width(counts, n, len(rows))

    def summarize(self, weights: np.ndarray | None = None) -> dict:
        rows, cols, vals = self.coalesced()
        return summarize_sparse(
            rows, cols, vals,
            n=self.num_samples, dim=self.feature_dim, weights=weights,
            assume_coalesced=True,
        )


@flax.struct.dataclass
class SparseShardDevice:
    """Device-resident flat-COO of one feature shard (row-sorted)."""

    values: Array
    col_indices: Array
    row_ids: Array
    num_samples: int = flax.struct.field(pytree_node=False)
    dim: int = flax.struct.field(pytree_node=False)

    def matvec(self, coefficients: Array) -> Array:
        """X @ w: gather + segment-sum, the sparse scoring path."""
        contrib = self.values * coefficients[self.col_indices]
        return jax.ops.segment_sum(
            contrib, self.row_ids,
            num_segments=self.num_samples, indices_are_sorted=True,
        )


@flax.struct.dataclass
class EllTier:
    """One further width tier of the ELL view: entries ``W_k + 1 .. W_{k+1}``
    of ONLY the rows that hold more than ``W_k`` (``W_k`` = the widths of the
    tiers before it, ``ell_vals`` first). Pad slots carry value 0 / col 0.
    The rows lie along the MINOR axis (``[width, n_k]``): a TPU keeps a
    narrow ``[n_k, 4]`` block with its rows along the lanes anyway, and
    flattening that row by row for the gather and the scatter-add compiled
    into 245 MB of code and 155 s where this takes 27 (PERF.md 6, PR 44);
    this way round a tier flattens as it lies."""

    vals: Array     # [W_{k+1} - W_k, n_k]
    cols: Array     # [W_{k+1} - W_k, n_k] int32
    row_ids: Array  # [n_k] int32, ascending and unique (a subset of the rows)

    @property
    def width(self) -> int:
        return self.vals.shape[0]


@flax.struct.dataclass
class SparseLabeledPointBatch:
    """A flat-COO block of labeled samples for a d too large to densify.

    values:      [nnz] float — nonzero feature values, sorted by row
    col_indices: [nnz] int32 — feature index of each value (< dim)
    row_ids:     [nnz] int32 — sample index of each value (< num_samples)
    labels:      [n] float
    offsets:     [n] float — prior/residual scores (CD residual mechanism)
    weights:     [n] float — sample weights; 0 marks padded samples
    dim:         static int — feature-space dimension (the model size)

    Padded *entries* (to a fixed nnz for jit) carry value 0.0; padded
    *samples* carry weight 0.0 — same masking contract as the dense
    LabeledPointBatch.
    """

    values: Array
    col_indices: Array
    row_ids: Array
    labels: Array
    offsets: Array
    weights: Array
    dim: int = flax.struct.field(pytree_node=False)
    #: optional ELL (padded-row) view, tier 0: [n, W_1] values / column ids,
    #: every row's first W_1 entries, pad slots carrying value 0 / col 0.
    #: Margins become one dense row-sum (no per-row segment-sum) and the dz
    #: term broadcasts (no per-entry row gather). The gradient's transpose
    #: scatter falls out of autodiff unchanged.
    ell_vals: Array | None = None
    ell_cols: Array | None = None
    #: the ELL view's further width tiers (:class:`EllTier`), narrowest rows
    #: first: the auto rule (``_ell_tier_widths``) picks the widths from the
    #: histogram of the rows' counts, so that the slots follow the counts
    #: where one [n, L] block pads every row to L. Empty at an explicit or
    #: agreed width (``ell=<int>``, ``SparseShard.ell_width``) and where all
    #: rows hold the same count. What lies beyond the last width spills into
    #: the flat COO arrays (which then hold ONLY that overflow).
    ell_tiers: tuple[EllTier, ...] = ()
    #: optional hybrid dense-head view (HybridPolicy): the hottest columns'
    #: entries live in a dense [n, k_hot] block (zero-filled, columns
    #: remapped to head slots) whose margin is X_hot @ w[hot_col_ids] — one
    #: MXU matmul + a k_hot-sized gather — and whose gradient is the
    #: transpose matmul + a k_hot-sized scatter. When present, the ELL/flat
    #: arrays hold ONLY the cold residual tail. Head pad columns are zero
    #: with the last hot id (inert both directions). Strictly opt-in.
    hot_vals: Array | None = None      # [n, k_hot]
    hot_col_ids: Array | None = None   # [k_hot] int32 global column ids

    @property
    def has_ell_view(self) -> bool:
        return self.ell_vals is not None

    @property
    def has_hybrid_view(self) -> bool:
        return self.hot_vals is not None

    @property
    def num_samples(self) -> int:
        return self.labels.shape[0]

    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def solve_dtype(self):
        """Coefficients/optimizer state dtype: bf16 entry values still
        solve in f32 (same contract as LabeledPointBatch.solve_dtype)."""
        import jax.numpy as _jnp

        d = self.values.dtype
        return _jnp.float32 if d == _jnp.bfloat16 else d

    def with_offsets(self, offsets: Array) -> "SparseLabeledPointBatch":
        return self.replace(offsets=offsets)

    def pad_nnz(self, target: int) -> "SparseLabeledPointBatch":
        """Pad the flat triple to ``target`` with inert entries — value 0,
        column 0 and the LAST row id, so the row segment-sum's
        ``indices_are_sorted=True`` promise survives. The ONE owner of the
        padding contract for device-resident batches (``_pad_flat`` is the
        builder's, on the host)."""
        pad = target - self.nnz
        if pad < 0:
            raise ValueError(f"cannot pad {self.nnz} entries down to {target}")
        if pad == 0:
            return self
        last_row = (
            self.row_ids[-1:] if self.nnz
            else jnp.zeros((1,), self.row_ids.dtype)
        )
        return self.replace(
            values=jnp.pad(self.values, (0, pad)),
            col_indices=jnp.pad(self.col_indices, (0, pad)),
            row_ids=jnp.concatenate(
                [self.row_ids, jnp.broadcast_to(last_row, (pad,))]
            ),
        )

    def add_scores_to_offsets(self, scores: Array) -> "SparseLabeledPointBatch":
        """Residual update used by coordinate descent (DataSet.addScoresToOffsets)."""
        return self.replace(offsets=self.offsets + scores)

    @classmethod
    def from_shard(
        cls,
        shard: "SparseShard",
        labels: Array,
        offsets: Array,
        weights: Array,
        *,
        ell: bool | int | str = "auto",
        hybrid: "HybridPolicy | bool | None" = None,
    ) -> "SparseLabeledPointBatch":
        """Bind a shard's entries to per-sample labels/offsets/weights.

        ell: "auto" (default) builds the padded-row ELL view in width tiers
        read off the rows' counts (one [n, L] block at ``shard.ell_width``
        where the shard carries an agreed width); an int fixes ONE width;
        False keeps pure flat COO. The overflow beyond the view pads to the
        shard's agreed ``flat_block_nnz`` where it carries one.
        hybrid: None (default) inherits the shard's attached
        ``hybrid_policy`` (the CLI grammar's ``hybrid=true``); False forces
        the hybrid view off; a HybridPolicy/True enables it — the hot head
        becomes a dense block and the ELL/flat tail keeps the cold
        residual only.
        """
        policy = (
            shard.hybrid_policy if hybrid is None
            else resolve_hybrid_policy(hybrid)
        )
        # no shard.device() here: the batch is built from fresh host-side
        # arrays, and caching a flat triple the layout does not use would pin
        # ~12 bytes/nnz of dead HBM on exactly the giant-d path
        return _build_batch(
            *shard.coalesced(), shard.num_samples, shard.feature_dim,
            labels, offsets, weights,
            policy=policy,
            split=None if policy is None else shard.hybrid_split(policy),
            ell=ell, agreed_width=shard.ell_width,
            # the agreed length is the ELL overflow's; a flat-only triple
            # holds every entry and stays at its natural length
            pad_to=shard.flat_block_nnz if ell else None, pad_agreed=True,
        )

    @classmethod
    def from_coo(
        cls,
        rows,
        cols,
        vals,
        labels,
        *,
        dim: int,
        offsets=None,
        weights=None,
        dtype=np.float32,
        pad_nnz_to: int | None = None,
        ell: bool | int | str = "auto",
        hybrid: "HybridPolicy | bool | None" = None,
    ) -> "SparseLabeledPointBatch":
        """Build from host COO triples (duplicates coalesce at build time,
        matching the dense reader's np.add.at accumulation rule).

        pad_nnz_to: round the entry count up (e.g. to a power of two) so
        repeated reads of growing data reuse compiled programs.
        ell: "auto" (default) builds the padded-row ELL view (fast TPU
        layout) in width tiers read off the rows' counts; an int fixes ONE
        [n, L] block; overflow entries stay in the flat tail, which
        pad_nnz_to then applies to; False keeps pure flat COO.
        hybrid: opt-in hybrid dense-head view (HybridPolicy/True); the
        hot head becomes a dense [n, k_hot] block and ELL/flat keep only
        the cold tail. Off (None/False) is bitwise-identical to before.
        """
        policy = resolve_hybrid_policy(hybrid)
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals, dtype=dtype)
        labels = np.asarray(labels, dtype=dtype)
        n = labels.shape[0]
        _check_index_ranges(rows, cols, n, dim)
        # coalesced output is row-major sorted (segment_sum wants sorted rows)
        rows, cols, vals = coalesce_coo(rows, cols, vals)
        offsets = (
            np.zeros((n,), dtype=dtype) if offsets is None
            else np.asarray(offsets, dtype=dtype)
        )
        weights = (
            np.ones((n,), dtype=dtype) if weights is None
            else np.asarray(weights, dtype=dtype)
        )
        return _build_batch(
            rows, cols, vals, n, dim, labels, offsets, weights,
            policy=policy, ell=ell, pad_to=pad_nnz_to,
        )


def _build_batch(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n: int, dim: int,
    labels, offsets, weights, *,
    policy: "HybridPolicy | None",
    split: tuple | None = None,
    ell: bool | int | str,
    agreed_width: int | None = None,
    pad_to: int | None = None,
    pad_agreed: bool = False,
) -> SparseLabeledPointBatch:
    """The ONE place a batch's layout is decided: ``from_coo`` and
    ``from_shard`` both end here, with row-major coalesced host triples.

    In order: the hybrid head split under ``policy`` (``split``: a shard's
    cached ``hybrid_split(policy)``, taken in place of a fresh one) -> the
    ELL view of what is left (``ell``: "auto"/True takes width tiers off the
    rows' counts, or ONE block at ``agreed_width`` where a shard carries
    one; an int takes ONE block of that width; False takes none) -> the
    flat triple (every entry, or only the view's overflow) padded to
    ``pad_to`` under ``_pad_flat``'s contract -> the dataclass -> the tail's
    gauges under the policy's label."""
    fields = {}
    if policy is not None:
        if split is None:
            split = _hybrid_arrays(rows, cols, vals, n, dim, policy)
        hot_vals, hot_ids, rows, cols, vals = split
        fields.update(
            hot_vals=jnp.asarray(hot_vals),
            hot_col_ids=jnp.asarray(hot_ids, dtype=jnp.int32),
        )
    tail_nnz = len(vals)
    if ell:
        ell_vals, ell_cols, tiers, rows, cols, vals = _ell_arrays(
            rows, cols, vals, n,
            agreed_width if ell == "auto" or ell is True else int(ell),
        )
        fields.update(
            ell_vals=jnp.asarray(ell_vals),
            ell_cols=jnp.asarray(ell_cols),
            ell_tiers=tuple(
                EllTier(vals=jnp.asarray(v), cols=jnp.asarray(c),
                        row_ids=jnp.asarray(r))
                for v, c, r in tiers
            ),
        )
    rows, cols, vals = _pad_flat(rows, cols, vals, pad_to, agreed=pad_agreed)
    batch = SparseLabeledPointBatch(
        values=jnp.asarray(vals),
        col_indices=jnp.asarray(cols, dtype=jnp.int32),
        row_ids=jnp.asarray(rows, dtype=jnp.int32),
        labels=jnp.asarray(labels),
        offsets=jnp.asarray(offsets),
        weights=jnp.asarray(weights),
        dim=int(dim),
        **fields,
    )
    if policy is not None:
        _record_tail_layout(policy.label, batch, tail_nnz)
    return batch


#: one-width ELL rule (an agreed width: the partitioned reader's and the
#: mesh's): the padded [n, L] may hold at most this multiple of the true nnz
#: (skewed rows spill to the COO tail instead of inflating L)
_ELL_WASTE_CAP = 1.6

#: the tier rule's prices, in slots (one slot = one gather of a coefficient
#: and one scatter-add into the gradient an evaluation): a row of a further
#: tier (its sum added at its row id, its dz gathered there), an overflow
#: entry (a gather, a segment-sum, a row gather, a scatter-add), a tier's
#: launches; and the most tiers a view may take
_TIER_ROW_COST = 0.5
_OVERFLOW_ENTRY_COST = 1.5
_TIER_LAUNCH_COST = 2048.0
_MAX_TIERS = 6
#: candidate widths the rule weighs at most (the distinct counts, thinned
#: evenly by rank beyond this many)
_MAX_TIER_CANDIDATES = 256


def _ell_tier_widths(freq: np.ndarray) -> tuple[int, ...]:
    """The ELL view's cumulative tier widths ``(W_1 < W_2 < ...)`` from the
    HISTOGRAM of the rows' entry counts (``freq[v]`` = rows that hold exactly
    v entries, empty rows included) and from nothing else.

    Tier 0 gives every row W_1 slots; tier k >= 1 gives the rows that hold
    more than W_k another W_{k+1} - W_k; entries beyond the last width
    overflow into the flat triple. The rule minimises what an evaluation
    moves: the slots, ``_TIER_ROW_COST`` a row of every further tier,
    ``_OVERFLOW_ENTRY_COST`` an overflow entry and ``_TIER_LAUNCH_COST`` a
    tier, over at most ``_MAX_TIERS`` tiers. Between two counts that occur the
    cost is linear in a width, so only counts that occur are candidates, and
    a shortest-path recursion over them is exact; ties go to fewer tiers,
    then to the narrower widths. Rows that all hold one count get one tier
    of that width."""
    freq = np.asarray(freq, dtype=np.int64)
    n = int(freq.sum())
    widths = np.flatnonzero(freq[1:]) + 1  # the counts that occur, ascending
    if len(widths) == 0:
        return (1,)
    if len(widths) > _MAX_TIER_CANDIDATES:
        keep = np.linspace(0, len(widths) - 1, _MAX_TIER_CANDIDATES)
        widths = widths[np.unique(keep.astype(np.int64))]
    # rows_from[v] = rows that hold v entries or more; entries_from[v] = the
    # entries that stand at position v or beyond in their rows
    rows_from = np.append(np.cumsum(freq[::-1])[::-1], 0)
    entries_from = np.append(np.cumsum(rows_from[::-1])[::-1], 0)
    lower = np.concatenate([[0], widths])  # where a tier starts
    holders = np.concatenate([[n], rows_from[widths + 1]]).astype(np.float64)
    span = widths[None, :] - lower[:, None]
    # step[i, j]: the price of a tier from width lower[i] to widths[j]
    step = holders[:, None] * span + _TIER_LAUNCH_COST
    step[1:] += _TIER_ROW_COST * holders[1:, None]
    step[span <= 0] = np.inf
    overflow = _OVERFLOW_ENTRY_COST * entries_from[widths + 1]
    reach = step[0]  # the cheapest t tiers that end at widths[j]
    came_from, best = [], (np.inf, 0, 0)
    for tiers in range(1, _MAX_TIERS + 1):
        total = reach + overflow
        j = int(np.argmin(total))
        if total[j] < best[0]:
            best = (float(total[j]), tiers, j)
        if tiers < _MAX_TIERS:
            via = reach[:, None] + step[1:]
            came_from.append(np.argmin(via, axis=0))
            reach = via.min(axis=0)
    _, tiers, j = best
    chosen = []
    for t in range(tiers - 1, -1, -1):
        chosen.append(int(widths[j]))
        if t:
            j = int(came_from[t - 1][j])
    return tuple(reversed(chosen))


def _ell_arrays(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n: int,
    width: int | None,
) -> tuple:
    """Split row-sorted COO triples into the ELL view + overflow tail.

    Returns (ell_vals [n, W_1], ell_cols [n, W_1], tiers, tail_rows,
    tail_cols, tail_vals). Each row's first W_1 entries land in the first
    block (pad value 0 / col 0); ``tiers`` holds, for every further width
    W_{k+1} of the view, the (vals [W_{k+1} - W_k, n_k], cols, row_ids) of
    entries W_k + 1 .. W_{k+1} of the rows that hold more than W_k (rows
    along the minor axis, :class:`EllTier`; row ids ascending); entries
    beyond the last width spill to the tail. width=None takes the widths
    ``_ell_tier_widths`` reads off the rows' counts; an explicit width is one
    block of that width and no further tier."""
    counts = np.bincount(rows, minlength=n) if len(rows) else np.zeros(n, np.int64)
    if width is None:
        widths = _ell_tier_widths(np.bincount(counts))
    else:
        widths = (max(1, int(width)),)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(rows)) - starts[rows] if len(rows) else np.zeros(0, np.int64)
    blocks, lower = [], 0
    for upper in widths:
        here = (pos >= lower) & (pos < upper)
        if lower == 0:
            row_ids = None  # every row
            shape, at = (n, upper), (rows[here], pos[here])
        else:
            holds = counts > lower
            row_ids = np.flatnonzero(holds).astype(np.int32)
            slot = np.cumsum(holds) - 1  # row -> its place in the tier
            shape = (upper - lower, len(row_ids))
            at = (pos[here] - lower, slot[rows[here]])
        tier_vals = np.zeros(shape, dtype=vals.dtype)
        tier_cols = np.zeros(shape, dtype=np.int32)
        tier_vals[at] = vals[here]
        tier_cols[at] = cols[here]
        blocks.append((tier_vals, tier_cols, row_ids))
        lower = upper
    tail = pos >= lower
    (ell_vals, ell_cols, _), tiers = blocks[0], tuple(blocks[1:])
    return ell_vals, ell_cols, tiers, rows[tail], cols[tail], vals[tail]


def _ell_auto_width(counts: np.ndarray, n: int, nnz: int) -> int:
    """The ONE width of an agreed [n, L] block (a mesh's, the partitioned
    reader's): smallest L covering the 98th-percentile row while keeping n·L
    under ``_ELL_WASTE_CAP``x nnz (skewed rows spill to the COO tail)."""
    if nnz == 0:
        return 1
    q = int(np.quantile(counts, 0.98)) if n else 1
    width = max(1, q)
    while width > 1 and n * width > _ELL_WASTE_CAP * nnz:
        width -= 1
    return width


def _ell_auto_width_from_hist(freq: np.ndarray, n: int, nnz: int) -> int:
    """``_ell_auto_width`` evaluated from a row-count HISTOGRAM (``freq[v]``
    = number of rows with exactly v entries, zero-count rows included)
    instead of the expanded per-row counts array.

    BIT-EXACT on purpose: io/partitioned_reader.py sums per-rank histograms
    and must land on exactly the width the full read's np.quantile call
    would pick, or the composed ELL/overflow split — and with it every
    downstream float — drifts from the unpartitioned run. The 0.98 order
    statistics come from the cumulative histogram and interpolate with
    numpy's own ``_lerp`` rule (a + (b-a)·t below t=0.5, b - (b-a)·(1-t)
    at or above — the two forms differ in the last ulp), pinned against
    np.quantile by a property test."""
    if nnz == 0:
        return 1
    if n:
        vi = 0.98 * (n - 1)
        prev = int(np.floor(vi))
        cum = np.cumsum(np.asarray(freq, dtype=np.int64))
        a = int(np.searchsorted(cum, prev + 1, side="left"))
        b = int(np.searchsorted(cum, int(np.ceil(vi)) + 1, side="left"))
        t = np.float64(vi) - np.float64(prev)
        d = np.float64(b - a)
        q = int(
            np.float64(b) - d * (np.float64(1.0) - t) if t >= 0.5
            else np.float64(a) + d * t
        )
    else:
        q = 1
    width = max(1, q)
    while width > 1 and n * width > _ELL_WASTE_CAP * nnz:
        width -= 1
    return width


def _pad_flat(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    target: int | None, *, agreed: bool,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a batch's flat triple to ``target`` entries under the one
    inert-entry contract: value 0 / col 0 / the LAST real row id, so that
    the row ids stay sorted (segment_sum is told ``indices_are_sorted=True``
    — a correctness promise, not just a hint) and the concatenated
    cross-rank row ids stay nondecreasing (the next rank's rows start past
    this rank's block). ``agreed``: the target is the length every rank's
    block must have (``SparseShard.flat_block_nnz``), so a triple longer
    than it raises; a ``pad_nnz_to`` below the count pads nothing."""
    if target is None:
        return rows, cols, vals
    pad = int(target) - len(vals)
    if pad < 0 and agreed:
        raise ValueError(
            f"flat overflow tail has {len(vals)} entries but the agreed "
            f"flat_block_nnz is {target} — the reader's ELL-width exchange "
            "must cover every rank's overflow"
        )
    if pad <= 0:
        return rows, cols, vals
    last = rows[-1] if len(rows) else 0
    return (
        np.concatenate([rows, np.full(pad, last, rows.dtype)]),
        np.concatenate([cols, np.zeros(pad, cols.dtype)]),
        np.concatenate([vals, np.zeros(pad, vals.dtype)]),
    )


def rank_hot_columns(
    uniq: np.ndarray, cnt: np.ndarray, nnz: int, policy: HybridPolicy
) -> np.ndarray:
    """The ONE head-sizing rule: given a per-column nnz histogram
    (``uniq`` sorted unique column ids, ``cnt`` their counts, ``nnz`` the
    total), return the sorted hot-head ids under ``policy``'s budget/
    coverage knobs. Shared verbatim by the local builder below and the
    partitioned reader's GLOBAL (histogram-summed-over-ranks) resolution
    (io/partitioned_reader.py) — the two paths must elect identical heads
    from identical histograms, including the stable count-then-id
    tie-break (``uniq`` is sorted, so stable argsort on -cnt breaks count
    ties by ascending column id deterministically)."""
    if nnz == 0 or len(uniq) == 0:
        return np.zeros(0, dtype=np.int64)
    order = np.argsort(-np.asarray(cnt), kind="stable")
    if policy.hot_cols is not None:
        k = int(policy.hot_cols)
    else:
        cum = np.cumsum(np.asarray(cnt)[order])
        k = int(np.searchsorted(cum, policy.effective_coverage * nnz)) + 1
    k = min(k, policy.max_hot_cols, len(uniq))
    return np.sort(np.asarray(uniq)[order[:k]].astype(np.int64))


def _select_hot_columns(
    cols: np.ndarray, nnz: int, policy: HybridPolicy
) -> tuple[np.ndarray, int]:
    """Rank columns by nnz and return (sorted global ids of the hot head —
    true size, before lane padding — , covered nonzero count).

    Column frequencies come from ``np.unique`` on the nnz entries, never a
    dim-length bincount — dim may be 10⁸⁺ while nnz stays modest. A policy
    carrying pre-resolved ``hot_ids`` (the partitioned reader's global
    ranking) skips the local ranking entirely: the head — including its
    SHAPE — must agree across ranks even on a rank whose local block never
    observed some (or any) of the hot columns.
    """
    if policy.hot_ids is not None:
        hot = np.asarray(policy.hot_ids, dtype=np.int64)
        if nnz == 0:
            return hot, 0
        pos = np.searchsorted(hot, cols)
        covered = int(
            (hot[np.minimum(pos, len(hot) - 1)] == cols).sum()
        )
        return hot, covered
    if nnz == 0:
        return np.zeros(0, dtype=np.int64), 0
    uniq, cnt = np.unique(cols, return_counts=True)
    hot = rank_hot_columns(uniq, cnt, nnz, policy)
    if len(hot):
        pos = np.searchsorted(uniq, hot)
        return hot, int(cnt[pos].sum())
    return hot, 0


def _hybrid_arrays(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    n: int, dim: int, policy: HybridPolicy, *, pad: bool = True,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Split row-sorted coalesced COO into a dense hot head + cold tail.

    Returns (hot_block [n, k_pad], hot_ids [k_pad], tail_rows, tail_cols,
    tail_vals). The head holds the k_hot hottest columns' entries as a
    zero-filled dense block (head slot j = rank of the column id within the
    sorted hot set); pad slots beyond the true k carry the LAST hot id over
    an all-zero column — inert in the margin gather AND the gradient
    scatter. ``pad=False`` skips the lane padding (the column-sharded
    builder re-pads PER BLOCK; padding first would dump every duplicate pad
    id into the last hot column's block and inflate its width). The tail
    triple preserves row-major order (it is a filtered subsequence of the
    sorted input), so the downstream ELL/segment-sum sorted promises
    survive. Records the head's gauges under ``layout/<policy.label>/*``
    (telemetry/layout.py); the tail's are set from the arrays a batch
    builder makes of the triple (``_record_tail_layout``).
    """
    from photon_ml_tpu.telemetry.layout import record_hybrid_layout

    nnz = len(vals)
    hot, covered = _select_hot_columns(cols, nnz, policy)
    k = len(hot)
    if k:
        pm = policy.pad_multiple if pad else 1
        k_pad = -(-k // pm) * pm
        pos = np.searchsorted(hot, cols)
        posc = np.minimum(pos, k - 1)
        is_hot = hot[posc] == cols
        hot_ids = np.concatenate(
            [hot, np.full(k_pad - k, hot[-1], dtype=np.int64)]
        )
    else:
        k_pad = 0
        posc = np.zeros(nnz, dtype=np.int64)
        is_hot = np.zeros(nnz, dtype=bool)
        hot_ids = np.zeros(0, dtype=np.int64)
    hot_block = np.zeros((n, k_pad), dtype=vals.dtype)
    if k:
        # input is coalesced: each (row, col) appears once, so assignment
        # (not np.add.at) is the correct accumulation
        hot_block[rows[is_hot], posc[is_hot]] = vals[is_hot]
    tail = ~is_hot
    tr, tc, tv = rows[tail], cols[tail], vals[tail]

    record_hybrid_layout(
        policy.label,
        k_hot=k,
        k_hot_padded=k_pad,
        hot_coverage=covered / nnz if nnz else 0.0,
        hot_nnz=covered,
        tail_nnz=len(tv),
    )
    return hot_block, hot_ids, tr, tc, tv


def _record_tail_layout(
    label: str, batch: SparseLabeledPointBatch, tail_nnz: int
) -> None:
    """The gauges of a hybrid batch's cold tail, read off the arrays that
    were BUILT (telemetry/layout.py ``record_tail_layout``): the slots an
    evaluation gathers and scatters (every ELL tier's, pads included, and the
    flat triple's), the tiers' count, the view's last width, the share of the
    slots that holds no entry, and the batch's bytes, head included."""
    from photon_ml_tpu.telemetry.layout import record_tail_layout

    widths = [batch.ell_vals.shape[1]] if batch.has_ell_view else []
    widths += [tier.width for tier in batch.ell_tiers]
    held = jax.tree_util.tree_leaves((
        batch.ell_vals, batch.ell_cols, batch.ell_tiers, batch.values,
        batch.col_indices, batch.row_ids, batch.hot_vals,
    ))
    slots = batch.nnz + sum(int(v.size) for v in jax.tree_util.tree_leaves(
        (batch.ell_vals, [tier.vals for tier in batch.ell_tiers])
    ))
    record_tail_layout(
        label,
        tail_slots=slots,
        tail_tiers=len(widths),
        tail_width=sum(widths),
        tail_pad_share=1.0 - tail_nnz / slots if slots else 0.0,
        hybrid_bytes=sum(int(a.size) * a.dtype.itemsize for a in held),
    )


def hot_head_dot(a: Array, b: Array) -> Array:
    """A dot with the dense hot head as one operand, held to float32: a TPU's
    DEFAULT matmul precision lets the compiler round float32 operands to
    bfloat16 (1e-3 on every margin), so the head's three products (margins,
    gradient, Hessian-vector) say ``HIGHEST``. (On a v5e XLA makes these
    matrix-vector products float32 multiply-reduces on the vector unit
    either way, PERF.md 6, PR 43: the name costs nothing and binds the next
    compiler too.)"""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def sparse_product(batch: SparseLabeledPointBatch, coefficients: Array) -> Array:
    """``X @ w`` without materializing X and without the offsets: what a
    model scores a sparse block with (models/coefficients.py).

    ELL view: one gather + a DENSE row-sum a width tier — no per-row
    segment-sum at all; a further tier's [n_k] sums are added into [n] at its
    row ids (ascending and unique: no sort, no conflict); only overflow-tail
    entries, if any, take the segment-sum path. Flat COO: gather w at the
    nonzero columns, multiply, segment-sum by row. Either way autodiff
    produces the transpose scatter-add gradient (the reference's
    hand-written sparse axpy, ValueAndGradientAggregator.scala).

    Hybrid view: the hot head adds ONE dense matmul over the [n, k_hot]
    block against a k_hot-sized coefficient gather (scope ``sparse/head``)
    — no per-entry index ops at all for the covered nonzeros — and the
    ELL/flat arrays (holding only the cold tail) contribute through the
    unchanged paths (scope ``sparse/tail_margins``). Head pad columns are
    zero, so their clamped ids are inert.
    """
    base = None
    if batch.has_hybrid_view:
        with jax.named_scope("sparse/head"):
            base = hot_head_dot(batch.hot_vals, coefficients[batch.hot_col_ids])
    with jax.named_scope("sparse/tail_margins"):
        if batch.has_ell_view:
            ell = jnp.einsum(
                "nl,nl->n", batch.ell_vals, coefficients[batch.ell_cols]
            )
            for tier in batch.ell_tiers:
                ell = ell.at[tier.row_ids].add(
                    jnp.einsum("ln,ln->n", tier.vals, coefficients[tier.cols]),
                    indices_are_sorted=True, unique_indices=True,
                )
            base = ell if base is None else base + ell
        # the flat triple: whatever overflowed the ELL view, or (no other
        # view) every entry, none included
        if batch.values.shape[0] or base is None:
            flat = jax.ops.segment_sum(
                batch.values * coefficients[batch.col_indices],
                batch.row_ids,
                num_segments=batch.num_samples, indices_are_sorted=True,
            )
            base = flat if base is None else base + flat
    return base


def sparse_margins(batch: SparseLabeledPointBatch, coefficients: Array) -> Array:
    """margin_i = Σ_j x_ij w_j + offset_i (:func:`sparse_product` plus the
    batch's offsets)."""
    return sparse_product(batch, coefficients) + batch.offsets


def tail_transpose_add(
    out: Array, batch: SparseLabeledPointBatch, row_terms: Array,
    square_values: bool = False,
) -> Array:
    """``out`` [dim] plus Σ_i row_terms_i · x_ij (or x_ij²) over the entries
    of the ELL view and the flat triple: the transpose scatter-adds of
    :func:`sparse_product`'s gathers. The row term broadcasts over a tier's
    width (no per-entry row gather; a further tier gathers it once a row, at
    its row ids); pad slots (value 0, col 0) add 0 to column 0."""
    def values(v):
        return v * v if square_values else v

    if batch.has_ell_view:
        contrib = row_terms[:, None] * values(batch.ell_vals)
        out = out.at[batch.ell_cols.ravel()].add(contrib.ravel())
        for tier in batch.ell_tiers:
            contrib = row_terms[tier.row_ids][None, :] * values(tier.vals)
            out = out.at[tier.cols.ravel()].add(contrib.ravel())
    if batch.values.shape[0]:
        out = out.at[batch.col_indices].add(
            row_terms[batch.row_ids] * values(batch.values)
        )
    return out


def sparse_column_sum(
    batch: SparseLabeledPointBatch, row_weights: Array,
    square_values: bool = False,
) -> Array:
    """Σ_i row_weights_i · x_ij (or x_ij²) per column j — the building block
    of Hessian diagonals (row_weights = w_i·l''_i). The ELL tiers and the
    flat triple go through :func:`tail_transpose_add`; the hybrid head
    reduces over rows as one dense matvec, then scatters just k_hot
    per-column sums (its zero pad columns are inert too)."""
    out = jnp.zeros((batch.dim,), dtype=batch.values.dtype)
    if batch.has_hybrid_view:
        hv = batch.hot_vals * batch.hot_vals if square_values else batch.hot_vals
        out = out.at[batch.hot_col_ids].add(row_weights @ hv)
    return tail_transpose_add(out, batch, row_weights, square_values)


def summarize_sparse(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    *,
    n: int,
    dim: int,
    weights: np.ndarray | None = None,
    assume_coalesced: bool = False,
) -> dict:
    """Weighted per-column summary from COO triples (host-side), matching
    data/batch.py::summarize on the equivalent dense matrix — including its
    treatment of implicit zeros (they participate in mean/variance).
    """
    rows = np.asarray(rows, dtype=np.intp)
    cols = np.asarray(cols, dtype=np.intp)
    vals = np.asarray(vals, dtype=np.float64)
    if not assume_coalesced:
        # duplicates must accumulate before any squaring/extremum — same
        # rule as the dense scatter (np.add.at) these stats must agree with
        rows, cols, vals = coalesce_coo(rows, cols, vals)
    if weights is None:
        weights = np.ones((n,), dtype=np.float64)
    else:
        weights = np.asarray(weights, dtype=np.float64)
    wsum = weights.sum()
    wv = weights[rows] * vals

    def col_acc(contrib):
        acc = np.zeros((dim,), dtype=np.float64)
        np.add.at(acc, cols, contrib)
        return acc

    s1 = col_acc(wv)  # Σ w x
    mean = s1 / wsum
    # Σ w (x - m)² = Σ_nnz w (x² - 2 m x) + m² Σ_all w   (implicit zeros
    # contribute w·m² each; Σ_all w telescopes them back in)
    s2 = col_acc(weights[rows] * vals * vals)
    var = (s2 - 2.0 * mean * s1 + mean * mean * wsum) / np.maximum(wsum - 1.0, 1.0)
    # max/min over the dense equivalent: columns with fewer than n explicit
    # entries implicitly contain 0
    counts = np.zeros((dim,), dtype=np.int64)
    np.add.at(counts, cols, 1)
    has_implicit_zero = counts < n
    cmax = np.full((dim,), -np.inf)
    np.maximum.at(cmax, cols, vals)
    cmax = np.where(has_implicit_zero, np.maximum(cmax, 0.0), cmax)
    cmax = np.where(np.isneginf(cmax), 0.0, cmax)
    cmin = np.full((dim,), np.inf)
    np.minimum.at(cmin, cols, vals)
    cmin = np.where(has_implicit_zero, np.minimum(cmin, 0.0), cmin)
    cmin = np.where(np.isinf(cmin), 0.0, cmin)
    return {
        "count": n,
        "weight_sum": wsum,
        "mean": mean,
        "variance": var,
        "max": cmax,
        "min": cmin,
        "max_magnitude": np.maximum(np.abs(cmax), np.abs(cmin)),
        "norm_l1": col_acc(np.abs(vals)),
        "norm_l2": np.sqrt(col_acc(vals * vals)),
        "num_nonzeros": col_acc((vals != 0).astype(np.float64)),
    }
