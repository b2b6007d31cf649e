"""Chunked out-of-core ingestion: double-buffered Avro decode behind compute.

Reference parity: photon-client data/avro/AvroDataReader.scala — the
reference never materializes the full input on one machine; Spark streams
HDFS splits through executor tasks while the driver aggregates. Here the
equivalent for a single host feeding an accelerator is an exact chunked
EPOCH: a background thread decodes the NEXT contiguous run of Avro
container blocks (the PR 2 block planner — ``avro.scan_block_index`` /
``read_container_block_range``) into host numpy buffers while the device
accumulates the CURRENT chunk's contribution (algorithm/streaming.py) —
the compute/ingest overlap Snap ML builds its hierarchy around
(arXiv:1803.06333).

Design rules (all enforced somewhere):

- **Fixed chunk shapes.** Every chunk pads to the plan's ``chunk_rows``
  (zero-weight rows — the framework padding contract), and sparse chunks
  share one ELL width / flat-entry length / hot-column count, so the
  device accumulator compiles ONCE and every chunk rides the same jit
  signature as an ARGUMENT (never a closed-over constant, which would make
  every chunk its own program; dev/lint_parity.py check 9 statically bans
  nested jit in the streaming modules).
- **Prefetch is bounded and hang-free.** The producer thread and the
  consumer exchange through a depth-bounded queue with timeouts both
  ways plus a bounded join on close — a wedged side surfaces as a typed
  :class:`StreamDecodeError`, never an unbounded hang (the chaos suite
  has no pytest-timeout to save it).
- **Failures are classified.** Chunk decode runs under a
  ``resilience.RetryPolicy`` (transient I/O heals, fatal corruption
  surfaces attributed with the chunk's file/block span); the prefetch
  thread never swallows — it forwards the classified error to the
  consumer, which re-raises it on the caller's stack.
- **Observable.** Per-chunk decode ms, per-epoch chunk count, and the
  epoch's overlap fraction feed the process-wide registry
  (telemetry/stream_counters.py) — the run-journal evidence that decode
  actually hid behind device time.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import queue
import threading
import time
from typing import Callable, Mapping, Sequence

import numpy as np

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.resilience import RetryPolicy, classify_exception, default_io_policy
from photon_ml_tpu.telemetry import io_counters, stream_counters, tracing

#: consumer-side wait bound per chunk (seconds): generous enough for a slow
#: multi-GB chunk decode, bounded enough that a wedged producer fails
#: attributed instead of hanging a run forever (same rationale as
#: parallel/multihost.DEFAULT_EXCHANGE_TIMEOUT)
DEFAULT_CHUNK_TIMEOUT = 120.0

#: bounded join for the producer thread at close
JOIN_TIMEOUT = 10.0


class StreamDecodeError(RuntimeError):
    """A chunk failed to decode (after classified retries) or the prefetch
    pipeline wedged; carries the chunk attribution in the message."""


@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """One planned chunk: ``runs`` are contiguous (file, start_block,
    num_blocks) container-block ranges whose records fill the chunk."""

    index: int
    num_records: int
    runs: tuple[tuple[str, int, int], ...] = ()


class ChunkSource:
    """Protocol for streaming chunk sources.

    specs:      the epoch's chunk plan (fixed, re-iterable)
    chunk_rows: fixed padded row count every ``load`` result carries
    dim:        feature-space dimension
    sparse:     True when ``load`` yields SparseLabeledPointBatch chunks
    load(spec): decode + assemble one chunk — pure and idempotent (it is
                retried on transient failures), padded to ``chunk_rows``
    """

    specs: "list[ChunkSpec]"
    chunk_rows: int
    dim: int
    sparse: bool = False

    def load(self, spec: ChunkSpec):
        raise NotImplementedError

    @property
    def num_chunks(self) -> int:
        return len(self.specs)

    @property
    def total_records(self) -> int:
        return int(sum(s.num_records for s in self.specs))


def _pad_dense_chunk(
    features: np.ndarray,
    labels: np.ndarray,
    offsets: np.ndarray,
    weights: np.ndarray,
    chunk_rows: int,
) -> LabeledPointBatch:
    """Host-side zero-weight padding to the fixed chunk shape (numpy — the
    producer thread must not touch the device)."""
    n = features.shape[0]
    pad = chunk_rows - n
    if pad < 0:
        raise ValueError(f"chunk has {n} rows > plan chunk_rows {chunk_rows}")
    if pad:
        features = np.pad(features, ((0, pad), (0, 0)))
        labels = np.pad(labels, (0, pad))
        offsets = np.pad(offsets, (0, pad))
        weights = np.pad(weights, (0, pad))
    return LabeledPointBatch(
        features=features, labels=labels, offsets=offsets, weights=weights
    )


class ArrayChunkSource(ChunkSource):
    """Dense in-memory source: chunks a host [n, d] array by row ranges.

    The reference workload for tests: ``decode_hook`` (called once
    per ``load`` in whichever thread loads) injects host decode cost or
    faults — e.g. a sleep standing in for disk/decompress latency, or a
    ``dev.faultinject.flaky`` transient failure.
    """

    sparse = False

    def __init__(
        self,
        features: np.ndarray,
        labels: np.ndarray,
        *,
        offsets: np.ndarray | None = None,
        weights: np.ndarray | None = None,
        chunk_rows: int,
        decode_hook: Callable[[], None] | None = None,
    ):
        self.features = np.asarray(features)
        n = self.features.shape[0]
        self.labels = np.asarray(labels, dtype=self.features.dtype)
        self.offsets = (
            np.zeros((n,), self.features.dtype) if offsets is None
            else np.asarray(offsets, dtype=self.features.dtype)
        )
        self.weights = (
            np.ones((n,), self.features.dtype) if weights is None
            else np.asarray(weights, dtype=self.features.dtype)
        )
        self.chunk_rows = int(chunk_rows)
        self.dim = int(self.features.shape[1])
        self.decode_hook = decode_hook
        self.specs = [
            ChunkSpec(index=i, num_records=min(self.chunk_rows, n - lo))
            for i, lo in enumerate(range(0, n, self.chunk_rows))
        ]

    def load(self, spec: ChunkSpec) -> LabeledPointBatch:
        if self.decode_hook is not None:
            self.decode_hook()
        lo = spec.index * self.chunk_rows
        hi = lo + spec.num_records
        # copies, not views: a real decode materializes fresh buffers, and
        # the accumulator must never alias the source arrays
        return _pad_dense_chunk(
            np.array(self.features[lo:hi]),
            np.array(self.labels[lo:hi]),
            np.array(self.offsets[lo:hi]),
            np.array(self.weights[lo:hi]),
            self.chunk_rows,
        )


class SparseArrayChunkSource(ChunkSource):
    """Sparse in-memory source: chunks host COO triples by row ranges into
    fixed-layout ELL (+ optional hybrid dense-head) chunks.

    The LAYOUT is resolved once, globally, at construction — one ELL width
    (the max post-head row count over every chunk), one flat-tail entry
    length, and one hot-column id set ranked on the FULL data — so every
    chunk shares a single jit signature (the same global-layout-agreement
    rule io/partitioned_reader._resolve_global_sparse_layout applies
    across ranks, applied here across chunks).
    """

    sparse = True

    def __init__(
        self,
        rows,
        cols,
        vals,
        labels,
        *,
        dim: int,
        chunk_rows: int,
        offsets=None,
        weights=None,
        hybrid=None,
        dtype=np.float64,
        decode_hook: Callable[[], None] | None = None,
    ):
        from photon_ml_tpu.data.sparse_batch import (
            coalesce_coo,
            rank_hot_columns,
            resolve_hybrid_policy,
        )

        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals, dtype=dtype)
        self.rows, self.cols, self.vals = coalesce_coo(rows, cols, vals)
        self.labels = np.asarray(labels, dtype=dtype)
        n = self.labels.shape[0]
        self.offsets = (
            np.zeros((n,), dtype) if offsets is None
            else np.asarray(offsets, dtype=dtype)
        )
        self.weights = (
            np.ones((n,), dtype) if weights is None
            else np.asarray(weights, dtype=dtype)
        )
        self.dim = int(dim)
        self.dtype = dtype
        self.chunk_rows = int(chunk_rows)
        self.decode_hook = decode_hook
        self.specs = [
            ChunkSpec(index=i, num_records=min(self.chunk_rows, n - lo))
            for i, lo in enumerate(range(0, n, self.chunk_rows))
        ]

        # ---- one global layout for every chunk ----
        policy = resolve_hybrid_policy(hybrid)
        if policy is not None and policy.hot_ids is None:
            uniq, cnt = np.unique(self.cols, return_counts=True)
            hot = rank_hot_columns(uniq, cnt, len(self.vals), policy)
            policy = dataclasses.replace(
                policy, hot_ids=tuple(int(c) for c in hot)
            )
        self.hybrid_policy = policy
        if policy is not None:
            hot_sorted = np.sort(np.asarray(policy.hot_ids, dtype=np.int64))
            pos = np.searchsorted(hot_sorted, self.cols)
            is_hot = (
                hot_sorted[np.minimum(pos, len(hot_sorted) - 1)] == self.cols
            )
            tail_rows = self.rows[~is_hot]
        else:
            tail_rows = self.rows
        counts = np.bincount(tail_rows, minlength=n) if n else np.zeros(0, int)
        self.ell_width = int(counts.max()) if len(counts) else 0
        # every row fits the agreed width, so the flat tail holds only the
        # inert minimum (one zero entry keeps the [nnz] axis non-empty)
        self.flat_nnz = 1

    def load(self, spec: ChunkSpec):
        from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch

        if self.decode_hook is not None:
            self.decode_hook()
        lo = spec.index * self.chunk_rows
        hi = lo + spec.num_records
        sel = (self.rows >= lo) & (self.rows < hi)
        labels = np.zeros((self.chunk_rows,), self.dtype)
        offsets = np.zeros((self.chunk_rows,), self.dtype)
        weights = np.zeros((self.chunk_rows,), self.dtype)
        labels[: spec.num_records] = self.labels[lo:hi]
        offsets[: spec.num_records] = self.offsets[lo:hi]
        weights[: spec.num_records] = self.weights[lo:hi]
        return SparseLabeledPointBatch.from_coo(
            self.rows[sel] - lo,
            self.cols[sel],
            self.vals[sel],
            labels,
            dim=self.dim,
            offsets=offsets,
            weights=weights,
            dtype=self.dtype,
            ell=self.ell_width,
            pad_nnz_to=self.flat_nnz,
            hybrid=self.hybrid_policy,
        )


# ---------------------------------------------------------------------------
# Entity-clustered GAME chunks (ISSUE 11): the out-of-core GAME contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GameChunk:
    """One decoded GAME chunk (host numpy, fixed ``chunk_rows`` padding).

    The GAME analogue of a :class:`LabeledPointBatch` chunk: per-shard
    feature blocks, per-sample scalars, per-RE-type entity indices (into
    the GLOBAL entity vocab, -1 for absent/padding), and each slot's
    GLOBAL sample row (``rows``, -1 padding) so host-resident [n] score
    vectors can be read/written per chunk. Padding rows carry weight 0 /
    zero features per the framework padding contract.
    """

    features: "dict[str, np.ndarray]"
    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    entity_idx: "dict[str, np.ndarray]"
    rows: np.ndarray
    num_records: int


def plan_entity_chunks(
    entity_idx: np.ndarray, chunk_records: int
) -> "list[np.ndarray]":
    """Entity-clustered chunk plan over in-memory rows: pack WHOLE
    entities (all rows sharing an entity index, in ascending row order)
    greedily into chunks of at most ``chunk_records`` rows — an entity
    larger than the budget forms its own chunk, like an over-budget Avro
    block in :func:`plan_chunks`. Rows with entity -1 (vocab-absent:
    scored, never trained) pack as singletons wherever they fall.

    This is what lets a random-effect bucket solve run per chunk with the
    chunk resident: every entity's rows co-reside in exactly ONE chunk,
    so its per-entity solve sees the identical padded block the in-core
    path builds (zero-weight cap padding is an exact no-op). Returns the
    per-chunk global row-index arrays.
    """
    if chunk_records <= 0:
        raise ValueError(f"chunk_records must be positive, got {chunk_records}")
    entity_idx = np.asarray(entity_idx)
    n = len(entity_idx)
    if n == 0:
        return []
    # stable sort groups each entity's rows contiguously while preserving
    # ascending row order within the entity (the in-core bucketing's order)
    order = np.argsort(entity_idx, kind="stable")
    ents = entity_idx[order]
    boundaries = np.concatenate(
        [[0], np.nonzero(ents[1:] != ents[:-1])[0] + 1, [n]]
    )
    chunks: list[np.ndarray] = []
    cur: list[np.ndarray] = []
    cur_n = 0

    def flush():
        nonlocal cur, cur_n
        if cur:
            chunks.append(np.concatenate(cur))
            cur, cur_n = [], 0

    for start, end in zip(boundaries[:-1], boundaries[1:]):
        if ents[start] < 0:
            # vocab-absent rows: no clustering constraint — split freely
            group_rows = order[start:end]
            for lo in range(0, len(group_rows), chunk_records):
                sub = group_rows[lo:lo + chunk_records]
                if cur and cur_n + len(sub) > chunk_records:
                    flush()
                cur.append(sub)
                cur_n += len(sub)
            continue
        group = order[start:end]
        if cur and cur_n + len(group) > chunk_records:
            flush()
        cur.append(group)
        cur_n += len(group)
    flush()
    return chunks


def entities_spanning_chunks(
    row_plan: "Sequence[np.ndarray]", entity_idx: np.ndarray
) -> np.ndarray:
    """Entity rows (vocab indices) whose samples land in MORE than one
    chunk of ``row_plan`` — the entities a per-chunk random-effect solve
    would silently train on partial data (last chunk wins). Empty means
    the plan entity-clusters this RE type."""
    entity_idx = np.asarray(entity_idx)
    chunk_of = np.full(len(entity_idx), -1, dtype=np.int64)
    for i, rows in enumerate(row_plan):
        chunk_of[rows] = i
    valid = entity_idx >= 0
    if not valid.any():
        return np.zeros((0,), dtype=np.int64)
    pairs = np.unique(
        np.stack([entity_idx[valid].astype(np.int64), chunk_of[valid]]),
        axis=1,
    )
    ents, counts = np.unique(pairs[0], return_counts=True)
    return ents[counts > 1]


class GameArrayChunkSource:
    """Entity-clustered in-memory GAME chunk source: host arrays chunked
    by whole-entity row groups (:func:`plan_entity_chunks`).

    The host-RAM >> HBM tier of the out-of-core hierarchy (Snap ML,
    arXiv:1803.06333): per-sample scalars ([n] labels/offsets/weights/
    entity indices and the score vectors the streamed GAME program keeps)
    stay host-resident, while the O(n·d) feature blocks enter the device
    one fixed-shape chunk at a time through the module-level jitted steps
    (algorithm/streaming_game.py — chunks as jit ARGUMENTS, lint check 9).

    ``cluster_by``: the RE type whose entities define chunk grouping
    (required when any random-effect coordinate trains from this source);
    other RE types must nest inside those groups —
    ``StreamingGameProgram`` verifies with :func:`entities_spanning_chunks`
    and fails fast otherwise. ``decode_hook`` runs once per load in the
    loading thread (prefetch-overlap and fault-injection seam, like
    :class:`ArrayChunkSource`).
    """

    sparse = False

    def __init__(
        self,
        *,
        features: "Mapping[str, np.ndarray]",
        labels: np.ndarray,
        entity_idx: "Mapping[str, np.ndarray]",
        offsets: np.ndarray | None = None,
        weights: np.ndarray | None = None,
        chunk_records: int,
        cluster_by: str | None = None,
        decode_hook: Callable[[], None] | None = None,
    ):
        self.features = {k: np.asarray(v) for k, v in features.items()}
        self.labels = np.asarray(labels)
        n = self.labels.shape[0]
        dtype = self.labels.dtype
        self.offsets = (
            np.zeros((n,), dtype) if offsets is None
            else np.asarray(offsets, dtype=dtype)
        )
        self.weights = (
            np.ones((n,), dtype) if weights is None
            else np.asarray(weights, dtype=dtype)
        )
        self.entity_idx = {
            t: np.asarray(v, dtype=np.int32) for t, v in entity_idx.items()
        }
        self.decode_hook = decode_hook
        if cluster_by is not None and cluster_by not in self.entity_idx:
            raise ValueError(
                f"cluster_by={cluster_by!r} is not an entity-index column "
                f"({sorted(self.entity_idx)})"
            )
        self.cluster_by = cluster_by
        if cluster_by is not None:
            self.row_plan = plan_entity_chunks(
                self.entity_idx[cluster_by], chunk_records
            )
        else:
            self.row_plan = [
                np.arange(lo, min(lo + chunk_records, n))
                for lo in range(0, n, chunk_records)
            ]
        self.specs = [
            ChunkSpec(index=i, num_records=len(rows))
            for i, rows in enumerate(self.row_plan)
        ]
        self.chunk_rows = max((len(r) for r in self.row_plan), default=0)
        self.dims = {k: int(v.shape[1]) for k, v in self.features.items()}

    @property
    def num_chunks(self) -> int:
        return len(self.specs)

    @property
    def total_records(self) -> int:
        return int(sum(s.num_records for s in self.specs))

    def load(self, spec: ChunkSpec) -> GameChunk:
        if self.decode_hook is not None:
            self.decode_hook()
        idx = self.row_plan[spec.index]
        pad = self.chunk_rows - len(idx)

        def pad1(a, fill=0):
            out = a[idx]
            if pad:
                out = np.concatenate(
                    [out, np.full((pad,) + out.shape[1:], fill, out.dtype)]
                )
            return out

        rows = idx.astype(np.int64)
        if pad:
            rows = np.concatenate([rows, np.full((pad,), -1, np.int64)])
        return GameChunk(
            # copies, not views (fancy indexing copies): the accumulator
            # must never alias the source arrays
            features={k: pad1(v) for k, v in self.features.items()},
            labels=pad1(self.labels),
            offsets=pad1(self.offsets),
            weights=pad1(self.weights),
            entity_idx={
                t: pad1(v, fill=-1) for t, v in self.entity_idx.items()
            },
            rows=rows,
            num_records=len(idx),
        )


def plan_entity_chunks_avro(
    files: Sequence[str],
    chunk_records: int,
    cluster_keys: np.ndarray,
    *,
    indexes: "list[list[tuple[int, int, int]]] | None" = None,
    on_corrupt: str = "raise",
):
    """Entity-clustered Avro chunk plan at RECORD granularity: a chunk is
    a record range whose end lands on the first clustering-entity CHANGE
    at or after ``chunk_records`` rows (``cluster_keys``: the per-record
    entity key of the cluster column in file+record order; "" — a missing
    id — is itself a vocab entity and clusters like any other), so an
    entity-sorted input yields chunks
    that hold whole entities without requiring entities to align to
    container-block boundaries. Each chunk's ``runs`` are the COVERING
    block ranges (a boundary block decodes for both neighbors — bounded
    extra decode, exact chunks); loads slice the decoded records to the
    range. An entity larger than the budget extends its chunk; unsorted
    input degrades to over-budget chunks rather than wrong solves
    (``StreamingGameProgram`` still verifies clustering per RE type).
    Returns (specs, per-file block indexes, per-chunk record starts,
    per-chunk leading-record skips into the first covering block).
    """
    if chunk_records <= 0:
        raise ValueError(f"chunk_records must be positive, got {chunk_records}")
    if indexes is None:
        indexes = [
            avro_io.scan_block_index(f, on_corrupt=on_corrupt) for f in files
        ]
    cluster_keys = np.asarray(cluster_keys).astype(str)
    total = sum(n for file_index in indexes for (n, _, _) in file_index)
    if len(cluster_keys) != total:
        raise ValueError(
            f"cluster_keys covers {len(cluster_keys)} records but the "
            f"block index holds {total}"
        )
    # "" (a record missing the id column) is a REAL vocab entity on the
    # decode path (np.unique of keys, the in-core build_game_dataset
    # rule), so "" runs cluster like any other entity — splitting them
    # freely would make the program's clustering verification reject an
    # input the in-core path trains fine
    splittable = np.ones(total + 1, dtype=bool)
    if total > 1:
        same = cluster_keys[1:] == cluster_keys[:-1]
        splittable[1:total] = ~same
    specs, starts, skips = _entity_chunks_over_blocks(
        files, indexes, chunk_records, splittable
    )
    return specs, indexes, starts, skips


def _entity_chunks_over_blocks(
    files: Sequence[str],
    indexes: "list[list[tuple[int, int, int]]]",
    chunk_records: int,
    splittable: np.ndarray,
):
    """The record-granular chunk loop shared by
    :func:`plan_entity_chunks_avro` (splittable mask from per-record
    cluster keys) and :func:`plan_partitioned_game_stream` (splittable
    mask reconstructed from the allgathered run-length encoding — never
    materializing [n] key strings). Returns (specs, starts, skips)."""
    blocks = [
        (fi, bi, file_index[bi][0])
        for fi, file_index in enumerate(indexes)
        for bi in range(len(file_index))
    ]
    if not blocks:
        raise ValueError("no Avro blocks to stream")
    total = sum(b[2] for b in blocks)
    if len(splittable) != total + 1:
        raise ValueError(
            f"boundary mask covers {len(splittable) - 1} records but the "
            f"block index holds {total}"
        )
    # global record offset at each block start
    block_starts = np.concatenate(
        [[0], np.cumsum([b[2] for b in blocks])]
    ).astype(np.int64)
    specs: list[ChunkSpec] = []
    starts: list[int] = []
    skips: list[int] = []
    pos = 0
    while pos < total:
        end = min(pos + chunk_records, total)
        while end < total and not splittable[end]:
            end += 1
        # covering blocks: those whose record ranges intersect [pos, end)
        first = int(np.searchsorted(block_starts, pos, side="right") - 1)
        last = int(np.searchsorted(block_starts, end, side="left") - 1)
        runs: list[tuple[str, int, int]] = []
        cover = [(blocks[i][0], blocks[i][1]) for i in range(first, last + 1)]
        for fi, group in itertools.groupby(cover, key=lambda b: b[0]):
            bis = [bi for _, bi in group]
            run_start = prev = bis[0]
            for bi in bis[1:] + [None]:
                if bi is None or bi != prev + 1:
                    runs.append((files[fi], run_start, prev - run_start + 1))
                    run_start = bi
                prev = bi if bi is not None else prev
        specs.append(
            ChunkSpec(index=len(specs), num_records=end - pos,
                      runs=tuple(runs))
        )
        starts.append(int(pos))
        skips.append(int(pos - block_starts[first]))
        pos = end
    return specs, starts, skips


class GameAvroChunkSource:
    """Streams GAME chunks from Avro container files, each chunk decoded
    through the SAME per-record assembly as the in-core read
    (io/data_reader.records_to_game_dataset with globally-agreed index
    maps and entity vocabs — label/response fallback, None offset/weight
    defaults, metadataMap id extraction), so a streamed epoch consumes
    the identical numbers the full read would build. Entity-clustered via
    :func:`plan_entity_chunks_avro` when ``cluster_by`` is given
    (reference AvroDataReader.scala never materializes the full input on
    one machine either; this is the single-host accelerator equivalent).
    """

    sparse = False

    def __init__(
        self,
        files: Sequence[str],
        shard_configs: "Mapping[str, object]",
        index_maps: "Mapping[str, object]",
        *,
        chunk_records: int,
        random_effect_id_columns: Sequence[str] = (),
        entity_vocabs: "Mapping[str, np.ndarray] | None" = None,
        cluster_by: str | None = None,
        cluster_keys: np.ndarray | None = None,
        indexes=None,
        on_corrupt: str = "raise",
        dtype=np.float32,
        chunk_plan=None,
    ):
        self.files = [str(f) for f in files]
        self.shard_configs = dict(shard_configs)
        self.index_maps = dict(index_maps)
        self.re_columns = tuple(random_effect_id_columns)
        self.entity_vocabs = dict(entity_vocabs or {})
        self.on_corrupt = on_corrupt
        self.dtype = dtype
        #: dynamic per-source decode evidence (bytes this rank decoded;
        #: io_counters stays process-global)
        self.bytes_decoded = 0
        if chunk_plan is not None:
            # a precomputed plan (plan_partitioned_game_stream's rank-local
            # slice of the exchange-agreed global plan): specs already
            # re-indexed 0..k-1, record starts in the rank's LOCAL row
            # universe, skips into each chunk's first covering block
            plan_specs, plan_starts, plan_skips = chunk_plan
            self.specs = list(plan_specs)
            self.record_starts = [int(s) for s in plan_starts]
            self._skips = [int(s) for s in plan_skips]
            self.indexes = (
                indexes if indexes is not None
                else [
                    avro_io.scan_block_index(f, on_corrupt=on_corrupt)
                    for f in self.files
                ]
            )
        elif cluster_by is not None:
            if cluster_keys is None:
                raise ValueError(
                    "cluster_by needs cluster_keys (the per-record entity "
                    "keys collected by scan_game_stream's vocab pass)"
                )
            self.specs, self.indexes, self.record_starts, self._skips = (
                plan_entity_chunks_avro(
                    self.files, chunk_records, cluster_keys,
                    indexes=indexes, on_corrupt=on_corrupt,
                )
            )
        else:
            self.specs, self.indexes = plan_chunks(
                self.files, chunk_records, on_corrupt=on_corrupt,
                indexes=indexes,
            )
            self.record_starts = list(
                np.concatenate(
                    [[0], np.cumsum([s.num_records for s in self.specs])[:-1]]
                ).astype(int)
            ) if self.specs else []
            self._skips = [0] * len(self.specs)
        self.cluster_by = cluster_by
        self.chunk_rows = max((s.num_records for s in self.specs), default=0)
        self.dims = {
            shard: int(self.index_maps[shard].size)
            for shard in self.shard_configs
        }
        self._file_pos = {f: i for i, f in enumerate(self.files)}

    @property
    def num_chunks(self) -> int:
        return len(self.specs)

    @property
    def total_records(self) -> int:
        return int(sum(s.num_records for s in self.specs))

    def load(self, spec: ChunkSpec) -> GameChunk:
        from photon_ml_tpu.io.data_reader import records_to_game_dataset

        records: list = []
        payload_bytes = 0
        for path, start, count in spec.runs:
            index = self.indexes[self._file_pos[path]]
            payload_bytes += sum(sz for _, sz, _ in index[start:start + count])
            records.extend(
                avro_io.read_container_block_range(
                    path, start, count, index=index,
                    on_corrupt=self.on_corrupt,
                )
            )
        io_counters.record_bytes_decoded(payload_bytes)
        self.bytes_decoded += payload_bytes
        # entity-clustered plans slice the covering blocks' records to the
        # chunk's exact record range (boundary blocks decode for both
        # neighbors)
        skip = self._skips[spec.index]
        records = records[skip:skip + spec.num_records]
        result = records_to_game_dataset(
            records, self.shard_configs, self.index_maps,
            random_effect_id_columns=self.re_columns,
            entity_vocabs=self.entity_vocabs,
            dtype=self.dtype,
        )
        ds = result.dataset
        n = spec.num_records
        pad = self.chunk_rows - n

        def pad1(a, fill=0):
            a = np.asarray(a)
            if pad:
                a = np.concatenate(
                    [a, np.full((pad,) + a.shape[1:], fill, a.dtype)]
                )
            return a

        start = self.record_starts[spec.index]
        rows = np.arange(start, start + n, dtype=np.int64)
        return GameChunk(
            features={
                k: pad1(ds.feature_shards[k]) for k in self.shard_configs
            },
            labels=pad1(ds.labels),
            offsets=pad1(ds.offsets),
            weights=pad1(ds.weights),
            entity_idx={
                t: pad1(ds.entity_idx[t], fill=-1) for t in self.re_columns
            },
            rows=pad1(rows, fill=-1),
            num_records=n,
        )


def scan_game_stream(
    files: Sequence[str],
    shard_configs: "Mapping[str, object]",
    random_effect_id_columns: Sequence[str],
    *,
    cluster_by: str | None = None,
    on_corrupt: str = "raise",
    dtype=np.float32,
):
    """One streaming pass over the input collecting everything a GAME
    chunk plan needs — records decoded and DISCARDED (memory stays
    O(vocabulary + [n] scalars), the out-of-core requirement):

    - global feature index maps (same keyset+sort rule as the full read,
      io/data_reader.build_index_maps),
    - entity vocabs per RE column (np.unique of observed keys — bitwise
      the in-core build_game_dataset rule),
    - per-record keys of the ``cluster_by`` column (the entity-clustered
      chunk planner's input), the per-file block indexes, and
    - the [n] per-sample SCALARS (labels/offsets/weights with the exact
      records_to_game_dataset defaults, plus per-RE-column entity
      indices into the vocabs) — so the streamed GAME program never has
      to re-decode the whole input just to collect them.

    Returns ``(index_maps, entity_vocabs, cluster_keys, indexes,
    scalars)``; ``scalars`` feeds ``StreamingGameProgram(scalars=...)``.
    """
    from photon_ml_tpu.io.data_reader import (
        META_DATA_MAP,
        OFFSET,
        RESPONSE,
        WEIGHT,
        build_index_maps,
    )

    indexes = [
        avro_io.scan_block_index(f, on_corrupt=on_corrupt) for f in files
    ]
    re_cols = tuple(random_effect_id_columns)
    keys: dict[str, list[str]] = {c: [] for c in re_cols}
    cluster: list[str] = []
    labels: list[float] = []
    offsets: list[float] = []
    weights: list[float] = []

    def records():
        for f in files:
            for record in avro_io.read_container(f, on_corrupt=on_corrupt):
                label = record.get("label", record.get(RESPONSE))
                if label is None:
                    raise ValueError(
                        "record has neither 'label' nor 'response'"
                    )
                labels.append(float(label))
                offset = record.get(OFFSET)
                offsets.append(0.0 if offset is None else float(offset))
                weight = record.get(WEIGHT)
                weights.append(1.0 if weight is None else float(weight))
                meta = record.get(META_DATA_MAP) or {}
                for c in re_cols:
                    value = meta.get(c, record.get(c))
                    keys[c].append("" if value is None else str(value))
                if cluster_by is not None:
                    value = meta.get(cluster_by, record.get(cluster_by))
                    cluster.append("" if value is None else str(value))
                yield record

    index_maps = build_index_maps(records(), shard_configs)
    vocabs = {c: np.unique(np.asarray(v).astype(str)) for c, v in keys.items()}
    cluster_keys = (
        np.asarray(cluster).astype(str) if cluster_by is not None else None
    )
    # vocab = np.unique(keys) is sorted with every key present, so
    # searchsorted IS the build_game_dataset index mapping
    entity_idx = {
        c: np.searchsorted(vocabs[c], np.asarray(v).astype(str)).astype(
            np.int32
        )
        for c, v in keys.items()
    }
    scalars = {
        "labels": np.asarray(labels, dtype=dtype),
        "offsets": np.asarray(offsets, dtype=dtype),
        "weights": np.asarray(weights, dtype=dtype),
        "entity_idx": entity_idx,
    }
    return index_maps, vocabs, cluster_keys, indexes, scalars


class DenseRecordAssembler:
    """TrainingExampleAvro record dicts -> one fixed-shape dense chunk.

    Mirrors ``io.data_reader.records_to_game_dataset``'s per-record
    semantics exactly (label/response fallback, None offset -> 0, None
    weight -> 1, name+term feature keys, duplicate (row, col) accumulation
    via np.add.at, intercept column) so a streamed epoch consumes the SAME
    numbers the in-core read would build — pinned by
    tests/test_streaming.py's bitwise chunk-identity test.
    """

    def __init__(self, index_map, shard_config, dtype=np.float32):
        self.index_map = index_map
        self.shard_config = shard_config
        self.dtype = dtype

    def __call__(self, records: list, chunk_rows: int) -> LabeledPointBatch:
        from photon_ml_tpu.io.data_reader import (
            OFFSET,
            RESPONSE,
            WEIGHT,
            _apply_intercept,
            _record_bags,
            _scatter_dense,
        )
        from photon_ml_tpu.io.index_map import feature_key

        n = len(records)
        labels = np.zeros((n,), np.float64)
        offsets = np.zeros((n,), np.float64)
        weights = np.ones((n,), np.float64)
        triples: list[tuple[int, int, float]] = []
        imap = self.index_map
        for i, record in enumerate(records):
            label = record.get("label", record.get(RESPONSE))
            if label is None:
                raise ValueError("record has neither 'label' nor 'response'")
            labels[i] = float(label)
            offset = record.get(OFFSET)
            offsets[i] = 0.0 if offset is None else float(offset)
            weight = record.get(WEIGHT)
            weights[i] = 1.0 if weight is None else float(weight)
            bags = _record_bags(record)
            for bag in self.shard_config.feature_bags:
                for feat in bags.get(bag, ()):
                    j = imap.get_index(
                        feature_key(feat["name"], feat.get("term") or "")
                    )
                    if j >= 0:
                        triples.append((i, j, float(feat["value"])))
        t = np.asarray(triples, dtype=np.float64) if triples else np.zeros((0, 3))
        x = _scatter_dense(n, imap.size, t[:, 0], t[:, 1], t[:, 2], self.dtype)
        if self.shard_config.has_intercept:
            _apply_intercept(x, imap, "features", {})
        return _pad_dense_chunk(
            x,
            labels.astype(self.dtype),
            offsets.astype(self.dtype),
            weights.astype(self.dtype),
            chunk_rows,
        )


def plan_chunks(
    files: Sequence[str],
    chunk_records: int,
    *,
    on_corrupt: str = "raise",
    indexes: "list[list[tuple[int, int, int]]] | None" = None,
    block_subset: "Sequence[tuple[int, int]] | None" = None,
) -> tuple[list[ChunkSpec], "list[list[tuple[int, int, int]]]"]:
    """Group contiguous container blocks into chunks of at most
    ``chunk_records`` records (a single over-budget block still forms its
    own chunk — blocks are the atomic decode unit). Costs one header
    decode + one seek per block (``avro.scan_block_index``), never a data
    read. ``block_subset``: optional (file_idx, block_idx) list — a rank's
    assignment from the partitioned planner; the epoch then streams only
    those blocks. Returns (specs, per-file block indexes) so loads skip
    the re-scan.
    """
    if chunk_records <= 0:
        raise ValueError(f"chunk_records must be positive, got {chunk_records}")
    if indexes is None:
        indexes = [
            avro_io.scan_block_index(f, on_corrupt=on_corrupt) for f in files
        ]
    if not any(len(ix) for ix in indexes):
        raise ValueError("no Avro blocks to stream")
    blocks = (
        list(block_subset)
        if block_subset is not None
        else [
            (fi, bi)
            for fi, file_index in enumerate(indexes)
            for bi in range(len(file_index))
        ]
    )
    specs: list[ChunkSpec] = []
    cur: list[tuple[int, int]] = []
    cur_records = 0

    def flush():
        nonlocal cur, cur_records
        if not cur:
            return
        runs: list[tuple[str, int, int]] = []
        for fi, group in itertools.groupby(cur, key=lambda b: b[0]):
            bis = [bi for _, bi in group]
            # split a file's blocks into contiguous runs (a gap — e.g. a
            # quarantined span or a partitioned subset — starts a new
            # seek range)
            run_start = prev = bis[0]
            for bi in bis[1:] + [None]:
                if bi is None or bi != prev + 1:
                    runs.append((files[fi], run_start, prev - run_start + 1))
                    run_start = bi
                prev = bi if bi is not None else prev
        specs.append(
            ChunkSpec(
                index=len(specs), num_records=cur_records, runs=tuple(runs)
            )
        )
        cur, cur_records = [], 0

    for fi, bi in blocks:
        n_rec = indexes[fi][bi][0]
        if cur and cur_records + n_rec > chunk_records:
            flush()
        cur.append((fi, bi))
        cur_records += n_rec
    flush()
    # an explicitly empty subset (a rank assigned no blocks) is a valid
    # zero-chunk plan — its epochs contribute zero to the cross-rank sum
    return specs, indexes


class AvroChunkSource(ChunkSource):
    """Streams chunks from Avro container files through a record
    assembler, decoding only each chunk's block ranges per load (the PR 2
    block planner's seek-to-payload reads)."""

    sparse = False

    def __init__(
        self,
        files: Sequence[str],
        assembler: Callable[[list, int], LabeledPointBatch],
        *,
        chunk_records: int,
        on_corrupt: str = "raise",
        indexes=None,
        block_subset=None,
        dim: int | None = None,
    ):
        self.files = [str(f) for f in files]
        self.assembler = assembler
        self.on_corrupt = on_corrupt
        self.specs, self.indexes = plan_chunks(
            self.files, chunk_records, on_corrupt=on_corrupt,
            indexes=indexes, block_subset=block_subset,
        )
        self.chunk_rows = max(
            (s.num_records for s in self.specs), default=0
        )
        if dim is not None:
            self.dim = int(dim)
        else:
            imap = getattr(assembler, "index_map", None)
            self.dim = int(imap.size) if imap is not None else 0
        self._file_pos = {f: i for i, f in enumerate(self.files)}

    def load(self, spec: ChunkSpec) -> LabeledPointBatch:
        records: list = []
        payload_bytes = 0
        for path, start, count in spec.runs:
            index = self.indexes[self._file_pos[path]]
            payload_bytes += sum(sz for _, sz, _ in index[start:start + count])
            records.extend(
                avro_io.read_container_block_range(
                    path, start, count, index=index,
                    on_corrupt=self.on_corrupt,
                )
            )
        io_counters.record_bytes_decoded(payload_bytes)
        return self.assembler(records, self.chunk_rows)


_END = object()


class ChunkPrefetcher:
    """One epoch's chunk iterator: double-buffered decode behind the
    consumer (prefetch=True) or inline (prefetch=False), with classified
    retry, bounded timeouts, and per-epoch overlap telemetry.

    Use as a context manager; iterating yields each chunk batch once, in
    plan order. ``close()`` (idempotent, called by ``__exit__``) stops the
    producer with a bounded join — abandoning an epoch mid-way (solver
    line-search rejection never does, but errors might) cannot leak a
    wedged thread.
    """

    def __init__(
        self,
        source: ChunkSource,
        *,
        prefetch: bool = True,
        depth: int = 1,
        retry_policy: RetryPolicy | None = None,
        chunk_timeout: float = DEFAULT_CHUNK_TIMEOUT,
    ):
        self.source = source
        self.prefetch = bool(prefetch)
        self.depth = max(1, int(depth))
        self.policy = retry_policy if retry_policy is not None else default_io_policy()
        self.chunk_timeout = float(chunk_timeout)
        self.decode_seconds = 0.0
        self.wait_seconds = 0.0
        self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- producer -------------------------------------------------------------

    def _load_timed(self, spec: ChunkSpec):
        t0 = time.perf_counter()
        # the decode span runs in whichever thread loads (producer when
        # prefetching, consumer inline otherwise) — per-thread trace
        # buffers keep both readable in the timeline
        with tracing.span("io/decode_chunk", cat="stream",
                          chunk=spec.index, records=spec.num_records):
            batch = self.policy.call(
                self.source.load, spec,
                description=f"decode chunk {spec.index}",
            )
        dt = time.perf_counter() - t0
        self.decode_seconds += dt
        stream_counters.record_chunk_decode_ms(dt * 1e3)
        return batch

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _producer(self) -> None:
        for spec in self.source.specs:
            if self._stop.is_set():
                return
            try:
                batch = self._load_timed(spec)
            except Exception as e:
                # the retry policy already classified and retried what was
                # transient; forward the surviving failure to the consumer's
                # stack — a thread cannot re-raise usefully, and swallowing
                # it would hang the epoch (reviewed allowlist entry in
                # dev/lint_parity.py check 5)
                classify_exception(e)
                try:
                    e._chunk_spec = spec
                except AttributeError:
                    pass  # __slots__ exception types lose the attribution
                self._put((None, e))
                return
            if not self._put((spec, batch)):
                return
        self._put((None, _END))

    # -- consumer -------------------------------------------------------------

    def __enter__(self) -> "ChunkPrefetcher":
        if self.prefetch:
            self._thread = threading.Thread(
                target=self._producer, name="chunk-prefetch", daemon=True
            )
            self._thread.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            # drain so a blocked put can finish, then bounded join
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=JOIN_TIMEOUT)
            self._thread = None

    def _next_prefetched(self):
        deadline = time.perf_counter() + self.chunk_timeout
        t0 = time.perf_counter()
        # consumer-side queue wait: the complement of io/decode_chunk in
        # the overlap story (overlap ≈ 1 - wait/decode, the
        # stream/overlap_fraction gauge — the spans reproduce it)
        with tracing.span("io/chunk_wait", cat="stream"):
            while True:
                try:
                    item = self._queue.get(timeout=0.2)
                    self.wait_seconds += time.perf_counter() - t0
                    return item
                except queue.Empty:
                    if self._thread is not None and not self._thread.is_alive():
                        raise StreamDecodeError(
                            "prefetch thread died without forwarding a result"
                        ) from None
                    if time.perf_counter() > deadline:
                        raise StreamDecodeError(
                            f"no chunk arrived within "
                            f"{self.chunk_timeout:.0f}s (wedged decode?)"
                        ) from None

    def __iter__(self):
        if not self.prefetch:
            for spec in self.source.specs:
                try:
                    yield self._load_timed(spec)
                except Exception as e:
                    raise self._attributed(e, spec) from e
            self._finish_epoch()
            return
        while True:
            spec, item = self._next_prefetched()
            if item is _END:
                break
            if isinstance(item, BaseException):
                failed = self._failed_spec(item)
                raise self._attributed(item, failed) from item
            yield item
        self._finish_epoch()

    def _failed_spec(self, exc) -> ChunkSpec | None:
        return getattr(exc, "_chunk_spec", None)

    def _attributed(self, exc, spec: ChunkSpec | None):
        where = (
            f"chunk {spec.index} (records={spec.num_records}, "
            f"runs={list(spec.runs)})" if spec is not None else "a chunk"
        )
        return StreamDecodeError(
            f"streaming epoch failed decoding {where}: "
            f"{type(exc).__name__}: {exc}"
        )

    def _finish_epoch(self) -> None:
        stream_counters.set_chunks_per_epoch(self.source.num_chunks)
        if self.prefetch and self.decode_seconds > 0.0:
            hidden = max(0.0, self.decode_seconds - self.wait_seconds)
            stream_counters.set_overlap_fraction(hidden / self.decode_seconds)
        else:
            stream_counters.set_overlap_fraction(0.0)


def build_streaming_index_maps(
    files: Sequence[str],
    shard_configs: Mapping[str, object],
    *,
    on_corrupt: str = "raise",
):
    """Global feature index maps from one streaming pass over the input —
    records are decoded and DISCARDED (memory stays O(vocabulary), the
    out-of-core requirement), exactly the keyset+sort rule the full read
    applies (io.data_reader.build_index_maps)."""
    from photon_ml_tpu.io.data_reader import build_index_maps

    return build_index_maps(
        itertools.chain.from_iterable(
            avro_io.read_container(f, on_corrupt=on_corrupt) for f in files
        ),
        shard_configs,
    )


def plan_partitioned_stream(
    path,
    shard_configs: Mapping[str, object],
    *,
    exchange,
    chunk_records: int,
    on_corrupt: str = "raise",
    dtype=np.float32,
    tag: str = "stream",
):
    """The --partitioned-io × --streaming-chunks composition: each rank
    gets a chunk source over ITS contiguous block assignment, with
    globally consistent index maps agreed over the metadata exchange —
    the same assignment rule (size-balanced contiguous block runs,
    ``partitioned_reader.assign_contiguous``) and the same
    key-union/sort map agreement the partitioned full read applies, so
    rank plans are verified identical by fingerprint and every rank's
    prefetcher decodes ~1/P of the bytes.

    The rank-local vocab pass decodes ONLY this rank's blocks (discarding
    records); ONE allgather unions the key sets. Dense feature shards
    (the GLM driver's layout). Returns
    ``(source, index_maps, intercept_indices)``; train with
    ``estimators.train_glm_streaming(source, ..., exchange=exchange)`` so
    the per-epoch accumulators sum across ranks in rank order.
    """
    from photon_ml_tpu.io.data_reader import build_index_maps
    from photon_ml_tpu.io.index_map import INTERCEPT_KEY, IndexMap
    from photon_ml_tpu.io.partitioned_reader import (
        _local_keys,
        _plan_fingerprint,
        assign_contiguous,
    )

    files = avro_io.list_avro_files(path)
    sizes = [int(os.path.getsize(f)) for f in files]
    io_counters.set_input_bytes_total(int(sum(sizes)))
    indexes = [
        avro_io.scan_block_index(f, on_corrupt=on_corrupt) for f in files
    ]
    blocks = [
        (fi, bi, payload)
        for fi, file_index in enumerate(indexes)
        for bi, (_, payload, _) in enumerate(file_index)
    ]
    if not blocks:
        raise ValueError(f"no Avro blocks under {path!r}")
    ranges = assign_contiguous([b[2] for b in blocks], exchange.num_ranks)
    lo, hi = ranges[exchange.rank]
    my_blocks = [(fi, bi) for fi, bi, _ in blocks[lo:hi]]

    def my_records():
        for spec_fi, group in itertools.groupby(my_blocks, key=lambda b: b[0]):
            bis = [bi for _, bi in group]
            yield from avro_io.read_container_block_range(
                files[spec_fi], bis[0], len(bis), index=indexes[spec_fi],
                on_corrupt=on_corrupt,
            )

    local_maps = build_index_maps(my_records(), shard_configs)
    payload = {
        "fingerprint": _plan_fingerprint(
            files, sizes, "stream-blocks", ranges
        ),
        "keys": {
            shard: _local_keys(local_maps[shard], cfg)
            for shard, cfg in shard_configs.items()
        },
    }
    with tracing.span("partitioned/stream_plan_exchange", cat="partitioned",
                      tag=tag, rank=exchange.rank):
        gathered = exchange.allgather(f"stream_plan/{tag}", payload)
    fingerprints = {g["fingerprint"] for g in gathered}
    if len(fingerprints) != 1:
        raise RuntimeError(
            f"ranks disagree on the streaming block plan ({fingerprints}); "
            "the input listing must be identical on every rank"
        )
    index_maps: dict[str, IndexMap] = {}
    intercepts: dict[str, int] = {}
    for shard, cfg in shard_configs.items():
        union: set[str] = set()
        for g in gathered:
            union.update(g["keys"][shard])
        imap = IndexMap.from_keys(union, add_intercept=cfg.has_intercept)
        index_maps[shard] = imap
        if cfg.has_intercept:
            ii = imap.get_index(INTERCEPT_KEY)
            if ii >= 0:
                intercepts[shard] = ii
    shard = next(iter(shard_configs))
    source = AvroChunkSource(
        files,
        DenseRecordAssembler(index_maps[shard], shard_configs[shard], dtype),
        chunk_records=chunk_records,
        on_corrupt=on_corrupt,
        indexes=indexes,
        block_subset=my_blocks,
    )
    return source, index_maps, intercepts


@dataclasses.dataclass(frozen=True)
class GameStreamPartition:
    """The exchange-agreed multi-rank streamed-GAME plan: every field is
    IDENTICAL on every rank (a deterministic function of the allgathered
    payloads), so per-rank programs can fingerprint checkpoints, drive one
    global DuHL schedule, and map global chunk ids to their local slice
    without further coordination.

    ``chunk_ranges[rank]`` is the rank's [lo, hi) slice of GLOBAL chunk
    ids (whole chunks — hence whole entities — per rank);
    ``payload_bytes[rank]`` is the deduped covering-block payload a full
    pass over that slice decodes (the per-rank I/O evidence: strictly
    less than ``input_bytes`` whenever the plan actually partitions).
    """

    rank: int
    num_ranks: int
    num_chunks: int
    chunk_ranges: "tuple[tuple[int, int], ...]"
    chunk_rows: int
    total_records: int
    payload_bytes: "tuple[int, ...]"
    input_bytes: int
    fingerprint: str

    def chunk_range(self) -> "tuple[int, int]":
        return self.chunk_ranges[self.rank]


def plan_partitioned_game_stream(
    path,
    shard_configs: Mapping[str, object],
    random_effect_id_columns: Sequence[str],
    *,
    exchange,
    chunk_records: int,
    cluster_by: str,
    schedule_budget: "Mapping[str, object] | None" = None,
    on_corrupt: str = "raise",
    dtype=np.float32,
    tag: str = "stream_game",
):
    """The --partitioned-io × --streaming-chunks composition for GAME
    (ISSUE 17): entity-granular per-rank chunk assignments agreed over the
    metadata exchange, so one streamed-GAME job spans the fleet's disks.

    Each rank decodes ONLY a provisional contiguous block slice
    (``assign_contiguous`` over payload sizes, the PR 6 rule) collecting
    its feature keys, RE entity keys, and a run-length encoding of the
    ``cluster_by`` column — O(vocabulary + entities) metadata, never the
    [n] sample axis. ONE allgather unions the key sets and concatenates
    the cluster runs in rank order (boundary runs of the same entity
    merge), after which every rank deterministically rebuilds the SAME
    global entity-clustered chunk plan (:func:`plan_entity_chunks_avro`
    semantics, reconstructed from run boundaries) and assigns WHOLE
    chunks — hence whole entities — contiguously to ranks. The agreed
    plan fields (input fingerprint, chunk budget, cluster column, rank
    geometry, schedule budget) are compared FIELD-WISE across ranks; any
    disagreement fails fast naming the differing fields and their
    per-rank values — a run never trains on a silently-disagreed plan.

    Returns ``(source, index_maps, entity_vocabs, partition)``: a
    rank-local :class:`GameAvroChunkSource` over this rank's chunks (rows
    renumbered into the rank's LOCAL universe — the streamed program's
    scalars stay O(n_rank)), the globally-agreed feature index maps and
    entity vocabs, and the :class:`GameStreamPartition` every rank agrees
    on. Feed all four to ``StreamingGameProgram(..., exchange=exchange,
    partition=partition, num_entities={t: len(vocabs[t])})``.
    """
    from photon_ml_tpu.io.data_reader import META_DATA_MAP, build_index_maps
    from photon_ml_tpu.io.index_map import IndexMap
    from photon_ml_tpu.io.partitioned_reader import (
        _local_keys,
        _plan_fingerprint,
        assign_contiguous,
    )

    if cluster_by is None:
        raise ValueError(
            "plan_partitioned_game_stream needs cluster_by (the RE type "
            "whose entities define chunk grouping) — a multi-rank streamed "
            "GAME run without entity clustering would split entities "
            "across ranks"
        )
    re_cols = tuple(random_effect_id_columns)
    files = avro_io.list_avro_files(path)
    sizes = [int(os.path.getsize(f)) for f in files]
    io_counters.set_input_bytes_total(int(sum(sizes)))
    indexes = [
        avro_io.scan_block_index(f, on_corrupt=on_corrupt) for f in files
    ]
    blocks = [
        (fi, bi, payload)
        for fi, file_index in enumerate(indexes)
        for bi, (_, payload, _) in enumerate(file_index)
    ]
    if not blocks:
        raise ValueError(f"no Avro blocks under {path!r}")
    ranges = assign_contiguous([b[2] for b in blocks], exchange.num_ranks)
    lo, hi = ranges[exchange.rank]
    my_blocks = [(fi, bi) for fi, bi, _ in blocks[lo:hi]]

    re_keys: "dict[str, set]" = {c: set() for c in re_cols}
    cluster_runs: "list[list]" = []  # [key, count] run-length pairs
    scan_bytes = 0

    def my_records():
        for spec_fi, group in itertools.groupby(my_blocks, key=lambda b: b[0]):
            bis = [bi for _, bi in group]
            for record in avro_io.read_container_block_range(
                files[spec_fi], bis[0], len(bis), index=indexes[spec_fi],
                on_corrupt=on_corrupt,
            ):
                meta = record.get(META_DATA_MAP) or {}
                for c in re_cols:
                    value = meta.get(c, record.get(c))
                    re_keys[c].add("" if value is None else str(value))
                value = meta.get(cluster_by, record.get(cluster_by))
                key = "" if value is None else str(value)
                if cluster_runs and cluster_runs[-1][0] == key:
                    cluster_runs[-1][1] += 1
                else:
                    cluster_runs.append([key, 1])
                yield record

    local_maps = build_index_maps(my_records(), shard_configs)
    scan_bytes = sum(
        indexes[fi][bi][1] for fi, bi in my_blocks
    )
    budget = (
        None if schedule_budget is None
        else {k: schedule_budget[k] for k in sorted(schedule_budget)}
    )
    plan_fields = {
        "input": _plan_fingerprint(files, sizes, "stream-game-blocks",
                                   ranges),
        "chunk_records": int(chunk_records),
        "cluster_by": str(cluster_by),
        "re_columns": list(re_cols),
        "num_ranks": int(exchange.num_ranks),
        "schedule": budget,
    }
    payload = {
        "plan": plan_fields,
        "keys": {
            shard: _local_keys(local_maps[shard], cfg)
            for shard, cfg in shard_configs.items()
        },
        "entities": {c: sorted(re_keys[c]) for c in re_cols},
        "cluster_runs": cluster_runs,
    }
    with tracing.span("partitioned/game_stream_plan_exchange",
                      cat="partitioned", tag=tag, rank=exchange.rank):
        gathered = exchange.allgather(f"stream_game_plan/{tag}", payload)
    diffs = []
    fields = sorted(set().union(*[set(g["plan"]) for g in gathered]))
    for field in fields:
        values = [g["plan"].get(field) for g in gathered]
        if any(v != values[0] for v in values[1:]):
            diffs.append(
                f"{field}: " + ", ".join(
                    f"rank{r}={v!r}" for r, v in enumerate(values)
                )
            )
    if diffs:
        raise RuntimeError(
            "ranks disagree on the partitioned GAME stream plan — refusing "
            "to train on a silently-disagreed plan; differing fields: "
            + "; ".join(diffs)
        )

    index_maps: "dict[str, IndexMap]" = {}
    for shard, cfg in shard_configs.items():
        union: "set[str]" = set()
        for g in gathered:
            union.update(g["keys"][shard])
        index_maps[shard] = IndexMap.from_keys(
            union, add_intercept=cfg.has_intercept
        )
    vocabs = {
        c: np.unique(
            np.asarray(
                sorted(set().union(*[set(g["entities"][c]) for g in gathered]))
            ).astype(str)
        )
        for c in re_cols
    }

    # global cluster runs: rank-order concatenation, merging boundary runs
    # of the same entity (an entity spanning a provisional block boundary
    # must still land in ONE chunk)
    run_keys: "list[str]" = []
    run_counts: "list[int]" = []
    for g in gathered:
        for key, count in g["cluster_runs"]:
            if run_keys and run_keys[-1] == key:
                run_counts[-1] += int(count)
            else:
                run_keys.append(key)
                run_counts.append(int(count))
    total = int(sum(run_counts))
    index_total = sum(n for file_index in indexes for (n, _, _) in file_index)
    if total != index_total:
        raise RuntimeError(
            f"rank-local scans cover {total} records but the block index "
            f"holds {index_total} — the input changed between the block "
            "scan and the key scan; re-run against a quiesced input"
        )
    splittable = np.zeros(total + 1, dtype=bool)
    splittable[0] = True
    splittable[total] = True
    if run_counts:
        ends = np.cumsum(np.asarray(run_counts, dtype=np.int64))
        splittable[ends[:-1]] = True
    if chunk_records <= 0:
        raise ValueError(f"chunk_records must be positive, got {chunk_records}")
    specs, starts, skips = _entity_chunks_over_blocks(
        files, indexes, chunk_records, splittable
    )
    chunk_ranges = assign_contiguous(
        [s.num_records for s in specs], exchange.num_ranks
    )
    empty = [r for r, (clo, chi) in enumerate(chunk_ranges) if chi <= clo]
    if empty:
        raise ValueError(
            f"the entity-clustered plan has {len(specs)} chunks for "
            f"{exchange.num_ranks} ranks — ranks {empty} would stream "
            "nothing; use a smaller --streaming-chunks budget (more "
            "chunks) or fewer ranks"
        )

    file_pos = {f: i for i, f in enumerate(files)}

    def rank_payload(clo: int, chi: int) -> int:
        cover: "set[tuple[int, int]]" = set()
        for s in specs[clo:chi]:
            for run_path, start, count in s.runs:
                fi = file_pos[run_path]
                cover.update((fi, bi) for bi in range(start, start + count))
        return int(sum(indexes[fi][bi][1] for fi, bi in cover))

    payload_bytes = tuple(rank_payload(clo, chi) for clo, chi in chunk_ranges)
    fingerprint = hashlib.sha256(
        json.dumps(
            [plan_fields, starts, [list(r) for r in chunk_ranges]],
            sort_keys=True,
        ).encode()
    ).hexdigest()[:16]
    partition = GameStreamPartition(
        rank=int(exchange.rank),
        num_ranks=int(exchange.num_ranks),
        num_chunks=len(specs),
        chunk_ranges=tuple((int(a), int(b)) for a, b in chunk_ranges),
        chunk_rows=max(s.num_records for s in specs),
        total_records=total,
        payload_bytes=payload_bytes,
        input_bytes=int(sum(sizes)),
        fingerprint=fingerprint,
    )
    clo, chi = chunk_ranges[exchange.rank]
    local_specs = [
        dataclasses.replace(s, index=i)
        for i, s in enumerate(specs[clo:chi])
    ]
    base = starts[clo]
    local_starts = [starts[c] - base for c in range(clo, chi)]
    local_skips = [skips[c] for c in range(clo, chi)]
    source = GameAvroChunkSource(
        files, shard_configs, index_maps,
        chunk_records=chunk_records,
        random_effect_id_columns=re_cols,
        entity_vocabs=vocabs,
        cluster_by=cluster_by,
        indexes=indexes,
        on_corrupt=on_corrupt,
        dtype=dtype,
        chunk_plan=(local_specs, local_starts, local_skips),
    )
    source.scan_bytes = scan_bytes
    return source, index_maps, vocabs, partition
