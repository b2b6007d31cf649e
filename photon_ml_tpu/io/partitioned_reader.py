"""Partitioned host ingestion: each rank decodes ~1/P of the input bytes.

Reference parity: photon-client data/avro/AvroDataReader.scala:125-200 —
the reference reads Avro per PARTITION on executors (Spark hands each task
a split of the input files/blocks) and assembles per-partition rows; only
feature-index metadata is shared via the driver. The host periphery here
was the last full-read funnel: both CLI drivers called ``read_merged`` on
EVERY process and only then sharded, so a multi-host run multiplied the
full-input decode by the process count (at the measured ~54 MB/s native
rate, a 1 TB input costs hours *per rank* before step 1 — BASELINE.md).

This module gives each rank a deterministic, order-preserving slice:

- **Assignment**: the sorted part files split into P contiguous,
  size-balanced runs (every rank computes the identical plan from the
  identical listing; a fingerprint allgather verifies it). Inputs with
  fewer files than ranks split by container *blocks* instead — the block
  index costs one header decode + one seek per block to scan
  (avro.scan_block_index), never a data read.
- **Decode**: only the local assignment flows through the existing
  native/Python reader stack (``read_merged`` on the file subset, or the
  block-range record iterator) — the ~13x native columnar decoder keeps
  working per rank.
- **Consistency**: feature index maps and entity vocabularies are made
  globally consistent by ONE small metadata allgather (distinct feature
  keys; entity ids + counts) over the host-side coordination-service
  channel (parallel/multihost.MetadataExchange) — not by re-reading
  everything everywhere. ``IndexMap.from_keys`` sorts, so the union of
  per-rank key sets reproduces the full-read map exactly; local column
  indices are then remapped into the global space (a cheap column
  scatter of the already-assembled local blocks).
- **Layout**: every rank pads its local rows to the agreed common block
  length (zero-weight rows, the framework-wide padding contract), so the
  global sample axis is P equal blocks and each rank's block places
  directly as the local addressable shards of the global sharded arrays
  (parallel/multihost.assemble_partitioned).

Single-process (num_ranks == 1) delegates to ``read_merged`` unchanged —
this module is the ONE ingestion dispatcher the CLI drivers call
(dev/lint_parity.py bans direct ``read_merged`` calls in cli/).

Per-rank decode progress is observable: the ``io/partitioned/*`` telemetry
counters record bytes decoded vs the total input (telemetry/io_counters).
"""

from __future__ import annotations

import base64
import dataclasses
import hashlib
import itertools
import json
import logging
import os
from typing import Mapping, Sequence

import numpy as np

from photon_ml_tpu.data.game_data import GameDataset, pad_game_dataset_to
from photon_ml_tpu.data.sparse_batch import SparseShard
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    ReadResult,
    build_index_maps,
    read_merged,
    records_to_game_dataset,
)
from photon_ml_tpu.io.index_map import INTERCEPT_KEY, IndexMap
from photon_ml_tpu.telemetry import io_counters, tracing

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class PartitionInfo:
    """Rank geometry of one partitioned read: the global sample axis is
    ``num_ranks`` blocks of ``block_rows`` rows; rank r's true rows are
    the first ``local_rows[r]`` of block r, the rest zero-weight padding."""

    rank: int
    num_ranks: int
    local_rows: tuple[int, ...]
    block_rows: int

    @property
    def global_rows(self) -> int:
        return self.num_ranks * self.block_rows

    @property
    def total_true_rows(self) -> int:
        return int(sum(self.local_rows))

    @property
    def base_row(self) -> int:
        return self.rank * self.block_rows

    @property
    def local_n(self) -> int:
        return int(self.local_rows[self.rank])

    def true_row_mask(self) -> np.ndarray:
        """[global_rows] bool: True on real rows, False on block padding."""
        mask = np.zeros(self.global_rows, dtype=bool)
        for r, n in enumerate(self.local_rows):
            mask[r * self.block_rows: r * self.block_rows + n] = True
        return mask


@dataclasses.dataclass
class PartitionedReadResult:
    """One rank's slice of a partitioned read.

    result: the LOCAL dataset (padded to ``partition.block_rows``) with
        GLOBALLY consistent index maps / entity vocabs / intercepts.
    entity_rank_presence: RE type -> [num_entities] int — on how many
        ranks each entity has samples. Entities spanning ranks make the
        rank-local random-effect view deviate from the full-read solve
        (data/game_data.build_random_effect_dataset_partitioned documents
        the semantics); entity-clustered inputs keep this at <= 1.
    """

    result: ReadResult
    partition: PartitionInfo
    mode: str  # "single" | "files" | "blocks"
    local_files: list[str]
    bytes_decoded: int
    input_bytes_total: int
    entity_rank_presence: dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict
    )


def assign_contiguous(weights: Sequence[int], num_ranks: int) -> list[tuple[int, int]]:
    """Split items into ``num_ranks`` contiguous [lo, hi) runs with
    near-equal total weight: boundary r lands where the prefix sum first
    reaches r/P of the total. Contiguity is semantic, not cosmetic — it
    keeps the concatenation of rank slices in the full-read row order, so
    the partitioned global sample axis is a padded permutation-free image
    of the full read's."""
    weights = [max(int(w), 0) for w in weights]
    prefix = np.concatenate([[0], np.cumsum(weights, dtype=np.int64)])
    total = int(prefix[-1])
    bounds = [0]
    for r in range(1, num_ranks):
        target = total * r / num_ranks
        idx = int(np.searchsorted(prefix, target, side="left"))
        # boundary at whichever adjacent prefix sits closer to the target
        if idx > 0 and (
            idx > len(weights)
            or target - prefix[idx - 1] <= prefix[min(idx, len(weights))] - target
        ):
            idx -= 1
        bounds.append(min(max(idx, bounds[-1]), len(weights)))
    bounds.append(len(weights))
    return [(bounds[r], bounds[r + 1]) for r in range(num_ranks)]


def _list_input_files(path, fmt: str) -> list[str]:
    paths = [path] if isinstance(path, (str, os.PathLike)) else list(path)
    if fmt == "avro":
        files: list[str] = []
        for p in paths:
            files += avro_io.list_avro_files(p)
        return files
    raise ValueError(
        f"partitioned ingestion supports fmt='avro' (got {fmt!r}); "
        "LibSVM inputs read through the single-process path"
    )


def _local_keys(imap: IndexMap, cfg: FeatureShardConfiguration) -> list[str]:
    """The DATA feature keys of a locally built map: the synthetic
    intercept is stripped (each rank's map appends it; the global rebuild
    re-adds it once, reproducing the full-read map). A literal
    '(INTERCEPT)' feature key in the data is indistinguishable from the
    synthetic one here — that pathological case may order the intercept
    column differently from a full read."""
    keys = list(imap)
    if cfg.has_intercept:
        keys = [k for k in keys if k != INTERCEPT_KEY]
    return keys


def _remap_dense(x: np.ndarray, local_map: IndexMap,
                 global_map: IndexMap) -> np.ndarray:
    out = np.zeros((x.shape[0], global_map.size), dtype=x.dtype)
    if local_map.size:
        gidx = np.asarray(
            [global_map.get_index(local_map.get_feature_name(j))
             for j in range(local_map.size)],
            dtype=np.int64,
        )
        if (gidx < 0).any():
            raise ValueError("local feature key missing from the global map")
        out[:, gidx] = np.asarray(x)
    return out


def _remap_sparse(shard: SparseShard, local_map: IndexMap,
                  global_map: IndexMap) -> SparseShard:
    gidx = np.asarray(
        [global_map.get_index(local_map.get_feature_name(j))
         for j in range(local_map.size)],
        dtype=np.int64,
    )
    cols = np.asarray(shard.cols, dtype=np.int64)
    new_cols = gidx[cols] if len(cols) else cols
    return dataclasses.replace(
        shard, cols=new_cols, feature_dim=global_map.size,
        _device=None, _coalesced=None, _hybrid_cache=None,
    )


def _schema_lacks_uid(files: list[str]) -> bool:
    """True when the input records carry no uid field at all — the reader
    then auto-assigns ROW NUMBERS as unique ids, which are rank-local in a
    partitioned read and must be shifted to the global row space (the full
    read numbers 0..N-1; stable-id sampling and score-output uids depend
    on it). Decided from the FIRST file's schema so every rank agrees.
    A uid field that exists but holds null for some rows still falls back
    to local row numbers for those rows — a documented edge the metadata
    exchange cannot see; give such data real uids."""
    if not files:
        return False
    try:
        schema = avro_io.read_container_schema(files[0])
    except (avro_io.AvroError, OSError):
        return False
    fields = schema.get("fields", []) if isinstance(schema, dict) else []
    from photon_ml_tpu.io.data_reader import UID

    return not any(f.get("name") == UID for f in fields)


def _plan_fingerprint(files: list[str], sizes: list[int], mode: str,
                      ranges) -> str:
    blob = json.dumps(
        [[os.path.basename(f) for f in files], sizes, mode, list(ranges)]
    ).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def read_partitioned(
    path,
    shard_configs: Mapping[str, FeatureShardConfiguration],
    *,
    exchange=None,
    index_maps: Mapping[str, IndexMap] | None = None,
    random_effect_id_columns: Sequence[str] = (),
    evaluation_id_columns: Sequence[str] = (),
    entity_vocabs: Mapping[str, np.ndarray] | None = None,
    fmt: str = "avro",
    dtype=np.float32,
    pad_multiple: int = 1,
    tag: str = "read",
    on_corrupt: str = "raise",
) -> PartitionedReadResult:
    """Partition-aware ``read_merged``: decode only this rank's slice.

    on_corrupt="quarantine" (Avro only) skips-and-counts corrupt container
    blocks instead of failing the read (io/avro.py). In blocks mode the
    quarantining INDEX scan is the authoritative skip decision — every
    rank scans every file identically, so the plan (and its fingerprint)
    stays rank-consistent with corrupt spans excluded.

    exchange: parallel/multihost.MetadataExchange. ``None`` means DO NOT
    partition — the full read on this process, exactly as before (the
    drivers' non---partitioned-io paths and every single-process caller
    ride this default; partitioning is opt-in, so it must never engage
    just because the process happens to be in a multi-process run). Pass
    ``multihost.default_exchange()`` (or a specific transport) to
    partition. Every rank must then call with identical arguments — the
    metadata allgathers are collective. ``pad_multiple``: round the common
    per-rank block length up to this (callers pass the per-rank device
    count along the mesh "data" axis so device shards never cross rank
    blocks). ``tag`` namespaces the exchanges when one run reads several
    inputs (train/validation).

    num_ranks == 1 delegates to ``read_merged`` byte-for-byte — this is
    the one ingestion entry point CLI drivers use.
    """
    if exchange is None:
        from photon_ml_tpu.parallel.multihost import SingleProcessExchange

        exchange = SingleProcessExchange()
    rank, num_ranks = exchange.rank, exchange.num_ranks

    if num_ranks == 1:
        result = read_merged(
            path, shard_configs, index_maps=index_maps,
            random_effect_id_columns=random_effect_id_columns,
            evaluation_id_columns=evaluation_id_columns,
            entity_vocabs=entity_vocabs, fmt=fmt, dtype=dtype,
            on_corrupt=on_corrupt,
        )
        n = result.dataset.num_samples
        return PartitionedReadResult(
            result=result,
            partition=PartitionInfo(0, 1, (n,), n),
            mode="single",
            local_files=[],
            bytes_decoded=0,
            input_bytes_total=0,
        )

    files = _list_input_files(path, fmt)
    sizes = [os.path.getsize(f) for f in files]
    input_total = int(sum(sizes))
    io_counters.set_input_bytes_total(input_total)

    if len(files) >= num_ranks:
        mode = "files"
        ranges = assign_contiguous(sizes, num_ranks)
        lo, hi = ranges[rank]
        local_files = files[lo:hi]
        bytes_decoded = int(sum(sizes[lo:hi]))
        local = _read_local_files(
            local_files, shard_configs,
            index_maps=index_maps,
            random_effect_id_columns=random_effect_id_columns,
            evaluation_id_columns=evaluation_id_columns,
            entity_vocabs=entity_vocabs, fmt=fmt, dtype=dtype,
            on_corrupt=on_corrupt,
        )
    else:
        mode = "blocks"
        # few-large-files: split by container blocks. The index scan is
        # header + seeks only; every rank scans every file's index (cheap)
        # but decodes only its contiguous block run. Under quarantine the
        # scan validates framing and drops corrupt spans identically on
        # every rank (the plan fingerprint stays consistent).
        indexes = [
            avro_io.scan_block_index(f, on_corrupt=on_corrupt)
            for f in files
        ]
        blocks = []  # (file_idx, block_idx, payload_bytes)
        for fi, file_index in enumerate(indexes):
            for bi, (_, payload, _) in enumerate(file_index):
                blocks.append((fi, bi, payload))
        if not blocks:
            raise ValueError(f"no Avro blocks under {path!r}")
        ranges = assign_contiguous([b[2] for b in blocks], num_ranks)
        lo, hi = ranges[rank]
        my_blocks = blocks[lo:hi]
        bytes_decoded = int(sum(b[2] for b in my_blocks))
        local_files = sorted({files[b[0]] for b in my_blocks})

        def local_records():
            for fi, group in itertools.groupby(my_blocks, key=lambda b: b[0]):
                run = list(group)
                yield from avro_io.read_container_block_range(
                    files[fi], run[0][1], len(run), index=indexes[fi],
                    on_corrupt=on_corrupt,
                )

        local = _read_local_records(
            list(local_records()), shard_configs,
            index_maps=index_maps,
            random_effect_id_columns=random_effect_id_columns,
            evaluation_id_columns=evaluation_id_columns,
            entity_vocabs=entity_vocabs, dtype=dtype,
        )
    io_counters.record_bytes_decoded(bytes_decoded)

    # ---- ONE metadata allgather: plan fingerprint, row counts, feature
    # keys (when maps were built locally), entity ids + counts. SCALE
    # NOTE: this channel is for metadata — distinct feature keys and
    # entity ids, not sample data. When the caller already provides the
    # entity vocabularies (scoring against a trained model), only the
    # per-entity COUNT vectors ride the exchange (no id strings).
    local_n = local.dataset.num_samples
    payload = {
        "fingerprint": _plan_fingerprint(files, sizes, mode, ranges),
        "n": local_n,
    }
    if index_maps is None:
        payload["keys"] = {
            shard: _local_keys(local.index_maps[shard], cfg)
            for shard, cfg in shard_configs.items()
            if not cfg.pre_indexed
        }
    vocab_counts = {}
    for t in random_effect_id_columns:
        vocab = np.asarray(local.dataset.entity_vocabs[t]).astype(str)
        idx = np.asarray(local.dataset.host_array(f"entity_idx/{t}"))
        counts = (
            np.bincount(idx[idx >= 0], minlength=len(vocab))
            if len(vocab) else np.zeros(0, np.int64)
        )
        if entity_vocabs is not None and t in entity_vocabs:
            # the vocab is shared knowledge; counts align to it already
            vocab_counts[t] = (None, counts.astype(int).tolist())
        else:
            vocab_counts[t] = (vocab.tolist(), counts.astype(int).tolist())
    payload["entities"] = vocab_counts

    # named layout-agreement span around the metadata allgather (the
    # exchange's own span records the wait; this one names the seam)
    with tracing.span("partitioned/metadata_exchange", cat="partitioned",
                      tag=tag, rank=exchange.rank):
        gathered = exchange.allgather(f"partitioned_read/{tag}", payload)

    fingerprints = {g["fingerprint"] for g in gathered}
    if len(fingerprints) != 1:
        raise RuntimeError(
            f"ranks disagree on the partition plan ({fingerprints}); the "
            "input listing must be identical on every rank"
        )
    local_rows = tuple(int(g["n"]) for g in gathered)
    if sum(local_rows) == 0:
        raise ValueError(f"no samples decoded from {path!r} on any rank")
    block_rows = -(-max(max(local_rows), 1) // pad_multiple) * pad_multiple

    # ---- globally consistent index maps (+ column remap of local blocks)
    result = local
    if index_maps is None:
        global_maps: dict[str, IndexMap] = {}
        for shard, cfg in shard_configs.items():
            if cfg.pre_indexed:
                global_maps[shard] = local.index_maps[shard]
                continue
            union: set[str] = set()
            for g in gathered:
                union.update(g["keys"][shard])
            global_maps[shard] = IndexMap.from_keys(
                union, add_intercept=cfg.has_intercept
            )
        result = _remap_to_global_maps(local, shard_configs, global_maps)

    # ---- globally consistent entity vocabs (+ entity index remap)
    presence: dict[str, np.ndarray] = {}
    if random_effect_id_columns:
        result, presence = _remap_to_global_vocabs(
            result, random_effect_id_columns, gathered,
            provided_vocabs=entity_vocabs,
        )

    # ---- globally consistent sparse layout decisions (hybrid hot head,
    # ELL width): layout statistics are GLOBAL, a rank's 1/P block must
    # never elect its own (arXiv:2004.02414's per-partition-statistics-vs-
    # global-solution pitfall, solved the same way the vocabs were)
    result = _resolve_global_sparse_layout(result, exchange, tag,
                                           pad_multiple=pad_multiple)

    # ---- uid-less inputs: shift the reader's auto-assigned row-number
    # uids into the global row space (the full read numbers 0..N-1)
    if _schema_lacks_uid(files):
        base = int(sum(local_rows[:rank]))
        if base:
            ds = result.dataset
            result = dataclasses.replace(
                result,
                dataset=dataclasses.replace(
                    ds, unique_ids=np.asarray(ds.unique_ids) + base
                ),
            )

    # ---- pad the local block to the agreed common length
    padded, _ = pad_game_dataset_to(result.dataset, block_rows)
    result = dataclasses.replace(result, dataset=padded)

    partition = PartitionInfo(rank, num_ranks, local_rows, block_rows)
    logger.info(
        "partitioned read rank %d/%d (%s mode): %d rows (block %d), "
        "%d/%d bytes decoded",
        rank, num_ranks, mode, local_n, block_rows, bytes_decoded,
        input_total,
    )
    return PartitionedReadResult(
        result=result,
        partition=partition,
        mode=mode,
        local_files=local_files,
        bytes_decoded=bytes_decoded,
        input_bytes_total=input_total,
        entity_rank_presence=presence,
    )


def _read_local_files(
    local_files, shard_configs, *, index_maps, random_effect_id_columns,
    evaluation_id_columns, entity_vocabs, fmt, dtype, on_corrupt="raise",
) -> ReadResult:
    if local_files:
        return read_merged(
            local_files, shard_configs, index_maps=index_maps,
            random_effect_id_columns=random_effect_id_columns,
            evaluation_id_columns=evaluation_id_columns,
            entity_vocabs=entity_vocabs, fmt=fmt, dtype=dtype,
            on_corrupt=on_corrupt,
        )
    return _read_local_records(
        [], shard_configs, index_maps=index_maps,
        random_effect_id_columns=random_effect_id_columns,
        evaluation_id_columns=evaluation_id_columns,
        entity_vocabs=entity_vocabs, dtype=dtype,
    )


def _read_local_records(
    records: list, shard_configs, *, index_maps, random_effect_id_columns,
    evaluation_id_columns, entity_vocabs, dtype,
) -> ReadResult:
    maps = index_maps or build_index_maps(records, shard_configs)
    result = records_to_game_dataset(
        records, shard_configs, maps,
        random_effect_id_columns=random_effect_id_columns,
        evaluation_id_columns=evaluation_id_columns,
        entity_vocabs=entity_vocabs, dtype=dtype,
    )
    result.decode_path = "avro-python"  # block-range records decode in Python
    return result


def _remap_to_global_maps(
    local: ReadResult,
    shard_configs: Mapping[str, FeatureShardConfiguration],
    global_maps: Mapping[str, IndexMap],
) -> ReadResult:
    """Move the local dataset's feature columns into the global index
    space: a column scatter per dense shard, a column relabel per sparse
    shard. O(n * d) numpy on 1/P of the rows — negligible next to decode."""
    ds = local.dataset
    new_shards: dict[str, object] = {}
    host_cache = dict(ds.host_cache)
    intercepts: dict[str, int] = {}
    for shard, cfg in shard_configs.items():
        lmap, gmap = local.index_maps[shard], global_maps[shard]
        value = ds.feature_shards[shard]
        if cfg.pre_indexed or lmap is gmap:
            new_shards[shard] = value
        elif isinstance(value, SparseShard):
            new_shards[shard] = _remap_sparse(value, lmap, gmap)
            host_cache.pop(f"shard/{shard}", None)
        else:
            remapped = _remap_dense(
                ds.host_array(f"shard/{shard}"), lmap, gmap
            )
            new_shards[shard] = remapped
            host_cache[f"shard/{shard}"] = remapped
        if cfg.has_intercept:
            ii = gmap.get_index(INTERCEPT_KEY)
            if ii >= 0:
                intercepts[shard] = ii
    return dataclasses.replace(
        local,
        dataset=dataclasses.replace(
            ds, feature_shards=new_shards, host_cache=host_cache
        ),
        index_maps=dict(global_maps),
        intercept_indices=intercepts,
    )


def _pack_i64(a: np.ndarray) -> str:
    """int64 array -> base64 string for the JSON exchange payloads: the
    hot-ranking histograms carry one entry per distinct column a rank
    observed (millions at giant d), and a per-int Python list would cost
    tens of MB of JSON per rank through the KV transport."""
    return base64.b64encode(
        np.ascontiguousarray(a, dtype="<i8").tobytes()
    ).decode("ascii")


def _unpack_i64(s: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(s), dtype="<i8")


def _resolve_global_sparse_layout(
    local: ReadResult,
    exchange,
    tag: str,
    *,
    pad_multiple: int = 1,
) -> ReadResult:
    """Make every sparse shard's LAYOUT decisions globally consistent:

    - **Hybrid hot head** (shards carrying a ``hybrid_policy``): each rank
      ships its local per-column nnz histogram — already in the GLOBAL
      column space after the index-map remap — through one metadata
      allgather; every rank sums the histograms and applies the identical
      ``rank_hot_columns`` sizing rule, so the resolved ``hot_ids`` (and
      therefore the [n, k_hot] head shape, column order, and
      parallel/column_sharded.py's per-block hot sub-blocks) agree bitwise
      across ranks. This is exactly how the entity vocabs were made
      globally consistent above, and the reason hybrid now composes with
      --partitioned-io instead of being rejected.
    - **ELL width + flat overflow length** (every sparse shard): each rank
      ships its post-hybrid-split per-row-count histogram (row counts over
      TRUE local rows) in the same allgather; the agreed width applies the
      full read's EXACT auto rule (``_ell_auto_width_from_hist`` — the
      98th-percentile/waste-cap rule evaluated on the summed histogram,
      with the zero-count rows train_distributed's mesh padding would
      append mirrored in, since the full read picks its width AFTER that
      padding), so the composed ELL/overflow split is bitwise what the
      unpartitioned read would build. Every rank's overflow beyond that width is also
      derivable from the same gathered histograms, so all ranks agree a
      common ``flat_block_nnz`` (max overflow, rounded up to
      ``pad_multiple`` so device shards never cross rank blocks) with no
      extra exchange — parallel/distributed._assemble_sparse_fe assembles
      that fixed-length flat tail across ranks. (Hybrid shards take two
      allgathers per shard: the tail histogram depends on the globally
      resolved hot head.)

    Histograms ride the existing exchange deadlines: a rank that never
    publishes surfaces as a rank-attributed ExchangeTimeout, never a hang
    (tests/test_resilience.py pins it with a WithholdingExchange).
    """
    ds = local.dataset
    sparse_shards = {
        k: v for k, v in ds.feature_shards.items()
        if isinstance(v, SparseShard)
    }
    if not sparse_shards:
        return local
    from photon_ml_tpu.data.sparse_batch import (
        _ell_auto_width_from_hist,
        rank_hot_columns,
    )
    from photon_ml_tpu.telemetry.layout import record_global_hot_ranking

    new_shards = dict(ds.feature_shards)
    for name in sorted(sparse_shards):  # fixed order: SPMD call discipline
        shard = sparse_shards[name]
        rows, cols, _ = shard.coalesced()
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        policy = shard.hybrid_policy
        hot = None
        if policy is not None and policy.hot_ids is None:
            uniq, cnt = (
                np.unique(cols, return_counts=True) if len(cols)
                else (np.zeros(0, np.int64), np.zeros(0, np.int64))
            )
            # packed int64 bytes, not per-int Python lists: unique columns
            # reach millions at giant d, and a list-of-ints JSON payload
            # would cost tens of MB per rank through the KV transport
            with tracing.span("partitioned/hybrid_hot_exchange",
                              cat="partitioned", shard=name,
                              rank=exchange.rank):
                gathered_hist = exchange.allgather(
                    f"hybrid_hot/{tag}/{name}",
                    {"cols": _pack_i64(uniq), "counts": _pack_i64(cnt)},
                )
            all_cols = np.concatenate(
                [_unpack_i64(g["cols"]) for g in gathered_hist]
            )
            all_cnts = np.concatenate(
                [_unpack_i64(g["counts"]) for g in gathered_hist]
            )
            # sum per-rank histograms into the global one (sorted by id,
            # exactly what np.unique over the full read would produce)
            guniq, inv = np.unique(all_cols, return_inverse=True)
            gcnt = np.zeros(len(guniq), dtype=np.int64)
            np.add.at(gcnt, inv, all_cnts)
            gnnz = int(gcnt.sum())
            hot = rank_hot_columns(guniq, gcnt, gnnz, policy)
            if len(hot) == 0:
                raise ValueError(
                    f"feature shard '{name}': hybrid=true but no rank "
                    "decoded any nonzero entry — nothing to rank"
                )
            policy = dataclasses.replace(
                policy, hot_ids=tuple(int(c) for c in hot)
            )
            record_global_hot_ranking(
                policy.label, k_hot=len(hot), global_nnz=gnnz,
                num_ranks=exchange.num_ranks,
            )
        elif policy is not None:
            hot = np.asarray(policy.hot_ids, dtype=np.int64)

        # agreed ELL width + flat overflow length: the full read's EXACT
        # auto rule evaluated on the summed per-row-count histograms
        if hot is not None and len(cols):
            pos = np.searchsorted(hot, cols)
            is_hot = hot[np.minimum(pos, len(hot) - 1)] == cols
            tail_rows = rows[~is_hot]
        else:
            tail_rows = rows
        n_local = int(shard.num_samples)
        counts = (
            np.bincount(tail_rows, minlength=n_local).astype(np.int64)
            if n_local else np.zeros(0, np.int64)
        )
        freq = np.bincount(counts) if n_local else np.zeros(1, np.int64)
        with tracing.span("partitioned/ell_width_exchange",
                          cat="partitioned", shard=name,
                          rank=exchange.rank):
            gathered_rows = exchange.allgather(
                f"ell_width/{tag}/{name}",
                {"freq": freq.astype(int).tolist(), "n": n_local},
            )
        depth = max(len(g["freq"]) for g in gathered_rows)
        gfreq = np.zeros(depth, dtype=np.int64)
        rank_freqs = []
        for g in gathered_rows:
            f = np.zeros(depth, dtype=np.int64)
            f[: len(g["freq"])] = np.asarray(g["freq"], dtype=np.int64)
            rank_freqs.append(f)
            gfreq += f
        gn = int(sum(int(g["n"]) for g in gathered_rows))
        widths = np.arange(depth, dtype=np.int64)
        gnnz = int((gfreq * widths).sum())
        # the full read computes its auto width AFTER train_distributed
        # pads the sample axis to a mesh-data-axis multiple (data_axis =
        # pad_multiple * num_ranks, the documented read contract): mirror
        # those zero-count padding rows in the histogram, or the agreed
        # width drifts from the full read's whenever the global row count
        # is not a mesh multiple (the 0.98 quantile shifts down as zero
        # rows are appended)
        data_axis = pad_multiple * exchange.num_ranks
        pad0 = (-gn) % data_axis
        if pad0:
            gfreq[0] += pad0
            gn += pad0
        width = _ell_auto_width_from_hist(gfreq, gn, gnnz)
        # per-rank overflow beyond the agreed width, from the SAME
        # gathered histograms — every rank lands on one flat block length
        flat = max(
            int((f * np.maximum(widths - width, 0)).sum())
            for f in rank_freqs
        )
        if flat:
            flat = -(-flat // pad_multiple) * pad_multiple
        new_shards[name] = dataclasses.replace(
            shard, hybrid_policy=policy, ell_width=width,
            flat_block_nnz=int(flat),
            _device=None, _hybrid_cache=None,
        )
    return dataclasses.replace(
        local, dataset=dataclasses.replace(ds, feature_shards=new_shards)
    )


def _remap_to_global_vocabs(
    local: ReadResult,
    re_types: Sequence[str],
    gathered: list[dict],
    *,
    provided_vocabs,
) -> tuple[ReadResult, dict[str, np.ndarray]]:
    """Union per-rank entity vocabularies into the sorted global vocab
    (identical to a full read's np.unique over all keys) and remap the
    local entity index column; also tally on how many ranks each entity
    appears (cross-rank entities change rank-local RE semantics)."""
    ds = local.dataset
    new_vocabs = dict(ds.entity_vocabs)
    new_idx = dict(ds.entity_idx)
    host_cache = dict(ds.host_cache)
    presence: dict[str, np.ndarray] = {}
    for t in re_types:
        rank_counts = [np.asarray(g["entities"][t][1], dtype=np.int64)
                       for g in gathered]
        if provided_vocabs is not None and t in provided_vocabs:
            # vocab was shared knowledge: no id strings crossed the wire,
            # every rank's counts already align to it
            global_vocab = np.asarray(provided_vocabs[t]).astype(str)
            remap_needed = False
            pres = np.zeros(len(global_vocab), dtype=np.int64)
            for c in rank_counts:
                pres += (c > 0).astype(np.int64)
        else:
            rank_vocabs = [np.asarray(g["entities"][t][0], dtype=str)
                           for g in gathered]
            global_vocab = np.unique(np.concatenate(
                [v for v in rank_vocabs if len(v)] or [np.zeros(0, str)]
            ))
            remap_needed = True
            pres = np.zeros(len(global_vocab), dtype=np.int64)
            for v, c in zip(rank_vocabs, rank_counts):
                if len(v):
                    pos = np.searchsorted(global_vocab, v)
                    pos = np.minimum(pos, max(len(global_vocab) - 1, 0))
                    hit = (
                        global_vocab[pos] == v if len(global_vocab)
                        else np.zeros(len(v), bool)
                    )
                    np.add.at(pres, pos[hit], (c[hit] > 0).astype(np.int64))
        presence[t] = pres
        if remap_needed:
            local_vocab = np.asarray(ds.entity_vocabs[t]).astype(str)
            idx = np.asarray(ds.host_array(f"entity_idx/{t}"))
            if len(local_vocab):
                lookup = np.searchsorted(global_vocab, local_vocab)
                remapped = np.where(
                    idx >= 0, lookup[np.maximum(idx, 0)], -1
                ).astype(np.int32)
            else:
                remapped = idx.astype(np.int32)
            new_idx[t] = remapped
            host_cache[f"entity_idx/{t}"] = remapped
            new_vocabs[t] = global_vocab
    return (
        dataclasses.replace(
            local,
            dataset=dataclasses.replace(
                ds, entity_idx=new_idx, entity_vocabs=new_vocabs,
                host_cache=host_cache,
            ),
        ),
        presence,
    )
