"""Out-of-core GAME training: streamed coordinate-descent sweeps with an
optional DuHL importance-ordered chunk schedule.

Reference parity: photon-lib algorithm/CoordinateDescent.scala:198-255 (the
GAME training loop this module re-runs chunk-wise) + data/avro/
AvroDataReader.scala (the reference never co-resides the full input on one
machine; Spark streams HDFS splits through executor tasks). The DuHL
working-set schedule has no reference analogue — it is the
duality-gap-ordered out-of-core strategy of Duenner et al.
(arXiv:1702.07005), applied at chunk granularity with the per-lane
convergence scalars the lane scheduler already reads (optim/common
.LaneTrace) as the importance signal.

Design (ISSUE 11):

- **Per-sample scalars stay host-resident; features stream.** The program
  owns [n] host score vectors (one per coordinate), labels/weights/base
  offsets, and per-RE-type entity indices — O(n) floats, the working set
  Snap ML's hierarchy keeps resident (arXiv:1803.06333). The O(n·d)
  feature blocks only ever exist one fixed-shape chunk at a time.
- **Entity-clustered chunks make RE solves exact.** The chunk plan
  (io/stream_reader.plan_entity_chunks) packs WHOLE entities per chunk,
  so each chunk's per-entity bucket solves see the identical padded
  blocks the in-core path builds (zero-weight cap padding is an exact
  no-op) — streamed GAME matches in-core ``train_distributed`` to float
  round-off (tests/test_streaming_game.py pins it). Every RE type is
  VERIFIED clustered before training; an entity spanning chunks fails
  fast (entity-cluster the input, or train that coordinate in-core).
- **The FE coordinate streams through the PR 7 contract.** Residual
  offsets overlay the chunk offsets host-side and the solve runs
  ``StreamingGLMObjective`` in host-loop mode — exact chunked epochs,
  decode double-buffered behind accumulation.
- **The batch-as-argument rule, mechanized.** Every chunk-consuming jit lives at module
  scope with the chunk pytree in its ARGUMENT list (``batch``); dev/
  lint_parity.py check 9 covers this module so a closed-over chunk (a new
  program, and a compile, per chunk) stays impossible on the GAME path too.
- **DuHL schedule (opt-in).** ``DuHLChunkSchedule`` keeps a fixed budget
  of gap-hottest chunks pinned (their decoded batches cached — FE epochs
  and RE solves hit the cache instead of the decoder), streams the cold
  tail round-robin, and re-ranks each sweep from the per-chunk aggregated
  gradient-norm scalars the bucket solves already return. Skipping a
  cold chunk's RE solve leaves its table rows — and therefore its scores
  — EXACT, just un-refreshed; on gap-skewed data the run reaches
  tolerance in far fewer chunk loads than uniform sweeps. ``schedule=None``
  (uniform order, every chunk every sweep, no cache) is the default and
  is pinned bitwise-identical to ``UniformChunkSchedule``.
- **Crash-safe resume.** Sweep-granular checkpoints ride
  ``io.checkpoint.commit_checkpoint`` (rank-0-gated, exchange-barriered
  when one is attached — lint check 10); the fingerprint pins the chunk
  plan AND the schedule mode/budget, so restoring under a different
  working-set budget fails fast naming the field. Scores are recomputed
  from the restored tables through the same jitted steps that produced
  them, so a resumed run continues bitwise.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinates import (
    _mask_padding_lanes,
    _solve_bucket_entities,
)
from photon_ml_tpu.algorithm.streaming import (
    StreamingGLMObjective,
    _pack_f64,
    _pack_i64,
    _unpack_f64,
    _unpack_i64,
)
from photon_ml_tpu.data.batch import LabeledPointBatch, solve_dtype_of
from photon_ml_tpu.data.game_data import (
    group_entities_into_buckets,
    pack_bucket_lanes,
)
from photon_ml_tpu.io.checkpoint import commit_checkpoint, fingerprint_mismatch
from photon_ml_tpu.io.stream_reader import (
    DEFAULT_CHUNK_TIMEOUT,
    ChunkPrefetcher,
    ChunkSpec,
    GameChunk,
    entities_spanning_chunks,
)
from photon_ml_tpu.models.game import score_random_effect
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    resolve_auto_optimizer,
)
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainState,
    RandomEffectStepSpec,
)
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.telemetry import stream_counters, tracing
from photon_ml_tpu.telemetry.program_ledger import ledger_jit
from photon_ml_tpu.types import TaskType

Array = jax.Array

logger = logging.getLogger(__name__)

#: default entity size buckets — identical to
#: data.game_data.build_random_effect_dataset's default, so a streamed
#: chunk's per-entity blocks land in the same capacity classes the
#: in-core path pads to
DEFAULT_BUCKET_SIZES = (8, 32, 128, 512, 2048)


# ---------------------------------------------------------------------------
# The jit signatures chunks ride (module scope; chunk pytrees are the
# `batch` ARGUMENT — lint check 9)
# ---------------------------------------------------------------------------


@partial(ledger_jit, label="streaming_game/solve_re_chunk_bucket",
         static_argnames=("objective", "opt"))
def _solve_re_chunk_bucket(table, batch, *, objective, opt):
    """Solve one chunk-local entity bucket and scatter into the [E, d]
    table. ``batch``: features [e, cap, d], labels/weights/offsets
    [e, cap], entity_rows [e] (GLOBAL vocab rows; padding lanes carry the
    OOB sentinel E — gathers clamp, scatters drop). Returns
    (table, per-lane trace, per-lane coefficient movement ‖Δw‖) — the
    movement plus the trace's final gradient norm is the DuHL importance
    signal: a chunk whose entities stopped moving AND sit at small
    gradients has nothing left to contribute (near-zero extra cost — one
    [e] norm on arrays XLA already holds)."""
    w0 = table[batch["entity_rows"]]
    solved, trace = _solve_bucket_entities(
        objective, opt,
        batch["features"], batch["labels"], batch["weights"],
        batch["offsets"], w0,
    )
    trace = _mask_padding_lanes(trace, batch["entity_rows"], table.shape[0])
    movement = jnp.sqrt(jnp.sum((solved - w0) ** 2, axis=-1))
    return table.at[batch["entity_rows"]].set(solved), trace, movement


@partial(ledger_jit, label="streaming_game/fe_margin_chunk",
         static_argnames=("objective",))
def _fe_margin_chunk(w, batch, *, objective):
    """Pure FE margin of one chunk (no offsets) from normalized-space
    coefficients — the chunk-wise twin of GameTrainProgram's
    ``_fe_margin_score``."""
    norm = objective.normalization
    eff = norm.effective_coefficients(w)
    return batch["features"] @ eff - norm.margin_shift(eff)


@partial(ledger_jit, label="streaming_game/re_score_chunk")
def _re_score_chunk(table, batch):
    """One chunk's RE coordinate scores: x_i . table[entity_idx_i]
    (0 for absent entities / padding rows)."""
    return score_random_effect(table, batch["features"], batch["entity_idx"])


# ---------------------------------------------------------------------------
# Chunk schedules
# ---------------------------------------------------------------------------


class UniformChunkSchedule:
    """Every chunk, every sweep, in plan order — the PR-7-style uniform
    epoch, as a schedule object. Pins nothing; pinned bitwise-identical to
    ``schedule=None`` (tests/test_streaming_game.py)."""

    mode = "uniform"

    def __init__(self, num_chunks: int):
        self.num_chunks = int(num_chunks)

    def plan_sweep(self) -> list[int]:
        return list(range(self.num_chunks))

    def pinned(self) -> "set[int]":
        return set()

    def record(self, chunk: int, importance: float) -> None:
        pass

    def sweep_done(self) -> None:
        pass

    def state_dict(self) -> dict:
        return {"mode": self.mode}

    def load_state(self, state: dict) -> None:
        pass

    def fingerprint(self) -> dict:
        return {"schedule": self.mode}


@dataclasses.dataclass(frozen=True)
class DuHLScheduleConfig:
    """DuHL working-set budget: ``working_set_chunks`` gap-hottest chunks
    stay pinned (decoded batches cached) and re-solve every sweep;
    ``tail_chunks_per_sweep`` cold chunks rotate in round-robin so stale
    importances refresh and every chunk is revisited eventually.
    ``warmup_sweeps`` full sweeps run first: the importance signal is
    coefficient MOVEMENT, which is large everywhere on the very first
    solve (everything moves off the zero init) — only after a second
    visit does "still moving" separate gap-hot chunks from converged
    ones."""

    working_set_chunks: int
    tail_chunks_per_sweep: int = 1
    warmup_sweeps: int = 2

    def __post_init__(self):
        if self.working_set_chunks < 1:
            raise ValueError("working_set_chunks must be >= 1")
        if self.tail_chunks_per_sweep < 1:
            raise ValueError("tail_chunks_per_sweep must be >= 1")
        if self.warmup_sweeps < 1:
            raise ValueError("warmup_sweeps must be >= 1")


class DuHLChunkSchedule:
    """Importance-ordered chunk schedule (arXiv:1702.07005 at chunk
    granularity). The first ``warmup_sweeps`` sweeps visit everything
    (building a differential importance signal); later sweeps visit the
    top-``B`` chunks by importance plus the next ``t`` cold chunks
    round-robin. Importance = the per-chunk sum over valid lanes of
    coefficient movement + final gradient norm from the RE bucket solves
    — scalars the solve returns anyway (near-zero extra cost)."""

    mode = "duhl"

    def __init__(self, config: DuHLScheduleConfig, num_chunks: int):
        self.config = config
        self.num_chunks = int(num_chunks)
        self.importance = np.zeros(self.num_chunks, dtype=np.float64)
        self.cursor = 0
        self.sweeps_done = 0

    def _working_set(self) -> "list[int]":
        b = min(self.config.working_set_chunks, self.num_chunks)
        # stable argsort on negated importance: ties break on chunk index,
        # so the plan is deterministic (checkpoint resume replays it)
        return list(np.argsort(-self.importance, kind="stable")[:b])

    def plan_sweep(self) -> list[int]:
        if self.sweeps_done < self.config.warmup_sweeps:
            return list(range(self.num_chunks))
        visit = set(self._working_set())
        tail = [c for c in range(self.num_chunks) if c not in visit]
        for _ in range(min(self.config.tail_chunks_per_sweep, len(tail))):
            visit.add(tail[self.cursor % len(tail)])
            self.cursor += 1
        return sorted(visit)

    def pinned(self) -> "set[int]":
        if self.sweeps_done < self.config.warmup_sweeps:
            return set()
        return set(self._working_set())

    def record(self, chunk: int, importance: float) -> None:
        self.importance[chunk] = float(importance)

    def sweep_done(self) -> None:
        self.sweeps_done += 1

    def state_dict(self) -> dict:
        return {
            "mode": self.mode,
            "importance": [float(x) for x in self.importance],
            "cursor": int(self.cursor),
            "sweeps_done": int(self.sweeps_done),
        }

    def load_state(self, state: dict) -> None:
        if state.get("mode") != self.mode:
            raise ValueError(
                f"schedule state holds mode {state.get('mode')!r}, this run "
                f"is {self.mode!r}"
            )
        self.importance = np.asarray(state["importance"], dtype=np.float64)
        self.cursor = int(state["cursor"])
        self.sweeps_done = int(state["sweeps_done"])

    def fingerprint(self) -> dict:
        return {
            "schedule": self.mode,
            "working_set_chunks": int(self.config.working_set_chunks),
            "tail_chunks_per_sweep": int(self.config.tail_chunks_per_sweep),
            "warmup_sweeps": int(self.config.warmup_sweeps),
        }


# ---------------------------------------------------------------------------
# Working-set chunk cache
# ---------------------------------------------------------------------------


class _ChunkCache:
    """Load-through cache over a GAME chunk source. Only PINNED chunks
    (the DuHL working set) are retained — host batch plus the
    device-placed FE feature block, so a pinned chunk's FE epochs re-read
    HBM-resident features instead of re-decoding and re-transferring.
    ``loads`` counts source decodes (the DuHL evidence metric); cache hits
    are free. Thread-safe: the FE prefetcher's producer thread loads
    through here."""

    def __init__(self, source):
        self.source = source
        self.loads = 0
        #: rows of zero-padding applied to every FE feature block (mesh
        #: divisibility) — a PROGRAM constant set once at build, so the
        #: cached placed blocks always carry the one shape every consumer
        #: expects (margins slice [:num_records] either way)
        self.fe_pad = 0
        self._store: dict[int, GameChunk] = {}
        self._fe_device: dict[int, Array] = {}
        self._pinned: "set[int]" = set()
        self._lock = threading.Lock()

    def get(self, index: int) -> GameChunk:
        with self._lock:
            cached = self._store.get(index)
        if cached is not None:
            return cached
        chunk = self.source.load(self.source.specs[index])
        with self._lock:
            self.loads += 1
            if index in self._pinned:
                self._store[index] = chunk
        return chunk

    def fe_features(self, index: int, shard: str):
        """FE feature block of one chunk, zero-padded by the program's
        ``fe_pad`` rows (mesh divisibility); device-resident for pinned
        chunks ("pinned in HBM": padding happens BEFORE placement, so
        mesh runs never round-trip the pinned block back to host), a
        plain host array otherwise."""
        with self._lock:
            placed = self._fe_device.get(index)
            pinned = index in self._pinned
        if placed is not None:
            return placed
        chunk = self.get(index)
        feats = chunk.features[shard]
        if self.fe_pad:
            feats = np.pad(feats, ((0, self.fe_pad), (0, 0)))
        if pinned:
            placed = jnp.asarray(feats)
            with self._lock:
                self._fe_device[index] = placed
            return placed
        return feats

    def set_pinned(self, pinned: "set[int]") -> None:
        with self._lock:
            self._pinned = set(pinned)
            for idx in list(self._store):
                if idx not in self._pinned:
                    del self._store[idx]
            for idx in list(self._fe_device):
                if idx not in self._pinned:
                    del self._fe_device[idx]


class _FixedEffectChunkView:
    """The FE coordinate's view of the GAME chunk stream: a dense
    ``ChunkSource`` whose every load overlays the CURRENT residual offsets
    (other coordinates' scores) onto the chunk's base offsets host-side —
    so the existing ``StreamingGLMObjective`` runs the FE solve unchanged
    (PR 7 contract: exact chunked epochs, one module-level jitted
    accumulator, chunks as jit ARGUMENTS)."""

    sparse = False

    def __init__(self, cache: _ChunkCache, shard: str,
                 residual: np.ndarray, *, pad_multiple: int = 1):
        self._cache = cache
        self._shard = shard
        self._residual = residual
        src = cache.source
        self.specs: "list[ChunkSpec]" = src.specs
        # mesh runs shard the chunk's sample axis; pad to the data-axis
        # multiple with zero-weight rows (an exact no-op, and constant per
        # source so every chunk keeps the one jit signature)
        self._pad = (-src.chunk_rows) % max(1, int(pad_multiple))
        self.chunk_rows = src.chunk_rows + self._pad
        self.dim = src.dims[shard]

    @property
    def num_chunks(self) -> int:
        return len(self.specs)

    @property
    def total_records(self) -> int:
        return int(sum(s.num_records for s in self.specs))

    def load(self, spec: ChunkSpec) -> LabeledPointBatch:
        chunk = self._cache.get(spec.index)
        rows = chunk.rows
        safe = np.maximum(rows, 0)
        # the residual ALREADY includes the base offsets (it is
        # base + Σ other coordinates' scores, the CD recursion's
        # offsets_excluding) — it REPLACES the chunk's base offsets here;
        # padding rows (-1) carry 0 like every padded field
        offsets = np.where(
            rows >= 0, self._residual[safe], 0.0
        ).astype(chunk.offsets.dtype)
        # the cache pads the feature block before device placement; the
        # per-sample vectors pad here (host, cheap, fresh per epoch)
        features = self._cache.fe_features(spec.index, self._shard)
        labels, weights = chunk.labels, chunk.weights
        if self._pad:
            pad = self._pad
            labels = np.pad(labels, (0, pad))
            offsets = np.pad(offsets, (0, pad))
            weights = np.pad(weights, (0, pad))
        return LabeledPointBatch(
            features=features,
            labels=labels,
            offsets=offsets,
            weights=weights,
        )


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamingGameResult:
    state: GameTrainState
    losses: "list[float]"
    sweeps: int
    chunk_loads: int
    chunk_visits: int

    def __iter__(self):
        return iter((self.state, self.losses))


class StreamingGameProgram:
    """Out-of-core GAME coordinate descent over an entity-clustered chunk
    source (io/stream_reader.GameArrayChunkSource / GameAvroChunkSource).

    Covers the production streamed surface: one dense primary FE
    coordinate plus IDENTITY random-effect coordinates, no normalization
    riders (projected/compact/MF coordinates keep the in-core paths —
    their build steps materialize O(n·d) state this module exists to
    avoid). The sweep replays GameTrainProgram's Gauss-Seidel recursion in
    the same update order with the same residual algebra, so the streamed
    fit matches in-core ``train_distributed`` to float round-off.
    """

    def __init__(
        self,
        task: TaskType,
        source,
        fe: FixedEffectStepSpec,
        re_specs: Sequence[RandomEffectStepSpec] = (),
        *,
        num_entities: Mapping[str, int] | None = None,
        bucket_sizes: Sequence[int] = DEFAULT_BUCKET_SIZES,
        schedule=None,
        prefetch: bool = True,
        mesh=None,
        exchange=None,
        partition=None,
        retry_policy=None,
        scalars: Mapping[str, object] | None = None,
    ):
        self.task = task
        self.source = source
        loss = loss_for_task(task)
        self._loss = loss
        # AUTO resolution mirrors GameTrainProgram: LBFGS on the (big-d,
        # host-loop streamed) FE; NEWTON on eligible RE bucket solves
        self.fe = dataclasses.replace(
            fe, optimizer=resolve_auto_optimizer(
                fe.optimizer, loss=loss, small_dense=False
            ),
        )
        self.re_specs = tuple(
            dataclasses.replace(
                s, optimizer=resolve_auto_optimizer(
                    s.optimizer, loss=loss, small_dense=True
                ),
            )
            for s in re_specs
        )
        for s in self.re_specs:
            if s.projector != ProjectorType.IDENTITY:
                raise ValueError(
                    f"streamed random-effect coordinate '{s.re_type}' uses "
                    f"projector {s.projector.name}; the streamed surface "
                    "covers IDENTITY — train projected coordinates in-core "
                    "(train_distributed)"
                )
        self.bucket_sizes = tuple(int(b) for b in sorted(bucket_sizes))
        self.num_entities = dict(num_entities or {})
        self.schedule = schedule
        self.prefetch = bool(prefetch)
        self.mesh = mesh
        self.exchange = exchange
        # ISSUE 17: the exchange-agreed multi-rank plan. None (or a
        # 1-rank partition) keeps every single-rank path bitwise — the
        # chunk-id mapping below degenerates to the identity and no
        # cross-rank exchange op runs. An exchange WITHOUT a partition is
        # the ISSUE 15 wiring (checkpoint barriers only) and must stay
        # exactly that: cross-rank sums are keyed off the partition, never
        # off exchange presence.
        self.partition = partition
        self._multi_rank = partition is not None and partition.num_ranks > 1
        if self._multi_rank and exchange is None:
            raise ValueError(
                "a multi-rank GameStreamPartition needs the exchange it "
                "was agreed over (pass exchange=)"
            )
        if partition is not None:
            self._chunk_lo, self._chunk_hi = partition.chunk_range()
            self._num_chunks_global = int(partition.num_chunks)
        else:
            self._chunk_lo, self._chunk_hi = 0, source.num_chunks
            self._num_chunks_global = int(source.num_chunks)
        if self._multi_rank:
            missing = [
                s.re_type for s in self.re_specs
                if s.re_type not in self.num_entities
            ]
            if missing:
                raise ValueError(
                    f"partitioned streamed GAME needs explicit num_entities "
                    f"for {missing} — each rank sees only its local "
                    "entities, so table sizes must come from the agreed "
                    "global vocabs (num_entities={t: len(vocabs[t])})"
                )
        self.retry_policy = retry_policy
        self._cache = _ChunkCache(source)
        if mesh is not None:
            data_axis = int(mesh.shape[mesh.axis_names[0]])
            self._cache.fe_pad = (-source.chunk_rows) % data_axis
        self._fe_objective = GLMObjective(
            loss, l2_weight=fe.l2_weight, use_pallas=False
        )
        self._re_objectives = {
            s.re_type: GLMObjective(
                loss, l2_weight=s.l2_weight, use_pallas=False
            )
            for s in self.re_specs
        }
        #: lane schedulers per (re_type, chunk): per-chunk bucket blocks
        #: are distinct jit/compaction universes, so each chunk keeps its
        #: own probe/rescue state (strictly opt-in via
        #: OptimizerConfig.scheduler, like the in-core paths)
        self._lane_schedulers: dict = {}
        # sweep order: primary FE then REs in spec order — the
        # GameTrainProgram default (FE, extras, REs, MFs) restricted to
        # the streamed surface
        self.update_order = (
            (self.fe.feature_shard_id,)
            + tuple(s.re_type for s in self.re_specs)
        )
        # coordinate names share one namespace (score slots, residual
        # skips) — a collision would silently corrupt the residual
        # algebra; same guard as GameTrainProgram.__init__
        dupes = {
            n for n in self.update_order if self.update_order.count(n) > 1
        }
        if dupes:
            raise ValueError(
                f"coordinate names must be unique across the FE feature "
                f"shard and RE types (duplicates: {sorted(dupes)})"
            )
        self._re_by_name = {s.re_type: s for s in self.re_specs}
        self._scalars_arg = scalars
        self._scan_scalars()
        self._verify_clustering()
        if self._multi_rank:
            self._verify_rank_entity_partition()

    # -- one-time host scans --------------------------------------------------

    def _row_plan_from_metadata(self):
        """The per-chunk global row arrays straight from source metadata
        (no decode): in-memory sources carry an explicit ``row_plan``;
        record-ordered file sources carry per-chunk ``record_starts``."""
        src = self.source
        if getattr(src, "row_plan", None) is not None:
            return [np.asarray(r) for r in src.row_plan]
        if getattr(src, "record_starts", None) is not None:
            return [
                np.arange(start, start + spec.num_records, dtype=np.int64)
                for start, spec in zip(src.record_starts, src.specs)
            ]
        return None

    def _scan_scalars(self) -> None:
        """Make the [n] per-sample scalars the sweeps need host-resident
        (labels/weights/base offsets, entity indices) — O(n) floats,
        never O(n·d). Fast paths avoid decoding any feature block: the
        caller may pass ``scalars`` (io/stream_reader.scan_game_stream
        collects them during its vocab pass — the driver route), and
        in-memory sources expose the arrays directly; only a source with
        neither falls back to one decode pass over the chunk plan."""
        n = self.source.total_records
        self.n = n
        src = self.source
        scalars = self._scalars_arg
        if scalars is None and (
            getattr(src, "labels", None) is not None
            and getattr(src, "entity_idx", None) is not None
        ):
            scalars = {
                "labels": src.labels,
                "offsets": src.offsets,
                "weights": src.weights,
                "entity_idx": src.entity_idx,
            }
        row_plan = self._row_plan_from_metadata()
        if scalars is not None and row_plan is not None:
            self.labels = np.asarray(scalars["labels"])
            self.base_offsets = np.asarray(
                scalars["offsets"], dtype=self.labels.dtype
            )
            self.weights = np.asarray(
                scalars["weights"], dtype=self.labels.dtype
            )
            self.entity_idx = {
                t: np.asarray(v, dtype=np.int32)
                for t, v in scalars["entity_idx"].items()
            }
            if len(self.labels) != n:
                raise ValueError(
                    f"scalars cover {len(self.labels)} records but the "
                    f"chunk plan holds {n}"
                )
            self.dtype = self.labels.dtype
            self.solve_dtype = solve_dtype_of(self.dtype)
            self._row_plan = row_plan
            seen = np.zeros(n, dtype=bool)
            for i, rows in enumerate(row_plan):
                if seen[rows].any():
                    raise ValueError(
                        f"chunk {i} re-covers sample rows already assigned "
                        "to another chunk — the plan must partition the "
                        "sample axis"
                    )
                seen[rows] = True
            if not seen.all():
                raise ValueError(
                    f"chunk plan covers {int(seen.sum())}/{n} sample rows"
                )
            for s in self.re_specs:
                if s.re_type not in self.entity_idx:
                    raise ValueError(
                        f"random-effect coordinate '{s.re_type}' has no "
                        "entity index column in the chunk stream"
                    )
                if s.re_type not in self.num_entities:
                    self.num_entities[s.re_type] = int(
                        self.entity_idx[s.re_type].max() + 1
                    )
            return
        dtype = None
        self.labels = None
        # the scan also pins the plan's row universe: a plan with
        # overlapping or missing rows would corrupt the score algebra
        # silently
        self._row_plan = [None] * self.source.num_chunks
        seen = np.zeros(n, dtype=bool)
        with tracing.span("stream_game/scan", cat="stream",
                          chunks=self.source.num_chunks):
            for spec in self.source.specs:
                chunk = self._cache.get(spec.index)
                if self.labels is None:
                    dtype = chunk.labels.dtype
                    self.labels = np.zeros(n, dtype)
                    self.base_offsets = np.zeros(n, dtype)
                    self.weights = np.zeros(n, dtype)
                    self.entity_idx = {
                        t: np.full(n, -1, np.int32) for t in chunk.entity_idx
                    }
                m = chunk.num_records
                rows = chunk.rows[:m]
                if seen[rows].any():
                    raise ValueError(
                        f"chunk {spec.index} re-covers sample rows already "
                        "assigned to another chunk — the plan must "
                        "partition the sample axis"
                    )
                seen[rows] = True
                self._row_plan[spec.index] = np.asarray(rows)
                self.labels[rows] = chunk.labels[:m]
                self.base_offsets[rows] = chunk.offsets[:m]
                self.weights[rows] = chunk.weights[:m]
                for t, idx in chunk.entity_idx.items():
                    self.entity_idx[t][rows] = idx[:m]
        if self.labels is None:
            raise ValueError("streamed GAME needs a non-empty chunk plan")
        self.dtype = dtype
        self.solve_dtype = solve_dtype_of(dtype)
        if not seen.all():
            raise ValueError(
                f"chunk plan covers {int(seen.sum())}/{n} sample rows"
            )
        for s in self.re_specs:
            if s.re_type not in self.entity_idx:
                raise ValueError(
                    f"random-effect coordinate '{s.re_type}' has no entity "
                    "index column in the chunk stream"
                )
            if s.re_type not in self.num_entities:
                self.num_entities[s.re_type] = int(
                    self.entity_idx[s.re_type].max() + 1
                )

    def _verify_clustering(self) -> None:
        for s in self.re_specs:
            spanning = entities_spanning_chunks(
                self._row_plan, self.entity_idx[s.re_type]
            )
            if len(spanning):
                raise ValueError(
                    f"random-effect coordinate '{s.re_type}': "
                    f"{len(spanning)} entities span chunk boundaries (e.g. "
                    f"vocab rows {spanning[:5].tolist()}) — a per-chunk "
                    "solve would train them on partial data. Entity-cluster "
                    "the chunk plan by this type (cluster_by), sort the "
                    "input by it, or train this coordinate in-core."
                )

    def _verify_rank_entity_partition(self) -> None:
        """The multi-rank twin of :meth:`_verify_clustering`: every RE
        entity's rows must co-reside on ONE rank (whole-chunk assignment
        guarantees it for the cluster column; other RE types could still
        straddle the rank boundary). An overlap would let two ranks solve
        the same entity on partial data and the rank-order table sync
        silently keep the last writer — fail fast instead. One allgather
        of each rank's present entity rows (model-sized, like the vocab
        agreement)."""
        if not self.re_specs:
            return
        payload = {}
        for s in self.re_specs:
            idx = self.entity_idx[s.re_type]
            payload[s.re_type] = _pack_i64(
                np.unique(idx[idx >= 0]).astype(np.int64)
            )
        gathered = self.exchange.allgather(
            "stream_game/entity_partition", payload
        )
        for s in self.re_specs:
            per_rank = [_unpack_i64(g[s.re_type]) for g in gathered]
            ids, counts = np.unique(
                np.concatenate(per_rank), return_counts=True
            )
            overlap = ids[counts > 1]
            if len(overlap):
                owners = [
                    r for r, present in enumerate(per_rank)
                    if np.isin(overlap[:5], present).any()
                ]
                raise ValueError(
                    f"random-effect coordinate '{s.re_type}': "
                    f"{len(overlap)} entities have rows on more than one "
                    f"rank (e.g. vocab rows {overlap[:5].tolist()} on ranks "
                    f"{owners}) — a per-rank solve would train them on "
                    "partial data. Sort the input by the cluster column, "
                    "nest this type inside it, or train this coordinate "
                    "in-core."
                )

    # -- state / scores -------------------------------------------------------

    def init_state(self) -> GameTrainState:
        fe_dim = self.source.dims[self.fe.feature_shard_id]
        return GameTrainState(
            fe_coefficients=jnp.zeros((fe_dim,), dtype=self.solve_dtype),
            re_tables={
                s.re_type: jnp.zeros(
                    (self.num_entities[s.re_type],
                     self.source.dims[s.feature_shard_id]),
                    dtype=self.solve_dtype,
                )
                for s in self.re_specs
            },
        )

    def _zero_scores(self) -> "dict[str, np.ndarray]":
        return {
            name: np.zeros(self.n, self.solve_dtype)
            for name in self.update_order
        }

    def _residual(self, scores, skip=None) -> np.ndarray:
        """base offsets + every coordinate score except ``skip``, summed in
        canonical update order — the identical accumulation order
        GameTrainProgram._sum_scores uses, element-wise on host."""
        total = self.base_offsets.astype(self.solve_dtype)
        for name in self.update_order:
            if name != skip:
                total = total + scores[name]
        return total

    def _refresh_fe_scores(self, scores, fe_w) -> None:
        """Recompute the FE margin for every sample, chunk-wise, through
        the module-level jitted step."""
        shard = self.fe.feature_shard_id
        for spec in self.source.specs:
            batch = {
                "features": self._cache.fe_features(spec.index, shard),
            }
            margins = np.asarray(
                _fe_margin_chunk(fe_w, batch, objective=self._fe_objective)
            )
            m = spec.num_records
            scores[shard][self._row_plan[spec.index]] = margins[:m].astype(
                self.solve_dtype
            )

    def _refresh_re_scores_chunk(self, scores, re_type, table, chunk,
                                 spec) -> None:
        s = self._re_by_name[re_type]
        batch = {
            "features": chunk.features[s.feature_shard_id],
            "entity_idx": chunk.entity_idx[re_type],
        }
        margins = np.asarray(_re_score_chunk(table, batch))
        m = spec.num_records
        scores[re_type][self._row_plan[spec.index]] = margins[:m].astype(
            self.solve_dtype
        )

    def refresh_all_scores(self, state: GameTrainState) -> "dict[str, np.ndarray]":
        """Scores of every coordinate at ``state`` — used on resume/warm
        start (a zero state's scores are exactly zero, no pass needed).
        Chunk-outer so each chunk decodes ONCE for the FE margin and
        every RE coordinate (the cache retains only pinned chunks)."""
        scores = self._zero_scores()
        shard = self.fe.feature_shard_id
        for spec in self.source.specs:
            chunk = self._cache.get(spec.index)
            m = spec.num_records
            rows = self._row_plan[spec.index]
            margins = np.asarray(_fe_margin_chunk(
                state.fe_coefficients, {"features": chunk.features[shard]},
                objective=self._fe_objective,
            ))
            scores[shard][rows] = margins[:m].astype(self.solve_dtype)
            for s in self.re_specs:
                self._refresh_re_scores_chunk(
                    scores, s.re_type, state.re_tables[s.re_type], chunk,
                    spec,
                )
        return scores

    # -- coordinate solves ----------------------------------------------------

    def _solve_fe(self, scores, fe_w) -> Array:
        residual = self._residual(scores, skip=self.fe.feature_shard_id)
        pad_multiple = 1
        if self.mesh is not None:
            pad_multiple = int(self.mesh.shape[self.mesh.axis_names[0]])
        view = _FixedEffectChunkView(
            self._cache, self.fe.feature_shard_id, residual,
            pad_multiple=pad_multiple,
        )
        objective = StreamingGLMObjective(
            view, self._loss,
            l2_weight=self.fe.l2_weight,
            mesh=self.mesh,
            # multi-rank: per-rank partial value/grad/Hv summed IN RANK
            # ORDER through the exchange every epoch (the PR 7 accumulator
            # rule) — every rank evaluates the identical global objective,
            # so the host-loop solver takes identical steps on every rank.
            # Keyed off the PARTITION, never off exchange presence: a
            # coordinated-recovery exchange on a full program must not
            # double-count (each such rank already streams ALL chunks).
            exchange=self.exchange if self._multi_rank else None,
            prefetch=self.prefetch,
            retry_policy=self.retry_policy,
        )
        from photon_ml_tpu.optim.optimizer import solve

        result = solve(self.fe.optimizer, objective, fe_w, host_loop=True)
        return result.coefficients

    def _chunk_blocks(self, chunk: GameChunk, re_type: str,
                      residual_local: np.ndarray):
        """Chunk-local entity buckets, packed exactly like
        build_random_effect_dataset's IDENTITY path (same bucket sizes,
        same lane layout, ascending row order per entity), with lanes
        padded to the next power of two so the per-chunk jit signatures
        stay bounded across chunks and sweeps. ``residual_local`` is the
        CD residual in chunk-local row coordinates ([chunk_rows], padding
        rows 0)."""
        s = self._re_by_name[re_type]
        idx = chunk.entity_idx[re_type]
        m = chunk.num_records
        feats = chunk.features[s.feature_shard_id]
        labels, weights = chunk.labels, chunk.weights
        # chunk.rows double as stable sample ids: the streamed surface
        # keeps build_game_dataset's default unique_ids (= row index)
        per_bucket = group_entities_into_buckets(
            idx[:m], chunk.rows[:m], bucket_sizes=self.bucket_sizes
        )
        num_rows = self.num_entities[re_type]
        blocks = []
        for cap, members in per_bucket.items():
            if not members:
                continue
            e = len(members)
            e_pad = 1 << (e - 1).bit_length()
            be, rows_concat, lane, slot = pack_bucket_lanes(members)
            bf = np.zeros((e_pad, cap, feats.shape[1]), feats.dtype)
            bl = np.zeros((e_pad, cap), labels.dtype)
            bw = np.zeros((e_pad, cap), weights.dtype)
            bo = np.zeros((e_pad, cap), residual_local.dtype)
            bf[lane, slot] = feats[rows_concat]
            bl[lane, slot] = labels[rows_concat]
            bw[lane, slot] = weights[rows_concat]
            bo[lane, slot] = residual_local[rows_concat]
            ents = np.full((e_pad,), num_rows, np.int32)  # OOB sentinel
            ents[:e] = be
            blocks.append({
                "features": bf, "labels": bl, "weights": bw,
                "offsets": bo, "entity_rows": ents,
            })
        return blocks

    def _solve_re_chunk(self, re_type, table, chunk, spec, residual_local,
                        final_sweep: bool):
        """All of one chunk's entity buckets for one RE coordinate.
        Returns (table, importance): importance = Σ valid lanes'
        coefficient movement + final gradient norm — the DuHL gap signal,
        read from scalars the solve computes anyway."""
        s = self._re_by_name[re_type]
        opt = s.optimizer
        objective = self._re_objectives[re_type]
        if opt.scheduler is not None:
            return self._solve_re_chunk_scheduled(
                re_type, table, chunk, spec, residual_local, final_sweep
            )
        importance = 0.0
        for batch in self._chunk_blocks(chunk, re_type, residual_local):
            table, trace, movement = _solve_re_chunk_bucket(
                table, batch, objective=objective, opt=opt
            )
            valid = np.asarray(trace.valid)
            signal = np.asarray(movement) + np.asarray(trace.gradient_norm)
            importance += float(np.where(valid, signal, 0.0).sum())
        return table, importance

    def _solve_re_chunk_scheduled(self, re_type, table, chunk, spec,
                                  residual_local, final_sweep):
        """Probe/rescue lane scheduling per chunk
        (algorithm/lane_scheduler.py — opt-in via
        OptimizerConfig.scheduler, exactly like the in-core paths).
        ``sample_rows`` and the offsets vector both live in CHUNK-LOCAL
        row coordinates — the scheduler only ever gathers offsets through
        them, so the pairing is self-consistent."""
        from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler

        s = self._re_by_name[re_type]
        key = (re_type, spec.index)
        scheduler = self._lane_schedulers.get(key)
        if scheduler is None or scheduler.config != s.optimizer.scheduler:
            scheduler = LaneScheduler(s.optimizer.scheduler)
            self._lane_schedulers[key] = scheduler
        blocks = []
        m = chunk.num_records
        idx = chunk.entity_idx[re_type]
        feats = chunk.features[s.feature_shard_id]
        per_bucket = group_entities_into_buckets(
            idx[:m], chunk.rows[:m], bucket_sizes=self.bucket_sizes
        )
        for cap, members in per_bucket.items():
            if not members:
                continue
            e = len(members)
            be, rows_concat, lane, slot = pack_bucket_lanes(members)
            bf = np.zeros((e, cap, feats.shape[1]), feats.dtype)
            bl = np.zeros((e, cap), chunk.labels.dtype)
            bw = np.zeros((e, cap), chunk.weights.dtype)
            bs = np.full((e, cap), -1, np.int32)
            bf[lane, slot] = feats[rows_concat]
            bl[lane, slot] = chunk.labels[rows_concat]
            bw[lane, slot] = chunk.weights[rows_concat]
            bs[lane, slot] = rows_concat
            blocks.append({
                "features": bf, "labels": bl, "weights": bw,
                "sample_rows": bs, "entity_rows": be,
            })
        # movement term computed around the scheduler call (its traces
        # carry no Δw): same movement + gradient-norm signal as the
        # unscheduled path, so both composition modes rank identically
        moved_rows = np.concatenate(
            [np.asarray(b["entity_rows"]) for b in blocks]
        ) if blocks else np.zeros(0, np.int32)
        before = np.asarray(table)[moved_rows]
        table, traces, _stats = scheduler.solve(
            self._re_objectives[re_type], s.optimizer, blocks,
            jnp.asarray(residual_local), table,
            projector=ProjectorType.IDENTITY, final_sweep=final_sweep,
        )
        after = np.asarray(table)[moved_rows]
        importance = float(
            np.sqrt(((after - before) ** 2).sum(axis=-1)).sum()
        )
        for trace in traces:
            valid = np.asarray(trace.valid)
            gnorm = np.asarray(trace.gradient_norm)
            importance += float(np.where(valid, gnorm, 0.0).sum())
        return table, importance

    # -- the sweep ------------------------------------------------------------

    def _weighted_loss(self, scores) -> float:
        margins = self._residual(scores)
        losses = self._loss.loss(jnp.asarray(margins),
                                 jnp.asarray(self.labels))
        wloss = float(jnp.sum(jnp.asarray(self.weights) * losses))
        wsum = float(self.weights.sum())
        if self._multi_rank:
            # rank-order f64 sum of (Σw·loss, Σw) — the loss every rank
            # reports (and plateau-stops on) is the GLOBAL training loss,
            # identical on every rank
            gathered = self.exchange.allgather(
                "stream_game/loss", {"acc": _pack_f64(
                    np.array([wloss, wsum], np.float64)
                )}
            )
            wloss, wsum = 0.0, 0.0
            for g in gathered:  # rank order — the exchange contract
                part = _unpack_f64(g["acc"])
                wloss += float(part[0])
                wsum += float(part[1])
        return wloss / max(wsum, 1.0)

    def _chunk_residual_local(self, scores, rows, m, skip) -> np.ndarray:
        """The CD residual for ONE chunk's rows, in chunk-local
        coordinates ([chunk_rows], padding rows 0): base offsets + every
        coordinate score except ``skip``, summed in the same canonical
        update order as :meth:`_residual` — elementwise-identical values,
        sliced instead of full-length so the sweep stays O(n) per
        coordinate, not O(n · num_chunks)."""
        vals = self.base_offsets[rows].astype(self.solve_dtype)
        for name in self.update_order:
            if name != skip:
                vals = vals + scores[name][rows]
        out = np.zeros(self.source.chunk_rows, self.solve_dtype)
        out[:m] = vals
        return out

    def _sweep(self, state: GameTrainState, scores, visit, final_sweep):
        """One Gauss-Seidel CD sweep over the streamed coordinates —
        GameTrainProgram._step_impl's recursion, chunk-wise. The RE phase
        is CHUNK-outer (each visited chunk decodes once for every RE
        coordinate): chunks partition the sample axis and an entity's
        rows co-reside in its chunk, so interleaving coordinates within a
        chunk sees exactly the residual values the coordinate-outer order
        would — bit-identical updates, (num_coordinates)x less I/O."""
        fe_w = state.fe_coefficients
        tables = dict(state.re_tables)
        with tracing.span("stream_game/fe_solve", cat="stream"):
            fe_w = self._solve_fe(scores, fe_w)
            self._refresh_fe_scores(scores, fe_w)
        re_names = [
            name for name in self.update_order
            if name != self.fe.feature_shard_id
        ]
        # importance accumulates ACROSS RE coordinates before recording:
        # a chunk gap-hot for any coordinate must stay in the working set
        # (per-coordinate record() calls would let the last coordinate
        # overwrite the others' signal)
        chunk_importance: dict[int, float] = {}
        updated_rows: dict[str, set] = {name: set() for name in re_names}
        for chunk_index in visit:
            spec = self.source.specs[chunk_index]
            chunk = self._cache.get(chunk_index)
            rows = self._row_plan[chunk_index]
            for name in re_names:
                with tracing.span("stream_game/re_chunk", cat="stream",
                                  coordinate=name, chunk=chunk_index):
                    residual = self._chunk_residual_local(
                        scores, rows, spec.num_records, skip=name
                    )
                    tables[name], importance = self._solve_re_chunk(
                        name, tables[name], chunk, spec, residual,
                        final_sweep,
                    )
                    self._refresh_re_scores_chunk(
                        scores, name, tables[name], chunk, spec
                    )
                if self._multi_rank:
                    idx = chunk.entity_idx[name][:spec.num_records]
                    updated_rows[name].update(
                        np.unique(idx[idx >= 0]).tolist()
                    )
                chunk_importance[chunk_index] = (
                    chunk_importance.get(chunk_index, 0.0) + importance
                )
        # the schedule speaks GLOBAL chunk ids (identical state on every
        # rank); local chunk k is global k + chunk_lo (identity when
        # unpartitioned)
        importance_global = {
            ci + self._chunk_lo: imp for ci, imp in chunk_importance.items()
        }
        if self._multi_rank:
            tables = self._sync_re_tables(tables, updated_rows)
            importance_global = self._merge_importance(importance_global)
        for chunk_index in sorted(importance_global):
            self.schedule.record(chunk_index, importance_global[chunk_index])
        return GameTrainState(fe_coefficients=fe_w, re_tables=tables)

    def _sync_re_tables(self, tables, updated_rows):
        """Rank-order merge of this sweep's RE table updates: each rank
        ships only the (row, value) pairs its chunks touched; every rank
        applies every rank's rows in rank order. Rows partition across
        ranks (whole-entity chunk assignment, verified at build time), so
        the merge is EXACT — after it, every rank holds the identical
        global tables, which is what lets the rank-0-gated checkpoint
        commit and the final model stay complete on every rank. The
        f32→f64→f32 round trip through the exchange is value-exact."""
        payload = {}
        for name, rows in updated_rows.items():
            rows_arr = np.asarray(sorted(rows), np.int64)
            vals = np.asarray(tables[name])[rows_arr]
            payload[name] = {
                "rows": _pack_i64(rows_arr),
                "vals": _pack_f64(vals.ravel()),
            }
        with tracing.span("stream_game/re_sync", cat="stream"):
            gathered = self.exchange.allgather("stream_game/re_sync", payload)
        out = {}
        for name, table in tables.items():
            if name not in payload:
                out[name] = table
                continue
            merged = np.asarray(table).copy()
            width = merged.shape[1]
            for g in gathered:  # rank order — the exchange contract
                rows_arr = _unpack_i64(g[name]["rows"])
                if len(rows_arr) == 0:
                    continue
                vals = _unpack_f64(g[name]["vals"]).reshape(-1, width)
                merged[rows_arr] = vals.astype(merged.dtype)
            out[name] = jnp.asarray(merged)
        return out

    def _merge_importance(self, importance_global):
        """ONE allgathered DuHL importance signal (arXiv:2004.02414's
        nonrandom-partition fix): every rank sees every chunk's importance
        before any schedule records it, so pin/evict decisions are a pure
        function of the same global signal on every rank — rank-local
        ranking is the measured 12-vs-8-sweeps footgun. Chunk-id keys are
        disjoint across ranks (each rank visits only its own range)."""
        payload = {
            "imp": {str(ci): float(v) for ci, v in importance_global.items()}
        }
        with tracing.span("stream_game/duhl_importance", cat="stream"):
            gathered = self.exchange.allgather(
                "stream_game/duhl_importance", payload
            )
        merged: dict[int, float] = {}
        for g in gathered:  # rank order (keys disjoint; order is for form)
            for ci, v in g["imp"].items():
                merged[int(ci)] = float(v)
        return merged

    # -- checkpoint plumbing --------------------------------------------------

    def _fingerprint(self) -> dict:
        sched = (
            {"schedule": "uniform"} if self.schedule is None
            else self.schedule.fingerprint()
        )

        def opt_fields(opt: OptimizerConfig) -> list:
            # EVERYTHING a restored sweep is only valid under — a changed
            # tolerance/history would silently resume a different solve
            # (the PR 8 hardening rule, applied to every coordinate)
            return [
                opt.optimizer_type.name,
                int(opt.max_iterations),
                float(opt.tolerance),
                None if opt.rel_function_tolerance is None
                else float(opt.rel_function_tolerance),
                int(opt.history),
                int(opt.max_cg_iterations),
                float(opt.l1_weight),
                opt.scheduler is not None,
            ]

        return {
            "kind": "game_streaming",
            "task": self.task.name,
            "fe": [
                self.fe.feature_shard_id,
                float(self.fe.l2_weight),
                *opt_fields(self.fe.optimizer),
            ],
            "coordinates": [
                [s.re_type, s.feature_shard_id, float(s.l2_weight),
                 *opt_fields(s.optimizer)]
                for s in self.re_specs
            ],
            "bucket_sizes": list(self.bucket_sizes),
            # GLOBAL geometry when partitioned — every rank's fingerprint
            # must be identical (rank 0 saves, every rank compares on
            # restore), and a restore under different rank geometry must
            # fail fast naming "partition"
            "num_chunks": int(
                self.partition.num_chunks if self.partition is not None
                else self.source.num_chunks
            ),
            "chunk_rows": int(
                self.partition.chunk_rows if self.partition is not None
                else self.source.chunk_rows
            ),
            "total_records": int(
                self.partition.total_records if self.partition is not None
                else self.source.total_records
            ),
            "partition": (
                None if self.partition is None else {
                    "num_ranks": int(self.partition.num_ranks),
                    "chunk_ranges": [
                        list(r) for r in self.partition.chunk_ranges
                    ],
                    "plan": self.partition.fingerprint,
                }
            ),
            # input IDENTITY, not just geometry: a daily re-run against
            # regenerated data of the same shape must fail fast, never
            # resume the old run's state (file-backed sources only)
            "input": (
                None if getattr(self.source, "files", None) is None
                else [
                    [os.path.basename(f), int(os.path.getsize(f))]
                    for f in self.source.files
                ]
            ),
            **sched,
        }

    def _restore(self, checkpointer, fingerprint, step=None):
        ckpt = checkpointer.restore(step=step)
        if ckpt is None:
            return None
        if ckpt.meta.get("kind") != "game_streaming":
            raise ValueError(
                f"checkpoint at {checkpointer.directory} is not a streamed-"
                f"GAME checkpoint (kind={ckpt.meta.get('kind')!r}); use a "
                "fresh checkpoint directory"
            )
        mismatch = fingerprint_mismatch(
            ckpt.meta.get("fingerprint"), fingerprint
        )
        if mismatch is not None:
            raise ValueError(
                f"streamed-GAME checkpoint at {checkpointer.directory} was "
                f"written under a different run fingerprint ({mismatch}); "
                "resume with the original chunk plan/schedule/optimizers, "
                "or use a fresh checkpoint directory"
            )
        state = GameTrainState(
            fe_coefficients=jnp.asarray(ckpt.arrays["fe_coefficients"]),
            re_tables={
                k[len("re_tables/"):]: jnp.asarray(v)
                for k, v in ckpt.arrays.items()
                if k.startswith("re_tables/")
            },
        )
        return ckpt, state

    # -- entry point ----------------------------------------------------------

    def train(
        self,
        *,
        num_sweeps: int,
        state: GameTrainState | None = None,
        tolerance: float = 0.0,
        checkpointer=None,
        checkpoint_every: int = 1,
        resume: bool = True,
        resume_step: "int | None" = None,
        on_sweep=None,
    ) -> StreamingGameResult:
        """Run up to ``num_sweeps`` streamed CD sweeps.

        on_sweep: optional observer ``(sweep_done, num_sweeps, loss)``
        called at the end of every sweep (the driver wires the journal
        heartbeat through it — ISSUE 12); observe-only.

        tolerance > 0 adds a loss-plateau stop: the run ends early when a
        sweep's relative training-loss decrease falls below it (the
        epochs-to-tolerance criterion the DuHL-vs-uniform comparison
        measures). ``checkpointer``: optional
        ``io.checkpoint.TrainingCheckpointer`` — sweep-granular commits
        through the exchange-consistent helper; a restored run recomputes
        its scores from the saved tables through the same jitted steps
        that produced them and continues bitwise. ``resume_step`` pins
        the restore to ONE published step (ISSUE 15's coordinated
        rollback; 0 = restart from scratch, None = newest intact).
        """
        if self.schedule is None:
            self.schedule = UniformChunkSchedule(self._num_chunks_global)
        fingerprint = self._fingerprint()
        start_sweep = 0
        losses: list[float] = []
        if resume_step == 0:
            resume = False
        if checkpointer is not None and resume and state is None:
            restored = self._restore(
                checkpointer, fingerprint,
                step=resume_step if resume_step else None,
            )
            if restored is not None:
                ckpt, state = restored
                start_sweep = min(int(ckpt.step), num_sweeps)
                losses = [float(x) for x in ckpt.meta.get("losses", [])]
                losses = losses[:start_sweep]
                self.schedule.load_state(ckpt.meta["schedule_state"])
                from photon_ml_tpu.telemetry import resilience_counters

                resilience_counters.record_checkpoint_restore()
                logger.info(
                    "resuming streamed GAME from checkpoint sweep %d",
                    start_sweep,
                )
        fresh_state = state is None
        if fresh_state:
            state = self.init_state()
        scores = (
            self._zero_scores() if fresh_state
            else self.refresh_all_scores(state)
        )
        chunk_visits = 0
        for sweep in range(start_sweep, num_sweeps):
            # the schedule plans in GLOBAL chunk ids (identical state on
            # every rank — the DuHL working set is a pure function of the
            # allgathered signal); each rank executes only its own range,
            # converted to local ids (identity when unpartitioned)
            self._cache.set_pinned({
                g - self._chunk_lo for g in self.schedule.pinned()
                if self._chunk_lo <= g < self._chunk_hi
            })
            visit = [
                g - self._chunk_lo for g in self.schedule.plan_sweep()
                if self._chunk_lo <= g < self._chunk_hi
            ]
            chunk_visits += len(visit) * len(self.re_specs)
            with tracing.span("stream_game/sweep", cat="stream",
                              sweep=sweep, chunks=len(visit)):
                state = self._sweep(
                    state, scores, visit,
                    final_sweep=(sweep + 1 == num_sweeps),
                )
            self.schedule.sweep_done()
            losses.append(self._weighted_loss(scores))
            if not np.isfinite(losses[-1]):
                from photon_ml_tpu.io.checkpoint import DivergenceError

                raise DivergenceError(
                    f"streamed GAME sweep {sweep} produced non-finite loss"
                    + (
                        f"; last good checkpoint: step "
                        f"{checkpointer.latest_step()} in "
                        f"{checkpointer.directory}"
                        if checkpointer is not None else ""
                    )
                )
            if checkpointer is not None and (
                (sweep + 1) % max(1, checkpoint_every) == 0
                or sweep + 1 == num_sweeps
            ):
                arrays = {
                    "fe_coefficients": np.asarray(
                        jax.device_get(state.fe_coefficients)
                    ),
                    **{
                        f"re_tables/{k}": np.asarray(jax.device_get(v))
                        for k, v in state.re_tables.items()
                    },
                }
                commit_checkpoint(
                    checkpointer, sweep + 1, arrays,
                    {
                        "kind": "game_streaming",
                        "fingerprint": fingerprint,
                        "losses": losses,
                        "schedule_state": self.schedule.state_dict(),
                    },
                    exchange=self.exchange,
                )
            if on_sweep is not None:
                on_sweep(sweep + 1, num_sweeps, losses[-1])
            if (
                tolerance > 0.0 and len(losses) >= 2
                and abs(losses[-2] - losses[-1])
                <= tolerance * max(abs(losses[-2]), 1e-12)
            ):
                logger.info(
                    "streamed GAME reached loss plateau at sweep %d", sweep
                )
                break
        # sweeps THIS invocation ran (restored sweeps are excluded, like
        # chunk_loads/chunk_visits — per-sweep divisions of the evidence
        # stay consistent across resumes; the full loss history still
        # rides `losses`)
        sweeps_run = len(losses) - start_sweep
        stream_counters.set_game_stream_evidence(
            chunk_loads=self._cache.loads,
            chunk_visits=chunk_visits,
            sweeps=sweeps_run,
        )
        return StreamingGameResult(
            state=state,
            losses=losses,
            sweeps=sweeps_run,
            chunk_loads=self._cache.loads,
            chunk_visits=chunk_visits,
        )


# ---------------------------------------------------------------------------
# Streamed validation scoring (ISSUE 17 rider)
# ---------------------------------------------------------------------------


def score_game_stream(
    state: GameTrainState,
    source,
    task: TaskType,
    fe_feature_shard_id: str,
    re_feature_shards: "Mapping[str, str]",
    *,
    prefetch: bool = True,
    retry_policy=None,
    chunk_timeout: float = DEFAULT_CHUNK_TIMEOUT,
    return_scalars: bool = False,
) -> np.ndarray:
    """Score a held-out dataset chunk-wise against a streamed GAME model —
    the out-of-core twin of ``GameModel.score_dataset(ds) + ds.offsets``
    (the driver's validation semantics, estimators.GameEstimator.fit):
    per chunk, the FE margin plus every RE coordinate's score plus the
    chunk's offsets, scattered into an [n] host vector through the SAME
    module-level jitted steps the training sweeps use (so the streamed
    scores match the in-core path to float round-off; O(n·d) features only
    ever exist one chunk at a time). The validation source must be built
    with the TRAINING index maps and entity vocabs — entities unseen in
    training carry index -1 and score 0, exactly like the in-core build.

    ``re_feature_shards`` maps each RE type in ``state.re_tables`` to the
    feature shard its coordinate scores (RandomEffectStepSpec
    .feature_shard_id). Single-rank: each rank scores only the chunks its
    source holds. ``return_scalars=True`` additionally returns the [n]
    evaluation scalars ({labels, offsets, weights}) collected from the
    same decode pass — what a validation evaluator needs, without a
    second pass over the input.
    """
    missing = [t for t in state.re_tables if t not in re_feature_shards]
    if missing:
        raise ValueError(
            f"re_feature_shards is missing shard assignments for {missing}"
        )
    objective = GLMObjective(loss_for_task(task), 0.0, use_pallas=False)
    n = source.total_records
    dtype = solve_dtype_of(np.dtype(source.dtype))
    scores = np.zeros(n, dtype)
    scalars = (
        {k: np.zeros(n, dtype) for k in ("labels", "offsets", "weights")}
        if return_scalars else None
    )
    starts = getattr(source, "record_starts", None)
    with tracing.span("stream_game/score", cat="stream",
                      chunks=source.num_chunks):
        with ChunkPrefetcher(
            source, prefetch=prefetch, retry_policy=retry_policy,
            chunk_timeout=chunk_timeout,
        ) as chunks:
            for spec, chunk in zip(source.specs, chunks):
                m = chunk.num_records
                total = np.asarray(_fe_margin_chunk(
                    state.fe_coefficients,
                    {"features": chunk.features[fe_feature_shard_id]},
                    objective=objective,
                ), dtype)
                for re_type, table in state.re_tables.items():
                    batch = {
                        "features":
                            chunk.features[re_feature_shards[re_type]],
                        "entity_idx": chunk.entity_idx[re_type],
                    }
                    total = total + np.asarray(
                        _re_score_chunk(table, batch), dtype
                    )
                total = total + np.asarray(chunk.offsets, dtype)
                if getattr(chunk, "rows", None) is not None:
                    rows = np.asarray(chunk.rows[:m])
                elif starts is not None:
                    rows = np.arange(starts[spec.index],
                                     starts[spec.index] + m)
                else:
                    raise ValueError(
                        "the validation chunk source carries neither row "
                        "ids nor record starts — scores cannot be placed"
                    )
                scores[rows] = total[:m]
                if scalars is not None:
                    scalars["labels"][rows] = chunk.labels[:m]
                    scalars["offsets"][rows] = chunk.offsets[:m]
                    scalars["weights"][rows] = chunk.weights[:m]
    if return_scalars:
        return scores, scalars
    return scores
