"""Converged-lane scheduling for vmapped random-effect solves.

Reference parity: photon-api algorithm/RandomEffectCoordinate.scala:104-153
— the reference's per-entity local solves are INDEPENDENT Spark tasks, so
each entity pays only its own iteration count and stragglers are scheduled
around by the task scheduler. The TPU port vmaps those solves, which makes
every lane advance in lock-step to the WORST lane: with the 1e-7 relative
tolerances that never fire in f32 for warm-started small solves, every lane
pays ``max_iter`` (the ~87% RE-solve share of the fused GAME sweep,
BASELINE.md r5 decomposition). This module restores the reference's
work-follows-convergence property without giving up the vmap:

1. **Probe** — run every bucket's vmapped solve for a short probe budget
   (``LaneSchedulerConfig.probe_iterations``) and read each lane's
   convergence reason from the existing ``LaneTrace`` scalars (tiny
   device-to-host reads).
2. **Rescue** — host-compact the lanes still at MAX_ITERATIONS across
   same-(capacity, feature-width) buckets (vectorized numpy,
   ``data.game_data.compact_lane_blocks``) into power-of-two-padded rescue
   blocks — bounded jit signatures, cached across sweeps — and re-run them
   with the remaining ``max_iterations - probe_iterations`` budget, warm-
   started from their probe rows; results scatter back into the [E, d]
   coefficient table inside the same jit.
3. **Cross-sweep active sets** (opt-in via the freeze tolerances) — entities
   whose per-sweep coefficient delta and final gradient norm fall below
   threshold are frozen: skipped by later sweeps' solves (still rescored by
   the coordinate's scoring path); the final sweep always runs everyone.

The scheduling literature motivates both moves: Snap ML (arxiv 1803.06333)
derives its hierarchy wins from matching work to the per-subproblem
convergence distribution, and distributed coordinate descent (arxiv
1611.02101) observes most coordinates converge within a handful of inner
iterations after the first outer pass.

Strictly opt-in: ``OptimizerConfig.scheduler=None`` keeps the unscheduled
single-jit path bitwise-identical (tests/test_lane_scheduler.py pins it).
Scheduled solves trade the one-jit sweep for a few extra dispatches and
small host reads per bucket (each read is a sync point: the host waits
for the device, then the device for the host) — worth it exactly when the
saved lane iterations outweigh those stalls. No benchmark cell runs a
scheduled sweep yet, so the scheduler has no speed claim (ROADMAP R8 is
the cell it waits for).

use_pallas MUST stay False in every objective this module receives — the
solves are vmapped, and a baked-in pallas_call would batch into a serial
per-lane loop (dev/lint_parity.py check 6 enforces this statically).
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinates import (
    _bucket_offsets,
    _mask_padding_lanes,
    _solve_bucket_entities,
)
from photon_ml_tpu.data.game_data import compact_lane_blocks
from photon_ml_tpu.optim.common import ConvergenceReason, LaneTrace
from photon_ml_tpu.optim.optimizer import LaneSchedulerConfig, OptimizerConfig
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.telemetry.program_ledger import ledger_jit

Array = jax.Array

logger = logging.getLogger(__name__)

#: entity_rows value for compacted padding lanes: out of range for any
#: coefficient table (the mesh-padding convention of shard_inputs), so
#: gathers clamp and scatters drop
SENTINEL_ROW = np.iinfo(np.int32).max

#: rescue blocks are padded to at least this many lanes, bounding the
#: number of distinct jit signatures at log2(E) per (cap, d) group
MIN_RESCUE_LANES = 8

#: registry namespace of the scheduler counters (reset per driver run next
#: to solver/*; journaled via the drivers' registry snapshot on success AND
#: failure paths)
SCHEDULER_METRIC_PREFIX = "scheduler/"


def _pow2_lanes(m: int) -> int:
    return 1 << (max(m, MIN_RESCUE_LANES) - 1).bit_length()


@dataclasses.dataclass
class SchedulerStats:
    """Per-sweep scheduling outcome of one coordinate's bucket set."""

    lanes_total: int = 0  # valid (non-padding) lanes across all buckets
    lanes_probed: int = 0  # lanes actually solved this sweep
    lanes_rescued: int = 0  # probed lanes re-run with the remaining budget
    lanes_frozen_skipped: int = 0  # lanes skipped by the active set
    lanes_newly_frozen: int = 0
    rescue_blocks: int = 0

    def merge(self, other: "SchedulerStats") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))


# -- jitted block solvers ----------------------------------------------------
# One per projector, mirroring algorithm/coordinates.py's *_traced solvers
# with two tiny extra outputs per lane (coefficient delta and norm — the
# active-set freeze inputs). (objective, opt) are static; shapes key the jit
# cache, so power-of-two rescue padding bounds compilation.


@partial(ledger_jit, label="scheduler/solve_identity", static_argnums=(0, 1))
def _block_solve_identity(
    objective, opt: OptimizerConfig,
    features: Array, labels: Array, weights: Array,
    sample_rows: Array, entity_rows: Array,
    full_offsets: Array, table: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table[entity_rows]  # OOB sentinel lanes clamp to the last row
    solved, trace = _solve_bucket_entities(
        objective, opt, features, labels, weights, offsets, w0s
    )
    trace = _mask_padding_lanes(trace, entity_rows, table.shape[0])
    delta = jnp.linalg.norm(solved - w0s, axis=-1)
    wnorm = jnp.linalg.norm(solved, axis=-1)
    return table.at[entity_rows].set(solved), trace, delta, wnorm


@partial(ledger_jit, label="scheduler/solve_indexmap", static_argnums=(0, 1))
def _block_solve_indexmap(
    objective, opt: OptimizerConfig,
    features: Array, labels: Array, weights: Array,
    sample_rows: Array, entity_rows: Array, col_index: Array,
    full_offsets: Array, table_ext: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table_ext[entity_rows[:, None], col_index]
    solved, trace = _solve_bucket_entities(
        objective, opt, features, labels, weights, offsets, w0s
    )
    trace = _mask_padding_lanes(trace, entity_rows, table_ext.shape[0])
    delta = jnp.linalg.norm(solved - w0s, axis=-1)
    wnorm = jnp.linalg.norm(solved, axis=-1)
    table_ext = table_ext.at[entity_rows[:, None], col_index].set(solved)
    return table_ext.at[:, -1].set(0.0), trace, delta, wnorm


@partial(ledger_jit, label="scheduler/solve_random", static_argnums=(0, 1))
def _block_solve_random(
    objective, opt: OptimizerConfig,
    features: Array, labels: Array, weights: Array,
    sample_rows: Array, entity_rows: Array, matrix: Array,
    full_offsets: Array, table: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table[entity_rows] @ matrix
    solved, trace = _solve_bucket_entities(
        objective, opt, features, labels, weights, offsets, w0s
    )
    trace = _mask_padding_lanes(trace, entity_rows, table.shape[0])
    delta = jnp.linalg.norm(solved - w0s, axis=-1)
    wnorm = jnp.linalg.norm(solved, axis=-1)
    return table.at[entity_rows].set(solved @ matrix.T), trace, delta, wnorm


@partial(ledger_jit, label="scheduler/extend_scratch")
def _extend_scratch(table: Array) -> Array:
    """[E, d] -> [E, d+1]: the INDEX_MAP scratch column that absorbs padding
    gather/scatter slots (algorithm/coordinates.py convention)."""
    return jnp.concatenate(
        [table, jnp.zeros((table.shape[0], 1), table.dtype)], axis=1
    )


@partial(ledger_jit, label="scheduler/strip_scratch")
def _strip_scratch(table_ext: Array) -> Array:
    return table_ext[:, :-1]


class LaneScheduler:
    """Per-coordinate probe/rescue state, persisted across sweeps.

    Holds the host copies of the bucket structure (read once — buckets are
    immutable across sweeps; only the table and offsets change), the frozen
    active-set mask, and the carried per-lane (value, gradient-norm) scalars
    that frozen lanes report to telemetry. Create one per random-effect
    coordinate and reuse it for every sweep; a fresh instance per call works
    but re-reads the bucket arrays to the host each time.

    ``mesh``: None (default) is the single-process host mode — compaction
    reads whole bucket arrays. Passing the training mesh switches to the
    COLLECTIVE-SAFE SPMD mode (the multi-process path): per-lane flags are
    read through a tiled ``process_allgather`` (a collective every rank
    makes), compaction is RANK-LOCAL over this rank's addressable bucket
    shards only, and the compacted stragglers assemble into one fixed
    ``[num_ranks * R]``-lane rescue block (R a power of two derived from
    the globally-agreed straggler maximum; ranks with fewer stragglers pad
    with sentinel lanes) — the same jit signature on every rank every
    sweep, so SPMD ranks stay in lock-step and ``train_distributed`` no
    longer falls back on multi-process runs.
    """

    def __init__(self, config: LaneSchedulerConfig, registry=None,
                 mesh=None, warn_no_live_stop: bool = True):
        #: set False when the probe IS the whole solve (the refresh policy:
        #: probe budget == max_iterations, no rescue) — the "probe flags
        #: rarely fire without a live function stop" warning only applies
        #: when a rescue phase exists to waste
        self.config = config
        self.mesh = mesh
        self._registry = registry
        self._warned_no_live_stop = not warn_no_live_stop
        self._host_blocks: list[dict[str, np.ndarray]] | None = None
        #: SPMD mode: (rank-local field slices, base row, owner map) per
        #: bucket — built lazily like the host cache
        self._spmd_blocks: list[dict] | None = None
        #: bool [table rows]; grows monotonically until the final sweep
        self.frozen_rows: np.ndarray | None = None
        #: per-block (value, gradient_norm) carried for lanes a later sweep
        #: skips (frozen lanes still appear in lane traces, with iterations 0)
        self._carry: list[tuple[np.ndarray, np.ndarray]] | None = None
        self.total_stats = SchedulerStats()
        self.last_stats: SchedulerStats | None = None
        self._num_rows: int | None = None

    # -- SPMD (collective-safe) helpers --------------------------------------

    def _gather_np(self, x):
        """Host copy of a per-lane device array — or a PYTREE of them,
        gathered in ONE collective (per-call dispatch is ~100 ms on this
        platform; never loop scalars through separate gathers). SPMD mode
        on a multi-process run allgathers (a COLLECTIVE — every rank
        calls it for every solve, by construction of the shared solve()
        flow); otherwise a plain device read."""
        import jax

        if self.mesh is not None and jax.process_count() > 1:
            from jax.experimental import multihost_utils

            x = multihost_utils.process_allgather(x, tiled=True)
        return jax.tree_util.tree_map(np.asarray, x)

    def _spmd_cache(self, blocks: Sequence[Mapping[str, Array]]):
        """Rank-local addressable slices + global owner maps, built once
        (buckets are immutable across sweeps)."""
        if self._spmd_blocks is None:
            self._spmd_blocks = [
                _rank_local_block(b) for b in blocks
            ]
        if len(self._spmd_blocks) != len(blocks):
            raise ValueError(
                "LaneScheduler is per-coordinate state: it was built over "
                f"{len(self._spmd_blocks)} buckets but is now asked to "
                f"schedule {len(blocks)} — create one scheduler per "
                "random-effect coordinate"
            )
        return self._spmd_blocks

    def registry(self):
        if self._registry is None:
            from photon_ml_tpu.telemetry.registry import default_registry

            self._registry = default_registry()
        return self._registry

    def freeze_rows(self, mask: np.ndarray) -> None:
        """Pre-seed the active set: coefficient-table rows True in ``mask``
        are FROZEN — a ``solve(final_sweep=False)`` skips their lanes
        (compacting the rest) and never scatters into their rows, so they
        carry over bitwise. This is the refresh-policy entry point
        (algorithm/refresh.py): "retrain only what changed" is the
        cross-sweep active set handed in from outside instead of grown
        from per-sweep convergence — the freeze tolerances need not be
        configured for a preset to take effect."""
        self.frozen_rows = np.ascontiguousarray(mask, dtype=bool).copy()

    def _host_cache(self, blocks: Sequence[Mapping[str, Array]]):
        if self._host_blocks is None:
            # one device-to-host read per field per bucket, amortized over
            # every later sweep (single-process only: a multi-process
            # sharded bucket is not addressable — callers gate on that)
            self._host_blocks = [
                {k: np.asarray(v) for k, v in b.items()} for b in blocks
            ]
        if len(self._host_blocks) != len(blocks):
            raise ValueError(
                "LaneScheduler is per-coordinate state: it was built over "
                f"{len(self._host_blocks)} buckets but is now asked to "
                f"schedule {len(blocks)} — create one scheduler per "
                "random-effect coordinate"
            )
        return self._host_blocks

    # -- the scheduled solve -------------------------------------------------

    def solve(
        self,
        objective,
        opt: OptimizerConfig,
        blocks: Sequence[Mapping[str, Array]],
        full_offsets: Array,
        table: Array,
        *,
        projector: ProjectorType = ProjectorType.IDENTITY,
        matrix: Array | None = None,
        final_sweep: bool = True,
    ) -> tuple[Array, list[LaneTrace], SchedulerStats]:
        """Probe + rescue (+ active-set skip) over one coordinate's buckets.

        blocks: bucket field dicts (features/labels/weights/sample_rows/
            entity_rows[/col_index]) — the shapes the unscheduled solvers
            consume. ``table`` is the RAW [E, d] coefficient table for every
            projector (the INDEX_MAP scratch column is handled internally).
        Returns (updated table, per-bucket numpy LaneTraces, stats). A
        frozen (skipped) lane reports iterations=0 with its carried value/
        gradient norm and reason FUNCTION_VALUES_WITHIN_TOLERANCE — the
        freeze criterion is a function-decrease statement.
        """
        cfg = self.config
        stats = SchedulerStats()
        if not blocks:
            self.last_stats = stats
            return table, [], stats
        from photon_ml_tpu.optim.optimizer import OptimizerType

        if (
            opt.rel_function_tolerance is None
            and opt.optimizer_type in (OptimizerType.LBFGS, OptimizerType.OWLQN)
            and not self._warned_no_live_stop
        ):
            # without a live function-decrease stop, warm-started LBFGS/OWLQN
            # lanes rarely flag converged after the probe (the CLAUDE.md
            # tolerance landmine): every lane gets rescued every sweep and
            # the scheduler only ADDS dispatch/compaction cost
            self._warned_no_live_stop = True
            logger.warning(
                "lane scheduler active with optimizer_type=%s but no "
                "rel_function_tolerance: probe convergence flags rarely fire "
                "at the plain tolerance for warm starts, so most lanes will "
                "be rescued anyway — set rel_function_tolerance (e.g. 1e-6) "
                "to get the probe/rescue win",
                opt.optimizer_type.name,
            )

        indexmap = projector == ProjectorType.INDEX_MAP
        if indexmap:
            table = _extend_scratch(table)
        num_rows = int(table.shape[0])
        # per-coordinate contract, checked even on no-compaction sweeps:
        # frozen_rows/_carry sized for another coordinate's table would
        # silently skip the wrong entities instead of raising
        if self._num_rows is None:
            self._num_rows = num_rows
        elif self._num_rows != num_rows:
            raise ValueError(
                "LaneScheduler is per-coordinate state: it was built over a "
                f"{self._num_rows}-row coefficient table but is now asked to "
                f"schedule a {num_rows}-row one — create one scheduler per "
                "random-effect coordinate"
            )

        probe_iters = max(1, min(cfg.probe_iterations, opt.max_iterations))
        rescue_budget = opt.max_iterations - probe_iters
        base_opt = dataclasses.replace(opt, scheduler=None)
        probe_opt = dataclasses.replace(base_opt, max_iterations=probe_iters)
        rescue_opt = (
            dataclasses.replace(base_opt, max_iterations=rescue_budget)
            if rescue_budget > 0 else None
        )

        def run_block(b: Mapping[str, Array], o: OptimizerConfig, tab: Array):
            if indexmap:
                return _block_solve_indexmap(
                    objective, o, b["features"], b["labels"], b["weights"],
                    b["sample_rows"], b["entity_rows"], b["col_index"],
                    full_offsets, tab,
                )
            if projector == ProjectorType.RANDOM:
                return _block_solve_random(
                    objective, o, b["features"], b["labels"], b["weights"],
                    b["sample_rows"], b["entity_rows"], matrix,
                    full_offsets, tab,
                )
            return _block_solve_identity(
                objective, o, b["features"], b["labels"], b["weights"],
                b["sample_rows"], b["entity_rows"], full_offsets, tab,
            )

        freezing = cfg.freezes
        frozen = self.frozen_rows
        if freezing and frozen is None:
            frozen = np.zeros(num_rows, dtype=bool)
        # a preset active set (freeze_rows — the refresh policy) skips even
        # when the per-sweep freeze tolerances are off; only the tolerance-
        # driven active-set GROWTH below stays gated on cfg.freezes
        skipping = freezing or frozen is not None
        if frozen is not None and len(frozen) != num_rows:
            raise ValueError(
                f"frozen-row mask covers {len(frozen)} rows but the "
                f"coefficient table has {num_rows} — freeze_rows() masks "
                "must match the coordinate's table"
            )

        # host lane bookkeeping (entity_rows only — cheap; the full host
        # bucket cache is built lazily, first time compaction is needed).
        # SPMD mode allgathers, so every rank sees the same global arrays.
        rows_h = [
            r.astype(np.int64)
            for r in self._gather_np(tuple(b["entity_rows"] for b in blocks))
        ]
        valid_h = [(r >= 0) & (r < num_rows) for r in rows_h]
        if skipping and not final_sweep and frozen.any():
            skip_h = [
                v & frozen[np.clip(r, 0, num_rows - 1)]
                for r, v in zip(rows_h, valid_h)
            ]
        else:
            skip_h = [np.zeros(len(r), dtype=bool) for r in rows_h]
        solve_h = [v & ~s for v, s in zip(valid_h, skip_h)]
        stats.lanes_total = int(sum(v.sum() for v in valid_h))
        stats.lanes_frozen_skipped = int(sum(s.sum() for s in skip_h))

        # per-block output arrays; frozen lanes keep carried scalars
        e_sizes = [len(r) for r in rows_h]
        iters_out = [np.zeros(e, np.int64) for e in e_sizes]
        reason_out = [
            np.full(e, int(ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE),
                    np.int64)
            for e in e_sizes
        ]
        value_out = [np.zeros(e, np.float64) for e in e_sizes]
        gnorm_out = [np.zeros(e, np.float64) for e in e_sizes]
        delta_out = [np.zeros(e, np.float64) for e in e_sizes]
        wnorm_out = [np.zeros(e, np.float64) for e in e_sizes]
        if self._carry is not None:
            for i, (cv, cg) in enumerate(self._carry):
                value_out[i][:] = cv
                gnorm_out[i][:] = cg

        def scatter_back(trace, delta, wnorm, blk, lane):
            """Write one solved block's per-lane scalars back into the
            per-original-bucket output arrays; (blk, lane) name the source
            of each REAL lane (padding lanes carry blk == -1 and never
            land anywhere). Iterations and deltas ADD (probe + rescue
            accumulate); the rest overwrite. SPMD mode reads the trace
            through the allgather — a collective every rank makes."""
            it, rs, vl, gn, dl, wn = self._gather_np(
                (trace.iterations, trace.reason, trace.value,
                 trace.gradient_norm, delta, wnorm)
            )
            for i in range(len(blocks)):
                mask = blk == i
                if not mask.any():
                    continue
                li = lane[mask]
                iters_out[i][li] += it[mask]
                reason_out[i][li] = rs[mask]
                value_out[i][li] = vl[mask]
                gnorm_out[i][li] = gn[mask]
                delta_out[i][li] += dl[mask]
                wnorm_out[i][li] = wn[mask]

        def run_compacted(lane_masks, o: OptimizerConfig, tab):
            """Solve only the masked lanes, grouped by (cap, d): host-mode
            compaction over whole bucket arrays, or rank-local SPMD
            compaction into fixed [num_ranks * R] blocks (the collective-
            safe path). Returns (table, lanes solved, blocks run)."""
            solved = 0
            n_blocks = 0
            if self.mesh is not None:
                local = self._spmd_cache(blocks)
                # _group_by_shape only reads shapes — fine on device blocks
                for picks in _group_by_shape(blocks, lane_masks):
                    tab, n = self._run_spmd_block(
                        picks, local, o, run_block, tab, scatter_back
                    )
                    solved += n
                    n_blocks += 1
                return tab, solved, n_blocks
            host = self._host_cache(blocks)
            for picks in _group_by_shape(host, lane_masks):
                pad_to = _pow2_lanes(sum(len(l) for _, l in picks))
                with tracing.span("scheduler/compaction", cat="scheduler",
                                  lanes=int(sum(len(l) for _, l in picks))):
                    fields, src_blk, src_lane = compact_lane_blocks(
                        host, picks, pad_to=pad_to, sentinel_row=SENTINEL_ROW,
                    )
                tab, trace, delta, wnorm = run_block(
                    _device_block(fields), o, tab
                )
                scatter_back(trace, delta, wnorm,
                             _pad_minus1(src_blk, pad_to),
                             _pad_zeros(src_lane, pad_to))
                solved += len(src_lane)
                n_blocks += 1
            return tab, solved, n_blocks

        # -- probe phase ----------------------------------------------------
        any_skip = any(s.any() for s in skip_h)
        with tracing.span("scheduler/probe", cat="scheduler",
                          lanes=stats.lanes_total,
                          frozen_skipped=stats.lanes_frozen_skipped):
            if not any_skip:
                # full buckets, original shapes — the same signatures the
                # unscheduled path compiles
                for i, b in enumerate(blocks):
                    table, trace, delta, wnorm = run_block(b, probe_opt, table)
                    blk = np.where(solve_h[i], i, -1).astype(np.int32)
                    lane = np.arange(e_sizes[i], dtype=np.int64)
                    scatter_back(trace, delta, wnorm, blk, lane)
                stats.lanes_probed = int(sum(s.sum() for s in solve_h))
            else:
                # active-set compaction: only unfrozen lanes probe
                table, probed, _ = run_compacted(solve_h, probe_opt, table)
                stats.lanes_probed = probed

        # -- rescue phase ---------------------------------------------------
        rescue_h = [
            s & (r_out == int(ConvergenceReason.MAX_ITERATIONS))
            for s, r_out in zip(solve_h, reason_out)
        ]
        n_rescue = int(sum(r.sum() for r in rescue_h))
        if rescue_opt is not None and n_rescue:
            with tracing.span("scheduler/rescue", cat="scheduler",
                              lanes=n_rescue):
                table, _, rescue_blocks = run_compacted(
                    rescue_h, rescue_opt, table
                )
            stats.rescue_blocks += rescue_blocks
            stats.lanes_rescued = n_rescue

        # -- active-set update ----------------------------------------------
        if freezing and not final_sweep:
            ftol = cfg.freeze_coefficient_tolerance
            gtol = cfg.freeze_gradient_tolerance
            for i in range(len(blocks)):
                sel = solve_h[i]
                quiet = (
                    sel
                    & (delta_out[i] <= ftol * (1.0 + wnorm_out[i]))
                    & (gnorm_out[i] <= gtol)
                )
                if quiet.any():
                    frozen[rows_h[i][quiet]] = True
                    stats.lanes_newly_frozen += int(quiet.sum())
            self.frozen_rows = frozen
        if final_sweep:
            # the active set does not outlive its training run
            self.frozen_rows = None

        self._carry = [
            (value_out[i].copy(), gnorm_out[i].copy())
            for i in range(len(blocks))
        ]

        traces = [
            LaneTrace(
                iterations=iters_out[i],
                reason=reason_out[i],
                value=value_out[i],
                gradient_norm=gnorm_out[i],
                valid=valid_h[i],
                # provenance: these lanes are observed into the
                # solver/lane_iters histogram below — telemetry consumers
                # (SolverTelemetry.record_lanes) must not count them again
                scheduled=True,
            )
            for i in range(len(blocks))
        ]
        self._record(stats, traces)
        self.last_stats = stats
        self.total_stats.merge(stats)
        if indexmap:
            table = _strip_scratch(table)
        return table, traces, stats

    def _run_spmd_block(self, picks, local, opt: OptimizerConfig,
                        run_block, table, scatter_back):
        """One same-(cap, d) group's compacted solve, collective-safe.

        Every rank computes the identical global layout (per-rank straggler
        assignment from the owner maps, R from the global per-rank maximum),
        builds ONLY its own rank's [R]-lane block from its addressable
        shard rows (sentinel-padding the spare lanes), and assembles the
        global [num_ranks * R] block via ``assemble_partitioned`` — so the
        solve jit (a collective SPMD program) sees the same signature on
        every rank, every sweep. Returns (table, lanes solved).
        """
        import jax
        from jax.sharding import PartitionSpec as P

        from photon_ml_tpu.parallel.multihost import assemble_partitioned

        num_ranks = jax.process_count()
        my_rank = jax.process_index()
        data_axis = int(self.mesh.shape["data"])
        if data_axis % num_ranks:
            raise ValueError(
                f"SPMD lane scheduling: mesh data axis {data_axis} must be "
                f"a multiple of the process count {num_ranks}"
            )
        dpr = data_axis // num_ranks  # devices per rank along "data"

        per_rank: list[list[tuple[int, np.ndarray]]] = [
            [] for _ in range(num_ranks)
        ]
        for b, lanes in picks:
            owner = local[b]["owner"]
            for r in range(num_ranks):
                sel = lanes[owner[lanes] == r]
                if len(sel):
                    per_rank[r].append((b, sel))
        max_count = max(
            sum(len(l) for _, l in pr) for pr in per_rank
        )
        rescue_lanes = _pow2_lanes(max(max_count, dpr))
        # round up to a multiple of the per-rank device count so the fixed
        # [num_ranks * rescue_lanes] block shards evenly over "data" on a
        # non-power-of-two dpr too (one value per pow2 tier, so the jit
        # signature set stays bounded; spare lanes are sentinel-padded)
        rescue_lanes = -(-rescue_lanes // dpr) * dpr

        # the global (block, lane) source map — identical on every rank
        src_blk = np.full(num_ranks * rescue_lanes, -1, np.int32)
        src_lane = np.zeros(num_ranks * rescue_lanes, np.int64)
        for r in range(num_ranks):
            j = r * rescue_lanes
            for b, lanes in per_rank[r]:
                src_blk[j: j + len(lanes)] = b
                src_lane[j: j + len(lanes)] = lanes
                j += len(lanes)

        # THIS rank's block only, from its addressable shard rows
        loc_picks = [
            (b, lanes - local[b]["base"]) for b, lanes in per_rank[my_rank]
        ]
        for (b, lanes), (_, loc) in zip(per_rank[my_rank], loc_picks):
            if len(loc) and (loc.min() < 0 or loc.max() >= local[b]["size"]):
                raise ValueError(
                    f"bucket {b}: owned lanes fall outside this rank's "
                    "addressable shard — the mesh 'data' axis must be "
                    "process-contiguous"
                )
        if loc_picks:
            fields, _, _ = compact_lane_blocks(
                [l["fields"] for l in local], loc_picks,
                pad_to=rescue_lanes, sentinel_row=SENTINEL_ROW,
            )
        else:
            fields = _sentinel_block(
                local[picks[0][0]]["fields"], rescue_lanes
            )

        specs = {
            "features": P("data", None, None),
            "labels": P("data", None),
            "weights": P("data", None),
            "sample_rows": P("data", None),
            "entity_rows": P("data"),
            "col_index": P("data", None),
        }
        assembled = {
            k: assemble_partitioned(
                {my_rank: v}, self.mesh, specs[k], num_ranks
            )
            for k, v in fields.items()
        }
        table, trace, delta, wnorm = run_block(assembled, opt, table)
        scatter_back(trace, delta, wnorm, src_blk, src_lane)
        return table, int((src_blk >= 0).sum())

    def _record(self, stats: SchedulerStats, traces: Sequence[LaneTrace]):
        """Feed the scheduler counters and the solver/lane_iters histogram
        (telemetry/registry.py conventions; journaled by the drivers'
        registry snapshot on success and failure paths)."""
        reg = self.registry()
        p = SCHEDULER_METRIC_PREFIX
        reg.counter(p + "sweeps").inc()
        reg.counter(p + "lanes_probed").inc(stats.lanes_probed)
        reg.counter(p + "lanes_rescued").inc(stats.lanes_rescued)
        reg.counter(p + "lanes_frozen_skipped").inc(stats.lanes_frozen_skipped)
        reg.counter(p + "rescue_blocks").inc(stats.rescue_blocks)
        if self.frozen_rows is not None:
            reg.gauge(p + "frozen_rows").set(int(self.frozen_rows.sum()))
        # the canonical per-lane iteration histogram (record_lanes skips
        # scheduler-produced traces, so lanes land here exactly once)
        from photon_ml_tpu.telemetry.solver_trace import LANE_ITERS_METRIC

        hist = reg.histogram(LANE_ITERS_METRIC)
        for t in traces:
            hist.observe_many(
                np.asarray(t.iterations)[np.asarray(t.valid)].tolist()
            )


def make_schedulers(re_specs, mesh=None, registry=None) -> dict:
    """One LaneScheduler per RE spec whose OptimizerConfig carries a
    scheduler config — the ONE mode-selection rule shared by
    ``train_distributed`` and ``train_partitioned``: collective-safe SPMD
    mode on multi-process runs (requires the training mesh), single-process
    host mode otherwise (bit-for-bit the pre-SPMD behavior)."""
    import jax

    spmd_mesh = mesh if jax.process_count() > 1 else None
    return {
        s.re_type: LaneScheduler(
            s.optimizer.scheduler, registry=registry, mesh=spmd_mesh
        )
        for s in re_specs
        if s.optimizer.scheduler is not None
    }


def _pad_minus1(arr: np.ndarray, length: int) -> np.ndarray:
    out = np.full(length, -1, np.int32)
    out[: len(arr)] = arr
    return out


def _pad_zeros(arr: np.ndarray, length: int) -> np.ndarray:
    out = np.zeros(length, np.int64)
    out[: len(arr)] = arr
    return out


def _sentinel_block(sample_fields: Mapping[str, np.ndarray],
                    lanes: int) -> dict[str, np.ndarray]:
    """An all-padding [lanes] block shaped like ``sample_fields`` — what a
    rank with zero stragglers contributes (weight 0 / sample_rows -1 /
    entity_rows sentinel: inert in the solve, dropped by the scatter)."""
    out = {}
    for k, arr in sample_fields.items():
        if k == "entity_rows":
            out[k] = np.full(lanes, SENTINEL_ROW, np.int32)
        elif k == "sample_rows":
            out[k] = np.full((lanes,) + arr.shape[1:], -1, arr.dtype)
        else:
            out[k] = np.zeros((lanes,) + arr.shape[1:], arr.dtype)
    return out


def _addressable_rows(arr) -> tuple[int, int, np.ndarray]:
    """(base, stop, rows) — the contiguous lane-axis slice of ``arr`` this
    process can read. Model-axis replicas (same row range on several local
    devices) dedup; a non-contiguous addressable range is rejected (SPMD
    lane scheduling requires the standard process-contiguous 'data'
    layout, the same contract as multihost.assemble_partitioned)."""
    arr = jnp.asarray(arr)
    pieces: dict[tuple[int, int], object] = {}
    for s in arr.addressable_shards:
        sl = s.index[0] if s.index else slice(None)
        start = 0 if sl.start is None else int(sl.start)
        stop = int(arr.shape[0]) if sl.stop is None else int(sl.stop)
        pieces.setdefault((start, stop), s)
    spans = sorted(pieces)
    expect = spans[0][0]
    datas = []
    for start, stop in spans:
        if start != expect:
            raise ValueError(
                "addressable shards are not contiguous along the lane "
                "axis; SPMD lane scheduling needs a process-contiguous "
                "'data' axis"
            )
        expect = stop
        datas.append(np.asarray(pieces[(start, stop)].data))
    return spans[0][0], expect, np.concatenate(datas, axis=0)


def _owner_map(arr) -> np.ndarray:
    """[lanes] int32: the lowest process index holding each lane — the
    rank that compacts it. Identical on every rank (computed from the
    GLOBAL device->index map, not from addressable state)."""
    arr = jnp.asarray(arr)
    owner = np.full(int(arr.shape[0]), np.iinfo(np.int32).max, np.int32)
    for dev, idx in arr.sharding.devices_indices_map(arr.shape).items():
        sl = idx[0] if idx else slice(None)
        start = 0 if sl.start is None else int(sl.start)
        stop = int(arr.shape[0]) if sl.stop is None else int(sl.stop)
        p = np.int32(getattr(dev, "process_index", 0))
        owner[start:stop] = np.minimum(owner[start:stop], p)
    return owner


def _rank_local_block(b: Mapping[str, Array]) -> dict:
    """SPMD cache entry for one bucket: this rank's addressable field
    slices (one device-to-host read each, amortized across sweeps), their
    common base row, and the global lane->owner-rank map."""
    fields = {}
    base = size = None
    for k, v in b.items():
        lo, hi, rows = _addressable_rows(v)
        if base is None:
            base, size = lo, hi - lo
        elif (lo, hi - lo) != (base, size):
            raise ValueError(
                f"bucket field '{k}' spans rows [{lo}, {hi}) but other "
                f"fields span [{base}, {base + size}) — bucket fields "
                "must share one lane-axis sharding"
            )
        fields[k] = rows
    return {
        "fields": fields,
        "base": int(base),
        "size": int(size),
        "owner": _owner_map(b["entity_rows"]),
    }


def _device_block(fields: dict[str, np.ndarray]) -> dict[str, Array]:
    return {k: jnp.asarray(v) for k, v in fields.items()}


def _group_by_shape(
    host_blocks: Sequence[Mapping[str, np.ndarray]],
    lane_masks: Sequence[np.ndarray],
) -> list[list[tuple[int, np.ndarray]]]:
    """Group selected (block, lanes) picks by (capacity, feature width) so
    each compacted block mixes only shape-compatible lanes."""
    groups: dict[tuple[int, int], list[tuple[int, np.ndarray]]] = {}
    for i, mask in enumerate(lane_masks):
        lanes = np.flatnonzero(mask)
        if not len(lanes):
            continue
        f = host_blocks[i]["features"]
        groups.setdefault((f.shape[1], f.shape[2]), []).append((i, lanes))
    return list(groups.values())
