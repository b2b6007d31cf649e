"""GAME training driver: the flagship end-to-end CLI entry point.

Reference parity: photon-client cli/game/training/GameTrainingDriver.scala —
params (:78-166), run() pipeline (:335-471): read + validate data, feature
stats, normalization contexts, λ-grid expansion (:612-621), GameEstimator
fit per configuration warm-starting from the previous (:352-366), optional
hyperparameter tuning (:631-663), model selection (:672-737), model save
(:748-815); shared GameDriver params (cli/game/GameDriver.scala:56-132).

Usage:
    python -m photon_ml_tpu.cli.game_training_driver \
        --input-data-path data/train --validation-data-path data/val \
        --root-output-dir out \
        --feature-shard-configurations name=global,feature.bags=features \
        --coordinate-configurations name=fe,feature.shard=global,reg.weights=0.1|1|10 \
        --task-type LOGISTIC_REGRESSION --evaluators AUC
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
from typing import Sequence

import numpy as np

from photon_ml_tpu.cli.configs import (
    CoordinateCliConfig,
    ModelOutputMode,
    estimator_coordinate_configs,
    evaluation_id_columns,
    expand_reg_weight_grid,
    format_coordinate_config,
    parse_coordinate_config,
    parse_feature_shard_config,
)
from photon_ml_tpu.data.batch import summarize
from photon_ml_tpu.data.sparse_batch import SparseShard
from photon_ml_tpu.data.validators import DataValidationType, validate_game_dataset
from photon_ml_tpu.estimators import GameEstimator
from photon_ml_tpu.evaluation.evaluators import parse_evaluator
from photon_ml_tpu.hyperparameter.game_glue import (
    GameHyperparameterTuner,
    HyperparameterTuningMode,
    load_prior_observations,
    save_tuned_config,
)
from photon_ml_tpu.io.index_map import IndexMap
from photon_ml_tpu.io.partitioned_reader import read_partitioned
from photon_ml_tpu.io.model_io import (
    DEFAULT_COMPACT_RE_THRESHOLD,
    load_game_model,
    save_game_model,
    write_feature_stats,
)
from photon_ml_tpu.ops.normalization import NormalizationType
from photon_ml_tpu.optim.optimizer import OptimizerType
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.telemetry import RunJournal, SolverTelemetry, default_registry
from photon_ml_tpu.telemetry.layout import reset_layout_metrics
from photon_ml_tpu.telemetry.probes import CompileMonitor, live_buffer_bytes
from photon_ml_tpu.telemetry.refresh_counters import reset_refresh_metrics
from photon_ml_tpu.telemetry.resilience_counters import reset_resilience_metrics
from photon_ml_tpu.telemetry.solver_trace import reset_solver_metrics
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.util import (
    EventEmitter,
    PhotonLogger,
    Timed,
    TrainingFinishEvent,
    TrainingStartEvent,
)
from photon_ml_tpu.util.timed import reset_timings, timing_summary

logger = logging.getLogger(__name__)

#: process-wide emitter; external telemetry registers listeners here
#: (reference Driver event emission, Driver.scala:120-393)
events = EventEmitter()


@dataclasses.dataclass
class GameTrainingParams:
    """Validated driver parameters (reference GameTrainingDriver params)."""

    input_data_path: str
    root_output_dir: str
    feature_shards: dict
    coordinates: dict[str, CoordinateCliConfig]
    task_type: TaskType
    validation_data_path: str | None = None
    #: "yyyyMMdd-yyyyMMdd" or "N-M" days-ago; expands the input path into
    #: its <base>/daily/yyyy/MM/dd subdirectories (reference GameDriver
    #: date-range params + IOUtils.getInputPathsWithinDateRange)
    input_date_range: str | None = None
    validation_data_date_range: str | None = None
    update_sequence: tuple[str, ...] = ()
    coordinate_descent_iterations: int = 1
    evaluators: tuple[str, ...] = ()
    normalization: NormalizationType = NormalizationType.NONE
    data_validation: DataValidationType = DataValidationType.VALIDATE_DISABLED
    model_input_dir: str | None = None  # warm start
    partial_retrain_locked_coordinates: tuple[str, ...] = ()
    model_output_mode: ModelOutputMode = ModelOutputMode.ALL
    hyperparameter_tuning: HyperparameterTuningMode = HyperparameterTuningMode.NONE
    hyperparameter_tuning_iter: int = 10
    hyperparameter_tuning_range: tuple[float, float] = (1e-4, 1e4)
    #: tuned-hyperparameters.json from a previous run, used as search priors
    #: (reference HyperparameterSerialization)
    hyperparameter_prior_json: str | None = None
    input_format: str = "avro"
    #: reuse index stores built by feature_indexing_driver (plain .keys or
    #: native off-heap .photonix) instead of scanning the data
    index_maps_dir: str | None = None
    override_output: bool = False
    #: mid-training checkpoint/resume (io/checkpoint.py); one subdirectory
    #: per λ-grid configuration. Empty = disabled.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    resume: bool = True
    #: jax.profiler trace output dir (TensorBoard); empty = disabled
    profile_dir: str | None = None
    #: warm-start models whose RE feature space exceeds this load compact
    compact_random_effect_threshold: int = DEFAULT_COMPACT_RE_THRESHOLD
    #: train through the fused mesh-sharded SPMD program
    #: (parallel/distributed.py) instead of the host-loop CD path — the
    #: cluster-scale mode of the reference driver
    #: (GameTrainingDriver.scala:822-843). ``mesh_shape`` lays the devices
    #: out as {"data": N, "model": M}; empty with distributed=True means all
    #: devices on "data".
    distributed: bool = False
    mesh_shape: dict[str, int] | None = None
    #: structured-telemetry output dir: a rank-0 JSONL run journal (config
    #: summary, phase timings, per-coordinate convergence rows, compile and
    #: HBM gauges) finalized on completion; None = disabled
    telemetry_dir: str | None = None
    #: run-trace output dir (telemetry/tracing.py): EVERY rank exports its
    #: host-side span timeline as Chrome-trace JSON (trace-{rank:05d}.json
    #: — rank-0 mkdir, barrier, per-rank write, the score-writer carve-out)
    #: and a rank-merged straggler report is journaled at run end. Flushed
    #: on success AND failure paths; None = disabled (zero overhead).
    trace_dir: str | None = None
    #: partitioned host I/O (io/partitioned_reader.py): on a multi-process
    #: run each rank decodes only ~1/P of the input bytes and feeds its
    #: local block as addressable shards of the global arrays. Opt-in:
    #: v1 supports dense shards + IDENTITY random effects without
    #: normalization/validation riders, and entities spanning rank
    #: partitions solve per-rank (entity-cluster the input for exact
    #: full-read parity). Single-process runs are unaffected.
    partitioned_io: bool = False
    #: corrupt-input handling for Avro ingestion: "raise" (strict,
    #: default) or "quarantine" (skip-and-count corrupt container blocks;
    #: spans journaled — io/avro.py, resilience layer)
    on_corrupt: str = "raise"
    #: crash-safe recovery budget: a mid-sweep DivergenceError (with a
    #: checkpoint to restore) or classified-transient failure restarts the
    #: configuration — resuming from the latest intact checkpoint — up to
    #: this many times before the error propagates
    #: (resilience/recovery.py). 0 disables recovery.
    max_restarts: int = 2
    #: out-of-core streamed GAME (ISSUE 11): records per chunk (> 0 opts
    #: in). The input streams as entity-clustered fixed-shape chunks
    #: through the one-jitted-step accumulators
    #: (io/stream_reader.GameAvroChunkSource +
    #: algorithm/streaming_game.StreamingGameProgram) — n bounded by disk,
    #: not HBM. Requires an entity-sorted Avro input (sorted by the first
    #: random-effect coordinate's id column). 0 = off (default), the
    #: unchanged in-core path.
    streaming_chunks: int = 0
    #: double-buffered chunk decode; --no-streaming-prefetch is the
    #: same-run OFF baseline for overlap measurements
    streaming_prefetch: bool = True
    #: DuHL importance-ordered chunk schedule (arXiv:1702.07005): > 0 pins
    #: this many gap-hottest chunks resident and streams the cold tail
    #: round-robin. 0 (default) = uniform order, bitwise-identical to the
    #: unscheduled streamed sweep.
    duhl_working_set: int = 0
    #: cold-tail chunks revisited per sweep under the DuHL schedule
    duhl_tail_chunks: int = 1
    #: incremental retrain (ISSUE 14, algorithm/refresh.py): re-solve only
    #: the random-effect entities that saw new data or whose gradient at
    #: the resident solution exceeds tolerance, against frozen residuals
    #: from the resident model's scores — a refresh costs ~the changed
    #: entities' solve time, not a full GAME fit. Strictly opt-in: off is
    #: the unchanged full-fit path. Needs a resident model
    #: (--model-input-dir, or --checkpoint-dir warm-start re-entry).
    incremental_refresh: bool = False
    #: gradient screen: re-solve entities whose solve-space gradient norm
    #: at the resident solution exceeds this (<= 0 disables the screen —
    #: only declared entities re-solve)
    refresh_gradient_tolerance: float = 1e-4
    #: raw "reType=key1|key2" specs: entities DECLARED changed (the ingest
    #: layer's knowledge); the gradient screen catches undeclared drift
    refresh_changed_entities: tuple[str, ...] = ()
    #: also re-solve fixed-effect coordinates (warm-started) — off by
    #: default: the FE is the slow-moving global part a refresh skips
    refresh_fixed_effects: bool = False

    def validate(self) -> None:
        """Cross-parameter checks (reference validateParams:196-298)."""
        problems = []
        if self.on_corrupt not in ("raise", "quarantine"):
            problems.append(
                f"--on-corrupt must be 'raise' or 'quarantine', got "
                f"{self.on_corrupt!r}"
            )
        if self.max_restarts < 0:
            problems.append("--max-restarts must be >= 0")
        # hybrid x --partitioned-io is a SUPPORTED composition since ISSUE
        # 6: the hot-column ranking is a global nnz statistic, so the
        # partitioned reader ships per-rank histograms through the metadata
        # exchange and every rank resolves the SAME head
        # (io/partitioned_reader._resolve_global_sparse_layout)
        sequence = self.update_sequence or tuple(self.coordinates.keys())
        for cid in sequence:
            if cid not in self.coordinates:
                problems.append(f"update sequence names unknown coordinate '{cid}'")
        for cid in self.partial_retrain_locked_coordinates:
            if cid not in sequence:
                problems.append(f"locked coordinate '{cid}' not in update sequence")
        if self.partial_retrain_locked_coordinates and self.model_input_dir is None:
            problems.append("partial retraining requires --model-input-dir")
        for name, cfg in self.coordinates.items():
            if cfg.is_matrix_factorization:
                continue  # MF coordinates take no feature shard
            if cfg.feature_shard not in self.feature_shards:
                problems.append(
                    f"coordinate '{name}' references undefined feature shard "
                    f"'{cfg.feature_shard}'"
                )
        if self.evaluators and self.validation_data_path is None:
            problems.append(
                "--evaluators are validation evaluators and require "
                "--validation-data-path"
            )
        for spec in self.evaluators:
            # fail fast on bad specs, before any data is read
            try:
                parse_evaluator(spec)
            except ValueError as e:
                problems.append(str(e))
        if self.index_maps_dir:
            # typo'd stores dir must fail before the output dir is touched;
            # filenames only — no store is opened/mmapped here
            try:
                found = IndexMap.list_directory(self.index_maps_dir)
                missing = set(self.feature_shards) - set(found)
                if missing:
                    problems.append(
                        f"--index-maps-dir {self.index_maps_dir!r} has no "
                        f"stores for shards {sorted(missing)}"
                    )
            except OSError as e:
                problems.append(
                    f"cannot read --index-maps-dir {self.index_maps_dir!r}: {e}"
                )
        if self.hyperparameter_prior_json:
            # a typo'd priors path must fail now, not after the grid trains
            try:
                load_prior_observations(self.hyperparameter_prior_json)
            except Exception as e:
                problems.append(
                    f"cannot read --hyperparameter-prior-json "
                    f"{self.hyperparameter_prior_json!r}: {e}"
                )
        if (
            self.hyperparameter_tuning != HyperparameterTuningMode.NONE
            and not self.evaluators
        ):
            problems.append("hyperparameter tuning requires --evaluators")
        if self.incremental_refresh:
            self._validate_refresh(problems)
        elif self.refresh_changed_entities or self.refresh_fixed_effects:
            problems.append(
                "--refresh-changed-entities/--refresh-fixed-effects tune "
                "the incremental-refresh policy; pass --incremental-refresh "
                "to opt into the refresh driver mode"
            )
        if self.streaming_chunks > 0:
            self._validate_streaming(problems)
        elif self.duhl_working_set > 0:
            problems.append(
                "--duhl-working-set schedules streamed chunks; pass "
                "--streaming-chunks N to opt into the streamed GAME path"
            )
        if problems:
            raise ValueError("invalid driver parameters: " + "; ".join(problems))

    def _validate_refresh(self, problems: list) -> None:
        """The incremental-refresh surface (ISSUE 14): the single-process
        host CD path, one λ per coordinate, against a resident model.
        Everything outside it fails fast with the composing alternative
        named (lint check 8)."""
        if not self.model_input_dir and not self.checkpoint_dir:
            problems.append(
                "--incremental-refresh needs a resident model: pass "
                "--model-input-dir (a saved model directory) or "
                "--checkpoint-dir (a training run's CD checkpoints — "
                "warm-start re-entry)"
            )
        if self.distributed or self.mesh_shape or self.partitioned_io:
            problems.append(
                "--incremental-refresh is the single-process host path; "
                "drop --distributed/--mesh/--partitioned-io (run the full "
                "fused fit to retrain at mesh scale)"
            )
        if self.streaming_chunks > 0:
            problems.append(
                "--incremental-refresh reads the refresh data in-core; "
                "drop --streaming-chunks (or run the streamed full fit)"
            )
        if self.hyperparameter_tuning != HyperparameterTuningMode.NONE:
            problems.append(
                "--incremental-refresh trains the resident λ; drop "
                "--hyperparameter-tuning (tune on a full fit)"
            )
        if self.validation_data_path or self.evaluators:
            problems.append(
                "--incremental-refresh has no validation pass; drop "
                "--validation-data-path/--evaluators and score with the "
                "scoring driver"
            )
        if self.refresh_gradient_tolerance < 0:
            problems.append("--refresh-gradient-tolerance must be >= 0")
        for name, cfg in self.coordinates.items():
            if len(cfg.reg_weights) != 1:
                problems.append(
                    f"coordinate '{name}': --incremental-refresh trains "
                    "the resident λ; pass a single reg.weights value"
                )
        try:
            _parse_changed_entities(self.refresh_changed_entities)
        except ValueError as e:
            problems.append(str(e))

    def _validate_streaming(self, problems: list) -> None:
        """The streamed-GAME surface (ISSUE 11 + 17): one dense primary FE
        + IDENTITY random effects over an entity-sorted Avro input —
        single-process, or multi-rank via --partitioned-io (the ISSUE 17
        composition). Everything outside it fails fast here with the
        composing alternative named (lint check 8)."""
        if self.input_format != "avro":
            problems.append(
                "--streaming-chunks streams Avro container blocks; for "
                "libsvm inputs drop --streaming-chunks (or convert with "
                "cli.libsvm_to_avro)"
            )
        if self.input_date_range:
            problems.append(
                "--streaming-chunks streams one input directory; drop "
                "--input-date-range (pass the resolved daily dir directly)"
            )
        if self.validation_data_date_range:
            problems.append(
                "--streaming-chunks streams one validation directory; drop "
                "--validation-data-date-range (pass the resolved dir "
                "directly)"
            )
        if self.distributed or self.mesh_shape:
            problems.append(
                "--streaming-chunks is the host-loop out-of-core GAME "
                "path; drop --distributed/--mesh (for multi-process "
                "streamed GAME use --partitioned-io, which partitions "
                "chunks across ranks instead of meshing devices)"
            )
        if self.normalization != NormalizationType.NONE:
            problems.append(
                "--streaming-chunks trains un-normalized; use "
                "--normalization NONE or run in-core"
            )
        for spec in self.evaluators:
            if ":" in str(spec):
                problems.append(
                    f"evaluator '{spec}': per-query evaluators need "
                    "evaluation id columns the chunk stream does not "
                    "decode; use a global evaluator or score with the "
                    "scoring driver"
                )
        if self.hyperparameter_tuning != HyperparameterTuningMode.NONE:
            problems.append(
                "--streaming-chunks trains one configuration; drop "
                "--hyperparameter-tuning"
            )
        if self.data_validation != DataValidationType.VALIDATE_DISABLED:
            problems.append(
                "--streaming-chunks has no chunked validation pass yet; "
                "use --data-validation VALIDATE_DISABLED or run in-core"
            )
        if self.model_input_dir or self.partial_retrain_locked_coordinates:
            problems.append(
                "--streaming-chunks does not warm-start from "
                "--model-input-dir yet; drop it or train in-core"
            )
        if self.duhl_working_set < 0 or self.duhl_tail_chunks < 1:
            problems.append(
                "--duhl-working-set must be >= 0 and --duhl-tail-chunks "
                ">= 1"
            )
        fe_coords = [
            n for n, c in self.coordinates.items()
            if not c.is_random_effect and not c.is_matrix_factorization
        ]
        if len(fe_coords) != 1:
            problems.append(
                "--streaming-chunks needs exactly one fixed-effect "
                f"coordinate (got {fe_coords}); train other layouts in-core"
            )
        sequence = self.update_sequence or tuple(self.coordinates.keys())
        if fe_coords and sequence and sequence[0] != fe_coords[0]:
            problems.append(
                "--streaming-chunks trains the fixed effect first; put "
                f"'{fe_coords[0]}' first in --update-sequence"
            )
        for name, c in self.coordinates.items():
            if c.is_matrix_factorization:
                problems.append(
                    f"coordinate '{name}': matrix factorization does not "
                    "stream; drop --streaming-chunks or the MF coordinate"
                )
            if (
                not c.is_random_effect
                and not c.is_matrix_factorization
                and c.optimizer == OptimizerType.NEWTON
            ):
                problems.append(
                    f"coordinate '{name}': NEWTON cannot stream the fixed "
                    "effect (dense [d, d] Hessian); use TRON or LBFGS"
                )
            if c.is_random_effect and c.projector != ProjectorType.IDENTITY:
                problems.append(
                    f"coordinate '{name}': projector {c.projector.name} "
                    "does not stream; use IDENTITY or train in-core"
                )
            if len(c.reg_weights) != 1:
                problems.append(
                    f"coordinate '{name}': --streaming-chunks trains one "
                    "λ per coordinate; pass a single reg.weights value"
                )
            if c.reg_alpha > 0.0:
                problems.append(
                    f"coordinate '{name}': elastic-net L1 does not stream "
                    "on the GAME path; set reg.alpha=0 or train in-core"
                )
            if c.compute_variance:
                problems.append(
                    f"coordinate '{name}': variances need the in-core "
                    "Hessian path; drop compute.variance or "
                    "--streaming-chunks"
                )
            if c.down_sampling_rate < 1.0:
                problems.append(
                    f"coordinate '{name}': down-sampling does not stream "
                    "yet; use down.sampling.rate=1"
                )
            if (
                c.is_random_effect
                and (c.active_data_lower_bound or c.active_data_upper_bound)
            ):
                problems.append(
                    f"coordinate '{name}': active-data bounds are not "
                    "supported streamed; drop them or train in-core"
                )


def _parse_changed_entities(specs) -> dict:
    """'reType=key1|key2' specs -> {reType: (keys...)} (repeatable,
    same-type specs merge)."""
    out: dict = {}
    for spec in specs:
        typ, sep, keys = str(spec).partition("=")
        typ = typ.strip()
        if not sep or not typ:
            raise ValueError(
                f"bad --refresh-changed-entities {spec!r}; expected "
                "reType=key1|key2"
            )
        out.setdefault(typ, [])
        out[typ] += [k for k in keys.split("|") if k]
    return {k: tuple(v) for k, v in out.items()}


def _trace_exchange():
    """Exchange for run-end trace publication + straggler merge: the
    coordination-service KV transport on multi-process runs (EVERY rank's
    run() reaches this finally, so the collective discipline holds),
    trivial single-process."""
    from photon_ml_tpu.parallel.multihost import default_exchange

    return default_exchange()


def run(params: GameTrainingParams) -> dict:
    """Execute the training pipeline; returns a result summary dict."""
    params.validate()
    import jax

    if jax.process_count() > 1:
        # Multi-process pods: every process executes the same SPMD program
        # (reads the same inputs, joins every collective), but filesystem
        # outputs belong to process 0 — workers write into a scratch
        # subdirectory. The checkpoint directory stays SHARED: all processes
        # restore from it, train_distributed writes it from process 0 only.
        if not (
            params.distributed or params.mesh_shape
            or (params.streaming_chunks > 0 and params.partitioned_io)
        ):
            # the host-loop CD path has no cross-process coordination (every
            # rank would train redundantly and race on the shared
            # checkpoint directory)
            raise ValueError(
                "multi-process runs require --distributed or --mesh "
                "(the fused SPMD training path) or --streaming-chunks with "
                "--partitioned-io (the partitioned streamed GAME path)"
            )
        if jax.process_index() > 0:
            params = dataclasses.replace(
                params,
                root_output_dir=os.path.join(
                    params.root_output_dir, f".worker-{jax.process_index()}"
                ),
                override_output=True,
            )
    out = params.root_output_dir
    # ignore worker scratch dirs: a faster rank may create out/.worker-N
    # before rank 0's emptiness check runs
    existing = (
        [e for e in os.listdir(out) if not e.startswith(".worker-")]
        if os.path.isdir(out) else []
    )
    if existing and not params.override_output:
        raise ValueError(
            f"output dir {out!r} is non-empty (pass --override-output to replace)"
        )
    os.makedirs(out, exist_ok=True)

    # per-run phase timings + solver/layout tallies (a sweep may call run()
    # repeatedly)
    reset_timings()
    reset_solver_metrics()
    reset_layout_metrics()
    reset_resilience_metrics()
    reset_refresh_metrics()
    events.send(TrainingStartEvent(job_name="game-training"))
    job_log = PhotonLogger(os.path.join(out, "driver.log"))
    # rank-gated journal: inert on worker ranks, so telemetry calls below
    # are unconditional (collectives must still run on EVERY rank). The
    # journal + registry sinks are opt-in via --telemetry-dir; the emitter
    # rides along for any registered listener. With no live sink,
    # SolverTelemetry skips row-building entirely, so default runs pay no
    # per-coordinate device-to-host reads (each one a sync point in the
    # async dispatch queue).
    journal = RunJournal(params.telemetry_dir) if params.telemetry_dir else None
    telemetry = SolverTelemetry(
        journal=journal,
        emitter=events,
        # registry only where the journal will persist it (rank 0): worker
        # ranks would otherwise pay the row-building host reads for metrics
        # nobody reads
        registry=default_registry() if journal and journal.active else None,
    )
    compiles = CompileMonitor()
    # program ledger rides --telemetry-dir (ISSUE 13): labeled jit sites
    # (train/step, coord/*, scheduler/*, score/*) journal per-program
    # compile/cost rows with recompile attribution; inert without it
    ledger = None
    if journal is not None:
        from photon_ml_tpu.telemetry.program_ledger import (
            ProgramLedger,
            install_ledger,
        )

        ledger = install_ledger(ProgramLedger(journal=journal))
    # span tracing is opt-in via --trace-dir; installed before any stage so
    # a failure mid-read still leaves a timeline on every rank
    tracer = None
    if params.trace_dir:
        from photon_ml_tpu.telemetry.tracing import Tracer, install_tracer

        tracer = install_tracer(Tracer())
    succeeded = False
    try:
        from photon_ml_tpu.util.timed import profile_trace

        with profile_trace(params.profile_dir), compiles:
            summary = _run_inner(params, job_log, telemetry)
        succeeded = True
        return summary
    except Exception:
        events.send(TrainingFinishEvent(job_name="game-training", succeeded=False))
        raise
    finally:
        # traces flush FIRST (before the failure journal rows) so a crash
        # leaves a readable per-rank timeline. Success path: the straggler
        # tables merge over the exchange and publication is barriered
        # (rank-0 mkdir, barrier, per-rank write); failure path: no new
        # collectives — local report, unbarriered per-rank write.
        if tracer is not None:
            from photon_ml_tpu.telemetry.tracing import (
                flush_trace_best_effort,
                uninstall_tracer,
            )

            try:
                # best-effort: a publication error or a mixed-outcome
                # straggler-merge timeout never masks the run's own
                # outcome or skips the journal rows below
                flush_trace_best_effort(
                    tracer, params.trace_dir,
                    exchange=_trace_exchange() if succeeded else None,
                    gather=succeeded,
                    journal=journal,
                )
            finally:
                uninstall_tracer()
        if ledger is not None:
            from photon_ml_tpu.telemetry.program_ledger import uninstall_ledger

            uninstall_ledger()
        # journal phase timings / gauges on failure too — a failed run's
        # journal is the one that most needs them. The registry snapshot
        # carries the resilience/* counters (retries, giveups,
        # quarantined_blocks, checkpoint_restores); quarantined block
        # SPANS get one forensic row each.
        if journal is not None:
            from photon_ml_tpu.telemetry import resilience_counters

            for event in resilience_counters.drain_quarantine_events():
                journal.record("quarantined_block", **event)
            journal.record_timings(timing_summary())
            journal.record_gauge("jax/backend_compile_count", compiles.count)
            journal.record_gauge("device/live_buffer_bytes", live_buffer_bytes())
            journal.record_metrics(default_registry().snapshot())
            journal.close()
        job_log.close()


def _run_inner(
    params: GameTrainingParams,
    job_log: PhotonLogger,
    telemetry: SolverTelemetry | None = None,
) -> dict:
    if params.incremental_refresh:
        # the refresh mode reads the data in the RESIDENT model's feature
        # space (its index maps + entity vocabs) — a separate pipeline
        return _run_refresh(params, job_log, telemetry)
    if params.streaming_chunks > 0:
        # the out-of-core path does its own streaming scans — the full
        # read below would materialize exactly what it exists to avoid
        return _run_streaming(params, job_log, telemetry)
    out = params.root_output_dir
    entity_columns = {
        c.random_effect_type
        for c in params.coordinates.values()
        if c.random_effect_type
    }
    for c in params.coordinates.values():
        # MF coordinates consume two entity-id columns (row + col)
        if c.is_matrix_factorization:
            entity_columns.update((c.mf_row_effect_type, c.mf_col_effect_type))
    re_columns = tuple(sorted(entity_columns))
    eval_columns = evaluation_id_columns(params.evaluators)

    def resolve(path, range_spec):
        if not range_spec:
            return path
        from photon_ml_tpu.util.date_range import (
            parse_date_or_days_range,
            resolve_input_paths,
        )

        return resolve_input_paths([path], parse_date_or_days_range(range_spec))

    prebuilt_maps = None
    if params.index_maps_dir:
        # reference GameDriver.prepareFeatureMaps (GameDriver.scala:195-240):
        # reuse stores built by the feature-indexing driver (plain .keys or
        # native off-heap .photonix) instead of scanning the data.
        # validate() already checked existence + shard coverage.
        prebuilt_maps = IndexMap.load_directory(params.index_maps_dir)

    # the mesh exists BEFORE ingestion: partitioned reads align their
    # per-rank blocks with the mesh's addressable shards
    import jax

    mesh = None
    model_axis = 1
    if params.distributed or params.mesh_shape:
        # the multi-chip entry point: one ("data", "model") mesh over all
        # (possibly multi-process) devices, topology-aware across slices
        from photon_ml_tpu.parallel.multihost import make_hybrid_mesh

        shape = dict(params.mesh_shape or {})
        model_axis = int(shape.get("model", 1))
        mesh = make_hybrid_mesh(
            data=shape.get("data"), model=model_axis
        )
        job_log.info(
            "distributed mode: mesh %s over %d devices",
            dict(zip(mesh.axis_names, mesh.devices.shape)), mesh.devices.size,
        )
    elif jax.device_count() > 1:
        job_log.warning(
            "training on 1 of %d devices — %d stay idle; pass --distributed "
            "(or --mesh data=N,model=M) to use them",
            jax.device_count(), jax.device_count() - 1,
        )

    # partitioned host I/O: each rank decodes ~1/P of the bytes
    # (io/partitioned_reader.py). exchange/pad_multiple resolve to the
    # trivial single-rank values unless --partitioned-io on a multi-process
    # run, so the single-process path reads byte-identically to before.
    exchange = None
    coordinator = None
    pad_multiple = 1
    if params.partitioned_io and jax.process_count() > 1:
        from photon_ml_tpu.parallel.multihost import default_exchange

        if mesh is None:
            raise ValueError(
                "--partitioned-io requires --distributed or --mesh (the "
                "partitioned blocks feed a mesh's addressable shards)"
            )
        exchange = default_exchange()
        # coordinated multi-rank recovery (ISSUE 15): fence the run's ONE
        # exchange into restart generations and attach the coordinator to
        # every run_with_recovery below — a preempted rank then becomes an
        # attributed all-rank rollback to the last barrier-committed
        # checkpoint instead of a whole-job ExchangeTimeout death. The
        # budget is SHARED across ranks AND grid configs (one job, one
        # budget). Host-side KV only: no device collective is added,
        # skipped, or reordered.
        from photon_ml_tpu.resilience import CoordinatedRecovery

        coordinator = CoordinatedRecovery(
            exchange,
            max_restarts=params.max_restarts,
            journal=telemetry.journal if telemetry is not None else None,
            description="partitioned game train",
        )
        data_axis = int(mesh.shape["data"])
        if data_axis % exchange.num_ranks:
            raise ValueError(
                f"--partitioned-io: mesh data axis {data_axis} must be a "
                f"multiple of the process count {exchange.num_ranks}"
            )
        pad_multiple = data_axis // exchange.num_ranks
        if params.validation_data_path:
            raise ValueError(
                "--partitioned-io does not support validation data yet; "
                "score + evaluate with the partitioned scoring driver"
            )

    # transient-I/O retry for the ingestion boundary — ONLY when the read
    # is not collective: retrying one rank of a partitioned (exchange-
    # coordinated) read would desynchronize the SPMD exchange sequence,
    # so the collective path keeps its deadlines (ExchangeTimeout) instead
    from photon_ml_tpu.resilience import default_io_policy

    def _read(description, fn):
        if exchange is not None:
            return fn()
        return default_io_policy().call(fn, description=description)

    with Timed("read training data"):
        train_part = _read(
            "read training data",
            lambda: read_partitioned(
                resolve(params.input_data_path, params.input_date_range),
                params.feature_shards,
                exchange=exchange,
                index_maps=prebuilt_maps,
                random_effect_id_columns=re_columns,
                evaluation_id_columns=eval_columns,
                fmt=params.input_format,
                pad_multiple=pad_multiple,
                tag="train",
                on_corrupt=params.on_corrupt,
            ),
        )
        train = train_part.result
    partition = train_part.partition
    job_log.info(
        "read %d training samples%s, shards %s",
        train.dataset.num_samples,
        (
            f" (rank {partition.rank}/{partition.num_ranks}, "
            f"{train_part.bytes_decoded}/{train_part.input_bytes_total} "
            "bytes decoded)"
            if partition.num_ranks > 1 else ""
        ),
        {k: v.size for k, v in train.index_maps.items()},
    )

    validation = None
    if params.validation_data_path:
        with Timed("read validation data"):
            validation = _read(
                "read validation data",
                lambda: read_partitioned(
                    resolve(
                        params.validation_data_path,
                        params.validation_data_date_range,
                    ),
                    params.feature_shards,
                    index_maps=train.index_maps,
                    random_effect_id_columns=re_columns,
                    evaluation_id_columns=eval_columns,
                    entity_vocabs=train.dataset.entity_vocabs,
                    fmt=params.input_format,
                    tag="validation",
                    on_corrupt=params.on_corrupt,
                ),
            ).result

    with Timed("validate data"):
        validate_game_dataset(train.dataset, params.task_type, params.data_validation)
        if validation is not None:
            validate_game_dataset(
                validation.dataset, params.task_type, params.data_validation
            )

    with Timed("feature shard stats"):
        from photon_ml_tpu.io.index_map import IdentityIndexMap

        if partition.num_ranks > 1:
            # rank-local rows: a per-rank stats file would summarize 1/P of
            # the data and masquerade as global statistics
            logger.info("partitioned ingest: skipping feature stats "
                        "(rank-local rows)")
        for shard_id, features in (
            {} if partition.num_ranks > 1 else train.dataset.feature_shards
        ).items():
            imap = train.index_maps[shard_id]
            if isinstance(imap, IdentityIndexMap) and imap.size > (1 << 20):
                # pre-indexed giant-d space: a per-column stats file would
                # be d records — skip (stats exist for name-term shards)
                logger.info(
                    "skipping feature stats for pre-indexed shard '%s' "
                    "(d=%d)", shard_id, imap.size,
                )
                continue
            if isinstance(features, SparseShard):
                stats = features.summarize(np.asarray(train.dataset.weights))
            else:
                stats = summarize(np.asarray(features), np.asarray(train.dataset.weights))
            write_feature_stats(
                os.path.join(out, "feature-stats", shard_id, "part-00000.avro"),
                stats,
                imap,
            )

    initial_model = None
    if params.model_input_dir:
        with Timed("load warm-start model"):
            initial_model = load_game_model(
                params.model_input_dir, train.index_maps,
                compact_random_effect_threshold=(
                    params.compact_random_effect_threshold
                ),
            )

    # save index maps next to the models so scoring is self-contained;
    # plain maps (built here OR prebuilt .keys) are cheap to copy, while
    # off-heap stores stay where they are (scoring takes --index-maps-dir)
    for shard_id, imap in train.index_maps.items():
        if isinstance(imap, IndexMap):
            imap.save(os.path.join(out, "index-maps"), shard_id)

    estimator_partition = None
    if partition.num_ranks > 1:
        from photon_ml_tpu.estimators import TrainPartition

        estimator_partition = TrainPartition(
            info=partition,
            exchange=exchange,
            lane_multiple=pad_multiple,
            entity_rank_presence=train_part.entity_rank_presence,
        )

    def make_estimator(
        reg_weights, checkpointer=None, resume=None, resume_step=None
    ) -> GameEstimator:
        return GameEstimator(
            task=params.task_type,
            coordinate_configs=estimator_coordinate_configs(
                params.coordinates, reg_weights
            ),
            update_sequence=params.update_sequence or None,
            num_iterations=params.coordinate_descent_iterations,
            normalization=params.normalization,
            validation_evaluators=params.evaluators,
            locked_coordinates=frozenset(params.partial_retrain_locked_coordinates),
            intercept_indices=train.intercept_indices,
            checkpointer=checkpointer,
            checkpoint_every=params.checkpoint_every,
            resume=params.resume if resume is None else resume,
            resume_step=resume_step,
            mesh=mesh,
            fe_feature_sharded=model_axis > 1,
            telemetry=telemetry,
            partition=estimator_partition,
        )

    def make_checkpointer(config_index: int, reg_weights):
        if not params.checkpoint_dir:
            return None
        import hashlib

        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        # key the directory by the configuration CONTENT, not just its grid
        # position — editing the λ grid between runs must not resume a
        # checkpoint trained under different regularization weights
        digest = hashlib.sha256(
            json.dumps(sorted(reg_weights.items()), default=float).encode()
        ).hexdigest()[:12]
        return TrainingCheckpointer(
            os.path.join(params.checkpoint_dir, f"config_{config_index}_{digest}")
        )

    grid = expand_reg_weight_grid(params.coordinates)
    job_log.info("expanded λ grid to %d configurations", len(grid))
    if telemetry is not None and telemetry.journal is not None:
        telemetry.journal.record(
            "config",
            task_type=params.task_type.name,
            distributed=mesh is not None,
            num_configurations=len(grid),
            coordinate_configurations={
                name: format_coordinate_config(cfg)
                for name, cfg in params.coordinates.items()
            },
            update_sequence=list(
                params.update_sequence or params.coordinates.keys()
            ),
            coordinate_descent_iterations=params.coordinate_descent_iterations,
            # lane-scheduled coordinates (algorithm/lane_scheduler.py): the
            # scheduler/* counters + solver/lane_iters histogram land in the
            # registry snapshot journaled on success AND failure paths
            scheduled_coordinates=[
                name for name, cfg in params.coordinates.items() if cfg.scheduler
            ],
        )
    first_evaluator = parse_evaluator(params.evaluators[0]) if params.evaluators else None

    from photon_ml_tpu.resilience import run_with_recovery

    results = []
    warm_model = initial_model
    best_index, best_metric = -1, float("nan")
    for i, reg_weights in enumerate(grid):
        with Timed(f"train config {i}"):
            # crash-safe sweep: a DivergenceError (with a checkpoint to
            # restore) or classified-transient failure restarts this
            # configuration — the re-created estimator resumes from the
            # latest intact checkpoint — instead of aborting the run
            initial = warm_model
            ckpt = make_checkpointer(i, reg_weights)

            def attempt(restart: int, _rw=reg_weights, _ck=ckpt, _init=initial):
                est = make_estimator(
                    _rw,
                    _ck,
                    # restarts must resume even under --no-resume (the
                    # whole point of the restart is the checkpoint)
                    resume=params.resume or restart > 0,
                    # a coordinated restart restores the PUBLISHED step on
                    # every rank, never each rank's own local newest
                    resume_step=(
                        coordinator.resume_step
                        if coordinator is not None else None
                    ),
                )
                return est.fit(
                    train.dataset,
                    validation_dataset=(
                        None if validation is None else validation.dataset
                    ),
                    initial_model=_init,
                )

            if coordinator is not None:
                # the rollback step is resolved against THIS config's
                # checkpoint directory (per-config dirs are content-keyed);
                # rebind also clears any resume step published for the
                # PREVIOUS config's rollback
                coordinator.rebind(ckpt)
            result = run_with_recovery(
                attempt,
                max_restarts=params.max_restarts,
                checkpointer=ckpt,
                journal=telemetry.journal if telemetry is not None else None,
                description=f"train config {i}",
                coordinator=coordinator,
            )
        # warm start the next grid point (reference GameEstimator.fit:352-366)
        warm_model = result.model
        results.append((reg_weights, result))
        metric = result.best_metric
        job_log.info("config %d %s -> metric %s", i, reg_weights, metric)
        if first_evaluator is None:
            if best_index < 0:
                best_index = i
        elif best_index < 0 or first_evaluator.better_than(metric, best_metric):
            best_index, best_metric = i, metric

        if params.model_output_mode in (ModelOutputMode.ALL, ModelOutputMode.EXPLICIT):
            save_game_model(
                os.path.join(out, "models", str(i)),
                result.best_model,
                train.index_maps,
                optimization_configurations={"regWeights": reg_weights},
            )

    summary: dict = {
        "distributed": mesh is not None,
        "num_configurations": len(grid),
        # effective configs in re-runnable CLI form (reference ScoptParameter
        # print-round-trip)
        "effective_coordinate_configurations": {
            name: format_coordinate_config(cfg)
            for name, cfg in params.coordinates.items()
        },
        "best_configuration_index": best_index,
        "best_reg_weights": grid[best_index],
        "best_metric": best_metric,
        "metric_history": [
            {"reg_weights": rw, "metrics": r.metric_history} for rw, r in results
        ],
    }

    # Save the grid best immediately (a later tuning failure must not cost
    # the already-trained model); if a tuned candidate wins the
    # best-over-all selection below it overwrites this directory
    # (reference GameTrainingDriver.selectModels:672-691).
    best_result = results[best_index][1]
    best_reg_weights = grid[best_index]
    if params.model_output_mode != ModelOutputMode.NONE:
        save_game_model(
            os.path.join(out, "best"),
            best_result.best_model,
            train.index_maps,
            optimization_configurations={"regWeights": best_reg_weights},
        )

    if params.hyperparameter_tuning != HyperparameterTuningMode.NONE:
        with Timed("hyperparameter tuning"):
            tunable = {
                name: params.hyperparameter_tuning_range
                for name in params.coordinates
                if name not in params.partial_retrain_locked_coordinates
            }
            tuner = GameHyperparameterTuner(
                estimator=make_estimator(grid[best_index]),
                reg_ranges=tunable,
                mode=params.hyperparameter_tuning,
            )
            priors = [
                (rw, r.best_metric)
                for rw, r in results
                if not np.isnan(r.best_metric)
            ]
            if params.hyperparameter_prior_json:
                priors += load_prior_observations(params.hyperparameter_prior_json)
            tuned = tuner.tune(
                train.dataset,
                validation.dataset,
                num_iterations=params.hyperparameter_tuning_iter,
                prior_observations=priors,
                # only TUNED/ALL need every candidate's model; the winner is
                # tracked O(1) either way (TuningResult.best_result)
                keep_models=params.model_output_mode
                in (ModelOutputMode.ALL, ModelOutputMode.TUNED),
            )
        save_tuned_config(tuned, os.path.join(out, "tuned-hyperparameters.json"))
        summary["tuned_reg_weights"] = tuned.best_reg_weights
        summary["tuned_metric"] = tuned.best_value
        if params.model_output_mode in (ModelOutputMode.ALL, ModelOutputMode.TUNED):
            for j, (reg, r) in enumerate(tuned.tuned_results):
                save_game_model(
                    os.path.join(out, "models-tuned", str(j)),
                    r.best_model,
                    train.index_maps,
                    optimization_configurations={"regWeights": reg},
                )
        # best over explicit + tuned (first evaluator decides)
        if first_evaluator is not None and tuned.best_result is not None:
            reg, r = tuned.best_result
            if not np.isnan(r.best_metric) and first_evaluator.better_than(
                r.best_metric, best_metric
            ):
                best_metric, best_result, best_reg_weights = (
                    r.best_metric, r, reg
                )
                summary["best_metric"] = best_metric
                summary["best_reg_weights"] = best_reg_weights
                # the grid index no longer identifies the winner
                summary["best_configuration_index"] = None
                summary["best_is_tuned"] = True
                if params.model_output_mode != ModelOutputMode.NONE:
                    save_game_model(
                        os.path.join(out, "best"),
                        best_result.best_model,
                        train.index_maps,
                        optimization_configurations={
                            "regWeights": best_reg_weights
                        },
                    )

    return _finish(out, summary, devices_used=(
        1 if mesh is None else int(mesh.devices.size)
    ), decode_paths={
        "train": train.decode_path,
        **({} if validation is None
           else {"validation": validation.decode_path}),
    })


def _run_streaming(
    params: GameTrainingParams,
    job_log: PhotonLogger,
    telemetry: SolverTelemetry | None = None,
) -> dict:
    """The --streaming-chunks GAME pipeline (ISSUE 11): one streaming scan
    (index maps + entity vocabs + cluster keys, records discarded), an
    entity-clustered chunk source, and StreamingGameProgram sweeps — the
    input never materializes in core, so n is bounded by disk, not HBM.
    With --partitioned-io on a multi-process run (ISSUE 17) the chunk plan
    is agreed over the metadata exchange, each rank streams only its own
    entity-clustered chunk slice, and sweeps recover through the
    coordinated all-rank rollback — n is then bounded by the fleet's
    disks. validate() already restricted the surface (dense single FE +
    IDENTITY REs, one λ)."""
    import jax  # noqa: F401  (platform selection must already be done)

    from photon_ml_tpu.algorithm.streaming_game import (
        DuHLChunkSchedule,
        DuHLScheduleConfig,
        StreamingGameProgram,
        score_game_stream,
    )
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
    from photon_ml_tpu.io.stream_reader import (
        GameAvroChunkSource,
        plan_partitioned_game_stream,
        scan_game_stream,
    )
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import GeneralizedLinearModel
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.resilience import run_with_recovery
    from photon_ml_tpu.telemetry import stream_counters

    out = params.root_output_dir
    sequence = tuple(params.update_sequence or params.coordinates.keys())
    fe_name = next(
        n for n in sequence
        if not params.coordinates[n].is_random_effect
    )
    fe_cfg = params.coordinates[fe_name]
    re_names = [n for n in sequence if n != fe_name]
    cluster_by = (
        params.coordinates[re_names[0]].random_effect_type
        if re_names else None
    )
    shard_ids = {fe_cfg.feature_shard} | {
        params.coordinates[n].feature_shard for n in re_names
    }
    shard_configs = {s: params.feature_shards[s] for s in shard_ids}
    re_columns = tuple(sorted(
        params.coordinates[n].random_effect_type for n in re_names
    ))

    exchange = None
    coordinator = None
    partition = None
    scalars = None
    if params.partitioned_io and jax.process_count() > 1:
        from photon_ml_tpu.parallel.multihost import default_exchange
        from photon_ml_tpu.resilience import CoordinatedRecovery

        if cluster_by is None:
            raise ValueError(
                "--partitioned-io streamed GAME needs at least one random-"
                "effect coordinate (its entities define the chunk "
                "partition); drop --partitioned-io or add one"
            )
        if params.validation_data_path:
            raise ValueError(
                "--partitioned-io streamed GAME has no multi-rank "
                "validation pass; drop --validation-data-path and score "
                "with the scoring driver"
            )
        exchange = default_exchange()
        schedule_budget = (
            {"working_set": params.duhl_working_set,
             "tail_chunks": params.duhl_tail_chunks}
            if params.duhl_working_set > 0 else None
        )
        with Timed("streaming scan"):
            source, index_maps, vocabs, partition = (
                plan_partitioned_game_stream(
                    params.input_data_path, shard_configs, re_columns,
                    exchange=exchange,
                    chunk_records=params.streaming_chunks,
                    cluster_by=cluster_by,
                    schedule_budget=schedule_budget,
                    on_corrupt=params.on_corrupt,
                )
            )
        job_log.info(
            "partitioned streamed plan %s: rank %d/%d holds chunks "
            "[%d, %d) of %d (payload %d/%d input bytes)",
            partition.fingerprint, partition.rank, partition.num_ranks,
            *partition.chunk_range(), partition.num_chunks,
            partition.payload_bytes[partition.rank], partition.input_bytes,
        )
        # coordinated multi-rank recovery (ISSUE 15, applied to the
        # streamed path): fence the run's ONE exchange into restart
        # generations so a preempted rank becomes an attributed all-rank
        # rollback to the last barrier-committed sweep. Host-side KV only.
        coordinator = CoordinatedRecovery(
            exchange,
            max_restarts=params.max_restarts,
            journal=telemetry.journal if telemetry is not None else None,
            description="partitioned streamed game train",
        )
    else:
        files = avro_io.list_avro_files(params.input_data_path)
        with Timed("streaming scan"):
            index_maps, vocabs, cluster_keys, indexes, scalars = (
                scan_game_stream(
                    files, shard_configs, re_columns,
                    cluster_by=cluster_by, on_corrupt=params.on_corrupt,
                )
            )
        source = GameAvroChunkSource(
            files, shard_configs, index_maps,
            chunk_records=params.streaming_chunks,
            random_effect_id_columns=re_columns,
            entity_vocabs=vocabs,
            cluster_by=cluster_by,
            cluster_keys=cluster_keys,
            indexes=indexes,
            on_corrupt=params.on_corrupt,
        )
    job_log.info(
        "streaming scan: %d files, shards %s, entities %s",
        len(source.files), {k: v.size for k, v in index_maps.items()},
        {k: len(v) for k, v in vocabs.items()},
    )
    for shard_id, imap in index_maps.items():
        if isinstance(imap, IndexMap):
            imap.save(os.path.join(out, "index-maps"), shard_id)
    job_log.info(
        "planned %d entity-clustered chunks (<=%d records requested, "
        "chunk_rows=%d)",
        source.num_chunks, params.streaming_chunks, source.chunk_rows,
    )

    def opt_config(cfg):
        return cfg.optimization_config(cfg.reg_weights[0])

    fe_opt = opt_config(fe_cfg)
    fe_spec = FixedEffectStepSpec(
        feature_shard_id=fe_cfg.feature_shard,
        optimizer=fe_opt.optimizer,
        l2_weight=fe_opt.l2_weight,
    )
    re_specs = []
    for n in re_names:
        cfg = params.coordinates[n]
        o = opt_config(cfg)
        re_specs.append(RandomEffectStepSpec(
            re_type=cfg.random_effect_type,
            feature_shard_id=cfg.feature_shard,
            optimizer=o.optimizer,
            l2_weight=o.l2_weight,
        ))

    schedule = None
    if params.duhl_working_set > 0:
        # the schedule spans GLOBAL chunks when partitioned — every rank
        # drives the same schedule from the same allgathered signal
        schedule = DuHLChunkSchedule(
            DuHLScheduleConfig(
                working_set_chunks=params.duhl_working_set,
                tail_chunks_per_sweep=params.duhl_tail_chunks,
            ),
            partition.num_chunks if partition is not None
            else source.num_chunks,
        )
    checkpointer = (
        TrainingCheckpointer(
            os.path.join(params.checkpoint_dir, "streaming-game")
        )
        if params.checkpoint_dir else None
    )

    with Timed("streamed game train"):
        def attempt(restart: int):
            program = StreamingGameProgram(
                params.task_type, source, fe_spec, tuple(re_specs),
                num_entities={t: len(vocabs[t]) for t in re_columns},
                schedule=schedule,
                prefetch=params.streaming_prefetch,
                exchange=exchange,
                partition=partition,
                # the scan pass already collected the [n] scalars — the
                # program skips its decode fallback entirely (partitioned
                # plans collect per-rank scalars in the program's own
                # chunk pass instead)
                scalars=scalars,
            )
            return program.train(
                num_sweeps=params.coordinate_descent_iterations,
                checkpointer=checkpointer,
                resume=params.resume or restart > 0,
                # a coordinated restart restores the PUBLISHED step on
                # every rank, never each rank's own local newest
                resume_step=(
                    coordinator.resume_step
                    if coordinator is not None else None
                ),
                on_sweep=(
                    None if telemetry is None else
                    lambda sweep, total, loss: telemetry.heartbeat(
                        "game_streaming", sweep=sweep, num_sweeps=total,
                        loss=loss,
                    )
                ),
            )

        if coordinator is not None:
            coordinator.rebind(checkpointer)
        result = run_with_recovery(
            attempt,
            max_restarts=params.max_restarts,
            checkpointer=checkpointer,
            journal=telemetry.journal if telemetry is not None else None,
            description="streamed game train",
            coordinator=coordinator,
        )

    state = result.state
    models: dict = {
        fe_name: FixedEffectModel(
            glm=GeneralizedLinearModel(
                Coefficients(means=state.fe_coefficients),
                params.task_type,
            ),
            feature_shard_id=fe_cfg.feature_shard,
        )
    }
    for n, spec in zip(re_names, re_specs):
        models[n] = RandomEffectModel(
            coefficients=state.re_tables[spec.re_type],
            entity_keys=vocabs[spec.re_type],
            random_effect_type=spec.re_type,
            feature_shard_id=spec.feature_shard_id,
            task=params.task_type,
        )
    model = GameModel(models=models)
    if params.model_output_mode != ModelOutputMode.NONE:
        save_game_model(
            os.path.join(out, "best"), model, index_maps,
            optimization_configurations={
                "regWeights": {
                    n: params.coordinates[n].reg_weights[0] for n in sequence
                }
            },
        )

    # streamed validation scoring (ISSUE 17 rider): chunk-wise scores
    # against the streamed model through the SAME jitted steps the sweeps
    # use — pinned == in-core score_dataset + offsets to float round-off
    best_metric = float("nan")
    validation_metrics: dict = {}
    if params.validation_data_path:
        from photon_ml_tpu.evaluation.evaluators import (
            EvaluationData,
            parse_evaluator,
        )

        with Timed("streamed validation scoring"):
            val_source = GameAvroChunkSource(
                avro_io.list_avro_files(params.validation_data_path),
                shard_configs, index_maps,
                chunk_records=params.streaming_chunks,
                random_effect_id_columns=re_columns,
                entity_vocabs=vocabs,
                on_corrupt=params.on_corrupt,
            )
            val_scores, val_scalars = score_game_stream(
                state, val_source, params.task_type, fe_cfg.feature_shard,
                {spec.re_type: spec.feature_shard_id for spec in re_specs},
                prefetch=params.streaming_prefetch,
                return_scalars=True,
            )
        val_data = EvaluationData(
            labels=val_scalars["labels"],
            offsets=val_scalars["offsets"],
            weights=val_scalars["weights"],
            ids={},
        )
        for spec_str in params.evaluators:
            validation_metrics[spec_str] = float(
                parse_evaluator(spec_str).evaluate(val_scores, val_data)
            )
        if params.evaluators:
            best_metric = validation_metrics[params.evaluators[0]]
        job_log.info(
            "streamed validation: %d records, metrics %s",
            val_source.total_records, validation_metrics,
        )

    evidence = stream_counters.game_stream_evidence()
    summary: dict = {
        "distributed": False,
        "streaming": {
            "chunks": (
                partition.num_chunks if partition is not None
                else source.num_chunks
            ),
            "chunk_rows": source.chunk_rows,
            "records": (
                partition.total_records if partition is not None
                else source.total_records
            ),
            "schedule": "duhl" if schedule is not None else "uniform",
            **evidence,
            **(
                {} if partition is None else {
                    "partitioned": {
                        "plan": partition.fingerprint,
                        "rank": partition.rank,
                        "num_ranks": partition.num_ranks,
                        "chunk_range": list(partition.chunk_range()),
                        "rank_records": source.total_records,
                        "bytes_decoded": source.bytes_decoded,
                        "input_bytes": partition.input_bytes,
                    }
                }
            ),
        },
        "num_configurations": 1,
        "effective_coordinate_configurations": {
            name: format_coordinate_config(cfg)
            for name, cfg in params.coordinates.items()
        },
        "best_configuration_index": 0,
        "best_reg_weights": {
            n: params.coordinates[n].reg_weights[0] for n in sequence
        },
        "best_metric": best_metric,
        "validation_metrics": validation_metrics,
        "losses": [float(x) for x in result.losses],
        "metric_history": [],
    }
    if telemetry is not None and telemetry.journal is not None:
        telemetry.journal.record(
            "config",
            task_type=params.task_type.name,
            distributed=False,
            streaming_chunks=params.streaming_chunks,
            duhl_working_set=params.duhl_working_set,
            partitioned_ranks=(
                partition.num_ranks if partition is not None else 1
            ),
            num_configurations=1,
        )
    return _finish(out, summary, devices_used=1,
                   decode_paths={"train": "avro-python"})


def _run_refresh(
    params: GameTrainingParams,
    job_log: PhotonLogger,
    telemetry: SolverTelemetry | None = None,
) -> dict:
    """The --incremental-refresh pipeline (ISSUE 14, algorithm/refresh.py):
    load the resident model (saved directory, or warm-start re-entry from
    a training run's CD checkpoints), read the refresh data in ITS feature
    space, fingerprint-guard the agreement (layout + λ — a mismatch fails
    fast naming fields), then re-solve only the policy-selected
    random-effect entities against frozen residuals, under
    ``run_with_recovery`` with per-coordinate refresh checkpoints."""
    import jax  # noqa: F401  (platform selection must already be done)

    from photon_ml_tpu.algorithm.refresh import (
        RefreshPolicy,
        check_refresh_fingerprint,
        expected_fingerprint,
        model_fingerprint,
    )
    from photon_ml_tpu.cli.game_scoring_driver import _load_scoring_model
    from photon_ml_tpu.io.checkpoint import (
        TrainingCheckpointer,
        latest_trained_model,
    )
    from photon_ml_tpu.resilience import default_io_policy, run_with_recovery

    out = params.root_output_dir
    reg_weights = {
        name: cfg.reg_weights[0] for name, cfg in params.coordinates.items()
    }

    saved_reg_weights = None
    if params.model_input_dir:
        model, index_maps, feature_shards, entity_vocabs, re_columns = (
            _load_scoring_model(
                model_input_dir=params.model_input_dir,
                index_maps_dir=params.index_maps_dir,
                feature_shards=params.feature_shards,
                compact_random_effect_threshold=(
                    params.compact_random_effect_threshold
                ),
            )
        )
        meta_path = os.path.join(params.model_input_dir, "model-metadata.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                saved = (
                    json.load(f).get("optimizationConfigurations") or {}
                ).get("regWeights")
            if isinstance(saved, dict):
                saved_reg_weights = {k: float(v) for k, v in saved.items()}
    else:
        # warm-start re-entry from PR 8 checkpoint state: the training
        # run's CD checkpoint directory IS the resident model
        with Timed("restore resident model from checkpoint"):
            restored = latest_trained_model(
                TrainingCheckpointer(params.checkpoint_dir)
            )
        if restored is None:
            raise ValueError(
                f"--checkpoint-dir {params.checkpoint_dir!r} holds no "
                "loadable checkpoint; pass --model-input-dir (a saved "
                "model) instead"
            )
        model, step = restored
        job_log.info("resident model restored from checkpoint step %d", step)
        if not params.index_maps_dir:
            raise ValueError(
                "checkpoint warm-start re-entry needs --index-maps-dir "
                "(the training run's saved stores) so the refresh data "
                "reads in the resident model's feature space; or pass "
                "--model-input-dir"
            )
        index_maps = IndexMap.load_directory(params.index_maps_dir)
        feature_shards = params.feature_shards
        entity_vocabs = {}
        re_set = set()
        from photon_ml_tpu.models.game import RandomEffectModel
        from photon_ml_tpu.models.matrix_factorization import (
            MatrixFactorizationModel,
        )

        for m in model.models.values():
            if isinstance(m, RandomEffectModel):
                entity_vocabs[m.random_effect_type] = np.asarray(m.entity_keys)
                re_set.add(m.random_effect_type)
            elif isinstance(m, MatrixFactorizationModel):
                entity_vocabs[m.row_effect_type] = np.asarray(m.row_keys)
                entity_vocabs[m.col_effect_type] = np.asarray(m.col_keys)
                re_set.update((m.row_effect_type, m.col_effect_type))
        re_columns = tuple(sorted(re_set))

    with Timed("read refresh data"):
        part = default_io_policy().call(
            lambda: read_partitioned(
                params.input_data_path,
                feature_shards,
                index_maps=index_maps or None,
                random_effect_id_columns=re_columns,
                evaluation_id_columns=(),
                entity_vocabs=entity_vocabs,
                fmt=params.input_format,
                tag="refresh",
                on_corrupt=params.on_corrupt,
            ),
            description="read refresh data",
        )
        dataset = part.result.dataset
    job_log.info("read %d refresh samples", dataset.num_samples)

    sequence = list(params.update_sequence or params.coordinates.keys())
    coordinate_configs = estimator_coordinate_configs(
        params.coordinates, reg_weights
    )
    # the agreement guard: layout + λ, both sides' differing fields named.
    # λ only cross-checks when the saved model METADATA recorded it (the
    # checkpoint re-entry path has no regWeights record).
    expected = expected_fingerprint(
        dataset, coordinate_configs, sequence,
        reg_weights=reg_weights if saved_reg_weights is not None else None,
    )
    resident_fp = model_fingerprint(
        model, sequence, reg_weights=saved_reg_weights
    )
    check_refresh_fingerprint(resident_fp, expected)

    policy = RefreshPolicy(
        gradient_tolerance=(
            params.refresh_gradient_tolerance
            if params.refresh_gradient_tolerance > 0 else None
        ),
        changed_entities=_parse_changed_entities(
            params.refresh_changed_entities
        ),
        refresh_fixed_effects=params.refresh_fixed_effects,
    )
    refresh_ckpt = None
    if params.checkpoint_dir:
        import shutil

        refresh_dir = os.path.join(params.checkpoint_dir, "refresh")
        if not params.resume and os.path.isdir(refresh_dir):
            # --no-resume: purge stale refresh progress NOW, so a
            # mid-run transient restart (which always resumes — that's
            # what the checkpoint is for) resumes THIS run's steps, never
            # yesterday's completed refresh
            shutil.rmtree(refresh_dir)
        refresh_ckpt = TrainingCheckpointer(refresh_dir)
    estimator = GameEstimator(
        task=params.task_type,
        coordinate_configs=coordinate_configs,
        update_sequence=sequence,
        normalization=params.normalization,
        locked_coordinates=frozenset(params.partial_retrain_locked_coordinates),
        intercept_indices=part.result.intercept_indices,
        telemetry=telemetry,
    )
    if telemetry is not None and telemetry.journal is not None:
        telemetry.journal.record(
            "config",
            task_type=params.task_type.name,
            incremental_refresh=True,
            update_sequence=sequence,
            refresh_gradient_tolerance=params.refresh_gradient_tolerance,
            refresh_changed_entities={
                k: len(v) for k, v in policy.changed_entities.items()
            },
            refresh_fixed_effects=params.refresh_fixed_effects,
        )

    with Timed("incremental refresh"):
        def attempt(restart: int):
            return estimator.refresh(
                dataset, model, policy,
                checkpointer=refresh_ckpt,
                fingerprint=expected,
                # restarts must resume even under --no-resume (the whole
                # point of the restart is the checkpoint)
                resume=params.resume or restart > 0,
            )

        result = run_with_recovery(
            attempt,
            max_restarts=params.max_restarts,
            checkpointer=refresh_ckpt,
            journal=telemetry.journal if telemetry is not None else None,
            description="incremental refresh",
        )

    if params.model_output_mode != ModelOutputMode.NONE:
        save_game_model(
            os.path.join(out, "best"), result.model, index_maps,
            optimization_configurations={"regWeights": reg_weights},
        )
    summary: dict = {
        "distributed": False,
        # ONE source of truth: the RefreshResult (the refresh/* registry
        # counters carry the same numbers into the journal snapshot)
        "incremental_refresh": {
            "lanes_total": result.lanes_total,
            "lanes_solved": result.lanes_solved,
            "lanes_changed": result.lanes_changed,
            "lanes_gradient": result.lanes_gradient,
            "coordinates": result.coordinate_stats,
            "coordinates_refreshed": sum(
                1 for s in result.coordinate_stats.values()
                if s.get("refreshed")
            ),
            "coordinates_carried": sum(
                1 for s in result.coordinate_stats.values()
                if not s.get("refreshed")
            ),
        },
        "num_configurations": 1,
        "effective_coordinate_configurations": {
            name: format_coordinate_config(cfg)
            for name, cfg in params.coordinates.items()
        },
        "best_configuration_index": 0,
        "best_reg_weights": reg_weights,
        "best_metric": float("nan"),
        "metric_history": [],
    }
    return _finish(out, summary, devices_used=1,
                   decode_paths={"train": part.result.decode_path})


def _finish(out: str, summary: dict, *, devices_used: int,
            decode_paths: dict) -> dict:
    """Stamp what ran the job (platform, device kind/count, versions, the
    ingest decoders, per-device memory), write ``training-summary.json``
    and emit the finish event — the shared tail of every training mode."""
    from photon_ml_tpu.telemetry.probes import runtime_stamp

    summary["runtime"] = dict(runtime_stamp(), devices_used=devices_used)
    summary["decode_paths"] = decode_paths
    summary["timings"] = timing_summary()
    with open(os.path.join(out, "training-summary.json"), "w") as f:
        json.dump(_json_safe(summary), f, indent=2, default=float)
    events.send(TrainingFinishEvent(job_name="game-training", succeeded=True))
    return summary


def _json_safe(obj):
    """NaN/Inf -> None so the summary is strict JSON."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game_training_driver", description=__doc__.split("\n")[0]
    )
    p.add_argument("--input-data-path", required=True)
    p.add_argument("--input-date-range",
                   help="yyyyMMdd-yyyyMMdd or N-M days ago: read "
                        "<input>/daily/yyyy/MM/dd dirs in the range")
    p.add_argument("--validation-data-path")
    p.add_argument("--validation-data-date-range")
    p.add_argument("--root-output-dir", required=True)
    p.add_argument(
        "--feature-shard-configurations", action="append", required=True,
        help="name=NAME,feature.bags=BAG|BAG,intercept=true (repeatable)",
    )
    p.add_argument(
        "--coordinate-configurations", action="append", required=True,
        help="name=NAME,feature.shard=SHARD,reg.weights=0.1|1,... (repeatable)",
    )
    p.add_argument("--task-type", required=True,
                   choices=[t.name for t in TaskType if t != TaskType.NONE])
    p.add_argument("--update-sequence", default="",
                   help="comma-separated coordinate order")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--evaluators", default="", help="comma-separated specs")
    p.add_argument("--normalization", default="NONE",
                   choices=[n.name for n in NormalizationType])
    p.add_argument("--data-validation", default="VALIDATE_DISABLED",
                   choices=[v.name for v in DataValidationType])
    p.add_argument("--model-input-dir", help="warm-start model directory")
    p.add_argument("--partial-retrain-locked-coordinates", default="")
    p.add_argument("--model-output-mode", default="ALL",
                   choices=[m.name for m in ModelOutputMode])
    p.add_argument("--hyperparameter-tuning", default="NONE",
                   choices=[m.name for m in HyperparameterTuningMode])
    p.add_argument("--hyperparameter-tuning-iter", type=int, default=10)
    p.add_argument("--hyperparameter-tuning-range", default="1e-4,1e4",
                   help="low,high λ search range (log-scale)")
    p.add_argument("--hyperparameter-prior-json",
                   help="tuned-hyperparameters.json from a previous run, "
                        "used to seed the search")
    p.add_argument("--input-format", default="avro", choices=["avro", "libsvm"])
    p.add_argument("--index-maps-dir",
                   help="reuse index stores built by the feature indexing "
                        "driver (plain .keys or off-heap .photonix)")
    p.add_argument("--override-output", action="store_true")
    p.add_argument("--checkpoint-dir",
                   help="mid-training checkpoint/resume directory")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="save every N coordinate updates")
    p.add_argument("--no-resume", action="store_true",
                   help="ignore existing checkpoints (fresh run)")
    p.add_argument("--profile-dir",
                   help="write a jax.profiler (TensorBoard / xplane) trace "
                        "here: the device's operations and, on the same "
                        "clock, the program's own spans as photon:<name> "
                        "host events (train/fit, train/sweep, "
                        "train/loss_wait, dispatch/<label>, ...)")
    p.add_argument("--telemetry-dir",
                   help="write a rank-0 JSONL run journal (config, phase "
                        "timings, per-coordinate convergence rows, compile/"
                        "HBM gauges) here")
    p.add_argument("--trace-dir",
                   help="write per-rank Chrome-trace span timelines "
                        "(trace-{rank:05d}.json, open in Perfetto) + a "
                        "rank-merged straggler report here; flushed on "
                        "success and failure, spans still open included. "
                        "The device-free timeline of the same spans "
                        "--profile-dir puts beside the device's work")
    p.add_argument("--compact-random-effect-threshold", type=int,
                   default=DEFAULT_COMPACT_RE_THRESHOLD,
                   help="warm-start RE models over this feature-space size "
                        "load as compact per-entity tables")
    p.add_argument("--distributed", action="store_true",
                   help="train through the fused mesh-sharded SPMD program "
                        "over all devices (multi-chip/multi-host path)")
    p.add_argument("--mesh", default="",
                   help="device mesh layout 'data=8,model=1' (implies "
                        "--distributed; model>1 shards the fixed-effect "
                        "feature axis)")
    p.add_argument("--partitioned-io", action="store_true",
                   help="multi-process runs: each rank decodes only ~1/P "
                        "of the input bytes (per-rank partitioned Avro "
                        "ingestion; dense IDENTITY configs, no validation "
                        "riders — see io/partitioned_reader.py)")
    p.add_argument("--on-corrupt", default="raise",
                   choices=["raise", "quarantine"],
                   help="corrupt Avro blocks: 'raise' (strict, default) "
                        "or 'quarantine' (skip-and-count; spans journaled "
                        "via resilience/quarantined_blocks)")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="mid-sweep recovery budget: restore the latest "
                        "intact checkpoint and resume after a divergence/"
                        "transient failure up to N times (0 disables)")
    p.add_argument("--streaming-chunks", type=int, default=0,
                   help="out-of-core streamed GAME: records per chunk "
                        "(> 0 opts in; entity-clustered chunks stream "
                        "through the one-jitted-step accumulators — "
                        "dense single-FE + IDENTITY-RE configs over an "
                        "entity-sorted Avro input)")
    p.add_argument("--no-streaming-prefetch", action="store_true",
                   help="decode chunks inline instead of double-buffered "
                        "(the same-run OFF baseline for overlap evidence)")
    p.add_argument("--duhl-working-set", type=int, default=0,
                   help="DuHL importance-ordered schedule: pin this many "
                        "gap-hottest chunks resident and stream the cold "
                        "tail round-robin (0 = uniform order, bitwise the "
                        "unscheduled streamed sweep)")
    p.add_argument("--duhl-tail-chunks", type=int, default=1,
                   help="cold-tail chunks revisited per sweep under "
                        "--duhl-working-set")
    p.add_argument("--incremental-refresh", action="store_true",
                   help="incremental retrain (ISSUE 14): re-solve only the "
                        "RE entities that saw new data or whose gradient "
                        "at the resident solution exceeds tolerance, "
                        "against frozen residuals — needs --model-input-dir "
                        "or --checkpoint-dir (the resident model)")
    p.add_argument("--refresh-gradient-tolerance", type=float, default=1e-4,
                   help="re-solve entities whose solve-space gradient norm "
                        "at the resident solution exceeds this (0 disables "
                        "the screen: only declared entities re-solve)")
    p.add_argument("--refresh-changed-entities", action="append", default=[],
                   help="reType=key1|key2 — entities DECLARED changed "
                        "(repeatable; the gradient screen catches "
                        "undeclared drift)")
    p.add_argument("--refresh-fixed-effects", action="store_true",
                   help="also re-solve fixed-effect coordinates "
                        "(warm-started) during the refresh")
    return p


def parse_args(argv: Sequence[str] | None = None) -> GameTrainingParams:
    args = build_arg_parser().parse_args(argv)
    shards = dict(
        parse_feature_shard_config(s) for s in args.feature_shard_configurations
    )
    coords = {}
    for spec in args.coordinate_configurations:
        cfg = parse_coordinate_config(spec)
        if cfg.name in coords:
            raise ValueError(f"duplicate coordinate name {cfg.name!r}")
        coords[cfg.name] = cfg
    split = lambda s: tuple(x.strip() for x in s.split(",") if x.strip())
    return GameTrainingParams(
        input_data_path=args.input_data_path,
        input_date_range=args.input_date_range,
        validation_data_path=args.validation_data_path,
        validation_data_date_range=args.validation_data_date_range,
        root_output_dir=args.root_output_dir,
        feature_shards=shards,
        coordinates=coords,
        task_type=TaskType[args.task_type],
        update_sequence=split(args.update_sequence),
        coordinate_descent_iterations=args.coordinate_descent_iterations,
        evaluators=split(args.evaluators),
        normalization=NormalizationType[args.normalization],
        data_validation=DataValidationType[args.data_validation],
        model_input_dir=args.model_input_dir,
        partial_retrain_locked_coordinates=split(
            args.partial_retrain_locked_coordinates
        ),
        model_output_mode=ModelOutputMode[args.model_output_mode],
        hyperparameter_tuning=HyperparameterTuningMode[args.hyperparameter_tuning],
        hyperparameter_tuning_iter=args.hyperparameter_tuning_iter,
        hyperparameter_tuning_range=tuple(
            float(x) for x in args.hyperparameter_tuning_range.split(",")
        ),
        hyperparameter_prior_json=args.hyperparameter_prior_json,
        input_format=args.input_format,
        index_maps_dir=args.index_maps_dir,
        override_output=args.override_output,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=not args.no_resume,
        profile_dir=args.profile_dir,
        telemetry_dir=args.telemetry_dir,
        trace_dir=args.trace_dir,
        compact_random_effect_threshold=args.compact_random_effect_threshold,
        distributed=args.distributed or bool(args.mesh),
        mesh_shape=_parse_mesh_shape(args.mesh),
        partitioned_io=args.partitioned_io,
        on_corrupt=args.on_corrupt,
        max_restarts=args.max_restarts,
        streaming_chunks=args.streaming_chunks,
        streaming_prefetch=not args.no_streaming_prefetch,
        duhl_working_set=args.duhl_working_set,
        duhl_tail_chunks=args.duhl_tail_chunks,
        incremental_refresh=args.incremental_refresh,
        refresh_gradient_tolerance=args.refresh_gradient_tolerance,
        refresh_changed_entities=tuple(args.refresh_changed_entities),
        refresh_fixed_effects=args.refresh_fixed_effects,
    )


def _parse_mesh_shape(spec: str) -> dict[str, int] | None:
    """'data=8,model=1' -> {"data": 8, "model": 1}; '' -> None."""
    if not spec:
        return None
    out: dict[str, int] = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        key, _, value = part.partition("=")
        key = key.strip()
        if (
            key not in ("data", "model")
            or not value.strip().isdigit()
            or int(value) < 1
        ):
            raise ValueError(
                f"bad --mesh component {part!r}; expected data=N,model=M "
                "with N,M >= 1"
            )
        out[key] = int(value)
    return out


def main(argv: Sequence[str] | None = None) -> dict:
    logging.basicConfig(level=logging.INFO)
    from photon_ml_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    # Multi-host pods: rendezvous before any jax.devices() call; a no-op for
    # single-process runs (parallel/multihost.py).
    from photon_ml_tpu.parallel import multihost

    multihost.initialize()
    return run(parse_args(argv))


if __name__ == "__main__":
    main()
