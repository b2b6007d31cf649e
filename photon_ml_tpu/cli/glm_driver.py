"""Legacy single-GLM training driver with staged pipeline + diagnostics.

Reference parity: photon-client Driver.scala — staged pipeline
INIT -> PREPROCESSED -> TRAINED -> VALIDATED -> DIAGNOSED (:158-218), train
via ModelTraining over the λ grid with warm starts (:334-368), validation
metrics + best-model selection (:373-450, ModelSelection.scala), diagnostics
+ HTML report (:608-635, 719-739), text model output (IOUtils
writeModelsInText, :211-215).
"""

from __future__ import annotations

import argparse
import dataclasses
import enum
import json
import logging
import os
from typing import Sequence

import numpy as np

from photon_ml_tpu.data.batch import LabeledPointBatch, summarize
from photon_ml_tpu.data.validators import DataValidationType, validate_arrays
from photon_ml_tpu.diagnostics.metrics import METRIC_DIRECTIONS, evaluate_model
from photon_ml_tpu.diagnostics.report_builder import build_diagnostic_report
from photon_ml_tpu.diagnostics.reporting import render_html, render_text
from photon_ml_tpu.estimators import train_glm, train_glm_grid
from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
from photon_ml_tpu.io.partitioned_reader import read_partitioned
from photon_ml_tpu.io.model_io import write_glm_text
from photon_ml_tpu.ops.normalization import NormalizationType, build_normalization
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.resilience import run_with_recovery
from photon_ml_tpu.telemetry import io_counters
from photon_ml_tpu.telemetry import RunJournal, SolverTelemetry, default_registry
from photon_ml_tpu.telemetry.layout import reset_layout_metrics
from photon_ml_tpu.telemetry.resilience_counters import reset_resilience_metrics
from photon_ml_tpu.telemetry.stream_counters import reset_stream_metrics
from photon_ml_tpu.telemetry.probes import CompileMonitor, runtime_stamp
from photon_ml_tpu.telemetry.solver_trace import reset_solver_metrics
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.util import (
    EventEmitter,
    PhotonLogger,
    SetupEvent,
    Timed,
    TrainingFinishEvent,
    TrainingStartEvent,
)
from photon_ml_tpu.util.timed import reset_timings, timing_summary

logger = logging.getLogger(__name__)

#: process-wide emitter; external telemetry registers listeners here — the
#: reference emitted PhotonSetupEvent/TrainingStart/Finish and per-update
#: PhotonOptimizationLogEvents from Driver.scala:120-393, which this driver
#: previously had no wiring for (only the GAME driver did)
events = EventEmitter()


class DriverStage(enum.Enum):
    """Reference: DriverStage.scala."""

    INIT = 0
    PREPROCESSED = 1
    TRAINED = 2
    VALIDATED = 3
    DIAGNOSED = 4


#: model selection metric per task (reference ModelSelection.scala:
#: best AUC for classification, best RMSE for regression)
_SELECTION_METRIC = {
    TaskType.LOGISTIC_REGRESSION: "AUC",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: "AUC",
    TaskType.LINEAR_REGRESSION: "RMSE",
    TaskType.POISSON_REGRESSION: "POISSON_LOSS",
}


@dataclasses.dataclass
class GLMDriverParams:
    input_data_path: str
    output_dir: str
    task_type: TaskType
    validation_data_path: str | None = None
    regularization_weights: tuple[float, ...] = (0.0,)
    elastic_net_alpha: float = 0.0
    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = 100
    tolerance: float = 1e-7
    normalization: NormalizationType = NormalizationType.NONE
    data_validation: DataValidationType = DataValidationType.VALIDATE_DISABLED
    enable_diagnostics: bool = False
    num_bootstraps: int = 0
    compute_variance: bool = False
    #: train the whole λ grid simultaneously as vmapped solver lanes
    #: (train_glm_grid) instead of the sequential warm-start fold; LBFGS/
    #: OWLQN only — see estimators.train_glm_grid
    grid_parallel: bool = False
    #: JSON constraint list (reference Params.constraintString): maps with
    #: name/term (+ optional lowerBound/upperBound), "*" wildcards allowed
    coefficient_box_constraints: str | None = None
    input_format: str = "avro"
    #: structured-telemetry output dir: a JSONL run journal (phase timings,
    #: per-λ convergence rows, compile-count gauge) finalized on completion;
    #: None = disabled
    telemetry_dir: str | None = None
    #: run-trace output dir (telemetry/tracing.py): host-side span timeline
    #: exported as Chrome-trace JSON (``trace-00000.json``, open in
    #: Perfetto) + a straggler report journaled next to it — flushed on
    #: success AND failure paths. None = disabled (zero overhead).
    trace_dir: str | None = None
    #: corrupt-input handling for Avro ingestion: "raise" (strict,
    #: default) or "quarantine" (skip-and-count corrupt container blocks;
    #: io/avro.py + resilience layer)
    on_corrupt: str = "raise"
    #: out-of-core streaming epochs: records per chunk (> 0 opts in). The
    #: training data is never materialized in core — each solver objective
    #: evaluation is one exact chunked epoch with host Avro decode
    #: double-buffered behind device accumulation
    #: (io/stream_reader.py + algorithm/streaming.py). 0 = off (default),
    #: byte-identical to the in-core path.
    streaming_chunks: int = 0
    #: disable the background prefetch thread (chunks decode inline) — the
    #: same-run OFF baseline for overlap measurements; streaming mode only
    streaming_prefetch: bool = True
    #: crash-safe resume for streaming solves (io/checkpoint.
    #: SolverCheckpointer): optimizer state + λ-grid position + epoch
    #: cursor persist at every epoch boundary; a restarted run
    #: fast-forwards past completed λs and resumes mid-solve. Requires
    #: --streaming-chunks (the in-core solve has no epoch-granular state
    #: to persist). None = disabled.
    checkpoint_dir: str | None = None
    #: iteration cadence for mid-solve snapshots (λ-boundary snapshots
    #: always save): the solver state is model-sized, so giant-d runs
    #: widen this instead of paying a blocking save every iteration
    checkpoint_every: int = 1
    #: crash-safe recovery budget (resilience/recovery.py): a classified-
    #: transient failure (incl. device-loss/pool-preemption shapes)
    #: restarts the run — resuming from the latest intact checkpoint when
    #: --checkpoint-dir is set — up to this many times. 0 disables.
    max_restarts: int = 2
    #: GP-driven model search (hyperparameter/search_driver.py): > 0 opts
    #: in — each round trains --search-lane-budget configs as ONE vmapped
    #: tournament, evaluated on-mesh by the task's selection metric, with
    #: the GP fit overlapping the next round's device solve. Replaces the
    #: --regularization-weights grid; requires --validation-data-path and
    #: --search-space.
    search_rounds: int = 0
    #: configs per tournament round (vmapped solver lanes)
    search_lane_budget: int = 8
    #: search-space grammar, e.g. "lambda=1e-4:1e2:log,alpha=0:1,
    #: tolerance=1e-9:1e-5:log" (see search_driver.parse_search_space)
    search_space: str | None = None
    #: one SeedSequence threads Sobol + the GP slice sampler — a search
    #: trajectory replays deterministically under a fixed seed
    search_seed: int = 0


@dataclasses.dataclass
class GLMDriverResult:
    stage: DriverStage
    models: dict
    best_lambda: float | None
    validation_metrics: dict
    summary_path: str


def _read_batch(path: str, fmt: str, shard_cfg, index_maps=None,
                on_corrupt: str = "raise"):
    # the single-GLM driver is a one-process tool: read through the
    # ingestion dispatcher with the trivial exchange (identical bytes to
    # the old direct read; the lint bans direct read_merged in cli/),
    # wrapped in the transient-I/O retry policy (non-collective read)
    from photon_ml_tpu.parallel.multihost import SingleProcessExchange
    from photon_ml_tpu.resilience import default_io_policy

    result = default_io_policy().call(
        lambda: read_partitioned(
            path, shard_cfg, exchange=SingleProcessExchange(),
            index_maps=index_maps, fmt=fmt, on_corrupt=on_corrupt,
        ),
        description=f"read {path}",
    ).result
    ds = result.dataset
    # ``create`` places the block as the kernels read it: no fit relayouts it
    batch = LabeledPointBatch.create(
        ds.feature_shards["features"], ds.labels, ds.offsets, ds.weights)
    return (batch, result.index_maps,
            result.intercept_indices.get("features"), result.decode_path)


def _check_streaming_supported(params: "GLMDriverParams") -> None:
    """Fail fast, with the alternative named, before any data is read:
    the streaming path never materializes the full batch, so stages that
    re-fit or decompose on the in-core batch cannot ride it."""
    if params.input_format != "avro":
        raise ValueError(
            "--streaming-chunks streams Avro container blocks; for "
            "libsvm inputs drop --streaming-chunks (or convert with "
            "cli/libsvm_to_avro.py and stream the result)"
        )
    if params.grid_parallel:
        raise ValueError(
            "--streaming-chunks trains the λ grid sequentially with warm "
            "starts (vmapped grid lanes need the in-core batch); drop "
            "--grid-parallel"
        )
    if params.enable_diagnostics or params.num_bootstraps:
        raise ValueError(
            "diagnostics re-fit on the in-core batch; drop "
            "--enable-diagnostics/--num-bootstraps or run without "
            "--streaming-chunks"
        )
    if params.compute_variance:
        raise ValueError(
            "coefficient variances decompose the in-core Hessian; drop "
            "--compute-variance or run without --streaming-chunks"
        )
    if params.optimizer == OptimizerType.NEWTON:
        raise ValueError(
            "NEWTON needs the dense [d, d] Hessian; use --optimizer TRON "
            "for streamed second-order solves"
        )


def _check_search_supported(params: "GLMDriverParams") -> None:
    """Fail fast, naming the alternative, before any data is read."""
    if not params.search_space:
        raise ValueError(
            "--search-rounds needs --search-space (grammar: "
            "name=low:high[:log][:int], comma-separated; e.g. "
            "'lambda=1e-4:1e2:log,alpha=0:1')"
        )
    if not params.validation_data_path:
        raise ValueError(
            "--search-rounds selects by the validation metric; pass "
            "--validation-data-path"
        )
    if params.streaming_chunks > 0:
        raise ValueError(
            "--search-rounds trains vmapped tournament lanes on the "
            "in-core batch; drop --streaming-chunks (stream-compose the "
            "winning config afterwards instead)"
        )
    if params.grid_parallel:
        raise ValueError(
            "--search-rounds replaces the λ grid (tournament lanes ARE "
            "the grid generalization); drop --grid-parallel"
        )
    if params.elastic_net_alpha:
        raise ValueError(
            "the elastic-net mix is a search dimension — add 'alpha=0:1' "
            "to --search-space instead of --elastic-net-alpha"
        )
    if params.enable_diagnostics or params.num_bootstraps:
        raise ValueError(
            "diagnostics re-fit the λ grid; run them on the winning "
            "config without --search-rounds"
        )
    if params.compute_variance:
        raise ValueError(
            "coefficient variances are not computed per tournament lane; "
            "re-fit the winning config with --compute-variance"
        )


def _check_checkpoint_supported(params: "GLMDriverParams") -> None:
    if params.checkpoint_dir and params.streaming_chunks <= 0:
        raise ValueError(
            "--checkpoint-dir resumes STREAMING solves (epoch-granular "
            "solver state; io/checkpoint.SolverCheckpointer) — pass "
            "--streaming-chunks N to opt in, or drop --checkpoint-dir "
            "(the in-core path re-runs from scratch under --max-restarts)"
        )
    if params.max_restarts < 0:
        raise ValueError("--max-restarts must be >= 0")
    if params.checkpoint_every < 1:
        raise ValueError("--checkpoint-every must be >= 1")


def run(params: GLMDriverParams) -> GLMDriverResult:
    if params.streaming_chunks > 0:
        _check_streaming_supported(params)
    if params.search_rounds > 0:
        _check_search_supported(params)
    _check_checkpoint_supported(params)
    if (
        params.coefficient_box_constraints
        and params.normalization != NormalizationType.NONE
    ):
        # bounds are stated in original feature space; the solvers work in
        # normalized space (reference Params.scala:219). Checked before any
        # data is read.
        raise ValueError(
            "coefficient box constraints cannot combine with feature "
            "normalization (bounds are stated in original feature space; "
            "the solvers work in normalized space) — drop "
            "normalization.type or the box constraints"
        )
    os.makedirs(params.output_dir, exist_ok=True)
    # per-run phase timings + solver/layout/stream tallies (sweeps may call
    # run() repeatedly)
    reset_timings()
    reset_solver_metrics()
    reset_layout_metrics()
    reset_stream_metrics()
    reset_resilience_metrics()
    journal = (
        RunJournal(params.telemetry_dir) if params.telemetry_dir else None
    )
    # program ledger rides --telemetry-dir (ISSUE 13): labeled jit sites
    # journal per-program compile/cost/signature rows with recompile
    # attribution; inert (null-object) without it
    ledger = None
    if journal is not None:
        from photon_ml_tpu.telemetry.program_ledger import (
            ProgramLedger,
            install_ledger,
        )

        ledger = install_ledger(ProgramLedger(journal=journal))
    # journal + registry are opt-in via --telemetry-dir; the emitter rides
    # along unconditionally (per-λ OptimizationLogEvents for any registered
    # listener). SolverTelemetry builds nothing — paying no host reads —
    # unless one of those sinks would actually consume the record.
    telemetry = SolverTelemetry(
        journal=journal,
        emitter=events,
        registry=default_registry() if journal and journal.active else None,
    )
    config_summary = {
        "task_type": params.task_type.name,
        "optimizer": params.optimizer.name,
        "regularization_weights": list(params.regularization_weights),
        "grid_parallel": params.grid_parallel,
        "max_iterations": params.max_iterations,
        "tolerance": params.tolerance,
        "normalization": params.normalization.name,
        "streaming_chunks": params.streaming_chunks,
        "streaming_prefetch": params.streaming_prefetch,
        "search_rounds": params.search_rounds,
        "search_lane_budget": params.search_lane_budget,
        "search_space": params.search_space,
        "checkpoint_dir": params.checkpoint_dir,
        "max_restarts": params.max_restarts,
        "trace_dir": params.trace_dir,
    }
    events.send(SetupEvent(config_summary=json.dumps(config_summary)))
    events.send(TrainingStartEvent(job_name="glm-training"))
    if journal is not None:
        journal.record("config", **config_summary)
    compiles = CompileMonitor()
    # crash-safe recovery (resilience/recovery.py — today GAME-only, now
    # here too): a classified-transient failure (device loss/preemption,
    # flaky filesystem) restarts the stages up to --max-restarts times; with
    # --checkpoint-dir the streaming solve resumes from the latest intact
    # epoch-boundary snapshot instead of from scratch
    checkpointer = None
    if params.checkpoint_dir:
        from photon_ml_tpu.io.checkpoint import SolverCheckpointer

        checkpointer = SolverCheckpointer(
            params.checkpoint_dir, save_every=params.checkpoint_every
        )
    # NO coordinator here (ISSUE 15): coordinated recovery requires the
    # run's hot path to ride a fenced MetadataExchange — the GLM streaming
    # path performs no exchange ops, so peers would never observe an abort
    # marker and a rank-local transient failure (which the detached
    # restart below genuinely recovers) would instead deadline out at the
    # restart rendezvous and kill the job. Attach one when a multi-rank
    # streamed-GLM surface (exchange-coordinated) lands.
    coordinator = None
    # span tracing is opt-in via --trace-dir; installed IMMEDIATELY before
    # the try whose finally uninstalls it (an exception in between would
    # leak the process-global tracer into the next run), early enough that
    # a failure mid-read still leaves a timeline
    tracer = None
    if params.trace_dir:
        from photon_ml_tpu.telemetry.tracing import Tracer, install_tracer

        tracer = install_tracer(Tracer())
    try:
        with compiles:
            result = run_with_recovery(
                lambda restart: _run_stages(
                    params, telemetry, checkpointer=checkpointer
                ),
                max_restarts=params.max_restarts,
                checkpointer=checkpointer,
                journal=journal,
                description="glm training",
                coordinator=coordinator,
            )
        events.send(TrainingFinishEvent(job_name="glm-training", succeeded=True))
        return result
    except Exception:
        events.send(TrainingFinishEvent(job_name="glm-training", succeeded=False))
        raise
    finally:
        # traces flush FIRST (before the failure journal) so a crash leaves
        # a readable timeline even if journaling itself fails; best-effort —
        # a trace-publication error never masks the run's own outcome
        if tracer is not None:
            from photon_ml_tpu.telemetry.tracing import (
                flush_trace_best_effort,
                uninstall_tracer,
            )

            try:
                flush_trace_best_effort(
                    tracer, params.trace_dir, journal=journal
                )
            finally:
                uninstall_tracer()
        if ledger is not None:
            from photon_ml_tpu.telemetry.program_ledger import uninstall_ledger

            uninstall_ledger()
        # journal phase timings / gauges on failure too — a failed run's
        # journal is the one that most needs them (the registry snapshot
        # carries the resilience/* counters)
        if journal is not None:
            from photon_ml_tpu.telemetry import resilience_counters

            for event in resilience_counters.drain_quarantine_events():
                journal.record("quarantined_block", **event)
            journal.record_timings(timing_summary())
            journal.record_gauge("jax/backend_compile_count", compiles.count)
            journal.record_metrics(default_registry().snapshot())
            journal.close()


def _prepare_streaming(params: GLMDriverParams, shard_cfg):
    """Streaming PREPROCESS: global index maps from one discarding vocab
    pass, the chunked epoch source over the block plan, per-chunk
    validation, and (when requested) normalization statistics from one
    streaming summary pass — the full batch is never materialized."""
    from photon_ml_tpu.algorithm.streaming import streaming_summarize
    from photon_ml_tpu.io.avro import list_avro_files
    from photon_ml_tpu.io.index_map import INTERCEPT_KEY
    from photon_ml_tpu.io.stream_reader import (
        AvroChunkSource,
        ChunkPrefetcher,
        DenseRecordAssembler,
        build_streaming_index_maps,
    )
    from photon_ml_tpu.resilience import default_io_policy

    cfg = shard_cfg["features"]
    files = list_avro_files(params.input_data_path)
    # same journal evidence as the full-read path (read_partitioned sets
    # it there; plan_partitioned_stream on the multi-process path)
    io_counters.set_input_bytes_total(
        sum(int(os.path.getsize(f)) for f in files)
    )
    index_maps = default_io_policy().call(
        lambda: build_streaming_index_maps(
            files, shard_cfg, on_corrupt=params.on_corrupt
        ),
        description=f"streaming vocab pass over {params.input_data_path}",
    )
    imap = index_maps["features"]
    intercept_index = imap.get_index(INTERCEPT_KEY)
    if intercept_index < 0:
        intercept_index = None
    source = AvroChunkSource(
        files,
        DenseRecordAssembler(imap, cfg),
        chunk_records=params.streaming_chunks,
        on_corrupt=params.on_corrupt,
    )
    if params.data_validation != DataValidationType.VALIDATE_DISABLED:
        # one inline pass, validating each chunk's TRUE rows (weight-0
        # chunk padding is layout, not data)
        with ChunkPrefetcher(source, prefetch=False) as chunks:
            for batch, spec in zip(chunks, source.specs):
                n = spec.num_records
                validate_arrays(
                    labels=np.asarray(batch.labels)[:n],
                    task=params.task_type,
                    offsets=np.asarray(batch.offsets)[:n],
                    weights=np.asarray(batch.weights)[:n],
                    feature_shards={
                        "features": np.asarray(batch.features)[:n]
                    },
                    validation_type=params.data_validation,
                )
    norm = None
    if params.normalization != NormalizationType.NONE:
        stats = streaming_summarize(
            source, prefetch=params.streaming_prefetch
        )
        import jax.numpy as jnp

        norm = build_normalization(
            params.normalization,
            mean=jnp.asarray(stats["mean"]),
            variance=jnp.asarray(stats["variance"]),
            max_magnitude=jnp.asarray(stats["max_magnitude"]),
            intercept_index=intercept_index,
        )
    return source, index_maps, intercept_index, norm


def _run_stages(params: GLMDriverParams, telemetry: SolverTelemetry,
                checkpointer=None) -> GLMDriverResult:
    stage = DriverStage.INIT
    shard_cfg = {"features": FeatureShardConfiguration(feature_bags=("features",))}
    streaming = params.streaming_chunks > 0
    # the stream reader decodes container blocks in Python
    decode_paths = {"train": "avro-python"} if streaming else {}

    with PhotonLogger(os.path.join(params.output_dir, "driver.log")) as job_log:
        # PREPROCESS
        batch = None
        with Timed("glm preprocess"):
            if streaming:
                source, index_maps, intercept_index, norm = (
                    _prepare_streaming(params, shard_cfg)
                )
            else:
                batch, index_maps, intercept_index, decode_paths["train"] = (
                    _read_batch(
                        params.input_data_path, params.input_format,
                        shard_cfg, on_corrupt=params.on_corrupt,
                    )
                )
                validate_arrays(
                    labels=np.asarray(batch.labels),
                    task=params.task_type,
                    offsets=np.asarray(batch.offsets),
                    weights=np.asarray(batch.weights),
                    feature_shards={"features": np.asarray(batch.features)},
                    validation_type=params.data_validation,
                )
                norm = None
                if params.normalization != NormalizationType.NONE:
                    stats = summarize(np.asarray(batch.features), np.asarray(batch.weights))
                    import jax.numpy as jnp

                    norm = build_normalization(
                        params.normalization,
                        mean=jnp.asarray(stats["mean"]),
                        variance=jnp.asarray(stats["variance"]),
                        max_magnitude=jnp.asarray(stats["max_magnitude"]),
                        intercept_index=intercept_index,
                    )
        stage = DriverStage.PREPROCESSED
        if streaming:
            job_log.info(
                "preprocessed %d samples, %d features (streaming: %d "
                "chunks of <=%d records)",
                source.total_records, source.dim, source.num_chunks,
                params.streaming_chunks,
            )
        else:
            job_log.info("preprocessed %d samples, %d features", batch.num_samples, batch.dim)

        # TRAIN
        opt = OptimizerConfig(
            optimizer_type=params.optimizer,
            max_iterations=params.max_iterations,
            tolerance=params.tolerance,
        )

        lower_bounds = upper_bounds = None
        if params.coefficient_box_constraints:
            from photon_ml_tpu.io.constraints import build_bound_arrays

            lower_bounds, upper_bounds = build_bound_arrays(
                params.coefficient_box_constraints, index_maps["features"]
            )

        def fit(b: LabeledPointBatch, lams, tel=None) -> dict:
            trainer = train_glm_grid if params.grid_parallel else train_glm
            return trainer(
                b,
                params.task_type,
                optimizer=opt,
                regularization_weights=lams,
                elastic_net_alpha=params.elastic_net_alpha,
                normalization=norm,
                intercept_index=intercept_index,
                compute_variance=params.compute_variance,
                lower_bounds=lower_bounds,
                upper_bounds=upper_bounds,
                telemetry=tel,
            )

        val_batch = None
        search_outcome = None
        with Timed("glm train"):
            if params.search_rounds > 0:
                from photon_ml_tpu.hyperparameter.search_driver import (
                    parse_search_space,
                    run_model_search,
                )

                # the validation batch doubles as the tournament metric
                # input; read it here (VALIDATE below reuses it)
                val_batch, _, _, decode_paths["validation"] = _read_batch(
                    params.validation_data_path, params.input_format,
                    shard_cfg, index_maps, on_corrupt=params.on_corrupt,
                )
                space = parse_search_space(params.search_space)
                search_outcome = run_model_search(
                    batch, val_batch, params.task_type, space,
                    rounds=params.search_rounds,
                    lane_budget=params.search_lane_budget,
                    optimizer=opt,
                    seed=params.search_seed,
                    evaluator=_SELECTION_METRIC[params.task_type],
                    normalization=norm,
                    intercept_index=intercept_index,
                    box_lower=lower_bounds,
                    box_upper=upper_bounds,
                    journal=telemetry.journal,
                    telemetry=telemetry,
                )
                models = {
                    search_outcome.best_config["lambda"]:
                        search_outcome.best_model
                }
                job_log.info(
                    "search best %s=%s config=%s (%d configs over %d rounds)",
                    search_outcome.evaluator_name,
                    search_outcome.best_metric,
                    search_outcome.best_config,
                    params.search_rounds * params.search_lane_budget,
                    params.search_rounds,
                )
            elif streaming:
                from photon_ml_tpu.estimators import train_glm_streaming

                models = train_glm_streaming(
                    source,
                    params.task_type,
                    optimizer=opt,
                    regularization_weights=params.regularization_weights,
                    elastic_net_alpha=params.elastic_net_alpha,
                    normalization=norm,
                    intercept_index=intercept_index,
                    telemetry=telemetry,
                    prefetch=params.streaming_prefetch,
                    lower_bounds=lower_bounds,
                    upper_bounds=upper_bounds,
                    checkpointer=checkpointer,
                )
            else:
                # telemetry only on the primary grid: diagnostics re-fits
                # below would repeat per-λ convergence rows
                models = fit(batch, params.regularization_weights, tel=telemetry)
        stage = DriverStage.TRAINED
        write_glm_text(
            os.path.join(params.output_dir, "models-text"),
            models,
            index_maps["features"],
        )

        # VALIDATE
        best_lambda = None
        validation_metrics: dict = {}
        if params.validation_data_path:
            with Timed("glm validate"):
                if val_batch is None:
                    val_batch, _, _, decode_paths["validation"] = _read_batch(
                        params.validation_data_path, params.input_format,
                        shard_cfg, index_maps, on_corrupt=params.on_corrupt,
                    )
                metric = _SELECTION_METRIC[params.task_type]
                larger = METRIC_DIRECTIONS[metric]
                best_value = None
                for lam, model in sorted(models.items()):
                    m = evaluate_model(model, val_batch)
                    validation_metrics[lam] = m
                    value = m[metric]
                    if np.isnan(value):  # a diverged model never wins
                        continue
                    if best_value is None or (value > best_value) == larger:
                        best_value, best_lambda = value, lam
            stage = DriverStage.VALIDATED
            job_log.info("best λ=%s by %s=%s", best_lambda, metric, best_value)

        # DIAGNOSE
        if params.enable_diagnostics:
            if val_batch is None:
                raise ValueError("diagnostics require --validation-data-path")
            if best_lambda is None:
                raise ValueError(
                    "no model produced a finite validation metric; nothing to diagnose"
                )
            with Timed("glm diagnose"):
                report = build_diagnostic_report(
                    models,
                    batch,
                    val_batch,
                    task=params.task_type,
                    train_fn_for_lambda=lambda lam: (
                        lambda b: fit(b, (lam,))[lam]
                    ),
                    best_lambda=best_lambda,
                    index_map=index_maps["features"],
                    num_bootstraps=params.num_bootstraps,
                    validation_metrics=validation_metrics,
                )
                with open(
                    os.path.join(params.output_dir, "diagnostic-report.html"), "w"
                ) as f:
                    f.write(render_html(report))
                with open(
                    os.path.join(params.output_dir, "diagnostic-report.txt"), "w"
                ) as f:
                    f.write(render_text(report))
            stage = DriverStage.DIAGNOSED

    summary_path = os.path.join(params.output_dir, "glm-summary.json")
    summary = {
        "stage": stage.name,
        "lambdas": sorted(models),
        "best_lambda": best_lambda,
        "validation_metrics": {
            str(k): v for k, v in validation_metrics.items()
        },
        "runtime": runtime_stamp(),
        "decode_paths": decode_paths,
    }
    if search_outcome is not None:
        summary["search"] = {
            "best_config": search_outcome.best_config,
            "best_metric": search_outcome.best_metric,
            "metric": search_outcome.evaluator_name,
            "rounds": len(search_outcome.trajectory),
            "configs": len(search_outcome.observations),
        }
    with open(summary_path, "w") as f:
        json.dump(summary, f, indent=2, default=float)
    return GLMDriverResult(
        stage=stage,
        models=models,
        best_lambda=best_lambda,
        validation_metrics=validation_metrics,
        summary_path=summary_path,
    )


def main(argv: Sequence[str] | None = None) -> GLMDriverResult:
    logging.basicConfig(level=logging.INFO)
    from photon_ml_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    p = argparse.ArgumentParser(prog="glm_driver", description=__doc__.split("\n")[0])
    p.add_argument("--input-data-path", required=True)
    p.add_argument("--validation-data-path")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task-type", required=True,
                   choices=[t.name for t in TaskType if t != TaskType.NONE])
    p.add_argument("--regularization-weights", default="0",
                   help="comma-separated λ grid")
    p.add_argument("--elastic-net-alpha", type=float, default=0.0)
    p.add_argument("--optimizer", default="LBFGS",
                   choices=[o.name for o in OptimizerType])
    p.add_argument("--max-iterations", type=int, default=100)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument("--normalization", default="NONE",
                   choices=[n.name for n in NormalizationType])
    p.add_argument("--data-validation", default="VALIDATE_DISABLED",
                   choices=[v.name for v in DataValidationType])
    p.add_argument("--enable-diagnostics", action="store_true")
    p.add_argument("--num-bootstraps", type=int, default=0)
    p.add_argument("--compute-variance", action="store_true")
    p.add_argument("--grid-parallel", action="store_true",
                   help="train all regularization weights simultaneously as "
                        "vmapped solver lanes (LBFGS/OWLQN only)")
    p.add_argument("--coefficient-box-constraints",
                   help='JSON constraint list, e.g. \'[{"name": "f0", '
                        '"term": "", "lowerBound": 0}]\'; "*" wildcards '
                        "match all features / all terms of a name")
    p.add_argument("--input-format", default="avro", choices=["avro", "libsvm"])
    p.add_argument("--telemetry-dir",
                   help="write a JSONL run journal (phase timings, per-λ "
                        "convergence rows, compile counts) here")
    p.add_argument("--trace-dir",
                   help="write a Chrome-trace span timeline "
                        "(trace-00000.json, open in Perfetto) + straggler "
                        "report here; flushed on success and failure, "
                        "spans still open included (the device-free "
                        "timeline: a jax.profiler session holds the same "
                        "spans beside the device)")
    p.add_argument("--on-corrupt", default="raise",
                   choices=["raise", "quarantine"],
                   help="corrupt Avro blocks: 'raise' (strict, default) "
                        "or 'quarantine' (skip-and-count)")
    p.add_argument("--streaming-chunks", type=int, default=0,
                   help="out-of-core streaming epochs: records per chunk "
                        "(> 0 opts in; the training data never "
                        "materializes in core — host Avro decode is "
                        "double-buffered behind device accumulation). "
                        "0 = off (default, byte-identical in-core path)")
    p.add_argument("--no-streaming-prefetch", action="store_true",
                   help="decode chunks inline instead of on the "
                        "background prefetch thread (the same-run OFF "
                        "baseline for overlap measurements)")
    p.add_argument("--checkpoint-dir",
                   help="crash-safe resume for --streaming-chunks runs: "
                        "solver state + λ-grid position + epoch cursor "
                        "persist at epoch boundaries; a restarted run "
                        "fast-forwards past completed λs and resumes "
                        "mid-solve")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="save the mid-solve snapshot every N solver "
                        "iterations (λ-boundary snapshots always save; "
                        "widen for giant-d runs where the state is large)")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="recovery budget: restart after a classified-"
                        "transient failure (incl. device-loss/preemption "
                        "shapes) up to N times, resuming from the latest "
                        "intact checkpoint when --checkpoint-dir is set "
                        "(0 disables)")
    p.add_argument("--search-rounds", type=int, default=0,
                   help="GP-driven model search: rounds of vmapped config "
                        "tournaments (> 0 opts in; replaces "
                        "--regularization-weights; requires "
                        "--validation-data-path and --search-space)")
    p.add_argument("--search-lane-budget", type=int, default=8,
                   help="configs per tournament round (vmapped solver "
                        "lanes sharing one feature-block read)")
    p.add_argument("--search-space",
                   help="search-space grammar: name=low:high[:log][:int], "
                        "comma-separated; dims: lambda (required), alpha, "
                        "tolerance, box — e.g. "
                        "'lambda=1e-4:1e2:log,alpha=0:1'")
    p.add_argument("--search-seed", type=int, default=0,
                   help="one SeedSequence threads Sobol + the GP slice "
                        "sampler; a trajectory replays deterministically "
                        "under a fixed seed")
    args = p.parse_args(argv)
    return run(
        GLMDriverParams(
            input_data_path=args.input_data_path,
            validation_data_path=args.validation_data_path,
            output_dir=args.output_dir,
            task_type=TaskType[args.task_type],
            regularization_weights=tuple(
                float(x) for x in args.regularization_weights.split(",") if x
            ),
            elastic_net_alpha=args.elastic_net_alpha,
            optimizer=OptimizerType[args.optimizer],
            max_iterations=args.max_iterations,
            tolerance=args.tolerance,
            normalization=NormalizationType[args.normalization],
            data_validation=DataValidationType[args.data_validation],
            enable_diagnostics=args.enable_diagnostics,
            num_bootstraps=args.num_bootstraps,
            compute_variance=args.compute_variance,
            grid_parallel=args.grid_parallel,
            coefficient_box_constraints=args.coefficient_box_constraints,
            input_format=args.input_format,
            telemetry_dir=args.telemetry_dir,
            trace_dir=args.trace_dir,
            on_corrupt=args.on_corrupt,
            streaming_chunks=args.streaming_chunks,
            streaming_prefetch=not args.no_streaming_prefetch,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            max_restarts=args.max_restarts,
            search_rounds=args.search_rounds,
            search_lane_budget=args.search_lane_budget,
            search_space=args.search_space,
            search_seed=args.search_seed,
        )
    )


if __name__ == "__main__":
    main()
