"""GameEstimator: the high-level training facade.

Reference parity: photon-api estimators/GameEstimator.scala —
``fit(data, validationData, configs)`` builds per-coordinate datasets
(:496-584), training-loss evaluator (:592-614), validation evaluators
(:624-696), per-coordinate normalization (:698-727), then runs
CoordinateDescent per optimization configuration (:746-828), warm-starting
each configuration from the previous one's model (:352-366).

Also the single-GLM trainer (reference photon-api ModelTraining.scala:55-228):
loop over sorted regularization weights with warm start.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinate_descent import (
    CoordinateDescentResult,
    run_coordinate_descent,
)
from photon_ml_tpu.algorithm.coordinates import (
    Coordinate,
    CoordinateOptimizationConfig,
    FixedEffectCoordinate,
    ModelCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm.mf_coordinate import (
    MatrixFactorizationCoordinate,
    build_mf_dataset,
)
from photon_ml_tpu.data.batch import LabeledPointBatch, in_kernel_layout, summarize
from photon_ml_tpu.data.game_data import (
    GameDataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.evaluation.evaluators import (
    EvaluationData,
    Evaluator,
    default_evaluator_for_task,
    parse_evaluator,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import (
    NormalizationContext,
    NormalizationType,
    build_normalization,
)
from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch, SparseShard
from photon_ml_tpu.ops.objective import BoundObjective, GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.ops.variance import (
    coefficient_variances,
    diag_inverse_from_hessian,
    inverse_of_diagonal,
    resolve_variance_mode_for,
    validate_variance_mode,
)
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    OptimizerType,
    resolve_auto_optimizer,
    solve,
)
from photon_ml_tpu.telemetry.program_ledger import ledger_jit
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfig:
    """Reference: FixedEffectDataConfiguration + optimization config."""

    feature_shard_id: str
    optimization: CoordinateOptimizationConfig


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfig:
    """Reference: RandomEffectDataConfiguration (:RE type, shard, bounds) +
    optimization config."""

    random_effect_type: str
    feature_shard_id: str
    optimization: CoordinateOptimizationConfig
    active_data_upper_bound: int | None = None
    active_data_lower_bound: int | None = None
    #: reference projector/ProjectorType.scala — INDEX_MAP trains each entity
    #: on its observed feature support; RANDOM on a shared Gaussian sketch
    projector_type: ProjectorType = ProjectorType.IDENTITY
    projected_dim: int | None = None  # RANDOM only
    #: per-entity Pearson feature selection: an entity with c samples keeps
    #: its ceil(ratio*c) best features (reference
    #: numFeaturesToSamplesRatioUpperBound, LocalDataSet.scala:221-280)
    features_to_samples_ratio: float | None = None


@dataclasses.dataclass(frozen=True)
class MatrixFactorizationCoordinateConfig:
    """MF coordinate over a (row entity, col entity) pair — the model family
    the reference declares (README.md:92-95, LatentFactorAvro.avsc) but
    never implemented."""

    row_effect_type: str
    col_effect_type: str
    num_latent_factors: int
    optimization: CoordinateOptimizationConfig
    num_alternations: int = 2
    active_data_upper_bound: int | None = None
    seed: int = 0


CoordinateConfig = (
    FixedEffectCoordinateConfig
    | RandomEffectCoordinateConfig
    | MatrixFactorizationCoordinateConfig
)


@dataclasses.dataclass
class TrainPartition:
    """Partitioned-ingest context for ``GameEstimator`` (multi-process
    runs where ``fit`` receives this rank's LOCAL padded block from
    io/partitioned_reader.py instead of the full dataset).

    info: the reader's PartitionInfo (rank geometry).
    exchange: the run's MetadataExchange (RE bucket structure rides it).
    lane_multiple: per-rank device count along the mesh "data" axis —
        keeps bucket/sample blocks aligned with addressable shards.
    entity_rank_presence: reader diagnostics (RE type -> ranks-per-entity)
        forwarded to the rank-local RE builder's cross-rank warning.
    """

    info: object
    exchange: object
    lane_multiple: int = 1
    entity_rank_presence: Mapping[str, np.ndarray] = dataclasses.field(
        default_factory=dict
    )


@dataclasses.dataclass
class GameEstimator:
    """Trains a GAME model: ordered coordinates, block coordinate descent."""

    task: TaskType
    coordinate_configs: Mapping[str, CoordinateConfig]
    update_sequence: Sequence[str] | None = None
    num_iterations: int = 1
    normalization: NormalizationType = NormalizationType.NONE
    validation_evaluators: Sequence[str] = ()
    locked_coordinates: frozenset[str] = frozenset()
    #: shard id -> index of the intercept column (exempt from normalization,
    #: absorbs the standardization margin shift). Required per shard when
    #: normalization is STANDARDIZATION.
    intercept_indices: Mapping[str, int] = dataclasses.field(default_factory=dict)
    #: optional io.checkpoint.TrainingCheckpointer for mid-training
    #: checkpoint/resume of the coordinate-descent loop (SURVEY.md §5 — a
    #: capability the reference lacks).
    checkpointer: object | None = None
    checkpoint_every: int = 1
    #: set False to ignore an existing checkpoint directory (fresh fit)
    resume: bool = True
    #: pin the partitioned restore to ONE published checkpoint step
    #: (ISSUE 15 coordinated rollback: every rank must restore the step
    #: rank 0 resolved, never its own local newest; 0 = from scratch).
    #: None keeps the newest-intact-step behavior.
    resume_step: int | None = None
    #: raise DivergenceError on non-finite coordinate updates
    check_finite: bool = True
    #: jax.sharding.Mesh ("data", "model") — when set, fit() trains through
    #: the fused mesh-sharded SPMD program (parallel/distributed.py) instead
    #: of the host-loop CD path: one jitted step per sweep spanning every
    #: coordinate, collectives inserted by XLA. This is the cluster-scale
    #: path of the reference (GameTrainingDriver.scala:822-843 →
    #: GameEstimator.fit over Spark executors), reachable from the same
    #: estimator facade.
    mesh: object | None = None
    #: shard the FE coordinate's feature axis over the mesh "model" axis
    #: (giant-d coordinates; requires mesh)
    fe_feature_sharded: bool = False
    #: single-pass Pallas GLM kernel on the primary FE solve. None (default)
    #: = auto: the kernel on TPU — per-device via shard_map when the mesh
    #: has >1 devices, direct when single-device — autodiff elsewhere.
    #: True forces it (interpret mode off-TPU; what the virtual-mesh tests
    #: use), False disables it.
    use_pallas: bool | None = None
    #: optional telemetry.SolverTelemetry: per-coordinate, per-outer-
    #: iteration convergence rows / OptimizationLogEvents from the CD loop
    #: (the drivers thread their run journal + event emitter through here)
    telemetry: object | None = None
    #: partitioned-ingest context (TrainPartition): fit() receives this
    #: rank's LOCAL block and trains through train_partitioned — each rank
    #: feeds only its addressable shards. Requires ``mesh``; v1 supports
    #: dense FE + IDENTITY REs without normalization/validation riders
    #: (see _fit_distributed's guard for the full list).
    partition: "TrainPartition | None" = None

    def fit(
        self,
        dataset: GameDataset,
        validation_dataset: GameDataset | None = None,
        initial_model: GameModel | None = None,
    ) -> CoordinateDescentResult:
        if self.partition is not None and self.mesh is None:
            # the CD path would silently train a full model on this rank's
            # 1/P block — fail before any work
            raise ValueError(
                "partitioned training requires a mesh (the per-rank blocks "
                "feed its addressable shards); pass GameEstimator(mesh=...)"
            )
        if self.mesh is not None:
            return self._fit_distributed(dataset, validation_dataset, initial_model)
        sequence, coordinates = self._build_coordinates(dataset, initial_model)

        train_eval_data = EvaluationData(
            labels=np.asarray(dataset.labels),
            offsets=np.asarray(dataset.offsets),
            weights=np.asarray(dataset.weights),
            ids=dataset.ids,
        )
        validation_scorer = None
        validation_data = None
        evaluators: list[Evaluator] = [parse_evaluator(s) for s in self.validation_evaluators]
        if validation_dataset is not None and evaluators:
            validation_data = EvaluationData(
                labels=np.asarray(validation_dataset.labels),
                offsets=np.asarray(validation_dataset.offsets),
                weights=np.asarray(validation_dataset.weights),
                ids=validation_dataset.ids,
            )

            def validation_scorer(model: GameModel):
                return np.asarray(model.score_dataset(validation_dataset)) + np.asarray(
                    validation_dataset.offsets
                )

        initial_models = dict(initial_model.models) if initial_model is not None else None
        return run_coordinate_descent(
            coordinates,
            sequence,
            self.num_iterations,
            initial_models=initial_models,
            locked_coordinates=self.locked_coordinates,
            training_evaluator=default_evaluator_for_task(self.task),
            training_data=train_eval_data,
            validation_evaluators=evaluators,
            validation_scorer=validation_scorer,
            validation_data=validation_data,
            checkpointer=self.checkpointer,
            checkpoint_every=self.checkpoint_every,
            resume=self.resume,
            check_finite=self.check_finite,
            telemetry=self.telemetry,
        )

    def _build_coordinates(
        self, dataset: GameDataset, initial_model: GameModel | None
    ):
        """The host-loop CD path's coordinate construction, shared by
        ``fit`` and ``refresh``: (sequence, coordinate map) with locked
        coordinates wrapped as ModelCoordinates."""
        sequence = list(self.update_sequence or self.coordinate_configs.keys())
        norms = self._prepare_normalization(dataset)
        coordinates: dict[str, Coordinate] = {}
        for cid in sequence:
            cfg = self.coordinate_configs[cid]
            if cid in self.locked_coordinates:
                if initial_model is None:
                    raise ValueError(
                        f"locked coordinate '{cid}' requires an initial model "
                        "(partial retraining needs a pre-trained model)"
                    )
                coordinates[cid] = ModelCoordinate(
                    coordinate_id=cid,
                    dataset=dataset,
                    model=initial_model.get(cid),
                )
            elif isinstance(cfg, FixedEffectCoordinateConfig):
                coordinates[cid] = FixedEffectCoordinate(
                    coordinate_id=cid,
                    dataset=dataset,
                    feature_shard_id=cfg.feature_shard_id,
                    task=self.task,
                    config=cfg.optimization,
                    normalization=norms.get(cfg.feature_shard_id),
                    intercept_index=self.intercept_indices.get(cfg.feature_shard_id),
                    use_pallas=self.use_pallas,
                )
            elif isinstance(cfg, MatrixFactorizationCoordinateConfig):
                mf_dataset = build_mf_dataset(
                    dataset,
                    cfg.row_effect_type,
                    cfg.col_effect_type,
                    active_data_upper_bound=cfg.active_data_upper_bound,
                    seed=cfg.seed,
                )
                coordinates[cid] = MatrixFactorizationCoordinate(
                    coordinate_id=cid,
                    dataset=dataset,
                    mf_dataset=mf_dataset,
                    task=self.task,
                    config=cfg.optimization,
                    num_latent_factors=cfg.num_latent_factors,
                    num_alternations=cfg.num_alternations,
                    seed=cfg.seed,
                )
            else:
                re_dataset = build_random_effect_dataset(
                    dataset,
                    cfg.random_effect_type,
                    cfg.feature_shard_id,
                    active_data_upper_bound=cfg.active_data_upper_bound,
                    active_data_lower_bound=cfg.active_data_lower_bound,
                    projector_type=cfg.projector_type,
                    projected_dim=cfg.projected_dim,
                    features_to_samples_ratio=cfg.features_to_samples_ratio,
                    # INDEX_MAP (and compact/sparse, which coerces to
                    # INDEX_MAP) + normalization: entity blocks are
                    # rewritten to normalized space at build time (the
                    # reference projects the context per entity,
                    # IndexMapProjectorRDD.scala:134-147)
                    normalization=_build_normalization_for(cfg, dataset, norms),
                )
                coordinates[cid] = RandomEffectCoordinate(
                    coordinate_id=cid,
                    dataset=dataset,
                    re_dataset=re_dataset,
                    task=self.task,
                    config=cfg.optimization,
                    normalization=norms.get(cfg.feature_shard_id),
                    intercept_index=self.intercept_indices.get(cfg.feature_shard_id),
                )
        return sequence, coordinates

    def refresh(
        self,
        dataset: GameDataset,
        resident_model: GameModel,
        policy=None,
        *,
        checkpointer=None,
        fingerprint: dict | None = None,
        resume: bool | None = None,
    ):
        """Incremental retrain (algorithm/refresh.py): re-solve only the
        random-effect entities the policy selects — declared-changed or
        gradient-screened — against frozen residuals from
        ``resident_model``'s scores, warm-started from its coefficients;
        everything unselected carries over bitwise. Strictly opt-in: the
        full-fit ``fit`` path is untouched. Host-loop path only (single
        process, no mesh)."""
        from photon_ml_tpu.algorithm.refresh import (
            RefreshPolicy,
            run_incremental_refresh,
        )

        if self.mesh is not None or self.partition is not None:
            raise ValueError(
                "incremental refresh is the single-process host path; "
                "drop mesh/partition and refresh on one host, or run the "
                "full fused fit to retrain at mesh scale"
            )
        sequence, coordinates = self._build_coordinates(
            dataset, resident_model
        )
        return run_incremental_refresh(
            coordinates,
            sequence,
            resident_model,
            policy if policy is not None else RefreshPolicy(),
            checkpointer=checkpointer if checkpointer is not None
            else self.checkpointer,
            resume=self.resume if resume is None else resume,
            check_finite=self.check_finite,
            telemetry=self.telemetry,
            fingerprint=fingerprint,
        )

    def _check_partition_supported(
        self, sequence, locked, dataset, validation_dataset
    ) -> None:
        """The partitioned-training surface (dense or sparse/hybrid primary
        FE + dense IDENTITY REs, scheduled or not, no global-statistics
        riders) — anything outside it must fail loudly BEFORE any
        rank-local work could silently diverge from the full-read
        semantics."""
        problems: list[str] = []
        if self.mesh is None:
            problems.append("a mesh is required")
        if locked:
            problems.append("locked coordinates")
        if validation_dataset is not None:
            problems.append(
                "validation datasets (score + evaluate partitioned via "
                "parallel/scoring.py instead)"
            )
        if self.normalization != NormalizationType.NONE:
            problems.append(
                "normalization (feature stats would be rank-local)"
            )
        # checkpointing composes since ISSUE 8: train_partitioned gathers
        # the model-sized state on every rank and commits through the
        # rank-0-gated, exchange-barrier'd io.checkpoint.commit_checkpoint,
        # with the partition plan + agreed sparse layout fingerprinted in
        # meta.json (a resume under a different topology fails fast)
        # the primary FE (first trainable fixed effect in the sequence) is
        # the one coordinate that may be sparse — its hybrid head / ELL
        # width were made globally consistent by the partitioned reader
        primary_fe = next(
            (cid for cid in sequence
             if cid not in locked and isinstance(
                 self.coordinate_configs[cid], FixedEffectCoordinateConfig
             )),
            None,
        )
        for cid in sequence:
            cfg = self.coordinate_configs[cid]
            if isinstance(cfg, MatrixFactorizationCoordinateConfig):
                problems.append(f"matrix-factorization coordinate '{cid}'")
                continue
            if isinstance(cfg, RandomEffectCoordinateConfig) and (
                cfg.projector_type != ProjectorType.IDENTITY
                or cfg.features_to_samples_ratio is not None
            ):
                problems.append(
                    f"projected/feature-selected random effect '{cid}'"
                )
            if cfg.optimization.down_sampling_rate < 1.0:
                problems.append(f"down-sampling on '{cid}'")
            if cfg.optimization.compute_variance:
                problems.append(f"compute_variance on '{cid}'")
            if cid != primary_fe and isinstance(
                dataset.feature_shards.get(cfg.feature_shard_id), SparseShard
            ):
                problems.append(
                    f"sparse feature shard on '{cid}' (only the primary "
                    "fixed effect may be sparse)"
                )
        if problems:
            raise ValueError(
                "partitioned training does not support: "
                + "; ".join(sorted(set(problems)))
                + " — use the full-read path for these"
            )

    def _fit_distributed(
        self,
        dataset: GameDataset,
        validation_dataset: GameDataset | None = None,
        initial_model: GameModel | None = None,
    ) -> CoordinateDescentResult:
        """fit() over the fused mesh-sharded SPMD program.

        One jitted step per sweep covers the full coordinate sequence in
        the CONFIGURED ``update_sequence`` order (the fused analogue of
        CoordinateDescent.scala:198-255 — order determines which residuals
        each solve sees), with per-sweep validation scoring and best-model
        tracking — the distributed analogue of run_coordinate_descent.
        Returns the same CoordinateDescentResult shape, so drivers/tuners
        work unchanged.

        Differences from the CD path, by design:
        - the FIRST trainable fixed-effect coordinate in the sequence is
          the primary (the only one that may be sparse / feature-sharded
          over the mesh "model" axis); additional FE coordinates train as
          dense replicated solves inside the same fused step;
        - locked coordinates contribute fixed score offsets (their models
          pass through to the output untouched);
        - variances are computed post-hoc at the final (and best) state:
          for the random-effect coordinates that request them, plus the
          fixed effect whenever any coordinate does.
        """
        from photon_ml_tpu.algorithm.coordinates import (
            ModelCoordinate,
            _solve_config,
        )
        from photon_ml_tpu.parallel.distributed import (
            FixedEffectStepSpec,
            GameTrainProgram,
            MatrixFactorizationStepSpec,
            RandomEffectStepSpec,
            game_model_to_state,
            state_to_game_model,
            train_distributed,
            train_partitioned,
        )

        sequence = list(self.update_sequence or self.coordinate_configs.keys())
        # AUTO resolution needs the solve SHAPE: RE/MF bucket solves are
        # the small-dense Newton-eligible kind, FE solves are not — the
        # spec sites below pass it so AUTO-through-the-estimator behaves
        # exactly like AUTO-through-GameTrainProgram
        task_loss = loss_for_task(self.task)
        locked = set(self.locked_coordinates)
        if locked and initial_model is None:
            raise ValueError(
                "locked coordinates require an initial model "
                "(partial retraining needs a pre-trained model)"
            )
        partition = self.partition
        if partition is not None:
            self._check_partition_supported(
                sequence, locked, dataset, validation_dataset
            )

        fe_ids = [
            cid for cid in sequence
            if cid not in locked
            and isinstance(self.coordinate_configs[cid], FixedEffectCoordinateConfig)
        ]
        # first trainable FE in the sequence is the PRIMARY (the only one
        # that may be sparse / feature-sharded); the rest become dense
        # replicated extra-FE coordinates inside the same fused step
        # (reference GameEstimator.scala:746-828 iterates arbitrary
        # coordinate sets).
        if fe_ids:
            fe_cid = fe_ids[0]
            fe_cfg: FixedEffectCoordinateConfig = self.coordinate_configs[fe_cid]
            fe_shard = fe_cfg.feature_shard_id
        else:
            # RE/MF-only (or locked-FE) layout: the fused step always carries
            # an FE coordinate, so synthesize a zero-width one — the d=0
            # solve is a no-op and its (empty) model is dropped on output
            fe_cid = None
            fe_shard = "__no_fe__"
            while fe_shard in dataset.feature_shards:
                fe_shard = "_" + fe_shard
            fe_cfg = FixedEffectCoordinateConfig(
                fe_shard,
                CoordinateOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=1)
                ),
            )
            def with_empty_shard(ds):
                empty = jnp.zeros((ds.num_samples, 0), dtype=np.asarray(ds.labels).dtype)
                return dataclasses.replace(
                    ds, feature_shards={**ds.feature_shards, fe_shard: empty}
                )
            dataset = with_empty_shard(dataset)
            if validation_dataset is not None:
                validation_dataset = with_empty_shard(validation_dataset)
        fe_intercept = self.intercept_indices.get(fe_shard)

        # feature-axis ("model") sharding wants the FE dim divisible by the
        # mesh model axis: right-pad with zero columns (their coefficients
        # stay exactly 0 — zero data column + L2 — and are sliced off again
        # on output)
        fe_pad = 0
        if self.fe_feature_sharded and fe_cid is not None:
            model_axis = int(self.mesh.shape["model"])
            fe_dim = int(dataset.feature_shards[fe_shard].shape[1])
            fe_pad = (-fe_dim) % model_axis
        if fe_pad:
            def with_padded_fe(ds):
                shard = ds.feature_shards[fe_shard]
                host_cache = dict(ds.host_cache)
                if isinstance(shard, SparseShard):
                    shard = dataclasses.replace(
                        shard, feature_dim=shard.feature_dim + fe_pad,
                        _device=None,
                    )
                else:
                    arr = np.asarray(shard)
                    arr = np.concatenate(
                        [arr, np.zeros((arr.shape[0], fe_pad), arr.dtype)],
                        axis=1,
                    )
                    host_cache[f"shard/{fe_shard}"] = arr
                    shard = jnp.asarray(arr)
                return dataclasses.replace(
                    ds,
                    feature_shards={**ds.feature_shards, fe_shard: shard},
                    host_cache=host_cache,
                )
            dataset = with_padded_fe(dataset)
            if validation_dataset is not None:
                validation_dataset = with_padded_fe(validation_dataset)
        norms = self._prepare_normalization(dataset)

        re_specs: list[RandomEffectStepSpec] = []
        re_datasets = {}
        re_cid_of_type: dict[str, str] = {}
        mf_specs: list[MatrixFactorizationStepSpec] = []
        mf_datasets = {}
        re_normalizations: dict[str, NormalizationContext] = {}
        extra_fe_specs: list[FixedEffectStepSpec] = []
        extra_fe_cid_of_shard: dict[str, str] = {}
        for cid in sequence:
            if cid in locked or cid == fe_cid:
                continue
            cfg = self.coordinate_configs[cid]
            if isinstance(cfg, FixedEffectCoordinateConfig):
                if cfg.feature_shard_id in extra_fe_cid_of_shard or (
                    cfg.feature_shard_id == fe_shard
                ):
                    raise ValueError(
                        f"distributed training: fixed-effect coordinates "
                        f"'{cid}' and another share feature shard "
                        f"'{cfg.feature_shard_id}' — the fused step keys FE "
                        "coordinates by feature shard; merge or rename"
                    )
                extra_fe_cid_of_shard[cfg.feature_shard_id] = cid
                extra_fe_specs.append(FixedEffectStepSpec(
                    feature_shard_id=cfg.feature_shard_id,
                    optimizer=_solve_config(
                        cfg.optimization, loss=task_loss
                    ),
                    l2_weight=cfg.optimization.l2_weight,
                    down_sampling_rate=cfg.optimization.down_sampling_rate,
                    intercept_index=self.intercept_indices.get(
                        cfg.feature_shard_id
                    ),
                ))
                continue
            if isinstance(cfg, MatrixFactorizationCoordinateConfig):
                mf_datasets[cid] = build_mf_dataset(
                    dataset, cfg.row_effect_type, cfg.col_effect_type,
                    active_data_upper_bound=cfg.active_data_upper_bound,
                    seed=cfg.seed,
                )
                mf_specs.append(MatrixFactorizationStepSpec(
                    name=cid,
                    row_effect_type=cfg.row_effect_type,
                    col_effect_type=cfg.col_effect_type,
                    num_latent_factors=cfg.num_latent_factors,
                    optimizer=_solve_config(
                        cfg.optimization, loss=task_loss, small_dense=True
                    ),
                    l2_weight=cfg.optimization.l2_weight,
                    num_alternations=cfg.num_alternations,
                    seed=cfg.seed,
                ))
                continue
            re_type = cfg.random_effect_type
            if re_type in re_cid_of_type:
                raise ValueError(
                    f"distributed training: coordinates "
                    f"'{re_cid_of_type[re_type]}' and '{cid}' share random "
                    f"effect type '{re_type}' — the fused step keys its "
                    "coefficient tables by RE type; merge or rename"
                )
            re_cid_of_type[re_type] = cid
            if partition is not None:
                # rank-local buckets with exchanged global structure — the
                # guard above already limited the surface to dense IDENTITY
                from photon_ml_tpu.data.game_data import (
                    build_random_effect_dataset_partitioned,
                )

                re_datasets[re_type] = build_random_effect_dataset_partitioned(
                    dataset, re_type, cfg.feature_shard_id,
                    partition=partition.info,
                    exchange=partition.exchange,
                    active_data_upper_bound=cfg.active_data_upper_bound,
                    active_data_lower_bound=cfg.active_data_lower_bound,
                    lane_multiple=partition.lane_multiple,
                    entity_rank_presence=(
                        partition.entity_rank_presence.get(re_type)
                    ),
                    tag=cid,
                )
            else:
                re_datasets[re_type] = build_random_effect_dataset(
                    dataset, re_type, cfg.feature_shard_id,
                    active_data_upper_bound=cfg.active_data_upper_bound,
                    active_data_lower_bound=cfg.active_data_lower_bound,
                    projector_type=cfg.projector_type,
                    projected_dim=cfg.projected_dim,
                    features_to_samples_ratio=cfg.features_to_samples_ratio,
                    normalization=_build_normalization_for(cfg, dataset, norms),
                )
            norm = norms.get(cfg.feature_shard_id)
            if norm is not None:
                re_normalizations[re_type] = norm
            re_specs.append(RandomEffectStepSpec(
                re_type=re_type,
                feature_shard_id=cfg.feature_shard_id,
                optimizer=_solve_config(
                    cfg.optimization, loss=task_loss, small_dense=True
                ),
                l2_weight=cfg.optimization.l2_weight,
                # the dataset's projector, not the config's: sparse shards
                # coerce to the compact INDEX_MAP representation
                projector=re_datasets[re_type].projector_type,
                intercept_index=self.intercept_indices.get(cfg.feature_shard_id),
            ))

        # Variances are available for every projector: INDEX_MAP/compact in
        # the solve space scattered back with the means
        # (IndexMapProjectorRDD.scala:103); RANDOM propagated through the
        # sketch as diag(P H_k⁻¹ Pᵀ) — an improvement over the reference's
        # unchanged pass-through (ProjectionMatrixBroadcast.scala:76).

        # the fused sweep trains coordinates in the CONFIGURED sequence
        # order (CoordinateDescent.scala:198-255 — order determines which
        # residuals each solve sees); the synthetic zero-width FE (if any)
        # goes first, where it is a no-op
        cid_to_name: dict[str, str] = {}
        if fe_cid is not None:
            cid_to_name[fe_cid] = fe_shard
        cid_to_name.update({cid: sh for sh, cid in extra_fe_cid_of_shard.items()})
        cid_to_name.update({cid: t for t, cid in re_cid_of_type.items()})
        cid_to_name.update({m.name: m.name for m in mf_specs})
        update_order = [cid_to_name[cid] for cid in sequence
                        if cid not in locked]
        if fe_cid is None:
            update_order = [fe_shard] + update_order

        program = GameTrainProgram(
            self.task,
            FixedEffectStepSpec(
                feature_shard_id=fe_shard,
                optimizer=_solve_config(fe_cfg.optimization, loss=task_loss),
                l2_weight=fe_cfg.optimization.l2_weight,
                down_sampling_rate=fe_cfg.optimization.down_sampling_rate,
            ),
            tuple(re_specs),
            mf_specs=tuple(mf_specs),
            extra_fes=tuple(extra_fe_specs),
            update_order=update_order,
            normalization=norms.get(fe_shard),
            re_normalizations=re_normalizations,
            extra_fe_normalizations={
                sh: norms[sh] for sh in extra_fe_cid_of_shard if sh in norms
            },
            # the single-pass kernel reaches the dense FE solve directly on
            # a single-device mesh and via the shard_map wrapper on a
            # multi-device one (the program gates on the mesh)
            use_pallas_fe=self.use_pallas,
            mesh=self.mesh,
            fe_feature_sharded=self.fe_feature_sharded,
        )

        # locked coordinates: fixed residual offsets + pass-through models
        # (reference ModelCoordinate semantics inside one fused program)
        locked_models: dict[str, object] = {}
        train_ds, val_ds = dataset, validation_dataset
        if locked:
            def locked_total(ds) -> jnp.ndarray:
                total = jnp.zeros_like(ds.offsets)
                for cid in sequence:
                    if cid not in locked:
                        continue
                    m = initial_model.get(cid)
                    locked_models[cid] = m
                    total = total + ModelCoordinate(cid, ds, m).score(m)
                return total

            def with_extra_offsets(ds, extra):
                new_off = ds.offsets + extra
                return dataclasses.replace(
                    ds, offsets=new_off,
                    host_cache={**ds.host_cache, "offsets": np.asarray(new_off)},
                )

            train_ds = with_extra_offsets(dataset, locked_total(dataset))
            if validation_dataset is not None:
                val_ds = with_extra_offsets(
                    validation_dataset, locked_total(validation_dataset)
                )

        warm_state = None
        if initial_model is not None:
            # The estimator's GameModel keys are coordinate ids; the program
            # keys the FE by feature shard and REs by effect type. Re-key
            # before conversion — a mismatch here would silently cold-start
            # every coordinate (missing_ok is for genuinely absent ones).
            program_key: dict[str, str] = {}
            if fe_cid is not None:
                program_key[fe_cid] = fe_shard
            program_key.update(
                {cid: sh for sh, cid in extra_fe_cid_of_shard.items()}
            )
            program_key.update({cid: t for t, cid in re_cid_of_type.items()})
            remapped = {
                program_key.get(cid, cid): m
                for cid, m in initial_model.models.items()
            }
            if fe_pad and fe_shard in remapped:
                from photon_ml_tpu.models.game import FixedEffectModel

                means = np.asarray(remapped[fe_shard].glm.coefficients.means)
                means = np.concatenate([means, np.zeros(fe_pad, means.dtype)])
                remapped[fe_shard] = FixedEffectModel(
                    glm=GeneralizedLinearModel(
                        Coefficients(means=jnp.asarray(means)), self.task
                    ),
                    feature_shard_id=fe_shard,
                )
            warm_state = game_model_to_state(
                program, GameModel(models=remapped), train_ds,
                intercept_index=fe_intercept, missing_ok=True,
                re_datasets=re_datasets, mf_datasets=mf_datasets,
            )

        evaluators: list[Evaluator] = [
            parse_evaluator(s) for s in self.validation_evaluators
        ]
        train_eval_data = EvaluationData(
            labels=np.asarray(dataset.host_array("labels")),
            offsets=np.asarray(dataset.host_array("offsets")),
            weights=np.asarray(dataset.host_array("weights")),
            ids=dataset.ids,
        )
        val_eval_data = None
        if validation_dataset is not None and evaluators:
            val_eval_data = EvaluationData(
                labels=np.asarray(validation_dataset.host_array("labels")),
                offsets=np.asarray(validation_dataset.host_array("offsets")),
                weights=np.asarray(validation_dataset.host_array("weights")),
                ids=validation_dataset.ids,
            )

        if partition is not None:
            # this rank contributes only its local block; the fused step
            # sees the assembled global arrays. No validation/metric riders
            # (the guard rejected them) — score + evaluate partitioned via
            # parallel/scoring.py instead. Scheduled RE coordinates compose:
            # multi-process runs get the collective-safe SPMD scheduler.
            from photon_ml_tpu.algorithm.lane_scheduler import make_schedulers

            result = train_partitioned(
                program,
                {partition.info.rank: (train_ds, re_datasets)},
                self.mesh,
                partition.info.num_ranks,
                num_iterations=self.num_iterations,
                state=warm_state,
                fe_feature_sharded=self.fe_feature_sharded,
                check_finite=self.check_finite,
                schedulers=make_schedulers(re_specs, mesh=self.mesh) or None,
                checkpointer=self.checkpointer,
                checkpoint_every=self.checkpoint_every,
                resume=self.resume,
                resume_step=self.resume_step,
                # the ingest exchange also gates the checkpoint commit
                # barriers (exchange-consistent: a checkpoint exists only
                # for sweeps every rank completed)
                exchange=partition.exchange,
            )
        else:
            result = train_distributed(
                program,
                train_ds,
                re_datasets,
                mf_datasets=mf_datasets,
                mesh=self.mesh,
                num_iterations=self.num_iterations,
                fe_feature_sharded=self.fe_feature_sharded,
                state=warm_state,
                checkpointer=self.checkpointer,
                checkpoint_every=self.checkpoint_every,
                resume=self.resume,
                validation_dataset=val_ds if val_eval_data is not None else None,
                validation_evaluators=evaluators,
                validation_eval_data=val_eval_data,
                training_evaluator=default_evaluator_for_task(self.task),
                training_eval_data=train_eval_data,
                check_finite=self.check_finite,
                on_sweep=(
                    None if self.telemetry is None else
                    lambda sweep, total, loss: self.telemetry.heartbeat(
                        "fused_game", sweep=sweep, num_sweeps=total,
                        loss=loss,
                    )
                ),
            )

        trainable_cids = {} if fe_cid is None else {fe_shard: fe_cid}
        trainable_cids.update(extra_fe_cid_of_shard)
        trainable_cids.update(
            {t: cid for t, cid in re_cid_of_type.items()}
        )

        compute_var = any(
            self.coordinate_configs[cid].optimization.compute_variance
            for cid in sequence if cid not in locked
        )
        variance_re_types = {
            t for t, cid in re_cid_of_type.items()
            if self.coordinate_configs[cid].optimization.compute_variance
        }

        def to_game_model(state) -> GameModel:
            m = state_to_game_model(
                program, state, train_ds,
                intercept_index=fe_intercept,
                compute_variance=compute_var,
                variance_mode=fe_cfg.optimization.variance_mode,
                re_datasets=re_datasets,
                variance_re_types=variance_re_types,
            )
            models_by_name = dict(m.models)
            if fe_pad:
                # slice the zero coefficients of the model-axis padding
                # columns back off (persisted models keep the true dim)
                from photon_ml_tpu.models.game import FixedEffectModel

                c = models_by_name[fe_shard].glm.coefficients
                models_by_name[fe_shard] = FixedEffectModel(
                    glm=GeneralizedLinearModel(
                        Coefficients(
                            means=c.means[:-fe_pad],
                            variances=None if c.variances is None
                            else c.variances[:-fe_pad],
                        ),
                        self.task,
                    ),
                    feature_shard_id=fe_shard,
                )
            # re-key from the program's internal names (FE: feature shard
            # id; RE: effect type; MF: coordinate id) to coordinate ids,
            # preserving the update-sequence order — the CD path's contract
            renamed = {
                trainable_cids.get(k, k): v for k, v in models_by_name.items()
                if not (fe_cid is None and k == fe_shard)  # synthetic FE
            }
            renamed.update(locked_models)
            return GameModel(models={
                cid: renamed[cid] for cid in sequence if cid in renamed
            })

        final_model = to_game_model(result.state)
        best_model = (
            to_game_model(result.best_state)
            if result.best_state is not None else final_model
        )
        if self.telemetry is not None:
            # the fused step carries no per-lane solver state out of the
            # SPMD program; report what the sweep loop does surface —
            # per-sweep evaluation metrics under a synthetic coordinate id
            for i, m in enumerate(result.metric_history or []):
                self.telemetry.record_coordinate(
                    "fused-sweep", i, None, metrics=m
                )
        return CoordinateDescentResult(
            model=final_model,
            best_model=best_model,
            best_metric=result.best_metric,
            metric_history=result.metric_history,
        )

    def _prepare_normalization(self, dataset: GameDataset) -> dict[str, NormalizationContext]:
        """Per-feature-shard normalization from feature summaries (reference
        GameTrainingDriver.prepareNormalizationContexts:545-562)."""
        norms: dict[str, NormalizationContext] = {}
        if self.normalization == NormalizationType.NONE:
            return norms
        weights = np.asarray(dataset.weights)
        for shard_id, features in dataset.feature_shards.items():
            intercept = self.intercept_indices.get(shard_id)
            norm_type = self.normalization
            if norm_type == NormalizationType.STANDARDIZATION and intercept is None:
                # Mean-shifting needs an intercept to absorb the margin shift;
                # without one, fall back to variance scaling only (the
                # reference attaches an intercept to every shard by default,
                # FeatureShardConfiguration).
                logger.warning(
                    "shard '%s' has no intercept_indices entry; using "
                    "SCALE_WITH_STANDARD_DEVIATION instead of STANDARDIZATION",
                    shard_id,
                )
                norm_type = NormalizationType.SCALE_WITH_STANDARD_DEVIATION
            if hasattr(features, "summarize"):  # SparseShard: COO stats
                stats = features.summarize(weights)
                dtype = features.dtype
            else:
                feats = np.asarray(features)
                stats = summarize(feats, weights)
                # match the shard dtype: float64 stats scattered into float32
                # coefficient tables would trip jax's strict promotion rules
                dtype = feats.dtype
            norms[shard_id] = build_normalization(
                norm_type,
                mean=jnp.asarray(stats["mean"], dtype=dtype),
                variance=jnp.asarray(stats["variance"], dtype=dtype),
                max_magnitude=jnp.asarray(stats["max_magnitude"], dtype=dtype),
                intercept_index=intercept,
            )
        return norms


def _build_normalization_for(cfg: RandomEffectCoordinateConfig,
                             dataset: GameDataset, norms) -> "NormalizationContext | None":
    """Context to PRE-normalize an RE coordinate's entity blocks at dataset
    build: INDEX_MAP and RANDOM coordinates (RANDOM normalizes BEFORE
    sketching — exact), and sparse shards (which coerce to the compact
    INDEX_MAP representation). IDENTITY coordinates normalize through the
    objective's context instead; one predicate shared by the CD and fused
    paths so they cannot drift."""
    if cfg.projector_type in (
        ProjectorType.INDEX_MAP, ProjectorType.RANDOM
    ) or isinstance(dataset.feature_shards[cfg.feature_shard_id], SparseShard):
        return norms.get(cfg.feature_shard_id)
    return None


def train_glm_grid(
    batch: LabeledPointBatch,
    task: TaskType,
    *,
    optimizer: OptimizerConfig | None = None,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: float = 0.0,
    normalization: NormalizationContext | None = None,
    intercept_index: int | None = None,
    compute_variance: bool = False,
    variance_mode: str = "auto",
    lower_bounds=None,
    upper_bounds=None,
    telemetry=None,
) -> dict[float, GeneralizedLinearModel]:
    """Train the whole regularization grid *simultaneously* with vmapped
    solver lanes.

    telemetry: optional ``telemetry.SolverTelemetry`` — reports per-λ-lane
    convergence rows plus the cross-lane convergence-reason tally (the
    "every lane pays max_iter" pathology made visible, CLAUDE.md).

    TPU-native alternative to the reference's sequential warm-start fold
    (ModelTraining.scala:202-220, mirrored by :func:`train_glm`): all λ
    lanes share every read of the `[n, d]` feature block, so the per-lane
    margin computation becomes one `X @ W` matmul on the MXU (float32: six
    bfloat16 passes) instead of |λ| separate matvecs. On a TPU v5e at n=400k,
    d=2000 a fit of 100 elastic-net λ reads 1.31 s, 13 ms a λ, where the fold's
    L2 path pays 88 ms a λ (PERF.md 5, PR 47). The trade: lanes start cold and
    run in lock step, so the block pays every evaluation its slowest live lane
    asks for (47 % of what it pays is wanted by the lane it is paid for).

    λ enters the objective as a *traced* per-lane value (the smooth L2 term
    and OWL-QN's l1_weight both accept tracers), so one compiled program
    serves any grid of the same size. Supports LBFGS and OWLQN lanes
    (elastic net included); TRON's trust-region loop is per-lane scalar
    control flow and stays on the sequential path.

    The lane-varying-L2-only special case of the config tournaments in
    algorithm/lane_search.py (per-lane l1/l2/tolerance/box vectors, warm
    starts — the GP model-search substrate); a uniform-config tournament is
    pinned bitwise-identical to this path (tests/test_lane_search.py).
    """
    optimizer = resolve_auto_optimizer(optimizer or OptimizerConfig())
    if optimizer.optimizer_type not in (
        OptimizerType.LBFGS, OptimizerType.OWLQN
    ):
        raise ValueError(
            "train_glm_grid supports LBFGS/OWLQN lanes; use train_glm for "
            f"{optimizer.optimizer_type.name}"
        )
    use_owlqn = (
        elastic_net_alpha > 0.0
        or optimizer.optimizer_type == OptimizerType.OWLQN
    )
    has_bounds = lower_bounds is not None or upper_bounds is not None
    if use_owlqn and has_bounds:
        raise ValueError(
            "box constraints cannot combine with OWL-QN / elastic-net lanes"
        )
    loss = loss_for_task(task)
    objective = _objective_for_batch(batch, loss, 0.0, normalization)
    # cheap typo check always; the full-vs-diagonal capability resolution
    # (L full Hessians at once; sparse objectives are diagonal-only) only
    # matters — and should only be able to fail — when variances are
    # actually requested
    validate_variance_mode(variance_mode)
    resolved_variance = None
    if compute_variance:
        resolved_variance = resolve_variance_mode_for(
            objective, variance_mode, batch.dim,
            num_problems=len(regularization_weights),
        )
    dtype = batch.solve_dtype
    lams = sorted(float(l) for l in regularization_weights)
    l2s = jnp.asarray([(1.0 - elastic_net_alpha) * l for l in lams], dtype)
    # Mirror the sequential path's L1 rule (train_glm): the elastic-net
    # component overrides the config's own l1_weight when alpha > 0.
    if elastic_net_alpha > 0.0:
        l1s = jnp.asarray([elastic_net_alpha * l for l in lams], dtype)
    else:
        l1s = jnp.full((len(lams),), optimizer.l1_weight, dtype)

    bounds = (
        jnp.asarray(lower_bounds, dtype) if lower_bounds is not None
        else jnp.full((batch.dim,), -jnp.inf, dtype),
        jnp.asarray(upper_bounds, dtype) if upper_bounds is not None
        else jnp.full((batch.dim,), jnp.inf, dtype),
    ) if has_bounds else None
    results = _jitted_grid_solve(
        objective, use_owlqn, optimizer.history,
        optimizer.max_iterations, optimizer.tolerance,
        optimizer.rel_function_tolerance, batch, l2s, l1s,
        bounds,
    )
    if telemetry is not None:
        telemetry.record_lanes(
            "glm-grid", results, keys=[{"lambda": lam} for lam in lams]
        )
    norm = objective.normalization
    lane_variances = None
    if compute_variance:
        if resolved_variance == "full":
            # reference-fidelity diag(H⁻¹) per lane; the [L, d, d] Hessian
            # stack shares one read of the feature block
            lane_variances = _jitted_grid_full_variances(
                objective, batch, results.coefficients, l2s
            )
        else:
            diags = _jitted_grid_diagonals(
                objective, batch, results.coefficients, l2s
            )
            lane_variances = inverse_of_diagonal(diags)
    models: dict[float, GeneralizedLinearModel] = {}
    for i, lam in enumerate(lams):
        w = results.coefficients[i]
        means = norm.to_model_space(w, intercept_index)
        variances = None
        if lane_variances is not None:
            variances = norm.variances_to_model_space(lane_variances[i])
        models[lam] = GeneralizedLinearModel(
            Coefficients(means=means, variances=variances), task
        )
    return models


@functools.partial(ledger_jit, label="glm/grid_solve", static_argnums=(0, 1, 2, 3, 4, 5))
def _jitted_grid_solve(objective, use_owlqn, history, max_iter, tolerance,
                       rel_function_tolerance, batch, l2v, l1v, bounds=None):
    """Module-level jit: one compiled vmapped-grid program per
    (objective, optimizer statics) pair, reused across train_glm_grid calls
    of the same shapes. ``bounds``: optional (lower[d], upper[d]) box shared
    by every lane. ``rel_function_tolerance``: the live function-decrease
    stop inside every lane's while_loop — the λ-grid shares the RE-bucket
    pathology of every lane paying the worst lane's max_iter (CLAUDE.md);
    the objective stays use_pallas=False because these lanes are vmapped."""
    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs
    from photon_ml_tpu.optim.owlqn import minimize_owlqn

    bound = objective.bind(batch)
    dtype = l2v.dtype

    def solve_one(l2, l1):
        def vg(w):
            v, g = bound.value_and_grad(w)
            return v + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w

        w0 = jnp.zeros((batch.dim,), dtype)
        if use_owlqn:
            return minimize_owlqn(
                vg, w0, l1_weight=l1,
                max_iter=max_iter, tolerance=tolerance, history=history,
                rel_function_tolerance=rel_function_tolerance,
            )
        return minimize_lbfgs(
            vg, w0, max_iter=max_iter, tolerance=tolerance, history=history,
            rel_function_tolerance=rel_function_tolerance,
            lower_bounds=None if bounds is None else bounds[0],
            upper_bounds=None if bounds is None else bounds[1],
        )

    return jax.vmap(solve_one)(l2v, l1v)


class _RidgeBoundObjective(BoundObjective):
    """A bound objective plus ``0.5·l2·w'w`` with ``l2`` a traced value: what
    lets ONE compiled solve serve every λ of an L2 path. The objective itself
    is built with ``l2_weight`` 0 (the kernel tests it in Python); the term is
    added round it in ``_jitted_grid_solve``'s formula and order."""

    def __init__(self, objective, batch, l2):
        super().__init__(objective, batch)
        self.l2 = l2

    def value(self, w):
        return super().value(w) + 0.5 * self.l2 * jnp.vdot(w, w)

    def value_and_grad(self, w):
        v, g = super().value_and_grad(w)
        return v + 0.5 * self.l2 * jnp.vdot(w, w), g + self.l2 * w

    def hessian_vector(self, w, v):
        return super().hessian_vector(w, v) + self.l2 * v

    def hessian_matrix(self, w):
        h = super().hessian_matrix(w)
        return h + self.l2 * jnp.eye(h.shape[0], dtype=h.dtype)


@functools.partial(ledger_jit, label="glm/path_solve", static_argnums=(0, 1))
def _jitted_path_solve(objective, opt, batch, w0, l2, lower_bounds, upper_bounds):
    """Module-level jit: one compiled solve per (objective statics,
    OptimizerConfig) pair, reused by every λ of a train_glm path and by every
    later train_glm call on the same shapes. The batch, the warm start, the
    L2 weight and the box are ARGUMENTS, so no feature block is baked into a
    program; ``objective`` carries ``l2_weight`` 0. The L1 weight of an
    elastic-net λ lives in the static ``opt``: one program per λ there."""
    return solve(
        opt, _RidgeBoundObjective(objective, batch, l2), w0,
        lower_bounds=lower_bounds, upper_bounds=upper_bounds,
    )


def train_glm_tournament(
    batch: LabeledPointBatch,
    task: TaskType,
    configs,
    *,
    optimizer: OptimizerConfig | None = None,
    warm_start=None,
    normalization: NormalizationContext | None = None,
    intercept_index: int | None = None,
    telemetry=None,
):
    """Train one vmapped config tournament (per-lane l1/l2/tolerance/box
    vectors — the generalization of :func:`train_glm_grid`'s λ-only lanes).

    ``configs``: algorithm.lane_search.LaneConfigs. Returns the
    TournamentResult (per-lane SolverResult stack + model-space GLMs); the
    GP ask/tell loop above it lives in hyperparameter/search_driver.py.
    """
    from photon_ml_tpu.algorithm.lane_search import run_lane_tournament

    return run_lane_tournament(
        batch, task, configs, optimizer=optimizer, warm_start=warm_start,
        normalization=normalization, intercept_index=intercept_index,
        telemetry=telemetry,
    )


def _objective_for_batch(batch, loss, l2_weight, normalization,
                         use_pallas: bool | None = False):
    """Dense or sparse objective by batch type — one train_glm[/grid] code
    path serves both the [n, d] block and the giant-d flat-COO layout.

    use_pallas: False for vmapped-lane consumers (train_glm_grid — a Pallas
    call inside a vmapped solver loop degrades to a serial per-lane loop),
    None (auto) for sequential solves (train_glm)."""
    if isinstance(batch, SparseLabeledPointBatch):
        return SparseGLMObjective(
            loss, l2_weight=l2_weight, normalization=normalization
        )
    return GLMObjective(loss, l2_weight=l2_weight, normalization=normalization,
                        use_pallas=use_pallas)


@functools.partial(ledger_jit, label="glm/grid_diagonals", static_argnums=(0,))
def _jitted_grid_diagonals(objective, batch, coeffs, l2v):
    """All lanes' Hessian diagonals in one shared read of the feature block."""
    per_lane = lambda w, l2: objective.hessian_diagonal(w, batch) + l2
    return jax.vmap(per_lane)(coeffs, l2v)


@functools.partial(ledger_jit, label="glm/grid_full_variances", static_argnums=(0,))
def _jitted_grid_full_variances(objective, batch, coeffs, l2v):
    """All lanes' diag(H⁻¹) (DistributedOptimizationProblem.scala:82-96)."""
    def per_lane(w, l2):
        h = objective.hessian_matrix(w, batch)
        h = h + l2 * jnp.eye(h.shape[0], dtype=h.dtype)
        return diag_inverse_from_hessian(h)

    return jax.vmap(per_lane)(coeffs, l2v)


def train_glm(
    batch: LabeledPointBatch,
    task: TaskType,
    *,
    optimizer: OptimizerConfig | None = None,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: float = 0.0,
    normalization: NormalizationContext | None = None,
    intercept_index: int | None = None,
    compute_variance: bool = False,
    variance_mode: str = "auto",
    lower_bounds=None,
    upper_bounds=None,
    telemetry=None,
) -> dict[float, GeneralizedLinearModel]:
    """Single-GLM regularization path with warm starts.

    Reference: ModelTraining.trainGeneralizedLinearModel (ModelTraining.scala:
    106-228) — foldLeft over sorted λs, warm-starting each from the previous.
    elastic_net_alpha: fraction of λ on L1 (α λ ‖w‖₁ + (1-α) λ/2 ‖w‖²).
    Returned models are in original feature space (warm starts stay in
    normalized space internally).

    Every λ is solved by ONE cached program (``_jitted_path_solve``) whose
    arguments are the batch, the warm start, the box and the L2 weight: an
    L2 path compiles one program whatever its length, and a caller that
    refits a resident batch (same shapes, same optimizer, the same
    ``normalization`` object) pays a dispatch per λ — nothing is traced,
    lowered or loaded again. Nothing between two λ waits on the device.
    On a TPU a dense block the kernels will read is put row-major before the
    first λ unless it already lies so (``LabeledPointBatch.create`` places
    it): the programs then read X as it lies and copy nothing.

    telemetry: optional ``telemetry.SolverTelemetry`` — one convergence row
    (iterations, reason, value history) per λ solve.
    """
    optimizer = resolve_auto_optimizer(optimizer or OptimizerConfig())
    validate_variance_mode(variance_mode)
    has_bounds = lower_bounds is not None or upper_bounds is not None
    if has_bounds and (
        elastic_net_alpha > 0.0
        or optimizer.optimizer_type
        not in (OptimizerType.LBFGS, OptimizerType.LBFGSB)
    ):
        # fail before any lambda trains; solve() enforces the same rule
        raise ValueError(
            "box constraints require the LBFGS family without L1 "
            "(elastic_net_alpha must be 0)"
        )
    loss = loss_for_task(task)
    if isinstance(batch, LabeledPointBatch):
        # the same object for a batch ``create`` placed; else ONE relayout a
        # fit, not two a solve (data/batch.in_kernel_layout)
        batch = batch.replace(features=in_kernel_layout(batch.features))
    if lower_bounds is not None:
        lower_bounds = jnp.asarray(lower_bounds, batch.dtype)
    if upper_bounds is not None:
        upper_bounds = jnp.asarray(upper_bounds, batch.dtype)
    # λ's L2 is an argument of the solve, so the program's objective has none
    objective = _objective_for_batch(batch, loss, 0.0, normalization,
                                     use_pallas=None)
    norm = objective.normalization
    models: dict[float, GeneralizedLinearModel] = {}
    w = jnp.zeros((batch.dim,), dtype=batch.solve_dtype)
    for lam in sorted(regularization_weights):
        l1 = elastic_net_alpha * lam
        l2 = (1.0 - elastic_net_alpha) * lam
        opt = optimizer
        if l1 > 0.0:
            opt = dataclasses.replace(
                optimizer.with_l1(l1), optimizer_type=OptimizerType.OWLQN
            )
        result = _jitted_path_solve(
            objective, opt, batch, w, np.asarray(l2, batch.solve_dtype),
            lower_bounds, upper_bounds,
        )
        w = result.coefficients
        if telemetry is not None:
            telemetry.record_solve("glm", result, extra={
                "lambda": lam, "optimizer": opt.optimizer_type.name})
            telemetry.heartbeat("glm", lam=lam, n_lambdas=len(regularization_weights))
        means = norm.to_model_space(w, intercept_index)
        variances = None
        if compute_variance:
            # the Hessian of THIS λ's objective: its own L2 inside
            variances = norm.variances_to_model_space(coefficient_variances(
                _objective_for_batch(batch, loss, l2, normalization,
                                     use_pallas=None),
                w, batch, mode=variance_mode,
            ))
        models[lam] = GeneralizedLinearModel(
            Coefficients(means=means, variances=variances), task
        )
        if logger.isEnabledFor(logging.INFO):  # the reads wait for the device
            logger.info(
                "trained λ=%g: value=%g iters=%d", lam, float(result.value), int(result.iterations)
            )
    return models


def _normalization_digest(norm) -> str | None:
    """16-hex content digest of a NormalizationContext's factor/shift
    arrays (None for no normalization) — the streaming checkpoint
    fingerprint field that makes a resume under DIFFERENT normalization
    statistics fail fast (the class name cannot: every non-NONE type is
    the same NormalizationContext)."""
    if norm is None:
        return None
    import hashlib

    h = hashlib.sha256()
    for part in (norm.factors, norm.shifts):
        if part is None:
            h.update(b"none")
        else:
            h.update(np.ascontiguousarray(jax.device_get(part)).tobytes())
    return h.hexdigest()[:16]


def train_glm_streaming(
    source,
    task: TaskType,
    *,
    optimizer: OptimizerConfig | None = None,
    regularization_weights: Sequence[float] = (0.0,),
    elastic_net_alpha: float = 0.0,
    normalization: NormalizationContext | None = None,
    intercept_index: int | None = None,
    telemetry=None,
    mesh=None,
    exchange=None,
    prefetch: bool = True,
    retry_policy=None,
    chunk_timeout: float | None = None,
    lower_bounds=None,
    upper_bounds=None,
    checkpointer=None,
) -> dict[float, GeneralizedLinearModel]:
    """Single-GLM regularization path over an OUT-OF-CORE chunk stream.

    The streaming twin of :func:`train_glm` (reference
    ModelTraining.scala:106-228's warm-started foldLeft over sorted λs):
    ``source`` is an ``io.stream_reader.ChunkSource`` whose data never
    materializes in core — every objective evaluation is one exact chunked
    epoch (algorithm/streaming.StreamingGLMObjective), host decode
    double-buffered behind device accumulation, and the solvers run their
    identical per-iteration math in ``host_loop`` mode. Final
    loss/coefficients match the in-core solve to float round-off (chunked
    summation order is the only difference; tests/test_streaming.py pins
    it on dense and hybrid-sparse fixtures).

    LBFGS/OWLQN/TRON only (NEWTON needs the dense [d, d] Hessian — use
    TRON for streamed second-order solves). ``exchange``: optional
    ``parallel.multihost.MetadataExchange`` — each rank streams its own
    block assignment and the per-epoch accumulators sum in rank order.
    ``prefetch=False`` decodes inline (the OFF side that tests compare the
    prefetching run with, bitwise).

    ``checkpointer``: optional ``io.checkpoint.SolverCheckpointer`` —
    crash-safe resume for the streaming path. Every outer solver iteration
    (an epoch boundary: each iteration is an integral number of chunked
    epochs) persists the full optimizer state + λ-grid position + epoch
    cursor through the atomic checkpoint contract; a restarted run
    fast-forwards past completed λs, re-enters the in-flight solve
    MID-STATE (no epochs redone — counted on ``resilience/
    epochs_resumed``), and continues bitwise where it left off (one eval
    path, state arrays round-trip exactly). A checkpoint written under a
    different λ grid/optimizer/input fingerprint fails fast with the
    differing fields named. None (default) is bitwise the un-checkpointed
    path. With ``exchange``, only rank 0 writes (shared directory); every
    rank restores the same snapshot — the per-rank solves are
    deterministic replicas after the rank-ordered accumulator sums.
    """
    from photon_ml_tpu.algorithm.streaming import StreamingGLMObjective
    from photon_ml_tpu.io.stream_reader import DEFAULT_CHUNK_TIMEOUT
    from photon_ml_tpu.optim.optimizer import solver_state_class
    from photon_ml_tpu.telemetry import resilience_counters

    # AUTO -> LBFGS: a streamed host-loop objective is never the small-d
    # dense vmapped shape Newton promotion targets
    optimizer = resolve_auto_optimizer(optimizer or OptimizerConfig())
    if optimizer.optimizer_type == OptimizerType.NEWTON:
        raise ValueError(
            "NEWTON cannot stream (dense [d, d] Hessian); use TRON for "
            "streamed second-order solves"
        )
    has_bounds = lower_bounds is not None or upper_bounds is not None
    if has_bounds and (
        elastic_net_alpha > 0.0
        or optimizer.optimizer_type
        not in (OptimizerType.LBFGS, OptimizerType.LBFGSB)
    ):
        # same rule as train_glm: fail before any lambda trains
        raise ValueError(
            "box constraints require the LBFGS family without L1 "
            "(elastic_net_alpha must be 0)"
        )
    loss = loss_for_task(task)
    solve_dtype = jnp.float32
    src_dtype = getattr(source, "dtype", None)
    if src_dtype is None and hasattr(source, "features"):
        src_dtype = source.features.dtype
    if src_dtype is not None:
        from photon_ml_tpu.data.batch import solve_dtype_of

        solve_dtype = solve_dtype_of(src_dtype)
    lams = sorted(float(l) for l in regularization_weights)

    # -- crash-safe resume: the fingerprint pins everything a restored
    # solver state is only valid under; a stale/mismatched checkpoint
    # fails fast attributed instead of silently resuming a different solve
    fingerprint = None
    start_index = 0
    completed: list[tuple[float, np.ndarray]] = []
    resume_state_arrays = None
    epochs_total = 0
    resume_epochs_lambda = 0
    writes = exchange is None or exchange.rank == 0
    if checkpointer is not None:
        # EVERYTHING a restored solver state is only valid under — a
        # changed history size would mis-slot L-BFGS curvature pairs, a
        # changed tolerance/task/normalization would silently resume a
        # different solve; all of it fails fast attributed instead
        fingerprint = {
            "kind": "glm_streaming",
            "task": task.name,
            "lambdas": lams,
            "optimizer": optimizer.optimizer_type.name,
            "max_iterations": int(optimizer.max_iterations),
            "history": int(optimizer.history),
            "tolerance": float(optimizer.tolerance),
            "rel_function_tolerance": (
                None if optimizer.rel_function_tolerance is None
                else float(optimizer.rel_function_tolerance)
            ),
            "max_cg_iterations": int(optimizer.max_cg_iterations),
            "elastic_net_alpha": float(elastic_net_alpha),
            # content digest, not a class name: every non-NONE
            # normalization type builds the same NormalizationContext
            # class — only the factor/shift ARRAYS distinguish the solve
            # space a restored state is valid in
            "normalization": _normalization_digest(normalization),
            "intercept_index": (
                None if intercept_index is None else int(intercept_index)
            ),
            "bounded": bool(
                lower_bounds is not None or upper_bounds is not None
            ),
            "dim": int(source.dim),
            "num_chunks": int(source.num_chunks),
            "total_records": int(source.total_records),
            "num_ranks": 1 if exchange is None else int(exchange.num_ranks),
            # input IDENTITY, not just shape: a daily re-run against new
            # data of the same geometry must fail fast, not resume the old
            # run's mid-solve state against different bytes (file-backed
            # sources only; in-memory sources carry no stable identity)
            "input": (
                None if getattr(source, "files", None) is None
                else [
                    [os.path.basename(f), int(os.path.getsize(f))]
                    for f in source.files
                ]
            ),
        }
        progress = checkpointer.restore_progress(fingerprint)
        if progress is not None:
            start_index = progress.lam_index
            completed = list(progress.completed)
            resume_state_arrays = progress.state_arrays
            epochs_total = progress.epochs_total
            resume_epochs_lambda = progress.epochs_lambda
            resilience_counters.record_checkpoint_restore()
            resilience_counters.record_epochs_resumed(
                progress.epochs_total + progress.epochs_lambda
            )
            logger.info(
                "resuming streaming solve from checkpoint: λ %d/%d, "
                "iteration %d (%d epochs not redone)",
                start_index, len(lams), progress.iteration,
                progress.epochs_total + progress.epochs_lambda,
            )

    models: dict[float, GeneralizedLinearModel] = {}
    w = jnp.zeros((source.dim,), dtype=solve_dtype)
    for li, lam in enumerate(lams):
        l1 = elastic_net_alpha * lam
        l2 = (1.0 - elastic_net_alpha) * lam
        objective = StreamingGLMObjective(
            source, loss,
            l2_weight=l2,
            normalization=normalization,
            mesh=mesh,
            exchange=exchange,
            prefetch=prefetch,
            retry_policy=retry_policy,
            chunk_timeout=(
                DEFAULT_CHUNK_TIMEOUT if chunk_timeout is None
                else chunk_timeout
            ),
        )
        norm = objective.objective.normalization
        if li < start_index:
            # completed before the restored checkpoint: the saved
            # solve-space coefficients ARE the model (and the next λ's
            # warm start) — zero epochs spent
            w = jnp.asarray(completed[li][1], solve_dtype)
            models[lam] = GeneralizedLinearModel(
                Coefficients(means=norm.to_model_space(w, intercept_index)),
                task,
            )
            continue
        opt = optimizer
        if l1 > 0.0:
            opt = dataclasses.replace(
                optimizer.with_l1(l1), optimizer_type=OptimizerType.OWLQN
            )
        resume_state = None
        if li == start_index and resume_state_arrays is not None:
            resume_state = checkpointer.solver_state(
                solver_state_class(opt), resume_state_arrays
            )
            objective.epochs = resume_epochs_lambda
        observers = []
        if telemetry is not None:
            # per-outer-iteration (== epoch-boundary) liveness heartbeat
            # (ISSUE 12): the epoch cursor a wedged run is diagnosed by,
            # appended to the crash-durable journal stage; observes only
            def _hb_observer(state, _li=li, _obj=objective):
                telemetry.heartbeat(
                    "glm_streaming", lam_index=_li, n_lambdas=len(lams),
                    iteration=int(state.iteration), epochs=_obj.epochs,
                )

            observers.append(_hb_observer)
        if checkpointer is not None and writes:
            def state_observer(state, _li=li, _obj=objective,
                               _mi=opt.max_iterations):
                if int(state.iteration) % checkpointer.save_every:
                    return  # cadence: model-sized snapshots are not free
                if int(state.reason) != 0 or int(state.iteration) >= _mi:
                    # the loop exits on this state; the λ-boundary
                    # snapshot right after solve() covers it — don't pay
                    # a second model-sized save for the same progress
                    return
                checkpointer.save_progress(
                    fingerprint=fingerprint,
                    lam_index=_li,
                    iteration=int(state.iteration),
                    epochs_total=epochs_total,
                    epochs_lambda=_obj.epochs,
                    completed=completed,
                    solver_state=state,
                )

            observers.append(state_observer)
        if not observers:
            state_observer = None
        elif len(observers) == 1:
            state_observer = observers[0]
        else:
            def state_observer(state, _obs=tuple(observers)):
                for obs in _obs:
                    obs(state)
        result = solve(
            opt, objective, w,
            lower_bounds=(
                None if lower_bounds is None
                else jnp.asarray(lower_bounds, solve_dtype)
            ),
            upper_bounds=(
                None if upper_bounds is None
                else jnp.asarray(upper_bounds, solve_dtype)
            ),
            host_loop=True,
            state_observer=state_observer,
            resume_state=resume_state,
        )
        w = result.coefficients
        if checkpointer is not None:
            completed.append((lam, np.asarray(jax.device_get(w))))
            epochs_total += objective.epochs
            if writes:
                # λ-boundary snapshot: a crash between λs resumes with
                # this λ done and no in-flight solver state
                checkpointer.save_progress(
                    fingerprint=fingerprint,
                    lam_index=li + 1,
                    iteration=0,
                    epochs_total=epochs_total,
                    epochs_lambda=0,
                    completed=completed,
                    solver_state=None,
                )
        if telemetry is not None:
            telemetry.record_solve(
                "glm_streaming", result,
                extra={"lambda": lam, "epochs": objective.epochs,
                       "chunks": source.num_chunks,
                       "optimizer": opt.optimizer_type.name},
            )
        models[lam] = GeneralizedLinearModel(
            Coefficients(means=norm.to_model_space(w, intercept_index)), task
        )
        logger.info(
            "streamed λ=%g: value=%g iters=%d epochs=%d",
            lam, float(result.value), int(result.iterations),
            objective.epochs,
        )
    return models
