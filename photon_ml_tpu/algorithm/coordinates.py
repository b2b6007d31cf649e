"""Training coordinates: fixed-effect and random-effect updates.

Reference parity: photon-api algorithm/FixedEffectCoordinate.scala:91-165
(broadcast model, treeAggregate-driven optimize, score = map dot-product),
algorithm/RandomEffectCoordinate.scala:104-153 (per-entity local solves),
locked-model coordinates (FixedEffectModelCoordinate,
RandomEffectModelCoordinate), algorithm/CoordinateFactory.scala:50-111.

TPU-native:
- Fixed effect: one jitted solve over the sample-sharded batch; gradients
  all-reduce over the mesh "data" axis automatically under jit (this is
  where Spark treeAggregate went).
- Random effect: ``vmap(minimize_*)`` over each entity bucket — thousands of
  independent solvers advancing in lock-step on the MXU instead of
  thousands of RDD records each running breeze. Warm start flows in as the
  per-entity coefficient rows; results scatter back into the [E, d] table.
- Residual offsets arrive via ``extra_offsets`` (the partial-score
  mechanism of CoordinateDescent, reference Coordinate.scala:60-63).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
from photon_ml_tpu.sampling import down_sampler_for_task
from photon_ml_tpu.data.game_data import GameDataset, RandomEffectDataset
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    DatumScoringModel,
    FixedEffectModel,
    RandomEffectModel,
    score_random_effect,
)
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.ops.variance import (
    FULL_VARIANCE_MAX_DIM,
    coefficient_variances,
    diag_inverse_from_hessian,
    full_inverse_from_hessian,
    inverse_of_diagonal,
    resolve_variance_mode,
    resolve_variance_mode_for,
    validate_variance_mode,
)
from photon_ml_tpu.optim.common import LaneTrace, LaneTraces, lane_trace_of
from photon_ml_tpu.telemetry.program_ledger import ledger_jit
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    OptimizerType,
    resolve_auto_optimizer,
    solve,
)
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.types import TaskType

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CoordinateOptimizationConfig:
    """Per-coordinate optimization settings (reference
    GLMOptimizationConfiguration: optimizer + reg weights + variance flag)."""

    optimizer: OptimizerConfig
    l2_weight: float = 0.0
    l1_weight: float = 0.0
    compute_variance: bool = False
    variance_mode: str = "auto"  # "auto" | "full" (diag(H⁻¹)) | "diagonal"
    down_sampling_rate: float = 1.0

    def __post_init__(self):
        validate_variance_mode(self.variance_mode)

    @property
    def uses_owlqn(self) -> bool:
        return self.l1_weight > 0.0 or self.optimizer.optimizer_type == OptimizerType.OWLQN


class Coordinate:
    """One block of the coordinate-descent update (reference Coordinate[D])."""

    coordinate_id: str

    def update_model(self, model: DatumScoringModel, extra_offsets: Array):
        """Train this coordinate with residual offsets; returns (model, info)."""
        raise NotImplementedError

    def score(self, model: DatumScoringModel) -> Array:
        raise NotImplementedError

    def initial_model(self) -> DatumScoringModel:
        raise NotImplementedError


def _make_objective(task: TaskType, cfg: CoordinateOptimizationConfig,
                    normalization: NormalizationContext | None,
                    sparse: bool = False,
                    use_pallas: bool | None = False) -> GLMObjective | SparseGLMObjective:
    """use_pallas MUST stay False for any objective whose solve is vmapped
    (per-entity RE/MF buckets, λ-grid lanes): `lax.while_loop` bodies trace
    with UNBATCHED tracers, so runtime batch-tracer detection cannot see the
    vmap — a Pallas call baked into the loop body then gets batched into a
    serial per-lane loop (~lanes× slower; seen in round 4). Only
    un-vmapped solve paths (the FE coordinate) pass None (= auto/on-TPU)."""
    if sparse:
        return SparseGLMObjective(
            loss_for_task(task),
            l2_weight=cfg.l2_weight,
            normalization=normalization,
        )
    return GLMObjective(
        loss_for_task(task),
        l2_weight=cfg.l2_weight,
        normalization=normalization,
        use_pallas=use_pallas,
    )


def _solve_config(
    cfg: CoordinateOptimizationConfig,
    *,
    loss=None,
    small_dense: bool = False,
) -> OptimizerConfig:
    """Concrete solver config for one coordinate solve: resolves AUTO
    (NEWTON on eligible small-d dense vmapped solves — RE/MF buckets —
    LBFGS elsewhere; optim/optimizer.resolve_auto_optimizer) and then
    applies the elastic-net OWLQN flip, which overrides any resolution
    exactly as it overrides an explicit LBFGS."""
    opt = resolve_auto_optimizer(
        cfg.optimizer, loss=loss, small_dense=small_dense
    )
    if cfg.uses_owlqn:
        opt = dataclasses.replace(
            opt, optimizer_type=OptimizerType.OWLQN, l1_weight=cfg.l1_weight
        )
    return opt


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    """Trains one GLM on a feature shard over the full (sharded) sample axis.

    Models are held in *original* feature space: training converts the warm
    start into normalized space, solves there, and converts back
    (NormalizationContext.to_model_space), so scoring and persistence never
    need the normalization context (reference saves original-space models
    too, NormalizationContext.modelToOriginalSpace).
    """

    coordinate_id: str
    dataset: GameDataset
    feature_shard_id: str
    task: TaskType
    config: CoordinateOptimizationConfig
    normalization: NormalizationContext | None = None
    intercept_index: int | None = None
    #: single-pass kernel on this (un-vmapped, dense) solve: None = TPU
    #: auto, True = force (interpret off-TPU), False = off
    use_pallas: bool | None = None
    _update_count: int = dataclasses.field(default=0, init=False, repr=False)

    def initial_model(self) -> FixedEffectModel:
        shard = self.dataset.feature_shards[self.feature_shard_id]
        from photon_ml_tpu.data.batch import solve_dtype_of

        return FixedEffectModel(
            glm=GeneralizedLinearModel(
                Coefficients.zeros(
                    shard.shape[1], dtype=solve_dtype_of(shard.dtype)
                ),
                self.task,
            ),
            feature_shard_id=self.feature_shard_id,
        )

    def update_model(self, model: FixedEffectModel, extra_offsets: Array | None = None):
        batch = self.dataset.fixed_effect_batch(self.feature_shard_id, extra_offsets)
        if self.config.down_sampling_rate < 1.0:
            # Training-only thinning via weight zeroing (reference
            # DistributedOptimizationProblem.runWithSampling:145-160); scoring
            # below still covers every sample. The seed rotates per update so
            # excluded rows differ across coordinate-descent iterations, like
            # the reference's per-update random seed — but deterministically.
            sampler = down_sampler_for_task(self.task, self.config.down_sampling_rate)
            new_w = sampler.down_sample_weights(
                np.asarray(self.dataset.labels),
                np.asarray(self.dataset.weights),
                self.dataset.unique_ids,
                seed=self._update_count,
            )
            self._update_count += 1
            batch = batch.replace(weights=jnp.asarray(new_w, dtype=batch.weights.dtype))
        # default use_pallas=None (auto): the FE solve is the one UN-vmapped
        # dense hot loop, where the single-pass Pallas kernel measures ~2x
        # the autodiff path on TPU (BASELINE.md r4 study; harmless no-op for
        # sparse batches, whose objective has no kernel)
        objective = _make_objective(
            self.task, self.config, self.normalization,
            sparse=isinstance(batch, SparseLabeledPointBatch),
            use_pallas=self.use_pallas,
        )
        if self.config.compute_variance:
            # fail a full-variance-on-sparse config BEFORE the (possibly
            # giant-d, hours-long) solve, not after
            resolve_variance_mode_for(
                objective, self.config.variance_mode, batch.dim
            )
        norm = objective.normalization
        w0 = norm.from_model_space(model.glm.coefficients.means, self.intercept_index)
        result = _jitted_fe_solve(
            objective, _solve_config(self.config, loss=objective.loss),
            batch, w0,
        )
        means = norm.to_model_space(result.coefficients, self.intercept_index)
        variances = None
        if self.config.compute_variance:
            variances = norm.variances_to_model_space(
                coefficient_variances(
                    objective, result.coefficients, batch,
                    mode=self.config.variance_mode,
                )
            )
        glm = GeneralizedLinearModel(
            Coefficients(means=means, variances=variances), self.task
        )
        return FixedEffectModel(glm=glm, feature_shard_id=self.feature_shard_id), result

    def score(self, model: FixedEffectModel) -> Array:
        return model.score_dataset(self.dataset)


@partial(ledger_jit, label="coord/fe_solve", static_argnums=(0, 1))
def _jitted_fe_solve(objective: GLMObjective, opt: OptimizerConfig,
                     batch: LabeledPointBatch, w0: Array):
    return solve(opt, objective.bind(batch), w0)


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Per-entity solves over bucketed padded blocks, vmapped."""

    coordinate_id: str
    dataset: GameDataset
    re_dataset: RandomEffectDataset
    task: TaskType
    config: CoordinateOptimizationConfig
    normalization: NormalizationContext | None = None
    intercept_index: int | None = None
    #: probe/rescue lane-scheduler state (algorithm/lane_scheduler.py),
    #: created on first scheduled update when the coordinate's
    #: OptimizerConfig carries a LaneSchedulerConfig; persists across CD
    #: iterations (host bucket caches + cross-sweep active sets)
    _scheduler: object = dataclasses.field(default=None, init=False, repr=False)
    #: (iteration, num_iterations) from the CD loop — the active set needs
    #: to know the final sweep (it runs everyone). Standalone update_model
    #: calls leave it None, which means "treat as final": never skip.
    _sweep_context: tuple = dataclasses.field(default=None, init=False, repr=False)
    #: bool [num_entities] refresh selection (algorithm/refresh.py): when
    #: set, update_model re-solves ONLY the selected entities' lanes
    #: (compacted; warm-started from the incoming table) and the rest carry
    #: over BITWISE. None (default) is the unchanged full solve — the
    #: refresh path is strictly opt-in.
    _refresh_selection: object = dataclasses.field(default=None, init=False, repr=False)
    #: SchedulerStats of the last refresh-selected solve (telemetry)
    last_refresh_stats: object = dataclasses.field(default=None, init=False, repr=False)

    def set_sweep(self, iteration: int, num_iterations: int) -> None:
        """Cross-sweep context hook, called by run_coordinate_descent before
        each update (CoordinateDescent.scala:198-255's per-iteration loop is
        where the reference knows the sweep index too)."""
        self._sweep_context = (iteration, num_iterations)

    def set_refresh_selection(self, selected: "np.ndarray | None") -> None:
        """Install (or clear, with None) the refresh policy's entity
        selection for the next ``update_model`` — the partial-retraining
        counterpart of the reference's locked coordinates
        (CoordinateDescent.scala:44-49), at ENTITY granularity instead of
        coordinate granularity (algorithm/refresh.py)."""
        if selected is None:
            self._refresh_selection = None
            return
        selected = np.ascontiguousarray(selected, dtype=bool)
        if selected.shape != (self.re_dataset.num_entities,):
            raise ValueError(
                f"refresh selection covers {selected.shape} but coordinate "
                f"'{self.coordinate_id}' has "
                f"{self.re_dataset.num_entities} entities"
            )
        self._refresh_selection = selected

    def initial_model(self) -> RandomEffectModel:
        from photon_ml_tpu.data.batch import solve_dtype_of

        re = self.re_dataset
        dtype = solve_dtype_of(
            self.dataset.feature_shards[re.feature_shard_id].dtype
        )
        return RandomEffectModel(
            # compact (sparse-shard) coordinates hold [E, K] tables over each
            # entity's active columns; dense hold [E, dim]
            coefficients=jnp.zeros(
                (re.num_entities, re.table_width), dtype=dtype
            ),
            entity_keys=self.dataset.entity_vocabs[re.random_effect_type],
            random_effect_type=re.random_effect_type,
            feature_shard_id=re.feature_shard_id,
            task=self.task,
            active_cols=re.active_cols,
            feature_dim=re.dim if re.is_compact else None,
        )

    def _prepare_solve(self, model: RandomEffectModel, extra_offsets: Array | None):
        """Shared solve prologue for ``update_model`` and
        ``refresh_gradient_norms``: validates the projector/normalization
        composition and converts the model into solve space. Returns
        (objective, projector, full_offsets, norm, compact_cols, table)."""
        projector = self.re_dataset.projector_type
        if (
            projector == ProjectorType.RANDOM
            and self.normalization is not None
            and not self.re_dataset.pre_normalized
        ):
            # normalization must be applied BEFORE the sketch (exact),
            # which happens at dataset build; a post-hoc context cannot be
            # folded through P (the reference's projected-context approach,
            # ProjectionMatrixBroadcast.projectNormalizationContext, does
            # not commute with per-feature scaling and is not reproduced)
            raise ValueError(
                "RANDOM-projected coordinate with normalization: the "
                "RandomEffectDataset must be built with the same "
                "normalization (build_random_effect_dataset(normalization=...)) "
                "so features are normalized before sketching"
            )
        # RANDOM-projected variances are PROPAGATED properly below:
        # var(w) = diag(P H_k⁻¹ Pᵀ). (The reference back-projects means but
        # passes the projected-space variance vector through unchanged —
        # ProjectionMatrixBroadcast.scala:76 — which we refuse to reproduce;
        # this is the mathematically consistent improvement.)
        if (
            self.re_dataset.is_compact
            and self.normalization is not None
            and self.normalization.shifts is not None
        ):
            raise ValueError(
                "compact (sparse-shard) random-effect coordinates support "
                "SCALE-only normalization; mean shifts (STANDARDIZATION) "
                "would densify the feature space"
            )
        if (
            projector == ProjectorType.INDEX_MAP
            and self.normalization is not None
            and not self.re_dataset.pre_normalized
        ):
            raise ValueError(
                "INDEX_MAP coordinate with normalization: the "
                "RandomEffectDataset must be built with the same "
                "normalization (build_random_effect_dataset(normalization=...)) "
                "so entity blocks are pre-normalized"
            )
        if self.re_dataset.pre_normalized and self.normalization is None:
            raise ValueError(
                "this RandomEffectDataset was built pre-normalized but the "
                "coordinate has no normalization context — its solved "
                "tables would be emitted as model-space coefficients while "
                "actually living in normalized space"
            )
        # pre-normalized projected blocks already hold x' = (x-shift)*factor
        # (INDEX_MAP: per-entity gathered columns; RANDOM: normalized before
        # sketching), so the SOLVE runs on a plain objective; table/model
        # conversions and variance post-processing still use the context
        solve_norm = (
            None if projector in (ProjectorType.INDEX_MAP, ProjectorType.RANDOM)
            else self.normalization
        )
        objective = _make_objective(self.task, self.config, solve_norm)
        full_offsets = self.dataset.offsets
        if extra_offsets is not None:
            full_offsets = full_offsets + extra_offsets
        norm = (
            self.normalization if self.normalization is not None
            else no_normalization()
        )
        compact_cols = (
            jnp.asarray(self.re_dataset.active_cols)
            if self.re_dataset.is_compact else None
        )
        if compact_cols is not None:
            # compact tables convert per entity through gathered factors
            table = norm.from_model_space_compact(
                model.coefficients, compact_cols
            )
        else:
            table = norm.from_model_space(model.coefficients, self.intercept_index)
        return objective, projector, full_offsets, norm, compact_cols, table

    def update_model(self, model: RandomEffectModel, extra_offsets: Array | None = None):
        objective, projector, full_offsets, norm, compact_cols, table = (
            self._prepare_solve(model, extra_offsets)
        )
        # AUTO resolves to NEWTON here: the per-entity bucket solve is
        # exactly the small-d dense vmapped shape the batched-Newton
        # solver is written for (optim/newton.py)
        opt = _solve_config(self.config, loss=objective.loss, small_dense=True)

        traces: list[LaneTrace] = []
        refresh_sel = self._refresh_selection
        if refresh_sel is not None:
            table, traces = self._solve_refresh(
                objective, opt, projector, full_offsets, table, refresh_sel
            )
        elif opt.scheduler is not None:
            table, traces = self._solve_scheduled(
                objective, opt, projector, full_offsets, table
            )
        elif projector == ProjectorType.INDEX_MAP:
            # extra scratch column absorbs the padding scatter/gather slots
            table_ext = jnp.concatenate(
                [table, jnp.zeros((table.shape[0], 1), table.dtype)], axis=1
            )
            for bucket in self.re_dataset.buckets:
                table_ext, trace = _jitted_re_bucket_solve_indexmap(
                    objective, opt,
                    bucket.features, bucket.labels, bucket.weights,
                    bucket.sample_rows, bucket.entity_rows, bucket.col_index,
                    full_offsets, table_ext,
                )
                traces.append(trace)
            table = table_ext[:, :-1]
        elif projector == ProjectorType.RANDOM:
            matrix = jnp.asarray(self.re_dataset.projection.matrix, dtype=table.dtype)
            for bucket in self.re_dataset.buckets:
                table, trace = _jitted_re_bucket_solve_random(
                    objective, opt,
                    bucket.features, bucket.labels, bucket.weights,
                    bucket.sample_rows, bucket.entity_rows,
                    matrix, full_offsets, table,
                )
                traces.append(trace)
        else:
            for bucket in self.re_dataset.buckets:
                table, trace = _jitted_re_bucket_solve(
                    objective, opt,
                    bucket.features, bucket.labels, bucket.weights,
                    bucket.sample_rows, bucket.entity_rows,
                    full_offsets, table,
                )
                traces.append(trace)
        variances = None
        if self.config.compute_variance:
            # per-entity diag(H⁻¹): one batched Cholesky per bucket
            # (reference SingleNodeOptimizationProblem.computeVariances:58-69
            # runs this per RDD record; here the entity axis is vmapped).
            # Mode resolution budgets for the whole [e, d, d] Hessian stack
            # of the largest bucket, not one Hessian. Entities in no bucket
            # (below active_data_lower_bound / vocab-only) keep NaN — "no
            # variance computed" — and the model writer drops their
            # variances field rather than persisting a false 0.
            max_bucket = max(
                (b.entity_rows.shape[0] for b in self.re_dataset.buckets),
                default=1,
            )
            if projector == ProjectorType.RANDOM:
                # propagate through the sketch: var(w) = diag(P H_k⁻¹ Pᵀ)
                resolved = random_variance_mode(
                    self.config.variance_mode,
                    self.re_dataset.dim,
                    int(self.re_dataset.projection.matrix.shape[1]),
                    max_bucket,
                )
                kernel = (
                    _jitted_re_bucket_variances_random if resolved == "full"
                    else _jitted_re_bucket_variances_random_diagonal
                )
                matrix = jnp.asarray(
                    self.re_dataset.projection.matrix, dtype=table.dtype
                )
                var_table = jnp.full_like(table, jnp.nan)
                for bucket in self.re_dataset.buckets:
                    var_table = kernel(
                        objective,
                        bucket.features, bucket.labels, bucket.weights,
                        bucket.sample_rows, bucket.entity_rows,
                        matrix, full_offsets, table, var_table,
                    )
            elif projector == ProjectorType.INDEX_MAP:
                # solve-space diag(H⁻¹) over each entity's active columns,
                # scattered back through the same index maps as the means —
                # the reference's IndexMapProjectorRDD.scala:103 contract.
                # Inactive columns keep NaN ("no variance computed": the
                # reference's projected model simply has no entry there).
                width = max(
                    (int(b.features.shape[2]) for b in self.re_dataset.buckets),
                    default=1,
                )
                resolved = resolve_variance_mode(
                    self.config.variance_mode, width, num_problems=max_bucket
                )
                kernel = (
                    _jitted_re_bucket_variances_indexmap
                    if resolved == "full"
                    else _jitted_re_bucket_variances_indexmap_diagonal
                )
                table_ext = jnp.concatenate(
                    [table, jnp.zeros((table.shape[0], 1), table.dtype)],
                    axis=1,
                )
                var_ext = jnp.full_like(table_ext, jnp.nan)
                for bucket in self.re_dataset.buckets:
                    var_ext = kernel(
                        objective,
                        bucket.features, bucket.labels, bucket.weights,
                        bucket.sample_rows, bucket.entity_rows,
                        bucket.col_index, full_offsets, table_ext, var_ext,
                    )
                var_table = var_ext[:, :-1]
            else:
                resolved = resolve_variance_mode(
                    self.config.variance_mode, self.re_dataset.dim,
                    num_problems=max_bucket,
                )
                kernel = (
                    _jitted_re_bucket_variances if resolved == "full"
                    else _jitted_re_bucket_variances_diagonal
                )
                var_table = jnp.full_like(table, jnp.nan)
                for bucket in self.re_dataset.buckets:
                    var_table = kernel(
                        objective,
                        bucket.features, bucket.labels, bucket.weights,
                        bucket.sample_rows, bucket.entity_rows,
                        full_offsets, table, var_table,
                    )
            variances = (
                norm.variances_to_model_space_compact(var_table, compact_cols)
                if compact_cols is not None
                else norm.variances_to_model_space(var_table)
            )
        table = (
            norm.to_model_space_compact(table, compact_cols)
            if compact_cols is not None
            else norm.to_model_space(table, self.intercept_index)
        )
        if refresh_sel is not None:
            # untouched entities carry over BITWISE — the compacted solve
            # never scatters into their rows, and this restore also erases
            # any normalization from/to-model-space round-off on them
            sel = jnp.asarray(refresh_sel)[:, None]
            table = jnp.where(
                sel, table, jnp.asarray(model.coefficients, dtype=table.dtype)
            )
            # variances follow the same carry-over rule: unselected
            # entities KEEP the resident variances; selected entities get
            # the freshly computed ones, or NaN ("no variance computed" —
            # the model writer drops NaN) when this refresh did not run
            # the variance pass. A refresh must never silently drop the
            # resident model's variances or overwrite carried entities'
            # variances under the new residuals.
            if variances is not None or model.variances is not None:
                nans = jnp.full(table.shape, jnp.nan, table.dtype)
                variances = jnp.where(
                    sel,
                    nans if variances is None
                    else jnp.asarray(variances, dtype=table.dtype),
                    nans if model.variances is None
                    else jnp.asarray(model.variances, dtype=table.dtype),
                )
        # info = the per-bucket lane traces: the coordinate-descent loop
        # hands them to telemetry (convergence-reason tallies over every
        # vmapped entity lane). LaneTraces keeps the device arrays unmerged —
        # no eager concatenate dispatches — so an update with no telemetry
        # attached pays nothing; consumers merge host-side.
        info = LaneTraces(traces) if traces else None
        return dataclasses.replace(
            model, coefficients=table, variances=variances
        ), info

    def score(self, model: RandomEffectModel) -> Array:
        return model.score_dataset(self.dataset)

    def _scheduler_blocks(self, projector) -> list:
        """Bucket field dicts in the shape the lane scheduler consumes."""
        return [
            {
                "features": b.features,
                "labels": b.labels,
                "weights": b.weights,
                "sample_rows": b.sample_rows,
                "entity_rows": b.entity_rows,
                **({"col_index": b.col_index}
                   if projector == ProjectorType.INDEX_MAP else {}),
            }
            for b in self.re_dataset.buckets
        ]

    def _projection_matrix(self, projector, dtype):
        return (
            jnp.asarray(self.re_dataset.projection.matrix, dtype=dtype)
            if projector == ProjectorType.RANDOM else None
        )

    def _solve_scheduled(self, objective, opt, projector, full_offsets, table):
        """Probe/rescue (+ cross-sweep active-set) solve of every bucket via
        algorithm/lane_scheduler.py; returns (table, host-numpy traces)."""
        # lazy import: lane_scheduler builds on this module's bucket solvers
        from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler

        if self._scheduler is None or self._scheduler.config != opt.scheduler:
            self._scheduler = LaneScheduler(opt.scheduler)
        iteration, num_iterations = self._sweep_context or (0, 1)
        table, traces, _stats = self._scheduler.solve(
            objective, opt, self._scheduler_blocks(projector), full_offsets,
            table,
            projector=projector,
            matrix=self._projection_matrix(projector, table.dtype),
            final_sweep=iteration >= num_iterations - 1,
        )
        return table, traces

    def _solve_refresh(self, objective, opt, projector, full_offsets, table,
                       selected: np.ndarray):
        """Refresh-policy solve (algorithm/refresh.py): the lane scheduler's
        active-set freezing promoted to an EXTERNALLY chosen set — compact
        and re-solve only the selected entities' lanes with the full
        iteration budget, warm-started from the resident table rows;
        unselected rows are never scattered into. A fresh scheduler per
        call: a refresh selection does not outlive its update."""
        from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler
        from photon_ml_tpu.optim.optimizer import LaneSchedulerConfig

        base = dataclasses.replace(opt, scheduler=None)
        # probe budget == the whole budget: one compacted solve of the
        # selected lanes, no rescue phase
        # the probe IS the whole solve here (no rescue phase), so the
        # "probe flags rarely fire without a live function stop" warning
        # does not apply
        scheduler = LaneScheduler(
            LaneSchedulerConfig(probe_iterations=base.max_iterations),
            warn_no_live_stop=False,
        )
        scheduler.freeze_rows(~selected)
        table, traces, stats = scheduler.solve(
            objective, base, self._scheduler_blocks(projector), full_offsets,
            table,
            projector=projector,
            matrix=self._projection_matrix(projector, table.dtype),
            final_sweep=False,
        )
        self.last_refresh_stats = stats
        return table, traces

    def refresh_gradient_norms(
        self, model: RandomEffectModel, extra_offsets: Array | None = None
    ) -> np.ndarray:
        """[num_entities] solve-space gradient norms of ``model`` at its own
        coefficients — the refresh policy's screening signal
        (algorithm/refresh.py): an entity whose data changed since the
        resident solve leaves a gradient well above rounding scale, while a
        converged untouched entity sits at it. One vmapped gradient pass
        per bucket (no solver state); entities in no bucket return NaN
        (nothing to re-solve)."""
        objective, projector, full_offsets, _norm, _cols, table = (
            self._prepare_solve(model, extra_offsets)
        )
        num_rows = int(table.shape[0])
        out = np.full(num_rows, np.nan)
        matrix = self._projection_matrix(projector, table.dtype)
        if projector == ProjectorType.INDEX_MAP:
            table_ext = jnp.concatenate(
                [table, jnp.zeros((num_rows, 1), table.dtype)], axis=1
            )
        for b in self.re_dataset.buckets:
            if projector == ProjectorType.INDEX_MAP:
                norms = _jitted_re_bucket_grad_norms_indexmap(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, b.col_index,
                    full_offsets, table_ext,
                )
            elif projector == ProjectorType.RANDOM:
                norms = _jitted_re_bucket_grad_norms_random(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, matrix,
                    full_offsets, table,
                )
            else:
                norms = _jitted_re_bucket_grad_norms(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, full_offsets, table,
                )
            rows = np.asarray(b.entity_rows)
            valid = (rows >= 0) & (rows < num_rows)
            out[rows[valid]] = np.asarray(norms)[valid]
        return out


def _bucket_offsets(sample_rows: Array, full_offsets: Array) -> Array:
    safe = jnp.maximum(sample_rows, 0)
    return jnp.where(sample_rows >= 0, full_offsets[safe], 0.0)


def _solve_bucket_entities(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,  # [e, cap, k]
    labels: Array,  # [e, cap]
    weights: Array,  # [e, cap]
    offsets: Array,  # [e, cap]
    w0s: Array,  # [e, k]
) -> tuple[Array, LaneTrace]:
    """vmapped per-entity solves: ([e, k] solved coefficients, [e] trace).

    The trace carries each lane's final iteration count / convergence reason
    / value and its line-search work (optim/common.lane_trace_of) — small
    extra outputs XLA computes anyway; consumers that only want the table
    drop it (DCE removes the cost)."""

    def solve_one(f, l, o, w, w0):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=w)
        return solve(opt, objective.bind(batch), w0)

    result = jax.vmap(solve_one)(features, labels, offsets, weights, w0s)
    return result.coefficients, lane_trace_of(result)


def _mask_padding_lanes(trace: LaneTrace, entity_rows: Array, num_rows: int) -> LaneTrace:
    """Mark padding lanes invalid: OOB-sentinel entity rows (gathers clamp,
    scatters drop) solve all-zero-weight batches whose iteration counts and
    reasons must not pollute convergence tallies."""
    return trace.replace(valid=(entity_rows >= 0) & (entity_rows < num_rows))


def solve_entity_bucket_traced(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,  # [e, cap, d]
    labels: Array,  # [e, cap]
    weights: Array,  # [e, cap]
    sample_rows: Array,  # [e, cap]
    entity_rows: Array,  # [e]
    full_offsets: Array,  # [n]
    table: Array,  # [E, d]
) -> tuple[Array, LaneTrace]:
    """Solve every entity in a bucket and scatter results into the table;
    also returns the per-lane convergence trace (padding lanes masked
    invalid): the CD path hands it to telemetry, the fused step reduces it
    to the bucket's row of counts (optim/common.bucket_count_parts).

    Pure/traceable: reused by the single-chip jit wrapper below and by the
    mesh-sharded full-GAME train step (parallel/distributed.py), where the
    entity axis shards over the mesh's "data" axis.

    Its three phases run under the scopes ``gather``, ``solve`` and
    ``scatter`` (``jax.named_scope``: the compiled instructions' ``op_name``,
    which telemetry/program_ledger.compiled_scopes reads back), here and in
    the index-map, random-projection and matrix-factorization siblings.
    """
    with jax.named_scope("gather"):
        offsets = _bucket_offsets(sample_rows, full_offsets)
        w0s = table[entity_rows]
    with jax.named_scope("solve"):
        solved, trace = _solve_bucket_entities(
            objective, opt, features, labels, weights, offsets, w0s
        )
        trace = _mask_padding_lanes(trace, entity_rows, table.shape[0])
    with jax.named_scope("scatter"):
        return table.at[entity_rows].set(solved), trace


@partial(ledger_jit, label="coord/re_bucket_solve", static_argnums=(0, 1))
def _jitted_re_bucket_solve(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    full_offsets: Array,
    table: Array,
):
    return solve_entity_bucket_traced(
        objective, opt, features, labels, weights, sample_rows, entity_rows,
        full_offsets, table,
    )


def _bucket_grad_norms(objective, features, labels, weights, offsets, w0s):
    """[e] gradient norms at each lane's warm start — the vmapped single
    pass behind ``RandomEffectCoordinate.refresh_gradient_norms``."""

    def one(f, l, o, wt, w):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return jnp.linalg.norm(objective.gradient(w, batch))

    return jax.vmap(one)(features, labels, offsets, weights, w0s)


@partial(ledger_jit, label="refresh/grad_norms", static_argnums=(0,))
def _jitted_re_bucket_grad_norms(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    full_offsets: Array,
    table: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    return _bucket_grad_norms(
        objective, features, labels, weights, offsets, table[entity_rows]
    )


@partial(ledger_jit, label="refresh/grad_norms_indexmap", static_argnums=(0,))
def _jitted_re_bucket_grad_norms_indexmap(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    col_index: Array,
    full_offsets: Array,
    table_ext: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table_ext[entity_rows[:, None], col_index]
    return _bucket_grad_norms(
        objective, features, labels, weights, offsets, w0s
    )


@partial(ledger_jit, label="refresh/grad_norms_random", static_argnums=(0,))
def _jitted_re_bucket_grad_norms_random(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    matrix: Array,
    full_offsets: Array,
    table: Array,
):
    offsets = _bucket_offsets(sample_rows, full_offsets)
    return _bucket_grad_norms(
        objective, features, labels, weights, offsets,
        table[entity_rows] @ matrix,
    )


@partial(ledger_jit, label="coord/re_bucket_variances", static_argnums=(0,))
def _jitted_re_bucket_variances(
    objective: GLMObjective,
    features: Array,  # [e, cap, d]
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    full_offsets: Array,
    table: Array,  # [E, d] solved coefficients (normalized space)
    var_table: Array,  # [E, d] accumulator
):
    """Per-entity diag(H⁻¹) at the solved coefficients, scattered into
    var_table with the same index semantics as solve_entity_bucket_traced."""
    offsets = _bucket_offsets(sample_rows, full_offsets)

    def one(f, l, o, wt, w):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return diag_inverse_from_hessian(objective.hessian_matrix(w, batch))

    vs = jax.vmap(one)(features, labels, offsets, weights, table[entity_rows])
    return var_table.at[entity_rows].set(vs)


@partial(ledger_jit, label="coord/re_bucket_variances_diagonal", static_argnums=(0,))
def _jitted_re_bucket_variances_diagonal(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    full_offsets: Array,
    table: Array,
    var_table: Array,
):
    """Diagonal-approximation twin of :func:`_jitted_re_bucket_variances` —
    1/diag(H) per entity without materializing the [e, d, d] Hessian stack."""
    offsets = _bucket_offsets(sample_rows, full_offsets)

    def one(f, l, o, wt, w):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return inverse_of_diagonal(objective.hessian_diagonal(w, batch))

    vs = jax.vmap(one)(features, labels, offsets, weights, table[entity_rows])
    return var_table.at[entity_rows].set(vs)


@partial(ledger_jit, label="coord/re_bucket_variances_indexmap", static_argnums=(0,))
def _jitted_re_bucket_variances_indexmap(
    objective: GLMObjective,
    features: Array,  # [e, cap, k] index-projected (possibly pre-normalized)
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    col_index: Array,  # [e, k], pad slots hold the scratch column
    full_offsets: Array,
    table_ext: Array,  # [E, d+1] solved coefficients + scratch
    var_ext: Array,  # [E, d+1] accumulator (NaN = not computed)
):
    """Per-entity diag(H⁻¹) in the PROJECTED space (H over the entity's
    active columns only), scattered back through the entity's index map —
    variances travel with the means exactly as in the reference
    (IndexMapProjectorRDD.scala:103)."""
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table_ext[entity_rows[:, None], col_index]

    def one(f, l, o, wt, w):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return diag_inverse_from_hessian(objective.hessian_matrix(w, batch))

    vs = jax.vmap(one)(features, labels, offsets, weights, w0s)
    return var_ext.at[entity_rows[:, None], col_index].set(vs)


@partial(ledger_jit, label="coord/re_bucket_variances_indexmap_diagonal", static_argnums=(0,))
def _jitted_re_bucket_variances_indexmap_diagonal(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    col_index: Array,
    full_offsets: Array,
    table_ext: Array,
    var_ext: Array,
):
    """Diagonal-approximation twin of
    :func:`_jitted_re_bucket_variances_indexmap`."""
    offsets = _bucket_offsets(sample_rows, full_offsets)
    w0s = table_ext[entity_rows[:, None], col_index]

    def one(f, l, o, wt, w):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return inverse_of_diagonal(objective.hessian_diagonal(w, batch))

    vs = jax.vmap(one)(features, labels, offsets, weights, w0s)
    return var_ext.at[entity_rows[:, None], col_index].set(vs)


def solve_entity_bucket_indexmap_traced(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,  # [e, cap, k]
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    col_index: Array,  # [e, k], padding slots hold d (the scratch column)
    full_offsets: Array,
    table_ext: Array,  # [E, d+1]
) -> tuple[Array, LaneTrace]:
    """Index-map-projected bucket solve: gather each entity's active columns
    as its warm start, solve in the projected space, scatter back. Padding
    slots read/write the scratch column, which is re-zeroed afterwards.
    Returns the table and the per-lane convergence trace.

    Pure/traceable (reference IndexMapProjectorRDD.scala:218-257 semantics):
    used by the single-chip jit wrapper below and by the mesh-sharded
    fused step (parallel/distributed.py), where the entity axis shards
    over "data"."""
    with jax.named_scope("gather"):
        offsets = _bucket_offsets(sample_rows, full_offsets)
        w0s = table_ext[entity_rows[:, None], col_index]
    with jax.named_scope("solve"):
        solved, trace = _solve_bucket_entities(
            objective, opt, features, labels, weights, offsets, w0s
        )
        trace = _mask_padding_lanes(trace, entity_rows, table_ext.shape[0])
    with jax.named_scope("scatter"):
        table_ext = table_ext.at[entity_rows[:, None], col_index].set(solved)
        return table_ext.at[:, -1].set(0.0), trace


@partial(ledger_jit, label="coord/re_bucket_variances_random", static_argnums=(0,))
def _jitted_re_bucket_variances_random(
    objective: GLMObjective,
    features: Array,  # [e, cap, k] (already projected)
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    matrix: Array,  # [d, k]
    full_offsets: Array,
    table: Array,  # [E, d] solved ORIGINAL-space coefficients
    var_table: Array,  # [E, d] accumulator (NaN = not computed)
):
    """Original-space variances of a RANDOM-projected solve: the estimator
    is w = P w_k, so Cov(w) = P Cov(w_k) Pᵀ and
    var(w) = diag(P H_k⁻¹ Pᵀ) = rowsum((P @ H_k⁻¹) ∘ P).

    This is an IMPROVEMENT over the reference, which back-projects the
    means but passes the PROJECTED-space variance vector through unchanged
    (ProjectionMatrixBroadcast.scala:76) — a length-k vector attached to a
    length-d model. Standalone entry points reject that; this kernel does
    the propagation properly."""
    offsets = _bucket_offsets(sample_rows, full_offsets)
    wks = _recover_sketch_coefficients(table[entity_rows], matrix)

    def one(f, l, o, wt, wk):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        h_inv = full_inverse_from_hessian(objective.hessian_matrix(wk, batch))
        return jnp.einsum("dk,kl,dl->d", matrix, h_inv, matrix)

    vs = jax.vmap(one)(features, labels, offsets, weights, wks)
    return var_table.at[entity_rows].set(vs)


def random_variance_mode(mode: str, d: int, k: int, num_problems: int) -> str:
    """AUTO gate for the RANDOM-projection variance kernels: the full
    propagation materializes a [d, k] (P @ H_k⁻¹) intermediate PER VMAPPED
    ENTITY — num_problems·d·k floats, unbounded in d (the axis the sketch
    exists to shrink) — so the budget must cover that stack, not just the
    e·k² Hessians."""
    resolved = resolve_variance_mode(mode, k, num_problems=num_problems)
    if (
        mode == "auto"
        and resolved == "full"
        and num_problems * d * k > FULL_VARIANCE_MAX_DIM * FULL_VARIANCE_MAX_DIM
    ):
        return "diagonal"
    return resolved


def _recover_sketch_coefficients(rows: Array, matrix: Array) -> Array:
    """EXACT solve-space coefficients from back-projected table rows.

    Table rows hold w = P w_k exactly (set by ``solved @ P.T``), so
    w_k = (PᵀP)⁻¹ Pᵀ w — a shared [k, k] Gram solve. The cheaper adjoint
    Pᵀw = (PᵀP) w_k is fine as a solver WARM START but deviates from w_k by
    ~sqrt(k/d) relative error, which would bias any coefficient-dependent
    Hessian (logistic/Poisson) evaluated there.
    """
    gram = matrix.T @ matrix  # [k, k]
    return jnp.linalg.solve(gram, (rows @ matrix).T).T


@partial(ledger_jit, label="coord/re_bucket_variances_random_diagonal", static_argnums=(0,))
def _jitted_re_bucket_variances_random_diagonal(
    objective: GLMObjective,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    matrix: Array,
    full_offsets: Array,
    table: Array,
    var_table: Array,
):
    """Diagonal-approximation twin: var(w) ≈ (P∘P) @ 1/diag(H_k)."""
    offsets = _bucket_offsets(sample_rows, full_offsets)
    wks = _recover_sketch_coefficients(table[entity_rows], matrix)
    p2 = matrix * matrix

    def one(f, l, o, wt, wk):
        batch = LabeledPointBatch(features=f, labels=l, offsets=o, weights=wt)
        return p2 @ inverse_of_diagonal(objective.hessian_diagonal(wk, batch))

    vs = jax.vmap(one)(features, labels, offsets, weights, wks)
    return var_table.at[entity_rows].set(vs)


def solve_entity_bucket_random_traced(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,  # [e, cap, k] (already projected)
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    matrix: Array,  # [d, k]
    full_offsets: Array,
    table: Array,  # [E, d]
) -> tuple[Array, LaneTrace]:
    """Random-projected bucket solve: warm start Pᵀw (the adjoint projection,
    ≈ the projected coefficients since E[PᵀP]=I), back-project P w_k.
    Returns the table and the per-lane convergence trace. Pure/traceable,
    shared with the fused step like its index-map twin."""
    with jax.named_scope("gather"):
        offsets = _bucket_offsets(sample_rows, full_offsets)
        w0s = table[entity_rows] @ matrix
    with jax.named_scope("solve"):
        solved, trace = _solve_bucket_entities(
            objective, opt, features, labels, weights, offsets, w0s
        )
        trace = _mask_padding_lanes(trace, entity_rows, table.shape[0])
    with jax.named_scope("scatter"):
        return table.at[entity_rows].set(solved @ matrix.T), trace


@partial(ledger_jit, label="coord/re_bucket_solve_indexmap", static_argnums=(0, 1))
def _jitted_re_bucket_solve_indexmap(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    col_index: Array,
    full_offsets: Array,
    table_ext: Array,
):
    return solve_entity_bucket_indexmap_traced(
        objective, opt, features, labels, weights, sample_rows, entity_rows,
        col_index, full_offsets, table_ext,
    )


@partial(ledger_jit, label="coord/re_bucket_solve_random", static_argnums=(0, 1))
def _jitted_re_bucket_solve_random(
    objective: GLMObjective,
    opt: OptimizerConfig,
    features: Array,
    labels: Array,
    weights: Array,
    sample_rows: Array,
    entity_rows: Array,
    matrix: Array,
    full_offsets: Array,
    table: Array,
):
    return solve_entity_bucket_random_traced(
        objective, opt, features, labels, weights, sample_rows, entity_rows,
        matrix, full_offsets, table,
    )


@dataclasses.dataclass
class ModelCoordinate(Coordinate):
    """A locked coordinate: contributes scores, never retrains (reference
    FixedEffectModelCoordinate / RandomEffectModelCoordinate, used by partial
    retraining, CoordinateDescent.scala:44-49)."""

    coordinate_id: str
    dataset: GameDataset
    model: DatumScoringModel

    def initial_model(self) -> DatumScoringModel:
        return self.model

    def update_model(self, model: DatumScoringModel, extra_offsets: Array | None = None):
        return model, None

    def score(self, model: DatumScoringModel) -> Array:
        return model.score_dataset(self.dataset)
