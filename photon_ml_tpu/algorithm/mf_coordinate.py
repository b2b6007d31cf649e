"""Matrix-factorization coordinate: alternating vmapped latent-factor solves.

The reference promises an MF coordinate (README.md:92-95,
LatentFactorAvro.avsc) but never implemented it; this module supplies the
missing capability as a first-class GAME coordinate so MF factors train on
coordinate-descent residuals alongside fixed/random effects
(algorithm/CoordinateDescent parity: photon-lib algorithm/Coordinate.scala).

Training is alternating minimization. With column factors held fixed, the
objective restricted to one row-entity r is an ordinary GLM over its
samples whose "feature vector" for sample i is ``col_factors[col_idx_i]``
— exactly the local subproblem shape of a random-effect entity. So each
half-step gathers the fixed side's factors as features and reuses the
vmapped per-entity solver (`coordinates._solve_bucket_entities`) over
size-bucketed padded blocks. The gather happens *inside* jit, so a bucket's
HLO is (embedding-lookup → vmapped LBFGS) fused by XLA, and each half-step
scatters straight back into the [E, k] factor table. Where a bucket's
``cap`` is a whole number of the device's vectors the lanes read the gathered
block ``[k, cap]``, the slots minor (``slots_minor``, ``_SlotsMinorObjective``).
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from photon_ml_tpu.algorithm.coordinates import (
    Coordinate,
    CoordinateOptimizationConfig,
    _bucket_offsets,
    _make_objective,
    _mask_padding_lanes,
    _solve_bucket_entities,
    _solve_config,
)
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import (
    GameDataset,
    group_entities_into_buckets,
    pack_bucket_lanes,
)
from photon_ml_tpu.models.matrix_factorization import (
    MatrixFactorizationModel,
    init_factors,
)
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.common import LaneTrace
from photon_ml_tpu.optim.optimizer import OptimizerConfig
from photon_ml_tpu.telemetry.program_ledger import ledger_jit
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.util.timed import Timed

Array = jax.Array


@dataclasses.dataclass
class MFSideBucket:
    """One size-bucket of per-entity sample groups for one MF side.

    Unlike EntityBucket there is no static feature block — features are the
    *other* side's factor rows, gathered at solve time (they change every
    half-step).

    labels/weights: [e, cap] (weight 0 marks padding)
    entity_rows:    [e]      row in this side's entity vocab
    sample_rows:    [e, cap] global sample row per slot, -1 pad

    The packer leaves all four on the HOST: whoever solves them places them
    (``GameTrainProgram.shard_inputs`` shard by shard over its mesh,
    ``MatrixFactorizationCoordinate`` once on the default device).
    """

    labels: "np.ndarray | Array"
    weights: "np.ndarray | Array"
    entity_rows: "np.ndarray | Array"
    sample_rows: "np.ndarray | Array"

    @property
    def num_entities(self) -> int:
        return int(self.entity_rows.shape[0])


@dataclasses.dataclass
class MFDataset:
    """Bucketed per-entity views of both MF sides."""

    row_effect_type: str
    col_effect_type: str
    row_buckets: list[MFSideBucket]
    col_buckets: list[MFSideBucket]
    num_row_entities: int
    num_col_entities: int

    def pad_fractions(self) -> tuple[float, float]:
        """(row side, column side): the share of the ``[e, cap]`` slots that
        is padding, which the gathers and the lanes pay for the ladder."""

        def padding(buckets) -> float:
            slots = sum(int(b.sample_rows.size) for b in buckets)
            kept = sum(int(np.count_nonzero(np.asarray(b.sample_rows) >= 0))
                       for b in buckets)
            return 1.0 - kept / slots if slots else 0.0

        return padding(self.row_buckets), padding(self.col_buckets)

    def slots_minor_fractions(self) -> tuple[float, float]:
        """(row side, column side): the share of the ``[e, cap]`` slots in
        buckets whose half-step lays its gathered features out with the slots
        minor (``slots_minor``, the rule ``solve_mf_side_bucket`` applies)."""

        def share(buckets) -> float:
            slots = sum(int(b.sample_rows.size) for b in buckets)
            minor = sum(int(b.sample_rows.size) for b in buckets
                        if slots_minor(int(b.sample_rows.shape[1])))
            return minor / slots if slots else 0.0

        return share(self.row_buckets), share(self.col_buckets)

    def trained_masks(self) -> tuple[np.ndarray, np.ndarray]:
        """Boolean [R] / [C] masks of entities that appear in any bucket.
        Entities outside (vocab members with zero samples) are never
        trained and must score 0, matching random-effect semantics."""
        row = np.zeros(self.num_row_entities, dtype=bool)
        for b in self.row_buckets:
            row[np.asarray(b.entity_rows)] = True
        col = np.zeros(self.num_col_entities, dtype=bool)
        for b in self.col_buckets:
            col[np.asarray(b.entity_rows)] = True
        return row, col


def _build_side_buckets(
    entity_idx: np.ndarray,
    other_idx: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    unique_ids: np.ndarray,
    *,
    bucket_sizes,
    active_data_upper_bound: int | None,
    seed: int,
) -> list[MFSideBucket]:
    """Group samples by this side's entity (shared bucketing with
    build_random_effect_dataset; reservoir caps keyed on stable sample ids).
    Samples whose other-side entity is unseen cannot contribute a
    factor-feature, so they are excluded BEFORE grouping — otherwise they
    would crowd usable samples out of the reservoir cap."""
    effective_idx = np.where(other_idx >= 0, entity_idx, -1)
    per_bucket = group_entities_into_buckets(
        effective_idx,
        unique_ids,
        bucket_sizes=bucket_sizes,
        active_data_upper_bound=active_data_upper_bound,
        seed=seed,
    )
    buckets: list[MFSideBucket] = []
    for cap, members in per_bucket.items():
        if not members:
            continue
        e = len(members)
        be, rows_concat, lane, slot = pack_bucket_lanes(members)
        bl = np.zeros((e, cap), dtype=labels.dtype)
        bw = np.zeros((e, cap), dtype=weights.dtype)
        bs = np.full((e, cap), -1, dtype=np.int32)
        bl[lane, slot] = labels[rows_concat]
        bw[lane, slot] = weights[rows_concat] * (other_idx[rows_concat] >= 0)
        bs[lane, slot] = rows_concat
        buckets.append(MFSideBucket(
            labels=bl, weights=bw, entity_rows=be, sample_rows=bs))
    return buckets


def build_mf_dataset(
    dataset: GameDataset,
    row_effect_type: str,
    col_effect_type: str,
    *,
    bucket_sizes=(8, 32, 128, 512, 2048),
    active_data_upper_bound: int | None = None,
    seed: int = 0,
) -> MFDataset:
    """Both sides' buckets, packed on the host and left there. Timed as
    ``pack/mf_side_buckets``; the padding the ladder costs each side is left
    in the gauges ``mf/<row>_x_<col>/row_pad_fraction`` and ``col_pad_fraction``,
    the share of each side's slots whose half-step takes the slots-minor
    layout in ``row_slots_minor_fraction`` and ``col_slots_minor_fraction``."""
    with Timed("pack/mf_side_buckets", logging.DEBUG):
        labels = dataset.host_array("labels")
        weights = dataset.host_array("weights")
        unique_ids = np.asarray(dataset.unique_ids)
        row_idx = dataset.host_array(f"entity_idx/{row_effect_type}")
        col_idx = dataset.host_array(f"entity_idx/{col_effect_type}")
        mf = MFDataset(
            row_effect_type=row_effect_type,
            col_effect_type=col_effect_type,
            row_buckets=_build_side_buckets(
                row_idx, col_idx, labels, weights, unique_ids,
                bucket_sizes=bucket_sizes,
                active_data_upper_bound=active_data_upper_bound, seed=seed,
            ),
            col_buckets=_build_side_buckets(
                col_idx, row_idx, labels, weights, unique_ids,
                bucket_sizes=bucket_sizes,
                active_data_upper_bound=active_data_upper_bound, seed=seed,
            ),
            num_row_entities=len(dataset.entity_vocabs[row_effect_type]),
            num_col_entities=len(dataset.entity_vocabs[col_effect_type]),
        )
    for gauge, fractions in (("pad_fraction", mf.pad_fractions()),
                             ("slots_minor_fraction", mf.slots_minor_fractions())):
        for side, fraction in zip(("row", "col"), fractions):
            default_registry().gauge(
                f"mf/{row_effect_type}_x_{col_effect_type}/{side}_{gauge}"
            ).set(fraction)
    return mf


#: the width of the device's vectors: the minor axis of an array is stored in
#: whole tiles of this many elements
_VECTOR_LANES = 128


def slots_minor(cap: int) -> bool:
    """Whether a half-step over buckets of ``cap`` slots hands its lanes the
    gathered features with the SLOTS minor, ``[e, k, cap]``. The gather writes
    the k factors of a slot along the vector lanes, so at k = 32 three lanes
    in four are padding in every pass of the solver over the block; with the
    slots minor nothing is padded when ``cap`` is a whole number of vectors,
    and more than before when it is not (8 slots: 16x). The one rule for the
    half-step and for the ``*_slots_minor_fraction`` gauges."""
    return cap % _VECTOR_LANES == 0


class _SlotsMinorObjective(GLMObjective):
    """``objective`` for a lane whose features lie ``[k, cap]``, the slots
    minor. The layout has to be the block's LOGICAL shape: the TPU compiler
    lays out what a solver's ``while`` carries from the loop body alone, last
    axis minor from k = 32 up, and converts whatever it is handed (a block
    transposed behind an ``optimization_barrier``, one held by a layout
    constraint, even a program argument) back to that at the loop's entry.
    The random effects keep the shared objective; this is the matrix-
    factorization half-step's own."""

    def __init__(self, objective: GLMObjective):
        super().__init__(
            objective.loss, l2_weight=objective.l2_weight,
            normalization=objective.normalization,
            axis_name=objective.axis_name, use_pallas=False)
        self._slots_major = objective

    def _key(self):
        return super()._key() + ("slots_minor",)

    def margins(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        eff = self.normalization.effective_coefficients(coefficients)
        shift = self.normalization.margin_shift(eff)
        return eff @ batch.features - shift + batch.offsets

    # the dense Hessians (the Newton solver's, a variance's) are small and no
    # loop carries them: taken from the shared objective on the block's
    # transposed view

    def hessian_matrix(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        return self._slots_major.hessian_matrix(
            coefficients, batch.replace(features=batch.features.T))

    def hessian_diagonal(self, coefficients: Array, batch: LabeledPointBatch) -> Array:
        return self._slots_major.hessian_diagonal(
            coefficients, batch.replace(features=batch.features.T))


def solve_mf_side_bucket(
    objective: GLMObjective,
    opt: OptimizerConfig,
    labels: Array,        # [e, cap]
    weights: Array,       # [e, cap]
    entity_rows: Array,   # [e]
    sample_rows: Array,   # [e, cap]
    other_idx_full: Array,  # [n] the fixed side's per-sample entity index
    other_factors: Array,   # [E_other, k] the fixed side's factor table
    full_offsets: Array,    # [n] base + residual offsets
    table: Array,           # [E_this, k] this side's factor table
) -> tuple[Array, LaneTrace]:
    """One alternating half-step over one bucket: gather the fixed side's
    factors as features, vmap-solve every entity, scatter back. Returns the
    table and the lanes' trace (padding lanes masked invalid), as
    ``solve_entity_bucket_traced`` does for a random-effect bucket.

    Pure/traceable: reused by the single-chip jit wrapper below and by the
    mesh-sharded fused GAME step (parallel/distributed.py), where the
    entity axis shards over the mesh's "data" axis. The same three scopes
    as there: ``gather`` holds the other side's factor rows, the mask and the
    slots-minor relayout too."""
    with jax.named_scope("gather"):
        safe_rows = jnp.maximum(sample_rows, 0)
        oidx = other_idx_full[safe_rows]                       # [e, cap]
        feats = other_factors[jnp.maximum(oidx, 0)]            # [e, cap, k]
        pad = sample_rows < 0
        feats = jnp.where(pad[..., None] | (oidx < 0)[..., None], 0.0, feats)
        if slots_minor(feats.shape[1]):
            # laid out once, [e, k, cap], as a random effect's features lie on
            # the device, and read that way by every trial of the lanes
            feats = jnp.swapaxes(feats, 1, 2)
            objective = _SlotsMinorObjective(objective)
        offsets = _bucket_offsets(sample_rows, full_offsets)
        w0s = table[entity_rows]
    with jax.named_scope("solve"):
        solved, trace = _solve_bucket_entities(
            objective, opt, feats, labels, weights, offsets, w0s
        )
        trace = _mask_padding_lanes(trace, entity_rows, table.shape[0])
    with jax.named_scope("scatter"):
        return table.at[entity_rows].set(solved), trace


@partial(ledger_jit, label="coord/mf_side_solve", static_argnums=(0, 1))
def _jitted_mf_side_solve(
    objective: GLMObjective,
    opt: OptimizerConfig,
    labels: Array,
    weights: Array,
    entity_rows: Array,
    sample_rows: Array,
    other_idx_full: Array,
    other_factors: Array,
    full_offsets: Array,
    table: Array,
) -> Array:
    return solve_mf_side_bucket(
        objective, opt, labels, weights, entity_rows, sample_rows,
        other_idx_full, other_factors, full_offsets, table,
    )[0]


@dataclasses.dataclass
class MatrixFactorizationCoordinate(Coordinate):
    """Trains (row_factors, col_factors) on the residual offsets.

    ``num_alternations`` inner row/col sweeps per coordinate update; the
    outer coordinate-descent loop supplies further alternations, so small
    values (1-2) suffice.
    """

    coordinate_id: str
    dataset: GameDataset
    mf_dataset: MFDataset
    task: TaskType
    config: CoordinateOptimizationConfig
    num_latent_factors: int
    num_alternations: int = 2
    seed: int = 0

    def __post_init__(self):
        # the packer leaves the buckets on the host: commit them to the
        # default device once, not at every half-step's call
        self._row_buckets, self._col_buckets = (
            [MFSideBucket(*jax.device_put(
                (b.labels, b.weights, b.entity_rows, b.sample_rows))) for b in side]
            for side in (self.mf_dataset.row_buckets, self.mf_dataset.col_buckets))

    def initial_model(self) -> MatrixFactorizationModel:
        mf = self.mf_dataset
        row, col = init_factors(
            mf.num_row_entities, mf.num_col_entities, self.num_latent_factors,
            seed=self.seed, dtype=self.dataset.labels.dtype,
        )
        # Vocab entities with no training samples keep zero factors (they are
        # never solved, so a random init would leak noise into their scores).
        row_mask, col_mask = mf.trained_masks()
        row = jnp.where(jnp.asarray(row_mask)[:, None], row, 0.0)
        col = jnp.where(jnp.asarray(col_mask)[:, None], col, 0.0)
        return MatrixFactorizationModel(
            row_factors=row,
            col_factors=col,
            row_effect_type=mf.row_effect_type,
            col_effect_type=mf.col_effect_type,
            row_keys=self.dataset.entity_vocabs[mf.row_effect_type],
            col_keys=self.dataset.entity_vocabs[mf.col_effect_type],
            task=self.task,
        )

    def update_model(
        self, model: MatrixFactorizationModel, extra_offsets: Array | None = None
    ):
        if self.config.l1_weight > 0.0:
            raise ValueError(
                "L1 regularization is not supported on latent factors "
                "(use l2_weight; the reference's MF design is L2-only)"
            )
        objective = _make_objective(self.task, self.config, None)
        # alternating factor solves are small-k dense vmapped problems:
        # AUTO resolves to the batched-Newton solver (optim/newton.py)
        opt = _solve_config(self.config, loss=objective.loss, small_dense=True)
        full_offsets = self.dataset.offsets
        if extra_offsets is not None:
            full_offsets = full_offsets + extra_offsets

        mf = self.mf_dataset
        row_idx = self.dataset.entity_idx[mf.row_effect_type]
        col_idx = self.dataset.entity_idx[mf.col_effect_type]
        rows, cols = model.row_factors, model.col_factors
        for _ in range(self.num_alternations):
            for b in self._row_buckets:
                rows = _jitted_mf_side_solve(
                    objective, opt, b.labels, b.weights, b.entity_rows,
                    b.sample_rows, col_idx, cols, full_offsets, rows,
                )
            for b in self._col_buckets:
                cols = _jitted_mf_side_solve(
                    objective, opt, b.labels, b.weights, b.entity_rows,
                    b.sample_rows, row_idx, rows, full_offsets, cols,
                )
        return model.with_factors(rows, cols), None

    def score(self, model: MatrixFactorizationModel) -> Array:
        return model.score_dataset(self.dataset)
