"""Mesh-sharded full-GAME training step: one jitted SPMD program.

This is the TPU replacement for the reference's entire distributed training
round (photon-api algorithm/FixedEffectCoordinate.scala:91-165 treeAggregate
optimization + algorithm/RandomEffectCoordinate.scala:104-153 per-entity RDD
solves + photon-lib algorithm/CoordinateDescent.scala:198-255 residual
choreography). One call = one full block-coordinate-descent sweep:

    FE solve (samples sharded over "data", features optionally over "model")
    -> residual score update
    -> per-RE-type vmapped entity solves (entities sharded over "data")
    -> residual score updates
    -> final training loss

Everything lives inside a single jit, so XLA inserts every collective:
gradient psums over the "data" axis where Spark ran treeAggregate, feature-
axis reduce-scatters/all-gathers over "model" where the reference broadcast
the coefficient vector, and gather/scatter collectives where the reference
ran RDD joins. Multi-host pods: build the mesh over all processes' devices
after jax.distributed.initialize; the same program then spans ICI + DCN.

Sharding convention (parallel/mesh.py): axis "data" carries both sample-DP
and entity-parallelism (the "EP" of this model family, SURVEY.md §2.5);
axis "model" carries the feature axis of giant fixed-effect coordinates
(the tensor-parallel analogue — 1B-coefficient FE vectors, SURVEY.md §7).
"""

from __future__ import annotations

import dataclasses
import logging
from functools import lru_cache, partial
from typing import Mapping, NamedTuple, Sequence

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.algorithm.coordinates import (
    solve_entity_bucket_indexmap_traced,
    solve_entity_bucket_random_traced,
    solve_entity_bucket_traced,
)
from photon_ml_tpu.algorithm.mf_coordinate import solve_mf_side_bucket
from photon_ml_tpu.models.matrix_factorization import score_matrix_factorization
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import GameDataset, RandomEffectDataset
from photon_ml_tpu.data.sparse_batch import SparseShard, sparse_margins
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.models.game import score_random_effect
from photon_ml_tpu.projector.projectors import ProjectorType
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.common import (
    BUCKET_COUNT_NAMES,
    SOLVER_COUNT_NAMES,
    SolverResult,
    bucket_count_parts,
    bucket_counts,
    lane_trace_of,
)
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType, solve
from photon_ml_tpu.parallel.mesh import place
from photon_ml_tpu.telemetry.program_ledger import current_ledger, ledger_jit
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.telemetry.tracing import span
from photon_ml_tpu.types import TaskType

Array = jax.Array

logger = logging.getLogger(__name__)

#: registry counter bumped once per TRACE of the exchange that brings the
#: arrays a coordinate's buckets index to every chip of a mesh
#: (``GameTrainProgram._whole_on_every_chip``): 0 in a one-device program, one
#: per random-effect coordinate and two per factorization alternation in a
#: traced step on a mesh
EXCHANGES_TRACED = "train/exchanges_traced"

#: registry counter bumped each time the entry program runs: the one jitted
#: ``GameTrainProgram._coordinate_scores`` that fills a state's empty
#: ``scores`` before a sweep (``GameTrainProgram._carried``). A fit pays it
#: once, whatever its number of sweeps; a resumed or warm-started fit once too
ENTRY_SCORINGS = "train/entry_scorings"


@flax.struct.dataclass
class GameTrainState:
    """Device-resident model state for one training step.

    fe_coefficients: [d_fe] — the fixed-effect coefficient vector; shard its
        (only) axis over "model" for giant coordinates, replicate otherwise.
    re_tables: RE type -> [num_entities, d_re] coefficient table; the entity
        axis shards over "data".
    mf_rows / mf_cols: MF coordinate name -> [num_entities, k] latent-factor
        tables (row / col side); entity axes shard over "data".
    extra_fe: feature shard id -> [d] coefficient vector for ADDITIONAL
        fixed-effect coordinates beyond the primary (reference
        GameEstimator.scala:746-828 trains arbitrary coordinate sets; the
        fused step keeps one primary FE — the only one that may be sparse
        or feature-sharded — and any number of dense replicated extras).
    scores: coordinate name -> [n] margin of that coordinate over the
        TRAINING rows at this state's coefficients (rows shard over "data"):
        what a sweep ends with and the next one starts from, so that a sweep
        scores a coordinate only after it has solved it. Empty means "not
        known": ``GameTrainProgram.step`` then fills it with one entry
        scoring before it dispatches. It belongs to one data set and to the
        training loop: ``score()`` drops it, and checkpoints, resumed states
        and the states a fit returns hold none. ``step`` CONSUMES the carry
        it is handed (the buffers are donated to the sweep, whose margins
        take their place): to step twice from one state, hand it over as
        ``state.replace(scores={})``.
    """

    fe_coefficients: Array
    re_tables: dict[str, Array]
    mf_rows: dict[str, Array] = flax.struct.field(default_factory=dict)
    mf_cols: dict[str, Array] = flax.struct.field(default_factory=dict)
    extra_fe: dict[str, Array] = flax.struct.field(default_factory=dict)
    scores: dict[str, Array] = flax.struct.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class RandomEffectStepSpec:
    """Static description of one RE coordinate inside the fused step.

    projector: must match the RandomEffectDataset's projector_type.
    INDEX_MAP solves each entity over its observed columns via the
    scratch-column gather/scatter (IndexMapProjectorRDD.scala:218-257);
    RANDOM solves in the sketched space and back-projects. The model table
    stays [E, dim] in original space either way, so scoring and residual
    updates are projector-agnostic."""

    re_type: str
    feature_shard_id: str
    optimizer: OptimizerConfig
    l2_weight: float = 0.0
    projector: ProjectorType = ProjectorType.IDENTITY
    #: intercept column of the feature shard — required when the
    #: coordinate's normalization carries shifts (STANDARDIZATION): model-
    #: space conversion absorbs each entity's margin shift into it
    intercept_index: int | None = None


@dataclasses.dataclass(frozen=True)
class FixedEffectStepSpec:
    """Static description of the fixed-effect coordinate.

    down_sampling_rate < 1 trains the FE solve on down-sampled weights
    (reference DistributedOptimizationProblem.runWithSampling:145-160):
    ``train_distributed`` computes a per-sweep stable-id multiplier with the
    same splitmix64 sampler the CD path uses and feeds it into the step as
    ``data["fe_weight_multiplier"]``; scoring and the training loss still
    cover every sample."""

    feature_shard_id: str
    optimizer: OptimizerConfig
    l2_weight: float = 0.0
    down_sampling_rate: float = 1.0
    #: intercept column of the feature shard — consulted for NON-primary
    #: (extra) FE coordinates whose normalization carries shifts; the
    #: primary FE's intercept rides the state_to_game_model /
    #: game_model_to_state ``intercept_index`` argument (historical API).
    intercept_index: int | None = None


@dataclasses.dataclass(frozen=True)
class MatrixFactorizationStepSpec:
    """Static description of one MF coordinate inside the fused step (the
    model family the reference declares but never implemented —
    algorithm/mf_coordinate.py)."""

    name: str
    row_effect_type: str
    col_effect_type: str
    num_latent_factors: int
    optimizer: OptimizerConfig
    l2_weight: float = 0.0
    num_alternations: int = 1
    seed: int = 0


def _input_leaf(x):
    """A data-set field as the step's input. A host array stays on the host:
    ``shard_inputs`` cuts it there and sends every shard straight to its
    device (``train_distributed`` without a mesh commits it to the default
    device once), so that nothing of global size is assembled on one chip.
    An array that is laid out already keeps its layout."""
    return x if isinstance(x, np.ndarray) else jnp.asarray(x)


def _pad_leading(x, pad: int, fill=0):
    """``x`` with ``pad`` more entries of ``fill`` along its first axis: on
    the host where ``x`` is a host array, so that the padded copy is never
    whole on one device."""
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                  constant_values=fill)


def _data_pytree(dataset: GameDataset, re_specs: Sequence[RandomEffectStepSpec],
                 fe_shard: str,
                 mf_specs: Sequence[MatrixFactorizationStepSpec] = (),
                 extra_fe_shards: Sequence[str] = ()) -> dict:
    shards = {fe_shard} | {s.feature_shard_id for s in re_specs} | set(extra_fe_shards)
    id_types = {s.re_type for s in re_specs}
    for m in mf_specs:
        id_types |= {m.row_effect_type, m.col_effect_type}
    from photon_ml_tpu.data.sparse_batch import (
        SparseLabeledPointBatch,
        SparseShard,
    )

    fe_sparse = isinstance(dataset.feature_shards[fe_shard], SparseShard)
    # sparse RE shards ride as compact per-entry mappings (see
    # prepare_inputs), never as dense blocks
    for k in shards:
        if isinstance(dataset.feature_shards[k], SparseShard) and k != fe_shard:
            # extra-FE shards are dense-only even when the same shard also
            # feeds a random-effect coordinate (sparse shards never enter
            # data["features"], which the extra-FE solve reads from)
            if k in extra_fe_shards or k not in {
                s.feature_shard_id for s in re_specs
            }:
                raise ValueError(
                    f"feature shard '{k}' is sparse (giant-d) but is not "
                    "the PRIMARY fixed-effect shard or a random-effect "
                    "shard (additional fixed effects are dense-only; make "
                    "the sparse one the primary)"
                )
    data = {
        "labels": _input_leaf(dataset.labels),
        "offsets": _input_leaf(dataset.offsets),
        "weights": _input_leaf(dataset.weights),
        "features": {
            k: _input_leaf(dataset.feature_shards[k])
            for k in shards
            if not isinstance(dataset.feature_shards[k], SparseShard)
        },
        "entity_idx": {
            t: _input_leaf(dataset.entity_idx[t]) for t in sorted(id_types)
        },
    }
    if fe_sparse:
        # flat-COO FE batch: offsets filled per step (residual scores);
        # the static `dim` rides the pytree treedef, so sparse-vs-dense is
        # a compile-time branch in the step
        # ONE [n, L] block (the agreed width, else the one-width auto rule):
        # shard_inputs lays it over "data" as it lays a dense feature block
        labels = jnp.asarray(dataset.labels)
        shard = dataset.feature_shards[fe_shard]
        data["fe_sparse_batch"] = SparseLabeledPointBatch.from_shard(
            shard, labels, jnp.zeros_like(labels),
            jnp.asarray(dataset.weights), ell=shard.one_ell_width(),
        )
    return data


def _buckets_pytree(
    re_datasets: Mapping[str, RandomEffectDataset],
    re_specs: Sequence[RandomEffectStepSpec] = (),
    normalized_re_types: "set[str]" = frozenset(),
) -> dict:
    spec_projector = {s.re_type: s.projector for s in re_specs}
    for k, ds in re_datasets.items():
        if (
            k in normalized_re_types
            and ds.projector_type in (ProjectorType.INDEX_MAP,
                                      ProjectorType.RANDOM)
            and not ds.pre_normalized
        ):
            raise ValueError(
                f"random-effect coordinate '{k}': projected coordinates "
                "with normalization require the RandomEffectDataset to be "
                "built with the same normalization "
                "(build_random_effect_dataset(normalization=...))"
            )
        if ds.pre_normalized and k not in normalized_re_types:
            raise ValueError(
                f"random-effect coordinate '{k}': the RandomEffectDataset "
                "was built pre-normalized but the program spec carries no "
                "normalization context for it — tables would leave the "
                "step in normalized space unconverted"
            )
        expected = spec_projector.get(k, ProjectorType.IDENTITY)
        if ds.projector_type != expected:
            raise ValueError(
                f"random-effect dataset '{k}' uses projector "
                f"{ds.projector_type.name} but the step spec declares "
                f"{expected.name} — the step's solve/scatter logic is "
                "compiled per projector, so they must match"
            )

    def bucket_dict(b, ds) -> dict:
        out = {
            "features": b.features,
            "labels": b.labels,
            "weights": b.weights,
            "sample_rows": b.sample_rows,
            "entity_rows": b.entity_rows,
        }
        if ds.projector_type == ProjectorType.INDEX_MAP:
            out["col_index"] = b.col_index
        return out

    out = {
        k: [bucket_dict(b, ds) for b in ds.buckets]
        for k, ds in re_datasets.items()
    }
    projections = {
        k: jnp.asarray(ds.projection.matrix)
        for k, ds in re_datasets.items()
        if ds.projector_type == ProjectorType.RANDOM
    }
    if projections:
        out["__projections__"] = projections
    return out


class SolveRow(NamedTuple):
    """What the host knows of one row of the step's count array without
    reading it, from the program's specs and the packed buckets' static
    shapes."""

    family: str  # "re", "mf" or "fe"
    coordinate: str  # the solve's scope: re/<type>, mf/<name>/<side>, fe/<shard>
    lanes: int  # e, padding lanes included; 1 for a fixed effect
    cap: int  # rows a lane; 0 for a fixed effect (its rows are the data's)
    newton: bool  # a random effect's bucket whose lanes Newton solves


#: the run journal's row kind for a sweep's counts by bucket (dev/doctor.py)
LANE_COUNTS_ROW = "lane_counts"

#: a family's totals beside the thirteen of SOLVER_COUNT_NAMES: sums of the
#: rows' columns of those names, and the rows a search passed over against
#: the rows a live lane asked for (Python integers: a sweep's may pass int32)
_FAMILY_TOTALS = (
    "lockstep_iterations", "lane_solves", "lanes_max_iterations",
    "lanes_function_tolerance", "lanes_gradient_tolerance",
    "lanes_search_failed",
)


@lru_cache(maxsize=32)
def _counter_plan(rows: "tuple[SolveRow, ...]") -> "tuple[tuple[str, ...], np.ndarray]":
    """How a sweep's count array becomes ``solver/<key>`` increments, worked
    out once for a program's rows: (the keys, an int64 matrix that takes the
    flattened ``[solves, columns]`` array to their values). The keys: the
    thirteen of SOLVER_COUNT_NAMES (always, zero where the program has no
    such solve), then for every family the rows hold (random effects bare,
    ``mf_``) its ``_FAMILY_TOTALS`` and ``row_trials_paid`` /
    ``row_trials_wanted``, then every coordinate's own four,
    ``<scope>/lockstep_trials|lockstep_iterations|row_trials_paid|
    row_trials_wanted``. A trial of a bucket passes over ``lanes x cap``
    rows and a lane wants ``cap`` of them. A bucket Newton solves is a
    random effect's like any other and fills the three ``newton_`` names
    besides; further ``newton_`` totals wait for a reader in the cell that
    has such lanes (ROADMAP R7 i): the journal's row has them by bucket."""
    column = {name: j for j, name in enumerate(BUCKET_COUNT_NAMES)}
    terms: dict[str, list] = {name: [] for name in SOLVER_COUNT_NAMES}

    def add(key, i, name, factor=1):
        terms.setdefault(key, []).append((i * len(column) + column[name], factor))

    for i, row in enumerate(rows):
        if row.family == "fe":
            add("fe_trials", i, "lane_trials")
            add("fe_floor_exits", i, "floor_exits")
            continue
        prefix = "" if row.family == "re" else "mf_"
        slots = row.lanes * row.cap
        for name in ("lockstep_trials", "lane_trials", "floor_exits",
                     "line_searches", *_FAMILY_TOTALS):
            add(prefix + name, i, name)
        for name in ("lockstep_trials", "lockstep_iterations"):
            add(f"{row.coordinate}/{name}", i, name)
        for key in (prefix, row.coordinate + "/"):
            add(key + "row_trials_paid", i, "lockstep_trials", slots)
            add(key + "row_trials_wanted", i, "lane_trials", row.cap)
        if row.newton:
            add("newton_lockstep_rounds", i, "lockstep_iterations")
            add("newton_lane_rounds", i, "line_searches")
            add("newton_rejected_rounds", i, "rejected_rounds")
    # coordinates after the families, each coordinate's four together
    keys = sorted(terms, key=lambda k: "/" in k)
    matrix = np.zeros((len(keys), len(rows) * len(column)), np.int64)
    for k, key in enumerate(keys):
        for j, factor in terms[key]:
            matrix[k, j] += factor
    return tuple(keys), matrix


class SweepCounts:
    """A fused sweep's solver counts on the host: the step's count array as
    it was read (``array[i]`` = the counts of ``rows[i]``, columns in
    optim/common.BUCKET_COUNT_NAMES' order). :meth:`counters` is what the
    registry is bumped by (Python integers: a sweep's rows paid may pass
    int32), :meth:`table` what a run journal keeps."""

    def __init__(self, rows: "tuple[SolveRow, ...]", array):
        self.rows, self.array = rows, np.asarray(array, np.int64)

    def table(self) -> list[dict]:
        """One dict a solve: scope, lanes, cap and the row's counts."""
        return [{"coordinate": row.coordinate, "lanes": row.lanes,
                 "cap": row.cap, **dict(zip(BUCKET_COUNT_NAMES, counts))}
                for row, counts in zip(self.rows, self.array.tolist())]

    def counters(self) -> dict[str, int]:
        """``solver/<key>`` increments of the sweep (:func:`_counter_plan`)."""
        keys, matrix = _counter_plan(self.rows)
        return dict(zip(keys, (matrix @ self.array.ravel()).tolist()))


def _fe_solved(result: SolverResult) -> tuple[Array, tuple[Array, Array]]:
    """A fixed-effect solve's (coefficients, its counts as a bucket of one
    lane: optim/common.bucket_count_parts)."""
    return result.coefficients, bucket_count_parts(lane_trace_of(result))


class _CarriedStep:
    """``train/step`` as its callers hold it: ``(data, buckets, state)``,
    called or lowered (the benchmark's traced runs read the compiled step's
    text through ``program._step.lower``), the state's carry filled first
    (:meth:`GameTrainProgram._carried`), so that the step is traced in ONE
    form. The jitted program takes the margins APART from the rest of the
    state, as its fourth argument, because they alone are donated: a sweep's
    margins are written where the last sweep's stood, and a fit holds one set
    of ``[n]`` vectors, not one coming in beside one going out. Every other
    attribute is the ``ledger_jit`` object's own (``label``, ``jitted``,
    ``clear_cache``; its ``trace`` and ``eval_shape`` take the four)."""

    def __init__(self, program: "GameTrainProgram"):
        def _step_impl(data, buckets, state, scores):  # the module's name
            return program._step_impl(
                data, buckets, state.replace(scores=scores))

        self._program = program
        self._jit = ledger_jit(_step_impl, label="train/step",
                               donate_argnums=3)

    def _arguments(self, data, buckets, state: GameTrainState):
        state = self._program._carried(data, state)
        return data, buckets, state.replace(scores={}), state.scores

    def __call__(self, data, buckets, state: GameTrainState):
        return self._jit(*self._arguments(data, buckets, state))

    def lower(self, data, buckets, state: GameTrainState):
        return self._jit.lower(*self._arguments(data, buckets, state))

    def __getattr__(self, name):
        if name.startswith("_"):  # ``_jit`` itself, of a half-made instance
            raise AttributeError(name)
        return getattr(self._jit, name)


class GameTrainProgram:
    """A compiled full-GAME training step bound to static specs.

    Build once per (task, coordinate specs); call ``step`` repeatedly — the
    jitted program is cached. Use ``shard_inputs`` to lay data and state out
    over a mesh first; the same program runs single-chip when no mesh is
    given (the SPMD partitioner simply sees one device).
    """

    def __init__(
        self,
        task: TaskType,
        fe: FixedEffectStepSpec,
        re_specs: Sequence[RandomEffectStepSpec] = (),
        *,
        mf_specs: Sequence[MatrixFactorizationStepSpec] = (),
        extra_fes: Sequence[FixedEffectStepSpec] = (),
        update_order: Sequence[str] | None = None,
        normalization: NormalizationContext | None = None,
        re_normalizations: Mapping[str, NormalizationContext] | None = None,
        extra_fe_normalizations: Mapping[str, NormalizationContext] | None = None,
        use_pallas_fe: bool | None = None,
        mesh: Mesh | None = None,
        fe_feature_sharded: bool = False,
    ):
        self.task = task
        # AUTO resolution happens ONCE, at program build: FE coordinates
        # (big-d, possibly sharded/streamed) take LBFGS; RE/MF coordinates
        # (small-d dense vmapped buckets) take NEWTON when the loss is
        # eligible (optim/optimizer.resolve_auto_optimizer), without naming
        # the solver (the cell game-ymusic-r2.sweeps runs it: PERF.md 5).
        # Explicit configs pass through untouched.
        from photon_ml_tpu.optim.optimizer import resolve_auto_optimizer

        _loss_for_auto = loss_for_task(task)

        def _resolved(spec, small_dense):
            opt = resolve_auto_optimizer(
                spec.optimizer, loss=_loss_for_auto, small_dense=small_dense
            )
            return (
                spec if opt is spec.optimizer
                else dataclasses.replace(spec, optimizer=opt)
            )

        fe = _resolved(fe, False)
        self.fe = fe
        self.re_specs = tuple(_resolved(s, True) for s in re_specs)
        self.mf_specs = tuple(_resolved(s, True) for s in mf_specs)
        self.extra_fes = tuple(_resolved(s, False) for s in extra_fes)
        re_specs = self.re_specs
        mf_specs = self.mf_specs
        extra_fes = self.extra_fes
        # coordinate names share one namespace: residual skip keys and the
        # GameModel coordinate ids of state_to_game_model (where each FE
        # coordinate is named after its feature shard)
        names = (
            [fe.feature_shard_id]
            + [s.feature_shard_id for s in self.extra_fes]
            + [s.re_type for s in self.re_specs]
            + [m.name for m in self.mf_specs]
        )
        dupes = {n for n in names if names.count(n) > 1}
        if dupes:
            raise ValueError(
                f"coordinate names must be unique across the FE feature "
                f"shards, RE types, and MF names (duplicates: {sorted(dupes)})"
            )
        # sweep order inside one fused step (reference
        # CoordinateDescent.scala:198-255 trains coordinates in the
        # CONFIGURED order — order changes what residuals each solve sees).
        # Default: primary FE, extra FEs, REs, MFs (the historical order),
        # which is also the order every residual sum adds in, whatever the
        # update order
        self._canonical_order: tuple[str, ...] = tuple(names)
        if update_order is None:
            self.update_order: tuple[str, ...] = tuple(names)
        else:
            if sorted(update_order) != sorted(names):
                raise ValueError(
                    f"update_order must be a permutation of the coordinate "
                    f"names {sorted(names)}; got {list(update_order)}"
                )
            self.update_order = tuple(update_order)
        self._kind = {fe.feature_shard_id: "fe"}
        self._kind.update({s.feature_shard_id: "extra_fe" for s in self.extra_fes})
        self._kind.update({s.re_type: "re" for s in self.re_specs})
        self._kind.update({m.name: "mf" for m in self.mf_specs})
        self._extra_fe_by_name = {s.feature_shard_id: s for s in self.extra_fes}
        self._re_by_name = {s.re_type: s for s in self.re_specs}
        self._mf_by_name = {m.name: m for m in self.mf_specs}
        reserved = {"__mf__", "__projections__"} & set(names)
        if reserved:
            raise ValueError(
                f"{sorted(reserved)} are reserved (internal bucket-group "
                "keys); rename the coordinate"
            )
        loss = loss_for_task(task)
        self._loss = loss
        self.normalization = normalization
        # use_pallas=False everywhere in the fused program by default: its
        # batches may be GSPMD mesh-sharded, and XLA cannot partition a
        # pallas_call. The single-pass kernel reaches the (un-vmapped,
        # dense) primary-FE solve two ways:
        #  - single device: use_pallas_fe opts this GLMObjective in
        #    (None = TPU auto, True = force/interpret, False = off);
        #  - multi-device mesh (pass ``mesh``): a shard_map wrapper runs
        #    the kernel per-device on local rows and psums — the
        #    reference's one-pass seqOp on every executor
        #    (ValueAndGradientAggregator.scala:133-154, :236-251). Not
        #    built when the FE block is feature-sharded over "model"
        #    (that path is sparse/column-sharded) or use_pallas_fe=False.
        # Callers that never pass a mesh keep the conservative False
        # default: their batches may be GSPMD-sharded later, where a
        # baked-in pallas_call cannot be partitioned.
        n_mesh_devices = int(mesh.devices.size) if mesh is not None else 1
        multi_device = mesh is not None and n_mesh_devices > 1
        # where the arrays a coordinate's buckets index lie and where
        # _whole_on_every_chip puts them; None for a program of one device,
        # which has nothing to exchange
        self._exchange = (
            (NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
            if multi_device else None
        )
        # the chips a bucket's lanes lie over: its counts are taken chip by
        # chip and brought together once a sweep (_stacked_counts)
        self._lane_parts = int(mesh.shape["data"]) if multi_device else 1
        if mesh is None and use_pallas_fe is None:
            use_pallas_fe = False  # topology unknown: keep the kernel out
        self._fe_objective = GLMObjective(
            loss, l2_weight=fe.l2_weight, normalization=normalization,
            use_pallas=False if (multi_device or use_pallas_fe is False)
            else use_pallas_fe,
        )
        self._fe_sharded_objective = None
        if multi_device and use_pallas_fe is not False and not fe_feature_sharded:
            from photon_ml_tpu.parallel.sharded_dense import (
                ShardedDenseGLMObjective,
            )

            self._fe_sharded_objective = ShardedDenseGLMObjective(
                loss, mesh, l2_weight=fe.l2_weight,
                normalization=normalization, use_pallas=use_pallas_fe,
            )
        # sparse twin, used when the FE shard arrives as flat COO (the
        # giant-d path); shares the normalization context so jit caches of
        # both variants stay identity-keyed
        self._fe_sparse_objective = SparseGLMObjective(
            loss, l2_weight=fe.l2_weight, normalization=normalization
        )
        # additional (dense, replicated) FE coordinates
        extra_fe_normalizations = dict(extra_fe_normalizations or {})
        for s in self.extra_fes:
            ctx = extra_fe_normalizations.get(s.feature_shard_id)
            if (
                ctx is not None and ctx.shifts is not None
                and s.intercept_index is None
            ):
                raise ValueError(
                    f"fixed-effect coordinate '{s.feature_shard_id}': "
                    "normalization with shifts (STANDARDIZATION) requires "
                    "the spec's intercept_index"
                )
        self._extra_fe_objectives = {
            s.feature_shard_id: GLMObjective(
                loss, l2_weight=s.l2_weight,
                normalization=extra_fe_normalizations.get(s.feature_shard_id),
                use_pallas=False,
            )
            for s in self.extra_fes
        }
        # RE normalization: the full factor+shift algebra. Factors scale the
        # effective coefficients; shifts subtract each entity's margin-shift
        # scalar in scoring (_re_coordinate_score) and are absorbed into the
        # shard's intercept on model-space conversion — the spec must carry
        # intercept_index then (same contract as the FE/CD paths,
        # ValueAndGradientAggregator.scala:36-49).
        re_normalizations = dict(re_normalizations or {})
        for s in self.re_specs:
            ctx = re_normalizations.get(s.re_type)
            if (
                ctx is not None and ctx.shifts is not None
                and s.intercept_index is None
            ):
                raise ValueError(
                    f"random-effect coordinate '{s.re_type}': normalization "
                    "with shifts (STANDARDIZATION) requires the spec's "
                    "intercept_index (the intercept absorbs each entity's "
                    "margin shift in model space)"
                )
        self._re_objectives = {
            s.re_type: GLMObjective(
                loss, l2_weight=s.l2_weight,
                normalization=re_normalizations.get(s.re_type),
                use_pallas=False,
            )
            for s in self.re_specs
        }
        # projected (INDEX_MAP/RANDOM) + normalization: entity blocks
        # arrive pre-normalized (build_random_effect_dataset(
        # normalization=...)), so their SOLVES use a plain objective;
        # scoring/table conversion keep the context
        self._re_solve_objectives = {
            s.re_type: (
                GLMObjective(loss, l2_weight=s.l2_weight, use_pallas=False)
                if (
                    s.projector in (ProjectorType.INDEX_MAP,
                                    ProjectorType.RANDOM)
                    and re_normalizations.get(s.re_type) is not None
                )
                else self._re_objectives[s.re_type]
            )
            for s in self.re_specs
        }
        self._mf_objectives = {
            m.name: GLMObjective(loss, l2_weight=m.l2_weight,
                                 use_pallas=False)
            for m in self.mf_specs
        }
        # ledger-labeled programs (telemetry/program_ledger.py): the whole
        # CD sweep and the validation score, the two hottest signatures of
        # a training run, and the entry scoring that fills an empty carry
        # ahead of a fit's first sweep (fused or scheduled)
        self._entry_scores = ledger_jit(self._entry_scores_impl,
                                        label="train/entry_scores")
        self._step = _CarriedStep(self)
        self._solver_counts = None  # of the last fused sweep, on the device
        self._rows_of = None  # (the buckets last stepped over, their solve_rows)
        self._score = ledger_jit(self._score_impl, label="train/score")

    def fe_coefficients_model_space(self, state: GameTrainState,
                                    intercept_index: int | None = None) -> Array:
        """Convert the state's normalized-space FE vector to original feature
        space for persistence/scoring outside the step."""
        return self._fe_objective.normalization.to_model_space(
            state.fe_coefficients, intercept_index
        )

    # -- state / input preparation ------------------------------------------

    def init_state(self, dataset: GameDataset,
                   re_datasets: Mapping[str, RandomEffectDataset],
                   mf_datasets: Mapping[str, "MFDataset"] | None = None,
                   dtype=None) -> GameTrainState:
        from photon_ml_tpu.models.matrix_factorization import init_factors

        from photon_ml_tpu.data.batch import solve_dtype_of

        fe_dim = dataset.feature_shards[self.fe.feature_shard_id].shape[1]
        dtype = solve_dtype_of(
            dtype or dataset.feature_shards[self.fe.feature_shard_id].dtype
        )
        tables = {
            s.re_type: jnp.zeros(
                (re_datasets[s.re_type].num_entities,
                 re_datasets[s.re_type].table_width),  # K in compact mode
                dtype=dtype,
            )
            for s in self.re_specs
        }
        mf_rows: dict[str, Array] = {}
        mf_cols: dict[str, Array] = {}
        for m in self.mf_specs:
            mf = (mf_datasets or {})[m.name]
            row, col = init_factors(
                mf.num_row_entities, mf.num_col_entities,
                m.num_latent_factors, seed=m.seed, dtype=dtype,
            )
            # zero the factors of vocab entities with no samples (they are
            # never solved; random init would leak noise into their scores)
            row_mask, col_mask = mf.trained_masks()
            mf_rows[m.name] = jnp.where(jnp.asarray(row_mask)[:, None], row, 0.0)
            mf_cols[m.name] = jnp.where(jnp.asarray(col_mask)[:, None], col, 0.0)
        return GameTrainState(
            fe_coefficients=jnp.zeros((fe_dim,), dtype=dtype),
            re_tables=tables,
            mf_rows=mf_rows,
            mf_cols=mf_cols,
            extra_fe={
                s.feature_shard_id: jnp.zeros(
                    (dataset.feature_shards[s.feature_shard_id].shape[1],),
                    dtype=dtype,
                )
                for s in self.extra_fes
            },
        )

    def _attach_re_sparse(self, data: dict, dataset: GameDataset,
                          re_datasets: Mapping[str, RandomEffectDataset]):
        """Compact (sparse-shard) RE coordinates: per-entry (entity, table
        position, row, value) mappings for O(nnz) scoring inside the step
        (models/game.compact_entry_positions against the TRAINING
        active-column lists)."""
        from photon_ml_tpu.models.game import compact_entry_positions

        for s in self.re_specs:
            shard = dataset.feature_shards[s.feature_shard_id]
            ds = re_datasets.get(s.re_type) if re_datasets else None
            if not isinstance(shard, SparseShard):
                continue
            if ds is None or ds.active_cols is None:
                raise ValueError(
                    f"random-effect coordinate '{s.re_type}' uses a sparse "
                    "feature shard; its RandomEffectDataset (with "
                    "active_cols) is required to prepare inputs"
                )
            ent, pos, rows, vals = compact_entry_positions(
                shard,
                np.asarray(dataset.host_array(f"entity_idx/{s.re_type}")),
                ds.active_cols,
            )
            norm = self._re_objectives[s.re_type].normalization
            if norm.factors is not None:
                # normalized compact coordinate: the state's table lives in
                # normalized space, so residual scoring needs normalized
                # entry values x' = x * factor[col] (SCALE-only; entry
                # order matches coalesced(), which compact_entry_positions
                # reads)
                from photon_ml_tpu.ops.normalization import host_factors

                _, cols_s, _ = shard.coalesced()
                vals = np.asarray(vals) * host_factors(norm).astype(
                    np.asarray(vals).dtype
                )[np.asarray(cols_s)]
            data.setdefault("re_sparse", {})[s.re_type] = {
                "ent": jnp.asarray(ent),
                "pos": jnp.asarray(pos),
                "rows": jnp.asarray(rows),
                "vals": jnp.asarray(vals),
            }
        return data

    def prepare_inputs(self, dataset: GameDataset,
                       re_datasets: Mapping[str, RandomEffectDataset],
                       mf_datasets: Mapping[str, "MFDataset"] | None = None):
        data = _data_pytree(
            dataset, self.re_specs, self.fe.feature_shard_id, self.mf_specs,
            extra_fe_shards=tuple(self._extra_fe_by_name),
        )
        data = self._attach_re_sparse(data, dataset, re_datasets)
        buckets = _buckets_pytree(
            {s.re_type: re_datasets[s.re_type] for s in self.re_specs},
            self.re_specs,
            normalized_re_types={
                k for k in self._re_solve_objectives
                if self._re_solve_objectives[k] is not self._re_objectives[k]
            },
        )
        buckets["__mf__"] = {
            m.name: {
                side: [
                    {
                        "labels": _input_leaf(b.labels),
                        "weights": _input_leaf(b.weights),
                        "sample_rows": _input_leaf(b.sample_rows),
                        "entity_rows": _input_leaf(b.entity_rows),
                    }
                    for b in side_buckets
                ]
                for side, side_buckets in (
                    ("row", (mf_datasets or {})[m.name].row_buckets),
                    ("col", (mf_datasets or {})[m.name].col_buckets),
                )
            }
            for m in self.mf_specs
        }
        return data, buckets

    def _shard_data(self, mesh: Mesh, data, *, fe_feature_sharded: bool = False,
                    put_fn=None, group: str = "data"):
        """Lay a data pytree (training or scoring) out over the mesh:
        sample-axis arrays over "data", the FE feature axis over "model"
        when requested. Through :func:`parallel.mesh.place`: what is laid
        out already stays, host arrays go shard by shard."""
        put = partial(place, put=put_fn or jax.device_put, group=group)
        vec = NamedSharding(mesh, P("data"))
        data_axis = int(mesh.shape["data"])
        fe_fspec = P("data", "model") if fe_feature_sharded else P("data", None)

        def put_feats(shard_id, arr):
            spec = fe_fspec if shard_id == self.fe.feature_shard_id else P("data", None)
            return put(arr, NamedSharding(mesh, spec))

        data = dict(data)
        data["labels"] = put(data["labels"], vec)
        data["offsets"] = put(data["offsets"], vec)
        data["weights"] = put(data["weights"], vec)
        data["features"] = {k: put_feats(k, v) for k, v in data["features"].items()}
        data["entity_idx"] = {k: put(v, vec) for k, v in data["entity_idx"].items()}
        if "fe_sparse_batch" in data:
            # flat entry arrays shard over "data" (nnz axis); per-sample
            # vectors over "data"; GSPMD inserts the psum that combines
            # per-shard partial margins and the model-axis collectives for
            # a "model"-sharded coefficient gather
            sb = data["fe_sparse_batch"]
            sb = sb.pad_nnz(sb.nnz + (-sb.nnz) % data_axis)
            sb = sb.replace(
                values=put(sb.values, vec),
                col_indices=put(sb.col_indices, vec),
                row_ids=put(sb.row_ids, vec),
                labels=put(sb.labels, vec),
                offsets=put(sb.offsets, vec),
                weights=put(sb.weights, vec),
            )
            if sb.has_ell_view:
                _refuse_ell_tiers(sb)
                # [n, L] rides the sample axis like a dense feature block
                sb = sb.replace(
                    ell_vals=put(sb.ell_vals, NamedSharding(mesh, P("data", None))),
                    ell_cols=put(sb.ell_cols, NamedSharding(mesh, P("data", None))),
                )
            if sb.has_hybrid_view:
                # the dense hot head [n, k_hot] rides the sample axis too;
                # the k_hot global column ids are model-sized and replicate
                sb = sb.replace(
                    hot_vals=put(sb.hot_vals, NamedSharding(mesh, P("data", None))),
                    hot_col_ids=put(sb.hot_col_ids, NamedSharding(mesh, P())),
                )
            data["fe_sparse_batch"] = sb
        if "re_sparse" in data:
            # compact RE entry mappings: nnz axis over "data"; pads carry
            # value 0 + the last row id (keeps the row segment-sum's sorted
            # promise) + entity 0 (their zero values contribute nothing)
            placed = {}
            for k, sp in data["re_sparse"].items():
                nnz = int(sp["vals"].shape[0])
                pad = (-nnz) % data_axis
                if pad:
                    last_row = (
                        sp["rows"][-1:] if nnz else jnp.zeros(1, jnp.int32)
                    )
                    sp = {
                        "ent": jnp.pad(sp["ent"], (0, pad)),
                        "pos": jnp.pad(sp["pos"], (0, pad)),
                        "rows": jnp.concatenate(
                            [sp["rows"], jnp.broadcast_to(last_row, (pad,))]
                        ),
                        "vals": jnp.pad(sp["vals"], (0, pad)),
                    }
                placed[k] = {n_: put(v, vec) for n_, v in sp.items()}
            data["re_sparse"] = placed
        return data

    def shard_inputs(self, mesh: Mesh, data, buckets, state,
                     *, fe_feature_sharded: bool = False, put_fn=None):
        """Lay out inputs over the mesh: samples and entities over "data",
        FE features (and coefficient vector) over "model" when requested.

        put_fn: placement function (array, sharding) -> Array. Defaults to
        jax.device_put; pass parallel.multihost.global_put when the mesh
        spans multiple processes (each feeds its addressable shards)."""
        put = partial(place, put=put_fn or jax.device_put, group="buckets")
        put_state = partial(place, put=put_fn or jax.device_put, group="state")
        rep = NamedSharding(mesh, P())
        data_axis = int(mesh.shape["data"])
        with span("train/shard/data"):
            data = self._shard_data(
                mesh, data, fe_feature_sharded=fe_feature_sharded,
                put_fn=put_fn,
            )

        ent3 = NamedSharding(mesh, P("data", None, None))
        ent2 = NamedSharding(mesh, P("data", None))
        ent1 = NamedSharding(mesh, P("data"))

        def put_bucket(b: dict) -> dict:
            # Pad the entity axis to a multiple of the mesh "data" axis
            # (nothing to do for buckets packed for this mesh:
            # build_random_effect_dataset(mesh=...) pads the lanes itself).
            # Padding lanes carry weight 0 and an out-of-range entity row:
            # JAX clamps out-of-bounds gathers (warm-start reads are junk but
            # harmless) and DROPS out-of-bounds scatter updates, so padded
            # lanes never write into the coefficient tables.
            e = int(b["entity_rows"].shape[0])
            pad = (-e) % data_axis
            if pad:
                # padded lanes' entity_rows are OOB, so the whole 2-D
                # scatter row of col_index drops whatever its column values
                fills = {"sample_rows": -1,
                         "entity_rows": np.iinfo(np.int32).max}
                b = {k: _pad_leading(v, pad, fills.get(k, 0))
                     for k, v in b.items()}
            out = {
                "labels": put(b["labels"], ent2),
                "weights": put(b["weights"], ent2),
                "sample_rows": put(b["sample_rows"], ent2),
                "entity_rows": put(b["entity_rows"], ent1),
            }
            if "features" in b:
                out["features"] = put(b["features"], ent3)
            if "col_index" in b:
                out["col_index"] = put(b["col_index"], ent2)
            return out

        with span("train/shard/buckets"):
            sharded_buckets: dict = {
                k: [put_bucket(b) for b in bs]
                for k, bs in buckets.items()
                if k not in ("__mf__", "__projections__")
            }
            if "__projections__" in buckets:
                sharded_buckets["__projections__"] = {
                    k: put(v, rep)
                    for k, v in buckets["__projections__"].items()
                }
            if "__mf__" in buckets:
                sharded_buckets["__mf__"] = {
                    name: {
                        side: [put_bucket(b) for b in side_buckets]
                        for side, side_buckets in sides.items()
                    }
                    for name, sides in buckets["__mf__"].items()
                }

        def put_table(v):
            # entity axis padded to a mesh multiple; padded rows are never
            # read (entity indices stay < E) nor written (scatter targets
            # are real rows), and are sliced off again on exit
            pad = (-int(v.shape[0])) % data_axis
            if pad:
                v = _pad_leading(v, pad)
            return put_state(v, ent2)

        fe_sharding = NamedSharding(mesh, P("model")) if fe_feature_sharded else rep
        with span("train/shard/state"):
            state = GameTrainState(
                fe_coefficients=put_state(state.fe_coefficients, fe_sharding),
                re_tables={k: put_table(v)
                           for k, v in state.re_tables.items()},
                mf_rows={k: put_table(v) for k, v in state.mf_rows.items()},
                mf_cols={k: put_table(v) for k, v in state.mf_cols.items()},
                # extra FE vectors replicate (only the primary may
                # feature-shard)
                extra_fe={k: put_state(v, rep)
                          for k, v in state.extra_fe.items()},
            )
        return data, sharded_buckets, state

    # -- the fused step ------------------------------------------------------

    def step(self, data, buckets, state: GameTrainState):
        """One full CD sweep. Returns (new_state, training_loss); the new
        state carries the sweep's margins (``GameTrainState.scores``), so the
        next sweep over the SAME data scores nothing at its entry. A state
        that carries none (a fresh, resumed or warm-started one) is given
        them by one entry scoring first (:meth:`_carried`). The carry handed
        in is donated (:class:`_CarriedStep`): ``state.scores`` is not to be
        read after the call, the tables and coefficients are.

        The sweep's solver counts (a row of optim/common.BUCKET_COUNT_NAMES
        for every solve, :meth:`solve_rows`) stay on the program as one
        unread device array, its copy to the host started here, behind the
        sweep's work: :meth:`take_solver_counts` reads it (a read that
        starts only once the loss has arrived waits 0.4 ms more for some
        hundred bytes, PERF.md 6, PR 52)."""
        state, loss, counts = self._step(data, buckets, state)
        counts.copy_to_host_async()
        self._solver_counts = (self.solve_rows(buckets), counts)
        return state, loss

    def take_solver_counts(self) -> "SweepCounts | None":
        """The solver counts of the sweep :meth:`step` last ran, on the host
        (ONE device-to-host read, which :meth:`step` started: call it once
        the loss has been waited for), or None when no fused sweep left any;
        each sweep's counts are handed out once."""
        held, self._solver_counts = self._solver_counts, None
        if held is None:
            return None
        rows, counts = held
        return SweepCounts(rows, counts)

    def solve_rows(self, buckets) -> "tuple[SolveRow, ...]":
        """The rows of the step's count array, in its order: the bucket
        solves (coordinates in update order, a random effect's buckets in
        the ladder's order, a factorization's alternations with the row
        side's buckets ahead of the column side's), then the fixed-effect
        solves in update order. From static shapes: nothing is read, and a
        fit's sweeps, which hand in one packed-buckets object, work them out
        once (the program keeps that object with its rows)."""
        if self._rows_of is not None and self._rows_of[0] is buckets:
            return self._rows_of[1]
        lanes, fixed = [], []
        for name in self.update_order:
            kind = self._kind[name]
            if kind == "re":
                newton = (self._re_by_name[name].optimizer.optimizer_type
                          == OptimizerType.NEWTON)
                lanes += [SolveRow("re", f"re/{name}", *b["labels"].shape, newton)
                          for b in buckets[name]]
            elif kind == "mf":
                sides = buckets["__mf__"][name]
                lanes += [
                    SolveRow("mf", f"mf/{name}/{side}", *b["labels"].shape, False)
                    for _ in range(self._mf_by_name[name].num_alternations)
                    for side in ("row", "col") for b in sides[side]]
            else:
                fixed.append(SolveRow("fe", f"fe/{name}", 1, 0, False))
        self._rows_of = (buckets, tuple(lanes + fixed))
        return self._rows_of[1]

    def _carried(self, data, state: GameTrainState) -> GameTrainState:
        """``state`` with its margins over ``data``'s rows: as it came where
        it carries them, else filled by the entry program, one jitted
        :meth:`_coordinate_scores` (counter ``train/entry_scorings``). The
        fill is the host's, ahead of the dispatch, so that ``train/step``
        is traced in one form."""
        if state.scores:
            return state
        default_registry().counter(ENTRY_SCORINGS).inc()
        return state.replace(scores=self._entry_scores(data, state))

    def _entry_scores_impl(self, data, state: GameTrainState):
        """The entry program: every coordinate's margins at ``state``."""
        return self._over_rows(self._coordinate_scores(data, state))

    def _over_rows(self, scores: dict[str, Array]) -> dict[str, Array]:
        """The margins a program hands on, held to the rows' sharding on a
        mesh (``P("data")``): the entry program's and a sweep's then reach
        the next sweep laid out alike, and it compiles once. As they came
        in a program of one device."""
        if self._exchange is None:
            return scores
        by_leading_axis, _ = self._exchange
        return {k: jax.lax.with_sharding_constraint(v, by_leading_axis)
                for k, v in scores.items()}

    def _carried_scores(self, data, state: GameTrainState) -> dict[str, Array]:
        """The state's carry as the recursion's dict, in canonical order: a
        dict inside a pytree crosses ``jit`` with its keys SORTED, and
        :meth:`_sum_scores` adds in the dict's order. A carry of other
        coordinates or of another row count (a state carried over to
        another data set) is refused, not broadcast."""
        n = data["labels"].shape[0]
        if set(state.scores) != set(self._canonical_order):
            raise ValueError(
                f"state.scores holds {sorted(state.scores)}, the program's "
                f"coordinates are {list(self._canonical_order)}; step() "
                "computes them for a state.replace(scores={})")
        for name, v in state.scores.items():
            if v.shape != (n,):
                raise ValueError(
                    f"state.scores['{name}'] has shape {tuple(v.shape)}, the "
                    f"data has {n} rows: scores are margins over the rows "
                    "the state was last stepped on; step() computes them "
                    "anew for a state.replace(scores={})")
        return {name: state.scores[name] for name in self._canonical_order}

    def _weighted_loss(self, labels, weights, total_margin):
        with jax.named_scope("loss"):
            losses = self._loss.loss(total_margin, labels)
            wsum = jnp.maximum(jnp.sum(weights), 1.0)
            return jnp.sum(weights * losses) / wsum

    def _sum_scores(self, base, scores, skip=None):
        """base + every coordinate score except ``skip`` — the residual-
        offset sum of the CD recursion, as its own jittable piece for the
        scheduled sweep."""
        with jax.named_scope("residual"):
            total = base
            for k, v in scores.items():
                if k != skip:
                    total = total + v
            return total

    def _scheduled_jits(self):
        """Per-coordinate jitted pieces of the sweep, for step_scheduled:
        the scheduler needs host control between the probe and rescue
        solves, so the one-jit sweep is traded for a handful of cached
        per-coordinate programs (compiled once, reused every sweep). The
        entry scoring is not among them: it is the fused step's
        (``_entry_scores``), run by :meth:`_carried` for an empty carry."""
        jits = getattr(self, "_sched_jits", None)
        if jits is None:
            jits = {
                "fe_solve": ledger_jit(self._solve_primary_fe,
                                       label="train/sched_fe_solve"),
                "fe_margin": ledger_jit(self._fe_margin_score,
                                        label="train/sched_fe_margin"),
                "extra_fe_solve": ledger_jit(
                    self._solve_extra_fe, label="train/sched_extra_fe_solve",
                    static_argnums=(1,)
                ),
                "extra_fe_margin": ledger_jit(
                    self._extra_fe_margin,
                    label="train/sched_extra_fe_margin", static_argnums=(1,)
                ),
                "re_solve": ledger_jit(self._solve_re,
                                       label="train/sched_re_solve",
                                       static_argnums=(2,)),
                "re_score": ledger_jit(
                    self._re_coordinate_score, label="train/sched_re_score",
                    static_argnums=(1, 3)
                ),
                "mf_solve": ledger_jit(self._solve_mf,
                                       label="train/sched_mf_solve",
                                       static_argnums=(2,)),
                "offsets": ledger_jit(self._sum_scores,
                                      label="train/sched_offsets",
                                      static_argnums=(2,)),
                "loss": ledger_jit(self._weighted_loss,
                                   label="train/sched_loss"),
            }
            self._sched_jits = jits
        return jits

    def step_scheduled(self, data, buckets, state: GameTrainState, *,
                       schedulers: Mapping[str, object],
                       final_sweep: bool = True):
        """One full CD sweep with probe/rescue lane scheduling on the
        random-effect coordinates (algorithm/lane_scheduler.py).

        Same Gauss-Seidel recursion as :meth:`step` in the same
        ``update_order`` and with the same carry of margins from sweep to
        sweep, but host-driven: each coordinate runs as its own
        cached jitted program so the scheduler can read per-lane converged
        flags between the probe and rescue solves and compact only the
        unconverged lanes. Strictly opt-in — ``train_distributed`` uses it
        only when an RE spec's OptimizerConfig carries a scheduler config.
        Multi-process runs use schedulers built with the training mesh
        (``make_schedulers``): rank-local compaction into a fixed
        [num_ranks * R] rescue-block signature, collectives on every rank.

        schedulers: re_type -> LaneScheduler, persisted across sweeps by
        the caller (bucket host caches + cross-sweep active sets live
        there). REs absent from the mapping solve unscheduled.
        """
        jits = self._scheduled_jits()
        scores = self._carried_scores(data, self._carried(data, state))
        labels, weights = data["labels"], data["weights"]
        base = data["offsets"]
        fe_w = state.fe_coefficients
        extra_fe = dict(state.extra_fe)
        tables = dict(state.re_tables)
        mf_rows = dict(state.mf_rows)
        mf_cols = dict(state.mf_cols)
        for name in self.update_order:
            kind = self._kind[name]
            off = jits["offsets"](base, scores, name)
            if kind == "fe":
                fe_w, _counts = jits["fe_solve"](data, off, weights, fe_w)
                scores[name] = jits["fe_margin"](data, fe_w)
            elif kind == "extra_fe":
                extra_fe[name], _counts = jits["extra_fe_solve"](
                    data, name, off, labels, weights, extra_fe[name]
                )
                scores[name] = jits["extra_fe_margin"](data, name, extra_fe[name])
            elif kind == "re":
                spec = self._re_by_name[name]
                scheduler = schedulers.get(name)
                if scheduler is None:
                    tables[name], _counts = jits["re_solve"](
                        data, buckets, name, off, tables[name]
                    )
                else:
                    matrix = buckets.get("__projections__", {}).get(name)
                    tables[name], _traces, _stats = scheduler.solve(
                        self._re_solve_objectives[name], spec.optimizer,
                        buckets[name], off, tables[name],
                        projector=spec.projector, matrix=matrix,
                        final_sweep=final_sweep,
                    )
                scores[name] = jits["re_score"](
                    data, name, tables[name], spec.feature_shard_id
                )
            else:  # mf
                mf_rows[name], mf_cols[name], scores[name], _counts = (
                    jits["mf_solve"](
                        data, buckets, name, off, mf_rows[name], mf_cols[name]
                    ))
        total = jits["offsets"](base, scores, None)
        loss = jits["loss"](labels, weights, total)
        new_state = GameTrainState(
            fe_coefficients=fe_w, re_tables=tables,
            mf_rows=mf_rows, mf_cols=mf_cols, extra_fe=extra_fe,
            scores=scores,
        )
        return new_state, loss

    # -- whole-model scoring (validation / best-model tracking) --------------

    def prepare_scoring_inputs(
        self, dataset: GameDataset,
        re_datasets: Mapping[str, RandomEffectDataset] | None = None,
    ) -> dict:
        """Data pytree for :meth:`score` over an arbitrary dataset (e.g. the
        validation split) — same layout the training step consumes, no
        entity buckets needed. Compact (sparse-shard) RE coordinates need
        ``re_datasets`` (the TRAINING datasets: their active-column lists
        define the table layout being scored)."""
        data = _data_pytree(
            dataset, self.re_specs, self.fe.feature_shard_id, self.mf_specs,
            extra_fe_shards=tuple(self._extra_fe_by_name),
        )
        return self._attach_re_sparse(data, dataset, re_datasets or {})

    def shard_scoring_inputs(self, mesh: Mesh, data, *,
                             fe_feature_sharded: bool = False, put_fn=None):
        return self._shard_data(
            mesh, data, fe_feature_sharded=fe_feature_sharded, put_fn=put_fn,
            group="validation",
        )

    def score(self, data, state: GameTrainState) -> Array:
        """[n] total model scores (margins INCLUDING the data offsets) at
        ``state`` — the validation-scoring analogue of the reference's
        per-update ``GameModel.scoreAndValidate``
        (CoordinateDescent.scala:291-356), as one jitted SPMD program over
        the same mesh shardings as the training step. The state's carried
        margins are of the training rows and stay behind."""
        return self._score(data, state.replace(scores={}))

    def _score_impl(self, data, state: GameTrainState) -> Array:
        total = data["offsets"]
        for v in self._coordinate_scores(data, state).values():
            total = total + v
        return total

    # -- scoring helpers shared by the step and the post-hoc variance path --

    def _re_coordinate_score(self, data, k: str, table: Array,
                             shard_id: str) -> Array:
        """Tables hold normalized-space coefficients when the coordinate is
        normalized; score through the full effective-coefficient algebra
        (factor scaling, and the per-entity margin-shift term for
        standardized coordinates)."""
        with jax.named_scope(f"score/{k}"):
            sp = data.get("re_sparse", {}).get(k)
            if sp is not None:
                # compact [E, K] table over per-entity active columns; when the
                # coordinate is SCALE-normalized, both the table and the entry
                # values (scaled in _attach_re_sparse) live in normalized space
                # — their product is the data-space margin, no shift term
                from photon_ml_tpu.models.game import score_random_effect_compact

                return score_random_effect_compact(
                    table, sp["ent"], sp["pos"], sp["rows"], sp["vals"],
                    data["labels"].shape[0],
                )
            norm = self._re_objectives[k].normalization
            eff = norm.effective_coefficients(table)
            scores = score_random_effect(
                eff, data["features"][shard_id], data["entity_idx"][k]
            )
            if norm.shifts is not None:
                # per-entity margin-shift scalar: (w_e ⊙ f) · shifts
                idx = data["entity_idx"][k]
                ent_shift = eff @ norm.shifts
                scores = scores - jnp.where(
                    idx >= 0, ent_shift[jnp.maximum(idx, 0)], 0.0
                )
            return scores

    def _fe_margin_score(self, data, fe_w: Array) -> Array:
        """The FE coordinate's pure margin (no offsets) from normalized-space
        coefficients, dense or flat-COO."""
        with jax.named_scope(f"score/{self.fe.feature_shard_id}"):
            fe_sparse = data.get("fe_sparse_batch")
            objective = (
                self._fe_sparse_objective if fe_sparse is not None
                else self._fe_objective
            )
            norm = objective.normalization
            eff = norm.effective_coefficients(fe_w)
            if fe_sparse is not None:
                # fe_sparse keeps its zero offsets, so this is the pure margin
                return sparse_margins(fe_sparse, eff) - norm.margin_shift(eff)
            return (
                data["features"][self.fe.feature_shard_id] @ eff
                - norm.margin_shift(eff)
            )

    def _extra_fe_margin(self, data, shard_id: str, w: Array) -> Array:
        """Pure margin of a non-primary (dense, replicated) FE coordinate."""
        with jax.named_scope(f"score/{shard_id}"):
            norm = self._extra_fe_objectives[shard_id].normalization
            eff = norm.effective_coefficients(w)
            return data["features"][shard_id] @ eff - norm.margin_shift(eff)

    def _coordinate_scores(self, data, state: GameTrainState) -> dict[str, Array]:
        """name -> score of EVERY coordinate at the state (primary FE
        margin, extra FE margins, RE scores, MF scores) — the residual
        terms of the CD recursion, in canonical name order (FEs, REs, MFs)
        so residual sums accumulate in a deterministic order."""
        scores = {
            self.fe.feature_shard_id:
                self._fe_margin_score(data, state.fe_coefficients)
        }
        for s in self.extra_fes:
            scores[s.feature_shard_id] = self._extra_fe_margin(
                data, s.feature_shard_id, state.extra_fe[s.feature_shard_id]
            )
        for s in self.re_specs:
            scores[s.re_type] = self._re_coordinate_score(
                data, s.re_type, state.re_tables[s.re_type], s.feature_shard_id
            )
        for m in self.mf_specs:
            with jax.named_scope(f"score/{m.name}"):
                scores[m.name] = score_matrix_factorization(
                    state.mf_rows[m.name],
                    state.mf_cols[m.name],
                    data["entity_idx"][m.row_effect_type],
                    data["entity_idx"][m.col_effect_type],
                )
        return scores

    def _step_impl(self, data, buckets, state: GameTrainState):
        """One fused sweep. Every phase is traced under a ``jax.named_scope``,
        entered where the phase's code lives so that the scheduled sweep and
        the coordinate-descent path carry it too: ``score/<coordinate>``,
        ``residual``, ``loss``, ``fe/solve``, ``extra_fe/<name>/solve``,
        ``re/<type>`` and ``mf/<name>/<side>`` round a bucket's ``gather``,
        ``solve`` and ``scatter``, ``lbfgs/direction|history|line_search``
        inside a solve. A scope is metadata (the compiled instructions'
        ``op_name``), never an instruction;
        ``program_ledger.compiled_scopes("train/step")`` reads them back."""
        labels, weights = data["labels"], data["weights"]
        base_offsets = data["offsets"]

        # Gauss-Seidel recursion over self.update_order: `scores` always
        # holds each coordinate's score at its LATEST coefficients, so a
        # coordinate solved later in the sweep sees the residuals of the
        # ones already updated (reference CoordinateDescent.scala:198-255 —
        # the configured order is semantic, not cosmetic). That holds across
        # sweeps too: the dict a sweep ends with is the one the next starts
        # from (state.scores), so a coordinate is scored once a sweep, after
        # its solve. An empty carry is refused: filling it is step()'s, by
        # the entry program, ahead of the dispatch.
        scores = self._carried_scores(data, state)

        def offsets_excluding(skip=None):
            return self._sum_scores(base_offsets, scores, skip)

        fe_w = state.fe_coefficients
        extra_fe = dict(state.extra_fe)
        tables = dict(state.re_tables)
        mf_rows = dict(state.mf_rows)
        mf_cols = dict(state.mf_cols)
        # every solve's counts by parts (optim/common.bucket_count_parts),
        # in solve_rows' order: the lanes' buckets, the fixed effects
        lane_counts: list = []
        fe_counts: list = []

        for name in self.update_order:
            kind = self._kind[name]
            if kind == "fe":
                fe_w, counts = self._solve_primary_fe(
                    data, offsets_excluding(name), weights, fe_w
                )
                fe_counts.append(counts)
                scores[name] = self._fe_margin_score(data, fe_w)
            elif kind == "extra_fe":
                extra_fe[name], counts = self._solve_extra_fe(
                    data, name, offsets_excluding(name), labels, weights,
                    extra_fe[name],
                )
                fe_counts.append(counts)
                scores[name] = self._extra_fe_margin(data, name, extra_fe[name])
            elif kind == "re":
                tables[name], counts = self._solve_re(
                    data, buckets, name, offsets_excluding(name), tables[name]
                )
                lane_counts += counts
                scores[name] = self._re_coordinate_score(
                    data, name, tables[name],
                    self._re_by_name[name].feature_shard_id,
                )
            else:  # mf
                mf_rows[name], mf_cols[name], scores[name], counts = (
                    self._solve_mf(
                        data, buckets, name, offsets_excluding(name),
                        mf_rows[name], mf_cols[name],
                    ))
                lane_counts += counts

        total_margin = offsets_excluding()
        train_loss = self._weighted_loss(labels, weights, total_margin)
        new_state = GameTrainState(
            fe_coefficients=fe_w, re_tables=tables,
            mf_rows=mf_rows, mf_cols=mf_cols, extra_fe=extra_fe,
            scores=self._over_rows(scores),
        )
        # one small array: one device-to-host read a sweep, not one a count
        return new_state, train_loss, self._stacked_counts(
            lane_counts, fe_counts)

    def _stacked_counts(self, lane_counts, fe_counts) -> Array:
        """``int32[solves, len(BUCKET_COUNT_NAMES)]`` from every solve's
        counts by parts: the lanes' buckets stacked and their parts (on a
        mesh a chip's own lanes each) brought together ONCE, one ``max`` and
        one ``sum`` across the chips a sweep and not a handful a bucket; the
        fixed effects' rows, whose counts every chip holds whole, after
        them. Trips are zero-padded to the widest solve's (a count is never
        negative)."""
        def stacked(counts):  # [parts, solves, ...] each
            width = max(maxima.shape[1] for maxima, _ in counts)
            return (jnp.stack([jnp.pad(maxima, ((0, 0), (0, width - maxima.shape[1])))
                               for maxima, _ in counts], axis=1),
                    jnp.stack([sums for _, sums in counts], axis=1))

        rows = []
        if lane_counts:
            by_part = stacked(lane_counts)
            if self._exchange is not None:  # a mesh: a part is a chip's own
                by_leading_axis, _ = self._exchange
                by_part = tuple(jax.lax.with_sharding_constraint(a, by_leading_axis)
                                for a in by_part)
            rows.append(bucket_counts(*by_part))
        if fe_counts:
            rows.append(bucket_counts(*stacked(fe_counts)))
        return jnp.concatenate(rows, axis=0)

    def _solve_primary_fe(self, data, fe_offsets, weights, fe_w0):
        """Primary fixed-effect solve (samples sharded; grads psum over the
        mesh; the only coordinate that may be sparse / feature-sharded).

        Optional down-sampling trains the FE solve on multiplied weights
        (0 = dropped, 1/rate = kept negative); every other use of
        ``weights`` — other solves, the training loss — stays full-sample.
        The returned vector lives in normalized space (warm starts stay
        there across steps); callers score through the same effective-
        coefficient algebra the objective uses, so residuals stay in data
        space. Returns (coefficients, the solve's counts: ``_fe_solved``).
        """
        with jax.named_scope("fe/solve"):
            fe_sparse = data.get("fe_sparse_batch")
            fe_mult = data.get("fe_weight_multiplier")
            fe_weights = weights if fe_mult is None else weights * fe_mult
            if fe_sparse is not None:
                fe_batch = fe_sparse.replace(offsets=fe_offsets, weights=fe_weights)
                fe_objective = self._fe_sparse_objective
            else:
                fe_batch = LabeledPointBatch(
                    features=data["features"][self.fe.feature_shard_id],
                    labels=data["labels"],
                    offsets=fe_offsets,
                    weights=fe_weights,
                )
                # multi-device mesh: per-device single-pass kernel + psum
                # (parallel/sharded_dense.py) instead of the GSPMD autodiff path
                fe_objective = (
                    self._fe_sharded_objective
                    if self._fe_sharded_objective is not None
                    else self._fe_objective
                )
            return _fe_solved(
                solve(self.fe.optimizer, fe_objective.bind(fe_batch), fe_w0)
            )

    def _solve_extra_fe(self, data, name, full_offsets, labels, weights, w0):
        """A non-primary FE coordinate: dense replicated solve, same
        residual + down-sampling contract as the primary."""
        with jax.named_scope(f"extra_fe/{name}/solve"):
            mult = data.get("extra_fe_weight_multipliers", {}).get(name)
            fe_weights = weights if mult is None else weights * mult
            batch = LabeledPointBatch(
                features=data["features"][name],
                labels=labels,
                offsets=full_offsets,
                weights=fe_weights,
            )
            spec = self._extra_fe_by_name[name]
            return _fe_solved(
                solve(spec.optimizer, self._extra_fe_objectives[name].bind(batch), w0)
            )

    def _whole_on_every_chip(self, *arrays):
        """Arrays that a coordinate's buckets index by their slots (the
        row-ordered ``[n]`` offsets by ``sample_rows``; for a factorization's
        half-step also the fixed side's ``[n]`` entity index and its
        ``[E, k]`` factors), whole on every chip of the program's mesh: ONE
        all-gather an array, under the scope ``gather``, ahead of the loop
        over the buckets. Such an array lies over the chips by its leading
        axis (``P("data")``), a bucket's ``[e, cap]`` indices by lanes and
        name rows anywhere; left to itself the partitioner all-gathers every
        bucket's indices, has every chip gather ALL lanes' slots against its
        own rows under a mask and all-reduces the results (PERF.md 6, PR 38).
        With the operand replicated a chip gathers its own lanes' slots and
        nobody else's. The values are the same bit for bit.

        Two constraints in a traced step: the first says where the array
        lies (``P("data")``, nothing moves) and is the instruction the
        partitioner reshards, so the all-gather carries ITS ``op_name``,
        ``.../gather/sharding_constraint``; constrained to replicated alone,
        the all-gather takes the name of whatever produced the array
        (``residual/add``) and its seconds are filed under that phase.

        A program of one device, and arrays that lie on one device (the
        post-hoc variances of a fit that was not laid over the mesh), are
        returned as they came: nothing is emitted."""
        if self._exchange is None or not any(
            isinstance(a, jax.core.Tracer) or len(a.sharding.device_set) > 1
            for a in arrays
        ):
            return arrays
        default_registry().counter(EXCHANGES_TRACED).inc()
        by_leading_axis, every_chip = self._exchange

        def whole(a):
            if isinstance(a, jax.core.Tracer):
                a = jax.lax.with_sharding_constraint(a, by_leading_axis)
            return jax.lax.with_sharding_constraint(a, every_chip)

        with jax.named_scope("gather"):
            return tuple(whole(a) for a in arrays)

    def _solve_re(self, data, buckets, k, full_offsets, table):
        """One random-effect coordinate (entities sharded, vmapped solves),
        under the scope ``re/<k>``; on a mesh the offsets are brought whole
        to every chip once, ahead of the buckets. Returns (table, its
        buckets' counts by parts, one optim/common.bucket_count_parts pair
        a bucket)."""
        spec = self._re_by_name[k]
        objective = self._re_solve_objectives[k]
        counts: list = []
        parts = self._lane_parts
        with jax.named_scope(f"re/{k}"):
            (full_offsets,) = self._whole_on_every_chip(full_offsets)
            if spec.projector == ProjectorType.INDEX_MAP:
                # scratch-column solve in each entity's observed columns
                # (ports algorithm/coordinates.py's single-chip path into
                # the SPMD program; IndexMapProjectorRDD.scala:218-257)
                table_ext = jnp.concatenate(
                    [table, jnp.zeros((table.shape[0], 1), table.dtype)],
                    axis=1,
                )
                for b in buckets[k]:
                    table_ext, trace = solve_entity_bucket_indexmap_traced(
                        objective, spec.optimizer,
                        b["features"], b["labels"], b["weights"],
                        b["sample_rows"], b["entity_rows"], b["col_index"],
                        full_offsets, table_ext,
                    )
                    counts.append(bucket_count_parts(trace, parts))
                return table_ext[:, :-1], counts
            if spec.projector == ProjectorType.RANDOM:
                matrix = buckets["__projections__"][k]
                for b in buckets[k]:
                    table, trace = solve_entity_bucket_random_traced(
                        objective, spec.optimizer,
                        b["features"], b["labels"], b["weights"],
                        b["sample_rows"], b["entity_rows"], matrix,
                        full_offsets, table,
                    )
                    counts.append(bucket_count_parts(trace, parts))
                return table, counts
            for b in buckets[k]:
                table, trace = solve_entity_bucket_traced(
                    objective,
                    spec.optimizer,
                    b["features"],
                    b["labels"],
                    b["weights"],
                    b["sample_rows"],
                    b["entity_rows"],
                    full_offsets,
                    table,
                )
                counts.append(bucket_count_parts(trace, parts))
            return table, counts

    def _solve_mf(self, data, buckets, name, full_offsets, rows, cols):
        """One matrix-factorization coordinate (alternating vmapped solves),
        every half-step under the scope ``mf/<name>/<side>`` so that its
        device instructions carry the coordinate's name; on a mesh what its
        buckets index (the offsets, the fixed side's entity index and
        factors) is brought whole to every chip once a half-step. Returns
        (rows, cols, score, the half-steps' buckets' counts by parts, in
        the order they were solved)."""
        m = self._mf_by_name[name]
        row_idx = data["entity_idx"][m.row_effect_type]
        col_idx = data["entity_idx"][m.col_effect_type]
        objective = self._mf_objectives[name]
        mf_buckets = buckets["__mf__"][name]
        counts: list = []

        def half_step(side, table, other_idx, other_factors):
            with jax.named_scope(f"mf/{name}/{side}"):
                offsets, other_idx, other_factors = self._whole_on_every_chip(
                    full_offsets, other_idx, other_factors)
                for b in mf_buckets[side]:
                    table, trace = solve_mf_side_bucket(
                        objective, m.optimizer, b["labels"], b["weights"],
                        b["entity_rows"], b["sample_rows"], other_idx,
                        other_factors, offsets, table,
                    )
                    counts.append(bucket_count_parts(trace, self._lane_parts))
            return table

        for _ in range(m.num_alternations):
            rows = half_step("row", rows, col_idx, cols)
            cols = half_step("col", cols, row_idx, rows)
        with jax.named_scope(f"score/{name}"):
            score = score_matrix_factorization(rows, cols, row_idx, col_idx)
        return rows, cols, score, counts


def compute_state_variances(
    program: GameTrainProgram,
    state: GameTrainState,
    dataset: GameDataset,
    re_datasets: Mapping[str, RandomEffectDataset] | None = None,
    *,
    variance_mode: str = "auto",
    re_types: "set[str] | None" = None,
) -> tuple[Array, dict[str, Array]]:
    """Post-hoc coefficient variances for a fused-trained state.

    ``re_types`` selects which random-effect coordinates get variances
    (None = all) — only SELECTED coordinates must satisfy the
    no-projection rule, matching the CD path's per-coordinate
    compute_variance semantics.

    The reference computes variances inside each optimization problem at
    the optimum (DistributedOptimizationProblem.computeVariances for the
    FE, SingleNodeOptimizationProblem for each entity); the fused step
    skips them (they are pure output, not part of the training recursion).
    This recomputes each coordinate's residual offsets from the final
    state — the same Hessians the reference evaluates — and returns
    (fe_variances, {re_type: [E, d] variance table},
    {extra_fe_shard: [d] variances}), all mapped to original model space.
    NaN rows mark entities no bucket trained.

    Requires ``re_datasets`` when the program has RE coordinates (their
    buckets carry the per-entity training views). Projected coordinates are
    fully supported, matching the CD path: INDEX_MAP/compact variances are
    computed in the solve space and scattered back through the entity index
    maps; RANDOM variances are propagated through the sketch as
    diag(P H_k⁻¹ Pᵀ).
    """
    from photon_ml_tpu.algorithm.coordinates import (
        _jitted_re_bucket_variances,
        _jitted_re_bucket_variances_diagonal,
        _jitted_re_bucket_variances_indexmap,
        _jitted_re_bucket_variances_indexmap_diagonal,
        _jitted_re_bucket_variances_random,
        _jitted_re_bucket_variances_random_diagonal,
    )
    from photon_ml_tpu.ops.variance import (
        coefficient_variances,
        resolve_variance_mode,
        validate_variance_mode,
    )

    # fail configuration errors BEFORE any device work (CD-path convention)
    validate_variance_mode(variance_mode)
    selected = [
        s for s in program.re_specs
        if re_types is None or s.re_type in re_types
    ]
    if program.re_specs:
        missing = [
            s.re_type for s in program.re_specs
            if re_datasets is None or s.re_type not in re_datasets
        ]
        if missing:
            raise ValueError(
                "compute_state_variances needs re_datasets entries for the "
                f"program's random-effect coordinates; missing: {missing}"
            )

    data = _data_pytree(
        dataset, program.re_specs, program.fe.feature_shard_id, program.mf_specs,
        extra_fe_shards=tuple(program._extra_fe_by_name),
    )
    # compact RE coordinates score through their entry mappings even here
    # (their scores are residual offsets for the other coordinates' Hessians)
    data = program._attach_re_sparse(data, dataset, re_datasets or {})
    base_offsets = data["offsets"]
    labels, weights = data["labels"], data["weights"]
    fe_sparse = data.get("fe_sparse_batch")

    # the exact residual-offset algebra of the fused step, via its own
    # scoring helpers (one definition for both the recursion and this path);
    # includes every FE coordinate's margin
    scores = program._coordinate_scores(data, state)

    def offsets_excluding(skip=None):
        return program._sum_scores(base_offsets, scores, skip)

    # fixed effects: Hessian at the final coefficients with every other
    # coordinate's score as residual offset
    fe_offsets = offsets_excluding(program.fe.feature_shard_id)
    if fe_sparse is not None:
        fe_batch = fe_sparse.replace(offsets=fe_offsets)
        fe_objective = program._fe_sparse_objective
    else:
        fe_batch = LabeledPointBatch(
            features=data["features"][program.fe.feature_shard_id],
            labels=labels, offsets=fe_offsets, weights=weights,
        )
        fe_objective = program._fe_objective
    fe_variances = fe_objective.normalization.variances_to_model_space(
        coefficient_variances(
            fe_objective, state.fe_coefficients, fe_batch, mode=variance_mode
        )
    )
    extra_fe_variances: dict[str, Array] = {}
    for s in program.extra_fes:
        k = s.feature_shard_id
        objective = program._extra_fe_objectives[k]
        batch = LabeledPointBatch(
            features=data["features"][k], labels=labels,
            offsets=offsets_excluding(k), weights=weights,
        )
        extra_fe_variances[k] = objective.normalization.variances_to_model_space(
            coefficient_variances(
                objective, state.extra_fe[k], batch, mode=variance_mode
            )
        )

    re_variances: dict[str, Array] = {}
    for spec in selected:
        ds = re_datasets[spec.re_type]
        table = state.re_tables[spec.re_type]
        (full_offsets,) = program._whole_on_every_chip(
            offsets_excluding(skip=spec.re_type))
        max_bucket = max((b.entity_rows.shape[0] for b in ds.buckets), default=1)
        norm = program._re_objectives[spec.re_type].normalization
        if spec.projector == ProjectorType.RANDOM:
            # propagated through the sketch: var(w) = diag(P H_k⁻¹ Pᵀ) — an
            # improvement over the reference, which passes the k-dim
            # projected variances through unchanged
            # (ProjectionMatrixBroadcast.scala:76)
            from photon_ml_tpu.algorithm.coordinates import (
                random_variance_mode,
            )

            # PLAIN solve objective: features/coefficients are k-dim
            # sketch-space (and pre-normalized at build when a context
            # exists) — the d-length context must not touch them
            objective = program._re_solve_objectives[spec.re_type]
            resolved = random_variance_mode(
                variance_mode, ds.dim, int(ds.projection.matrix.shape[1]),
                max_bucket,
            )
            kernel = (
                _jitted_re_bucket_variances_random if resolved == "full"
                else _jitted_re_bucket_variances_random_diagonal
            )
            matrix = jnp.asarray(ds.projection.matrix, dtype=table.dtype)
            var_table = jnp.full_like(table, jnp.nan)
            for b in ds.buckets:
                var_table = kernel(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, matrix,
                    full_offsets, table, var_table,
                )
        elif spec.projector == ProjectorType.INDEX_MAP:
            # solve-space diag(H⁻¹) scattered back through the entity index
            # maps (IndexMapProjectorRDD.scala:103); serves dense INDEX_MAP
            # and compact (sparse-shard) coordinates alike — col_index holds
            # original columns (pad=dim) resp. local positions (pad=K)
            objective = program._re_solve_objectives[spec.re_type]
            width = max(
                (int(b.features.shape[2]) for b in ds.buckets), default=1
            )
            resolved = resolve_variance_mode(variance_mode, width,
                                             num_problems=max_bucket)
            kernel = (
                _jitted_re_bucket_variances_indexmap if resolved == "full"
                else _jitted_re_bucket_variances_indexmap_diagonal
            )
            table_ext = jnp.concatenate(
                [table, jnp.zeros((table.shape[0], 1), table.dtype)], axis=1
            )
            var_ext = jnp.full_like(table_ext, jnp.nan)
            for b in ds.buckets:
                var_ext = kernel(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, b.col_index,
                    full_offsets, table_ext, var_ext,
                )
            var_table = var_ext[:, :-1]
            if ds.is_compact and norm.factors is not None:
                re_variances[spec.re_type] = (
                    norm.variances_to_model_space_compact(
                        var_table, jnp.asarray(ds.active_cols)
                    )
                )
                continue
        else:
            objective = program._re_objectives[spec.re_type]
            resolved = resolve_variance_mode(variance_mode, ds.dim,
                                             num_problems=max_bucket)
            kernel = (
                _jitted_re_bucket_variances if resolved == "full"
                else _jitted_re_bucket_variances_diagonal
            )
            var_table = jnp.full_like(table, jnp.nan)
            for b in ds.buckets:
                var_table = kernel(
                    objective, b.features, b.labels, b.weights,
                    b.sample_rows, b.entity_rows, full_offsets, table,
                    var_table,
                )
        re_variances[spec.re_type] = (
            norm.variances_to_model_space(var_table)
        )
    return fe_variances, re_variances, extra_fe_variances


def state_to_game_model(
    program: GameTrainProgram,
    state: GameTrainState,
    dataset: GameDataset,
    *,
    intercept_index: int | None = None,
    compute_variance: bool = False,
    variance_mode: str = "auto",
    re_datasets: Mapping[str, RandomEffectDataset] | None = None,
    variance_re_types: "set[str] | None" = None,
):
    """Convert a fused-step ``GameTrainState`` into a ``GameModel`` so
    multi-chip-trained models flow into the standard persistence/scoring
    stack (io/model_io.save_game_model, transformers.GameTransformer).

    Coordinate ids: the FE coordinate is named after its feature shard; RE
    coordinates after their RE type; MF coordinates after their spec name.
    The FE vector is converted back to original feature space (warm starts
    live in normalized space inside the step).

    compute_variance=True attaches post-hoc diag(H⁻¹)-style variances from
    :func:`compute_state_variances` (pass ``re_datasets`` for RE
    coordinates).
    """
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import GeneralizedLinearModel
    from photon_ml_tpu.models.matrix_factorization import (
        MatrixFactorizationModel,
    )

    fe_variances = None
    re_variances: dict[str, Array] = {}
    extra_fe_variances: dict[str, Array] = {}
    if compute_variance:
        fe_variances, re_variances, extra_fe_variances = compute_state_variances(
            program, state, dataset, re_datasets, variance_mode=variance_mode,
            re_types=variance_re_types,
        )

    models: dict[str, object] = {}
    fe_means = program.fe_coefficients_model_space(state, intercept_index)
    models[program.fe.feature_shard_id] = FixedEffectModel(
        glm=GeneralizedLinearModel(
            Coefficients(means=fe_means, variances=fe_variances), program.task
        ),
        feature_shard_id=program.fe.feature_shard_id,
    )
    for s in program.extra_fes:
        k = s.feature_shard_id
        norm = program._extra_fe_objectives[k].normalization
        models[k] = FixedEffectModel(
            glm=GeneralizedLinearModel(
                Coefficients(
                    means=norm.to_model_space(
                        state.extra_fe[k], s.intercept_index
                    ),
                    variances=extra_fe_variances.get(k),
                ),
                program.task,
            ),
            feature_shard_id=k,
        )
    for spec in program.re_specs:
        # normalized coordinates hold normalized-space tables in the state;
        # models are always persisted in original space (factors only, so
        # no intercept index is needed)
        re_norm = program._re_objectives[spec.re_type].normalization
        ds = (re_datasets or {}).get(spec.re_type)
        is_compact = ds is not None and ds.active_cols is not None
        if isinstance(
            dataset.feature_shards[spec.feature_shard_id], SparseShard
        ) and not is_compact:
            raise ValueError(
                f"random-effect coordinate '{spec.re_type}' trained on a "
                "sparse shard; pass its RandomEffectDataset via re_datasets "
                "so the compact model keeps its active-column lists"
            )
        models[spec.re_type] = RandomEffectModel(
            coefficients=(
                re_norm.to_model_space_compact(
                    state.re_tables[spec.re_type],
                    jnp.asarray(ds.active_cols),
                )
                if is_compact
                else re_norm.to_model_space(
                    state.re_tables[spec.re_type], spec.intercept_index
                )
            ),
            entity_keys=dataset.entity_vocabs[spec.re_type],
            random_effect_type=spec.re_type,
            feature_shard_id=spec.feature_shard_id,
            task=program.task,
            variances=re_variances.get(spec.re_type),
            active_cols=ds.active_cols if is_compact else None,
            feature_dim=ds.dim if is_compact else None,
        )
    for m in program.mf_specs:
        models[m.name] = MatrixFactorizationModel(
            row_factors=state.mf_rows[m.name],
            col_factors=state.mf_cols[m.name],
            row_effect_type=m.row_effect_type,
            col_effect_type=m.col_effect_type,
            row_keys=dataset.entity_vocabs[m.row_effect_type],
            col_keys=dataset.entity_vocabs[m.col_effect_type],
            task=program.task,
        )
    return GameModel(models=models)


def _remap_compact_rows(
    values: np.ndarray,
    model_cols: np.ndarray | None,
    target_cols: np.ndarray,
    dim: int,
) -> np.ndarray:
    """Re-key per-entity coefficient rows onto new active-column lists.

    values: [E, Km] compact (with model_cols [E, Km], sorted, pad=dim) or
    [E, dim] dense (model_cols None). target_cols: [E, Kt] sorted pad=dim.
    Returns [E, Kt]; columns absent from the source row are 0.
    """
    from photon_ml_tpu.models.game import match_active_positions

    e, kt = target_cols.shape
    if model_cols is None:  # dense source: plain per-row gather
        safe = np.minimum(target_cols, dim - 1)
        out = values[np.arange(e)[:, None], safe]
        return (out * (target_cols < dim)).astype(values.dtype)
    km = model_cols.shape[1]
    ent = np.repeat(np.arange(e, dtype=np.int64), kt)
    pos = match_active_positions(ent, target_cols.ravel(), model_cols, dim)
    vals_ext = np.concatenate(
        [values, np.zeros((e, 1), values.dtype)], axis=1
    )
    return vals_ext[ent, pos].reshape(e, kt).astype(values.dtype)


def game_model_to_state(
    program: GameTrainProgram,
    model,
    dataset: GameDataset,
    *,
    intercept_index: int | None = None,
    missing_ok: bool = False,
    re_datasets: Mapping[str, RandomEffectDataset] | None = None,
    mf_datasets: Mapping[str, "MFDataset"] | None = None,
) -> GameTrainState:
    """Inverse of :func:`state_to_game_model`: warm-start the fused step from
    a (possibly loaded-from-Avro) GameModel.

    Coefficient tables are re-aligned to the dataset's entity vocabs by key,
    so a model trained/saved against one dataset warm-starts training on
    another whose vocab ordering differs; entities absent from the model
    start at zero. The FE vector is converted into normalized space (the
    step's warm-start convention).

    missing_ok=True cold-starts (zeros / fresh factors) any coordinate the
    model lacks instead of raising — needed when a partial model warm-starts
    a program with more coordinates (reference GameEstimator.getInitialModel
    tolerates absent coordinates the same way). Requires ``re_datasets`` /
    ``mf_datasets`` for the cold-started coordinates' table shapes.
    """
    def coordinate_model(cid: str):
        try:
            return model.get(cid)
        except KeyError:
            if missing_ok:
                return None
            raise

    norm = program._fe_objective.normalization
    fe_model = coordinate_model(program.fe.feature_shard_id)
    if fe_model is None:
        fe_dim = dataset.feature_shards[program.fe.feature_shard_id].shape[1]
        dtype = dataset.feature_shards[program.fe.feature_shard_id].dtype
        fe_w = jnp.zeros((fe_dim,), dtype=dtype)
    else:
        fe_w = norm.from_model_space(
            jnp.asarray(fe_model.glm.coefficients.means), intercept_index
        )
    extra_fe: dict[str, Array] = {}
    for s in program.extra_fes:
        k = s.feature_shard_id
        m = coordinate_model(k)
        if m is None:
            extra_fe[k] = jnp.zeros(
                (dataset.feature_shards[k].shape[1],), dtype=fe_w.dtype
            )
        else:
            extra_fe[k] = program._extra_fe_objectives[k].normalization.from_model_space(
                jnp.asarray(m.glm.coefficients.means), s.intercept_index
            )

    def align(table, model_keys, vocab, coordinate: str) -> Array:
        table = np.asarray(table)
        row_of = {k: i for i, k in enumerate(np.asarray(model_keys).tolist())}
        pairs = [
            (i, row_of[key])
            for i, key in enumerate(np.asarray(vocab).tolist())
            if key in row_of
        ]
        if not pairs and len(row_of) and len(vocab):
            # a warm start that matches nothing is almost certainly the wrong
            # model/vocab pairing — degrade loudly, not to a silent cold start
            raise ValueError(
                f"warm-start model for coordinate '{coordinate}' shares no "
                f"entity keys with the dataset vocab ({len(row_of)} model "
                f"keys vs {len(vocab)} vocab keys) — wrong model directory "
                "or entity namespace?"
            )
        out = np.zeros((len(vocab), table.shape[1]), dtype=table.dtype)
        if pairs:
            vi, mi = (np.asarray(p, dtype=np.intp) for p in zip(*pairs))
            out[vi] = table[mi]
        return jnp.asarray(out)

    re_tables = {}
    for spec in program.re_specs:
        m = coordinate_model(spec.re_type)
        ds = (re_datasets or {}).get(spec.re_type)
        ds_compact = ds is not None and ds.active_cols is not None
        if m is None:
            if ds is None:
                raise ValueError(
                    f"missing_ok warm start: coordinate '{spec.re_type}' is "
                    "absent from the model AND re_datasets — cannot size the "
                    "cold-start table"
                )
            re_tables[spec.re_type] = jnp.zeros(
                (ds.num_entities, ds.table_width), dtype=fe_w.dtype
            )
            continue
        aligned = align(
            m.coefficients, m.entity_keys,
            dataset.entity_vocabs[spec.re_type], spec.re_type,
        )
        model_compact = getattr(m, "active_cols", None) is not None
        if model_compact and not ds_compact:
            # compact model warm-starting a DENSE dataset: expand each
            # entity's active columns into a dense row (the dataset being
            # dense means dim is materializable by definition)
            mc = np.asarray(align(
                m.active_cols, m.entity_keys,
                dataset.entity_vocabs[spec.re_type], spec.re_type,
            )).astype(np.int64)
            vals = np.asarray(aligned)
            e_rows = np.repeat(np.arange(vals.shape[0]), mc.shape[1])
            flat_cols = mc.ravel()
            dim = int(dataset.feature_shards[spec.feature_shard_id].shape[1])
            live = flat_cols < dim
            dense = np.zeros((vals.shape[0], dim), dtype=vals.dtype)
            dense[e_rows[live], flat_cols[live]] = vals.ravel()[live]
            aligned = jnp.asarray(dense)
        elif ds_compact or model_compact:
            # compact-layout warm starts re-key per entity from the model's
            # active columns to the dataset's (a grid re-fit on the same
            # data keeps identical lists; cross-dataset fits remap, columns
            # absent from the new list are dropped, new ones start at 0)
            model_cols = None
            if getattr(m, "active_cols", None) is not None:
                # align the model's column lists to the dataset vocab order
                model_cols = np.asarray(align(
                    m.active_cols, m.entity_keys,
                    dataset.entity_vocabs[spec.re_type], spec.re_type,
                )).astype(np.int64)
                # rows absent from the model aligned to all-zeros — make
                # them all-pads instead so nothing matches
                absent = ~np.isin(
                    np.asarray(dataset.entity_vocabs[spec.re_type]).astype(str),
                    np.asarray(m.entity_keys).astype(str),
                )
                model_cols[absent] = ds.dim
            aligned = jnp.asarray(_remap_compact_rows(
                np.asarray(aligned), model_cols,
                np.asarray(ds.active_cols, dtype=np.int64), ds.dim,
            ))
        re_norm = program._re_objectives[spec.re_type].normalization
        re_tables[spec.re_type] = (
            re_norm.from_model_space_compact(
                aligned, jnp.asarray(ds.active_cols)
            )
            if ds_compact
            else re_norm.from_model_space(aligned, spec.intercept_index)
        )
    mf_rows, mf_cols = {}, {}
    for spec in program.mf_specs:
        m = coordinate_model(spec.name)
        if m is None:
            from photon_ml_tpu.models.matrix_factorization import init_factors

            mf = (mf_datasets or {}).get(spec.name)
            if mf is None:
                raise ValueError(
                    f"missing_ok warm start: MF coordinate '{spec.name}' is "
                    "absent from the model AND mf_datasets — cannot size the "
                    "cold-start factors"
                )
            row, col = init_factors(
                mf.num_row_entities, mf.num_col_entities,
                spec.num_latent_factors, seed=spec.seed, dtype=fe_w.dtype,
            )
            row_mask, col_mask = mf.trained_masks()
            mf_rows[spec.name] = jnp.where(
                jnp.asarray(row_mask)[:, None], row, 0.0
            )
            mf_cols[spec.name] = jnp.where(
                jnp.asarray(col_mask)[:, None], col, 0.0
            )
            continue
        model_k = np.asarray(m.row_factors).shape[1]
        if model_k != spec.num_latent_factors:
            raise ValueError(
                f"warm-start MF model for coordinate '{spec.name}' has "
                f"latent dimension {model_k} but the spec configures "
                f"num_latent_factors={spec.num_latent_factors} — retrain or "
                "match the spec to the saved model"
            )
        mf_rows[spec.name] = align(
            m.row_factors, m.row_keys,
            dataset.entity_vocabs[spec.row_effect_type], spec.name,
        )
        mf_cols[spec.name] = align(
            m.col_factors, m.col_keys,
            dataset.entity_vocabs[spec.col_effect_type], spec.name,
        )
    return GameTrainState(
        fe_coefficients=fe_w, re_tables=re_tables,
        mf_rows=mf_rows, mf_cols=mf_cols, extra_fe=extra_fe,
    )


@dataclasses.dataclass
class DistributedTrainResult:
    """Result of :func:`train_distributed`.

    Iterates as ``(state, losses)`` for backward compatibility with the
    2-tuple this function used to return. ``best_state``/``best_metric``/
    ``metric_history`` are populated when validation evaluators were given
    (reference CoordinateDescent best-model tracking, :183-192, :323-356);
    otherwise ``best_state`` is None and callers should treat the final
    state as best.
    """

    state: GameTrainState
    losses: list[float]
    best_state: GameTrainState | None = None
    best_metric: float = float("nan")
    metric_history: list[dict] = dataclasses.field(default_factory=list)

    def __iter__(self):
        return iter((self.state, self.losses))


def _host_scores(scores: Array, n: int) -> np.ndarray:
    """Gather a (possibly mesh-sharded, possibly multi-process) score vector
    to the host and drop mesh-padding rows."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        scores = multihost_utils.process_allgather(scores, tiled=True)
    return np.asarray(jax.device_get(scores))[:n]


def _record_shard_spread(group: str, tree) -> None:
    """``mesh/<group>/devices`` = the fewest distinct local devices any
    placed array of the group has a shard on; ``.../max_shard_fraction`` =
    the largest share of a non-replicated array one device holds. Metadata
    only (no transfer, no sync): what tells a four-chip run that used four
    chips from one that put everything on device 0."""
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array) and x.size]
    if not leaves:
        return
    reg = default_registry()
    reg.gauge(f"mesh/{group}/devices").set(min(
        len({s.device.id for s in x.addressable_shards}) for x in leaves
    ))
    fractions = [
        max(s.data.size for s in x.addressable_shards) / x.size
        for x in leaves if not x.sharding.is_fully_replicated
    ]
    if fractions:
        reg.gauge(f"mesh/{group}/max_shard_fraction").set(max(fractions))


def _record_placed_bytes(*trees) -> None:
    """``train/placed_bytes`` += the bytes of every array ``shard_inputs``
    laid out over the mesh; ``mesh/placed_bytes/max_device`` and
    ``.../min_device`` = the most and the fewest of those bytes any one
    local device was handed (equal when the chips share every array; the
    whole of it on one and nothing on the rest when they do not). Metadata
    only: no transfer, no sync."""
    leaves = [x for x in jax.tree_util.tree_leaves(trees)
              if isinstance(x, jax.Array)]
    reg = default_registry()
    reg.counter("train/placed_bytes").inc(sum(int(x.nbytes) for x in leaves))
    held: dict[int, int] = {}
    for x in leaves:
        share = int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
        for device in x.sharding.addressable_devices:
            held[device.id] = held.get(device.id, 0) + share
    if held:
        reg.gauge("mesh/placed_bytes/max_device").set(max(held.values()))
        reg.gauge("mesh/placed_bytes/min_device").set(min(held.values()))


# -- the spans and counters of a fit ------------------------------------------
# train_distributed and train_partitioned run the same loop; these helpers
# are where its span names and counters live, so the two cannot drift. Every
# span is host-side only (telemetry/tracing.py): nothing here enters a
# compiled program, and none adds, skips or reorders a collective.


def _fit_span(num_iterations: int, mesh: Mesh | None):
    """``train/fit`` round one whole fit; bumps ``train/fits``, whose new
    value is the ``fit`` identifier every span inside inherits."""
    fits = default_registry().counter("train/fits")
    fits.inc()
    shape = "none" if mesh is None else "x".join(
        str(size) for size in mesh.shape.values())
    return span("train/fit", fit=fits.value, sweeps=num_iterations, mesh=shape)


def _step_and_wait(program: GameTrainProgram, data, buckets,
                   state: GameTrainState, *, sweep: int, num_iterations: int,
                   schedulers, rows: int, check_finite: bool, checkpointer,
                   what: str):
    """One sweep through the fused program, then the host's wait for its
    loss: ``train/step`` ends when the work is ENQUEUED, ``train/loss_wait``
    is the host blocked on the device. Bumps ``train/sweeps`` and
    ``train/rows`` (rows trained, the work as a count), and the
    ``solver/<name>`` counters from the fused sweep's solver counts
    (:meth:`SweepCounts.counters`: trials, outer trips, rows paid and
    wanted, the lanes by stop reason; by family and by coordinate), read
    under ``train/solver_counts`` once the loss has arrived (the device is
    done by then: no second wait); a run journal, where the installed
    program ledger has one (``--telemetry-dir``), gets the table by bucket
    as one ``lane_counts`` row a sweep, and nothing else does. Returns
    (state, loss as a float); a non-finite loss raises before any
    checkpoint could overwrite the last finite state with NaNs (CD-path
    DivergenceError contract, coordinate_descent.py)."""
    with span("train/step"):
        if schedulers:
            state, loss = program.step_scheduled(
                data, buckets, state, schedulers=schedulers,
                final_sweep=(sweep + 1 == num_iterations),
            )
        else:
            state, loss = program.step(data, buckets, state)
    with span("train/loss_wait"):
        loss = float(loss)
    if check_finite and not np.isfinite(loss):
        from photon_ml_tpu.io.checkpoint import DivergenceError

        raise DivergenceError(
            f"{what} training step produced non-finite loss "
            f"{loss} at sweep {sweep}"
            + (
                f"; last good checkpoint: step "
                f"{checkpointer.latest_step()} in {checkpointer.directory}"
                if checkpointer is not None else ""
            )
        )
    registry = default_registry()
    registry.counter("train/sweeps").inc()
    registry.counter("train/rows").inc(rows)
    with span("train/solver_counts"):
        counts = program.take_solver_counts()
    if counts is not None:
        for name, value in counts.counters().items():
            registry.counter(f"solver/{name}").inc(value)
        ledger = current_ledger()
        if ledger is not None and ledger.journal is not None:
            ledger.journal.record(LANE_COUNTS_ROW, sweep=sweep + 1,
                                  buckets=counts.table())
    return state, loss


def _checkpoint_if_due(checkpointer, checkpoint_every: int, sweep: int,
                       num_iterations: int, commit) -> None:
    """Run ``commit()`` under ``train/checkpoint`` after every
    ``checkpoint_every``-th sweep and after the last. Every process calls
    it: ``commit`` holds the gathers (collectives) and the commit helper's
    barriers; only process 0 writes (lint check 10)."""
    if checkpointer is None or not (
        (sweep + 1) % max(1, checkpoint_every) == 0
        or sweep + 1 == num_iterations
    ):
        return
    with span("train/checkpoint"):
        commit()


def train_distributed(
    program: GameTrainProgram,
    dataset: GameDataset,
    re_datasets: Mapping[str, RandomEffectDataset],
    *,
    mf_datasets: Mapping[str, "MFDataset"] | None = None,
    mesh: Mesh | None = None,
    num_iterations: int = 1,
    fe_feature_sharded: bool = False,
    state: GameTrainState | None = None,
    checkpointer=None,
    checkpoint_every: int = 1,
    resume: bool = True,
    put_fn=None,
    validation_dataset: GameDataset | None = None,
    validation_evaluators: Sequence = (),
    validation_eval_data=None,
    training_evaluator=None,
    training_eval_data=None,
    down_sampling_seed: int = 0,
    check_finite: bool = True,
    on_sweep=None,
) -> DistributedTrainResult:
    """Run ``num_iterations`` fused CD sweeps, optionally mesh-sharded.

    on_sweep: optional observer ``(sweep_done, num_iterations, loss)``
    called at the end of every sweep (ISSUE 12: the estimator wires the
    journal heartbeat through it). Observe-only — it runs after all of the
    sweep's collectives, on every rank, and must never gate one.

    put_fn: placement function forwarded to ``shard_inputs``. Defaults to
    ``jax.device_put`` single-process and to ``multihost.global_put`` when
    this is a multi-process run (each process feeds its addressable shards),
    so the same call works on a laptop and on a pod.

    checkpointer: optional ``io.checkpoint.TrainingCheckpointer``. Saves the
    full ``GameTrainState`` (host-gathered) every ``checkpoint_every`` sweeps;
    with ``resume=True`` the latest checkpoint short-circuits completed
    sweeps. Restored arrays are re-laid-out over the mesh by the normal
    ``shard_inputs`` path, so a run checkpointed on one topology restores
    onto another (elastic recovery — absent in the reference, SURVEY.md §5).

    Validation (reference CoordinateDescent.scala:183-192, 291-356): when
    ``validation_dataset`` + ``validation_evaluators`` (+
    ``validation_eval_data``, an evaluation.EvaluationData over the
    *unpadded* validation split) are given, each sweep scores the validation
    split through the program's jitted scoring program over the same mesh,
    evaluates every evaluator host-side, and tracks the best state by the
    FIRST evaluator's ``better_than`` direction. ``training_evaluator`` +
    ``training_eval_data`` add a per-sweep ``train:<name>`` metric.

    Datasets whose sample counts don't divide the mesh "data" axis are
    padded with zero-weight rows automatically (pad_game_dataset).

    Returns a :class:`DistributedTrainResult` (unpacks as
    ``(final_state, losses)``).
    """
    with _fit_span(num_iterations, mesh):
        start_sweep = 0
        prior_losses: list[float] = []
        best_state: GameTrainState | None = None
        best_sweep: int | None = None  # of this call's sweeps
        best_metric = float("nan")
        history: list[dict] = []
        # An explicit caller-supplied state takes precedence over resume: passing
        # both a warm start and a stale checkpoint must not silently ignore the
        # warm start.
        if checkpointer is not None and resume and state is None:
            with span("train/restore"):
                ckpt = checkpointer.restore()
                if ckpt is not None:
                    if "fe_coefficients" not in ckpt.arrays:
                        # e.g. a CD-path checkpoint (model/... keys) in the same dir
                        raise ValueError(
                            f"checkpoint at {checkpointer.directory} is not a "
                            "distributed-training checkpoint (no 'fe_coefficients' "
                            f"array; found keys like {sorted(ckpt.arrays)[:3]}). Pass "
                            "resume=False or use a fresh checkpoint directory."
                        )
                    def by_prefix(prefix, arrays=None):
                        arrays = ckpt.arrays if arrays is None else arrays
                        return {
                            k[len(prefix):]: jnp.asarray(v)
                            for k, v in arrays.items()
                            if k.startswith(prefix) and "/" not in k[len(prefix):]
                        }
                    state = GameTrainState(
                        fe_coefficients=jnp.asarray(ckpt.arrays["fe_coefficients"]),
                        re_tables=by_prefix("re_tables/"),
                        mf_rows=by_prefix("mf_rows/"),
                        mf_cols=by_prefix("mf_cols/"),
                        extra_fe=by_prefix("extra_fe/"),
                    )
                    expected = {
                        "re_tables": {s.re_type for s in program.re_specs},
                        "mf_rows": {m.name for m in program.mf_specs},
                        "mf_cols": {m.name for m in program.mf_specs},
                        "extra_fe": {s.feature_shard_id for s in program.extra_fes},
                    }
                    found = {
                        "re_tables": set(state.re_tables),
                        "mf_rows": set(state.mf_rows),
                        "mf_cols": set(state.mf_cols),
                        "extra_fe": set(state.extra_fe),
                    }
                    if expected != found:
                        raise ValueError(
                            f"checkpoint at {checkpointer.directory} is incompatible "
                            f"with the program's coordinate specs: checkpoint has "
                            f"{found}, program expects {expected}. Pass resume=False "
                            "or use a fresh checkpoint directory."
                        )
                    if "best/fe_coefficients" in ckpt.arrays:
                        best_state = GameTrainState(
                            fe_coefficients=jnp.asarray(ckpt.arrays["best/fe_coefficients"]),
                            re_tables=by_prefix("best/re_tables/"),
                            mf_rows=by_prefix("best/mf_rows/"),
                            mf_cols=by_prefix("best/mf_cols/"),
                            extra_fe=by_prefix("best/extra_fe/"),
                        )
                    best_metric = float(ckpt.meta.get("best_metric", float("nan")))
                    # journaled restore evidence (resilience/checkpoint_restores)
                    from photon_ml_tpu.telemetry import resilience_counters

                    resilience_counters.record_checkpoint_restore()
                    start_sweep = min(int(ckpt.step), num_iterations)
                    prior_losses = [float(x) for x in ckpt.meta.get("losses", [])][:start_sweep]
                    history = [
                        h for h in ckpt.meta.get("metric_history", [])
                        if int(h.get("iteration", 0)) < start_sweep
                    ]

        n_train = dataset.num_samples
        n_val = validation_dataset.num_samples if validation_dataset is not None else 0
        with span("train/pad"):
            if mesh is not None:
                from photon_ml_tpu.data.game_data import pad_game_dataset

                data_axis = int(mesh.shape["data"])
                # buckets reference sample rows by index, which appending
                # zero-weight rows leaves intact — pad AFTER the caller built
                # re_datasets
                dataset, n_train = pad_game_dataset(dataset, data_axis)
                if validation_dataset is not None:
                    validation_dataset, n_val = pad_game_dataset(
                        validation_dataset, data_axis
                    )

        with span("train/prepare_inputs"):
            data, buckets = program.prepare_inputs(
                dataset, re_datasets, mf_datasets)
        with span("train/init_state"):
            if state is None:
                state = program.init_state(dataset, re_datasets, mf_datasets)

        # probe/rescue lane scheduling (algorithm/lane_scheduler.py): opt-in per
        # RE spec via OptimizerConfig.scheduler. Multi-process runs use the
        # collective-safe SPMD mode (rank-local compaction into a fixed
        # [num_ranks * R] rescue-block signature, per-lane flags through tiled
        # allgathers — collectives on every rank); single-process keeps the
        # host mode unchanged. No more multi-process fallback.
        schedulers = None
        scheduled_specs = [
            s for s in program.re_specs if s.optimizer.scheduler is not None
        ]
        if scheduled_specs:
            if jax.process_count() > 1 and mesh is None:
                logger.warning(
                    "lane scheduler configured on %s but this multi-process run "
                    "has no mesh — falling back to the unscheduled fused step; "
                    "pass mesh= (the SPMD scheduler assembles rescue blocks "
                    "over it)",
                    [s.re_type for s in scheduled_specs],
                )
            else:
                from photon_ml_tpu.algorithm.lane_scheduler import make_schedulers

                schedulers = make_schedulers(scheduled_specs, mesh=mesh)

        # per-sweep FE down-sampling multipliers (stable-id splitmix64, identical
        # to the CD path's FixedEffectCoordinate seed rotation); keyed per FE
        # coordinate ("" = primary)
        samplers: dict[str, object] = {}
        from photon_ml_tpu.sampling import down_sampler_for_task

        for key, fe_spec in [("", program.fe)] + [
            (s.feature_shard_id, s) for s in program.extra_fes
        ]:
            if fe_spec.down_sampling_rate < 1.0:
                samplers[key] = down_sampler_for_task(
                    program.task, fe_spec.down_sampling_rate
                )
        if samplers:
            samp_labels = dataset.host_array("labels")
            samp_weights = dataset.host_array("weights")
            samp_uids = np.asarray(dataset.unique_ids)
            samp_dtype = np.asarray(samp_weights).dtype

        def sweep_multiplier(sampler, sweep: int):
            new_w = sampler.down_sample_weights(
                samp_labels, samp_weights, samp_uids,
                seed=down_sampling_seed + sweep,
            )
            mult = np.where(
                samp_weights > 0, new_w / np.where(samp_weights > 0, samp_weights, 1.0), 0.0
            ).astype(samp_dtype)
            if mesh is not None:
                put = put_fn if put_fn is not None else jax.device_put
                return put(jnp.asarray(mult), NamedSharding(mesh, P("data")))
            return jnp.asarray(mult)

        val_data = None
        evaluators = list(validation_evaluators)
        if validation_dataset is not None and evaluators and validation_eval_data is not None:
            with span("train/prepare_validation"):
                val_data = program.prepare_scoring_inputs(
                    validation_dataset, re_datasets
                )

        # true entity counts, to slice off any mesh-padding rows on the way out
        table_sizes = {
            "re_tables": {s.re_type: re_datasets[s.re_type].num_entities
                          for s in program.re_specs},
            "mf_rows": {m.name: (mf_datasets or {})[m.name].num_row_entities
                        for m in program.mf_specs},
            "mf_cols": {m.name: (mf_datasets or {})[m.name].num_col_entities
                        for m in program.mf_specs},
        }

        def unpadded(state_: GameTrainState) -> GameTrainState:
            def trim(tables, sizes):
                return {k: v[: sizes[k]] for k, v in tables.items()}
            return GameTrainState(
                fe_coefficients=state_.fe_coefficients,
                re_tables=trim(state_.re_tables, table_sizes["re_tables"]),
                mf_rows=trim(state_.mf_rows, table_sizes["mf_rows"]),
                mf_cols=trim(state_.mf_cols, table_sizes["mf_cols"]),
                extra_fe=dict(state_.extra_fe),
            )
        if mesh is not None:
            if put_fn is None:
                from photon_ml_tpu.parallel.multihost import default_put

                put_fn = default_put()
            with span("train/shard_inputs"):
                data, buckets, state = program.shard_inputs(
                    mesh, data, buckets, state,
                    fe_feature_sharded=fe_feature_sharded, put_fn=put_fn,
                )
            _record_shard_spread("sample_arrays", data)
            _record_shard_spread("entity_arrays", buckets)
            _record_placed_bytes(data, buckets, state)
            if val_data is not None:
                with span("train/shard_validation"):
                    val_data = program.shard_scoring_inputs(
                        mesh, val_data, fe_feature_sharded=fe_feature_sharded,
                        put_fn=put_fn,
                    )
        else:
            # no mesh to lay host arrays out over: commit them to the default
            # device once, not on every sweep's call of the step
            data, val_data, buckets = jax.device_put((data, val_data, buckets))

        if val_data is not None and mesh is not None:
            # device twins of the evaluators (evaluation/sharded.py): consts
            # (labels/weights/query codes) are padded to the mesh length and
            # placed sharded over "data" alongside the scores they reduce with.
            # Prepared AFTER put_fn resolution so multi-process runs place
            # through global_put like every other sharded input. mesh=None runs
            # keep the exact host evaluators — there is no giant-n funnel to
            # avoid, and the device AUC is a histogram approximation.
            from photon_ml_tpu.evaluation.sharded import (
                mesh_data_placer,
                prepare_device_evaluators,
            )

            with span("train/device_evaluators"):
                device_evals = prepare_device_evaluators(
                    evaluators, validation_eval_data,
                    n_pad=validation_dataset.num_samples,
                    place=mesh_data_placer(mesh, put_fn),
                )
        else:
            device_evals = [None] * len(evaluators)

        def to_host(v):
            """Host copy of a (possibly multi-process sharded) array. The
            allgather is a COLLECTIVE — every process must call it, even those
            that discard the result (rank-0-only writes)."""
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                return np.asarray(multihost_utils.process_allgather(v, tiled=True))
            return jax.device_get(v)

        def state_arrays(state_: GameTrainState, prefix: str = "") -> dict:
            clean = unpadded(state_)
            arrays = {prefix + "fe_coefficients": to_host(clean.fe_coefficients)}
            for sub, tables in (
                ("re_tables/", clean.re_tables),
                ("mf_rows/", clean.mf_rows),
                ("mf_cols/", clean.mf_cols),
                ("extra_fe/", clean.extra_fe),
            ):
                for k, v in tables.items():
                    arrays[prefix + sub + k] = to_host(v)
            return arrays

        def commit():
            # every process participates in the gathers (collectives); the
            # commit helper gates the write to process 0 (the shared
            # checkpoint directory convention; lint check 10)
            from photon_ml_tpu.io.checkpoint import commit_checkpoint

            arrays = state_arrays(state)
            if best_state is not None:
                arrays.update(state_arrays(best_state, prefix="best/"))
            commit_checkpoint(
                checkpointer, sweep + 1, arrays,
                {"losses": losses, "metric_history": history,
                 "best_metric": best_metric},
            )

        losses = list(prior_losses)
        for sweep in range(start_sweep, num_iterations):
            # the sweep's number as on_sweep reports it (1-based)
            with span("train/sweep", sweep=sweep + 1):
                if samplers:
                    with span("train/down_sample"):
                        for key, sampler in samplers.items():
                            mult = sweep_multiplier(sampler, sweep)
                            if key == "":
                                data["fe_weight_multiplier"] = mult
                            else:
                                data.setdefault(
                                    "extra_fe_weight_multipliers", {}
                                )[key] = mult
                state, loss = _step_and_wait(
                    program, data, buckets, state, sweep=sweep,
                    num_iterations=num_iterations, schedulers=schedulers,
                    rows=n_train, check_finite=check_finite,
                    checkpointer=checkpointer, what="fused",
                )
                losses.append(loss)

                metrics: dict[str, float] = {}
                if training_evaluator is not None and training_eval_data is not None:
                    with span("train/train_metric"):
                        train_scores = _host_scores(
                            program.score(data, state), n_train)
                        metrics[f"train:{training_evaluator.name}"] = float(
                            training_evaluator.evaluate(
                                train_scores, training_eval_data)
                        )
                if val_data is not None:
                    # device-side evaluation (evaluation/sharded.py): on a
                    # mesh, metrics reduce ON it from the still-sharded score
                    # vector; only scalars cross to the host — the giant-n
                    # validation pass never funnels [n] rows through one core
                    # (the reference's executor-side Evaluator/MultiEvaluator,
                    # Evaluator.scala:39-49). Evaluators without a device form
                    # (custom types), and every evaluator on mesh=None runs,
                    # take the single host gather.
                    from photon_ml_tpu.evaluation.sharded import (
                        evaluate_prepared,
                    )

                    with span("train/validate"):
                        with span("train/validate/score"):
                            val_scores = program.score(val_data, state)
                        # blocks on the scalars: the host waiting for the
                        # device, like train/loss_wait
                        with span("train/validate/evaluate"):
                            values = evaluate_prepared(
                                evaluators, device_evals, val_scores,
                                validation_eval_data,
                                lambda: _host_scores(val_scores, n_val),
                            )
                    for i, (ev, v) in enumerate(zip(evaluators, values)):
                        metrics[f"validate:{ev.name}"] = v
                        if i == 0 and (
                            best_state is None or ev.better_than(v, best_metric)
                        ):
                            # without the margins: a best state kept
                            # whole would pin a set of [n] vectors that
                            # result_state only throws away
                            best_state = state.replace(scores={})
                            best_metric, best_sweep = v, sweep
                if metrics:
                    history.append({"iteration": sweep,
                                    "coordinate": "fused_sweep", **metrics})

                _checkpoint_if_due(checkpointer, checkpoint_every, sweep,
                                   num_iterations, commit)

                if on_sweep is not None:
                    # time in the caller's observer is not the program's
                    with span("train/on_sweep"):
                        on_sweep(sweep + 1, num_iterations, loss)

        def result_state(state_: GameTrainState) -> GameTrainState:
            clean = unpadded(state_)
            if jax.process_count() > 1:
                # downstream (model conversion, Avro persistence) materializes
                # host arrays; a multi-process sharded state is not addressable,
                # so hand back fully-gathered host-backed arrays
                clean = GameTrainState(
                    fe_coefficients=jnp.asarray(to_host(clean.fe_coefficients)),
                    re_tables={k: jnp.asarray(to_host(v))
                               for k, v in clean.re_tables.items()},
                    mf_rows={k: jnp.asarray(to_host(v))
                             for k, v in clean.mf_rows.items()},
                    mf_cols={k: jnp.asarray(to_host(v))
                             for k, v in clean.mf_cols.items()},
                    extra_fe={k: jnp.asarray(to_host(v))
                              for k, v in clean.extra_fe.items()},
                )
            return clean

        with span("train/result_state"):
            return DistributedTrainResult(
                state=result_state(state),
                losses=losses,
                # best == final collapses to None ("treat final as best") so
                # callers never convert/variance-compute the same state twice
                best_state=(
                    None if best_state is None
                    or best_sweep == num_iterations - 1
                    else result_state(best_state)
                ),
                best_metric=best_metric,
                metric_history=history,
            )


# ---------------------------------------------------------------------------
# Partitioned training: each rank feeds only its local ingest block
# ---------------------------------------------------------------------------


def _partitioned_guards(program: GameTrainProgram, prepared: dict) -> None:
    """The partitioned surface: dense or sparse (incl. hybrid) primary FE,
    dense extra FEs, and IDENTITY random effects. Everything else still
    trains through the full-read path — fail loudly, never silently
    mis-shard."""
    if program.mf_specs:
        raise ValueError(
            "partitioned training does not support matrix-factorization "
            "coordinates; use the full-read path"
        )
    for data, buckets in prepared.values():
        if "re_sparse" in data:
            raise ValueError(
                "partitioned training does not support sparse RANDOM-"
                "EFFECT shards (the primary fixed effect may be sparse); "
                "use the full-read path"
            )
        if "__projections__" in buckets:
            raise ValueError(
                "partitioned training does not support projected random "
                "effects; use the full-read path"
            )


def _refuse_ell_tiers(sb) -> None:
    """A mesh lays the ELL view out as ONE [n, L] block over "data"; a batch
    whose view is in width tiers would lose them here, so it is refused."""
    if sb.ell_tiers:
        raise ValueError(
            f"the sparse FE batch's ELL view has {1 + len(sb.ell_tiers)} "
            "width tiers; a mesh takes one agreed width: build it with "
            "from_shard(..., ell=shard.one_ell_width()) (prepare_inputs "
            "does), or read through io/partitioned_reader.read_partitioned, "
            "which sets SparseShard.ell_width"
        )


def _assemble_sparse_fe(prepared: dict, ranks, mesh: Mesh,
                        num_ranks: int, put) -> "SparseLabeledPointBatch":
    """Assemble per-rank local sparse-FE batches into ONE mesh-sharded
    global batch (the sparse twin of the dense ``asm`` closure in
    prepare_partitioned_inputs).

    The per-sample arrays (labels/offsets/weights, the [n, L] ELL tail,
    the [n, k_hot] hybrid head) are per-rank ROW blocks and assemble like
    any dense field; the hot column ids are model-sized, must be IDENTICAL
    on every rank (io/partitioned_reader.py's global hot ranking
    guarantees it), and replicate. The flat COO overflow tail is padded to
    one agreed per-rank length (SparseShard.flat_block_nnz, also from the
    reader's layout exchange; pads carry value 0 / col 0 / the rank's last
    real row id) and assembles over "data" with each rank's row ids
    shifted into the global sample axis — the concatenation stays
    nondecreasing, preserving the flat segment-sum's sorted promise. An
    un-exchanged local batch (mismatched shapes) fails here loudly.
    """
    from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
    from photon_ml_tpu.parallel.multihost import assemble_partitioned

    sbs = {r: prepared[r][0]["fe_sparse_batch"] for r in ranks}
    first = sbs[ranks[0]]
    for r, sb in sbs.items():
        if not sb.has_ell_view:
            raise ValueError(
                f"rank {r}: the sparse FE batch has no ELL view; "
                "partitioned sparse training rides the fixed-width ELL "
                "layout (read through read_partitioned)"
            )
        _refuse_ell_tiers(sb)
        if sb.dim != first.dim or (
            sb.ell_vals.shape != first.ell_vals.shape
        ) or sb.nnz != first.nnz:
            raise ValueError(
                f"rank {r}: sparse FE batch shapes disagree across ranks "
                f"(dim {sb.dim} vs {first.dim}, ELL "
                f"{sb.ell_vals.shape} vs {first.ell_vals.shape}, flat "
                f"{sb.nnz} vs {first.nnz}) — ingest through "
                "io/partitioned_reader.read_partitioned, which agrees the "
                "global layout"
            )
        if sb.has_hybrid_view != first.has_hybrid_view or (
            sb.has_hybrid_view
            and not bool(
                jnp.array_equal(sb.hot_col_ids, first.hot_col_ids)
            )
        ):
            raise ValueError(
                f"rank {r}: hybrid hot heads disagree across ranks — the "
                "hot ranking must be resolved globally "
                "(read_partitioned's hybrid_hot exchange)"
            )

    vec_spec = P("data")
    row2 = P("data", None)

    def asm(field, spec):
        blocks = {r: np.asarray(getattr(sbs[r], field)) for r in ranks}
        return assemble_partitioned(blocks, mesh, spec, num_ranks)

    # the fixed-length flat COO overflow tail (SparseShard.flat_block_nnz,
    # already padded per rank by from_shard): row ids shift by the rank's
    # base row into the global sample axis — each rank's block is
    # row-major and its pads carry the rank's last real row, so the
    # concatenation stays nondecreasing (the flat segment-sum's sorted
    # promise); pad values are 0, bitwise inert in every per-row sum
    n_rank = int(np.asarray(first.labels).shape[0])

    def asm_rows(r):
        return (
            np.asarray(sbs[r].row_ids, np.int64) + r * n_rank
        ).astype(np.int32)

    extra = {}
    if first.has_hybrid_view:
        extra = dict(
            hot_vals=asm("hot_vals", row2),
            hot_col_ids=put(
                np.asarray(first.hot_col_ids), NamedSharding(mesh, P())
            ),
        )
    return SparseLabeledPointBatch(
        values=asm("values", vec_spec),
        col_indices=asm("col_indices", vec_spec),
        row_ids=assemble_partitioned(
            {r: asm_rows(r) for r in ranks}, mesh, vec_spec, num_ranks
        ),
        labels=asm("labels", vec_spec),
        offsets=asm("offsets", vec_spec),
        weights=asm("weights", vec_spec),
        dim=int(first.dim),
        ell_vals=asm("ell_vals", row2),
        ell_cols=asm("ell_cols", row2),
        **extra,
    )


def prepare_partitioned_inputs(
    program: GameTrainProgram,
    parts: "Mapping[int, tuple[GameDataset, Mapping[str, RandomEffectDataset]]]",
    mesh: Mesh,
    num_ranks: int,
    *,
    fe_feature_sharded: bool = False,
    state: GameTrainState | None = None,
):
    """(data, buckets, state) for :meth:`GameTrainProgram.step` where the
    global sample/entity axes are assembled from per-rank LOCAL blocks
    (io/partitioned_reader.py layout: ``num_ranks`` equal blocks, padding
    rows/lanes inert) via ``multihost.assemble_partitioned`` — no host
    ever materializes a global-size array.

    parts: rank -> (local padded GameDataset, rank-local RE datasets from
    ``build_random_effect_dataset_partitioned``). Multi-process callers
    pass only their own rank; single-process simulations (tests, virtual
    ranks) pass all of them. The model state is replicated/entity-sharded
    exactly as ``shard_inputs`` lays it out.
    """
    from photon_ml_tpu.parallel.multihost import (
        assemble_partitioned,
        default_put,
    )

    ranks = sorted(parts)
    prepared = {
        r: program.prepare_inputs(ds, res, None) for r, (ds, res) in parts.items()
    }
    _partitioned_guards(program, prepared)

    vec = P("data")
    row2 = P("data", None)
    fe_fspec = P("data", "model") if fe_feature_sharded else row2
    put = default_put()

    def asm(getter, spec):
        blocks = {r: np.asarray(getter(prepared[r][0])) for r in ranks}
        return assemble_partitioned(blocks, mesh, spec, num_ranks)

    data = {
        "labels": asm(lambda d: d["labels"], vec),
        "offsets": asm(lambda d: d["offsets"], vec),
        "weights": asm(lambda d: d["weights"], vec),
        "features": {
            k: asm(
                lambda d, _k=k: d["features"][_k],
                fe_fspec if k == program.fe.feature_shard_id else row2,
            )
            for k in prepared[ranks[0]][0]["features"]
        },
        "entity_idx": {
            t: asm(lambda d, _t=t: d["entity_idx"][_t], vec)
            for t in prepared[ranks[0]][0]["entity_idx"]
        },
    }
    if "fe_sparse_batch" in prepared[ranks[0]][0]:
        # sparse (possibly hybrid) primary FE: per-rank row blocks of the
        # hot head / ELL tail assemble like dense fields; the reader's
        # global layout exchange guarantees the shapes agree
        data["fe_sparse_batch"] = _assemble_sparse_fe(
            prepared, ranks, mesh, num_ranks, put
        )

    def asm_b(key, i, field, spec):
        blocks = {
            r: np.asarray(prepared[r][1][key][i][field]) for r in ranks
        }
        return assemble_partitioned(blocks, mesh, spec, num_ranks)

    buckets: dict = {"__mf__": {}}
    for key, bucket_list in prepared[ranks[0]][1].items():
        if key == "__mf__":  # guarded empty (no MF specs)
            continue
        counts = {len(prepared[r][1][key]) for r in ranks}
        if len(counts) != 1:
            raise ValueError(
                f"random-effect coordinate '{key}': ranks disagree on the "
                f"bucket list ({counts}); build the RE views with "
                "build_random_effect_dataset_partitioned"
            )
        buckets[key] = [
            {
                "labels": asm_b(key, i, "labels", row2),
                "weights": asm_b(key, i, "weights", row2),
                "sample_rows": asm_b(key, i, "sample_rows", row2),
                "entity_rows": asm_b(key, i, "entity_rows", vec),
                "features": asm_b(key, i, "features", P("data", None, None)),
            }
            for i in range(len(bucket_list))
        ]

    # model state: identical on every rank (zeros or a shared warm start)
    # — replicate / entity-shard exactly as shard_inputs does
    r0 = ranks[0]
    if state is None:
        state = program.init_state(parts[r0][0], parts[r0][1], None)
    rep = NamedSharding(mesh, P())
    ent2 = NamedSharding(mesh, P("data", None))
    data_axis = int(mesh.shape["data"])

    def put_table(v):
        pad = (-int(v.shape[0])) % data_axis
        if pad:
            v = np.concatenate(
                [np.asarray(v),
                 np.zeros((pad,) + tuple(v.shape[1:]), np.asarray(v).dtype)]
            )
        return put(v, ent2)

    fe_sharding = NamedSharding(mesh, P("model")) if fe_feature_sharded else rep
    state = GameTrainState(
        fe_coefficients=put(np.asarray(state.fe_coefficients), fe_sharding),
        re_tables={k: put_table(v) for k, v in state.re_tables.items()},
        mf_rows={},
        mf_cols={},
        extra_fe={k: put(np.asarray(v), rep) for k, v in state.extra_fe.items()},
    )
    return data, buckets, state


def _partition_fingerprint(program: GameTrainProgram, parts,
                           num_ranks: int) -> dict:
    """The agreement a partitioned checkpoint is only valid under: rank
    geometry (the per-rank block a restored table row maps to), the
    agreed GLOBAL sparse layout (``io/partitioned_reader.
    _resolve_global_sparse_layout``'s hybrid hot head / ELL width / flat
    overflow — per-partition statistics must pin the global decision they
    were trained with, arXiv:2004.02414), and the coordinate structure.
    A resume under a different rank count or layout agreement fails fast
    attributed (train_partitioned's restore check) instead of silently
    training on mis-mapped rows. Computed from any single rank's LOCAL
    part — these are exactly the globally-agreed quantities, identical on
    every rank by the reader's exchange."""
    import hashlib

    r0 = sorted(parts)[0]
    ds, res = parts[r0]
    fe_shard = ds.feature_shards[program.fe.feature_shard_id]
    if isinstance(fe_shard, SparseShard):
        policy = fe_shard.hybrid_policy
        hot = tuple(policy.hot_ids) if policy is not None and policy.hot_ids else ()
        layout = {
            "dim": int(fe_shard.feature_dim),
            "ell_width": (
                None if fe_shard.ell_width is None else int(fe_shard.ell_width)
            ),
            "flat_block_nnz": (
                None if fe_shard.flat_block_nnz is None
                else int(fe_shard.flat_block_nnz)
            ),
            "k_hot": len(hot),
            "hot_hash": hashlib.sha256(
                np.asarray(hot, np.int64).tobytes()
            ).hexdigest()[:16],
        }
    else:
        layout = {"dim": int(np.asarray(fe_shard).shape[1])}
    return {
        "num_ranks": int(num_ranks),
        "block_rows": int(ds.num_samples),
        "fe_shard": program.fe.feature_shard_id,
        "fe_layout": layout,
        "re_entities": {
            s.re_type: int(res[s.re_type].num_entities)
            for s in program.re_specs
        },
        "extra_fe": sorted(s.feature_shard_id for s in program.extra_fes),
    }


def train_partitioned(
    program: GameTrainProgram,
    parts: "Mapping[int, tuple[GameDataset, Mapping[str, RandomEffectDataset]]]",
    mesh: Mesh,
    num_ranks: int,
    *,
    num_iterations: int = 1,
    state: GameTrainState | None = None,
    fe_feature_sharded: bool = False,
    check_finite: bool = True,
    schedulers: "Mapping[str, object] | None" = None,
    checkpointer=None,
    checkpoint_every: int = 1,
    resume: bool = True,
    exchange=None,
    resume_step: "int | None" = None,
) -> DistributedTrainResult:
    """``train_distributed`` over partitioned ingest blocks: each rank
    contributes only its local slice of the data/bucket arrays (every rank
    decoded ~1/P of the input; see io/partitioned_reader.py), the fused
    step runs unchanged, and only the MODEL-sized final state is host-
    gathered. Scope: dense or sparse/hybrid primary FE + dense IDENTITY
    REs, no validation riders (score + evaluate partitioned via
    parallel/scoring.py).

    schedulers: optional re_type -> algorithm.lane_scheduler.LaneScheduler
    (see ``make_schedulers`` — SPMD mode on multi-process runs): sweeps
    then run through ``step_scheduled``, composing probe/rescue lane
    scheduling with partitioned ingestion. None keeps the one-jit step.

    checkpointer: optional ``io.checkpoint.TrainingCheckpointer`` —
    crash-safe resume for the production configuration. Every
    ``checkpoint_every`` sweeps the model-sized state is host-gathered on
    EVERY rank (collectives) and committed through
    ``io.checkpoint.commit_checkpoint``: rank 0 writes, and — when
    ``exchange`` (the run's ``MetadataExchange``) is attached — the commit
    is gated by its rank-attributed deadline barriers, so a checkpoint
    exists only for sweeps every rank completed. ``meta.json`` carries a
    fingerprint of the partition plan + the agreed global sparse layout
    (``_partition_fingerprint``): a resume under a different rank count or
    layout agreement FAILS FAST with the differing fields named instead of
    silently training restored rows against a re-mapped block. An
    explicitly-passed ``state`` (warm start) takes precedence over resume,
    as in ``train_distributed``. ``checkpointer=None`` is bitwise the
    un-checkpointed path.

    resume_step: pin the restore to ONE published step (ISSUE 15's
    coordinated rollback: every rank must restore the step rank 0
    resolved and published, never its own local newest) — a missing pinned
    step fails fast instead of silently resolving to a different one; 0
    means "restart from scratch" (the rollback found no checkpoint).
    None (default) keeps the newest-intact-step behavior."""
    with _fit_span(num_iterations, mesh):
        fingerprint = None
        start_sweep = 0
        prior_losses: list[float] = []
        if resume_step == 0:
            resume = False
        if checkpointer is not None:
            freezing = sorted(
                k for k, sch in (schedulers or {}).items()
                if getattr(getattr(sch, "config", None), "freezes", False)
            )
            if freezing:
                # cross-sweep active sets (frozen_rows + carried values) are
                # scheduler-internal state the checkpoint does not capture: a
                # restart would re-probe every lane and diverge from the
                # uninterrupted run, breaking the resume-exactness contract
                raise ValueError(
                    "partitioned checkpointing cannot yet resume cross-sweep "
                    f"active-set state (freeze tolerances set on {freezing}); "
                    "drop scheduler.freeze.tolerance/scheduler.freeze.gradient "
                    "(probe/rescue scheduling resumes exactly) or disable "
                    "checkpointing for this run"
                )
            fingerprint = _partition_fingerprint(program, parts, num_ranks)
            if resume and state is None:
                with span("train/restore"):
                    ckpt = checkpointer.restore(
                        step=resume_step if resume_step else None
                    )
                if ckpt is not None:
                    from photon_ml_tpu.io.checkpoint import fingerprint_mismatch

                    mismatch = fingerprint_mismatch(
                        ckpt.meta.get("partition_fingerprint"), fingerprint
                    )
                    if mismatch is not None:
                        raise ValueError(
                            f"partitioned checkpoint at {checkpointer.directory}"
                            f" was written under a different partition "
                            f"fingerprint ({mismatch}) — a restored table row "
                            "would map onto a different rank block / sparse "
                            "layout; resume with the original rank count and "
                            "layout agreement, or use a fresh checkpoint "
                            "directory"
                        )
                    if int(ckpt.step) > num_iterations:
                        # never silently relabel an over-trained state as an
                        # N-sweep result: a shrunken num_iterations must fail
                        # fast, not return the sweep-{step} model
                        raise ValueError(
                            f"partitioned checkpoint at {checkpointer.directory}"
                            f" is at sweep {int(ckpt.step)}, beyond this run's "
                            f"num_iterations={num_iterations}; raise "
                            "num_iterations to continue training, or use a "
                            "fresh checkpoint directory"
                        )

                    def by_prefix(prefix):
                        return {
                            k[len(prefix):]: np.asarray(v)
                            for k, v in ckpt.arrays.items()
                            if k.startswith(prefix) and "/" not in k[len(prefix):]
                        }

                    # host arrays; prepare_partitioned_inputs re-places them
                    # over the mesh exactly like a warm start (tables were
                    # saved UNSLICED, so shapes — and the jit signature —
                    # match the interrupted run's)
                    state = GameTrainState(
                        fe_coefficients=np.asarray(ckpt.arrays["fe_coefficients"]),
                        re_tables=by_prefix("re_tables/"),
                        mf_rows={},
                        mf_cols={},
                        extra_fe=by_prefix("extra_fe/"),
                    )
                    start_sweep = int(ckpt.step)
                    prior_losses = [
                        float(x) for x in ckpt.meta.get("losses", [])
                    ][:start_sweep]
                    from photon_ml_tpu.telemetry import resilience_counters

                    resilience_counters.record_checkpoint_restore()
                    # resumed sweeps are the fused path's epochs-not-redone
                    resilience_counters.record_epochs_resumed(start_sweep)
                    logger.info(
                        "resuming partitioned training from checkpoint sweep "
                        "%d/%d", start_sweep, num_iterations,
                    )

        with span("train/prepare_inputs"):
            data, buckets, st = prepare_partitioned_inputs(
                program, parts, mesh, num_ranks,
                fe_feature_sharded=fe_feature_sharded, state=state,
            )
        r0 = sorted(parts)[0]
        table_sizes = {
            s.re_type: parts[r0][1][s.re_type].num_entities
            for s in program.re_specs
        }

        def to_host(v):
            """Model-sized arrays only (coefficients/tables) — every process
            joins the gather (collective), unlike the O(n) score funnel the
            partitioned path exists to remove."""
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                return np.asarray(multihost_utils.process_allgather(v, tiled=True))
            return jax.device_get(v)

        def commit():
            # every rank gathers (collectives) and calls the commit helper
            # (its barriers are exchange calls every rank must make); only
            # rank 0 writes the shared directory
            from photon_ml_tpu.io.checkpoint import commit_checkpoint

            arrays = {
                "fe_coefficients": np.asarray(to_host(st.fe_coefficients))
            }
            for k, v in st.re_tables.items():
                arrays[f"re_tables/{k}"] = np.asarray(to_host(v))
            for k, v in st.extra_fe.items():
                arrays[f"extra_fe/{k}"] = np.asarray(to_host(v))
            commit_checkpoint(
                checkpointer, sweep + 1, arrays,
                {"partition_fingerprint": fingerprint, "losses": losses},
                exchange=exchange,
            )

        # the assembled sample axis: every rank's block, padding rows included
        rows = int(data["labels"].shape[0])
        losses: list[float] = list(prior_losses)
        for sweep in range(start_sweep, num_iterations):
            with span("train/sweep", sweep=sweep + 1):
                st, loss = _step_and_wait(
                    program, data, buckets, st, sweep=sweep,
                    num_iterations=num_iterations, schedulers=schedulers,
                    rows=rows, check_finite=check_finite,
                    checkpointer=checkpointer, what="partitioned",
                )
                losses.append(loss)
                _checkpoint_if_due(checkpointer, checkpoint_every, sweep,
                                   num_iterations, commit)

        with span("train/result_state"):
            final = GameTrainState(
                fe_coefficients=jnp.asarray(to_host(st.fe_coefficients)),
                re_tables={
                    k: jnp.asarray(to_host(v))[: table_sizes[k]]
                    for k, v in st.re_tables.items()
                },
                mf_rows={},
                mf_cols={},
                extra_fe={k: jnp.asarray(to_host(v))
                          for k, v in st.extra_fe.items()},
            )
        return DistributedTrainResult(state=final, losses=losses)
