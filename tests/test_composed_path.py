"""The composed production configuration (ISSUE 6): partitioned I/O x
hybrid layout x scheduled RE solves as ONE run.

Reference parity: photon-lib driver flow (GameTrainingDriver.scala:120-210
runs partitioned ingestion, feature-shard layout, and per-entity solves as
one job, not as mutually exclusive demos). The composition seams under
test:

- GLOBAL hot-column ranking over partitioned ingestion: every rank
  resolves the SAME HybridPolicy head from the summed per-rank nnz
  histograms (io/partitioned_reader._resolve_global_sparse_layout), the
  arXiv:2004.02414 per-partition-statistics-vs-global-solution pitfall
  solved exactly like the entity vocabs.
- Globally-agreed ELL width + flat overflow block: the composed layout is
  bitwise what the unpartitioned read would build, so when the agreed
  width covers every tail row the composed TRAINED STATE is bitwise equal
  to the full-read run. With flat overflow the layouts still agree
  bitwise; trained floats agree to f32 round-off (the flat scatter-add's
  association is device-layout-dependent — the same caveat as the
  existing 1-vs-8-device rtol contracts in test_sparse.py).
- Collective-safe rescue compaction (algorithm/lane_scheduler.py SPMD
  mode): rank-local compaction into a fixed [num_ranks * R] rescue-block
  signature, identical solves to the host mode.

Virtual ranks (threads + InProcessExchange) on the 8-device CPU mesh, the
same code paths a multi-process pod takes.
"""

import threading

import numpy as np
import pytest

from photon_ml_tpu.data.game_data import (
    build_random_effect_dataset,
    build_random_effect_dataset_partitioned,
)
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io import photon_schemas as schemas
from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    read_merged,
)
from photon_ml_tpu.io.partitioned_reader import read_partitioned
from photon_ml_tpu.optim.optimizer import (
    LaneSchedulerConfig,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainProgram,
    RandomEffectStepSpec,
    train_distributed,
    train_partitioned,
)
from photon_ml_tpu.parallel.multihost import (
    InProcessExchange,
    make_hybrid_mesh,
)
from photon_ml_tpu.types import TaskType

SCHEMA = {
    "name": "ComposedPathExampleAvro", "type": "record",
    "fields": [
        {"name": "uid", "type": ["string", "null"]},
        {"name": "label", "type": "double"},
        {"name": "features",
         "type": {"type": "array", "items": schemas.FEATURE_AVRO}},
        {"name": "entityFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "weight", "type": ["double", "null"], "default": None},
        {"name": "offset", "type": ["double", "null"], "default": None},
        {"name": "metadataMap",
         "type": [{"type": "map", "values": "string"}, "null"],
         "default": None},
    ],
}


def _shard_configs(hot_cols=5):
    return {
        "global": FeatureShardConfiguration(
            feature_bags=("features",), sparse=True, hybrid=True,
            hybrid_hot_cols=hot_cols,
        ),
        "perUser": FeatureShardConfiguration(
            feature_bags=("entityFeatures",), has_intercept=False
        ),
    }


def _write_input(tmp_path, *, num_files=4, rows_per_file=40, seed=3,
                 tail="uniform"):
    """Entity-clustered power-law input: hot name-term bags h0..h3 on most
    rows, a cold tail from a 30-name pool.

    tail="uniform": every row carries exactly 2 DISTINCT cold names, so
    the 98th-percentile ELL rule covers every tail row and the flat
    overflow is empty (the bitwise-composed regime). tail="skewed": 0-2
    cold names with duplicates, so the agreed width leaves real flat
    overflow on both ranks.
    """
    rng = np.random.default_rng(seed)
    uid = 0
    for part in range(num_files):
        recs = []
        for _ in range(rows_per_file):
            feats = []
            for j in range(4):
                if rng.random() < 0.8:
                    feats.append({"name": f"h{j}", "term": "",
                                  "value": float(rng.normal())})
            if tail == "uniform":
                cold = rng.choice(30, size=2, replace=False)
            else:
                cold = rng.integers(0, 30, size=int(rng.integers(0, 3)))
            for ci in cold:
                feats.append({"name": f"c{int(ci)}", "term": "",
                              "value": float(rng.normal())})
            if not feats:
                feats.append({"name": "h0", "term": "", "value": 1.0})
            xu = rng.normal(size=2)
            recs.append({
                "uid": str(uid),
                "label": float(sum(f["value"] for f in feats)
                               + 0.1 * rng.normal()),
                "features": feats,
                "entityFeatures": [
                    {"name": f"u{j}", "term": "", "value": float(xu[j])}
                    for j in range(2)
                ],
                "weight": 1.0, "offset": 0.0,
                "metadataMap": {
                    "userId": f"user{part}_{int(rng.integers(0, 4))}"
                },
            })
            uid += 1
        avro_io.write_container(
            str(tmp_path / f"part-{part:05d}.avro"), SCHEMA, recs,
            block_records=4096,
        )
    return str(tmp_path)


def _fe_opt():
    return OptimizerConfig(optimizer_type=OptimizerType.LBFGS,
                           max_iterations=8)


def _re_opt(scheduled=True):
    return OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=8,
        rel_function_tolerance=1e-6 if scheduled else None,
        scheduler=LaneSchedulerConfig(probe_iterations=2)
        if scheduled else None,
    )


def _program(scheduled=True):
    return GameTrainProgram(
        TaskType.LINEAR_REGRESSION,
        FixedEffectStepSpec("global", _fe_opt(), l2_weight=0.5),
        (RandomEffectStepSpec("userId", "perUser", _re_opt(scheduled),
                              l2_weight=1.0),),
    )


def _read_ranks(path, shard_configs, num_ranks=2, wrap=None):
    exchanges = InProcessExchange.create_group(num_ranks)
    if wrap is not None:
        exchanges = [wrap(e) for e in exchanges]
    parts = [None] * num_ranks
    errors = []

    def run(r):
        try:
            parts[r] = read_partitioned(
                path, shard_configs, exchange=exchanges[r],
                random_effect_id_columns=("userId",), pad_multiple=2,
            )
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append((r, e))

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(num_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return parts, exchanges, errors


def _build_re_ranks(parts, exchanges):
    num_ranks = len(parts)
    re_parts = [None] * num_ranks

    def build(r):
        p = parts[r]
        re_parts[r] = {"userId": build_random_effect_dataset_partitioned(
            p.result.dataset, "userId", "perUser",
            partition=p.partition, exchange=exchanges[r],
            bucket_sizes=(64,), lane_multiple=2,
            entity_rank_presence=p.entity_rank_presence.get("userId"),
        )}

    threads = [threading.Thread(target=build, args=(r,))
               for r in range(num_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return re_parts


def _full_read_reference(path, shard_configs, scheduled=True, mesh=None):
    full = read_merged(path, shard_configs,
                       random_effect_id_columns=("userId",))
    full_re = {"userId": build_random_effect_dataset(
        full.dataset, "userId", "perUser", bucket_sizes=(64,),
    )}
    ref = train_distributed(
        _program(scheduled), full.dataset, full_re, mesh=mesh,
        num_iterations=2,
    )
    return full, ref


def _train_composed_with(parts, re_parts, mesh, scheduled=True):
    from photon_ml_tpu.algorithm.lane_scheduler import make_schedulers

    prog = _program(scheduled)
    scheds = make_schedulers(prog.re_specs, mesh=mesh)
    return train_partitioned(
        prog,
        {r: (parts[r].result.dataset, re_parts[r])
         for r in range(len(parts))},
        mesh, len(parts), num_iterations=2,
        schedulers=scheds or None,
    )


def test_composed_run_bitwise_matches_full_read(tmp_path):
    """THE acceptance claim: partitioned read + global hybrid head +
    scheduled RE solves in one virtual-rank run trains BITWISE identically
    to the unpartitioned hybrid scheduled run (entity-clustered input,
    agreed ELL width covering every tail row)."""
    path = _write_input(tmp_path, tail="uniform")
    configs = _shard_configs()
    mesh = make_hybrid_mesh(data=4, model=2)
    full, ref = _full_read_reference(path, configs, mesh=mesh)

    parts, exchanges, errors = _read_ranks(path, configs)
    assert not errors, errors
    # every rank resolved the SAME pre-baked global head and ELL width
    shards = [p.result.dataset.feature_shards["global"] for p in parts]
    assert shards[0].hybrid_policy.hot_ids is not None
    assert shards[0].hybrid_policy.hot_ids == shards[1].hybrid_policy.hot_ids
    assert shards[0].ell_width == shards[1].ell_width
    assert shards[0].flat_block_nnz == shards[1].flat_block_nnz == 0

    re_parts = _build_re_ranks(parts, exchanges)
    res = _train_composed_with(parts, re_parts, mesh)

    np.testing.assert_array_equal(res.losses, ref.losses)
    np.testing.assert_array_equal(
        np.asarray(res.state.fe_coefficients),
        np.asarray(ref.state.fe_coefficients),
    )
    np.testing.assert_array_equal(
        np.asarray(res.state.re_tables["userId"]),
        np.asarray(ref.state.re_tables["userId"]),
    )


def test_composed_overflow_layout_bitwise_training_close(tmp_path):
    """With real flat overflow the LAYOUT decisions still agree bitwise —
    the agreed width is exactly the full read's auto width, and stripping
    the per-rank pads reconstructs the full read's overflow triple entry
    for entry — while the trained floats agree to f32 round-off (the flat
    scatter-add's association follows the device layout, which
    partitioning necessarily changes; same contract as the 1-vs-8-device
    sharding tests)."""
    from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch

    path = _write_input(tmp_path, tail="skewed")
    configs = _shard_configs(hot_cols=4)
    mesh = make_hybrid_mesh(data=4, model=2)
    full, ref = _full_read_reference(path, configs, mesh=mesh)

    # the full read's batch as a mesh takes it (prepare_inputs): ONE [n, L]
    # block at the one-width auto rule on the rows' counts, the rule the
    # ranks agree on (a one-process "auto" batch is tiered: another layout)
    full_shard = full.dataset.feature_shards["global"]
    full_batch = SparseLabeledPointBatch.from_shard(
        full_shard,
        np.asarray(full.dataset.host_array("labels")),
        np.asarray(full.dataset.host_array("offsets")),
        np.asarray(full.dataset.host_array("weights")),
        ell=full_shard.one_ell_width(),
    )
    assert full_batch.nnz > 0  # the fixture really overflows
    assert not full_batch.ell_tiers

    parts, exchanges, errors = _read_ranks(path, configs)
    assert not errors, errors
    shards = [p.result.dataset.feature_shards["global"] for p in parts]
    # agreed width == the width the one-width auto rule picks on the full
    # read's counts
    assert shards[0].ell_width == full_batch.ell_vals.shape[1]
    assert shards[0].ell_width == shards[1].ell_width
    assert shards[0].flat_block_nnz == shards[1].flat_block_nnz > 0

    # stripping pads (value 0 entries) and unshifting rank base rows
    # reconstructs the full read's overflow triple entry for entry
    got_rows, got_cols, got_vals = [], [], []
    for r, p in enumerate(parts):
        ds = p.result.dataset
        b = SparseLabeledPointBatch.from_shard(
            ds.feature_shards["global"],
            np.asarray(ds.host_array("labels")),
            np.asarray(ds.host_array("offsets")),
            np.asarray(ds.host_array("weights")),
        )
        vals = np.asarray(b.values)
        real = vals != 0.0
        got_rows.append(np.asarray(b.row_ids)[real] + r * p.partition.block_rows)
        got_cols.append(np.asarray(b.col_indices)[real])
        got_vals.append(vals[real])
    want_real = np.asarray(full_batch.values) != 0.0
    np.testing.assert_array_equal(
        np.concatenate(got_rows), np.asarray(full_batch.row_ids)[want_real]
    )
    np.testing.assert_array_equal(
        np.concatenate(got_cols),
        np.asarray(full_batch.col_indices)[want_real],
    )
    np.testing.assert_array_equal(
        np.concatenate(got_vals), np.asarray(full_batch.values)[want_real]
    )

    re_parts = _build_re_ranks(parts, exchanges)
    res = _train_composed_with(parts, re_parts, mesh)
    np.testing.assert_allclose(res.losses, ref.losses, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(res.state.fe_coefficients),
        np.asarray(ref.state.fe_coefficients), atol=5e-3,
    )
    np.testing.assert_allclose(
        np.asarray(res.state.re_tables["userId"]),
        np.asarray(ref.state.re_tables["userId"]), atol=5e-3,
    )


def test_composed_width_mirrors_mesh_padding_on_non_multiple_n(tmp_path):
    """Regression: the agreed ELL width must mirror the zero-count rows
    train_distributed's mesh padding appends — the full read picks its
    auto width AFTER ``pad_game_dataset`` runs, so on a global row count
    that is not a mesh-data-axis multiple the padded and unpadded widths
    can differ (this fixture is chosen so they DO, guard-asserted below:
    n=42 pads to 44 and the 0.98-quantile width flips 3 -> 2). Without the
    histogram mirroring in _resolve_global_sparse_layout the composed
    split silently drifts from the unpartitioned run's."""
    from photon_ml_tpu.data.game_data import pad_game_dataset_to
    from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch

    path = _write_input(tmp_path, num_files=2, rows_per_file=21, seed=10,
                        tail="skewed")
    configs = _shard_configs(hot_cols=4)
    full = read_merged(path, configs, random_effect_id_columns=("userId",))
    n = full.dataset.num_samples
    data_axis = 4  # pad_multiple=2 x 2 ranks == the reference mesh axis
    assert n % data_axis != 0

    def batch_width(ds):
        # the mesh's rule (one width from the counts), as prepare_inputs
        # applies it to the padded data set
        shard = ds.feature_shards["global"]
        b = SparseLabeledPointBatch.from_shard(
            shard,
            np.asarray(ds.host_array("labels")),
            np.asarray(ds.host_array("offsets")),
            np.asarray(ds.host_array("weights")),
            ell=shard.one_ell_width(),
        )
        assert not b.ell_tiers
        return b.ell_vals.shape[1]

    padded, _ = pad_game_dataset_to(
        full.dataset, -(-n // data_axis) * data_axis
    )
    w_padded = batch_width(padded)
    # the fixture discriminates: an unmirrored histogram would agree the
    # UNPADDED width and this test would not catch the drift
    assert batch_width(full.dataset) != w_padded

    parts, _, errors = _read_ranks(path, configs)
    assert not errors, errors
    shards = [p.result.dataset.feature_shards["global"] for p in parts]
    assert shards[0].ell_width == shards[1].ell_width == w_padded
    assert shards[0].flat_block_nnz == shards[1].flat_block_nnz


def test_composed_off_unscheduled_unhybrid_stays_default(tmp_path):
    """Composed-off pin: the same partitioned flow with hybrid AND the
    scheduler off rides exactly the pre-existing partitioned path — and a
    DENSE partitioned read performs no layout exchange at all (the layout
    resolution only activates on sparse shards)."""
    path = _write_input(tmp_path, tail="uniform")
    dense_configs = {
        "global": FeatureShardConfiguration(feature_bags=("features",)),
        "perUser": FeatureShardConfiguration(
            feature_bags=("entityFeatures",), has_intercept=False
        ),
    }
    seen_tags = []

    class SpyExchange:
        def __init__(self, inner):
            self._inner = inner
            self.rank = inner.rank
            self.num_ranks = inner.num_ranks

        def allgather(self, tag, payload):
            seen_tags.append(tag)
            return self._inner.allgather(tag, payload)

        def barrier(self, tag):
            return self._inner.barrier(tag)

    parts, _, errors = _read_ranks(path, dense_configs, wrap=SpyExchange)
    assert not errors, errors
    assert not any(
        t.startswith(("hybrid_hot/", "ell_width/")) for t in seen_tags
    ), seen_tags
    assert seen_tags  # the pre-existing exchanges (vocab/index map) ran


def test_spmd_rescue_mode_matches_host_mode(tmp_path):
    """The collective-safe SPMD rescue compaction (rank-local compaction
    into the fixed [num_ranks * R] block) solves the SAME lanes to the
    same values as the host mode: on one process the two modes are
    bitwise-identical (padding lanes are inert sentinels), and the SPMD
    mode is sharding-invariant across mesh widths."""
    from photon_ml_tpu.algorithm.lane_scheduler import LaneScheduler

    path = _write_input(tmp_path, tail="uniform")
    configs = _shard_configs()
    mesh = make_hybrid_mesh(data=4, model=2)
    full = read_merged(path, configs, random_effect_id_columns=("userId",))
    full_re = {"userId": build_random_effect_dataset(
        full.dataset, "userId", "perUser", bucket_sizes=(64,),
    )}

    def run(scheduler):
        return train_partitioned(
            _program(), {0: (full.dataset, full_re)}, mesh, 1,
            num_iterations=2,
            schedulers={"userId": scheduler},
        )

    cfg = LaneSchedulerConfig(probe_iterations=2)
    host = run(LaneScheduler(cfg))
    spmd = run(LaneScheduler(cfg, mesh=mesh))
    np.testing.assert_array_equal(host.losses, spmd.losses)
    np.testing.assert_array_equal(
        np.asarray(host.state.re_tables["userId"]),
        np.asarray(spmd.state.re_tables["userId"]),
    )

    # sharding invariance of the SPMD rescue step across mesh widths
    mesh1 = make_hybrid_mesh(data=1, model=1)
    spmd1 = train_partitioned(
        _program(), {0: (full.dataset, full_re)}, mesh1, 1,
        num_iterations=2,
        schedulers={"userId": LaneScheduler(cfg, mesh=mesh1)},
    )
    # losses ride the hybrid head matmul's cross-device psum, whose
    # association changes with mesh width (f32 round-off)
    np.testing.assert_allclose(spmd1.losses, spmd.losses, rtol=1e-4)
    # solver-tolerance agreement, not bitwise: the hybrid FE margins
    # differ across widths at f32 round-off, which can flip a
    # near-tolerance lane's probe flag and change its rescue iteration
    # count — same contract as the scheduled-vs-unscheduled comparison
    np.testing.assert_allclose(
        np.asarray(spmd1.state.re_tables["userId"]),
        np.asarray(spmd.state.re_tables["userId"]),
        atol=5e-3,
    )


def test_make_schedulers_mode_selection(monkeypatch):
    """ONE mode-selection rule: multi-process runs get the SPMD mesh mode,
    single-process runs keep the host mode (mesh=None) regardless of the
    mesh argument."""
    import jax

    from photon_ml_tpu.algorithm.lane_scheduler import make_schedulers

    specs = _program().re_specs
    mesh = make_hybrid_mesh(data=4, model=2)
    scheds = make_schedulers(specs, mesh=mesh)
    assert scheds["userId"].mesh is None  # single process: host mode

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    scheds = make_schedulers(specs, mesh=mesh)
    assert scheds["userId"].mesh is mesh  # multi-process: SPMD mode

    assert make_schedulers([s for s in specs
                            if s.optimizer.scheduler is None]) == {}
