"""Partitioned host I/O: per-rank ingestion, training/scoring parity, and
per-rank score output (io/partitioned_reader.py, io/score_writer.py,
parallel/multihost.py exchange + assembly, train_partitioned,
DistributedScorer.score_partitioned).

Rank-parallel flows run as VIRTUAL ranks on one host (threads +
multihost.InProcessExchange) against the 8-device virtual CPU mesh — the
same code paths a multi-process pod takes, with every rank's block
addressable so the assembled global arrays can be checked against the
full-read reference bit for bit. The real two-OS-process flow is covered
by tests/test_partitioned_multihost_e2e.py.
"""

import os
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.data.game_data import (
    build_random_effect_dataset,
    build_random_effect_dataset_partitioned,
    pad_game_dataset_to,
)
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io import photon_schemas as schemas
from photon_ml_tpu.io.data_reader import (
    FeatureShardConfiguration,
    read_merged,
)
from photon_ml_tpu.io.partitioned_reader import (
    PartitionInfo,
    assign_contiguous,
    read_partitioned,
)
from photon_ml_tpu.io.score_writer import ShardedScoreWriter
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.parallel.multihost import (
    InProcessExchange,
    SingleProcessExchange,
    assemble_partitioned,
    make_hybrid_mesh,
)
from photon_ml_tpu.telemetry import io_counters

SCHEMA = {
    "name": "PartitionedIoExampleAvro", "type": "record",
    "fields": [
        {"name": "uid", "type": ["string", "null"]},
        {"name": "label", "type": "double"},
        {"name": "features",
         "type": {"type": "array", "items": schemas.FEATURE_AVRO}},
        {"name": "entityFeatures", "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "weight", "type": ["double", "null"], "default": None},
        {"name": "offset", "type": ["double", "null"], "default": None},
        {"name": "metadataMap",
         "type": [{"type": "map", "values": "string"}, "null"],
         "default": None},
    ],
}

SHARD_CONFIGS = {
    "global": FeatureShardConfiguration(feature_bags=("features",)),
    "perUser": FeatureShardConfiguration(
        feature_bags=("entityFeatures",), has_intercept=False
    ),
}


def _write_input(tmp_path, *, num_files=4, rows_per_file=40, seed=1,
                 block_records=4096, entity_clustered=True):
    """Entity-clustered Avro parts: each file owns disjoint users, so a
    contiguous file assignment keeps every entity on one rank (the layout
    the reference's partitioner produces — exact full-read parity)."""
    rng = np.random.default_rng(seed)
    uid = 0
    for part in range(num_files):
        recs = []
        ekey = part if entity_clustered else 0
        for _ in range(rows_per_file):
            xg = rng.normal(size=4)
            xu = rng.normal(size=2)
            recs.append({
                "uid": str(uid),
                "label": float(xg.sum() + 0.1 * rng.normal()),
                "features": [
                    {"name": f"g{j}", "term": "", "value": float(xg[j])}
                    for j in range(4)
                ],
                "entityFeatures": [
                    {"name": f"u{j}", "term": "", "value": float(xu[j])}
                    for j in range(2)
                ],
                "weight": 1.0, "offset": 0.0,
                "metadataMap": {
                    "userId": f"user{ekey}_{int(rng.integers(0, 4))}"
                },
            })
            uid += 1
        avro_io.write_container(
            str(tmp_path / f"part-{part:05d}.avro"), SCHEMA, recs,
            block_records=block_records,
        )
    return str(tmp_path)


def _read_ranks(path, num_ranks, *, pad_multiple=1, **kwargs):
    """Run read_partitioned on ``num_ranks`` virtual ranks (threads)."""
    exchanges = InProcessExchange.create_group(num_ranks)
    results = [None] * num_ranks
    errors = []

    def run(r):
        try:
            results[r] = read_partitioned(
                path, SHARD_CONFIGS, exchange=exchanges[r],
                random_effect_id_columns=("userId",),
                pad_multiple=pad_multiple, **kwargs,
            )
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append((r, e))

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(num_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results, exchanges


def _concat_true_rows(parts, name):
    return np.concatenate([
        np.asarray(p.result.dataset.host_array(name))[: p.partition.local_n]
        for p in parts
    ])


def test_assign_contiguous_properties():
    # contiguous cover of all items, deterministic, order-preserving
    for weights, ranks in (
        ([10, 10, 10, 10], 2), ([1, 1, 1, 100], 2), ([5], 3),
        ([3, 9, 1, 1, 7, 2], 4), ([], 2),
    ):
        ranges = assign_contiguous(weights, ranks)
        assert len(ranges) == ranks
        assert ranges[0][0] == 0 and ranges[-1][1] == len(weights)
        for (a, b), (c, d) in zip(ranges, ranges[1:]):
            assert b == c and a <= b and c <= d
        assert ranges == assign_contiguous(weights, ranks)
    # near-balanced on equal weights
    assert assign_contiguous([10] * 8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]


def test_scan_block_index_and_block_range(tmp_path):
    path = _write_input(tmp_path, num_files=1, rows_per_file=100,
                        block_records=16)
    f = os.path.join(path, "part-00000.avro")
    index = avro_io.scan_block_index(f)
    assert sum(n for n, _, _ in index) == 100
    assert len(index) == -(-100 // 16)
    full = list(avro_io.read_container(f))
    # any block slice reproduces the corresponding record slice
    got = list(avro_io.read_container_block_range(f, 2, 3))
    assert got == full[32:80]
    assert list(avro_io.read_container_block_range(f, 0, len(index))) == full
    with pytest.raises(avro_io.AvroError, match="exceeds"):
        list(avro_io.read_container_block_range(f, 0, len(index) + 1))


@pytest.mark.parametrize("num_ranks,kwargs,mode", [
    (2, dict(num_files=4), "files"),
    (3, dict(num_files=1, rows_per_file=160, block_records=16), "blocks"),
])
def test_partitioned_read_matches_full(tmp_path, num_ranks, kwargs, mode):
    """Concatenating rank slices (file- and block-assigned) reproduces the
    full read row for row, with identical index maps, intercepts, and
    entity vocabs, and each rank decoding strictly less than the input."""
    path = _write_input(tmp_path, **kwargs)
    full = read_merged(path, SHARD_CONFIGS,
                       random_effect_id_columns=("userId",))
    parts, _ = _read_ranks(path, num_ranks, pad_multiple=2)
    assert parts[0].mode == mode
    assert parts[0].partition.local_rows == tuple(
        p.partition.local_n for p in parts
    )
    for p in parts:
        assert 0 < p.bytes_decoded < p.input_bytes_total
        assert dict(p.result.index_maps["global"]) == dict(
            full.index_maps["global"]
        )
        assert p.result.intercept_indices == full.intercept_indices
        np.testing.assert_array_equal(
            p.result.dataset.entity_vocabs["userId"],
            full.dataset.entity_vocabs["userId"],
        )
        # padded block: pad rows carry weight 0
        ds = p.result.dataset
        assert ds.num_samples == p.partition.block_rows
        w = np.asarray(ds.host_array("weights"))
        assert (w[p.partition.local_n:] == 0).all()
    for name in ("labels", "offsets", "weights", "shard/global",
                 "shard/perUser", "entity_idx/userId"):
        np.testing.assert_array_equal(
            _concat_true_rows(parts, name),
            np.asarray(full.dataset.host_array(name)), err_msg=name,
        )
    np.testing.assert_array_equal(
        np.concatenate([
            np.asarray(p.result.dataset.unique_ids)[: p.partition.local_n]
            for p in parts
        ]),
        np.asarray(full.dataset.unique_ids),
    )


def test_partitioned_read_uidless_input_renumbers_globally(tmp_path):
    """Inputs with NO uid field: the reader auto-assigns row numbers, which
    must land in the GLOBAL row space (0..N-1 like the full read) — not
    restart at 0 per rank (duplicate score-output uids, unstable
    reservoir keys)."""
    schema = {
        "name": "NoUid", "type": "record",
        "fields": [
            {"name": "label", "type": "double"},
            {"name": "features",
             "type": {"type": "array", "items": schemas.FEATURE_AVRO}},
        ],
    }
    rng = np.random.default_rng(2)
    for part in range(2):
        recs = [
            {"label": float(rng.normal()),
             "features": [{"name": f"f{j}", "term": "", "value": 1.0}
                          for j in range(2)]}
            for _ in range(20 + part * 10)
        ]
        avro_io.write_container(
            str(tmp_path / f"part-{part:05d}.avro"), schema, recs
        )
    cfgs = {"g": FeatureShardConfiguration(feature_bags=("features",))}
    exchanges = InProcessExchange.create_group(2)
    results = [None, None]

    def run(r):
        results[r] = read_partitioned(str(tmp_path), cfgs,
                                      exchange=exchanges[r])

    threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    uids = np.concatenate([
        np.asarray(p.result.dataset.unique_ids)[: p.partition.local_n]
        for p in results
    ])
    np.testing.assert_array_equal(uids, np.arange(50))


def test_partitioned_read_single_rank_delegates(tmp_path):
    path = _write_input(tmp_path)
    full = read_merged(path, SHARD_CONFIGS,
                       random_effect_id_columns=("userId",))
    part = read_partitioned(
        path, SHARD_CONFIGS, exchange=SingleProcessExchange(),
        random_effect_id_columns=("userId",),
    )
    assert part.mode == "single"
    assert part.partition.num_ranks == 1
    assert part.partition.local_n == full.dataset.num_samples
    np.testing.assert_array_equal(
        np.asarray(part.result.dataset.host_array("shard/global")),
        np.asarray(full.dataset.host_array("shard/global")),
    )


def test_partitioned_read_telemetry_counters(tmp_path):
    path = _write_input(tmp_path, num_files=2)
    before = io_counters.bytes_decoded()
    parts, _ = _read_ranks(path, 2)
    decoded = io_counters.bytes_decoded() - before
    # in-process virtual ranks share the registry: the counter carries the
    # SUM of both ranks' decodes (per-rank separation is the two-process
    # e2e's assertion)
    assert decoded == sum(p.bytes_decoded for p in parts)
    assert io_counters.input_bytes_total() == parts[0].input_bytes_total
    assert decoded == parts[0].input_bytes_total  # disjoint cover


def test_assemble_partitioned_layout(tmp_path):
    mesh = make_hybrid_mesh(data=8, model=1)
    b0 = np.arange(8.0).reshape(4, 2)
    b1 = np.arange(8.0, 16.0).reshape(4, 2)
    out = assemble_partitioned({0: b0, 1: b1}, mesh, jax.sharding.PartitionSpec("data", None), 2)
    np.testing.assert_array_equal(np.asarray(out), np.concatenate([b0, b1]))
    # device shards that would cross a rank-block boundary are rejected
    # (8 devices over 3 ranks x 8 rows: chunk 3 straddles row 8)
    blocks3 = {r: np.full((8, 2), float(r)) for r in range(3)}
    with pytest.raises(ValueError, match="block boundary"):
        assemble_partitioned(
            blocks3, mesh, jax.sharding.PartitionSpec("data", None), 3
        )


def _toy_programs():
    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS,
                          max_iterations=8)
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.types import TaskType

    def make():
        return GameTrainProgram(
            TaskType.LINEAR_REGRESSION,
            FixedEffectStepSpec("global", opt, l2_weight=0.5),
            (RandomEffectStepSpec("userId", "perUser", opt, l2_weight=1.0),),
        )

    return make


def test_partitioned_training_matches_full_read(tmp_path):
    """The e2e model-identity claim: partitioned ingest (2 virtual ranks)
    + rank-local RE buckets + train_partitioned lands on EXACTLY the
    full-read train_distributed state (entity-clustered input)."""
    from photon_ml_tpu.parallel.distributed import (
        train_distributed,
        train_partitioned,
    )

    path = _write_input(tmp_path, num_files=4)
    make_program = _toy_programs()
    mesh = make_hybrid_mesh(data=4, model=2)

    full = read_merged(path, SHARD_CONFIGS,
                       random_effect_id_columns=("userId",))
    full_re = {"userId": build_random_effect_dataset(
        full.dataset, "userId", "perUser", bucket_sizes=(64,),
    )}
    ref = train_distributed(make_program(), full.dataset, full_re,
                            mesh=mesh, num_iterations=2)

    parts, exchanges = _read_ranks(path, 2, pad_multiple=2)
    re_parts = [None, None]

    def build_re(r):
        p = parts[r]
        re_parts[r] = {"userId": build_random_effect_dataset_partitioned(
            p.result.dataset, "userId", "perUser",
            partition=p.partition, exchange=exchanges[r],
            bucket_sizes=(64,), lane_multiple=2,
            entity_rank_presence=p.entity_rank_presence.get("userId"),
        )}

    threads = [threading.Thread(target=build_re, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # entity-clustered input: no entity spans ranks
    assert int(np.max(parts[0].entity_rank_presence["userId"])) == 1

    from photon_ml_tpu.telemetry.tracing import (
        Tracer,
        install_tracer,
        uninstall_tracer,
    )

    tracer = install_tracer(Tracer(rank=0))
    try:
        res = train_partitioned(
            make_program(),
            {r: (parts[r].result.dataset, re_parts[r]) for r in range(2)},
            mesh, 2, num_iterations=2,
        )
    finally:
        uninstall_tracer()
    # the same loop marks the same spans as train_distributed's
    names = [e.name for e in tracer.events() if e.name.startswith("train/")]
    assert sorted(names) == sorted(
        ["train/fit", "train/prepare_inputs", "train/result_state"]
        + 2 * ["train/sweep", "train/step", "train/loss_wait",
               "train/solver_counts"])
    np.testing.assert_allclose(res.losses, ref.losses, rtol=1e-12)
    np.testing.assert_allclose(
        np.asarray(res.state.fe_coefficients),
        np.asarray(ref.state.fe_coefficients), rtol=1e-9, atol=1e-12,
    )
    np.testing.assert_allclose(
        np.asarray(res.state.re_tables["userId"]),
        np.asarray(ref.state.re_tables["userId"]), rtol=1e-9, atol=1e-12,
    )


def test_partitioned_scoring_matches_full(tmp_path):
    """score_partitioned returns each rank's exact slice of score_dataset
    — the [n] vector never gathers."""
    from photon_ml_tpu.parallel.distributed import train_distributed
    from photon_ml_tpu.parallel.scoring import DistributedScorer
    from photon_ml_tpu.parallel.distributed import state_to_game_model

    path = _write_input(tmp_path, num_files=4)
    make_program = _toy_programs()
    mesh = make_hybrid_mesh(data=4, model=2)
    full = read_merged(path, SHARD_CONFIGS,
                       random_effect_id_columns=("userId",))
    full_re = {"userId": build_random_effect_dataset(
        full.dataset, "userId", "perUser", bucket_sizes=(64,),
    )}
    program = make_program()
    result = train_distributed(program, full.dataset, full_re,
                               mesh=mesh, num_iterations=1)
    model = state_to_game_model(program, result.state, full.dataset,
                                re_datasets=full_re)

    scorer = DistributedScorer(model, mesh)
    ref = scorer.score_dataset(full.dataset)

    parts, _ = _read_ranks(path, 2, pad_multiple=2,
                           entity_vocabs=full.dataset.entity_vocabs)
    got = scorer.score_partitioned(
        {r: parts[r].result.dataset for r in range(2)}, parts[0].partition
    )
    lo = 0
    for r in range(2):
        n = parts[r].partition.local_n
        np.testing.assert_allclose(got[r], ref[lo:lo + n], rtol=1e-12)
        lo += n


def test_sharded_score_writer_parts_match_rank0_writer(tmp_path):
    """Per-rank part files, concatenated in part order, equal the rank-0
    writer's output record for record; bytes-written telemetry moves."""
    from photon_ml_tpu.io.model_io import write_scores

    rng = np.random.default_rng(7)
    n = 111
    scores = rng.normal(size=n)
    uids = np.arange(n)
    labels = rng.normal(size=n)
    weights = np.ones(n)

    ref_dir = tmp_path / "ref"
    write_scores(str(ref_dir), scores, model_id="m", uids=uids,
                 labels=labels, weights=weights, records_per_file=1 << 20)

    out_dir = tmp_path / "scores"
    exchanges = InProcessExchange.create_group(2)
    split = 60
    before = io_counters.score_bytes_written()

    def write(r):
        sl = slice(0, split) if r == 0 else slice(split, n)
        ShardedScoreWriter(str(out_dir), exchange=exchanges[r]).write(
            scores[sl], model_id="m", uids=uids[sl], labels=labels[sl],
            weights=weights[sl],
        )

    threads = [threading.Thread(target=write, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    parts = sorted(os.listdir(out_dir))
    assert parts == ["part-00000.avro", "part-00001.avro"]
    got = [r for p in parts
           for r in avro_io.read_container(os.path.join(out_dir, p))]
    want = [r for p in sorted(os.listdir(ref_dir))
            for r in avro_io.read_container(os.path.join(ref_dir, p))]
    assert got == want
    written = io_counters.score_bytes_written() - before
    assert written == sum(
        os.path.getsize(os.path.join(out_dir, p)) for p in parts
    )


def test_sharded_score_writer_single_rank_keeps_layout(tmp_path):
    from photon_ml_tpu.io.model_io import write_scores

    rng = np.random.default_rng(9)
    scores = rng.normal(size=50)
    ref_dir, out_dir = tmp_path / "ref", tmp_path / "out"
    write_scores(str(ref_dir), scores, model_id="m",
                 uids=np.arange(50), records_per_file=1 << 20)
    ShardedScoreWriter(str(out_dir), exchange=SingleProcessExchange()).write(
        scores, model_id="m", uids=np.arange(50)
    )
    assert sorted(os.listdir(out_dir)) == sorted(os.listdir(ref_dir))
    for name in os.listdir(ref_dir):
        assert (ref_dir / name).read_bytes() == (out_dir / name).read_bytes()


def test_estimator_partition_guard(tmp_path):
    """Configs outside the partitioned v1 surface fail loudly before any
    rank-local work."""
    from photon_ml_tpu.algorithm.coordinates import CoordinateOptimizationConfig
    from photon_ml_tpu.estimators import (
        GameEstimator,
        RandomEffectCoordinateConfig,
        TrainPartition,
    )
    from photon_ml_tpu.ops.normalization import NormalizationType
    from photon_ml_tpu.projector.projectors import ProjectorType
    from photon_ml_tpu.types import TaskType

    path = _write_input(tmp_path, num_files=2)
    parts, exchanges = _read_ranks(path, 2, pad_multiple=2)
    mesh = make_hybrid_mesh(data=4, model=2)
    partition = TrainPartition(
        info=parts[0].partition, exchange=exchanges[0], lane_multiple=2,
    )
    est = GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "re": RandomEffectCoordinateConfig(
                "userId", "perUser",
                CoordinateOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=2), l2_weight=1.0
                ),
                projector_type=ProjectorType.RANDOM, projected_dim=2,
            ),
        },
        mesh=mesh,
        partition=partition,
        normalization=NormalizationType.STANDARDIZATION,
    )
    with pytest.raises(ValueError, match="partitioned training"):
        est.fit(parts[0].result.dataset)


def test_rank_local_re_builder_shifts_sample_rows(tmp_path):
    """Rank-1 buckets index the GLOBAL sample axis (base-row shift) and
    both ranks agree on the padded bucket structure."""
    path = _write_input(tmp_path, num_files=2)
    parts, exchanges = _read_ranks(path, 2, pad_multiple=2)
    built = [None, None]

    def build(r):
        built[r] = build_random_effect_dataset_partitioned(
            parts[r].result.dataset, "userId", "perUser",
            partition=parts[r].partition, exchange=exchanges[r],
            bucket_sizes=(64,), lane_multiple=2,
        )

    threads = [threading.Thread(target=build, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built[0].buckets) == len(built[1].buckets)
    for b0, b1 in zip(built[0].buckets, built[1].buckets):
        assert b0.features.shape == b1.features.shape
        rows1 = np.asarray(b1.sample_rows)
        valid = rows1 >= 0
        base = parts[1].partition.base_row
        assert (rows1[valid] >= base).all()
        assert (rows1[valid] < base + parts[1].partition.block_rows).all()
    assert built[0].num_entities == built[1].num_entities == len(
        parts[0].result.dataset.entity_vocabs["userId"]
    )
