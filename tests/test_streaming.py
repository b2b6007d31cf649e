"""Out-of-core streaming epochs (io/stream_reader.py +
algorithm/streaming.py): exact chunked objectives double-buffered behind
device compute.

Reference parity: function/glm/DistributedGLMLossFunction.scala:91-135 —
the reference's treeAggregate over partitions that never co-reside in one
machine's memory. The correctness backbone here mirrors the repo's other
opt-in layers: streaming OFF is bitwise-identical to the in-core path,
streaming ON agrees with the in-core solve to float round-off on dense AND
hybrid-sparse fixtures, the chunked accumulator is sharding-invariant
(1 == 8 devices), and the chunk count is a layout choice, not a semantic
one (1 chunk == N chunks to round-off).
"""

from __future__ import annotations

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.algorithm.streaming import (
    StreamingGLMObjective,
    streaming_summarize,
)
from photon_ml_tpu.data.batch import LabeledPointBatch, summarize
from photon_ml_tpu.data.sparse_batch import HybridPolicy, SparseLabeledPointBatch
from photon_ml_tpu.estimators import train_glm, train_glm_streaming
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io.stream_reader import (
    ArrayChunkSource,
    AvroChunkSource,
    ChunkPrefetcher,
    DenseRecordAssembler,
    SparseArrayChunkSource,
    build_streaming_index_maps,
    plan_chunks,
    plan_partitioned_stream,
)
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import BoundObjective, GLMObjective
from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.telemetry import stream_counters
from photon_ml_tpu.types import TaskType

SCHEMA = {
    "type": "record",
    "name": "TrainingExampleAvro",
    "fields": [
        {"name": "uid", "type": ["string", "null"], "default": None},
        {"name": "label", "type": "double"},
        {
            "name": "features",
            "type": {
                "type": "array",
                "items": {
                    "type": "record",
                    "name": "FeatureAvro",
                    "fields": [
                        {"name": "name", "type": "string"},
                        {"name": "term", "type": ["string", "null"],
                         "default": None},
                        {"name": "value", "type": "double"},
                    ],
                },
            },
        },
        {"name": "weight", "type": ["double", "null"], "default": None},
        {"name": "offset", "type": ["double", "null"], "default": None},
    ],
}


def _dense_data(n=240, d=6, seed=3, dtype=np.float64):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=d)
    x = rng.normal(size=(n, d)).astype(dtype)
    p = 1.0 / (1.0 + np.exp(-3.0 * (x @ w.astype(dtype))))
    y = (rng.random(n) < p).astype(dtype)
    offsets = (0.1 * rng.normal(size=n)).astype(dtype)
    weights = rng.uniform(0.5, 2.0, size=n).astype(dtype)
    return x, y, offsets, weights


def _avro_records(n=200, d=5, seed=7):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=d)
    recs = []
    for i in range(n):
        x = rng.normal(size=d)
        y = 1.0 if rng.random() < 1 / (1 + np.exp(-3 * float(x @ w))) else 0.0
        recs.append({
            "uid": str(i),
            "label": y,
            "features": [
                {"name": f"f{j}", "term": "", "value": float(x[j])}
                for j in range(d)
            ],
            "weight": float(rng.uniform(0.5, 2.0)),
            "offset": float(0.1 * rng.normal()),
        })
    return recs


def _write_avro_dir(tmp_path, records, *, parts=1, block_records=32):
    data = tmp_path / "train"
    os.makedirs(data, exist_ok=True)
    per = (len(records) + parts - 1) // parts
    for p in range(parts):
        avro_io.write_container(
            str(data / f"part-{p:05d}.avro"), SCHEMA,
            records[p * per:(p + 1) * per], block_records=block_records,
        )
    return str(data)


# ---------------------------------------------------------------------------
# chunk planning
# ---------------------------------------------------------------------------


class TestPlanChunks:
    def test_groups_contiguous_blocks_into_budgeted_chunks(self, tmp_path):
        path = _write_avro_dir(tmp_path, _avro_records(100), block_records=10)
        files = avro_io.list_avro_files(path)
        specs, indexes = plan_chunks(files, 25)
        assert sum(s.num_records for s in specs) == 100
        assert all(s.num_records <= 25 for s in specs)
        # contiguous runs: one file, consecutive blocks -> one run per chunk
        for s in specs:
            assert len(s.runs) == 1
        # chunk indexes are the plan order
        assert [s.index for s in specs] == list(range(len(specs)))

    def test_over_budget_block_forms_its_own_chunk(self, tmp_path):
        path = _write_avro_dir(tmp_path, _avro_records(60), block_records=30)
        files = avro_io.list_avro_files(path)
        specs, _ = plan_chunks(files, 10)  # budget < block: atomic unit wins
        assert [s.num_records for s in specs] == [30, 30]

    def test_block_subset_plans_only_assigned_blocks(self, tmp_path):
        path = _write_avro_dir(tmp_path, _avro_records(100), block_records=10)
        files = avro_io.list_avro_files(path)
        _, indexes = plan_chunks(files, 100)
        subset = [(0, 1), (0, 2), (0, 5)]  # a gap: (0,2) -> (0,5)
        specs, _ = plan_chunks(files, 100, indexes=indexes,
                               block_subset=subset)
        assert sum(s.num_records for s in specs) == 30
        # the gap splits the seek ranges
        assert [(start, cnt) for _, start, cnt in specs[0].runs] == [
            (1, 2), (5, 1)
        ]

    def test_rejects_nonpositive_budget(self, tmp_path):
        path = _write_avro_dir(tmp_path, _avro_records(10))
        with pytest.raises(ValueError, match="positive"):
            plan_chunks(avro_io.list_avro_files(path), 0)


# ---------------------------------------------------------------------------
# streaming OFF identity: the chunked assembler builds the in-core arrays
# ---------------------------------------------------------------------------


class TestInCoreIdentity:
    def test_assembled_chunks_bitwise_match_full_read(self, tmp_path):
        """One epoch's chunks, concatenated, are BYTE-identical to the
        in-core read — same index maps, same per-record semantics, same
        f32 scatter."""
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            read_merged,
        )

        records = _avro_records(120, d=5)
        path = _write_avro_dir(tmp_path, records, parts=2, block_records=16)
        cfg = {"features": FeatureShardConfiguration(feature_bags=("features",))}
        full = read_merged(path, cfg)
        files = avro_io.list_avro_files(path)
        imaps = build_streaming_index_maps(files, cfg)
        # identical vocabulary resolution
        assert imaps["features"].size == full.index_maps["features"].size
        source = AvroChunkSource(
            files, DenseRecordAssembler(imaps["features"], cfg["features"]),
            chunk_records=40,
        )
        rows, labels, offsets, weights = [], [], [], []
        with ChunkPrefetcher(source, prefetch=False) as chunks:
            for batch, spec in zip(chunks, source.specs):
                n = spec.num_records
                rows.append(np.asarray(batch.features)[:n])
                labels.append(np.asarray(batch.labels)[:n])
                offsets.append(np.asarray(batch.offsets)[:n])
                weights.append(np.asarray(batch.weights)[:n])
        ds = full.dataset
        np.testing.assert_array_equal(
            np.concatenate(rows),
            np.asarray(ds.feature_shards["features"]),
        )
        np.testing.assert_array_equal(
            np.concatenate(labels), np.asarray(ds.labels))
        np.testing.assert_array_equal(
            np.concatenate(offsets), np.asarray(ds.offsets))
        np.testing.assert_array_equal(
            np.concatenate(weights), np.asarray(ds.weights))

    def test_host_loop_solver_matches_compiled_loop(self):
        """host_loop=True runs the IDENTICAL body math from Python — on an
        in-core objective the two drivers agree to round-off, and the
        default (host_loop absent) is the unchanged compiled path."""
        x, y, offsets, weights = _dense_data()
        batch = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(y),
            offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
        )
        objective = BoundObjective(
            GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION), 0.1),
            batch,
        )
        from photon_ml_tpu.optim.optimizer import solve

        cfg = OptimizerConfig(max_iterations=25)
        w0 = jnp.zeros((x.shape[1],), jnp.float64)
        compiled = solve(cfg, objective, w0)
        hosted = solve(cfg, objective, w0, host_loop=True)
        np.testing.assert_allclose(
            np.asarray(hosted.coefficients), np.asarray(compiled.coefficients),
            rtol=1e-9, atol=1e-9,
        )
        assert int(hosted.iterations) == int(compiled.iterations)


# ---------------------------------------------------------------------------
# streaming vs in-core agreement
# ---------------------------------------------------------------------------


class TestStreamingAgreement:
    def test_value_grad_hv_match_incore_dense(self):
        x, y, offsets, weights = _dense_data()
        batch = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(y),
            offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
        )
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        incore = BoundObjective(GLMObjective(loss, 0.3), batch)
        source = ArrayChunkSource(
            x, y, offsets=offsets, weights=weights, chunk_rows=64,
        )
        streamed = StreamingGLMObjective(source, loss, l2_weight=0.3)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=x.shape[1]))
        v = jnp.asarray(rng.normal(size=x.shape[1]))
        f_i, g_i = incore.value_and_grad(w)
        f_s, g_s = streamed.value_and_grad(w)
        np.testing.assert_allclose(float(f_s), float(f_i), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_s), np.asarray(g_i), rtol=1e-11, atol=1e-11)
        np.testing.assert_allclose(
            np.asarray(streamed.hessian_vector(w, v)),
            np.asarray(incore.hessian_vector(w, v)),
            rtol=1e-11, atol=1e-11,
        )

    @pytest.mark.parametrize("opt_type,alpha", [
        (OptimizerType.LBFGS, 0.0),
        (OptimizerType.TRON, 0.0),
        (OptimizerType.LBFGS, 0.5),  # elastic net -> OWLQN path
    ])
    def test_trained_models_match_incore(self, opt_type, alpha):
        x, y, offsets, weights = _dense_data(n=192, d=5)
        batch = LabeledPointBatch(
            features=jnp.asarray(x), labels=jnp.asarray(y),
            offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
        )
        source = ArrayChunkSource(
            x, y, offsets=offsets, weights=weights, chunk_rows=48,
        )
        cfg = OptimizerConfig(optimizer_type=opt_type, max_iterations=40)
        kwargs = dict(
            optimizer=cfg,
            regularization_weights=(0.1, 1.0),
            elastic_net_alpha=alpha,
        )
        incore = train_glm(batch, TaskType.LOGISTIC_REGRESSION, **kwargs)
        streamed = train_glm_streaming(
            source, TaskType.LOGISTIC_REGRESSION, **kwargs)
        for lam in (0.1, 1.0):
            np.testing.assert_allclose(
                np.asarray(streamed[lam].coefficients.means),
                np.asarray(incore[lam].coefficients.means),
                rtol=2e-5, atol=2e-5,
            )

    def test_hybrid_sparse_stream_matches_dense_incore(self):
        """The sparse/hybrid chunk path agrees with the DENSE in-core
        objective on the densified matrix — layout and accumulation both
        covered by one ground truth."""
        rng = np.random.default_rng(11)
        n, d = 160, 40
        # power-law columns: a few hot, many cold
        nnz = 1400
        rows = rng.integers(0, n, size=nnz)
        cols = (rng.zipf(1.7, size=nnz) - 1) % d
        vals = rng.normal(size=nnz)
        dense = np.zeros((n, d))
        np.add.at(dense, (rows, cols), vals)
        y = (rng.random(n) < 0.5).astype(np.float64)
        weights = rng.uniform(0.5, 2.0, size=n)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        incore = BoundObjective(
            GLMObjective(loss, 0.2),
            LabeledPointBatch(
                features=jnp.asarray(dense), labels=jnp.asarray(y),
                offsets=jnp.zeros(n), weights=jnp.asarray(weights),
            ),
        )
        source = SparseArrayChunkSource(
            rows, cols, vals, y, dim=d, chunk_rows=48, weights=weights,
            hybrid=HybridPolicy(hot_cols=4, pad_multiple=4),
        )
        assert source.hybrid_policy.hot_ids is not None
        streamed = StreamingGLMObjective(source, loss, l2_weight=0.2)
        w = jnp.asarray(rng.normal(size=d))
        v = jnp.asarray(rng.normal(size=d))
        f_i, g_i = incore.value_and_grad(w)
        f_s, g_s = streamed.value_and_grad(w)
        np.testing.assert_allclose(float(f_s), float(f_i), rtol=1e-10)
        np.testing.assert_allclose(
            np.asarray(g_s), np.asarray(g_i), rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(
            np.asarray(streamed.hessian_vector(w, v)),
            np.asarray(incore.hessian_vector(w, v)),
            rtol=1e-9, atol=1e-9,
        )
        # and an end-to-end hybrid-sparse solve agrees with the dense one
        cfg = OptimizerConfig(max_iterations=30)
        dense_models = train_glm(
            incore.batch, TaskType.LOGISTIC_REGRESSION, optimizer=cfg,
            regularization_weights=(0.5,),
        )
        sparse_models = train_glm_streaming(
            source, TaskType.LOGISTIC_REGRESSION, optimizer=cfg,
            regularization_weights=(0.5,),
        )
        np.testing.assert_allclose(
            np.asarray(sparse_models[0.5].coefficients.means),
            np.asarray(dense_models[0.5].coefficients.means),
            rtol=2e-5, atol=2e-5,
        )

    def test_chunk_count_robustness_one_equals_many(self):
        """1 chunk == 6 chunks to round-off: the chunk budget is a memory
        layout choice, never a semantic one."""
        x, y, offsets, weights = _dense_data(n=180, d=5)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        rng = np.random.default_rng(5)
        w = jnp.asarray(rng.normal(size=x.shape[1]))
        results = []
        for chunk_rows in (180, 30):
            source = ArrayChunkSource(
                x, y, offsets=offsets, weights=weights,
                chunk_rows=chunk_rows,
            )
            obj = StreamingGLMObjective(source, loss, l2_weight=0.1)
            f, g = obj.value_and_grad(w)
            models = train_glm_streaming(
                source, TaskType.LOGISTIC_REGRESSION,
                optimizer=OptimizerConfig(max_iterations=30),
                regularization_weights=(0.1,),
            )
            results.append(
                (float(f), np.asarray(g),
                 np.asarray(models[0.1].coefficients.means))
            )
        (f1, g1, m1), (fn, gn, mn) = results
        np.testing.assert_allclose(fn, f1, rtol=1e-12)
        np.testing.assert_allclose(gn, g1, rtol=1e-11, atol=1e-12)
        np.testing.assert_allclose(mn, m1, rtol=2e-6, atol=2e-6)

    def test_streaming_summarize_matches_incore(self):
        x, y, offsets, weights = _dense_data(n=150, d=7)
        source = ArrayChunkSource(
            x, y, offsets=offsets, weights=weights, chunk_rows=40,
        )
        stats = streaming_summarize(source)
        ref = summarize(x, weights)
        np.testing.assert_allclose(stats["mean"], np.asarray(ref["mean"]),
                                   rtol=1e-10)
        np.testing.assert_allclose(
            stats["variance"], np.asarray(ref["variance"]), rtol=1e-10)
        np.testing.assert_allclose(
            stats["max_magnitude"], np.asarray(ref["max_magnitude"]),
            rtol=1e-12)


# ---------------------------------------------------------------------------
# sharding invariance of the chunked accumulator
# ---------------------------------------------------------------------------


class TestShardingInvariance:
    @pytest.mark.parametrize("devices", [1, 8])
    def test_accumulator_identical_across_mesh_sizes(self, devices):
        from jax.sharding import Mesh

        x, y, offsets, weights = _dense_data(n=192, d=6)
        mesh = Mesh(
            np.asarray(jax.devices()[:devices]).reshape(devices), ("data",)
        )
        source = ArrayChunkSource(
            x, y, offsets=offsets, weights=weights, chunk_rows=64,
        )
        obj = StreamingGLMObjective(
            source, loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=0.2, mesh=mesh,
        )
        rng = np.random.default_rng(9)
        w = jnp.asarray(rng.normal(size=x.shape[1]))
        f, g = obj.value_and_grad(w)
        hv = obj.hessian_vector(w, jnp.asarray(rng.normal(size=x.shape[1])))
        # reference: unsharded accumulation
        ref = StreamingGLMObjective(
            source, loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=0.2,
        )
        rng = np.random.default_rng(9)
        w_r = jnp.asarray(rng.normal(size=x.shape[1]))
        f_r, g_r = ref.value_and_grad(w_r)
        hv_r = ref.hessian_vector(
            w_r, jnp.asarray(rng.normal(size=x.shape[1])))
        np.testing.assert_allclose(float(f), float(f_r), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_r),
                                   rtol=1e-11, atol=1e-12)
        np.testing.assert_allclose(np.asarray(hv), np.asarray(hv_r),
                                   rtol=1e-11, atol=1e-12)


# ---------------------------------------------------------------------------
# prefetch overlap + telemetry
# ---------------------------------------------------------------------------


class TestPrefetchOverlap:
    def test_prefetch_on_off_bitwise_identical(self):
        x, y, offsets, weights = _dense_data(n=160, d=5)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        w = jnp.asarray(np.random.default_rng(2).normal(size=x.shape[1]))
        outs = []
        for prefetch in (True, False):
            source = ArrayChunkSource(
                x, y, offsets=offsets, weights=weights, chunk_rows=40,
            )
            obj = StreamingGLMObjective(
                source, loss, l2_weight=0.1, prefetch=prefetch)
            f, g = obj.value_and_grad(w)
            outs.append((float(f), np.asarray(g)))
        assert outs[0][0] == outs[1][0]
        np.testing.assert_array_equal(outs[0][1], outs[1][1])

    def test_overlap_fraction_nonzero_and_on_beats_off(self):
        """decode 2 ms/chunk behind an 8 ms/chunk consumer (the consumer
        sleep stands in for the consumer's synchronous per-chunk work —
        the host-to-device transfer and step): after the first chunk
        every decode hides entirely, so overlap is decisively nonzero and
        the prefetch-ON epoch is strictly faster than the inline OFF
        epoch — the acceptance-criterion evidence path, d=512 and
        n >> chunk budget."""
        x, y, _, _ = _dense_data(n=160, d=512)
        epoch_ms = {}
        for prefetch in (True, False):
            source = ArrayChunkSource(
                x, y, chunk_rows=20, decode_hook=lambda: time.sleep(0.002),
            )
            stream_counters.reset_stream_metrics()
            t0 = time.perf_counter()
            with ChunkPrefetcher(source, prefetch=prefetch) as chunks:
                for _ in chunks:
                    time.sleep(0.008)  # the blocking consume step
            epoch_ms[prefetch] = (time.perf_counter() - t0) * 1e3
            if prefetch:
                assert stream_counters.overlap_fraction() > 0.2
                assert stream_counters.chunks_per_epoch() == source.num_chunks
                assert stream_counters.chunk_decode_summary()["count"] == (
                    source.num_chunks
                )
        # OFF pays every decode serially; ON hides all but the first
        assert epoch_ms[True] < epoch_ms[False]

    def test_prefetch_off_reports_zero_overlap(self):
        x, y, _, _ = _dense_data(n=80, d=4)
        source = ArrayChunkSource(x, y, chunk_rows=20)
        stream_counters.reset_stream_metrics()
        with ChunkPrefetcher(source, prefetch=False) as chunks:
            for _ in chunks:
                pass
        assert stream_counters.overlap_fraction() == 0.0

    def test_reset_stream_metrics_clears(self):
        stream_counters.set_overlap_fraction(0.5)
        stream_counters.set_chunks_per_epoch(3)
        stream_counters.record_chunk_decode_ms(1.0)
        stream_counters.reset_stream_metrics()
        assert stream_counters.overlap_fraction() == 0.0
        assert stream_counters.chunks_per_epoch() == 0
        assert stream_counters.chunk_decode_summary()["count"] == 0


# ---------------------------------------------------------------------------
# --partitioned-io composition: per-rank prefetchers, exchanged sums
# ---------------------------------------------------------------------------


class TestPartitionedComposition:
    def test_rank_plans_are_disjoint_and_agree(self, tmp_path):
        from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        records = _avro_records(160, d=5)
        path = _write_avro_dir(tmp_path, records, parts=2, block_records=16)
        cfg = {"features": FeatureShardConfiguration(feature_bags=("features",))}
        exchanges = InProcessExchange.create_group(2)
        results = [None, None]
        errors = []

        def run(r):
            try:
                results[r] = plan_partitioned_stream(
                    path, cfg, exchange=exchanges[r], chunk_records=40,
                )
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append((r, e))

        threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        (src0, maps0, int0), (src1, maps1, int1) = results
        # identical globally-agreed vocabulary on both ranks
        assert maps0["features"].size == maps1["features"].size
        assert int0 == int1
        # disjoint cover: every record streamed exactly once across ranks
        assert src0.total_records + src1.total_records == 160
        assert src0.total_records > 0 and src1.total_records > 0

    def test_partitioned_streaming_train_matches_single_rank(self, tmp_path):
        from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        records = _avro_records(160, d=5)
        path = _write_avro_dir(tmp_path, records, parts=2, block_records=16)
        cfg = {"features": FeatureShardConfiguration(feature_bags=("features",))}

        # single-rank reference: full-input chunk source, no exchange
        files = avro_io.list_avro_files(path)
        imaps = build_streaming_index_maps(files, cfg)
        full_source = AvroChunkSource(
            files, DenseRecordAssembler(imaps["features"], cfg["features"]),
            chunk_records=40,
        )
        opt = OptimizerConfig(max_iterations=25)
        ref = train_glm_streaming(
            full_source, TaskType.LOGISTIC_REGRESSION, optimizer=opt,
            regularization_weights=(0.1,),
        )

        exchanges = InProcessExchange.create_group(2)
        results = [None, None]
        errors = []

        def run(r):
            try:
                source, _maps, intercepts = plan_partitioned_stream(
                    path, cfg, exchange=exchanges[r], chunk_records=40,
                )
                results[r] = train_glm_streaming(
                    source, TaskType.LOGISTIC_REGRESSION, optimizer=opt,
                    regularization_weights=(0.1,),
                    intercept_index=intercepts.get("features"),
                    exchange=exchanges[r],
                )
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append((r, e))

        threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        m0 = np.asarray(results[0][0.1].coefficients.means)
        m1 = np.asarray(results[1][0.1].coefficients.means)
        # every rank computes the identical rank-ordered f64 sum
        np.testing.assert_array_equal(m0, m1)
        np.testing.assert_allclose(
            m0, np.asarray(ref[0.1].coefficients.means), rtol=2e-5, atol=2e-5,
        )


# ---------------------------------------------------------------------------
# driver path
# ---------------------------------------------------------------------------


class TestStreamingDriver:
    def _run(self, path, out, extra=()):
        from photon_ml_tpu.cli import glm_driver

        return glm_driver.main([
            "--input-data-path", path,
            "--output-dir", str(out),
            "--task-type", "LOGISTIC_REGRESSION",
            "--regularization-weights", "0.1",
            "--max-iterations", "40",
            *extra,
        ])

    def test_driver_streaming_matches_incore(self, tmp_path):
        path = _write_avro_dir(
            tmp_path, _avro_records(200, d=5), block_records=25)
        incore = self._run(path, tmp_path / "a")
        streamed = self._run(
            path, tmp_path / "b", ["--streaming-chunks", "50"])
        np.testing.assert_allclose(
            np.asarray(streamed.models[0.1].coefficients.means),
            np.asarray(incore.models[0.1].coefficients.means),
            rtol=1e-3, atol=1e-3,  # driver trains in f32
        )

    def test_driver_journals_stream_evidence(self, tmp_path):
        import json

        path = _write_avro_dir(
            tmp_path, _avro_records(120, d=4), block_records=20)
        tel = tmp_path / "tel"
        self._run(path, tmp_path / "out", [
            "--streaming-chunks", "30", "--telemetry-dir", str(tel),
        ])
        rows = []
        for f in os.listdir(tel):
            with open(tel / f) as fh:
                rows += [json.loads(line) for line in fh if line.strip()]
        metrics = [r for r in rows if r.get("kind") == "metrics"]
        assert metrics, rows
        names = set()
        for m in metrics:
            snap = m.get("snapshot", {})
            names.update(snap.get("gauges", {}))
            names.update(snap.get("histograms", {}))
        assert stream_counters.OVERLAP_FRACTION in names
        assert stream_counters.CHUNKS_PER_EPOCH in names
        assert stream_counters.CHUNK_DECODE_MS in names
        config = [r for r in rows if r.get("kind") == "config"]
        assert config and config[0]["streaming_chunks"] == 30

    @pytest.mark.parametrize("extra,match", [
        (["--grid-parallel"], "grid"),
        (["--optimizer", "NEWTON"], "TRON"),
        (["--input-format", "libsvm"], "Avro"),
        (["--compute-variance"], "variance"),
    ])
    def test_driver_rejects_unsupported_combinations(
            self, tmp_path, extra, match):
        path = _write_avro_dir(tmp_path, _avro_records(40, d=4))
        with pytest.raises(ValueError, match=match):
            self._run(path, tmp_path / "out",
                      ["--streaming-chunks", "20", *extra])
