"""Resident scoring service (ISSUE 10): shape-bucketed micro-batch scores
must be BITWISE identical to DistributedScorer.score_dataset on the
unpadded rows (dense, ELL, and hybrid layouts), bucket misses must split
instead of compiling, the compiled-signature count must stay bounded by
the configured bucket set across a long replay, and the micro-batched loop
must beat one-request-per-dispatch on the replay fixture — the serving
layer is strictly additive (reference GameTransformer.scala:156-203 is a
batch path; the resident path is its online counterpart)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.game_data import (
    build_game_dataset,
    concat_game_datasets,
    slice_game_dataset,
)
from photon_ml_tpu.data.sparse_batch import HybridPolicy, SparseShard
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.parallel.scoring import DistributedScorer
from photon_ml_tpu.serving import (
    MicroBatchServer,
    ResidentScorer,
    ServeError,
)
from photon_ml_tpu.telemetry import serving_counters
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.types import TaskType


def _glm(w):
    return GeneralizedLinearModel(
        Coefficients(means=jnp.asarray(np.asarray(w, np.float32))),
        TaskType.LINEAR_REGRESSION,
    )


def _dense_fixture(n=37, seed=0, d=12, d_re=4, n_ent=9):
    r = np.random.default_rng(seed)
    users = np.array([f"u{i}" for i in r.integers(0, n_ent, size=n)])
    ds = build_game_dataset(
        labels=r.normal(size=n).astype(np.float32),
        feature_shards={
            "g": r.normal(size=(n, d)).astype(np.float32),
            "u": r.normal(size=(n, d_re)).astype(np.float32),
        },
        entity_keys={"userId": users},
        offsets=r.normal(scale=0.1, size=n).astype(np.float32),
    )
    model = GameModel(models={
        "fe": FixedEffectModel(glm=_glm(r.normal(size=d)),
                               feature_shard_id="g"),
        "re": RandomEffectModel(
            coefficients=jnp.asarray(
                r.normal(size=(n_ent, d_re)).astype(np.float32)
            ),
            entity_keys=ds.entity_vocabs["userId"],
            random_effect_type="userId",
            feature_shard_id="u",
            task=TaskType.LINEAR_REGRESSION,
        ),
    })
    return ds, model


def _sparse_fixture(n=53, seed=3, d=4000, per_row=6, hybrid=None):
    r = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n), per_row)
    cols = r.integers(0, d, size=n * per_row)
    vals = r.normal(size=n * per_row).astype(np.float32)
    shard = SparseShard(
        rows=rows, cols=cols, vals=vals, num_samples=n, feature_dim=d,
        hybrid_policy=hybrid,
    )
    ds = build_game_dataset(
        labels=r.normal(size=n).astype(np.float32),
        feature_shards={"giant": shard},
        offsets=r.normal(scale=0.1, size=n).astype(np.float32),
    )
    model = GameModel(models={
        "fe": FixedEffectModel(
            glm=_glm(r.normal(size=d) / np.sqrt(d)), feature_shard_id="giant"
        ),
    })
    return ds, model


class TestShapeBucketCorrectness:
    """The correctness pin: padded micro-batch == unpadded batch scorer,
    bitwise, per layout."""

    def test_dense_bitwise(self):
        ds, model = _dense_fixture()
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = ResidentScorer(model, shapes=(64, 256)).score(ds)
        assert got.dtype == ref.dtype
        assert np.array_equal(got, ref)

    def test_ell_sparse_bitwise(self):
        ds, model = _sparse_fixture()
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = ResidentScorer(model, shapes=(64,)).score(ds)
        assert np.array_equal(got, ref)

    def test_hybrid_sparse_bitwise(self):
        ds, model = _sparse_fixture(
            hybrid=HybridPolicy(hot_cols=8, label="serve_test")
        )
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = ResidentScorer(model, shapes=(64,)).score(ds)
        assert np.array_equal(got, ref)

    def test_every_bucket_bitwise(self):
        # each request size lands in a different bucket; all must agree
        ds, model = _dense_fixture(n=300, seed=1)
        scorer = ResidentScorer(model, shapes=(16, 64, 256))
        full_ref = DistributedScorer(model, None)
        for lo, hi in ((0, 9), (9, 60), (60, 300)):
            req = slice_game_dataset(ds, lo, hi)
            assert np.array_equal(scorer.score(req),
                                  full_ref.score_dataset(req))
        assert len(scorer.signatures) == 3

    def test_bucket_miss_splits_not_recompiles(self):
        ds, model = _dense_fixture(n=150, seed=2)
        scorer = ResidentScorer(model, shapes=(16, 32))
        got = scorer.score(ds)  # 150 rows >> 32: five 32-row chunks
        ref = DistributedScorer(model, None).score_dataset(ds)
        assert np.array_equal(got, ref)
        # only configured buckets compiled — the miss split, it did not
        # mint a 150-row signature
        assert {sig[0] for sig in scorer.signatures} <= {16, 32}
        assert (
            default_registry()
            .counter(serving_counters.BUCKET_SPLITS).value > 0
        )

    def test_mesh_matches_unpadded(self):
        from photon_ml_tpu.parallel.mesh import make_mesh

        ds, model = _dense_fixture(n=41, seed=4)
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = ResidentScorer(model, shapes=(64, 256),
                             mesh=make_mesh()).score(ds)
        assert np.array_equal(got, ref)

    def test_bf16_close_not_required_bitwise(self):
        ds, model = _dense_fixture(n=40, seed=5)
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = ResidentScorer(model, shapes=(64,), bf16=True).score(ds)
        assert got.dtype == np.float32
        assert np.allclose(got, ref, rtol=5e-2, atol=5e-2)

    def test_rejects_non_pow2_shapes(self):
        _, model = _dense_fixture(n=8)
        with pytest.raises(ValueError, match="power of two"):
            ResidentScorer(model, shapes=(48,))


class TestDatasetSliceConcat:
    def test_round_trip(self):
        ds, _ = _dense_fixture(n=45, seed=6)
        parts = [slice_game_dataset(ds, lo, min(lo + 7, 45))
                 for lo in range(0, 45, 7)]
        back = concat_game_datasets(parts)
        for name in ("labels", "offsets", "weights"):
            assert np.array_equal(back.host_array(name),
                                  ds.host_array(name))
        assert np.array_equal(back.host_array("shard/g"),
                              ds.host_array("shard/g"))
        assert np.array_equal(back.host_array("entity_idx/userId"),
                              ds.host_array("entity_idx/userId"))
        assert np.array_equal(back.unique_ids, ds.unique_ids)

    def test_sparse_round_trip(self):
        ds, model = _sparse_fixture(n=30, seed=7)
        parts = [slice_game_dataset(ds, lo, lo + 10) for lo in (0, 10, 20)]
        back = concat_game_datasets(parts)
        ref = DistributedScorer(model, None).score_dataset(ds)
        got = DistributedScorer(model, None).score_dataset(back)
        assert np.array_equal(got, ref)

    def test_vocab_mismatch_rejected(self):
        ds, _ = _dense_fixture(n=20, seed=8)
        other, _ = _dense_fixture(n=20, seed=8, n_ent=5)
        with pytest.raises(ValueError, match="entity vocab"):
            concat_game_datasets([ds, other])


class TestMicroBatchServer:
    def test_coalesces_and_matches_bitwise(self):
        serving_counters.reset_serving_metrics()
        ds, model = _dense_fixture(n=60, seed=9)
        ref = DistributedScorer(model, None).score_dataset(ds)
        scorer = ResidentScorer(model, shapes=(64, 256))
        parts = [slice_game_dataset(ds, lo, lo + 5) for lo in range(0, 60, 5)]
        with MicroBatchServer(scorer, max_wait_ms=50) as server:
            futures = [server.submit(p) for p in parts]
            got = np.concatenate([f.result(30) for f in futures])
        assert np.array_equal(got, ref)
        reg = default_registry()
        # coalesced: far fewer dispatches than requests
        assert (reg.counter(serving_counters.BATCHES).value
                < reg.counter(serving_counters.REQUESTS).value)
        assert reg.histogram(serving_counters.LATENCY_MS).count >= len(parts)

    def test_flushes_on_max_batch_rows(self):
        ds, model = _dense_fixture(n=64, seed=10)
        scorer = ResidentScorer(model, shapes=(16, 32))
        serving_counters.reset_serving_metrics()
        parts = [slice_game_dataset(ds, lo, lo + 8) for lo in range(0, 64, 8)]
        with MicroBatchServer(scorer, max_wait_ms=500,
                              max_batch_rows=16) as server:
            futures = [server.submit(p) for p in parts]
            for f in futures:
                f.result(30)
        # 64 rows / 16-row budget: at least 4 dispatches, none waited the
        # full 500 ms (the max-batch flush fired first)
        assert default_registry().counter(
            serving_counters.BATCHES
        ).value >= 4

    def test_submit_after_stop_rejected(self):
        ds, model = _dense_fixture(n=8, seed=11)
        scorer = ResidentScorer(model, shapes=(16,))
        server = MicroBatchServer(scorer)
        server.start()
        server.stop()
        with pytest.raises(ServeError, match="not running"):
            server.submit(ds)

    def test_stop_fails_queued_futures_typed(self):
        ds, model = _dense_fixture(n=8, seed=12)
        scorer = ResidentScorer(model, shapes=(16,))
        server = MicroBatchServer(scorer, max_wait_ms=1.0)
        # never started: enqueue directly, then stop() must fail them
        server._thread = object()  # pretend running for submit()
        fut = None
        try:
            fut = server.submit(ds)
        finally:
            server._thread = None
        server.stop()
        with pytest.raises(ServeError, match="server stopped"):
            fut.result(1)


class TestBoundedCompilesAndThroughput:
    def test_compile_count_bounded_over_1000_request_replay(self):
        from photon_ml_tpu.telemetry.probes import CompileMonitor

        ds, model = _dense_fixture(n=256, seed=13, d=16)
        shapes = (64, 256)
        scorer = ResidentScorer(model, shapes=shapes)
        scorer.warm(ds)
        requests = [
            slice_game_dataset(ds, i % 128, i % 128 + np.random.default_rng(i)
                               .integers(1, 5))
            for i in range(1000)
        ]
        with CompileMonitor() as cm:
            with MicroBatchServer(scorer, max_wait_ms=1.0) as server:
                futures = [server.submit(r) for r in requests]
                for f in futures:
                    f.result(60)
        # the whole 1000-request replay rides the warmed signatures: the
        # per-signature compile count is bounded by the bucket set (zero
        # NEW compiles here — warm() already built them)
        assert cm.count == 0, f"{cm.count} compiles during replay"
        assert len(scorer.signatures) <= len(shapes)

    def test_microbatched_beats_one_request_per_dispatch(self):
        import time

        ds, model = _dense_fixture(n=512, seed=14, d=128)
        scorer = ResidentScorer(model, shapes=(64, 256))
        requests = [slice_game_dataset(ds, i, i + 1) for i in range(512)]
        scorer.warm(requests[0])
        t0 = time.perf_counter()
        for r in requests:
            scorer.score(r)
        unbatched = time.perf_counter() - t0
        with MicroBatchServer(scorer, max_wait_ms=2.0) as server:
            t0 = time.perf_counter()
            futures = [server.submit(r) for r in requests]
            for f in futures:
                f.result(60)
            batched = time.perf_counter() - t0
        assert batched < unbatched, (
            f"micro-batched replay {batched:.3f}s did not beat "
            f"one-request-per-dispatch {unbatched:.3f}s"
        )

    def test_pad_fraction_and_signature_gauges(self):
        serving_counters.reset_serving_metrics()
        ds, model = _dense_fixture(n=10, seed=15)
        scorer = ResidentScorer(model, shapes=(16,))
        scorer.score(ds)
        reg = default_registry()
        assert reg.counter(serving_counters.ROWS).value == 10
        assert reg.counter(serving_counters.PADDED_ROWS).value == 6
        assert serving_counters.pad_fraction() == pytest.approx(6 / 16)
        assert reg.gauge(
            serving_counters.COMPILED_SIGNATURES
        ).value == 1
        serving_counters.reset_serving_metrics()
        assert reg.counter(serving_counters.ROWS).value == 0


class TestServeDriver:
    @pytest.fixture(scope="class")
    def trained(self, tmp_path_factory):
        from photon_ml_tpu.cli import game_training_driver
        from tests.test_cli import _write_game_avro

        base = tmp_path_factory.mktemp("serve-driver")
        _write_game_avro(base / "train", 300, seed=0)
        _write_game_avro(base / "req", 120, seed=1)
        game_training_driver.main([
            "--input-data-path", str(base / "train"),
            "--root-output-dir", str(base / "out"),
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=1.0,max.iter=10",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,random.effect.type=userId,"
            "reg.weights=0.1,max.iter=10",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ])
        return base

    def test_replay_end_to_end(self, trained, tmp_path):
        import json
        import os

        from photon_ml_tpu.cli import serve_driver

        out = tmp_path / "serve"
        s = serve_driver.main([
            "--requests-avro", str(trained / "req"),
            "--model-input-dir", str(trained / "out" / "best"),
            "--output-dir", str(out),
            "--microbatch-shapes", "32,128",
            "--request-rows", "4",
            "--max-wait-ms", "5",
            "--telemetry-dir", str(out / "telemetry"),
        ])
        assert s["num_requests"] == 30
        assert s["num_rows"] == 120
        assert s["scores_per_sec"] > 0
        assert np.isfinite(s["latency_ms_p95"])
        assert s["compiled_signatures"] <= 2
        assert s["replay_compiles"] == 0  # warm() built every signature
        assert os.path.exists(out / "serving-summary.json")
        assert s["runtime"]["platform"] == "cpu" and "requests" in s["decode_paths"]
        # the replay's answers are written out like the scoring driver's
        from photon_ml_tpu.io.model_io import read_scores

        served = read_scores(out / "scores")
        assert len(served) == 120
        assert all(np.isfinite(r["predictionScore"]) for r in served)
        journal_dir = out / "telemetry"
        files = os.listdir(journal_dir)
        assert any(f.endswith(".jsonl") for f in files)
        rows = []
        for f in files:
            if f.endswith(".jsonl"):
                with open(journal_dir / f) as fh:
                    rows += [json.loads(line) for line in fh]
        kinds = {r.get("kind") for r in rows}
        assert "serving_summary" in kinds
        assert "metrics" in kinds or "registry" in kinds or len(kinds) > 1
        text = json.dumps(rows)
        assert "serve/latency_ms" in text
        assert "serve/requests" in text
        # the program ledger rides --telemetry-dir (ISSUE 13): the warm
        # compiles journal phase-stamped program rows under serve/score,
        # and the summary carries the per-label snapshot — with zero
        # replay compiles, every compile row is phase "warm"
        compile_rows = [r for r in rows if r.get("kind") == "program_compile"]
        serve_rows = [r for r in compile_rows
                      if r.get("label") == "serve/score"]
        assert serve_rows, kinds
        assert all(r.get("phase") == "warm" for r in serve_rows)
        assert s["program_compiles"]["serve/score"]["compiles"] >= 1
        assert s["program_compiles"]["serve/score"]["recompiles"] >= 1

    def test_matches_scoring_driver_bitwise(self, trained, tmp_path):
        """The resident path and the batch scorer agree on the replay
        fixture (same model, same data, both unpadded at the edges)."""
        from photon_ml_tpu.cli.game_scoring_driver import (
            _load_scoring_model,
        )
        from photon_ml_tpu.data.game_data import slice_game_dataset
        from photon_ml_tpu.io.partitioned_reader import read_partitioned

        model, index_maps, shards, vocabs, re_cols = _load_scoring_model(
            model_input_dir=str(trained / "out" / "best"),
            index_maps_dir=None,
            feature_shards=None,
            compact_random_effect_threshold=100000,
        )
        ds = read_partitioned(
            str(trained / "req"), shards, index_maps=index_maps,
            random_effect_id_columns=re_cols, entity_vocabs=vocabs,
        ).result.dataset
        ref = DistributedScorer(model, None).score_dataset(ds)
        scorer = ResidentScorer(model, shapes=(32, 128))
        with MicroBatchServer(scorer, max_wait_ms=20) as server:
            futures = [
                server.submit(slice_game_dataset(ds, lo, lo + 4))
                for lo in range(0, ds.num_samples, 4)
            ]
            got = np.concatenate([f.result(30) for f in futures])
        assert np.array_equal(got, ref)

    def test_swap_poll_continuous_applies_and_rejects_typed(
            self, trained, tmp_path):
        """ROADMAP item 2 rider (ISSUE 15 satellite): --swap-poll-ms
        watches --swap-model-dir for atomically-renamed model dirs and
        hot-swaps each continuously through the guarded swap API; an
        unloadable publish is rejected TYPED (model_swap journal row) and
        the replay keeps serving — zero dropped requests either way."""
        import json
        import os
        import shutil

        from photon_ml_tpu.cli import serve_driver

        watch = tmp_path / "watch"
        os.makedirs(watch)
        # the atomic-rename publish discipline: stage under tmp.*, rename
        staged = watch / "tmp.m1"
        shutil.copytree(trained / "out" / "best", staged)
        os.rename(staged, watch / "model-0001")
        # a bad publish (no model files) — must reject typed, keep serving
        os.makedirs(watch / "model-0002")
        out = tmp_path / "serve"
        s = serve_driver.run(
            requests_avro=str(trained / "req"),
            model_input_dir=str(trained / "out" / "best"),
            output_dir=str(out),
            microbatch_shapes="32,128",
            request_rows=4,
            max_wait_ms=5,
            skip_unbatched_baseline=True,
            swap_model_dir=str(watch),
            swap_poll_ms=5,
            telemetry_dir=str(out / "telemetry"),
        )
        assert s["num_rows"] == 120  # every request served
        assert s["swap"]["mode"] == "poll"
        assert "model-0001" in s["swap"]["applied"]
        rejected = {r["dir"] for r in s["swap"]["rejected"]}
        assert "model-0002" in rejected
        rows = []
        for f in os.listdir(out / "telemetry"):
            if f.endswith(".jsonl"):
                with open(out / "telemetry" / f) as fh:
                    rows += [json.loads(line) for line in fh]
        swaps = [r for r in rows if r.get("kind") == "model_swap"]
        assert {(r["dir"], r["applied"]) for r in swaps} >= {
            ("model-0001", True), ("model-0002", False)
        }
        assert all("error" in r for r in swaps if not r["applied"])

    def test_rejects_bad_shapes_and_rows(self, trained, tmp_path):
        from photon_ml_tpu.cli import serve_driver

        with pytest.raises(ValueError, match="request_rows"):
            serve_driver.run(
                requests_avro=str(trained / "req"),
                model_input_dir=str(trained / "out" / "best"),
                output_dir=str(tmp_path / "x"),
                request_rows=0,
            )
        with pytest.raises(ValueError, match="power of two"):
            serve_driver.run(
                requests_avro=str(trained / "req"),
                model_input_dir=str(trained / "out" / "best"),
                output_dir=str(tmp_path / "y"),
                microbatch_shapes="48",
            )


class TestMultiDatasetScoringDriver:
    def test_model_loaded_once_across_datasets(self, tmp_path):
        """The small fix: several --input-data-path values score in one
        run with ONE model parse, writing per-dataset outputs."""
        import os

        from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
        from tests.test_cli import _write_game_avro

        base = tmp_path
        _write_game_avro(base / "train", 200, seed=0)
        _write_game_avro(base / "a", 40, seed=1)
        _write_game_avro(base / "b", 52, seed=2)
        game_training_driver.main([
            "--input-data-path", str(base / "train"),
            "--root-output-dir", str(base / "out"),
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=1.0,max.iter=8",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ])
        calls = {"n": 0}
        from photon_ml_tpu.io import model_io

        orig = model_io.load_game_model

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        model_io.load_game_model = counting
        # the driver imports the symbol at module load; patch there too
        game_scoring_driver.load_game_model = counting
        try:
            s = game_scoring_driver.main([
                "--input-data-path", str(base / "a"),
                "--input-data-path", str(base / "b"),
                "--model-input-dir", str(base / "out" / "best"),
                "--output-dir", str(base / "scores"),
            ])
        finally:
            model_io.load_game_model = orig
            game_scoring_driver.load_game_model = orig
        assert calls["n"] == 1, "model re-parsed per dataset"
        assert s["num_scored"] == 92
        assert s["num_datasets"] == 2
        assert [d["num_scored"] for d in s["datasets"]] == [40, 52]
        for i in range(2):
            sub = base / "scores" / f"dataset-{i:04d}"
            assert os.path.isdir(sub / "scores")
            assert os.path.exists(sub / "scoring-summary.json")
        assert os.path.exists(base / "scores" / "scoring-summary.json")


class TestHotSwap:
    """Zero-downtime resident-model refresh (ISSUE 14): a same-layout swap
    re-uses every compiled score program (ledger-pinned zero recompiles),
    serves both model versions' scores with zero dropped requests, and
    swap-then-score is bitwise a fresh scorer on the new model; a
    layout-changing swap is rejected typed — naming the differing leaves —
    with the loop still serving."""

    @staticmethod
    def _two_models(n=60, seed=20):
        ds, model_a = _dense_fixture(n=n, seed=seed)
        _, model_b = _dense_fixture(n=n, seed=seed + 77)
        # same fixture dims: equal layout, different coefficients
        return ds, model_a, model_b

    def test_same_layout_swap_zero_compiles_and_bitwise(self):
        from photon_ml_tpu.telemetry.probes import CompileMonitor

        ds, model_a, model_b = self._two_models()
        ref_a = DistributedScorer(model_a, None).score_dataset(ds)
        ref_b = DistributedScorer(model_b, None).score_dataset(ds)
        scorer = ResidentScorer(model_a, shapes=(64,))
        scorer.warm(ds)
        assert np.array_equal(scorer.score(ds), ref_a)
        with CompileMonitor() as cm:
            scorer.swap_model(model_b)
            got = scorer.score(ds)
        assert cm.count == 0, f"{cm.count} compiles across the swap"
        assert np.array_equal(got, ref_b)
        # swap-then-score == a fresh ResidentScorer on the new model
        fresh = ResidentScorer(model_b, shapes=(64,)).score(ds)
        assert np.array_equal(got, fresh)

    def test_ledger_pins_zero_recompiles_across_swap(self):
        from photon_ml_tpu.telemetry.program_ledger import (
            ProgramLedger,
            install_ledger,
            uninstall_ledger,
        )

        ds, model_a, model_b = self._two_models(seed=21)
        ledger = install_ledger(ProgramLedger())
        try:
            scorer = ResidentScorer(model_a, shapes=(64,))
            scorer.warm(ds)
            before = ledger.snapshot().get("serve/score", {})
            scorer.swap_model(model_b)
            scorer.score(ds)
            after = ledger.snapshot()["serve/score"]
            assert after["compiles"] == before.get("compiles", 0)
            assert after["signatures"] == before.get("signatures", 0)
        finally:
            uninstall_ledger()

    def test_mid_replay_swap_serves_both_versions_zero_dropped(self):
        serving_counters.reset_serving_metrics()
        ds, model_a, model_b = self._two_models(n=80, seed=22)
        ref_a = DistributedScorer(model_a, None).score_dataset(ds)
        ref_b = DistributedScorer(model_b, None).score_dataset(ds)
        scorer = ResidentScorer(model_a, shapes=(16, 64))
        parts = [slice_game_dataset(ds, lo, lo + 4) for lo in range(0, 80, 4)]
        with MicroBatchServer(scorer, max_wait_ms=5) as server:
            first = [server.submit(p) for p in parts[:10]]
            got_a = np.concatenate([f.result(30) for f in first])
            server.swap_model(model_b)
            second = [server.submit(p) for p in parts[10:]]
            got_b = np.concatenate([f.result(30) for f in second])
        # both versions' scores served, zero dropped requests
        assert np.array_equal(got_a, ref_a[:40])
        assert np.array_equal(got_b, ref_b[40:])
        reg = default_registry()
        assert reg.counter(serving_counters.REQUEST_FAILURES).value == 0
        assert reg.counter(serving_counters.MODEL_SWAPS).value == 1

    def test_layout_changing_swap_rejected_naming_leaves(self):
        from photon_ml_tpu.serving import ModelSwapError

        ds, model_a, _ = self._two_models(seed=23)
        _, wrong = _dense_fixture(n=20, seed=23, d=13)  # different FE dim
        scorer = ResidentScorer(model_a, shapes=(64,))
        ref_a = scorer.score(ds)
        with pytest.raises(ModelSwapError, match="fe/w"):
            scorer.swap_model(wrong)
        # resident model untouched, still serving
        assert scorer.model is model_a
        assert np.array_equal(scorer.score(ds), ref_a)
        assert default_registry().counter(
            serving_counters.SWAP_REJECTED
        ).value >= 1

    def test_swap_refeeds_resident_params_bytes(self):
        serving_counters.reset_serving_metrics()
        ds, model_a, model_b = self._two_models(seed=24)
        scorer = ResidentScorer(model_a, shapes=(64,))
        scorer.score(ds)
        reg = default_registry()
        before = reg.gauge(serving_counters.RESIDENT_PARAMS_BYTES).value
        assert before and before > 0
        scorer.swap_model(model_b)
        after = reg.gauge(serving_counters.RESIDENT_PARAMS_BYTES).value
        # same layout -> same byte count, but the gauge was RE-fed (it
        # must reflect the rebuilt cache, not a stale read)
        assert after == scorer._scorer._params_cache_bytes

    def test_ledger_forecast_refeed(self):
        """refeed_resident_forecast recomputes the per-label HBM forecast
        from the CURRENT resident gauge + the recorded peak (the swap must
        not leave the PR 13 forecast pricing the stale model)."""
        from photon_ml_tpu.telemetry.program_ledger import ProgramLedger
        from photon_ml_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry()
        ledger = ProgramLedger(registry=reg)
        assert ledger.refeed_resident_forecast("serve/score") is None
        reg.gauge("xla/serve/score/peak_bytes").set(1000)
        reg.gauge(serving_counters.RESIDENT_PARAMS_BYTES).set(5000)
        assert ledger.refeed_resident_forecast("serve/score") == 6000
        assert reg.gauge("xla/serve/score/hbm_forecast_bytes").value == 6000
        reg.gauge(serving_counters.RESIDENT_PARAMS_BYTES).set(700)
        assert ledger.refeed_resident_forecast("serve/score") == 1700

    def test_serve_driver_mid_replay_swap(self, tmp_path):
        """The serve driver's --swap-model-dir seam: zero dropped
        requests, ledger-attributed score compiles across the swap == 0,
        swap evidence in the summary."""
        from photon_ml_tpu.cli import game_training_driver, serve_driver
        from tests.test_cli import _write_game_avro

        base = tmp_path
        _write_game_avro(base / "train", 200, seed=0)
        _write_game_avro(base / "req", 80, seed=1)
        common = [
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=1.0,max.iter=8",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,"
            "random.effect.type=userId,reg.weights=0.1,max.iter=8",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ]
        game_training_driver.main([
            "--input-data-path", str(base / "train"),
            "--root-output-dir", str(base / "out"),
        ] + common)
        # the refreshed model: the incremental-refresh driver's output
        game_training_driver.main([
            "--input-data-path", str(base / "train"),
            "--root-output-dir", str(base / "refreshed"),
            "--model-input-dir", str(base / "out" / "best"),
            "--incremental-refresh",
            "--refresh-gradient-tolerance", "0",
            "--refresh-changed-entities", "userId=u1",
        ] + common)
        s = serve_driver.main([
            "--requests-avro", str(base / "req"),
            "--model-input-dir", str(base / "out" / "best"),
            "--swap-model-dir", str(base / "refreshed" / "best"),
            "--output-dir", str(base / "serve"),
            "--microbatch-shapes", "32",
            "--request-rows", "4",
            "--max-wait-ms", "5",
            "--skip-unbatched-baseline",
            "--telemetry-dir", str(base / "serve" / "telemetry"),
        ])
        assert s["swap"]["performed"] is True
        assert s["swap"]["at_request"] == 10
        assert s["swap"]["score_compiles_after_swap"] == 0
        assert s["replay_compiles"] == 0
        assert s["num_requests"] == 20
        reg = default_registry()
        assert reg.counter(serving_counters.REQUEST_FAILURES).value == 0
