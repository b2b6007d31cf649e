"""CLI driver tests: config grammar (reference ScoptParserHelpers tests) and
end-to-end train -> score through the drivers (reference
GameTrainingDriverIntegTest / GameScoringDriverIntegTest intent)."""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.cli.configs import (
    CoordinateCliConfig,
    expand_reg_weight_grid,
    parse_coordinate_config,
    parse_feature_shard_config,
    parse_kv_list,
)
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io import photon_schemas as schemas
from photon_ml_tpu.optim.optimizer import OptimizerType
from photon_ml_tpu.projector.projectors import ProjectorType


class TestConfigGrammar:
    def test_parse_kv_list(self):
        assert parse_kv_list("a=1, b=x|y") == {"a": "1", "b": "x|y"}
        with pytest.raises(ValueError, match="key=value"):
            parse_kv_list("a=1,b")
        with pytest.raises(ValueError, match="duplicate"):
            parse_kv_list("a=1,a=2")

    def test_feature_shard(self):
        name, cfg = parse_feature_shard_config(
            "name=global,feature.bags=features|userFeatures,intercept=false"
        )
        assert name == "global"
        assert cfg.feature_bags == ("features", "userFeatures")
        assert not cfg.has_intercept
        assert cfg.dtype == "float32"
        with pytest.raises(ValueError, match="unknown"):
            parse_feature_shard_config("name=g,feature.bags=f,bogus=1")

    def test_feature_shard_dtype(self):
        """dtype=bf16 grammar (VERDICT r4 #3): aliases accepted, sparse
        shards rejected, junk rejected."""
        for alias in ("bf16", "bfloat16", "BF16"):
            _, cfg = parse_feature_shard_config(
                f"name=g,feature.bags=f,dtype={alias}"
            )
            assert cfg.dtype == "bfloat16"
        for alias in ("f32", "float32", "fp32"):
            _, cfg = parse_feature_shard_config(
                f"name=g,feature.bags=f,dtype={alias}"
            )
            assert cfg.dtype == "float32"
        with pytest.raises(ValueError, match="unknown feature shard dtype"):
            parse_feature_shard_config("name=g,feature.bags=f,dtype=fp8")
        with pytest.raises(ValueError, match="dense"):
            parse_feature_shard_config(
                "name=g,feature.bags=f,sparse=true,dtype=bf16"
            )

    def test_coordinate_fixed_effect(self):
        cfg = parse_coordinate_config(
            "name=fe,feature.shard=global,optimizer=TRON,"
            "reg.weights=0.1|1|10,max.iter=25,variance=true"
        )
        assert not cfg.is_random_effect
        assert cfg.optimizer == OptimizerType.TRON
        assert cfg.reg_weights == (0.1, 1.0, 10.0)
        assert cfg.max_iterations == 25
        assert cfg.compute_variance
        opt = cfg.optimization_config(1.0)
        assert opt.l2_weight == 1.0 and opt.l1_weight == 0.0

    def test_coordinate_random_effect_with_projection(self):
        cfg = parse_coordinate_config(
            "name=per-user,feature.shard=user,random.effect.type=userId,"
            "active.data.upper.bound=512,projector=INDEX_MAP,reg.weights=1"
        )
        assert cfg.is_random_effect
        assert cfg.active_data_upper_bound == 512
        assert cfg.projector == ProjectorType.INDEX_MAP
        est = cfg.estimator_config(1.0)
        assert est.random_effect_type == "userId"

    def test_elastic_net_split(self):
        cfg = parse_coordinate_config(
            "name=fe,feature.shard=g,reg.weights=10,reg.alpha=0.25"
        )
        opt = cfg.optimization_config(10.0)
        assert opt.l1_weight == pytest.approx(2.5)
        assert opt.l2_weight == pytest.approx(7.5)

    def test_grid_expansion(self):
        configs = {
            "a": CoordinateCliConfig(name="a", feature_shard="g", reg_weights=(0.1, 1.0)),
            "b": CoordinateCliConfig(name="b", feature_shard="g", reg_weights=(2.0,)),
        }
        grid = expand_reg_weight_grid(configs)
        assert grid == [{"a": 0.1, "b": 2.0}, {"a": 1.0, "b": 2.0}]


def _write_game_avro(path, n, seed, n_users=12, d=6):
    """Synthetic GAME training file: global features + per-user effects via
    metadataMap userId (TrainingExampleAvro layout). The ground truth is
    drawn from a fixed seed so train/val share it; only the samples vary."""
    truth = np.random.default_rng(1234)
    w = truth.normal(size=d)
    user_w = {f"u{i}": truth.normal(scale=0.5, size=d) for i in range(n_users)}
    rng = np.random.default_rng(seed)
    records = []
    for i in range(n):
        uid = f"u{rng.integers(0, n_users)}"
        x = rng.normal(size=d)
        y = x @ (w + user_w[uid]) + rng.normal(scale=0.1)
        records.append(
            {
                "uid": str(i),
                "label": float(y),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[j])}
                    for j in range(d)
                ],
                "weight": 1.0,
                "offset": 0.0,
                "foldId": None,
                "metadataMap": {"userId": uid, "queryId": f"q{i % 7}"},
            }
        )
    os.makedirs(path, exist_ok=True)
    avro_io.write_container(
        os.path.join(path, "part-00000.avro"), schemas.TRAINING_EXAMPLE_AVRO, records
    )


@pytest.fixture(scope="module")
def game_data(tmp_path_factory):
    base = tmp_path_factory.mktemp("game-data")
    _write_game_avro(base / "train", 800, seed=0)
    _write_game_avro(base / "val", 300, seed=1)
    return base


class TestEndToEnd:
    def test_train_then_score(self, game_data, tmp_path):
        from photon_ml_tpu.cli import game_scoring_driver, game_training_driver

        out = tmp_path / "out"
        summary = game_training_driver.main(
            [
                "--input-data-path", str(game_data / "train"),
                "--validation-data-path", str(game_data / "val"),
                "--root-output-dir", str(out),
                "--feature-shard-configurations",
                "name=global,feature.bags=features,intercept=true",
                "--coordinate-configurations",
                "name=fe,feature.shard=global,reg.weights=0.01|1.0,max.iter=40",
                "--coordinate-configurations",
                "name=per-user,feature.shard=global,random.effect.type=userId,"
                "reg.weights=0.1,max.iter=30",
                "--task-type", "LINEAR_REGRESSION",
                "--coordinate-descent-iterations", "2",
                "--evaluators", "RMSE,RMSE:queryId",
                "--data-validation", "VALIDATE_FULL",
            ]
        )
        assert summary["num_configurations"] == 2
        assert np.isfinite(summary["best_metric"])
        assert summary["best_metric"] < 1.0  # signal recovered
        # reference layout on disk
        assert (out / "best" / "model-metadata.json").exists()
        assert (out / "best" / "fixed-effect" / "fe" / "id-info").exists()
        assert (out / "best" / "random-effect" / "per-user" / "id-info").exists()
        assert (out / "models" / "0").is_dir() and (out / "models" / "1").is_dir()
        assert (out / "index-maps" / "global.keys").exists()
        assert (out / "driver.log").exists()
        assert (out / "feature-stats" / "global" / "part-00000.avro").exists()
        # the summary says what ran the job and which decoders fed it
        import jax

        on_disk = json.loads((out / "training-summary.json").read_text())
        runtime = on_disk["runtime"]
        assert runtime["platform"] == "cpu"
        assert runtime["device_count"] == jax.device_count()
        assert runtime["devices_used"] == 1  # no --distributed
        assert runtime["jax_version"] == jax.__version__
        assert [m["id"] for m in runtime["device_memory"]] == [
            d.id for d in jax.local_devices()]
        assert set(on_disk["decode_paths"]) == {"train", "validation"}
        assert all(v in ("avro-native", "avro-python")
                   for v in on_disk["decode_paths"].values())

        score_out = tmp_path / "scores"
        s = game_scoring_driver.main(
            [
                "--input-data-path", str(game_data / "val"),
                "--model-input-dir", str(out / "best"),
                "--output-dir", str(score_out),
                "--evaluators", "RMSE",
            ]
        )
        assert s["num_scored"] == 300
        assert s["evaluations"]["RMSE"] == pytest.approx(summary["best_metric"], rel=0.2)
        assert s["runtime"]["platform"] == "cpu" and "score" in s["decode_paths"]
        from photon_ml_tpu.io.model_io import read_scores

        scores = read_scores(score_out / "scores")
        assert len(scores) == 300
        assert all(np.isfinite(r["predictionScore"]) for r in scores)

    def test_output_dir_protection(self, game_data, tmp_path):
        from photon_ml_tpu.cli import game_training_driver

        out = tmp_path / "occupied"
        out.mkdir()
        (out / "something").write_text("x")
        with pytest.raises(ValueError, match="non-empty"):
            game_training_driver.main(
                [
                    "--input-data-path", str(game_data / "train"),
                    "--root-output-dir", str(out),
                    "--feature-shard-configurations",
                    "name=global,feature.bags=features",
                    "--coordinate-configurations",
                    "name=fe,feature.shard=global",
                    "--task-type", "LINEAR_REGRESSION",
                ]
            )

    def test_param_validation(self, game_data, tmp_path):
        from photon_ml_tpu.cli import game_training_driver

        with pytest.raises(ValueError, match="undefined feature shard"):
            game_training_driver.main(
                [
                    "--input-data-path", str(game_data / "train"),
                    "--root-output-dir", str(tmp_path / "o1"),
                    "--feature-shard-configurations",
                    "name=global,feature.bags=features",
                    "--coordinate-configurations",
                    "name=fe,feature.shard=WRONG",
                    "--task-type", "LINEAR_REGRESSION",
                ]
            )

    def test_warm_start_and_partial_retrain(self, game_data, tmp_path):
        from photon_ml_tpu.cli import game_training_driver

        out1 = tmp_path / "stage1"
        game_training_driver.main(
            [
                "--input-data-path", str(game_data / "train"),
                "--root-output-dir", str(out1),
                "--feature-shard-configurations",
                "name=global,feature.bags=features",
                "--coordinate-configurations",
                "name=fe,feature.shard=global,max.iter=30",
                "--task-type", "LINEAR_REGRESSION",
            ]
        )
        out2 = tmp_path / "stage2"
        summary = game_training_driver.main(
            [
                "--input-data-path", str(game_data / "train"),
                "--validation-data-path", str(game_data / "val"),
                "--root-output-dir", str(out2),
                "--feature-shard-configurations",
                "name=global,feature.bags=features",
                "--coordinate-configurations",
                "name=fe,feature.shard=global,max.iter=30",
                "--coordinate-configurations",
                "name=per-user,feature.shard=global,random.effect.type=userId,"
                "reg.weights=0.1,max.iter=30",
                "--task-type", "LINEAR_REGRESSION",
                "--model-input-dir", str(out1 / "best"),
                "--partial-retrain-locked-coordinates", "fe",
                "--evaluators", "RMSE",
            ]
        )
        assert np.isfinite(summary["best_metric"])
        # locked fe model must be identical to stage1's
        from photon_ml_tpu.io.index_map import IndexMap
        from photon_ml_tpu.io.model_io import load_game_model

        imaps = {"global": IndexMap.load(out1 / "index-maps", "global")}
        m1 = load_game_model(out1 / "best", imaps)
        m2 = load_game_model(out2 / "best", imaps)
        np.testing.assert_allclose(
            np.asarray(m2.get("fe").glm.coefficients.means),
            np.asarray(m1.get("fe").glm.coefficients.means),
            atol=1e-6,
        )

    def test_train_then_score_with_mf_coordinate(self, tmp_path):
        """FE + matrix-factorization coordinate through both drivers —
        the model family the reference declares but never implemented."""
        from photon_ml_tpu.cli import game_scoring_driver, game_training_driver

        # data with a true low-rank user x item interaction on the residual
        truth = np.random.default_rng(7)
        d, k, n_users, n_items = 4, 2, 10, 8
        w = truth.normal(size=d)
        u = truth.normal(size=(n_users, k))
        v = truth.normal(size=(n_items, k))
        rng = np.random.default_rng(0)
        base = tmp_path / "mf-data"
        for split, n, seed in (("train", 900, 0), ("val", 300, 1)):
            rng = np.random.default_rng(seed)
            records = []
            for i in range(n):
                ui, vi = rng.integers(0, n_users), rng.integers(0, n_items)
                x = rng.normal(size=d)
                y = x @ w + u[ui] @ v[vi] + rng.normal(scale=0.05)
                records.append(
                    {
                        "uid": str(i),
                        "label": float(y),
                        "features": [
                            {"name": f"f{j}", "term": "", "value": float(x[j])}
                            for j in range(d)
                        ],
                        "weight": 1.0,
                        "offset": 0.0,
                        "foldId": None,
                        "metadataMap": {"userId": f"u{ui}", "itemId": f"i{vi}"},
                    }
                )
            os.makedirs(base / split, exist_ok=True)
            avro_io.write_container(
                os.path.join(base / split, "part-00000.avro"),
                schemas.TRAINING_EXAMPLE_AVRO,
                records,
            )

        out = tmp_path / "out"
        summary = game_training_driver.main(
            [
                "--input-data-path", str(base / "train"),
                "--validation-data-path", str(base / "val"),
                "--root-output-dir", str(out),
                "--feature-shard-configurations",
                "name=global,feature.bags=features,intercept=true",
                "--coordinate-configurations",
                "name=fe,feature.shard=global,reg.weights=0.001,max.iter=40",
                "--coordinate-configurations",
                "name=mf,mf.row.effect.type=userId,mf.col.effect.type=itemId,"
                "mf.latent.factors=2,reg.weights=0.001,max.iter=25",
                "--task-type", "LINEAR_REGRESSION",
                "--coordinate-descent-iterations", "4",
                "--evaluators", "RMSE",
            ]
        )
        # FE alone leaves the u.v residual (std ~ k=2 products of unit
        # normals); the MF coordinate must soak most of it up
        assert summary["best_metric"] < 0.6
        assert (out / "best" / "matrix-factorization" / "mf" / "id-info").exists()
        assert (
            out / "best" / "matrix-factorization" / "mf" / "row-latent-factors"
            / "part-00000.avro"
        ).exists()

        score_out = tmp_path / "scores"
        s = game_scoring_driver.main(
            [
                "--input-data-path", str(base / "val"),
                "--model-input-dir", str(out / "best"),
                "--output-dir", str(score_out),
                "--evaluators", "RMSE",
            ]
        )
        assert s["num_scored"] == 300
        assert s["evaluations"]["RMSE"] == pytest.approx(
            summary["best_metric"], rel=0.2
        )

    def test_feature_indexing_and_name_term_drivers(self, game_data, tmp_path):
        from photon_ml_tpu.cli import (
            feature_indexing_driver,
            name_term_feature_bags_driver,
        )

        sizes = feature_indexing_driver.main(
            [
                "--input-data-path", str(game_data / "train"),
                "--output-dir", str(tmp_path / "index"),
                "--feature-shard-configurations",
                "name=global,feature.bags=features",
            ]
        )
        assert sizes["global"] == 7  # 6 features + intercept
        counts = name_term_feature_bags_driver.main(
            [
                "--input-data-path", str(game_data / "train"),
                "--output-dir", str(tmp_path / "bags"),
                "--feature-bags", "features",
            ]
        )
        assert counts["features"] == 6
        lines = (tmp_path / "bags" / "features" / "part-00000.tsv").read_text().splitlines()
        assert lines[0].split("\t")[0] == "f0"


def test_glm_driver_grid_parallel_matches_sequential(tmp_path):
    """--grid-parallel must select the same best λ and near-identical
    validation metrics as the sequential warm-start path."""
    import numpy as np
    from photon_ml_tpu.cli import glm_driver

    rng = np.random.default_rng(4)
    n, d = 500, 10
    w = rng.normal(size=d)
    base = tmp_path / "data"
    for split, nn in (("train", n), ("val", 200)):
        lines = []
        for _ in range(nn):
            x = rng.normal(size=d)
            y = 1 if rng.random() < 1 / (1 + np.exp(-(x @ w))) else -1
            lines.append(
                f"{'+1' if y > 0 else '-1'} "
                + " ".join(f"{j+1}:{x[j]:.6f}" for j in range(d))
            )
        (base / split).mkdir(parents=True, exist_ok=True)
        (base / split / "data.libsvm").write_text("\n".join(lines))

    def run(flag, out):
        return glm_driver.main([
            "--input-data-path", str(base / "train" / "data.libsvm"),
            "--validation-data-path", str(base / "val" / "data.libsvm"),
            "--output-dir", str(tmp_path / out),
            "--task-type", "LOGISTIC_REGRESSION",
            "--regularization-weights", "0.1,1,10",
            "--input-format", "libsvm",
            "--max-iterations", "60",
            *(["--grid-parallel"] if flag else []),
        ])

    seq = run(False, "seq")
    par = run(True, "par")
    assert par.best_lambda == seq.best_lambda
    for lam in (0.1, 1.0, 10.0):
        assert par.validation_metrics[lam]["AUC"] == pytest.approx(
            seq.validation_metrics[lam]["AUC"], abs=1e-3
        )


def test_feature_indexing_offheap_store(game_data, tmp_path):
    """--index-store-format offheap writes partitioned native mmap stores
    readable by OffHeapIndexMap (reference PalDB FeatureIndexingDriver)."""
    from photon_ml_tpu.cli import feature_indexing_driver
    from photon_ml_tpu.io.index_map import feature_key
    from photon_ml_tpu.io.offheap_index_map import OffHeapIndexMap

    sizes = feature_indexing_driver.main([
        "--input-data-path", str(game_data / "train"),
        "--output-dir", str(tmp_path / "index"),
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--index-store-format", "offheap",
        "--num-partitions", "3",
    ])
    store = OffHeapIndexMap(tmp_path / "index", "global")
    assert len(store) == sizes["global"] == 7
    j = store.get_index(feature_key("f0", ""))
    assert j >= 0 and store.get_feature_name(j) == feature_key("f0", "")
    assert store.get_index("missing\x01") == -1


def test_scoring_reads_offheap_index_stores(game_data, tmp_path):
    """Train normally, re-index off-heap, then score using ONLY the native
    stores (no .keys files) — the pipeline consumes what the indexing
    driver writes."""
    from photon_ml_tpu.cli import (
        feature_indexing_driver,
        game_scoring_driver,
        game_training_driver,
    )

    out = tmp_path / "train"
    game_training_driver.main([
        "--input-data-path", str(game_data / "train"),
        "--root-output-dir", str(out),
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--coordinate-configurations", "name=fe,feature.shard=global,max.iter=25",
        "--task-type", "LINEAR_REGRESSION",
    ])
    feature_indexing_driver.main([
        "--input-data-path", str(game_data / "train"),
        "--output-dir", str(tmp_path / "offheap-index"),
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--index-store-format", "offheap", "--num-partitions", "2",
    ])
    s = game_scoring_driver.main([
        "--input-data-path", str(game_data / "val"),
        "--model-input-dir", str(out / "best"),
        "--output-dir", str(tmp_path / "scores"),
        "--index-maps-dir", str(tmp_path / "offheap-index"),
        "--feature-shard-configurations", "name=global,feature.bags=features",
    ])
    assert s["num_scored"] == 300


def test_training_with_prebuilt_offheap_index_maps(game_data, tmp_path):
    """Training consumes prebuilt native off-heap stores (--index-maps-dir),
    the reference's PalDB prepareFeatureMaps path; results match the
    scan-the-data path."""
    from photon_ml_tpu.cli import feature_indexing_driver, game_training_driver

    feature_indexing_driver.main([
        "--input-data-path", str(game_data / "train"),
        "--output-dir", str(tmp_path / "idx"),
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--index-store-format", "offheap", "--num-partitions", "2",
    ])
    common = [
        "--input-data-path", str(game_data / "train"),
        "--validation-data-path", str(game_data / "val"),
        "--evaluators", "RMSE",
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--coordinate-configurations", "name=fe,feature.shard=global,max.iter=30",
        "--task-type", "LINEAR_REGRESSION",
    ]
    s_pre = game_training_driver.main(
        common + ["--root-output-dir", str(tmp_path / "o1"),
                  "--index-maps-dir", str(tmp_path / "idx")]
    )
    s_scan = game_training_driver.main(
        common + ["--root-output-dir", str(tmp_path / "o2")]
    )
    assert s_pre["best_metric"] == pytest.approx(s_scan["best_metric"], rel=1e-6)
    # missing shard stores fail fast
    with pytest.raises(ValueError, match="no stores"):
        game_training_driver.main(
            common + ["--root-output-dir", str(tmp_path / "o3"),
                      "--index-maps-dir", str(tmp_path)]
        )


def test_coordinate_config_print_round_trip():
    """Reference ScoptParameter print-round-trip: parse(format(cfg)) == cfg
    across every coordinate family."""
    from photon_ml_tpu.cli.configs import (
        format_coordinate_config,
        parse_coordinate_config,
    )

    specs = [
        "name=fe,feature.shard=g,optimizer=TRON,reg.weights=0.1|1|10,"
        "max.iter=25,variance=true,reg.alpha=0.25",
        "name=ru,feature.shard=u,random.effect.type=userId,"
        "active.data.upper.bound=512,projector=INDEX_MAP,"
        "features.to.samples.ratio=0.2,reg.weights=1",
        "name=mf,mf.row.effect.type=u,mf.col.effect.type=i,"
        "mf.latent.factors=8,mf.alternations=3,reg.weights=0.01",
        "name=plain,feature.shard=g",
    ]
    for spec in specs:
        cfg = parse_coordinate_config(spec)
        assert parse_coordinate_config(format_coordinate_config(cfg)) == cfg
