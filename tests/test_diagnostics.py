"""Diagnostics tests (reference photon-diagnostics test intent: HL detects
calibration, bootstrap quantifies stability, fitting curves move the right
way, importance ranks signal features first, reports render)."""

import json

import numpy as np
import pytest

from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.diagnostics import (
    CoefficientSummary,
    bootstrap_training,
    evaluate_model,
    feature_importance,
    fitting_diagnostic,
    hosmer_lemeshow,
    kendall_tau_independence,
)
from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.optim.optimizer import OptimizerConfig
from photon_ml_tpu.types import TaskType


@pytest.fixture(scope="module")
def logistic_data():
    rng = np.random.default_rng(0)
    n, d = 2000, 6
    w = rng.normal(size=d) * 2.5  # strong signal -> high Bayes AUC
    x = rng.normal(size=(n, d)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(x @ w)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return LabeledPointBatch.create(x[:1500], y[:1500]), LabeledPointBatch.create(
        x[1500:], y[1500:]
    ), w


def _train_fn(task, l2=1e-3, iters=60):
    def fn(batch):
        return train_glm(
            batch,
            task,
            optimizer=OptimizerConfig(max_iterations=iters),
            regularization_weights=(l2,),
        )[l2]

    return fn


class TestMetrics:
    def test_logistic_metrics(self, logistic_data):
        train, val, _ = logistic_data
        model = _train_fn(TaskType.LOGISTIC_REGRESSION)(train)
        m = evaluate_model(model, val)
        assert m["AUC"] > 0.85
        assert 0 < m["LOGISTIC_LOSS"] < 1.0
        assert "AUPR" in m


class TestCoefficientSummary:
    def test_quartiles(self):
        s = CoefficientSummary.from_samples(np.arange(101, dtype=float))
        assert s.min == 0 and s.max == 100
        assert s.median == 50 and s.q1 == 25 and s.q3 == 75
        assert not s.straddles_zero()
        assert CoefficientSummary.from_samples(np.array([-1.0, 1.0])).straddles_zero()


class TestHosmerLemeshow:
    def test_calibrated_model_passes(self):
        rng = np.random.default_rng(1)
        n = 20000
        margins = rng.normal(size=n)
        p = 1.0 / (1.0 + np.exp(-margins))
        labels = (rng.uniform(size=n) < p).astype(float)
        report = hosmer_lemeshow(margins, labels)
        assert report.well_calibrated
        assert len(report.bins) == 10
        assert sum(b.count for b in report.bins) == n

    def test_miscalibrated_model_fails(self):
        rng = np.random.default_rng(2)
        n = 20000
        margins = rng.normal(size=n)
        # true probabilities much steeper than the model's
        p_true = 1.0 / (1.0 + np.exp(-3.0 * margins))
        labels = (rng.uniform(size=n) < p_true).astype(float)
        report = hosmer_lemeshow(margins, labels)
        assert not report.well_calibrated
        assert report.chi_square > 100


class TestIndependence:
    def test_unbiased_errors_independent(self):
        rng = np.random.default_rng(3)
        scores = rng.normal(size=3000)
        labels = scores + rng.normal(scale=1.0, size=3000)
        assert kendall_tau_independence(scores, labels).independent

    def test_structured_errors_detected(self):
        rng = np.random.default_rng(4)
        scores = rng.normal(size=3000)
        labels = 2.0 * scores  # error = labels - scores = scores (fully dependent)
        report = kendall_tau_independence(scores, labels)
        assert not report.independent
        assert report.tau > 0.9


class TestFeatureImportance:
    def test_ranks_signal_features(self):
        rng = np.random.default_rng(5)
        n = 1000
        x = rng.normal(size=(n, 4)).astype(np.float32)
        y = (3.0 * x[:, 2] + 0.1 * x[:, 0]).astype(np.float32)
        batch = LabeledPointBatch.create(x, y)
        model = _train_fn(TaskType.LINEAR_REGRESSION)(batch)
        for kind in ("expected_magnitude", "variance"):
            report = feature_importance(model, batch, kind=kind)
            assert report.ranked[0].index == 2
        with pytest.raises(ValueError):
            feature_importance(model, batch, kind="bogus")


class TestBootstrap:
    def test_stable_and_unstable_coefficients(self):
        rng = np.random.default_rng(6)
        n = 800
        x = rng.normal(size=(n, 3)).astype(np.float32)
        # strong signal on feature 0, none on features 1-2
        y = (2.0 * x[:, 0] + rng.normal(scale=0.5, size=n)).astype(np.float32)
        batch = LabeledPointBatch.create(x, y)
        report = bootstrap_training(
            _train_fn(TaskType.LINEAR_REGRESSION, iters=40),
            batch,
            batch,
            num_bootstraps=8,
        )
        assert 0 not in report.unstable_coefficients  # signal coefficient stable
        assert report.coefficient_summaries[0].median > 1.5
        assert "RMSE" in report.metric_distributions
        assert report.metric_distributions["RMSE"].std < 0.2
        with pytest.raises(ValueError):
            bootstrap_training(_train_fn(TaskType.LINEAR_REGRESSION), batch, batch,
                               num_bootstraps=1)


class TestFitting:
    def test_validation_improves_with_data(self, logistic_data):
        train, val, _ = logistic_data
        report = fitting_diagnostic(
            _train_fn(TaskType.LOGISTIC_REGRESSION, iters=40),
            train,
            val,
            portions=(0.1, 0.5, 1.0),
        )
        _, _, test_auc = report.metric_curve("AUC")
        assert test_auc[-1] >= test_auc[0] - 0.02  # more data never much worse
        assert len(report.portions) == 3


class TestReporting:
    def test_render_html_and_text(self):
        from photon_ml_tpu.diagnostics.reporting import (
            Chapter,
            LineChart,
            Report,
            Section,
            Table,
            Text,
            render_html,
            render_text,
        )

        report = Report(
            title="Test <Report>",
            chapters=[
                Chapter(
                    title="C1",
                    sections=[
                        Section(
                            title="S1",
                            items=[
                                Text("hello & goodbye"),
                                Table(headers=["a", "b"], rows=[[1, 2.5]], caption="t"),
                                LineChart(
                                    title="curve",
                                    x=[0.0, 1.0],
                                    series={"s": [0.0, 1.0]},
                                ),
                            ],
                        )
                    ],
                )
            ],
        )
        html_out = render_html(report)
        assert "Test &lt;Report&gt;" in html_out  # escaped
        assert "<svg" in html_out and "polyline" in html_out
        assert "<table>" in html_out
        text_out = render_text(report)
        assert "C1" in text_out and ("a " in text_out or "a|" in text_out)


class TestGLMDriver:
    def test_staged_pipeline_with_diagnostics(self, tmp_path):
        from photon_ml_tpu.cli.glm_driver import DriverStage, main

        # libsvm fixture (a1a-style)
        rng = np.random.default_rng(7)
        w = np.random.default_rng(99).normal(size=8)
        for name, n in [("train.txt", 500), ("val.txt", 200)]:
            with open(tmp_path / name, "w") as f:
                for _ in range(n):
                    x = rng.normal(size=8)
                    y = 1 if x @ w > 0 else -1
                    feats = " ".join(f"{j+1}:{x[j]:.4f}" for j in range(8))
                    f.write(f"{y} {feats}\n")

        result = main(
            [
                "--input-data-path", str(tmp_path / "train.txt"),
                "--validation-data-path", str(tmp_path / "val.txt"),
                "--output-dir", str(tmp_path / "out"),
                "--task-type", "LOGISTIC_REGRESSION",
                "--regularization-weights", "0.1,10",
                "--max-iterations", "40",
                "--input-format", "libsvm",
                "--enable-diagnostics",
                "--num-bootstraps", "4",
                "--data-validation", "VALIDATE_FULL",
            ]
        )
        assert result.stage == DriverStage.DIAGNOSED
        assert result.best_lambda in (0.1, 10)
        assert result.validation_metrics[result.best_lambda]["AUC"] > 0.8
        out = tmp_path / "out"
        assert (out / "diagnostic-report.html").exists()
        html_text = (out / "diagnostic-report.html").read_text()
        assert "Hosmer-Lemeshow" in html_text
        assert "Bootstrap analysis" in html_text
        assert (out / "models-text" / "0.1.txt").exists()
        glm_summary = json.loads((out / "glm-summary.json").read_text())
        assert glm_summary["runtime"]["platform"] == "cpu"
        assert set(glm_summary["decode_paths"]) == {"train", "validation"}
