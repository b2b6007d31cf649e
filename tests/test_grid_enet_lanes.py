"""An elastic-net λ grid fitted as lanes (``estimators.train_glm_grid``, PR 47)
against the benchmark's plain reference (accelerated proximal gradient, nothing
of the program in it) and against ``train_glm``'s sequential elastic-net path,
on seeded data: value, coefficients, support."""

import os

import jax
import numpy as np
import pytest

from benchmark.manifest import HERE, load_module
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.estimators import train_glm, train_glm_grid
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.types import TaskType

ALPHA = 0.5
LANES = 8


class _Lanes:
    def record_lanes(self, _coordinate, result, *, keys=None, **_):
        self.result, self.lambdas = result, [key["lambda"] for key in keys]
        return {}


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(47)
    n, d = 3000, 24
    x = rng.normal(size=(n, d)) + 0.6 * rng.normal(size=(n, 1))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    truth = np.where(rng.random(d) < 0.4, rng.normal(size=d) * 6.0, 0.0)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ truth))).astype(np.float64)
    lam_max = 1.00001 * np.abs(x.T @ (0.5 - y)).max() / ALPHA
    lambdas = [float(lam_max * 10 ** (-3 * k / (LANES - 1))) for k in range(LANES)]
    return x, y, lambdas


@pytest.fixture(scope="module")
def lanes(problem):
    x, y, lambdas = problem
    recorder = _Lanes()
    models = train_glm_grid(
        LabeledPointBatch.create(x, y), TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerConfig(OptimizerType.OWLQN, max_iterations=200, tolerance=1e-9),
        regularization_weights=lambdas, elastic_net_alpha=ALPHA, telemetry=recorder)
    assert recorder.lambdas == sorted(lambdas)
    w = np.stack([np.asarray(models[lam].coefficients.means) for lam in lambdas])
    return w, np.asarray(recorder.result.value)[::-1], recorder.result


def test_the_lanes_reach_the_plain_references_minimizers(problem, lanes, monkeypatch):
    x, y, lambdas = problem
    w, values, _ = lanes
    reference = load_module(os.path.join(HERE, "references", "logistic-epsilon-enet.py"))
    monkeypatch.setattr(reference, "RESIDUAL_TARGET", 1e-5)
    data = {"x": x.astype(np.float32), "y": y.astype(np.float32),
            "x_val": x[:5].astype(np.float32), "y_val": y[:5].astype(np.float32)}
    exact = reference.fit(data, {"lambdas": lambdas, "elastic_net_alpha": ALPHA},
                          jax.devices()[:1])
    assert exact.shape == w.shape and not exact[0].any() and not w[0].any()
    ours = reference.evaluate(data, w, lambdas, ALPHA)
    theirs = reference.evaluate(data, exact, lambdas, ALPHA)
    # the program's float64 fit of the float64 rows against a float32 fit of
    # the rows rounded to float32: the value agrees to the rounding of the
    # rows, the coefficients to the residual the reference stops at
    np.testing.assert_allclose(values, ours["value"], rtol=1e-6)
    np.testing.assert_allclose(ours["value"], theirs["value"], rtol=1e-7)
    for k in range(1, LANES):
        scale = np.linalg.norm(exact[k])
        assert np.linalg.norm(w[k] - exact[k]) <= 2e-3 * scale
        # the support: a coefficient the reference holds clear of zero is
        # held by the lane, and the counts differ by a borderline one at most
        clear = np.abs(exact[k]) > 1e-2 * np.abs(exact[k]).max()
        assert (w[k][clear] != 0).all()
        assert abs(int(ours["nonzeros"][k]) - int(theirs["nonzeros"][k])) <= 1
    assert (np.diff(ours["nonzeros"]) >= 0).all() and ours["nonzeros"][-1] > ours["nonzeros"][1]


def test_the_lanes_are_the_sequential_paths_minimizers(problem, lanes):
    x, y, lambdas = problem
    w, values, result = lanes
    recorded = {}

    class Solves:
        def record_solve(self, _coordinate, solve, *, extra=None, **_):
            recorded[float(extra["lambda"])] = solve
            return {}

        def heartbeat(self, *_a, **_k):
            return None

    models = train_glm(
        LabeledPointBatch.create(x, y), TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerConfig(OptimizerType.OWLQN, max_iterations=200, tolerance=1e-9),
        regularization_weights=lambdas, elastic_net_alpha=ALPHA, telemetry=Solves())
    for k, lam in enumerate(lambdas):
        sequential = np.asarray(models[lam].coefficients.means)
        assert float(recorded[lam].value) == pytest.approx(values[k], rel=1e-9)
        np.testing.assert_allclose(w[k], sequential, atol=2e-4 * max(np.abs(sequential).max(), 1.0))
        assert ((w[k] != 0) == (sequential != 0)).mean() >= 1 - 1.5 / w.shape[1]
    # both count their trials now: a cold lane's evaluations, a warm solve's
    assert np.asarray(result.line_search_trials).sum() > 0
    assert all(int(np.asarray(s.line_search_trials).sum()) >= int(s.iterations)
               for s in recorded.values())
