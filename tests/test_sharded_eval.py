"""Device-side evaluation (evaluation/sharded.py) vs the host evaluators.

VERDICT r4 #4: metrics must reduce on-mesh from still-sharded scores —
these tests pin each device metric against its exact host twin
(evaluation/evaluators.py) on the 8-device virtual CPU mesh, including
ties, weights, padding rows, and the train_distributed validation pass.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.evaluation.evaluators import (
    EvaluationData,
    parse_evaluator,
)
from photon_ml_tpu.evaluation.sharded import device_evaluator
from photon_ml_tpu.parallel.mesh import make_mesh


def _data(rng, n=500, with_ties=False):
    scores = rng.normal(size=n)
    if with_ties:
        # heavy exact ties across and within queries
        scores = np.round(scores * 4) / 4
    labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
    weights = rng.uniform(0.2, 2.0, size=n)
    qids = np.array([f"q{i}" for i in rng.integers(0, 23, size=n)])
    return scores, EvaluationData(
        labels=labels,
        offsets=np.zeros(n),
        weights=weights,
        ids={"queryId": qids},
    )


EXACT_SPECS = [
    "RMSE", "MAE", "LOGISTIC_LOSS", "SQUARED_LOSS", "POISSON_LOSS",
    "SMOOTHED_HINGE_LOSS", "AUC", "AUPR", "RMSE:queryId", "AUC:queryId",
    "PRECISION@3:queryId",
]


@pytest.mark.parametrize("spec", EXACT_SPECS)
@pytest.mark.parametrize("with_ties", [False, True])
def test_device_metric_matches_host(rng, spec, with_ties):
    scores, data = _data(rng, with_ties=with_ties)
    ev = parse_evaluator(spec)
    host = ev.evaluate(scores, data)
    dev = device_evaluator(ev, data)
    assert dev is not None
    got = float(dev.compute(jnp.asarray(scores), dev.consts))
    np.testing.assert_allclose(got, host, rtol=1e-9, atol=1e-12, err_msg=spec)


def _auc_case(name, rng):
    """(scores, labels, weights, pad scores) of one edge of the sort-and-scan
    AUC: where a tie run starts and ends is found by running scans, so the
    edges are the first and the last sorted element, runs of one, and rows
    of no weight inside a run."""
    n = 1000
    scores = np.round(rng.normal(size=n) * 3) / 3
    labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
    weights = rng.uniform(0.2, 2.0, size=n)
    pads = np.zeros(0)
    if name == "all-scores-equal":
        scores = np.full(n, 0.25)
    elif name == "two-distinct-scores":
        scores = np.where(rng.uniform(size=n) < 0.4, -1.0, 2.0)
    elif name == "tie-runs-hold-first-and-last":
        # the lowest and the highest score are each tied, and the first and
        # the last input row are in those runs: the runs at both ends of
        # the sort, where nothing precedes a start and nothing follows an end
        scores[[0, 17, 400]] = scores.min() - 1.0
        scores[[n - 1, 33, 600]] = scores.max() + 1.0
    elif name == "zero-weights-inside-ties":
        weights[rng.uniform(size=n) < 0.3] = 0.0
    elif name == "pads-at-the-front-of-the-sort":
        pads = np.concatenate([np.full(3, scores.min() - 50.0), -rng.uniform(60, 70, 4)])
    elif name == "one-class":
        labels = np.ones(n)
    elif name == "n-not-a-power-of-two":
        keep = 777
        scores, labels, weights = scores[:keep], labels[:keep], weights[:keep]
    else:
        raise AssertionError(name)
    return scores, labels, weights, pads


@pytest.mark.parametrize("name", [
    "all-scores-equal", "two-distinct-scores", "tie-runs-hold-first-and-last",
    "zero-weights-inside-ties", "pads-at-the-front-of-the-sort", "one-class",
    "n-not-a-power-of-two",
])
def test_device_auc_matches_host_at_the_edges_of_a_run(rng, name):
    from photon_ml_tpu.evaluation import local_metrics

    scores, labels, weights, pads = _auc_case(name, rng)
    n = len(scores)
    data = EvaluationData(labels=labels, offsets=np.zeros(n), weights=weights, ids={})
    host = local_metrics.area_under_roc_curve(scores, labels, weights)
    dev = device_evaluator(parse_evaluator("AUC"), data, n_pad=n + len(pads))
    got = float(jax.jit(dev.compute)(
        jnp.asarray(np.concatenate([scores, pads])), dev.consts))
    if name == "one-class":
        assert np.isnan(host) and np.isnan(got)
    else:
        assert dev.consts["weights"].dtype == jnp.float64  # the suite's x64
        np.testing.assert_allclose(got, host, rtol=1e-12, atol=0.0)


def test_device_auc_in_float32_counts_a_million_unit_weights_exactly(rng):
    """float32, as the chip runs it: with unit weights every running sum is
    a whole number under 2^24, so the scans lose nothing and the value is
    the host's float64 one to the rounding of the last sum."""
    from photon_ml_tpu.evaluation import local_metrics

    n = 2**20
    scores = (np.round(rng.normal(size=n) * 50) / 50).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.3).astype(np.float32)
    host = local_metrics.area_under_roc_curve(scores, labels)
    data = EvaluationData(labels=labels, offsets=np.zeros(n), weights=np.ones(n), ids={})
    dev = device_evaluator(parse_evaluator("AUC"), data)
    consts = {k: v.astype(jnp.float32) for k, v in dev.consts.items()}
    got = jax.jit(dev.compute)(jnp.asarray(scores), consts)
    assert got.dtype == jnp.float32
    assert abs(float(got) - host) < 1e-6, (float(got), host)


def test_device_auc_lowers_to_one_sort_and_no_index_operation():
    """The metric program's text BEFORE compilation (the same on a CPU and
    for the chip): the weights ride the one sort and the runs' bounds are
    running scans, so no operation of it takes an index for each score. A
    gather or a scatter over the scores cost the four-chip benchmark cell
    0.15 s a call where the sort cost under 0.01 (PERF.md 6, PR 51)."""
    import re
    from collections import Counter

    n = 4096
    data = EvaluationData(labels=np.zeros(n), offsets=np.zeros(n),
                          weights=np.ones(n), ids={})
    dev = device_evaluator(parse_evaluator("AUC"), data)
    text = jax.jit(dev.compute).lower(
        jax.ShapeDtypeStruct((n,), jnp.float32), dev.consts).as_text()
    ops = Counter(re.findall(r"stablehlo\.(\w+)", text))
    assert ops["sort"] == 1, ops
    assert not any("gather" in op or "scatter" in op for op in ops), ops
    assert ops["reduce_window"] >= 1, ops  # the scans are there, as scans


def test_best_model_selection_agrees_mesh_vs_host(rng):
    """VERDICT r5 weak #2: global AUC on mesh is now EXACT (the sort-based
    device form replaced the 8192-bin histogram whose ≲1e-3 error could
    flip best-model selection). Candidates whose host AUCs sit within 1e-3
    of each other must rank identically under the device metric computed
    from mesh-sharded scores."""
    n = 512
    scores, data = _data(rng, n=n)
    ev = parse_evaluator("AUC")
    mesh = make_mesh(data=8, model=1)
    sharding = NamedSharding(mesh, P("data"))

    def place(a):
        return jax.device_put(np.asarray(a), sharding)

    dev = device_evaluator(ev, data, place=place)

    # candidate "models" = tiny perturbations of one score vector — their
    # AUCs cluster within ~1e-3, the regime the histogram got wrong
    candidates = [
        scores + 2e-3 * rng.normal(size=n) for _ in range(6)
    ]
    host_aucs = [ev.evaluate(s, data) for s in candidates]
    dev_aucs = [
        float(jax.jit(dev.compute)(place(s), dev.consts))
        for s in candidates
    ]
    spreads = np.ptp(host_aucs)
    assert spreads < 1e-3, spreads  # the scenario under test
    np.testing.assert_allclose(dev_aucs, host_aucs, rtol=1e-9, atol=1e-12)
    assert int(np.argmax(dev_aucs)) == int(np.argmax(host_aucs))

    # same agreement for AUPR's new device form
    ev_pr = parse_evaluator("AUPR")
    dev_pr = device_evaluator(ev_pr, data, place=place)
    host_pr = [ev_pr.evaluate(s, data) for s in candidates]
    dev_prs = [
        float(jax.jit(dev_pr.compute)(place(s), dev_pr.consts))
        for s in candidates
    ]
    np.testing.assert_allclose(dev_prs, host_pr, rtol=1e-9, atol=1e-12)
    assert int(np.argmax(dev_prs)) == int(np.argmax(host_pr))


def test_device_metric_padding_rows_inert(rng):
    # pad scores 100x the real range: the sort-based metrics (AUC/AUPR)
    # must keep them off the threshold ladder, not just weight them out
    scores, data = _data(rng, n=61)
    padded_scores = np.concatenate([scores, rng.normal(size=3) * 100])
    for spec in ("RMSE", "AUC:queryId", "PRECISION@3:queryId", "AUC", "AUPR"):
        ev = parse_evaluator(spec)
        host = ev.evaluate(scores, data)
        dev = device_evaluator(ev, data, n_pad=64)
        got = float(dev.compute(jnp.asarray(padded_scores), dev.consts))
        np.testing.assert_allclose(got, host, rtol=1e-9, err_msg=spec)


def test_device_metric_on_sharded_scores(rng):
    """Consts placed P('data') on the 8-device mesh, scores sharded: the
    reduction runs under jit over the mesh and matches the host."""
    scores, data = _data(rng, n=512)
    mesh = make_mesh(data=8, model=1)
    sharding = NamedSharding(mesh, P("data"))

    def place(a):
        return jax.device_put(np.asarray(a), sharding)

    s_sharded = place(scores)
    for spec in ("RMSE", "LOGISTIC_LOSS", "RMSE:queryId", "AUC:queryId"):
        ev = parse_evaluator(spec)
        dev = device_evaluator(ev, data, place=place)
        got = float(jax.jit(dev.compute)(s_sharded, dev.consts))
        np.testing.assert_allclose(
            got, ev.evaluate(scores, data), rtol=1e-9, err_msg=spec
        )


def test_unsupported_evaluator_returns_none(rng):
    _, data = _data(rng)
    # AUPR gained an exact device form (it used to be the host fallback)
    assert device_evaluator(parse_evaluator("AUPR"), data) is not None

    # evaluators outside the registry still fall back to the host path
    from photon_ml_tpu.evaluation.evaluators import Evaluator

    class CustomEvaluator(Evaluator):
        name = "CUSTOM"
        larger_is_better = True

        def evaluate(self, scores, data):  # pragma: no cover
            return 0.0

    assert device_evaluator(CustomEvaluator(), data) is None


def test_train_distributed_validation_uses_device_metrics(rng):
    """The fused trainer's validation pass: device metrics (incl. a
    per-query one and the sort-based AUC/AUPR) must reproduce the
    host-evaluated metric history."""
    from photon_ml_tpu.data.game_data import build_game_dataset
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
        train_distributed,
    )
    from photon_ml_tpu.types import TaskType

    n, d = 300, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d)
    logits = x @ w_true
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    qids = np.array([f"q{i}" for i in rng.integers(0, 11, size=n)])

    def ds(sl):
        return build_game_dataset(
            labels=y[sl], feature_shards={"g": x[sl]},
            ids={"queryId": qids[sl]},
        )

    train, val = ds(slice(0, 200)), ds(slice(200, 300))
    eval_data = EvaluationData(
        labels=y[200:300].astype(np.float64),
        offsets=np.zeros(100),
        weights=np.ones(100),
        ids={"queryId": qids[200:300]},
    )
    evaluators = [parse_evaluator(s)
                  for s in ("AUC", "AUC:queryId", "AUPR")]
    opt = OptimizerConfig(max_iterations=10)
    program = GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("g", opt, l2_weight=0.1),
        (),
    )
    mesh = make_mesh(data=8, model=1)
    result = train_distributed(
        program, train, {}, mesh=mesh, num_iterations=1,
        validation_dataset=val, validation_evaluators=evaluators,
        validation_eval_data=eval_data,
    )
    got = result.metric_history[-1]

    # recompute all three host-side from gathered scores
    program2 = GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("g", opt, l2_weight=0.1), (),
    )
    r2 = train_distributed(
        program2, train, {}, num_iterations=1,
        validation_dataset=val, validation_evaluators=evaluators,
        validation_eval_data=eval_data,
    )
    host = r2.metric_history[-1]
    for k in ("validate:AUC", "validate:AUC:queryId", "validate:AUPR"):
        np.testing.assert_allclose(got[k], host[k], rtol=1e-6, err_msg=k)
    assert np.isfinite(result.best_metric)


def test_distributed_scorer_evaluate_dataset_matches_host(rng):
    from photon_ml_tpu.algorithm.coordinates import CoordinateOptimizationConfig
    from photon_ml_tpu.data.game_data import build_game_dataset
    from photon_ml_tpu.estimators import FixedEffectCoordinateConfig, GameEstimator
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.parallel.scoring import DistributedScorer
    from photon_ml_tpu.types import TaskType

    n, d = 300, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    qids = np.array([f"q{i}" for i in rng.integers(0, 9, size=n)])
    ds = build_game_dataset(
        labels=y, feature_shards={"g": x}, ids={"queryId": qids}
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fe": FixedEffectCoordinateConfig(
                "g",
                CoordinateOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=8),
                    l2_weight=0.5,
                ),
            )
        },
        num_iterations=1,
    )
    model = est.fit(ds).model
    mesh = make_mesh(data=8, model=1)
    specs = ("RMSE", "AUC:queryId", "AUPR")
    got = DistributedScorer(model, mesh).evaluate_dataset(ds, specs)

    scores = DistributedScorer(model, None).score_dataset(ds)
    data = EvaluationData(
        labels=y.astype(np.float64), offsets=np.zeros(n),
        weights=np.ones(n), ids={"queryId": qids},
    )
    for s in specs:
        ev = parse_evaluator(s)
        np.testing.assert_allclose(
            got[ev.name], ev.evaluate(scores, data), rtol=1e-6, err_msg=s
        )
