"""A ratings GAME model through the normal path: squared loss, a fixed effect
and three random effects (two of them over ONE feature shard) under
``OptimizerType.AUTO``, which the program resolves to L-BFGS and batched
NEWTON; Newton's stop at the float's floor, its scopes and its counts.

The data and the plain reference are the benchmark's
(``benchmark/datagen_ratings.py``, ``benchmark/references/game-ymusic-r2.py``)
at a tiny size; the fused step's compile for a described v5e lives in
``tests/test_tpu_compile.py``, the one file that describes a topology.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_ratings
from benchmark.manifest import HERE, load_module
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import GameDataset, build_random_effect_dataset
from photon_ml_tpu.evaluation.evaluators import EvaluationData, parse_evaluator
from photon_ml_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import newton
from photon_ml_tpu.optim.common import (
    BUCKET_COUNT_NAMES,
    SOLVER_COUNT_NAMES,
    ConvergenceReason,
    bucket_count_parts,
    bucket_counts,
    lane_trace_of,
)
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType, solve
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainProgram,
    RandomEffectStepSpec,
    SweepCounts,
    train_distributed,
)
from photon_ml_tpu.telemetry import program_ledger
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.types import TaskType

CONFIG = {
    "rows": 6144, "validation_rows": 600, "data_seed": 50,
    "users": {"count": 60, "min": 20, "max": 3000, "a": 1.3},
    "songs": {"count": 400, "min": 1, "max": 3000, "a": 1.05},
    "artists": {"count": 30, "min": 1, "max": 200, "a": 1.0},
    "widths": {"global_features": 31, "entity_features": 7, "global_nnz": 8,
               "entity_nnz": 4},
    "truth": {"global_scale": 0.1, "entity_scale": 0.12, "entity_bias_scale": 0.3,
              "noise": 1.0},
    "l2_weight": 1.0, "coordinate_descent_iterations": 3,
}
SHARDS = {"global": "x_global", "per_user": "x_user", "per_item": "x_item"}
RE = (("user", "per_user"), ("song", "per_item"), ("artist", "per_item"))
AUTO = OptimizerConfig(optimizer_type=OptimizerType.AUTO, max_iterations=10,
                       rel_function_tolerance=1e-6)


def dataset_of(split, dtype=np.float32):
    n = len(split["y"])
    host = {"labels": split["y"].astype(dtype), "offsets": np.zeros(n, dtype),
            "weights": np.ones(n, dtype),
            **{f"shard/{k}": split[v].astype(dtype) for k, v in SHARDS.items()},
            **{f"entity_idx/{t}": split[t] for t, _ in RE}}
    return GameDataset(
        unique_ids=np.arange(n, dtype=np.int64),
        labels=jnp.asarray(host["labels"]), offsets=jnp.asarray(host["offsets"]),
        weights=jnp.asarray(host["weights"]),
        feature_shards={k: jnp.asarray(host[f"shard/{k}"]) for k in SHARDS},
        entity_idx={t: jnp.asarray(split[t]) for t, _ in RE},
        entity_vocabs={t: np.arange(CONFIG[t + "s"]["count"]).astype(str)
                       for t, _ in RE},
        host_cache=host)


def packed_and_program(data, dtype=np.float32):
    dataset = dataset_of(data["train"], dtype)
    re_datasets = {t: build_random_effect_dataset(
        dataset, t, shard, bucket_sizes=(8, 32, 128)) for t, shard in RE}
    program = GameTrainProgram(
        TaskType.LINEAR_REGRESSION, FixedEffectStepSpec("global", AUTO, l2_weight=1.0),
        tuple(RandomEffectStepSpec(t, shard, AUTO, l2_weight=1.0) for t, shard in RE))
    return dataset, re_datasets, program


@pytest.fixture(scope="module")
def ratings():
    """The tiny data set, its packed coordinates and the program under AUTO."""
    data = datagen_ratings.make_ratings(CONFIG, seed=3)
    return (data,) + packed_and_program(data)


def kept_rows(re_datasets, n):
    kept = {}
    for t, ds in re_datasets.items():
        mask = np.zeros(n, bool)
        for bucket in ds.buckets:
            rows = np.asarray(bucket.sample_rows).ravel()
            mask[rows[rows >= 0]] = True
        kept[t] = mask
    return kept


def test_auto_resolves_to_lbfgs_and_newton_once_at_the_programs_build(ratings):
    program = ratings[3]
    assert program.fe.optimizer.optimizer_type == OptimizerType.LBFGS
    assert [s.optimizer.optimizer_type for s in program.re_specs] == [
        OptimizerType.NEWTON] * 3
    # two coordinates over ONE feature shard, each with its own table
    assert [s.feature_shard_id for s in program.re_specs] == [
        "per_user", "per_item", "per_item"]


def test_the_three_coordinate_fit_agrees_with_the_references_block_descent(ratings):
    data, dataset, re_datasets, program = ratings
    validation = dataset_of(data["validation"])
    result = train_distributed(
        program, dataset, re_datasets, num_iterations=3,
        validation_dataset=validation, validation_evaluators=[parse_evaluator("RMSE")],
        validation_eval_data=EvaluationData(
            labels=data["validation"]["y"],
            offsets=np.zeros(len(data["validation"]["y"]), np.float32),
            weights=np.ones(len(data["validation"]["y"]), np.float32)))
    reference = load_module(os.path.join(HERE, "references", "game-ymusic-r2.py"))
    # the top rung (128) caps the head entities: the reference trains on the
    # rows the packer kept and scores them all
    kept = kept_rows(re_datasets, CONFIG["rows"])
    assert not kept["user"].all() and not kept["artist"].all()
    expected = reference.fit(data, CONFIG, kept, jax.devices()[:1])
    rmse = [h["validate:RMSE"] for h in result.metric_history]
    assert np.allclose(result.losses, expected["losses"], rtol=2e-5)
    assert np.allclose(rmse, expected["val_rmse"], atol=2e-5)
    # lower is better: the state kept as best is the lowest RMSE's
    assert result.best_metric == min(rmse)
    for name, table in (("fe", result.state.fe_coefficients),
                        *((t, result.state.re_tables[t]) for t, _ in RE)):
        gap = np.linalg.norm(np.asarray(table, np.float64) - expected[name])
        assert gap / np.linalg.norm(expected[name]) < 3e-3, name
    # and at the program's OWN coefficients the float64 loss is the reported one
    own = reference.evaluate(data, {"fe": np.asarray(result.state.fe_coefficients), **{
        t: np.asarray(result.state.re_tables[t]) for t, _ in RE}})
    assert result.losses[-1] == pytest.approx(own["loss"], rel=1e-5)
    assert rmse[-1] == pytest.approx(own["val_rmse"], abs=1e-5)


# -- the stop at the float's floor ---------------------------------------------


def ridge_lanes(lanes=4000, cap=32, d=16, seed=0):
    rng = np.random.default_rng(seed)
    x = np.zeros((lanes, cap, d), np.float32)
    cols = rng.integers(0, d - 1, (lanes, cap, 8))
    np.put_along_axis(x, cols, rng.standard_normal((lanes, cap, 8)).astype(np.float32), 2)
    x[..., d - 1] = 1.0
    w = rng.normal(scale=0.3, size=(lanes, d)).astype(np.float32)
    y = np.clip(np.round(3 + np.einsum("ecd,ed->ec", x, w)
                         + rng.standard_normal((lanes, cap))), 1, 5).astype(np.float32)
    offsets = (3 + rng.normal(size=(lanes, cap))).astype(np.float32)
    return x, y, offsets


def solve_lanes(loss, x, y, offsets, w0, config=None):
    config = config or OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON, max_iterations=10,
        rel_function_tolerance=1e-6)
    objective = GLMObjective(loss=loss, l2_weight=1.0)

    def one(f, l, o, start):
        return solve(config, objective.bind(LabeledPointBatch(
            features=f, labels=l, offsets=o, weights=jnp.ones_like(l))), start)

    return jax.vmap(one)(x, y, offsets, w0)


def test_a_ridge_lane_under_vmap_stops_within_two_rounds_counted():
    x, y, offsets = ridge_lanes()
    result = solve_lanes(SquaredLoss(), x, y, offsets, jnp.zeros(x.shape[::2], jnp.float32))
    rounds = np.asarray(result.iterations)
    assert rounds.max() == 2 and rounds.min() >= 1  # the exact step and one check
    assert not (np.asarray(result.reason) == ConvergenceReason.MAX_ITERATIONS).any()
    # the exact answer: the ridge normal equations in float64
    x64 = x.astype(np.float64)
    exact = np.linalg.solve(np.einsum("ecd,ecf->edf", x64, x64) + np.eye(x.shape[2]),
                            np.einsum("ecd,ec->ed", x64, (y - offsets).astype(np.float64))
                            [..., None])[..., 0]
    gap = np.linalg.norm(np.asarray(result.coefficients) - exact) / np.linalg.norm(exact)
    assert gap < 2e-6
    # counted: a round's trials are its five candidates; a lane the floor ended
    # says so; a round that accepted no candidate is a rejected one
    trials = np.asarray(result.line_search_trials)
    assert (trials.sum(1) == len(newton._ALPHAS) * rounds).all()
    assert (trials[:, 0] == 0).all() and (trials[:, 3:] == 0).all()
    floored = np.asarray(result.floor_exits)
    by_function = np.asarray(result.reason) == ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE
    assert set(floored) <= {0, 1} and floored.sum() > 0 and not (floored & ~by_function).any()
    rejected = np.asarray(result.rejected_rounds)
    assert (rejected <= rounds - 1).all() and rejected.sum() > 0  # the first round moves
    # a lane started AT its minimum stops in its first round
    again = solve_lanes(SquaredLoss(), x, y, offsets, result.coefficients)
    assert np.asarray(again.iterations).max() == 1
    # and the bucket's counts are the lanes' own
    valid = jnp.arange(x.shape[0]) % 7 != 0
    counts = dict(zip(BUCKET_COUNT_NAMES, np.asarray(bucket_counts(
        *bucket_count_parts(lane_trace_of(result, valid)))).tolist()))
    assert counts["lockstep_iterations"] == 2  # Newton's lock-step rounds
    assert counts["line_searches"] == rounds[np.asarray(valid)].sum()  # its lanes' rounds
    assert counts["rejected_rounds"] == rejected[np.asarray(valid)].sum()
    assert counts["lane_solves"] == int(valid.sum())
    assert counts["lanes_function_tolerance"] == by_function[np.asarray(valid)].sum()


def test_without_the_floor_a_ridge_buckets_last_lane_runs_to_the_cap(monkeypatch):
    """What the rule is for (the parent's loop is this one with the floor out of
    reach): some lane of a few thousand finds no candidate strictly lower,
    round after round, and the bucket runs ``max_iterations``."""
    monkeypatch.setattr(newton, "line_search_floor", lambda f: -jnp.ones_like(f))
    x, y, offsets = ridge_lanes()
    result = solve_lanes(SquaredLoss(), x, y, offsets, jnp.zeros(x.shape[::2], jnp.float32))
    assert np.asarray(result.iterations).max() == 10
    assert int(np.asarray(result.floor_exits).sum()) == 0


def glm_problem(loss, dtype, seed):
    rng = np.random.default_rng(seed)
    n, d = 400, 6
    x = rng.normal(size=(n, d)).astype(dtype)
    x[:, -1] = 1.0
    margin = x @ rng.normal(scale=0.5, size=d)
    y = (rng.poisson(np.exp(margin)) if isinstance(loss, PoissonLoss)
         else rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(dtype)
    batch = LabeledPointBatch(features=jnp.asarray(x), labels=jnp.asarray(y),
                              offsets=jnp.zeros(n, dtype), weights=jnp.ones(n, dtype))
    return GLMObjective(loss=loss, l2_weight=1.0).bind(batch), jnp.zeros(d, dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["float32", "float64"])
@pytest.mark.parametrize("loss", [LogisticLoss(), PoissonLoss()],
                         ids=["logistic", "poisson"])
def test_logistic_and_poisson_solves_read_what_they_read_before(loss, dtype, monkeypatch):
    """The floor is a rule of the solver, not of the squared loss: where a
    solve converged before, it ends at the same point, to rounding, in no more
    rounds (in float64 the floor lies far under every other stop)."""
    objective, w0 = glm_problem(loss, dtype, seed=11)

    def run():
        return newton.minimize_newton(
            objective.value_and_grad, objective.hessian_matrix, w0,
            value_fn=objective.value, max_iter=25)

    now = run()
    monkeypatch.setattr(newton, "line_search_floor", lambda f: -jnp.ones_like(f))
    before = run()
    assert int(before.reason) != ConvergenceReason.MAX_ITERATIONS
    assert int(now.iterations) <= int(before.iterations)
    eps = np.finfo(dtype).eps
    assert float(now.value) == pytest.approx(float(before.value), rel=4 * eps)
    assert np.allclose(now.coefficients, before.coefficients, rtol=0,
                       atol=(2e-3 if dtype == np.float32 else 1e-9))
    if dtype == np.float64:
        assert int(now.iterations) == int(before.iterations)
        assert np.array_equal(now.coefficients, before.coefficients)
        assert int(now.floor_exits) == 0


# -- the counts and the scopes of the fused step ----------------------------------


def test_the_three_new_counts_sum_as_the_lanes_own(ratings, monkeypatch):
    """One fused sweep run op by op (``jax.disable_jit``), every bucket's lane
    trace kept as the step saw it: the step's sums are the lanes' own."""
    from photon_ml_tpu.parallel import distributed

    _data, dataset, re_datasets, program = ratings
    traces = []
    traced_solve = distributed.solve_entity_bucket_traced

    def keeping(*args):
        table, trace = traced_solve(*args)
        traces.append(trace)
        return table, trace

    monkeypatch.setattr(distributed, "solve_entity_bucket_traced", keeping)
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    state = program._carried(data, program.init_state(dataset, re_datasets, None))
    with jax.disable_jit():
        _state, _loss, counts = program._step_impl(data, buckets, state)
    counts = SweepCounts(program.solve_rows(buckets),
                         np.asarray(counts).tolist()).counters()
    assert len(traces) == sum(len(buckets[t]) for t, _ in RE)
    rounds = [np.asarray(t.iterations)[np.asarray(t.valid)] for t in traces]
    assert counts["newton_lockstep_rounds"] == sum(
        int(np.asarray(t.iterations).max()) for t in traces)
    assert counts["newton_lane_rounds"] == sum(int(r.sum()) for r in rounds)
    assert counts["newton_rejected_rounds"] == sum(
        int(np.asarray(t.rejected_rounds)[np.asarray(t.valid)].sum()) for t in traces)
    # the exact step and at most one check, every bucket of the sweep
    assert len(traces) <= counts["newton_lockstep_rounds"] <= 2 * len(traces)
    assert all(r.min() >= 1 for r in rounds)  # from zero every lane moves
    # a round's trials are its five candidates; the fixed effect's are its own
    assert counts["lockstep_trials"] == len(newton._ALPHAS) * counts["newton_lockstep_rounds"]
    assert counts["lane_trials"] == len(newton._ALPHAS) * counts["newton_lane_rounds"]
    assert counts["line_searches"] == counts["newton_lane_rounds"]
    assert counts["newton_rejected_rounds"] < counts["newton_lane_rounds"]
    assert counts["fe_trials"] > 0 and counts["mf_lane_trials"] == 0
    # the lanes Newton solves are the random effects' lanes: every valid lane
    # once, by why it stopped, and the rows its rounds' candidates paid for and
    # wanted, in that family; of its own family the three names above, no more
    # (further ``newton_`` totals wait for a reader in the cell: ROADMAP R7 i)
    valid_lanes = sum(int(np.asarray(t.valid).sum()) for t in traces)
    assert counts["lane_solves"] == valid_lanes
    assert valid_lanes == sum(counts["lanes_" + reason] for reason in (
        "max_iterations", "function_tolerance", "gradient_tolerance", "search_failed"))
    assert counts["lanes_max_iterations"] == 0
    assert counts["row_trials_paid"] == len(newton._ALPHAS) * sum(
        int(np.asarray(t.iterations).max()) * int(np.prod(b["labels"].shape))
        for t, b in zip(traces, (b for k, _ in RE for b in buckets[k])))
    assert 0 < counts["row_trials_wanted"] <= counts["row_trials_paid"]
    assert sorted(key for key in counts if key.startswith("newton_")) == [
        "newton_lane_rounds", "newton_lockstep_rounds", "newton_rejected_rounds"]
    assert not any(key.startswith("mf") and value for key, value in counts.items())


def test_a_fit_adds_the_new_counts_to_the_registry_like_the_rest(ratings):
    _data, dataset, re_datasets, program = ratings
    registry = default_registry()
    before = {n: registry.counter("solver/" + n).value for n in SOLVER_COUNT_NAMES}
    train_distributed(program, dataset, re_datasets, num_iterations=2)
    gained = {n: registry.counter("solver/" + n).value - before[n]
              for n in SOLVER_COUNT_NAMES}
    buckets_a_sweep = sum(len(ds.buckets) for ds in re_datasets.values())
    assert 2 * buckets_a_sweep <= gained["newton_lockstep_rounds"] <= 4 * buckets_a_sweep
    assert gained["newton_lane_rounds"] > gained["newton_rejected_rounds"] > 0
    assert {"newton_lockstep_rounds", "newton_lane_rounds",
            "newton_rejected_rounds"} <= set(SOLVER_COUNT_NAMES)


def test_the_fused_steps_newton_instructions_carry_the_four_scopes(ratings):
    _data, dataset, re_datasets, program = ratings
    train_distributed(program, dataset, re_datasets, num_iterations=1)
    record = program_ledger.compiled_scopes("train/step")
    names = {op_name for _signature, op_name in record.instructions.values() if op_name}
    for t, _ in RE:
        for phase in ("hessian", "solve", "shrink", "gradient"):
            assert any(f"re/{t}/solve" in n and f"newton/{phase}/" in n for n in names), (
                t, phase)
    assert not any("lbfgs/" in n and "/re/" in n for n in names)  # no L-BFGS lane
    assert any("fe/solve" in n and "lbfgs/line_search" in n for n in names)


def test_the_lanes_hessian_is_contracted_at_the_highest_precision():
    """The line of ``ops/objective.py`` that a TPU would otherwise feed to the
    MXU in bfloat16: every ``dot_general`` under ``newton/hessian`` in the
    lanes' jaxpr asks for the highest precision (``tests/test_tpu_compile.py``
    reads the same off the text compiled for a described v5e)."""
    x, y, offsets = ridge_lanes(lanes=8, cap=8)
    jaxpr = jax.make_jaxpr(lambda *a: solve_lanes(SquaredLoss(), *a).coefficients)(
        x, y, offsets, jnp.zeros(x.shape[::2], jnp.float32))
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general" and "newton/hessian" in str(
                    eqn.source_info.name_stack):
                found.append(eqn.params["precision"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    highest = jax.lax.Precision.HIGHEST
    assert found and all(p in (highest, (highest, highest)) for p in found), found
