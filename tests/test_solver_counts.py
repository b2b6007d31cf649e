"""The fused sweep's line-search counts: a third output of the step program,
kept on the program as device scalars, read after the loss under
``train/solver_counts`` and summed into the registry's ``solver/*`` counters
(PERF.md §3)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.algorithm.coordinates import _bucket_offsets
from photon_ml_tpu.algorithm.mf_coordinate import (
    build_mf_dataset,
    solve_mf_side_bucket,
)
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import (
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.optim.common import SOLVER_COUNT_NAMES
from photon_ml_tpu.optim.optimizer import (
    OptimizerConfig,
    OptimizerType,
    solve,
)
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainProgram,
    GameTrainState,
    MatrixFactorizationStepSpec,
    RandomEffectStepSpec,
    train_distributed,
)
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.telemetry.tracing import (
    Tracer,
    install_tracer,
    uninstall_tracer,
)
from photon_ml_tpu.types import TaskType

SWEEPS = 2
RE_TYPES = ("user", "item")


def glmix(dtype):
    """A GLMix problem with several buckets a coordinate, and its program."""
    rng = np.random.default_rng(7)
    n = 240
    keys = {"user": np.array([f"u{i}" for i in rng.zipf(1.6, size=n) % 20]),
            "item": np.array([f"i{i}" for i in rng.integers(0, 9, size=n)])}
    x_re = rng.normal(size=(n, 4))
    x_re[:, 0] = 1.0
    dataset = build_game_dataset(
        labels=(rng.uniform(size=n) < 0.5).astype(np.float64),
        feature_shards={"global": rng.normal(size=(n, 12)), "re": x_re},
        entity_keys=keys, dtype=dtype,
    )
    re_datasets = {t: build_random_effect_dataset(
        dataset, t, "re", bucket_sizes=(8, 32, 128)) for t in RE_TYPES}
    opt = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=6,
                          rel_function_tolerance=1e-6)
    program = GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("global", opt, l2_weight=0.5),
        tuple(RandomEffectStepSpec(t, "re", opt, l2_weight=1.0)
              for t in RE_TYPES),
    )
    return dataset, re_datasets, program


def counted_fit(dataset, re_datasets, program, sweeps=SWEEPS):
    """(what the six counters gained over a fit, the fit's ring events)"""
    registry = default_registry()
    before = {name: registry.counter("solver/" + name).value
              for name in SOLVER_COUNT_NAMES}
    tracer = install_tracer(Tracer(rank=0))
    try:
        train_distributed(program, dataset, re_datasets, num_iterations=sweeps)
    finally:
        uninstall_tracer()
    gained = {name: registry.counter("solver/" + name).value - before[name]
              for name in SOLVER_COUNT_NAMES}
    return gained, list(tracer.events())


def _whole_results(program, data, buckets, state):
    """One sweep again, coordinate by coordinate in the program's order, every
    solve's WHOLE SolverResult kept (a lane each for the bucket solves)."""
    scores = program._coordinate_scores(data, state)
    fe_offsets = program._sum_scores(data["offsets"], scores, "global")
    fe = solve(program.fe.optimizer, program._fe_objective.bind(LabeledPointBatch(
        features=data["features"]["global"], labels=data["labels"],
        offsets=fe_offsets, weights=data["weights"])), state.fe_coefficients)
    scores["global"] = program._fe_margin_score(data, fe.coefficients)
    tables, lanes = dict(state.re_tables), []
    for re_type in RE_TYPES:
        offsets = program._sum_scores(data["offsets"], scores, re_type)
        spec = program._re_by_name[re_type]
        objective = program._re_solve_objectives[re_type]
        for b in buckets[re_type]:
            result = jax.vmap(lambda f, l, o, w, w0: solve(
                spec.optimizer, objective.bind(LabeledPointBatch(
                    features=f, labels=l, offsets=o, weights=w)), w0))(
                        b["features"], b["labels"],
                        _bucket_offsets(b["sample_rows"], offsets),
                        b["weights"], tables[re_type][b["entity_rows"]])
            tables[re_type] = tables[re_type].at[b["entity_rows"]].set(
                result.coefficients)
            lanes.append(result)
        scores[re_type] = program._re_coordinate_score(
            data, re_type, tables[re_type], spec.feature_shard_id)
    return fe, lanes, GameTrainState(
        fe_coefficients=fe.coefficients, re_tables=tables,
        mf_rows=state.mf_rows, mf_cols=state.mf_cols, extra_fe=state.extra_fe)


@pytest.fixture(scope="module")
def float64_fit():
    dataset, re_datasets, program = glmix(np.float64)
    gained, events = counted_fit(dataset, re_datasets, program)
    return dataset, re_datasets, program, gained, events


def test_counters_equal_a_recount_from_whole_solver_results(float64_fit):
    dataset, re_datasets, program, gained, _events = float64_fit
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    state = program.init_state(dataset, re_datasets, None)
    expect = dict.fromkeys(SOLVER_COUNT_NAMES, 0)
    sweep = jax.jit(functools.partial(_whole_results, program))
    for _ in range(SWEEPS):
        fe, lanes, state = sweep(data, buckets, state)
        expect["fe_trials"] += int(jnp.sum(fe.line_search_trials))
        expect["fe_floor_exits"] += int(fe.floor_exits)
        for result in lanes:  # no mesh: no padding lane, every lane is valid
            trials = np.asarray(result.line_search_trials)
            expect["lockstep_trials"] += int(trials.max(axis=0).sum())
            expect["lane_trials"] += int(trials.sum())
            expect["floor_exits"] += int(jnp.sum(result.floor_exits))
            expect["line_searches"] += int(jnp.sum(result.iterations))
    assert gained == expect
    # several lanes a bucket: the device ran fewer trials than the lanes' sum
    assert 0 < gained["lockstep_trials"] < gained["lane_trials"]
    assert 0 < gained["line_searches"] <= gained["lane_trials"]
    assert gained["fe_trials"] > 0


def test_float32_fit_counts_searches_the_floor_ended():
    """Float32, four sweeps (the later ones start near their optimum): all
    six counters of a GLMix program are positive, and they hang together (a
    floor exit is a search, a search has a trial); with no factorization
    coordinate the ``mf_*`` four stay zero, and with no lane solved by
    Newton the ``newton_*`` three."""
    gained, _events = counted_fit(*glmix(np.float32), sweeps=4)
    assert all((gained[name] > 0) != name.startswith(("mf_", "newton_"))
               for name in SOLVER_COUNT_NAMES), gained
    assert gained["floor_exits"] <= gained["line_searches"] <= gained["lane_trials"]
    assert gained["lockstep_trials"] <= gained["lane_trials"]
    assert gained["fe_floor_exits"] <= 4  # at most one a fixed-effect solve


def test_step_still_returns_a_pair_and_keeps_the_counts_on_the_program(
        float64_fit):
    dataset, re_datasets, program, _gained, _events = float64_fit
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    state = program.init_state(dataset, re_datasets, None)
    assert program.take_solver_counts() is None  # the fit took its own
    out = program.step(data, buckets, state)
    assert len(out) == 2 and isinstance(out[0], GameTrainState)
    counts = program.take_solver_counts()
    assert sorted(counts) == sorted(SOLVER_COUNT_NAMES)
    assert all(isinstance(v, int) for v in counts.values())
    assert program.take_solver_counts() is None  # handed out once


def test_counts_are_read_after_the_loss_has_arrived(float64_fit):
    events = float64_fit[-1]
    waits = sorted((e for e in events if e.name == "train/loss_wait"),
                   key=lambda e: e.start)
    reads = sorted((e for e in events if e.name == "train/solver_counts"),
                   key=lambda e: e.start)
    assert len(waits) == len(reads) == SWEEPS
    for wait, read in zip(waits, reads):
        assert read.start >= wait.start + wait.dur
        assert read.parent == wait.parent  # the same sweep


MF_COUNTS = ("mf_lockstep_trials", "mf_lane_trials", "mf_floor_exits",
             "mf_line_searches")


def test_mf_counts_are_the_half_steps_lane_traces_summed():
    """A fused sweep with a factorization coordinate: its ``mf_*`` counts are
    the lane traces of its half-steps' bucket solves summed (row side, then
    column side against the rows just solved), apart from the random
    effects' four."""
    dataset, re_datasets, glmix_program = glmix(np.float64)
    mf_datasets = {"mf": build_mf_dataset(dataset, "user", "item",
                                          bucket_sizes=(8, 32, 128))}
    opt = glmix_program.fe.optimizer
    spec = MatrixFactorizationStepSpec("mf", "user", "item", 3, opt, l2_weight=1.0)
    program = GameTrainProgram(
        glmix_program.task, glmix_program.fe, glmix_program.re_specs,
        mf_specs=(spec,))
    data, buckets = program.prepare_inputs(dataset, re_datasets, mf_datasets)
    state = program.init_state(dataset, re_datasets, mf_datasets)
    new_state, _loss = program.step(data, buckets, state)
    counts = program.take_solver_counts()
    assert sorted(counts) == sorted(SOLVER_COUNT_NAMES)

    # the half-steps again, outside the step, at the offsets the step gave them
    scores = program._coordinate_scores(data, GameTrainState(
        fe_coefficients=new_state.fe_coefficients, re_tables=new_state.re_tables,
        mf_rows=state.mf_rows, mf_cols=state.mf_cols))
    offsets = program._sum_scores(data["offsets"], scores, "mf")
    expect = dict.fromkeys(MF_COUNTS, 0)
    rows, cols = state.mf_rows["mf"], state.mf_cols["mf"]
    sides = buckets["__mf__"]["mf"]
    for side in ("row", "col"):
        for b in sides[side]:
            table, other, other_idx = (
                (rows, cols, data["entity_idx"]["item"]) if side == "row"
                else (cols, rows, data["entity_idx"]["user"]))
            table, trace = solve_mf_side_bucket(
                program._mf_objectives["mf"], opt, b["labels"], b["weights"],
                b["entity_rows"], b["sample_rows"], other_idx, other, offsets, table)
            rows, cols = (table, cols) if side == "row" else (rows, table)
            assert bool(np.all(trace.valid))  # no mesh: no padding lane
            trials = np.asarray(trace.line_search_trials)
            expect["mf_lockstep_trials"] += int(trace.lockstep_trials)
            expect["mf_lane_trials"] += int(trials.sum())
            expect["mf_floor_exits"] += int(np.sum(trace.floor_exits))
            expect["mf_line_searches"] += int(np.sum(trace.iterations))
    assert {name: counts[name] for name in MF_COUNTS} == expect
    assert 0 < counts["mf_lockstep_trials"] < counts["mf_lane_trials"]
    np.testing.assert_allclose(np.asarray(new_state.mf_rows["mf"]), np.asarray(rows))
    np.testing.assert_allclose(np.asarray(new_state.mf_cols["mf"]), np.asarray(cols))
    # the random effects' lanes are counted beside them, under their own names
    assert counts["lockstep_trials"] > 0 and counts["lane_trials"] > 0


def test_without_an_mf_spec_the_mf_counts_are_zero_and_cost_nothing(float64_fit):
    """A GLMix program: the four ``mf_*`` entries of the step's count vector
    are constants of the program (literal zeros), not computed."""
    dataset, re_datasets, program, gained, _events = float64_fit
    assert [gained[name] for name in MF_COUNTS] == [0, 0, 0, 0]
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    state = program.init_state(dataset, re_datasets, None)
    jaxpr = jax.make_jaxpr(program._step_impl)(
        data, buckets, program._carried(data, state))
    assert jaxpr.out_avals[-1].shape == (len(SOLVER_COUNT_NAMES),)
