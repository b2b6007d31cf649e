"""The fused sweep's solver counts: a third output of the step program, one
small array with a row for every solve (optim/common.BUCKET_COUNT_NAMES),
kept on the program unread, read once after the loss under
``train/solver_counts`` and summed into the registry's ``solver/*`` counters
by family and by coordinate; a run journal gets the rows themselves
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
from photon_ml_tpu.optim.common import (
    BUCKET_COUNT_NAMES,
    SOLVER_COUNT_NAMES,
    ConvergenceReason,
    SolverResult,
    bucket_count_parts,
    bucket_counts,
    lane_trace_of,
)
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
    SolveRow,
    SweepCounts,
    train_distributed,
)
from photon_ml_tpu.telemetry.journal import RunJournal, read_journal
from photon_ml_tpu.telemetry.program_ledger import (
    ProgramLedger,
    install_ledger,
    uninstall_ledger,
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


REASONS = ("lanes_max_iterations", "lanes_function_tolerance",
           "lanes_gradient_tolerance", "lanes_search_failed")
FAMILY_TOTALS = ("lockstep_iterations", "lane_solves", *REASONS,
                 "row_trials_paid", "row_trials_wanted")
COORDINATE_COUNTS = ("lockstep_trials", "lockstep_iterations",
                     "row_trials_paid", "row_trials_wanted")


def glmix(dtype, **optimizer):
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
    opt = OptimizerConfig(**{
        "optimizer_type": OptimizerType.LBFGS, "max_iterations": 6,
        "rel_function_tolerance": 1e-6, **optimizer})
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
    assert counts.rows == program.solve_rows(buckets)
    assert np.shape(counts.array) == (len(counts.rows), len(BUCKET_COUNT_NAMES))
    counters = counts.counters()
    # the thirteen first and whole, then the random effects' new totals and
    # four counters a coordinate: no other family's, and nothing a bucket
    assert list(counters) == [
        *SOLVER_COUNT_NAMES, *FAMILY_TOTALS,
        *(f"re/{t}/{name}" for t in RE_TYPES for name in COORDINATE_COUNTS)]
    assert all(isinstance(v, int) for v in counters.values())
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
    taken = program.take_solver_counts()
    counts = taken.counters()
    # a row a bucket of every half-step, the row side's ahead of the column
    # side's, after the random effects' and ahead of the fixed effect's
    sides = buckets["__mf__"]["mf"]
    assert [row.coordinate for row in taken.rows if row.family == "mf"] == [
        f"mf/mf/{side}" for side in ("row", "col") for _ in sides[side]]
    assert [row.family for row in taken.rows] == sorted(
        (row.family for row in taken.rows), key=("re", "mf", "fe").index)

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
    # the factorization's sides fill their own family and their own
    # coordinates, and nothing of Newton's
    lanes = sum(b["labels"].shape[0] for side in sides.values() for b in side)
    assert counts["mf_lane_solves"] == lanes == sum(
        counts["mf_" + reason] for reason in REASONS)
    assert 0 < counts["mf_row_trials_wanted"] < counts["mf_row_trials_paid"]
    for name in COORDINATE_COUNTS:
        assert counts["mf_" + name] == (
            counts[f"mf/mf/row/{name}"] + counts[f"mf/mf/col/{name}"])
        assert counts[name] == sum(counts[f"re/{t}/{name}"] for t in RE_TYPES)
    assert not any(key.startswith("newton_") and value
                   for key, value in counts.items())
    assert not any(key.startswith("newton_lane_solves") for key in counts)
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
    assert jaxpr.out_avals[-1].shape == (
        len(program.solve_rows(buckets)), len(BUCKET_COUNT_NAMES))
    counters = SweepCounts(program.solve_rows(buckets), np.zeros(
        jaxpr.out_avals[-1].shape, int).tolist()).counters()
    assert not any(key.startswith(("mf_row", "mf_lane_solves", "mf/"))
                   for key in counters)
    # of Newton's family the three names the step has had since PR 50, no more
    assert [key for key in counters if key.startswith("newton_")] == [
        name for name in SOLVER_COUNT_NAMES if name.startswith("newton_")]


def _lanes_result(lanes, rng, iterations_at_most=6):
    """A vmapped SolverResult as a solver fills it: a lane's trials stand in
    slots 1 .. iterations, zeros elsewhere."""
    slots = iterations_at_most + 1
    iterations = rng.integers(0, slots, lanes)
    trials = rng.integers(1, 5, (lanes, slots)) * (
        (np.arange(slots) >= 1) & (np.arange(slots) <= iterations[:, None]))
    zeros = np.zeros(lanes)
    return SolverResult(
        coefficients=jnp.zeros((lanes, 3)), value=jnp.asarray(zeros),
        gradient_norm=jnp.asarray(zeros),
        iterations=jnp.asarray(iterations, jnp.int32),
        reason=jnp.asarray(rng.integers(1, 5, lanes), jnp.int32),
        value_history=jnp.zeros((lanes, slots)),
        grad_norm_history=jnp.zeros((lanes, slots)),
        line_search_trials=jnp.asarray(trials, jnp.int32),
        floor_exits=jnp.asarray(rng.integers(0, 2, lanes), jnp.int32),
        rejected_rounds=None)


@pytest.mark.parametrize("lanes, parts", [
    (1, 1), (12, 1), (12, 2), (12, 4), (12, 6), (64, 4), (64, 8)])
def test_a_buckets_row_is_numpys_own_count_of_its_trace(lanes, parts):
    """Whatever the parts the lanes are counted in (on a mesh: a chip's own
    each), a bucket's row is: the slowest lane's outer trips and, iteration by
    iteration, the slowest lane's trials, over ALL lanes; everything else over
    the valid lanes alone: padding lanes are in no reason and in no sum."""
    rng = np.random.default_rng(100 * lanes + parts)
    result = _lanes_result(lanes, rng)
    valid = np.ones(1, bool) if lanes == 1 else rng.random(lanes) < 0.7
    trace = lane_trace_of(result, jnp.asarray(valid))
    maxima, sums = bucket_count_parts(trace, parts)
    assert maxima.shape[0] == sums.shape[0] == parts
    row = dict(zip(BUCKET_COUNT_NAMES, np.asarray(bucket_counts(maxima, sums)).tolist()))
    iterations, trials = np.asarray(result.iterations), np.asarray(result.line_search_trials)
    reason = np.asarray(result.reason)
    assert row["lockstep_iterations"] == iterations.max()
    assert row["lockstep_trials"] == trials.max(axis=0).sum() == int(trace.lockstep_trials)
    assert row["lane_trials"] == trials[valid].sum()
    assert row["line_searches"] == iterations[valid].sum()
    assert row["floor_exits"] == np.asarray(result.floor_exits)[valid].sum()
    assert row["lane_solves"] == valid.sum() == sum(row[name] for name in REASONS)
    for name, code in zip(REASONS, (
            ConvergenceReason.MAX_ITERATIONS,
            ConvergenceReason.FUNCTION_VALUES_WITHIN_TOLERANCE,
            ConvergenceReason.GRADIENT_WITHIN_TOLERANCE,
            ConvergenceReason.LINE_SEARCH_FAILED)):
        assert row[name] == (reason[valid] == code).sum()
    assert row["rejected_rounds"] == 0  # no Newton lane
    # what the device paid against what a live lane asked for, in rows
    cap = 32
    counters = SweepCounts((SolveRow("re", "re/t", lanes, cap, False),),
                           [list(row.values())]).counters()
    assert counters["row_trials_paid"] == row["lockstep_trials"] * lanes * cap
    assert counters["row_trials_wanted"] == row["lane_trials"] * cap
    assert counters["row_trials_wanted"] <= counters["row_trials_paid"]
    if lanes == 1:  # a bucket of one lane pays what the lane wants
        assert counters["row_trials_wanted"] == counters["row_trials_paid"] > 0
    with pytest.raises(ValueError, match="do not divide"):
        bucket_count_parts(trace, lanes + 1)


def test_a_sweeps_rows_are_its_whole_solver_results_own(float64_fit):
    """One sweep from the start, its rows against the same sweep's WHOLE
    solver results: a bucket's ``lockstep_iterations`` is the largest
    ``iterations`` of its lanes, its lanes are counted once each by why they
    stopped, and the fixed effect's solve is a row of one lane."""
    dataset, re_datasets, program, _gained, _events = float64_fit
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    state = program.init_state(dataset, re_datasets, None)
    fe, lanes, _state = jax.jit(functools.partial(_whole_results, program))(
        data, buckets, state)
    program.step(data, buckets, state)
    taken = program.take_solver_counts()
    table = taken.table()
    shapes = [b["labels"].shape for t in RE_TYPES for b in buckets[t]]
    assert [(row["coordinate"], row["lanes"], row["cap"]) for row in table] == [
        *((f"re/{t}", *b["labels"].shape) for t in RE_TYPES for b in buckets[t]),
        ("fe/global", 1, 0)]
    for row, result, (e, cap) in zip(table, lanes, shapes):
        iterations = np.asarray(result.iterations)
        assert row["lockstep_iterations"] == iterations.max()
        assert row["line_searches"] == iterations.sum()
        assert row["lane_solves"] == e == sum(row[name] for name in REASONS)
        assert row["lanes_max_iterations"] == (
            np.asarray(result.reason) == ConvergenceReason.MAX_ITERATIONS).sum()
        assert row["lane_trials"] * cap <= row["lockstep_trials"] * e * cap
    assert table[-1]["lane_solves"] == 1 == sum(table[-1][name] for name in REASONS)
    assert table[-1]["lockstep_iterations"] == int(fe.iterations)
    assert table[-1]["lockstep_trials"] == table[-1]["lane_trials"] == int(
        jnp.sum(fe.line_search_trials))
    counters = taken.counters()
    assert counters["lockstep_iterations"] == sum(
        int(np.asarray(r.iterations).max()) for r in lanes)
    assert 0 < counters["row_trials_wanted"] < counters["row_trials_paid"]


def test_at_a_cap_of_one_iteration_every_lane_stops_at_the_cap():
    """``max_iterations=1`` from zero: no lane can meet a tolerance after one
    step, so every valid lane, and the fixed effect's one, is counted under
    ``lanes_max_iterations``: the pathology this count exists to show."""
    dataset, re_datasets, program = glmix(
        np.float64, max_iterations=1, tolerance=1e-14, rel_function_tolerance=1e-14)
    data, buckets = program.prepare_inputs(dataset, re_datasets, None)
    program.step(data, buckets, program.init_state(dataset, re_datasets, None))
    table = program.take_solver_counts().table()
    assert all(row["lanes_max_iterations"] == row["lane_solves"] > 0 for row in table)
    assert all(row["lockstep_iterations"] == 1 for row in table)


def test_a_run_journal_gets_a_row_a_sweep_and_nothing_else_gets_the_table(tmp_path):
    """With a program ledger installed that has a journal (``--telemetry-dir``)
    every sweep leaves one ``lane_counts`` row holding the table by bucket;
    the registry holds totals and a coordinate's four, never a bucket's."""
    dataset, re_datasets, program = glmix(np.float64)
    registry = default_registry()
    names = ("lockstep_trials", "lockstep_iterations", "lane_solves")
    before = {n: registry.counter("solver/" + n).value for n in names}
    journal = RunJournal(str(tmp_path))
    install_ledger(ProgramLedger(journal=journal))
    try:
        train_distributed(program, dataset, re_datasets, num_iterations=SWEEPS)
    finally:
        uninstall_ledger()
        journal.close()
    rows = [r for r in read_journal(journal.path) if r["kind"] == "lane_counts"]
    assert [r["sweep"] for r in rows] == list(range(1, SWEEPS + 1))
    buckets = sum(len(ds.buckets) for ds in re_datasets.values())
    for r in rows:
        assert len(r["buckets"]) == buckets + 1  # and the fixed effect's solve
        assert set(r["buckets"][0]) == {"coordinate", "lanes", "cap", *BUCKET_COUNT_NAMES}
    for name in names:
        assert registry.counter("solver/" + name).value - before[name] == sum(
            b[name] for r in rows for b in r["buckets"] if b["coordinate"] != "fe/global")
    counters = registry.snapshot()["counters"]
    assert not any(key.startswith("solver/") and any(ch.isdigit() for ch in key)
                   for key in counters)
    # and the run doctor renders the newest row by coordinate, every row by sweep
    from dev.doctor import run_doctor

    code, _findings, report = run_doctor(str(tmp_path))
    assert code == 0 and f"lanes, sweep {SWEEPS} of {SWEEPS} journaled" in report
    last = {b["coordinate"]: 0 for b in rows[-1]["buckets"]}
    for b in rows[-1]["buckets"]:
        last[b["coordinate"]] += b["lockstep_iterations"]
    for coordinate, trips in last.items():
        line = next(l for l in report.splitlines() if l.strip().startswith(coordinate + " "))
        assert f"trips {trips:>5}" in line and "stops " in line
        trend = report.splitlines()[report.splitlines().index(line) + 1]
        assert trend.split("by sweep: trials ")[1].split(";")[0].split() == [
            str(sum(b["lockstep_trials"] for b in r["buckets"]
                    if b["coordinate"] == coordinate)) for r in rows]
