"""A sweep scores each coordinate once: the margins a fused step ends with
ride in ``GameTrainState.scores`` to the next one (PERF.md 6, PR 48).

Held here: (a) equality: a fit that carries its margins is the fit that
scores every coordinate anew at every sweep's entry (the parent's recursion,
had by emptying the carry before every sweep), bit for bit, on one device and
on four, with down-sampling and under the lane scheduler; four
devices against one as the agreement tests hold them; (b) structure: the
compiled step holds each coordinate's scoring once where the parent's holds
it twice, a fit compiles ``train/step`` and ``train/score`` once each, and
``train/entry_scorings`` reads one a fit; (c) the guards: a carry of other
rows or other coordinates is refused by name, a checkpoint holds none and a
resumed fit is the uninterrupted one, returned states hold none, and the
residual sum adds in canonical order whatever order the names sort in.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from photon_ml_tpu.algorithm.mf_coordinate import build_mf_dataset
from photon_ml_tpu.data.game_data import (
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.evaluation.evaluators import EvaluationData, parse_evaluator
from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
from photon_ml_tpu.optim.optimizer import (
    LaneSchedulerConfig,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.parallel.distributed import (
    ENTRY_SCORINGS,
    FixedEffectStepSpec,
    GameTrainProgram,
    GameTrainState,
    MatrixFactorizationStepSpec,
    RandomEffectStepSpec,
    train_distributed,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.telemetry.program_ledger import (
    ProgramLedger,
    compiled_scopes,
    install_ledger,
    scopes_of_text,
    uninstall_ledger,
)
from photon_ml_tpu.telemetry.registry import MetricsRegistry, default_registry
from photon_ml_tpu.types import TaskType

TASK = TaskType.LOGISTIC_REGRESSION
#: update order = canonical order: global, side, user, item, mf; the names
#: SORT otherwise (global, item, mf, side, user), which is the order a dict
#: has once it crossed ``jit`` inside a pytree
RE_TYPES = ("user", "item")
CANONICAL = ("global", "side", "user", "item", "mf")
SWEEPS = 3
OPT = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=4,
                      rel_function_tolerance=1e-6)
SOLVER_COUNTERS = ("solver/line_searches", "solver/lane_trials",
                   "solver/lockstep_trials", "solver/fe_trials",
                   "solver/mf_line_searches", "solver/mf_lockstep_trials")


def tiny_data(seed=48, n=328, n_val=64):
    """(dataset, random-effect buckets, factorization buckets, validation
    set and its evaluation data) of a tiny full-GAME fit; 328 rows divide by
    four, so that one device and four see the same rows."""
    rng = np.random.default_rng(seed)

    def split(rows):
        keys = {"user": np.array([f"u{i}" for i in rng.zipf(1.6, size=rows) % 12]),
                "item": np.array([f"i{i}" for i in rng.integers(0, 6, size=rows)])}
        x_re = rng.normal(size=(rows, 3))
        x_re[:, 0] = 1.0
        return dict(
            labels=(rng.uniform(size=rows) < 0.5).astype(np.float32),
            feature_shards={"global": rng.normal(size=(rows, 6)),
                            "side": rng.normal(size=(rows, 2)), "re": x_re},
            entity_keys=keys, dtype=np.float32)

    dataset = build_game_dataset(**split(n))
    validation = build_game_dataset(
        **split(n_val), entity_vocabs=dataset.entity_vocabs)
    re_datasets = {t: build_random_effect_dataset(
        dataset, t, "re", bucket_sizes=(8, 128)) for t in RE_TYPES}
    mf_datasets = {"mf": build_mf_dataset(dataset, "user", "item",
                                          bucket_sizes=(8, 128))}
    evaluation = EvaluationData(
        labels=validation.host_array("labels"),
        offsets=validation.host_array("offsets"),
        weights=validation.host_array("weights"))
    return dataset, re_datasets, mf_datasets, validation, evaluation


class EmptiedBeforeEverySweep(GameTrainProgram):
    """The parent's recursion: no sweep finds a carry, each scores every
    coordinate at its entry."""

    def step(self, data, buckets, state):
        return super().step(data, buckets, state.replace(scores={}))

    def step_scheduled(self, data, buckets, state, **kwargs):
        return super().step_scheduled(
            data, buckets, state.replace(scores={}), **kwargs)


def program_of(cls=GameTrainProgram, *, mesh=None, down_sampling=1.0,
               re_optimizer=OPT, update_order=None):
    return cls(
        TASK,
        FixedEffectStepSpec("global", OPT, l2_weight=0.5,
                            down_sampling_rate=down_sampling),
        tuple(RandomEffectStepSpec(t, "re", re_optimizer, l2_weight=1.0)
              for t in RE_TYPES),
        mf_specs=(MatrixFactorizationStepSpec(
            "mf", "user", "item", 2, OPT, l2_weight=1.0),),
        extra_fes=(FixedEffectStepSpec("side", OPT, l2_weight=0.5),),
        update_order=update_order, mesh=mesh,
    )


def mesh_of(devices):
    return devices and make_mesh(devices, 1, devices=jax.devices()[:devices])


def counters() -> dict:
    return dict(default_registry().snapshot()["counters"])


def fit(inputs, cls=GameTrainProgram, *, devices=None, sweeps=SWEEPS,
        validate=False, **kwargs):
    """(result, what the fit added to the registry's counters); ``kwargs``
    are the program's but for ``state`` / ``checkpointer``, the fit's."""
    dataset, re_datasets, mf_datasets, validation, evaluation = inputs
    mesh = mesh_of(devices)
    fit_kwargs = {k: kwargs.pop(k) for k in ("state", "checkpointer")
                  if k in kwargs}
    if validate:
        fit_kwargs.update(validation_dataset=validation,
                          validation_evaluators=[parse_evaluator("AUC")],
                          validation_eval_data=evaluation)
    before = counters()
    result = train_distributed(
        program_of(cls, mesh=mesh, **kwargs), dataset, re_datasets,
        mf_datasets=mf_datasets, mesh=mesh, num_iterations=sweeps,
        **fit_kwargs)
    after = counters()
    return result, {k: v - before.get(k, 0) for k, v in after.items()}


def leaves(state: GameTrainState) -> dict:
    """Every array of a returned state under its path, on the host."""
    flat = {"fe": state.fe_coefficients,
            **{f"extra_fe/{k}": v for k, v in state.extra_fe.items()},
            **{f"re/{k}": v for k, v in state.re_tables.items()},
            **{f"mf_rows/{k}": v for k, v in state.mf_rows.items()},
            **{f"mf_cols/{k}": v for k, v in state.mf_cols.items()}}
    return {k: np.asarray(v) for k, v in flat.items()}


def assert_the_same_model(result_a, result_b):
    """Two results equal bit for bit: every table, the factors, both fixed
    effects, and the losses."""
    state_a, state_b = leaves(result_a.state), leaves(result_b.state)
    assert sorted(state_a) == sorted(state_b) and len(state_a) == 6
    for name, value in state_a.items():
        assert value.tobytes() == state_b[name].tobytes(), name
    assert result_a.losses == result_b.losses and len(result_a.losses) == SWEEPS


def assert_the_same_fit(a, b):
    """Two (result, counters): the same model, and the same line-search
    counts."""
    (result_a, counts_a), (result_b, counts_b) = a, b
    assert_the_same_model(result_a, result_b)
    for name in SOLVER_COUNTERS:
        assert counts_a[name] == counts_b[name] > 0, name


@pytest.fixture(scope="module")
def inputs():
    return tiny_data()


# -- (a) equality ---------------------------------------------------------------


@pytest.fixture(scope="module")
def carried(inputs):
    """The carried fit on one device and on four, made once."""
    return {devices: fit(inputs, devices=devices) for devices in (None, 4)}


@pytest.mark.parametrize("devices", [None, 4], ids=["one-device", "four-devices"])
def test_a_carried_fit_is_the_fit_that_scores_at_every_entry(inputs, carried, devices):
    recomputed = fit(inputs, EmptiedBeforeEverySweep, devices=devices)
    assert_the_same_fit(carried[devices], recomputed)
    assert carried[devices][1][ENTRY_SCORINGS] == 1
    assert recomputed[1][ENTRY_SCORINGS] == SWEEPS


def test_with_down_sampling_the_margins_do_not_follow_the_multiplier(inputs, carried):
    """The fixed effect's weights change every sweep; a margin is ``X w``
    whatever weights its solve saw."""
    sampled = fit(inputs, down_sampling=0.6)
    assert_the_same_fit(
        sampled, fit(inputs, EmptiedBeforeEverySweep, down_sampling=0.6))
    assert sampled[0].losses != carried[None][0].losses  # it did sample


def test_four_devices_agree_with_one_as_the_agreement_tests_hold_them(carried):
    """Float32 sums in another order end a line search an evaluation earlier
    or later; measured once here (fe 2.6e-6, tables 1.1e-5 at most, losses
    1.2e-7): the limits are those of ``tests/test_x4_placement.py``."""
    (one, _), (four, _) = carried[None], carried[4]
    state_one, state_four = leaves(one.state), leaves(four.state)
    for name, value in state_one.items():
        if name.startswith("mf_"):
            continue  # bilinear: compare the scores, never the factors
        gap = np.linalg.norm(state_four[name] - value) / np.linalg.norm(value)
        assert gap < 1e-3, (name, gap)
    score = state_one["mf_rows/mf"] @ state_one["mf_cols/mf"].T
    score_four = state_four["mf_rows/mf"] @ state_four["mf_cols/mf"].T
    assert np.linalg.norm(score_four - score) / np.linalg.norm(score) < 1e-3
    np.testing.assert_allclose(four.losses, one.losses, rtol=1.3e-5)


def test_the_scheduled_sweep_takes_the_same_carry(inputs):
    """``step_scheduled`` opens with the entry program the fused step opens
    with, once a fit, and hands its margins on."""
    scheduled = dataclasses.replace(
        OPT, scheduler=LaneSchedulerConfig(probe_iterations=2))
    a = fit(inputs, re_optimizer=scheduled)
    b = fit(inputs, EmptiedBeforeEverySweep, re_optimizer=scheduled)
    assert a[1][ENTRY_SCORINGS] == 1 and b[1][ENTRY_SCORINGS] == SWEEPS
    assert a[1]["scheduler/lanes_probed"] > 0
    assert_the_same_model(a[0], b[0])


# -- (b) structure --------------------------------------------------------------


def placed(inputs, program):
    data, buckets = program.prepare_inputs(*inputs[:3])
    return data, buckets, program.init_state(*inputs[:3])


def scorings(record) -> dict:
    """Per coordinate, the instructions of a compiled step that ARE its
    scoring: the row gathers of a random effect's table and of both factor
    tables, the matrix-vector product of a fixed effect."""
    found = dict.fromkeys(CANONICAL, 0)
    for signature, op_name in record.instructions.values():
        scope = re.search(r"(?<![^/(])score/(\w+)(?![^/)])", op_name)
        opcode = re.search(r"[\w-]+$", signature).group()
        if scope and opcode == ("dot" if scope.group(1) in ("global", "side")
                                else "gather"):
            found[scope.group(1)] += 1
    return found


@pytest.fixture(scope="module")
def compiled_steps(inputs):
    """(the step ``step()`` compiled, the parent's: the same trace with the
    carry computed inside it), as the program's own record parses them."""
    program = program_of()
    data, buckets, state = placed(inputs, program)
    program.step(data, buckets, state)
    carried = compiled_scopes("train/step")
    assert carried is not None
    text = jax.jit(lambda d, b, s: program._step_impl(
        d, b, s.replace(scores=program._coordinate_scores(d, s)))
    ).lower(data, buckets, state).compile().as_text()
    return scorings(carried), scorings(scopes_of_text(text))


@pytest.mark.parametrize("coordinate", CANONICAL)
def test_the_compiled_step_scores_a_coordinate_once(compiled_steps, coordinate):
    """Once: after its solve. The parent's step holds every scoring twice
    but the first-updated coordinate's, whose entry margin nothing reads
    (its own offsets skip it) and the compiler removes."""
    carried, parent = compiled_steps
    once = {"global": 1, "side": 1, "user": 1, "item": 1, "mf": 2}[coordinate]
    assert carried[coordinate] == once
    assert parent[coordinate] == (once if coordinate == "global" else 2 * once)


@pytest.fixture
def ledger():
    led = install_ledger(ProgramLedger(registry=MetricsRegistry()))
    try:
        yield led
    finally:
        uninstall_ledger()


def test_a_fit_on_four_devices_compiles_the_step_and_the_scoring_once(inputs, ledger):
    """Sweep 2 finds its margins laid out as sweep 1 found the entry
    program's: one signature, one compile; ``score()`` sees no carry."""
    _, counts = fit(inputs, devices=4, validate=True)
    rows = ledger.snapshot()
    for label, calls in (("train/step", SWEEPS), ("train/score", SWEEPS),
                         ("train/entry_scores", 1)):
        assert rows[label]["calls"] == calls, label
        assert rows[label]["compiles"] == 1, (label, rows[label])
        assert rows[label]["recompiles"] == 0
    # (the ledger files sweep 2's tables under a second signature, as in the
    # parent: ``P("data")`` where sweep 1's were placed ``P("data", None)``,
    # one sharding under two spellings; the margins' reads the same in both)
    assert counts[ENTRY_SCORINGS] == 1


def test_scoring_adds_no_entry_scoring_and_leaves_the_carry_behind(inputs):
    program = program_of()
    data, buckets, state = placed(inputs, program)
    state, _ = program.step(data, buckets, state)
    assert tuple(sorted(state.scores)) == tuple(sorted(CANONICAL))
    before = counters()[ENTRY_SCORINGS]
    with_carry = np.asarray(program.score(data, state))
    without = np.asarray(program.score(data, state.replace(scores={})))
    assert counters()[ENTRY_SCORINGS] == before
    assert with_carry.tobytes() == without.tobytes()
    # and the carry IS the model's margins over these rows
    total = np.asarray(data["offsets"]) + sum(
        np.asarray(state.scores[name]) for name in CANONICAL)
    np.testing.assert_allclose(total, with_carry, rtol=1e-6, atol=1e-6)


def test_lowering_the_step_by_hand_gives_the_program_that_ran(inputs):
    """``program._step.lower`` (the benchmark's traced runs read the compiled
    step's text through it) fills an empty carry as a call does; what it
    does not override is the ``ledger_jit`` object's."""
    program = program_of()
    data, buckets, state = placed(inputs, program)
    program.step(data, buckets, state)
    text = program._step.lower(data, buckets, state).compile().as_text()
    assert scorings(scopes_of_text(text)) == scorings(compiled_scopes("train/step"))
    assert program._step.label == "train/step"
    assert callable(program._step.clear_cache)


def test_the_traced_step_refuses_an_empty_carry(inputs):
    """One form: ``_step_impl`` never scores at its entry; filling the carry
    is ``step()``'s, ahead of the dispatch."""
    program = program_of()
    data, buckets, state = placed(inputs, program)
    with pytest.raises(ValueError, match=r"state\.scores holds \[\]"):
        jax.eval_shape(program._step_impl, data, buckets, state)


def test_a_step_consumes_the_carry_it_is_handed_and_nothing_else(inputs):
    """The margins are donated to the sweep, whose own take their place (a
    fit holds one set of them); the tables and coefficients are not. The
    first-updated coordinate's margin no sweep reads (its own offsets skip
    it): ``jit`` drops the argument, and it goes with its state."""
    program = program_of()
    data, buckets, state = placed(inputs, program)
    first, _ = program.step(data, buckets, state)
    second, _ = program.step(data, buckets, first)
    assert {k for k, v in first.scores.items() if not v.is_deleted()} == {"global"}
    assert not any(v.is_deleted() for v in jax.tree_util.tree_leaves(
        (first.replace(scores={}), second, state)))
    # so the same sweep again, from the state with its carry set aside
    again, _ = program.step(data, buckets, first.replace(scores={}))
    for name, value in leaves(second).items():
        assert value.tobytes() == leaves(again)[name].tobytes(), name


# -- (c) the guards -------------------------------------------------------------


def test_a_carry_of_other_rows_is_refused_by_name(inputs):
    program = program_of()
    data, buckets, state = placed(inputs, program)
    state, _ = program.step(data, buckets, state)
    other = tiny_data(seed=49, n=240)
    other_data, other_buckets = program.prepare_inputs(*other[:3])
    with pytest.raises(ValueError, match=r"state\.scores\['\w+'\] has shape "
                                         r"\(328,\), the data has 240 rows"):
        program.step(other_data, other_buckets, state)
    # emptied, the state is stepped on the other rows like any warm start
    # (traced, not run: the refusal is the trace's)
    stepped, _, _ = jax.eval_shape(
        program._step_impl, other_data, other_buckets,
        program._carried(other_data, state.replace(scores={})))
    assert {v.shape for v in stepped.scores.values()} == {(240,)}


def test_a_carry_of_other_coordinates_is_refused_by_name(inputs):
    program = program_of()
    data, buckets, state = placed(inputs, program)
    state, _ = program.step(data, buckets, state)
    fewer = {k: v for k, v in state.scores.items() if k != "item"}
    with pytest.raises(ValueError, match=r"state\.scores holds .*coordinates are"):
        program.step(data, buckets, state.replace(scores=fewer))


@pytest.mark.parametrize("devices", [None, 4], ids=["one-device", "four-devices"])
def test_a_checkpoint_holds_no_carry_and_a_resumed_fit_is_the_whole_one(
        inputs, carried, devices, tmp_path):
    interrupted, _ = fit(inputs, devices=devices, sweeps=2,
                         checkpointer=TrainingCheckpointer(str(tmp_path)))
    assert len(interrupted.losses) == 2
    arrays = TrainingCheckpointer(str(tmp_path)).restore().arrays
    assert arrays and not [k for k in arrays if "scores" in k]
    resumed = fit(inputs, devices=devices,
                  checkpointer=TrainingCheckpointer(str(tmp_path)))
    assert resumed[1][ENTRY_SCORINGS] == 1 and resumed[1]["train/sweeps"] == 1
    assert_the_same_model(resumed[0], carried[devices][0])


def test_a_warm_started_fit_pays_one_entry_scoring(inputs, carried):
    first, _ = fit(inputs, sweeps=1)
    second, counts = fit(inputs, sweeps=2, state=first.state)
    assert counts[ENTRY_SCORINGS] == 1
    whole, _ = carried[None]
    assert len(second.losses) == 2
    for name, value in leaves(whole.state).items():
        assert value.tobytes() == leaves(second.state)[name].tobytes(), name
    assert first.losses + second.losses == whole.losses


def test_returned_states_hold_no_carry(inputs):
    result, _ = fit(inputs, validate=True)
    assert result.state.scores == {}
    # the validation AUC of noise labels is best before the last sweep
    assert result.best_state is not None and result.best_state.scores == {}
    assert any(value.tobytes() != leaves(result.best_state)[name].tobytes()
               for name, value in leaves(result.state).items())


def test_a_best_state_that_is_the_final_one_is_not_returned_twice(inputs):
    """Kept without its margins, the best state is no longer the final
    state's object; it is still told from it."""
    result, _ = fit(inputs, validate=True, sweeps=1)
    assert result.best_state is None and len(result.metric_history) == 1


def test_the_residual_sum_adds_in_canonical_order(inputs, monkeypatch):
    """Carried against recomputed under an update order that is neither the
    canonical nor the sorted one; and the order matters to the bits: the
    same fit with the carry left in the order ``jit`` returns it (sorted)
    parts from both."""
    order = ("user", "global", "mf", "item", "side")
    a = fit(inputs, update_order=order)
    assert_the_same_fit(a, fit(inputs, EmptiedBeforeEverySweep, update_order=order))

    def as_jit_returns_it(self, data, state):
        return dict(sorted(state.scores.items()))

    monkeypatch.setattr(GameTrainProgram, "_carried_scores", as_jit_returns_it)
    unordered, _ = fit(inputs, update_order=order)
    assert any(value.tobytes() != leaves(unordered.state)[name].tobytes()
               for name, value in leaves(a[0].state).items())
