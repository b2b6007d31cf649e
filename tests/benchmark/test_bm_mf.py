"""The full-GAME cell ``game-ml20m-mf.sweeps`` at tiny size on the CPU: a whole
run through ``run_cell`` comes out correct against the new reference, the
bfloat16 control does not, a timed path that drops the coordinate or hands
back its starting factors does not, the generator changes ``make_glmix``'s
labels and nothing else, the three readers the cell brings read what they
say, and the manifest that holds it passes every test a manifest has to
pass."""

import copy
import os
import re

import jax
import numpy as np
import pytest

import benchmark.manifest
from benchmark import compare, datagen, datagen_mf, manifest as M, run
from benchmark.manifest import find_cell, layer_metric_reader, load_manifest, load_module
from benchmark.spans import Spans
from bm_helpers import MANIFEST_ASSERTIONS, TINY as GLMIX_TINY
from photon_ml_tpu.telemetry import registry as registry_module
from photon_ml_tpu.telemetry.registry import MetricsRegistry

WORKLOAD = "game-ml20m-mf.sweeps"
#: the one-chip GLMix cell's tiny size, letter for letter
TINY = GLMIX_TINY["glmix-ml20m.sweeps"]
#: limits for the tiny size on the CPU, set as the chip's are: about three
#: times what the float32 run gives here (one reading each: every seed poses
#: the same fit), below the bfloat16 control's where the control separates:
#: loss at own coefficients f32 3.2e-8, bf16 2.6e-5; validation margins at
#: own coefficients f32 4.7e-7, bf16 5.7e-3. Against the reference's exact
#: alternating minimization, where ten L-BFGS iterations a block stop: loss
#: 7.3e-4 / 1.16e-3 / 1.27e-3; val_auc 3.3e-4; fe_coef 5.1e-3; user_coef
#: 4.2e-2; item_coef 2.7e-2; norms 2.8e-3; the factorization's scores 8.7e-2
#: (validation rows), 8.5e-2 (training rows), their norm 7.6e-4 (a dropped
#: coordinate reads 1, starting factors handed back about 1).
TINY_LIMITS = {"loss_own_coef_rel_gap": 4e-7, "val_margin_own_coef_max_gap": 3e-5,
               "loss_rel_gap": 3.5e-3, "val_auc_gap": 1.1e-3, "fe_coef_rel_l2": 1.6e-2,
               "user_coef_rel_l2": 0.13, "item_coef_rel_l2": 0.09,
               "norm_rel_gap": 5e-3, "mf_val_score_rel_l2": 0.29,
               "mf_train_score_rel_l2": 0.29, "mf_score_norm_rel_gap": 0.02}


def tiny_mf(**overrides) -> dict:
    found = find_cell(load_manifest(), WORKLOAD)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY)
    found["config"]["limits"] = copy.deepcopy(TINY_LIMITS)
    found["config"].update(overrides)
    return found


def run_tiny(monkeypatch, break_it=None, seed=35, **overrides):
    """Everything of a run but the look for a chip (bm_helpers'
    ``run_with_the_timed_path_broken``, for this cell's tiny size)."""
    found = tiny_mf(**overrides)
    driver = load_module(found["driver"])
    if break_it is not None:
        sound_episode = driver.Cell.episode

        def broken(self):
            self.last = break_it(self, sound_episode(self))
            return self.last

        monkeypatch.setattr(driver.Cell, "episode", broken)
        monkeypatch.setattr(benchmark.manifest, "load_module", lambda path: (
            driver if path == found["driver"] else load_module(path)))
    return found, run.run_cell(found, load_manifest(), seed=seed, seconds=0.0,
                               trace=False, devices=jax.devices()[:1])


def test_the_cell_is_the_glmix_cell_with_the_coordinate():
    found = find_cell(load_manifest(), WORKLOAD)
    cfg, one = found["config"], find_cell(load_manifest(), "glmix-ml20m.sweeps")["config"]
    assert found["cell"]["chips"] == 1 and found["traffic"]["kind"] == "game_sweeps_mf"
    same = ("task", "rows", "validation_rows", "users", "items", "widths",
            "bucket_ladder", "coordinate_descent_iterations", "optimizer",
            "l2_weight", "feature_dtype", "mesh", "data_seed", "reduced")
    assert {k: cfg[k] for k in same} == {k: one[k] for k in same}
    assert cfg["assumed"][:len(one["assumed"])] == one["assumed"]
    mf = cfg["mf"]
    assert (mf["row"], mf["col"], mf["latent_factors"], mf["alternations"],
            mf["l2_weight"], mf["true_rank"]) == ("user", "item", 32, 1, 1.0, 32)
    # true factors' entries are drawn so that p . q has deviation 1
    assert mf["true_rank"] * mf["true_scale"] ** 4 == pytest.approx(1.0, rel=1e-4)
    for name in ("loop", "clients", "min_episodes", "traced_episodes"):
        assert found["traffic"][name] == find_cell(
            load_manifest(), "glmix-ml20m.sweeps")["traffic"][name]
    # the two numbers that hold the precision are not loosened
    for name in ("val_margin_own_coef_max_gap", "loss_own_coef_rel_gap"):
        assert cfg["limits"][name] <= one["limits"][name]
    # every limit stands beside its reading and what it is held against
    assert set(cfg["limit_readings"]) - {"_"} == set(cfg["limits"])


def test_a_whole_run_is_correct_and_counts_the_coordinate(monkeypatch):
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    found, line = run_tiny(monkeypatch, seed=3000000035)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == found["traffic"]["min_episodes"]
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert list(line["compared"])[:2] == [
        "loss_own_coef_rel_gap", "val_margin_own_coef_max_gap"]
    assert list(line["compared"])[-3:] == [
        "mf_val_score_rel_l2", "mf_train_score_rel_l2", "mf_score_norm_rel_gap"]
    assert len(line["compared"]) == 17
    # two sets of rows: the training rows compared are not the pairs the
    # validation split rates
    assert (line["compared"]["mf_val_score_rel_l2"]["value"]
            != line["compared"]["mf_train_score_rel_l2"]["value"])
    # the fused step ran the coordinate and counted its lanes apart from the
    # random effects'; the packer timed itself and left its padding
    snapshot = registry.snapshot()
    sweeps = snapshot["counters"]["train/sweeps"]
    assert sweeps == 3 * (1 + found["traffic"]["min_episodes"])
    assert layer_metric_reader("sweeps_mf_lockstep_trials")({}) == (
        snapshot["counters"]["solver/mf_lockstep_trials"] / sweeps) > 0
    assert layer_metric_reader("sweeps_re_lockstep_trials")({}) == (
        snapshot["counters"]["solver/lockstep_trials"] / sweeps) > 0
    assert layer_metric_reader("pack_mf_s")({}) == snapshot["histograms"][
        "timing/pack/mf_side_buckets"]["total"] > 0
    for side in ("row", "col"):
        assert 0 < snapshot["gauges"][f"mf/user_x_item/{side}_pad_fraction"] < 1


def test_the_training_rows_compared_are_not_the_validation_splits_pairs():
    """At the cell's own size the validation split rates the pairs of 500,000
    evenly spaced training rows: the training rows compared lie between
    them (they were the same rows once, and the two numbers the same)."""
    cfg = find_cell(load_manifest(), WORKLOAD)["config"]
    n, n_val = int(cfg["rows"]), int(cfg["validation_rows"])
    rows = load_module(find_cell(load_manifest(), WORKLOAD)["driver"]).scored_train_rows(n)
    pick = (np.arange(n_val, dtype=np.int64) * n) // n_val
    assert len(rows) == 500_000 == len(np.unique(rows)) and rows.max() < n
    assert not np.intersect1d(rows, pick).size


def test_every_seed_poses_the_same_fit():
    """``--seed`` names the entities and orders the validation rows: the
    losses of a fit are the same numbers for every seed (the starting
    factors are laid out by size rank, not by id)."""
    losses, starts = [], []
    for seed in (21, 2147483999):
        found = tiny_mf()
        cell = load_module(found["driver"]).Cell(
            found["config"], found["traffic"], seed, jax.devices()[:1], Spans())
        losses.append(cell.episode()["losses"])
        # the fit starts from the GENERATOR's factors, not from any the
        # program drew: by size rank they are the same table for every seed
        by_rank = datagen_mf.start_factors(found["config"])[0]
        start = np.asarray(cell.start_state.mf_rows["user_x_item"])
        assert np.array_equal(start, by_rank[cell.data["user_rank"]])
        assert start.std() == pytest.approx(32 ** -0.5, rel=0.02)
        starts.append(start)
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    assert not np.array_equal(*starts)  # by id they are not


def test_the_control_comes_out_not_correct():
    """The program's own bfloat16 feature path fails the validation margins
    at its own coefficients, factorization term included, by over a hundred
    times the limit."""
    found = tiny_mf(feature_dtype="bfloat16")
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 21, jax.devices()[:1], Spans())
    compared = cell.verify(reference, cell.episode())
    assert not compare.judge(compared), compared
    by_name = {n: (v, lim) for n, v, lim in compared}
    value, limit = by_name["val_margin_own_coef_max_gap"]
    assert value > 100 * limit, (value, limit)


def _drop_the_coordinate(cell, produced):
    return {**produced, "mf_user": np.zeros_like(produced["mf_user"]),
            "mf_item": np.zeros_like(produced["mf_item"])}


def _hand_back_the_starting_factors(cell, produced):
    return {**produced, **cell.start_factors}


@pytest.mark.parametrize("break_it", [_drop_the_coordinate,
                                      _hand_back_the_starting_factors])
def test_a_run_without_the_coordinates_work_is_not_correct(break_it, monkeypatch):
    _, line = run_tiny(monkeypatch, break_it)
    assert line["correct"] is False
    compared = line["compared"]
    # by the scores (never the factors), each far over its limit
    for name in ("mf_val_score_rel_l2", "mf_train_score_rel_l2"):
        assert compared[name]["value"] > 0.8 > compared[name]["limit"], compared[name]
    # and by the margins at the state the run reports: the scoring program
    # added the factorization's term, the altered state does not hold it
    assert compared["val_margin_own_coef_max_gap"]["value"] > compared[
        "val_margin_own_coef_max_gap"]["limit"]


def _start_from_zero_factors(cell, produced):
    # from the warm-up on, every episode starts at zero factors, a stationary
    # point of the bilinear objective; the reference is handed the generator's
    state = cell.start_state
    cell.start_state = state.replace(
        mf_rows={k: 0 * v for k, v in state.mf_rows.items()},
        mf_cols={k: 0 * v for k, v in state.mf_cols.items()})
    return produced


def test_a_fit_from_other_starting_factors_is_not_correct(monkeypatch):
    _, line = run_tiny(monkeypatch, _start_from_zero_factors)
    assert line["correct"] is False
    for name in ("mf_val_score_rel_l2", "mf_train_score_rel_l2"):
        assert line["compared"][name]["value"] == pytest.approx(1.0)  # scores of 0


def test_the_generator_changes_the_labels_and_nothing_else():
    cfg = tiny_mf()["config"]
    seed = 3000000077
    glmix, game = datagen.make_glmix(cfg, seed), datagen_mf.make_game(cfg, seed)
    for split in ("train", "validation"):
        for name, array in glmix[split].items():
            if name != "y":
                assert array.dtype == game[split][name].dtype
                assert np.array_equal(array, game[split][name]), (split, name)
        flipped = np.mean(glmix[split]["y"] != game[split]["y"])
        assert 0.05 < flipped < 0.5, flipped
        assert set(np.unique(game[split]["y"])) == {0.0, 1.0}
    assert np.array_equal(glmix["user_sizes"], game["user_sizes"])
    # id -> size rank: the entity of rank 0 is the largest one
    for side in ("user", "item"):
        counts = np.bincount(game["train"][side], minlength=len(game[side + "_rank"]))
        assert np.array_equal(counts, game[side + "_sizes"][game[side + "_rank"]])


def test_the_generators_truth_is_make_glmixs_and_a_seed_moves_no_label():
    """The GLMix margin is computed anew from ``make_glmix``'s truth stream:
    read in the wrong order it would not predict ``make_glmix``'s own labels.
    Labels belong to canonical rows, so another seed gives the training rows
    the same labels and the validation rows the same labels in another
    order."""
    cfg = tiny_mf()["config"]
    cfg["mf"] = {**cfg["mf"], "true_scale": 0.0}  # no interaction: GLMix's margin
    glmix, game = datagen.make_glmix(cfg, 7), datagen_mf.make_game(cfg, 7)
    train = glmix["train"]
    w_g, w_u, w_i = datagen_mf._glmix_truth(cfg)
    margin = (train["x_global"] @ w_g
              + np.einsum("rd,rd->r", train["x_user"], w_u[game["user_rank"][train["user"]]])
              + np.einsum("rd,rd->r", train["x_item"], w_i[game["item_rank"][train["item"]]]))
    assert compare.auc(margin, train["y"]) > 0.8  # a wrong truth reads 0.5 to 0.6
    assert compare.auc(margin, game["train"]["y"]) > 0.8
    other = datagen_mf.make_game(tiny_mf()["config"], 8)
    again = datagen_mf.make_game(tiny_mf()["config"], 9)
    assert np.array_equal(other["train"]["y"], again["train"]["y"])
    assert not np.array_equal(other["validation"]["y"], again["validation"]["y"])
    assert other["validation"]["y"].sum() == again["validation"]["y"].sum()


def test_the_reference_is_independent_and_its_newton_steps_have_converged():
    found = tiny_mf()
    with open(found["reference"]) as f:
        assert "photon_ml_tpu" not in f.read()
    reference = load_module(found["reference"])
    cfg = found["config"]
    data = datagen_mf.make_game(cfg, 5)
    n = int(cfg["rows"])
    kept = {k: np.ones(n, bool) for k in ("user", "item", "mf_user", "mf_item")}
    rng = np.random.default_rng(0)
    start = {"mf_" + side: (rng.normal(size=(int(cfg[side + "s"]["count"]), 32))
                            / np.sqrt(32)).astype(np.float32)
             for side in ("user", "item")}
    fits = []
    for steps in (reference.NEWTON_STEPS, 2 * reference.NEWTON_STEPS):
        reference.NEWTON_STEPS = steps
        fits.append(reference.fit(data, cfg, kept, jax.devices()[:1], start))
    scores = [reference.mf_scores(data["validation"], fit) for fit in fits]
    assert compare.rel_l2(*scores) < 1e-5
    assert fits[0]["losses"] == pytest.approx(fits[1]["losses"], rel=1e-6)
    # the coordinate does work: the scores moved far from where they started
    assert compare.rel_l2(reference.mf_scores(data["validation"], start), scores[1]) > 0.5


# -- the three readers the cell brings ----------------------------------------


@pytest.fixture
def registry(monkeypatch):
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    return registry


def test_mf_lockstep_trials_are_read_per_sweep_beside_the_random_effects(registry):
    registry.counter("train/sweeps").inc(12)
    registry.counter("solver/lockstep_trials").inc(2052)
    registry.counter("solver/mf_lockstep_trials").inc(3000)
    assert layer_metric_reader("sweeps_mf_lockstep_trials")({}) == 250.0
    assert layer_metric_reader("sweeps_re_lockstep_trials")({}) == 171.0


@pytest.mark.parametrize("name", ["sweeps_mf_lockstep_trials", "pack_mf_s"])
def test_a_program_without_the_counters_gives_nothing(registry, name):
    """The parent counts its sweeps and its random effects' trials, no
    factorization trials, and does not time the factorization's packer."""
    registry.counter("train/sweeps").inc(12)
    registry.counter("solver/lockstep_trials").inc(2052)
    assert layer_metric_reader(name)({}) is None


def test_pack_mf_s_is_the_packers_histogram(registry):
    registry.histogram("timing/pack/mf_side_buckets").observe(2.5)
    registry.histogram("timing/pack/mf_side_buckets").observe(0.5)
    assert layer_metric_reader("pack_mf_s")({}) == 3.0


def _trace():
    """``trace_reduce.load_xplane``'s lists: one device, a window of 10 s, 6 s
    of instructions, 3 s of them inside a ``while`` of the coordinate's (one
    a compiler-made copy with no metadata of its own); another program
    reuses an instruction's name."""
    text = "%{} = f32[8,32]{{1,0}} op(f32[8,32] %p)".format
    return {"host": [("bench:window", 1e9, 10e9)], "devices": {0: {
        "modules": [("jit__step_impl(77)", 1e9, 6e9), ("jit__score_impl(5)", 8e9, 1e9)],
        "ops": [(text("fusion.1"), 1e9, 2e9), (text("while.7"), 3e9, 3e9),
                (text("fusion.2"), 3e9, 1e9), (text("copy.3"), 4e9, 2e9),
                (text("while.7"), 8e9, 1e9),  # the score program's
                (text("fusion.2"), 20e9, 5e9)],  # after the window
    }}}


def test_mf_time_share_reads_the_scoped_instructions_of_the_step():
    read = layer_metric_reader("mf_time_share_pct")
    scoped = {"while.7": "f32[8,32]op", "fusion.2": "f32[8,32]op"}
    counters = {"mf_scoped_instructions": scoped, "mf_scoped_loops": frozenset({"while.7"})}
    assert read({"mf_trace": _trace(), "counters": counters}) == pytest.approx(
        100 * 3 / 6)  # the while spans its body
    # the step holds no scoped instruction (a parent, a GLMix cell): nothing
    assert read({"mf_trace": _trace(), "counters": {}}) is None
    assert read({"mf_trace": _trace(), "counters": {"mf_scoped_instructions": {}}}) is None
    assert read({"mf_trace": _trace(), "counters": {
        "mf_scoped_instructions": {"fusion.99": "f32[8,32]op"}}}) is None


def test_mf_time_share_holds_a_name_cut_short_to_the_prefix_it_kept():
    read = layer_metric_reader("mf_time_share_pct")
    trace = _trace()
    ops = trace["devices"][0]["ops"]
    ops[1] = ("%while.7 = f32[8,3", *ops[1][1:])  # the step's; cut inside its shape
    counters = {"mf_scoped_instructions": {"while.7": "f32[8,32]op", "fusion.2": "f32[8,32]op"},
                "mf_scoped_loops": frozenset({"while.7"})}
    assert read({"mf_trace": trace, "counters": counters}) == pytest.approx(100 * 3 / 6)
    counters["mf_scoped_instructions"]["while.7"] = "f32[8,64]op"  # not its prefix
    assert read({"mf_trace": trace, "counters": counters}) is None


@pytest.mark.parametrize("counters", [
    # a name of the step that is another instruction there: the text read was
    # not the program's that ran
    {"mf_scoped_instructions": {"while.7": "(s32[],f32[8,32])while",
                                "fusion.2": "f32[8,32]op"}},
    # one of the step's own loops never ran in the window
    {"mf_scoped_instructions": {"while.7": "f32[8,32]op", "while.8": "f32[8,32]op"},
     "mf_scoped_loops": frozenset({"while.7", "while.8"})},
], ids=["another_signature", "a_loop_without_an_event"])
def test_mf_time_share_gives_nothing_for_another_programs_names(counters):
    read = layer_metric_reader("mf_time_share_pct")
    assert read({"mf_trace": _trace(), "counters": counters}) is None


def test_the_scope_is_read_from_the_compiled_steps_text():
    reader = load_module(M.reader_file("mf_time_share_pct"))
    text = """HloModule jit__step_impl

%body.1 (p: (s32[])) -> (s32[]) {
  %while.12 = (s32[], f32[8,32]{1,0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(_step_impl)/jit(main)/mf/user_x_item/col/while/body/while"}
}

ENTRY %main.5 (p: f32[8]) -> f32[8] {
  %fusion.25 = f32[416256,32]{1,0:T(8,128)S(1)} fusion(%p), kind=kLoop, metadata={op_name="jit(_step_impl)/jit(main)/mf/user_x_item/row/gather" source_file="a.py"}
  ROOT %while.9 = (s32[], /*index=1*/f32[8,32]{1,0}) while(%t), condition=%c, body=%body.1, metadata={op_name="jit(_step_impl)/jit(main)/mf/user_x_item/col/while"}
  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(_step_impl)/jit(main)/while/body/dot_general"}
  %copy.1 = f32[8]{0} copy(%x)
  %add.4 = f32[] add(%a, %b), metadata={op_name="jit(_step_impl)/ramf/user/rowing"}
}
"""
    scoped, loops = reader.scoped_instructions(text)
    assert scoped == {"fusion.25": "f32[416256,32]fusion",
                      "while.9": "(s32[],f32[8,32])while",
                      "while.12": "(s32[],f32[8,32])while"}
    assert loops == {"while.9"}
    assert reader.scoped_instructions("") == ({}, frozenset())
    # a device event's name prints the operands' shapes too: the same signature
    event = ("%while.9 = (s32[]{:T(128)}, f32[8,32]{1,0:T(8,128)}) while((s32[], f32[8,32]) "
             "%tuple.1), condition=%c, body=%body.1")
    assert reader.signature(event) == ("while.9", "(s32[],f32[8,32])while")
    # the profiler cuts a long name short, anywhere: what is left is a prefix
    for cut in range(len("%while.9 = "), len(event)):
        name, kept, whole = reader._parse(event[:cut])
        assert name == "while.9" and scoped["while.9"].startswith(kept), (cut, kept)
        assert whole == (cut > event.index("while((")  + len("while"))


def test_the_driver_hands_over_the_compiled_steps_scoped_instructions():
    """On traced runs ``counters`` lowers the step the episode ran and reads
    the scope from the compiled text: both half-steps are there, the random
    effects' and the fixed effect's instructions are not, and the step's own
    loops of the coordinate are named."""
    found = tiny_mf(rows=4000, validation_rows=400,
                    users=dict(count=40, min=20, max=9254, a=1.0),
                    items=dict(count=30, min=1, max=16828, a=1.8))
    cell = load_module(found["driver"]).Cell(
        found["config"], found["traffic"], 3, jax.devices()[:1], Spans())
    assert cell.counters() == {}  # untraced runs pay nothing
    cell.read_counters = True
    counters = cell.counters()
    scoped, loops = counters["mf_scoped_instructions"], counters["mf_scoped_loops"]
    data, buckets = cell.program.prepare_inputs(
        cell.dataset, cell.re_datasets, cell.mf_datasets)
    text = cell.program._step.lower(data, buckets, cell.start_state).compile().as_text()
    assert scoped and all("%" + name + " = " in text for name in scoped)
    sides = {side for side in ("row", "col")
             if f"mf/user_x_item/{side}" in text}
    assert sides == {"row", "col"}
    whiles = [name for name, sig in scoped.items() if sig.endswith(")while")]
    every_while = re.findall(r"%(while[\w.]*) = ", text)
    assert whiles and len(whiles) < len(set(every_while))
    # one loop a bucket of each side, each among the scoped whiles
    packed = cell.mf_datasets["user_x_item"]
    assert len(loops) == len(packed.row_buckets) + len(packed.col_buckets)
    assert loops <= set(whiles)


# -- the manifest ---------------------------------------------------------------


@pytest.mark.parametrize("check", MANIFEST_ASSERTIONS, ids=lambda c: c.__name__)
def test_the_manifest_with_the_cell_passes_every_manifest_test(check):
    check(load_manifest(), M.ROOT)


def test_the_manifest_holds_the_cell():
    """The cell is the newest, so it stands where the benchmark takes an
    addition: after every accepted name of each list it is in."""
    manifest = load_manifest()
    assert M.check_manifest(manifest) == []
    assert [w["name"] for w in manifest["workloads"]][-1] == WORKLOAD
    for section in ("end_to_end", "per_layer"):
        for entry in manifest[section]:
            if WORKLOAD in entry.get("workloads", ()):
                assert entry["workloads"][-1] == WORKLOAD
    assert "game-ml20m-mf" in [c["name"] for c in manifest["configs"]]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    reported = {m["name"] for m in M.metrics_of(manifest, "end_to_end", WORKLOAD, set())}
    assert reported == {"train_rows_per_s", "setup_s"}
    layers = {m["name"] for m in M.metrics_of(manifest, "per_layer", WORKLOAD, reported)}
    own = {"sweeps_mf_lockstep_trials", "mf_time_share_pct", "pack_mf_s"}
    # every per-layer metric the one-chip GLMix cell reports is reported here,
    # and the cell's own three
    one = {m["name"] for m in M.metrics_of(
        manifest, "per_layer", "glmix-ml20m.sweeps", reported)}
    assert layers == one | own and len(one) == 16
    # the GLMix cells read none of the three
    for name in own:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [WORKLOAD]
        assert os.path.isfile(M.reader_file(name))
