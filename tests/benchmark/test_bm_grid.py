"""The cell ``logistic-epsilon-enet.grid``: its manifest entries, its files
against the accepted dense cell's, its driver, its six readers and what its
comparison catches. Whatever needs a device runs at a tiny size on the CPU;
the readers run on a small synthetic trace.

The manifest tests assert that the cell and its entries are IN the lists,
never where: the next cell appended turns nothing here red.
"""

import copy
import inspect
import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare_grid, grid_scopes, roofline_grid
from benchmark import manifest as M
from benchmark.manifest import (
    find_cell,
    layer_metric_reader,
    load_manifest,
    load_module,
    metrics_of,
)
from benchmark.spans import Spans
from photon_ml_tpu.telemetry import program_ledger

CELL = "logistic-epsilon-enet.grid"
OWN_METRICS = {
    "grid_lockstep_evals": ("evals", "lower", "program_counter", "solver"),
    "grid_lane_occupancy_pct": ("%", "higher", "program_counter", "solver"),
    "grid_eval_time_share_pct": ("%", "lower", "device_trace", "objective"),
    "grid_solver_time_share_pct": ("%", "lower", "device_trace", "solver"),
    "grid_score_time_share_pct": ("%", "lower", "device_trace", "scoring"),
    "grid_eval_roofline": ("%", "higher", "device_trace", "objective"),
}
JOINED = ("trace_lower_s", "program_load_s", "episode_s.fit", "device_idle_pct.fit",
          "peak_hbm_GiB.fit", "compiles_in_window.fit", "path_retrace_s")
#: the lists whose readers want the Pallas kernel: no lane reaches it
KERNEL_ONLY = ("path_solver_evals", "sweeps_kernel_time_share_pct.fit",
               "sweeps_glm_kernel_roofline.fit", "path_pad_time_share_pct")
HERE = os.path.join(M.ROOT, "benchmark")
TINY = dict(rows=4000, validation_rows=1000, features=64, latent_factors=4)
LANES = 12
#: limits for the tiny size on the CPU, set as the chip's are: above what the
#: float32 run reads here (one reading each: every seed poses the same fit;
#: largest over the twelve lanes): value at own coefficients 5.8e-7, the
#: pseudo-gradient norm 1.5e-5, validation margins 3.6e-7, value against the
#: reference's minimum 1.3e-6, coef_rel_l2 2.4e-3, val_auc 6.4e-5, non-zeros
#: one feature of 64.
TINY_LIMITS = {"loss_own_coef_rel_gap": 3e-6, "grad_norm_own_coef_gap": 1e-4,
               "val_margin_own_coef_max_gap": 3e-5, "loss_rel_gap": 1e-5,
               "coef_rel_l2": {"q1": 1e-2, "q2": 1e-2, "q3": 1e-2, "q4": 1e-2},
               "val_auc_gap": 3e-4, "nonzero_share_gap": 0.05}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


def tiny_cell() -> dict:
    """The cell cut to test size, its grid made by the configuration's own
    rule from the tiny rows' own gradient at zero."""
    from benchmark import datagen_dense

    found = find_cell(load_manifest(), CELL)
    config = found["config"] = copy.deepcopy(found["config"])
    config.update(TINY)
    data = datagen_dense.make_dense(config, 1, jax.devices()[0])
    g = np.asarray(data["x"], np.float64).T @ (0.5 - np.asarray(data["y"], np.float64))
    lam_max = 1.00001 * float(np.abs(g).max()) / config["elastic_net_alpha"]
    config["lambdas"] = [lam_max * 10 ** (-4 * k / (LANES - 1)) for k in range(LANES)]
    config["limits"] = copy.deepcopy(TINY_LIMITS)
    return found


# -- the manifest --------------------------------------------------------------


def test_the_manifest_holds_the_cell(manifest):
    assert M.check_manifest(manifest) == []
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "logistic-epsilon-enet", "traffic": "grid",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    config = {c["name"]: c for c in manifest["configs"]}["logistic-epsilon-enet"]
    assert config["reduced"] == []
    assert config["file"] == "benchmark/configs/logistic-epsilon-enet.json"
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in end_to_end["fit_s"]["workloads"]
    assert {m["name"] for m in metrics_of(manifest, "end_to_end", CELL, set())} == {
        "fit_s", "setup_s"}
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("name", sorted(OWN_METRICS))
def test_the_manifest_holds_the_cells_own_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    unit, better, source, layer = OWN_METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "fit_s", "workloads": [CELL]}
    assert os.path.isfile(M.reader_file(name))


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_dense_cells_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert CELL in entry["workloads"] and "logistic-epsilon.path" in entry["workloads"]


@pytest.mark.parametrize("name", KERNEL_ONLY)
def test_the_cell_joins_no_list_whose_reader_wants_the_kernel(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert CELL not in entry["workloads"]


def test_the_cell_reports_the_joined_metrics_and_six_more(manifest):
    names = {m["name"] for m in metrics_of(manifest, "per_layer", CELL, {"fit_s", "setup_s"})}
    assert names == set(JOINED) | set(OWN_METRICS)


# -- the files against the accepted cell's ---------------------------------------


@pytest.fixture(scope="module")
def configs():
    with open(os.path.join(HERE, "configs", "logistic-epsilon.json")) as f:
        accepted = json.load(f)
    with open(os.path.join(HERE, "configs", "logistic-epsilon-enet.json")) as f:
        return accepted, json.load(f)


def test_the_configuration_is_the_dense_cells_but_for_the_grid_and_the_solver(configs):
    accepted, ours = configs
    assert [k for k in ours if k in accepted] == list(accepted)
    assert set(ours) - set(accepted) == {"elastic_net_alpha", "lambda_max_rule"}
    differing = {key for key in accepted if ours[key] != accepted[key]}
    assert differing == {"name", "source", "deployment", "lambdas", "optimizer",
                         "assumed", "reference", "limits", "limit_readings"}
    assert ours["source"].startswith(accepted["source"] + " under an elastic-net lambda grid")
    assert ours["source_facts"] == accepted["source_facts"]
    assert ours["optimizer"] == {"type": "OWLQN", "max_iterations": 50,
                                 "rel_function_tolerance": 1e-06}
    assert ours["reduced"] == {} and ours["elastic_net_alpha"] == 0.5
    # the rows are the accepted cell's bit for bit: every key the generator reads
    for key in ("rows", "validation_rows", "features", "feature_dtype", "latent_factors",
                "factor_share", "true_margin_std", "data_seed", "task"):
        assert ours[key] == accepted[key]
    for said in ("values:", "labels:", "data_seed:"):
        assert [a for a in ours["assumed"] if a.startswith(said)] == [
            a for a in accepted["assumed"] if a.startswith(said)]
    assert {a.split(":")[0] for a in ours["assumed"]} == {
        "values", "labels", "objective", "lambdas", "lanes", "optimizer", "data_seed"}


def test_the_grid_is_a_hundred_literal_numbers_by_glmnets_rule(configs):
    _, ours = configs
    lambdas, rule = ours["lambdas"], ours["lambda_max_rule"]
    assert len(lambdas) == 100 and all(isinstance(lam, float) for lam in lambdas)
    assert lambdas == sorted(lambdas, reverse=True)
    # the largest is the smallest λ whose minimizer is zero, rounded UP
    exact = rule["max_abs_gradient_at_zero"] / ours["elastic_net_alpha"]
    assert rule["lambda_max_exact"] == pytest.approx(exact, rel=1e-12)
    assert lambdas[0] == rule["lambda_max"] and 0 < lambdas[0] / exact - 1 < 1e-5
    assert lambdas[-1] / lambdas[0] == pytest.approx(1e-4, rel=1e-6)
    np.testing.assert_allclose(np.diff(np.log10(lambdas)), -4 / 99, rtol=1e-5)
    made = eval(rule["made_by"].split(" = ", 1)[1], {"lambda_max": rule["lambda_max"]})  # noqa: S307
    assert made == lambdas


def test_every_limit_stands_beside_its_readings(configs):
    _, ours = configs
    assert set(ours["limits"]) == {
        "loss_own_coef_rel_gap", "grad_norm_own_coef_gap", "val_margin_own_coef_max_gap",
        "loss_rel_gap", "coef_rel_l2", "val_auc_gap", "nonzero_share_gap"}
    assert set(ours["limit_readings"]) == set(ours["limits"]) | {"_origin"}
    for kind, limit in ours["limits"].items():
        for value in (limit.values() if isinstance(limit, dict) else [limit]):
            assert 0 < value < 1
        if isinstance(limit, dict):
            assert tuple(limit) == compare_grid.QUARTERS


def test_the_traffic_is_the_dense_cells_episode_with_the_call_changed():
    with open(os.path.join(HERE, "traffic", "path.json")) as f:
        accepted = json.load(f)
    with open(os.path.join(HERE, "traffic", "grid.json")) as f:
        ours = json.load(f)
    assert ours.pop("kind") == "glm_grid" and accepted.pop("kind") == "glm_path"
    assert "estimators.train_glm_grid" in ours["episode"]
    assert "as vmapped solver lanes from zero" in ours["episode"]
    assert ours.pop("episode").split(";")[1:] == accepted.pop("episode").split(";")[1:]
    assert ours == accepted


def test_the_reference_imports_nothing_from_the_program():
    source = inspect.getsource(load_module(
        os.path.join(HERE, "references", "logistic-epsilon-enet.py")))
    assert "photon_ml_tpu" not in source and "owlqn" not in source.lower()


def test_the_references_evaluate_is_the_float64_elastic_net():
    reference = load_module(os.path.join(HERE, "references", "logistic-epsilon-enet.py"))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 6)).astype(np.float32)
    y = (rng.random(500) < 0.5).astype(np.float32)
    w = rng.normal(size=(2, 6))
    w[0, 2] = w[1, 4] = 0.0
    lambdas, alpha = [0.5, 3.0], 0.25
    got = reference.evaluate({"x": x, "y": y, "x_val": x[:7], "y_val": y[:7]}, w,
                             lambdas, alpha)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    for k, lam in enumerate(lambdas):
        m = x64 @ w[k]
        l1, l2 = alpha * lam, (1 - alpha) * lam
        value = (np.sum(np.logaddexp(0, m) - y64 * m) + l1 * np.abs(w[k]).sum()
                 + 0.5 * l2 * w[k] @ w[k])
        g = x64.T @ (1 / (1 + np.exp(-m)) - y64) + l2 * w[k]
        pseudo = np.where(w[k] != 0, g + l1 * np.sign(w[k]),
                          np.sign(g) * np.maximum(np.abs(g) - l1, 0))
        assert got["value"][k] == pytest.approx(value, rel=1e-12)
        assert got["grad_norm"][k] == pytest.approx(np.linalg.norm(pseudo), rel=1e-10)
        assert got["nonzeros"][k] == 5
        np.testing.assert_allclose(got["val_margin"][k], x64[:7] @ w[k], rtol=1e-12)


# -- the driver -----------------------------------------------------------------


@pytest.fixture(scope="module")
def sound_run():
    """One tiny episode, what it produced, and every number ``correct``
    compares."""
    found = tiny_cell()
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 3000000471,
                       jax.devices()[:1], Spans())
    cell.read_counters = True
    produced = cell.episode()
    counters = cell.counters()
    lane_line = cell.lane_lines(produced)
    data = {k: v.copy() for k, v in cell.host_data().items()}
    optimizer = cell.optimizer
    compared = cell.verify(reference, produced)
    exact = reference.fit(data, found["config"], jax.devices()[:1])
    return dict(found=found, driver=driver, reference=reference, produced=produced,
                exact=exact, counters=counters, lane_line=lane_line, data=data,
                compared=compared, optimizer=optimizer)


def test_the_driver_builds_the_configurations_optimizer_key_for_key(sound_run):
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

    assert sound_run["optimizer"] == OptimizerConfig(
        optimizer_type=OptimizerType.OWLQN, max_iterations=50,
        rel_function_tolerance=1e-6)
    assert sound_run["optimizer"].history == 10


def test_a_sound_tiny_run_is_correct(sound_run):
    from benchmark import compare

    names = [name for name, _, _ in sound_run["compared"]]
    kinds = ("loss_own_coef_rel_gap", "grad_norm_own_coef_gap",
             "val_margin_own_coef_max_gap", "loss_rel_gap", "coef_rel_l2",
             "val_auc_gap", "nonzero_share_gap")
    assert names == [f"{q}_{kind}" for kind in kinds for q in compare_grid.QUARTERS]
    assert compare.judge(sound_run["compared"])


def test_the_largest_lambdas_lane_stays_at_zero_and_the_reference_finds_it_so(sound_run):
    produced = sound_run["produced"]
    assert produced["lambdas"] == sorted(produced["lambdas"], reverse=True)
    assert not produced["coefficients"][0].any() and not sound_run["exact"][0].any()
    assert produced["iterations"][0] == 0 and produced["evaluations"][0] == 1
    assert produced["coefficients"][-1].all()  # the smallest λ keeps every feature
    nonzeros = np.count_nonzero(produced["coefficients"], axis=1)
    assert (np.diff(nonzeros) >= 0).all()


def test_the_episode_counts_what_the_device_ran_and_what_the_lanes_asked(sound_run):
    produced, counters = sound_run["produced"], sound_run["counters"]
    driver = sound_run["driver"]
    (_, lockstep, own), = counters["grid_evaluations"]
    assert lockstep == produced["lockstep_evaluations"]
    assert own == sum(produced["evaluations"])
    # a lane asks for no more than the block ran, and the slowest for all but
    # the trials other lanes' longer searches added
    assert max(produced["evaluations"]) <= lockstep < own
    assert counters["grid_operand"] == (4000, 64, 4, LANES)
    assert counters["retrace_s"] and len(counters["retrace_s"][0]) == 2
    trials = np.array([[0, 2, 1, 0], [0, 1, 3, 1], [0, 0, 0, 0]])
    assert driver.lockstep_evaluations(trials) == 1 + 2 + 3 + 1
    assert driver.own_evaluations(trials).tolist() == [4, 6, 1]
    line = sound_run["lane_line"]
    assert line.startswith(f"lanes: lock-step evaluations {lockstep} own {own} of {LANES} lanes")
    assert line.count("/GRADIENT_WITHIN_TOLERANCE/0") == 1  # the first lane
    assert "MAX_ITERATIONS" not in line


def test_a_program_without_the_lane_rules_is_refused_at_once(monkeypatch):
    from photon_ml_tpu.optim import common

    found = find_cell(load_manifest(), CELL)
    driver = load_module(found["driver"])
    monkeypatch.delattr(common, "at_line_search_floor")
    with pytest.raises(SystemExit, match="stopped-lane rule"):
        driver.Cell(found["config"], found["traffic"], 1, jax.devices()[:1], Spans())


def _judge_with(sound_run, produced):
    """The comparisons of the sound run with what was produced replaced."""
    found, reference, data = (sound_run[k] for k in ("found", "reference", "data"))
    limits, alpha = found["config"]["limits"], found["config"]["elastic_net_alpha"]
    lambdas, exact = found["config"]["lambdas"], sound_run["exact"]
    both = reference.evaluate(
        data, np.concatenate([produced["coefficients"], exact]),
        list(produced["lambdas"]) + list(lambdas), alpha)
    k = len(produced["coefficients"])
    own = {name: values[:k] for name, values in both.items()}
    expected = {"coefficients": exact, "lambdas": list(lambdas),
                **{name: values[k:] for name, values in both.items()}}
    return (compare_grid.own_coefficient_comparisons(produced, own, limits)
            + compare_grid.minimizer_comparisons(produced, expected, data["y_val"], limits))


def _failed(comparisons) -> set:
    return {name for name, value, limit in comparisons
            if not (np.isfinite(value) and value <= limit)}


def test_the_sound_run_judged_again_fails_nothing(sound_run):
    assert _failed(_judge_with(sound_run, sound_run["produced"])) == set()


def test_a_misreported_coefficient_is_caught_by_its_limit(sound_run):
    produced = copy.deepcopy(sound_run["produced"])
    lane = 7  # the third quarter of twelve lanes
    largest = np.argmax(np.abs(produced["coefficients"][lane]))
    produced["coefficients"][lane, largest] *= 1.2
    failed = _failed(_judge_with(sound_run, produced))
    assert "q3_coef_rel_l2" in failed
    assert not any(name.startswith(("q1_", "q2_", "q4_")) for name in failed)


def test_a_dropped_lane_is_caught(sound_run):
    produced = copy.deepcopy(sound_run["produced"])
    for key in ("coefficients", "val_margin"):
        produced[key] = produced[key][:-1]
    for key in ("lambdas", "l1_weights", "values", "gradient_norms", "iterations",
                "reasons", "floor_exits", "evaluations"):
        produced[key] = produced[key][:-1]
    assert "lanes_missing" in _failed(_judge_with(sound_run, produced))


def test_a_lane_fitted_at_another_lambda_is_caught_by_a_limit(sound_run):
    """Lane 5 handed its neighbour's fit (λ 2.3 times larger): its reported
    value is another objective's, its coefficients another minimizer's."""
    produced = copy.deepcopy(sound_run["produced"])
    for key in ("coefficients", "val_margin"):
        produced[key][5] = produced[key][4]
    for key in ("values", "gradient_norms"):
        produced[key][5] = produced[key][4]
    failed = _failed(_judge_with(sound_run, produced))
    assert {"q2_loss_own_coef_rel_gap", "q2_loss_rel_gap", "q2_coef_rel_l2"} <= failed
    assert not any(name.startswith(("q1_", "q3_", "q4_")) for name in failed)


def test_the_comparison_is_by_quarter_and_the_zero_lane_divides_nothing():
    assert [compare_grid.quarter_of(k, 100) for k in (0, 24, 25, 49, 50, 74, 75, 99)] == [
        "q1", "q1", "q2", "q2", "q3", "q3", "q4", "q4"]
    zero, some = np.zeros(4), np.array([1.0, 0.0, 0.0, 0.0])
    assert compare_grid.coef_gap(zero, zero) == 0.0
    assert compare_grid.coef_gap(some, zero) == compare_grid.coef_gap(zero, some) == 1.0
    rows = compare_grid.by_quarter("k", [0.1, 0.4, 0.2, 0.3, np.nan, 0.0, 0.0, 0.9],
                                   list(range(8)), {"k": {"q1": 1, "q2": 2, "q3": 3, "q4": 4}})
    assert [(n, limit) for n, _, limit in rows] == [
        ("q1_k", 1.0), ("q2_k", 2.0), ("q3_k", 3.0), ("q4_k", 4.0)]
    assert rows[0][1] == 0.4 and rows[1][1] == 0.3 and np.isnan(rows[2][1])
    assert rows[3][1] == 0.9


# -- the scopes of a traced tiny solve --------------------------------------------


def test_every_scope_of_a_tiny_grid_solve_falls_to_its_category(sound_run):
    """The program's own record of the tiny solve the fixture ran: the five
    scopes stand in its compiled text, each files under its category, and the
    forward product is told from its transpose by the wrapper alone."""
    record = program_ledger.compiled_scopes(grid_scopes.LABEL)
    assert record is not None
    hit = {}
    for name, (_, op_name) in record.instructions.items():
        hit.setdefault(grid_scopes.category(op_name), []).append(op_name)
    assert set(hit) == set(grid_scopes.CATEGORIES)
    assert all("transpose(" in op and "glm/margins" in op for op in hit["margins_t"])
    assert all("glm/margins" in op and "transpose(" not in op.split("glm/margins")[0]
               .rpartition("/")[2] for op in hit["margins"])
    # a trial's products stand inside the search loop (which of the two keeps
    # an instruction of its own is the backend's fusion)
    assert any("owlqn/line_search/while/body" in op
               for op in hit["margins"] + hit["margins_t"])
    assert all("owlqn/line_search" in op and "glm/margins" not in op.rpartition("/")[0]
               for op in hit["search"])
    assert all("owlqn/pseudo_gradient" in op for op in hit["pseudo_gradient"])
    assert all("lbfgs/" in op for op in hit["history"])
    for op in hit["other"]:
        assert not any(scope in op.rpartition("/")[0] for scope in grid_scopes.CATEGORY_OF)


@pytest.mark.parametrize("text,expected", [
    ("jit(f)/vmap()/while/body/owlqn/line_search/while/body/jvp(glm/margins)/dot_general",
     "margins"),
    ("jit(f)/vmap()/while/body/owlqn/line_search/while/body/transpose(jvp(glm/margins))/"
     "dot_general", "margins_t"),
    ("jit(f)/vmap(transpose(jvp(glm/margins)))/dot_general", "margins_t"),
    ("jit(f)/vmap(jvp(glm/margins))/dot_general", "margins"),
    ("jit(f)/vmap()/while/body/owlqn/line_search/while/body/jvp()/logistic", "search"),
    ("jit(f)/vmap()/while/body/owlqn/line_search/while", "search"),
    ("jit(f)/vmap()/while/body/owlqn/pseudo_gradient/select_n", "pseudo_gradient"),
    ("jit(f)/vmap()/while/body/lbfgs/direction/while/body/dot_general", "history"),
    ("jit(f)/vmap()/while/body/lbfgs/history/concatenate", "history"),
    ("jit(f)/vmap()/while/body/add", "other"),
    ("jit(f)/vmap()/while/body/bowlqn/line_search/add", "other"),
    ("jit(f)/glm/margins", "other"),  # the primitive's own name is taken off
    (None, "other"),
])
def test_an_op_names_category(text, expected):
    assert grid_scopes.category(text) == expected


# -- the readers, on a synthetic trace ---------------------------------------------

_IN_SEARCH = "jit(_jitted_grid_solve)/vmap()/while/body/owlqn/line_search/while/body/"
#: one grid solve of the program as its compiled text would record it
RECORD = ({
    "copy.9": ("f32[4000,64]copy", "batch.features"),
    "multiply_reduce_fusion.1": ("f32[4000]fusion",
                                 "jit(_jitted_grid_solve)/vmap(jvp(glm/margins))/dot_general"),
    "while.53": ("(f32[12,64],s32[12])while", "jit(_jitted_grid_solve)/vmap()/while"),
    "while.54": ("(f32[12,64],s32[12])while",
                 "jit(_jitted_grid_solve)/vmap()/while/body/owlqn/line_search/while"),
    "fusion.14": ("(f32[12],f32[12,4000])fusion", _IN_SEARCH + "jvp(glm/margins)/dot_general"),
    "fusion.21": ("f32[12,64]fusion", _IN_SEARCH + "transpose(jvp(glm/margins))/dot_general"),
    "fusion.6": ("f32[12,4000]fusion", _IN_SEARCH + "jvp()/logistic"),
    "fusion.7": ("f32[12,64]fusion",
                 "jit(_jitted_grid_solve)/vmap()/while/body/owlqn/pseudo_gradient/select_n"),
    "fusion.8": ("f32[12,64]fusion",
                 "jit(_jitted_grid_solve)/vmap()/while/body/lbfgs/direction/while/body/mul"),
    "fusion.9": ("f32[12]fusion", "jit(_jitted_grid_solve)/vmap()/while/body/sub"),
}, frozenset({"while.53"}))

MS = 1e6  # ns


def _event(name, signature_text, start_ms, dur_ms):
    return (f"%{name} = {signature_text}", start_ms * MS, dur_ms * MS)


def synthetic_trace(module="jit__jitted_grid_solve(7)"):
    """A window of 100 ms holding one solve of 80 ms: a relayout copy of X
    (10 ms), the first evaluation's product (4), the outer loop (60) whose
    search loop (40) holds two evaluations of 8 + 6 ms of products with 3 ms
    of row work each and a metadata-less copy inside the first product's
    span, then the pseudo-gradient (2), the recursion (3) and a stop test
    (1). Two scorings of 5 ms lie outside the solve."""
    x = "f32[4000,64]{1,0:T(8,128)}"
    ops = [
        _event("copy.9", f"{x} copy(f32[4000,64]{{0,1}} %p)", 0, 10),
        _event("multiply_reduce_fusion.1", f"f32[4000]{{0}} fusion({x} %c)", 10, 4),
        _event("while.53", "(f32[12,64]{1,0}, s32[12]{0}) while(%t)", 14, 60),
        _event("while.54", "(f32[12,64]{1,0}, s32[12]{0}) while(%t)", 16, 40),
        _event("fusion.14", f"(f32[12]{{0}}, f32[12,4000]{{1,0}}) fusion({x} %c)", 16, 8),
        _event("copy-done.3", "f32[4000]{0:S(1)} copy-done((f32[4000]{0}) %c)", 18, 1),
        _event("fusion.6", "f32[12,4000]{1,0} fusion(f32[12,4000]{1,0} %m)", 24, 3),
        _event("fusion.21", f"f32[12,64]{{1,0}} fusion({x} %c)", 27, 6),
        _event("fusion.14", f"(f32[12]{{0}}, f32[12,4000]{{1,0}}) fusion({x} %c)", 34, 8),
        _event("fusion.6", "f32[12,4000]{1,0} fusion(f32[12,4000]{1,0} %m)", 42, 3),
        _event("fusion.21", f"f32[12,64]{{1,0}} fusion({x} %c)", 45, 6),
        _event("fusion.7", "f32[12,64]{1,0} fusion(f32[12,64]{1,0} %a)", 58, 2),
        _event("fusion.8", "f32[12,64]{1,0} fusion(f32[12,64]{1,0} %a)", 61, 3),
        _event("fusion.9", "f32[12]{0} fusion(f32[12]{0} %a)", 70, 1),
        ("%fusion.1 = f32[1000]{0} fusion(f32[1000]{0} %s)", 85 * MS, 5 * MS),
        ("%fusion.1 = f32[1000]{0} fusion(f32[1000]{0} %s)", 92 * MS, 5 * MS),
    ]
    return {"devices": {0: {"ops": ops, "modules": [
        (module, 0.0, 80 * MS), ("jit_matmul(9)", 85 * MS, 5 * MS),
        ("jit_matmul(9)", 92 * MS, 5 * MS)]}},
        "host": [("bench:window", 0.0, 100 * MS)]}


def test_the_partition_files_every_busy_instant_of_a_solve():
    part = grid_scopes.partition(synthetic_trace(), RECORD,
                                 program_ledger.parse_instruction)
    seconds = {k: round(v * 1e3, 6) for k, v in part["seconds"].items()}
    # the copy inside a product's span has no metadata: it is the product's;
    # the search loop keeps what its body's events leave uncovered (6 ms), the
    # outer loop likewise (14 ms, with the stop test 15)
    assert seconds == {"margins": 20.0, "margins_t": 12.0, "search": 12.0,
                       "pseudo_gradient": 2.0, "history": 3.0, "other": 25.0}
    assert round(part["solve_s"] * 1e3, 6) == 74.0
    assert round(part["busy_s"] * 1e3, 6) == 84.0  # the solve's 74 and 10 outside it
    assert part["evaluation_events"] == 6
    assert round(part["by_instruction"]["margins", "fusion"] * 1e3, 6) == 15.0
    assert round(part["by_instruction"]["margins", "copy-done"] * 1e3, 6) == 1.0


@pytest.mark.parametrize("what", ["a program from before the scope",
                                  "no solve in the window", "a record of another program"])
def test_the_partition_is_nothing_without_the_scope(what):
    trace, record = synthetic_trace(), RECORD
    if what == "a program from before the scope":
        record = ({name: (sig, op.replace("glm/margins", ""))
                   for name, (sig, op) in RECORD[0].items()}, RECORD[1])
    elif what == "no solve in the window":
        trace = synthetic_trace(module="jit__jitted_path_solve(7)")
    else:
        record = ({**RECORD[0], "fusion.14": ("f32[9]fusion", RECORD[0]["fusion.14"][1])},
                  RECORD[1])
    assert grid_scopes.partition(trace, record, program_ledger.parse_instruction) is None


@pytest.fixture
def ctx(monkeypatch):
    from benchmark.trace_reduce import reduce_trace

    trace = synthetic_trace()
    part = grid_scopes.partition(trace, RECORD, program_ledger.parse_instruction)
    monkeypatch.setattr(grid_scopes, "of_this_run", lambda: part)
    return {"window_start": 100.0, "device": {"kind": "TPU v5 lite"},
            "trace": reduce_trace(trace),
            "counters": {"grid_evaluations": [(50.0, 99, 999), (100.5, 3, 20), (101.5, 5, 28)],
                         "grid_operand": (4000, 64, 4, 12)}}


def test_the_six_readers_give_the_known_values(ctx):
    assert layer_metric_reader("grid_lockstep_evals")(ctx) == 4  # median of 3 and 5
    assert layer_metric_reader("grid_lane_occupancy_pct")(ctx) == pytest.approx(
        100 * 48 / (12 * 8))
    assert layer_metric_reader("grid_eval_time_share_pct")(ctx) == pytest.approx(
        100 * 32 / 84)
    assert layer_metric_reader("grid_solver_time_share_pct")(ctx) == pytest.approx(
        100 * 17 / 84)
    assert layer_metric_reader("grid_score_time_share_pct")(ctx) == pytest.approx(
        100 * 10 / 84)
    # eight lock-step evaluations of one read of X each over the 32 ms under
    # the product's scopes; at this size the bytes set the least time
    bytes_one = 4000 * 64 * 4 + 2 * 4000 * 4 + 2 * 64 * 12 * 4
    assert roofline_grid.eval_bytes(4000, 64, 4, 12) == bytes_one
    assert layer_metric_reader("grid_eval_roofline")(ctx) == pytest.approx(
        100 * (8 * bytes_one / 819e9) / 0.032)


@pytest.mark.parametrize("name", sorted(OWN_METRICS))
def test_a_reader_on_a_program_without_the_scope(name, ctx, monkeypatch):
    """A program whose compiled text carries no ``glm/margins``: the two
    counts and the scorings' share read on, the three scope readers nothing,
    and none raises."""
    monkeypatch.setattr(grid_scopes, "of_this_run", lambda: None)
    value = layer_metric_reader(name)(ctx)
    by_scope = ("grid_eval_time_share_pct", "grid_solver_time_share_pct",
                "grid_eval_roofline")
    assert (value is None) == (name in by_scope)


@pytest.mark.parametrize("name", sorted(OWN_METRICS))
def test_a_reader_with_no_counters_returns_nothing(name, monkeypatch):
    """Another cell's traced run (every cell's traced runs use this PR's
    benchmark files): no count, no scope, nothing read and nothing raised."""
    from benchmark.trace_reduce import reduce_trace

    monkeypatch.setattr(grid_scopes, "of_this_run", lambda: None)
    assert layer_metric_reader(name)({
        "window_start": 0.0, "device": {"kind": "TPU v5 lite"}, "counters": {},
        "trace": reduce_trace(synthetic_trace())}) is None


def test_of_this_run_is_nothing_on_the_cpu():
    # no device plane was ever traced here: no xplane file, no partition
    assert grid_scopes.of_this_run() is None


def test_the_roofline_counts_one_read_of_x_whatever_implements_the_evaluation():
    # 400,000 x 2,000 float32 under 100 lanes: 3.2 GB, the labels and weights,
    # the coefficients in and the gradients out; 3.2e11 flops
    assert roofline_grid.eval_bytes(400_000, 2_000, 4, 100) == (
        3_200_000_000 + 3_200_000 + 1_600_000)
    assert roofline_grid.eval_flops(400_000, 2_000, 100) == 320_000_000_000
    at_peak = roofline_grid.eval_bytes(400_000, 2_000, 4, 100) / 819e9
    assert at_peak > roofline_grid.eval_flops(400_000, 2_000, 100) / 197e12
    assert roofline_grid.grid_eval_roofline_pct(
        10, 400_000, 2_000, 4, 100, 10 * at_peak, "TPU v5 lite") == pytest.approx(100.0)
    # an implementation that reads X twice at the peak shows as a half
    assert roofline_grid.grid_eval_roofline_pct(
        10, 400_000, 2_000, 4, 100, 20 * at_peak, "TPU v5 lite") == pytest.approx(50.0)
    # from 247 lanes on the two products' flops set the least time
    flops_bound = roofline_grid.eval_flops(400_000, 2_000, 512) / 197e12
    assert flops_bound > roofline_grid.eval_bytes(400_000, 2_000, 4, 512) / 819e9
    assert roofline_grid.grid_eval_roofline_pct(
        1, 400_000, 2_000, 4, 512, flops_bound, "TPU v5 lite") == pytest.approx(100.0)
    with pytest.raises(KeyError):
        roofline_grid.grid_eval_roofline_pct(1, 1, 1, 4, 1, 1.0, "an unknown chip")
