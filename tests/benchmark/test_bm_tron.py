"""The cell ``logistic-epsilon-tron.path``: its manifest entries, its files
against the accepted dense cell's, its driver, its three readers and what its
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

from benchmark import manifest as M
from benchmark import path_scopes, roofline_hv
from benchmark.manifest import (
    find_cell,
    layer_metric_reader,
    load_manifest,
    load_module,
    metrics_of,
)
from benchmark.spans import Spans
from photon_ml_tpu.telemetry import program_ledger

CELL = "logistic-epsilon-tron.path"
OWN_METRICS = ("path_hv_products", "path_hv_time_share_pct", "path_hv_roofline")
JOINED = ("trace_lower_s", "program_load_s", "episode_s.fit", "path_solver_evals",
          "sweeps_kernel_time_share_pct.fit", "sweeps_glm_kernel_roofline.fit",
          "path_pad_time_share_pct", "device_idle_pct.fit", "peak_hbm_GiB.fit",
          "compiles_in_window.fit", "path_retrace_s")
HERE = os.path.join(M.ROOT, "benchmark")
TINY = dict(rows=4000, validation_rows=1000, features=64, latent_factors=4)
#: limits for the tiny size on the CPU, set as the chip's are: above what the
#: float32 run reads here (one reading each: every seed poses the same fit;
#: largest over the four λ): value at own coefficients 6.5e-8, validation
#: margins 3.0e-7, value against the reference's minimum 6.5e-8, coef_rel_l2
#: 3.0e-5, val_auc 1.6e-5, the product 8.4e-7. The solve's gradient norm at
#: own coefficients reads 1.3e-2: TRON ends where the float32 gradient is
#: rounding, so at this size the number holds a misreported norm and no more.
TINY_LIMITS = {"loss_own_coef_rel_gap": 1e-6, "grad_norm_own_coef_rel_gap": 0.1,
               "val_margin_own_coef_max_gap": 3e-5, "coef_rel_l2": 2e-4,
               "loss_rel_gap": 2e-6, "val_auc_gap": 1e-4,
               "hv_own_coef_rel_gap": 1e-5}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


def tiny_cell() -> dict:
    found = find_cell(load_manifest(), CELL)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY)
    found["config"]["limits"] = dict(TINY_LIMITS)
    return found


# -- the manifest --------------------------------------------------------------


def test_the_manifest_holds_the_cell(manifest):
    assert M.check_manifest(manifest) == []
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "logistic-epsilon-tron", "traffic": "path-tron",
        "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    config = {c["name"]: c for c in manifest["configs"]}["logistic-epsilon-tron"]
    assert config["reduced"] == []
    assert config["file"] == "benchmark/configs/logistic-epsilon-tron.json"
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in end_to_end["fit_s"]["workloads"]
    assert {m["name"] for m in metrics_of(manifest, "end_to_end", CELL, set())} == {
        "fit_s", "setup_s"}


@pytest.mark.parametrize("name", OWN_METRICS)
def test_the_manifest_holds_the_cells_own_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "fit_s" and entry["layer"] == "solver"
    assert entry["source"] == ("program_counter" if name == "path_hv_products"
                               else "device_trace")
    assert os.path.isfile(M.reader_file(name))


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_dense_cells_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert CELL in entry["workloads"] and "logistic-epsilon.path" in entry["workloads"]


def test_the_cell_reports_the_dense_cells_metrics_and_three_more(manifest):
    def names(cell):
        return {m["name"] for m in metrics_of(manifest, "per_layer", cell, {"fit_s", "setup_s"})}

    assert names(CELL) == names("logistic-epsilon.path") | set(OWN_METRICS)


# -- the files against the accepted cell's ---------------------------------------


def test_the_configuration_is_the_dense_cells_but_for_the_solver():
    with open(os.path.join(HERE, "configs", "logistic-epsilon.json")) as f:
        accepted = json.load(f)
    with open(os.path.join(HERE, "configs", "logistic-epsilon-tron.json")) as f:
        ours = json.load(f)
    assert list(ours) == list(accepted)
    differing = {key for key in accepted if ours[key] != accepted[key]}
    # ``source``: the manifest's test holds a file and its entry to ONE string,
    # and two deployments of one data set name sources that differ (the solver's)
    assert differing == {"name", "source", "optimizer", "assumed", "reference",
                         "limits", "limit_readings"}
    assert ours["source"].startswith(accepted["source"] + " under LIBLINEAR -s 0")
    assert ours["source_facts"] == accepted["source_facts"]
    assert ours["optimizer"] == {"type": "TRON", "max_iterations": 15,
                                 "tolerance": 1e-05, "max_cg_iterations": 20}
    assert ours["reduced"] == {}
    # the rows are the accepted cell's bit for bit: every key the generator reads
    assert [a for a in ours["assumed"] if not a.startswith("optimizer:")] == [
        a for a in accepted["assumed"] if not a.startswith("optimizer:")]
    assert set(ours["limits"]) == set(accepted["limits"]) | {"hv_own_coef_rel_gap"}


def test_the_traffic_is_the_dense_cells_episode_word_for_word():
    with open(os.path.join(HERE, "traffic", "path.json")) as f:
        accepted = json.load(f)
    with open(os.path.join(HERE, "traffic", "path-tron.json")) as f:
        ours = json.load(f)
    assert ours.pop("kind") == "glm_path_tron" and accepted.pop("kind") == "glm_path"
    assert ours == accepted


def test_the_reference_is_the_dense_cells_copy_plus_the_product():
    accepted = load_module(os.path.join(HERE, "references", "logistic-epsilon.py"))
    ours = load_module(os.path.join(HERE, "references", "logistic-epsilon-tron.py"))
    for name in ("fit", "evaluate"):
        assert inspect.getsource(getattr(ours, name)) == inspect.getsource(
            getattr(accepted, name))
    assert not hasattr(accepted, "hessian_vector")


def test_the_references_product_is_the_float64_hessians():
    reference = load_module(os.path.join(HERE, "references", "logistic-epsilon-tron.py"))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 6)).astype(np.float32)
    w, v = rng.normal(size=(2, 6)), rng.normal(size=(2, 6))
    got = reference.hessian_vector({"x": x}, w, v, [0.5, 3.0])
    assert got.dtype == np.float64 and got.shape == (2, 6)
    x64 = x.astype(np.float64)
    for k, lam in enumerate((0.5, 3.0)):
        p = 1.0 / (1.0 + np.exp(-x64 @ w[k]))
        hessian = x64.T @ (x64 * (p * (1 - p))[:, None]) + lam * np.eye(6)
        np.testing.assert_allclose(got[k], hessian @ v[k], rtol=1e-12)


# -- the driver -----------------------------------------------------------------


def test_the_driver_builds_the_configurations_optimizer_key_for_key():
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

    found = find_cell(load_manifest(), CELL)
    driver = load_module(found["driver"])
    built = driver.optimizer_config(found["config"]["optimizer"])
    assert built == OptimizerConfig(
        optimizer_type=OptimizerType.TRON, max_iterations=15, tolerance=1e-5,
        max_cg_iterations=20)
    assert built.rel_function_tolerance is None
    with pytest.raises(TypeError):  # a key the program has no field for
        driver.optimizer_config({"type": "TRON", "cg_cap": 20})


def test_rejected_rounds_are_the_ones_whose_value_and_gradient_both_repeat():
    driver = load_module(os.path.join(HERE, "drivers", "glm_path_tron.py"))
    values = np.array([9.0, 9.0, 5.0, 5.0, 5.0, np.nan])
    grads = np.array([3.0, 3.0, 2.0, 1.5, 1.5, np.nan])
    # round 1 rejected; round 3 kept (float32 left the value, the gradient
    # moved); round 4 rejected
    assert driver.rejected_rounds(values, grads, 4) == 2
    assert driver.rejected_rounds(values, grads, 3) == 1


@pytest.fixture(scope="module")
def sound_run():
    """One tiny episode, what it produced, the products after it and every
    number ``correct`` compares."""
    found = tiny_cell()
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 3000000401,
                       jax.devices()[:1], Spans())
    cell.read_counters = True
    produced = cell.episode()
    counters = cell.counters()
    solve_lines = cell.solve_lines()
    vectors, products = cell.next_products(produced)
    data = {k: v.copy() for k, v in cell.host_data().items()}
    compared = cell.verify(reference, produced)
    exact = reference.fit(data, found["config"], jax.devices()[:1])
    return dict(found=found, driver=driver, reference=reference, produced=produced, exact=exact,
                counters=counters, solve_lines=solve_lines, vectors=vectors,
                products=products, data=data, compared=compared)


def test_a_sound_tiny_run_is_correct(sound_run):
    from benchmark import compare

    names = [name for name, _, _ in sound_run["compared"]]
    assert len(names) == 7 * 4
    assert [n for n in names if "hv_own_coef_rel_gap" in n] == [
        f"lambda{lam}_hv_own_coef_rel_gap" for lam in ("0.1", "1", "10", "100")]
    assert compare.judge(sound_run["compared"])


def test_the_episode_counts_its_products_and_says_how_each_solve_ended(sound_run):
    produced, counters = sound_run["produced"], sound_run["counters"]
    assert all(n > 0 for n in produced["hv_products"])
    # one launch at the start and one a round; the products are counted apart
    assert produced["evaluations"] == [1 + i for i in produced["iterations"]]
    (_, in_episode), = counters["hv_products"]
    assert in_episode == sum(produced["hv_products"])
    assert counters["hv_operand"] == (4000, 64, 4)
    assert counters["retrace_s"] and len(counters["retrace_s"][0]) == 2
    line = sound_run["solve_lines"]
    assert line.startswith("tron solves: lambda0.1: rounds ")
    for word in ("products", "rejected", "floor_exits", "reason"):
        assert line.count(word) == 4
    assert "MAX_ITERATIONS" not in line


def _judge_with(sound_run, produced=None, products=None):
    """The comparisons of the sound run with what was produced, or the
    products, replaced."""
    from benchmark.compare import (
        path_comparisons,
        path_own_coefficient_comparisons,
    )

    found, reference, data = (sound_run[k] for k in ("found", "reference", "data"))
    limits = found["config"]["limits"]
    produced = produced or sound_run["produced"]
    products = sound_run["products"] if products is None else products
    lambdas = found["config"]["lambdas"]
    exact = sound_run["exact"]
    both = reference.evaluate(
        data, np.concatenate([produced["coefficients"], exact]),
        list(produced["lambdas"]) + list(lambdas))
    k = len(produced["coefficients"])
    own = {name: values[:k] for name, values in both.items()}
    expected = {"coefficients": exact,
                **{name: values[k:] for name, values in both.items()}}
    expected_hv = reference.hessian_vector(
        data, sound_run["produced"]["coefficients"], sound_run["vectors"],
        sound_run["produced"]["lambdas"])
    return (path_own_coefficient_comparisons(produced, own, limits)
            + path_comparisons(produced, expected, data["y_val"], limits)
            + sound_run["driver"].hv_comparisons(
                products, expected_hv, sound_run["produced"]["lambdas"], limits))


def _failed(comparisons) -> set:
    return {name for name, value, limit in comparisons
            if not (np.isfinite(value) and value <= limit)}


def test_a_misreported_coefficient_is_caught_by_its_limit(sound_run):
    produced = copy.deepcopy(sound_run["produced"])
    produced["coefficients"][2, 5] *= 1.01
    failed = _failed(_judge_with(sound_run, produced=produced))
    assert "lambda10_coef_rel_l2" in failed
    assert not any(name.startswith(("lambda0.1_", "lambda1_", "lambda100_"))
                   for name in failed)


def test_a_dropped_lambda_is_caught(sound_run):
    produced = copy.deepcopy(sound_run["produced"])
    for key in ("coefficients", "val_margin"):
        produced[key] = produced[key][:3]
    for key in ("lambdas", "values", "gradient_norms", "iterations", "reasons"):
        produced[key] = produced[key][:3]
    assert "lambdas_missing" in _failed(_judge_with(sound_run, produced=produced))
    assert _failed(sound_run["driver"].hv_comparisons(
        sound_run["products"][:3], sound_run["products"], [0.1, 1, 10, 100],
        sound_run["found"]["config"]["limits"])) == {"hv_lambdas_missing"}


def test_a_product_at_other_coefficients_is_caught_by_its_limit(sound_run):
    """The product the program would take at coefficients 1e-2 off the ones it
    returned: the Hessian's ``D`` moves, and the product with it."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMObjective

    data, produced = sound_run["data"], sound_run["produced"]
    batch = LabeledPointBatch.create(jnp.asarray(data["x"]), jnp.asarray(data["y"]))
    objective = GLMObjective(LogisticLoss())
    elsewhere = []
    for k, lam in enumerate(produced["lambdas"]):
        w = produced["coefficients"][k]
        off = w + 0.01 * np.linalg.norm(w) / np.sqrt(w.size)
        v = jnp.asarray(sound_run["vectors"][k])
        elsewhere.append(np.asarray(objective.hessian_vector(
            jnp.asarray(off, jnp.float32), v, batch) + np.float32(lam) * v))
    failed = _failed(_judge_with(sound_run, products=np.stack(elsewhere)))
    assert {f"lambda{lam:g}_hv_own_coef_rel_gap" for lam in (0.1, 1.0)} <= failed
    assert all(name.endswith("hv_own_coef_rel_gap") for name in failed)


# -- the readers, on a synthetic trace ---------------------------------------------

#: one path solve of the program as its compiled text would record it
RECORD = ({
    "copy.9": ("f32[4000,64]copy", "b.features"),
    "_fused_padded.7": ("(f32[1,1],f32[1,128],f32[1,1])custom-call",
                        "jit(_jitted_path_solve)/pallas_call"),
    "while.53": ("(f32[64],f32[])while", "jit(_jitted_path_solve)/while"),
    "while.54": ("(f32[64],s32[])while",
                 "jit(_jitted_path_solve)/while/body/tron/cg/while"),
    "multiply_reduce_fusion.33": (
        "f32[4000]fusion",
        "jit(_jitted_path_solve)/while/body/tron/cg/while/body/tron/hv/jvp(jvp())/dot_general"),
    "multiply_reduce_fusion.34": (
        "f32[64]fusion",
        "jit(_jitted_path_solve)/while/body/tron/cg/while/body/tron/hv/"
        "jvp(transpose(jvp()))/dot_general"),
    "fusion.6": ("f32[64]fusion",
                 "jit(_jitted_path_solve)/while/body/tron/cg/while/body/add"),
    "fusion.9": ("f32[]fusion", "jit(_jitted_path_solve)/while/body/tron/update/sub"),
}, frozenset({"while.53"}))

MS = 1e6  # ns


def _event(name, signature_text, start_ms, dur_ms):
    return (f"%{name} = {signature_text}", start_ms * MS, dur_ms * MS)


def synthetic_trace(module="jit__jitted_path_solve(7)"):
    """A window of 100 ms holding one solve of 80 ms: a relayout copy of X
    (10 ms), a kernel launch (8), a round's loop (60) whose CG loop (50) holds
    two products of 10 + 8 ms with 2 ms of vector work each and a
    metadata-less copy inside the first product's span, then the update (1).
    10 ms of another module's work lie outside the solve."""
    x = "f32[4000,64]{1,0:T(8,128)}"
    ops = [
        _event("copy.9", f"{x} copy(f32[4000,64]{{0,1}} %p)", 0, 10),
        _event("_fused_padded.7", "(f32[1,1]{1,0}, f32[1,128]{1,0}, f32[1,1]{1,0}) "
               f"custom-call({x} %copy.9, f32[4000,3]{{1,0}} %a)", 10, 8),
        _event("while.53", "(f32[64]{0}, f32[]) while((f32[64]{0}, f32[]) %t)", 18, 60),
        _event("while.54", "(f32[64]{0}, s32[]) while((f32[64]{0}, s32[]) %t)", 20, 50),
        _event("multiply_reduce_fusion.33", f"f32[4000]{{0}} fusion({x} %g, f32[64]{{0}} %v)", 20, 10),
        _event("copy-done.3", "f32[4000]{0:S(1)} copy-done((f32[4000]{0}) %c)", 24, 1),
        _event("multiply_reduce_fusion.34", f"f32[64]{{0}} fusion({x} %g, f32[4000]{{0}} %u)", 30, 8),
        _event("fusion.6", "f32[64]{0} fusion(f32[64]{0} %a)", 38, 2),
        _event("multiply_reduce_fusion.33", f"f32[4000]{{0}} fusion({x} %g, f32[64]{{0}} %v)", 40, 10),
        _event("multiply_reduce_fusion.34", f"f32[64]{{0}} fusion({x} %g, f32[4000]{{0}} %u)", 50, 8),
        _event("fusion.6", "f32[64]{0} fusion(f32[64]{0} %a)", 58, 2),
        _event("fusion.9", "f32[] fusion(f32[] %a)", 70, 1),
        ("%fusion.1 = f32[1000]{0} fusion(f32[1000]{0} %s)", 85 * MS, 10 * MS),
    ]
    return {"devices": {0: {"ops": ops, "modules": [
        (module, 0.0, 80 * MS), ("jit_matmul(9)", 85 * MS, 10 * MS)]}},
        "host": [("bench:window", 0.0, 100 * MS)]}


def test_the_partition_files_every_busy_instant_of_a_solve():
    part = path_scopes.partition(synthetic_trace(), RECORD,
                                 program_ledger.parse_instruction)
    seconds = {k: round(v * 1e3, 6) for k, v in part["seconds"].items()}
    # the copy inside a product's span has no metadata: it is the product's
    assert seconds == {"hv": 36.0, "cg": 14.0, "update": 1.0, "kernel": 8.0,
                       "copy_x": 10.0, "other": 9.0}
    assert round(part["busy_s"] * 1e3, 6) == 88.0  # the solve's 78 and 10 outside it
    assert round(part["solve_s"] * 1e3, 6) == 78.0
    assert part["hv_events"] == 5


@pytest.mark.parametrize("what", ["another solver's program", "no solve in the window",
                                  "a record of another program"])
def test_the_partition_is_nothing_without_the_scope(what):
    trace, record = synthetic_trace(), RECORD
    if what == "another solver's program":
        record = ({name: (sig, op.replace("tron/", "lbfgs/"))
                   for name, (sig, op) in RECORD[0].items()}, RECORD[1])
    elif what == "no solve in the window":
        trace = synthetic_trace(module="jit__step_impl(7)")
    else:
        record = ({**RECORD[0], "multiply_reduce_fusion.33": (
            "f32[9]fusion", RECORD[0]["multiply_reduce_fusion.33"][1])}, RECORD[1])
    assert path_scopes.partition(trace, record, program_ledger.parse_instruction) is None


@pytest.mark.parametrize("text,expected", [
    ("jit(f)/while/body/tron/cg/while/body/tron/hv/jvp(jvp())/dot_general", "hv"),
    ("jit(f)/while/body/tron/cg/while/body/add", "cg"),
    ("jit(f)/while/body/tron/cg/while", "cg"),
    ("jit(f)/while/body/tron/update/sub", "update"),
    ("jit(f)/while/body/pallas_call", None),
    ("jit(f)/while/body/lbfgs/line_search/while", None),
    ("jit(f)/electron/hv/add", None),
    (None, None),
])
def test_an_op_names_innermost_tron_scope(text, expected):
    assert path_scopes.tron_scope(text) == expected


@pytest.fixture
def ctx(monkeypatch):
    part = path_scopes.partition(synthetic_trace(), RECORD,
                                 program_ledger.parse_instruction)
    monkeypatch.setattr(path_scopes, "of_this_run", lambda: part)
    return {"window_start": 100.0, "device": {"kind": "TPU v5 lite"},
            "counters": {"hv_products": [(50.0, 99), (100.5, 2), (101.5, 4)],
                         "hv_operand": (4000, 64, 4)}}


def test_the_three_readers_give_the_known_values(ctx):
    assert layer_metric_reader("path_hv_products")(ctx) == 3  # median of 2 and 4
    assert layer_metric_reader("path_hv_time_share_pct")(ctx) == pytest.approx(
        100 * 36 / 88)
    # six products of one read of X each over the 36 ms under tron/hv
    bytes_one = 4000 * 64 * 4 + 4000 * 4 + 2 * 64 * 4
    assert roofline_hv.hv_bytes(4000, 64, 4) == bytes_one
    assert layer_metric_reader("path_hv_roofline")(ctx) == pytest.approx(
        100 * (6 * bytes_one / 819e9) / 0.036)


@pytest.mark.parametrize("name", OWN_METRICS)
def test_a_reader_on_a_program_without_the_scope(name, ctx, monkeypatch):
    """The parent: its TRON counts no product (the driver hands over zeros)
    and its program carries no scope. The count reads 0, the two trace
    readers nothing, and none raises."""
    monkeypatch.setattr(path_scopes, "of_this_run", lambda: None)
    ctx["counters"]["hv_products"] = [(100.5, 0), (101.5, 0)]
    value = layer_metric_reader(name)(ctx)
    assert value == (0 if name == "path_hv_products" else None)


@pytest.mark.parametrize("name", OWN_METRICS)
def test_a_reader_with_no_counters_returns_nothing(name, monkeypatch):
    monkeypatch.setattr(path_scopes, "of_this_run", lambda: None)
    assert layer_metric_reader(name)({
        "window_start": 0.0, "device": {"kind": "TPU v5 lite"}, "counters": {}}) is None


def test_of_this_run_is_nothing_on_the_cpu():
    # no device plane was ever traced here: no xplane file, no partition
    assert path_scopes.of_this_run() is None


def test_the_roofline_counts_one_read_of_x_whatever_implements_the_product():
    # 400,000 x 2,000 float32: 3.2 GB and the vectors, 3.91 ms at the peak
    assert roofline_hv.hv_bytes(400_000, 2_000, 4) == 3_200_000_000 + 1_600_000 + 16_000
    at_peak = roofline_hv.hv_bytes(400_000, 2_000, 4) / 819e9
    assert roofline_hv.hv_roofline_pct(10, 400_000, 2_000, 4, 10 * at_peak,
                                       "TPU v5 lite") == pytest.approx(100.0)
    # an implementation that reads X twice at the peak shows as a half
    assert roofline_hv.hv_roofline_pct(10, 400_000, 2_000, 4, 20 * at_peak,
                                       "TPU v5 lite") == pytest.approx(50.0)
    with pytest.raises(KeyError):
        roofline_hv.hv_roofline_pct(1, 1, 1, 4, 1.0, "an unknown chip")
