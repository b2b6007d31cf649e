"""The cell ``logistic-kdda-sparse.path``: its manifest entries, its generator,
its driver and reference at a tiny size on the CPU, its five readers on a
synthetic trace, its roofline's byte count, and what its comparison catches.

The manifest tests assert that the cell and its entries are IN the lists,
never where: the next cell appended turns nothing here red.
"""

import copy
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_sparse, path_sparse_scopes, roofline_sparse
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

CELL = "logistic-kdda-sparse.path"
OWN_METRICS = ("path_sparse_tail_time_share_pct", "path_sparse_head_time_share_pct",
               "path_history_time_share_pct", "path_sparse_tail_ns_per_entry",
               "path_sparse_eval_roofline")
JOINED = ("trace_lower_s", "program_load_s", "episode_s.fit", "device_idle_pct.fit",
          "peak_hbm_GiB.fit", "compiles_in_window.fit", "path_retrace_s")
#: entries that read a Pallas kernel or a Hessian-vector product: none runs here
NOT_JOINED = ("sweeps_kernel_time_share_pct.fit", "sweeps_glm_kernel_roofline.fit",
              "path_pad_time_share_pct", "path_solver_evals", "path_hv_products",
              "path_hv_time_share_pct", "path_hv_roofline")
HERE = os.path.join(M.ROOT, "benchmark")
TINY = dict(rows=4000, validation_rows=500, features=50_000, hot_cols=128)
#: limits for the tiny size on the CPU, set as the chip's are: above what the
#: float32 run reads here (one reading each: every seed poses the same fit;
#: larger of the two λ) and, for the first three, below the bfloat16
#: control's: value at own coefficients 3.8e-7 (control 2.9e-6 to 1.3e-5),
#: the solve's gradient norm 9.9e-6 (1.5e-2), validation margins 4.3e-7
#: (5.9e-3); coef_rel_l2 5.6e-3, value against the reference's 4.5e-6,
#: val_auc 2.7e-4 (held against a solve that returns its start, which reads 1)
TINY_LIMITS = {"loss_own_coef_rel_gap": 1.5e-6, "grad_norm_own_coef_rel_gap": 1e-3,
               "val_margin_own_coef_max_gap": 4e-5, "coef_rel_l2": 2e-2,
               "loss_rel_gap": 2e-5, "val_auc_gap": 1e-3}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


def tiny_cell(**overrides) -> dict:
    found = find_cell(load_manifest(), CELL)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY)
    found["config"]["limits"] = dict(TINY_LIMITS)
    found["config"].update(overrides)
    return found


# -- the manifest: membership, never position --------------------------------------


def test_the_manifest_holds_the_cell(manifest):
    assert M.check_manifest(manifest) == []
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "logistic-kdda-sparse", "path-sparse", 1)
    entry = {c["name"]: c for c in manifest["configs"]}["logistic-kdda-sparse"]
    assert sorted(entry["reduced"]) == ["rows", "validation_rows"]
    assert "kdd2010 (algebra)" in entry["source"]
    fit_s = {m["name"]: m for m in manifest["end_to_end"]}["fit_s"]
    assert CELL in fit_s["workloads"] and fit_s["bound"] == 0.05
    found = find_cell(manifest, CELL)
    assert found["traffic"]["kind"] == "glm_path_sparse"
    assert (found["traffic"]["min_episodes"], found["traffic"]["traced_episodes"]) == (3, 2)
    for key in ("driver", "reference"):
        assert os.path.isfile(found[key]), found[key]


@pytest.mark.parametrize("name", OWN_METRICS)
def test_the_manifest_holds_the_cells_own_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "fit_s"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("ns" if name.endswith("ns_per_entry") else "%")
    assert entry["better"] == ("higher" if name.endswith("_roofline") else "lower")
    assert os.path.isfile(M.reader_file(name))


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_accepted_entry(manifest, name):
    assert CELL in {m["name"]: m for m in manifest["per_layer"]}[name]["workloads"]


@pytest.mark.parametrize("name", NOT_JOINED)
def test_the_cell_stays_off_an_entry_that_reads_a_kernel_or_a_product(manifest, name):
    assert CELL not in {m["name"]: m for m in manifest["per_layer"]}[name]["workloads"]


def test_the_cell_reports_twelve_per_layer_metrics(manifest):
    names = {m["name"] for m in metrics_of(manifest, "per_layer", CELL,
                                           {"fit_s", "setup_s"})}
    assert names == set(JOINED) | set(OWN_METRICS)


def test_the_configuration_states_the_deployment_as_the_issue_names_it():
    cfg = find_cell(load_manifest(), CELL)["config"]
    assert cfg["features"] == 20_216_830  # not cut
    assert cfg["rows"] in (8_407_752 // 8, 8_407_752 // 16)
    sixteenth = cfg["rows"] == 8_407_752 // 16
    assert cfg["validation_rows"] == 510_302 // (16 if sixteenth else 8)
    assert cfg["hot_cols"] == (2048 if sixteenth else 1024)
    assert cfg["rows"] * cfg["hot_cols"] * 4 < 4.31e9  # the 4.30 GB head
    assert cfg["lambdas"] == [1, 10] and cfg["feature_dtype"] == "float32"
    assert cfg["optimizer"] == {"type": "LBFGS", "max_iterations": 15,
                                "rel_function_tolerance": 1e-6, "history": 10}
    assert sorted(cfg["reduced"]) == ["rows", "validation_rows"]
    assert set(cfg["limits"]) == set(TINY_LIMITS)
    assert set(cfg["limit_readings"]) >= set(TINY_LIMITS)
    assert all(isinstance(line, str) and line for line in cfg["assumed"])


# -- the generator ------------------------------------------------------------------

GEN = dict(rows=20_000, validation_rows=1_000)


@pytest.fixture(scope="module")
def generated():
    cfg = {**find_cell(load_manifest(), CELL)["config"], **GEN}
    return cfg, datagen_sparse.make_sparse(cfg, 7)


def test_the_generator_is_a_pure_function_of_the_two_seeds(generated):
    cfg, data = generated
    again = datagen_sparse.make_sparse(cfg, 7)
    assert all(np.array_equal(data[k], again[k]) for k in data)
    other_seed = datagen_sparse.make_sparse(cfg, 8)
    for key in ("rows", "cols", "vals", "y"):  # --seed reaches nothing of the fit
        assert np.array_equal(data[key], other_seed[key])
    assert not np.array_equal(data["y_val"], other_seed["y_val"])
    # the same validation rows in another order: each row's entries move with it
    assert sorted(np.bincount(data["rows_val"])) == sorted(np.bincount(other_seed["rows_val"]))
    assert data["vals_val"].sum() == pytest.approx(other_seed["vals_val"].sum(), rel=1e-6)
    other_data = datagen_sparse.make_sparse({**cfg, "data_seed": cfg["data_seed"] + 1}, 7)
    assert not np.array_equal(data["cols"][:1000], other_data["cols"][:1000])


def test_the_triple_is_row_major_unique_and_every_row_has_unit_length(generated):
    cfg, data = generated
    for suffix, n in (("", cfg["rows"]), ("_val", cfg["validation_rows"])):
        rows, cols, vals = (data[k + suffix] for k in ("rows", "cols", "vals"))
        key = rows.astype(np.int64) * cfg["features"] + cols
        assert (np.diff(key) > 0).all()
        assert rows.dtype == cols.dtype == np.int32 and vals.dtype == np.float32
        assert 0 <= cols.min() and cols.max() < cfg["features"]
        squares = np.bincount(rows, weights=vals.astype(np.float64) ** 2, minlength=n)
        assert np.allclose(squares, 1.0, atol=1e-6)
        assert set(np.unique(data["y" + suffix])) == {0.0, 1.0}


def test_the_generator_reads_what_the_configuration_says(generated):
    cfg, data = generated
    reads = cfg["generator"]["reads"]
    per_row = len(data["vals"]) / cfg["rows"]
    assert per_row == pytest.approx(reads["nonzeros_per_row"], rel=0.02)
    assert reads["nonzeros_per_row"] == pytest.approx(36.3, rel=0.02)  # the source's
    assert datagen_sparse.hot_coverage(data["cols"], 1024) == pytest.approx(
        reads["hot_coverage_1024"], abs=0.01)
    assert reads["hot_coverage_1024"] == pytest.approx(0.60, abs=0.02)
    # the hottest columns are scattered over the id space, not its first ids
    ids, counts = np.unique(data["cols"], return_counts=True)
    hottest = ids[np.argsort(-counts)[:1024]]
    assert np.median(hottest) > 0.25 * cfg["features"]
    assert np.mean(hottest < 1024) < 0.01


def test_the_bijection_is_one():
    for d in (10, 97, 1000, 20_216_830):
        assert np.gcd(datagen_sparse.multiplier_for(d), d) == 1
    ranks = np.arange(1000)
    assert len(set(datagen_sparse.column_of_rank(ranks, 1000, 17).tolist())) == 1000


# -- the share ties to the whole -----------------------------------------------------


def test_eight_row_shares_add_up_to_the_uncut_reference():
    """Value and gradient of the eight row shares, each through
    ``SparseGLMObjective`` on a hybrid batch with the WHOLE d, add up to the
    reference's float64 value and gradient norm over all the rows."""
    from photon_ml_tpu.data.sparse_batch import HybridPolicy, SparseLabeledPointBatch
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective

    found = tiny_cell(rows=8 * 500)
    cfg = found["config"]
    data = datagen_sparse.make_sparse(cfg, 3)
    d = cfg["features"]
    w = (0.3 * np.random.default_rng(5).standard_normal(d)).astype(np.float64)
    objective = SparseGLMObjective(LogisticLoss())
    value, gradient = 0.0, np.zeros(d)
    for share in range(8):
        lo, hi = 500 * share, 500 * (share + 1)
        take = (data["rows"] >= lo) & (data["rows"] < hi)
        batch = SparseLabeledPointBatch.from_coo(
            data["rows"][take] - lo, data["cols"][take],
            data["vals"][take].astype(np.float64), data["y"][lo:hi].astype(np.float64),
            dim=d, dtype=np.float64, hybrid=HybridPolicy(hot_cols=128, label="share"))
        v, g = objective.value_and_gradient(jnp.asarray(w), batch)
        value += float(v)
        gradient += np.asarray(g)
    whole = load_module(found["reference"]).evaluate(data, w[None], [0.0])
    assert value == pytest.approx(float(whole["value"][0]), rel=1e-12)
    assert np.linalg.norm(gradient) == pytest.approx(float(whole["grad_norm"][0]), rel=1e-11)


# -- the driver and the reference, at tiny size ------------------------------------


@pytest.fixture(scope="module")
def sound_run():
    found = tiny_cell()
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 11, jax.devices()[:1], Spans())
    cell.read_counters = True
    produced = copy.deepcopy(cell.episode())
    counters = cell.counters()
    data = cell.host_data()
    comparisons = cell.verify(reference, produced)
    return {"found": found, "driver": driver, "reference": reference, "data": data,
            "produced": produced, "counters": counters, "comparisons": comparisons,
            "base": driver.base}


def test_a_sound_tiny_run_is_correct(sound_run):
    from benchmark import compare

    names = [name for name, _, _ in sound_run["comparisons"]]
    assert len(names) == 12 and len(set(names)) == 12  # six kinds x two λ
    assert compare.judge(sound_run["comparisons"])


def test_the_episode_is_glm_paths_own_method(sound_run):
    driver, base = sound_run["driver"], sound_run["base"]
    for name in ("end_to_end", "host_data", "release", "verify"):
        assert getattr(driver.Cell, name) is getattr(base.Cell, name)
    # the override keeps the traced runs' count and calls the accepted episode
    source = inspect.getsource(driver.Cell.episode)
    assert "super().episode()" in source and "train_glm" not in source


def test_the_episode_hands_the_readers_its_counts(sound_run):
    produced, counters = sound_run["produced"], sound_run["counters"]
    assert produced["lambdas"] == [1.0, 10.0]
    assert produced["coefficients"].shape == (2, TINY["features"])
    assert produced["val_margin"].shape == (2, TINY["validation_rows"])
    assert all(0 < i <= 15 for i in produced["iterations"])
    (_, evaluations), = counters["evaluations"]
    assert evaluations == sum(produced["evaluations"]) >= sum(produced["iterations"]) + 2
    entries, rows, features = counters["sparse_shape"]
    assert (rows, features) == (TINY["rows"], TINY["features"])
    assert entries == len(sound_run["data"]["vals"])
    assert 0 < counters["tail_entries"] < entries and counters["k_hot"] == 128


def test_the_bfloat16_control_fails_a_limit():
    from benchmark import compare

    found = tiny_cell(feature_dtype="bfloat16")
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 11, jax.devices()[:1], Spans())
    comparisons = cell.verify(reference, cell.episode(), fit=False)
    failed = {name for name, value, limit in comparisons if not value <= limit}
    assert {"lambda1_val_margin_own_coef_max_gap",
            "lambda10_val_margin_own_coef_max_gap"} <= failed
    assert not compare.judge(comparisons)


def test_a_head_with_bfloat16_operands_fails_the_margin_limit(sound_run, monkeypatch):
    """What a TPU's DEFAULT matmul precision does to float32 operands, emulated
    on the CPU: both operands of the head's dots rounded to bfloat16's eight
    bits of mantissa. The float32 head passes ``val_margin_own_coef_max_gap``
    (the sound run); the rounded one fails it."""
    from photon_ml_tpu.data import sparse_batch
    from photon_ml_tpu.models import coefficients as coefficients_module

    def rounded_dot(a, b):
        a, b = (jax.lax.reduce_precision(x.astype(jnp.float32), 8, 7) for x in (a, b))
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    monkeypatch.setattr(sparse_batch, "hot_head_dot", rounded_dot)
    # a new function object: a fresh trace (jit's cache is the function's)
    monkeypatch.setattr(coefficients_module, "_sparse_score", jax.jit(
        lambda batch, means: sparse_batch.sparse_product(batch, means)))
    found = sound_run["found"]
    cell = sound_run["driver"].Cell(found["config"], found["traffic"], 11,
                                    jax.devices()[:1], Spans())
    produced = copy.deepcopy(sound_run["produced"])
    from photon_ml_tpu.models.coefficients import Coefficients

    produced["val_margin"] = np.stack([
        np.asarray(Coefficients(jnp.asarray(w)).compute_score(cell.val_features))
        for w in produced["coefficients"]])
    comparisons = cell.verify(sound_run["reference"], produced, fit=False)
    margins = {name: (value, limit) for name, value, limit in comparisons
               if name.endswith("val_margin_own_coef_max_gap")}
    assert len(margins) == 2
    assert all(value > 10 * limit for value, limit in margins.values()), margins
    sound = {name: value for name, value, _ in sound_run["comparisons"]}
    assert all(sound[name] < limit / 10 for name, (_, limit) in margins.items())


def _judge_with(sound_run, **changes):
    found = sound_run["found"]
    cell = sound_run["driver"].Cell(found["config"], found["traffic"], 11,
                                    jax.devices()[:1], Spans())
    produced = {**copy.deepcopy(sound_run["produced"]), **changes}
    return cell.verify(sound_run["reference"], produced)


def test_a_solve_that_returns_its_start_is_caught(sound_run):
    produced = sound_run["produced"]
    comparisons = _judge_with(
        sound_run, coefficients=np.zeros_like(produced["coefficients"]))
    failed = {name for name, value, limit in comparisons if not value <= limit}
    assert {"lambda1_coef_rel_l2", "lambda10_coef_rel_l2"} <= failed


def test_a_dropped_lambda_is_caught(sound_run):
    produced = sound_run["produced"]
    comparisons = _judge_with(
        sound_run, lambdas=produced["lambdas"][:1],
        coefficients=produced["coefficients"][:1], val_margin=produced["val_margin"][:1])
    assert ("lambdas_missing", 1.0, 0.0) in comparisons


def test_the_references_evaluate_is_the_float64_objective(sound_run):
    """Against a dense float64 recomputation on a cut of the columns."""
    data, reference = sound_run["data"], sound_run["reference"]
    n, d = TINY["rows"], TINY["features"]
    w = (0.2 * np.random.default_rng(2).standard_normal((1, d))).astype(np.float32)
    got = reference.evaluate(data, w, [3.0])
    x = np.zeros((n, d))
    x[data["rows"], data["cols"]] = data["vals"]
    m = x @ w[0].astype(np.float64)
    y = data["y"].astype(np.float64)
    value = np.sum(np.logaddexp(0, m) - y * m) + 1.5 * np.sum(w[0].astype(np.float64) ** 2)
    gradient = x.T @ (1 / (1 + np.exp(-m)) - y) + 3.0 * w[0].astype(np.float64)
    assert got["value"][0] == pytest.approx(value, rel=1e-12)
    assert got["grad_norm"][0] == pytest.approx(np.linalg.norm(gradient), rel=1e-12)
    x_val = np.zeros((TINY["validation_rows"], d))
    x_val[data["rows_val"], data["cols_val"]] = data["vals_val"]
    assert np.allclose(got["val_margin"][0], x_val @ w[0].astype(np.float64), atol=1e-12)


def test_the_reference_imports_nothing_of_the_program(sound_run):
    source = inspect.getsource(sound_run["reference"])
    assert "photon_ml_tpu" not in source.split('"""', 2)[2]
    assert "sparse_batch" not in source.split('"""', 2)[2]


# -- the readers, on a synthetic trace ---------------------------------------------

LS = "jit(_jitted_path_solve)/while/body/lbfgs/line_search/while/body"
#: one path solve of the program as its compiled text would record it
RECORD = ({
    "fusion.1": ("f32[4000]fusion", "jit(_jitted_path_solve)/sparse/head/dot_general"),
    "fusion.2": ("f32[4000]fusion",
                 "jit(_jitted_path_solve)/sparse/tail_margins/nl,nl->n/dot_general"),
    "while.5": ("(f32[50000],f32[])while", "jit(_jitted_path_solve)/while"),
    "while.6": ("(f32[50000],s32[])while",
                "jit(_jitted_path_solve)/while/body/lbfgs/line_search/while"),
    "fusion.3": ("f32[4000]fusion", LS + "/sparse/head/dot_general"),
    "fusion.4": ("f32[4000]fusion", LS + "/sparse/tail_margins/gather"),
    "fusion.7": ("f32[4000]fusion", LS + "/logistic_loss/sub"),
    "fusion.8": ("f32[50000]fusion", LS + "/sparse/tail_gradient/scatter-add"),
    "fusion.9": ("f32[50000]fusion",
                 "jit(_jitted_path_solve)/while/body/lbfgs/direction/mul"),
    "fusion.10": ("f32[10,50000]fusion",
                  "jit(_jitted_path_solve)/while/body/lbfgs/history/concatenate"),
    "fusion.11": ("pred[]fusion", "jit(_jitted_path_solve)/while/body/lt"),
}, frozenset({"while.5"}))

MS = 1e6  # ns


def _event(name, signature_text, start_ms, dur_ms):
    return (f"%{name} = {signature_text}", start_ms * MS, dur_ms * MS)


def synthetic_trace(module="jit__jitted_path_solve(7)"):
    """A window of 100 ms holding one solve of 80 ms: the first evaluation's
    head (4 ms) and tail margins (6), then the iterations' loop (70) whose line
    search (50) holds a head dot (5), a tail gather (15) with a metadata-less
    copy inside it, the loss over the rows (2) and the tail's scatter (20);
    after it the recursion (6), the shift (4) and a stop test (1). 10 ms of
    another module's work (the scoring) lie outside the solve."""
    v = "f32[4000]{0}"
    ops = [
        _event("fusion.1", f"{v} fusion(f32[4000,128]{{1,0}} %h)", 0, 4),
        _event("fusion.2", f"{v} fusion(f32[4000,27]{{0,1}} %e)", 4, 6),
        _event("while.5", "(f32[50000]{0}, f32[]) while((f32[50000]{0}, f32[]) %t)", 10, 70),
        _event("while.6", "(f32[50000]{0}, s32[]) while((f32[50000]{0}, s32[]) %t)", 12, 50),
        _event("fusion.3", f"{v} fusion(f32[4000,128]{{1,0}} %h)", 12, 5),
        _event("fusion.4", f"{v} fusion(f32[50000]{{0}} %w)", 17, 15),
        _event("copy-done.3", "f32[50000]{0:S(1)} copy-done((f32[50000]{0}) %c)", 20, 1),
        _event("fusion.7", f"{v} fusion({v} %m)", 32, 2),
        _event("fusion.8", "f32[50000]{0} fusion(f32[50000]{0} %g)", 34, 20),
        _event("fusion.9", "f32[50000]{0} fusion(f32[50000]{0} %g)", 62, 6),
        _event("fusion.10", "f32[10,50000]{1,0} fusion(f32[10,50000]{1,0} %s)", 68, 4),
        _event("fusion.11", "pred[] fusion(f32[] %a)", 72, 1),
        ("%fusion.99 = f32[500]{0} fusion(f32[500]{0} %s)", 85 * MS, 10 * MS),
    ]
    return {"devices": {0: {"ops": ops, "modules": [
        (module, 0.0, 80 * MS), ("jit_sparse_product(9)", 85 * MS, 10 * MS)]}},
        "host": [("bench:window", 0.0, 100 * MS)]}


def test_the_partition_files_every_busy_instant_of_a_solve():
    part = path_sparse_scopes.partition(synthetic_trace(), RECORD,
                                        program_ledger.parse_instruction)
    seconds = {k: round(v * 1e3, 6) for k, v in part["seconds"].items()}
    # the copy inside the gather's span has no metadata: it is the gather's;
    # the line search keeps the loss and what its loop leaves uncovered (8 ms);
    # the outer loop's own 9 ms and the stop test are `other`
    assert seconds == {"tail_margins": 21.0, "tail_gradient": 20.0, "head": 9.0,
                       "history": 10.0, "line_search": 10.0, "other": 10.0}
    assert round(part["busy_s"] * 1e3, 6) == 90.0  # the solve's 80 and 10 outside it
    assert round(part["solve_s"] * 1e3, 6) == 80.0
    assert part["sparse_events"] == 6  # five scoped events and the copy inside one


@pytest.mark.parametrize("what", ["a dense batch's program", "no solve in the window",
                                  "a record of another program"])
def test_the_partition_is_nothing_without_the_scope(what):
    trace, record = synthetic_trace(), RECORD
    if what == "a dense batch's program":
        record = ({name: (sig, op.replace("sparse/", "dense/"))
                   for name, (sig, op) in RECORD[0].items()}, RECORD[1])
    elif what == "no solve in the window":
        trace = synthetic_trace(module="jit__step_impl(7)")
    else:
        record = ({**RECORD[0], "fusion.4": ("f32[9]fusion", RECORD[0]["fusion.4"][1])},
                  RECORD[1])
    assert path_sparse_scopes.partition(
        trace, record, program_ledger.parse_instruction) is None


@pytest.mark.parametrize("text,expected", [
    (LS + "/sparse/tail_margins/gather", "tail_margins"),
    (LS + "/sparse/tail_gradient/scatter-add", "tail_gradient"),
    (LS + "/sparse/head/dot_general", "head"),
    ("jit(f)/sparse/head/gather", "head"),
    (LS + "/logistic/sub", "line_search"),
    ("jit(f)/while/body/lbfgs/direction/mul", "history"),
    ("jit(f)/while/body/lbfgs/history/concatenate", "history"),
    ("jit(f)/while/body/lt", "other"),
    ("jit(f)/while/body/tron/cg/while/body/tron/hv/sparse/head/dot_general", "head"),
    ("jit(f)/nonsparse/head/add", "other"),
    (None, "other"),
])
def test_an_op_names_innermost_scope_decides(text, expected):
    assert path_sparse_scopes.category(text) == expected


@pytest.fixture
def ctx(monkeypatch):
    part = path_sparse_scopes.partition(synthetic_trace(), RECORD,
                                        program_ledger.parse_instruction)
    monkeypatch.setattr(path_sparse_scopes, "of_this_run", lambda: part)
    return {"window_start": 100.0, "device": {"kind": "TPU v5 lite"},
            "counters": {"evaluations": [(50.0, 99), (100.5, 2), (101.5, 3)],
                         "tail_entries": 60_000, "k_hot": 128,
                         "sparse_shape": (138_000, 4000, 50_000)}}


def test_the_five_readers_give_the_known_values(ctx):
    read = lambda name: layer_metric_reader(name)(ctx)
    assert read("path_sparse_tail_time_share_pct") == pytest.approx(100 * 41 / 90)
    assert read("path_sparse_head_time_share_pct") == pytest.approx(100 * 9 / 90)
    assert read("path_history_time_share_pct") == pytest.approx(100 * 10 / 90)
    # 41 ms over 60,000 entries x 2 passes x the window's 5 evaluations
    assert read("path_sparse_tail_ns_per_entry") == pytest.approx(
        41e6 / (60_000 * 2 * 5))
    bytes_one = 138_000 * 8 + (2 * 50_000 + 3 * 4000) * 4
    assert read("path_sparse_eval_roofline") == pytest.approx(
        100 * (5 * bytes_one / 819e9) / 0.050)


@pytest.mark.parametrize("name", OWN_METRICS)
def test_a_reader_on_a_program_without_the_scopes(name, ctx, monkeypatch):
    """The parent: its program carries no ``sparse/`` scope. Every reader
    gives nothing, and none raises."""
    monkeypatch.setattr(path_sparse_scopes, "of_this_run", lambda: None)
    assert layer_metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", OWN_METRICS)
def test_a_reader_with_no_counters_returns_nothing(name, ctx):
    ctx["counters"] = {}
    value = layer_metric_reader(name)(ctx)
    assert value is None or name.endswith("time_share_pct")


def test_of_this_run_is_nothing_on_the_cpu():
    # no device plane was ever traced here: no xplane file, no partition
    assert path_sparse_scopes.of_this_run() is None


def test_the_roofline_counts_the_data_whatever_layout_holds_it():
    # a hand-made triple: 5 entries, 3 rows, 4 features
    assert roofline_sparse.eval_bytes(5, 3, 4) == 5 * 8 + (2 * 4 + 3 * 3) * 4
    # the cell's: 38.4 M entries, 1,050,969 rows, d 20,216,830: 0.48 GB
    one = roofline_sparse.eval_bytes(38_387_113, 1_050_969, 20_216_830)
    assert one == 38_387_113 * 8 + (2 * 20_216_830 + 3 * 1_050_969) * 4
    at_peak = one / 819e9
    assert roofline_sparse.eval_roofline_pct(
        7, 38_387_113, 1_050_969, 20_216_830, 7 * at_peak, "TPU v5 lite"
    ) == pytest.approx(100.0)
    assert roofline_sparse.eval_roofline_pct(
        7, 38_387_113, 1_050_969, 20_216_830, 70 * at_peak, "TPU v5 lite"
    ) == pytest.approx(10.0)
    with pytest.raises(KeyError):
        roofline_sparse.eval_roofline_pct(1, 1, 1, 1, 1.0, "an unknown chip")
