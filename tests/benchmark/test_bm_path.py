"""The cell ``logistic-epsilon.path``: its generator, its reference against a
float64 closed computation, the cell through ``run_cell`` at tiny size on the
CPU, the bfloat16 control and a broken timed path coming out not correct,
and its per-layer readers on a small recorded trace."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, datagen_dense, trace_reduce
from benchmark.manifest import layer_metric_reader, load_manifest, load_module
from benchmark.spans import Spans
from bm_helpers import fit_and_compare, run_with_the_timed_path_broken, tiny_cell

WORKLOAD = "logistic-epsilon.path"
HERE = os.path.dirname(os.path.abspath(__file__))


# -- the generator -------------------------------------------------------------


@pytest.fixture(scope="module")
def two_seeds():
    cfg = tiny_cell(WORKLOAD)["config"]
    device = jax.devices()[0]
    return cfg, *({k: np.asarray(v) for k, v in
                   datagen_dense.make_dense(cfg, seed, device).items()}
                  for seed in (11, 4000000123))


def test_the_generator_gives_the_sources_shape_and_preparation(two_seeds):
    cfg, a, _ = two_seeds
    n, n_val, d = cfg["rows"], cfg["validation_rows"], cfg["features"]
    assert a["x"].shape == (n, d) and a["x_val"].shape == (n_val, d)
    assert all(v.dtype == np.float32 for v in a.values())
    for x in (a["x"], a["x_val"]):  # every row scaled to unit length
        assert np.allclose(np.linalg.norm(x.astype(np.float64), axis=1), 1.0, atol=1e-6)
    # standardized before that: a column's mean is small against its deviation
    columns = a["x"].astype(np.float64)
    assert np.abs(columns.mean(0)).max() < 0.02 * columns.std(0).min()
    assert set(np.unique(a["y"])) == {0.0, 1.0} and 0.4 < a["y"].mean() < 0.6
    # correlated through the shared factors, as dense real features are
    corr = np.corrcoef(columns.T)
    assert np.abs(corr[np.triu_indices(d, 1)]).mean() > 0.05


def test_the_seed_orders_the_validation_rows_and_nothing_of_the_fit(two_seeds):
    _, a, b = two_seeds
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert not np.array_equal(a["x_val"], b["x_val"])
    order = lambda s: np.lexsort(s["x_val"][:, :3].T)  # noqa: E731
    assert np.array_equal(a["x_val"][order(a)], b["x_val"][order(b)])
    assert np.array_equal(a["y_val"][order(a)], b["y_val"][order(b)])


def test_big_blocks_are_drawn_in_whole_steps():
    assert datagen_dense._chunks(400_000) == (8, 50_000)
    assert datagen_dense._chunks(4_000) == (1, 4_000)
    with pytest.raises(ValueError, match="multiple"):
        datagen_dense._chunks(50_001)


# -- the reference ---------------------------------------------------------------


@pytest.fixture(scope="module")
def small_problem():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((200, 20)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    w = 3.0 * rng.standard_normal(20)
    y = (rng.random(200) < 1 / (1 + np.exp(-x @ w))).astype(np.float32)
    return {"x": x, "y": y, "x_val": x[:50], "y_val": y[:50]}


def _newton_float64(x, y, lam):
    """The minimizer by Newton in float64 numpy, to a gradient of 1e-12."""
    x, y = x.astype(np.float64), y.astype(np.float64)
    w = np.zeros(x.shape[1])
    for _ in range(50):
        p = 1 / (1 + np.exp(-x @ w))
        g = x.T @ (p - y) + lam * w
        if np.linalg.norm(g) < 1e-12:
            break
        w -= np.linalg.solve(x.T @ (x * (p * (1 - p))[:, None]) + lam * np.eye(len(w)), g)
    return w


def test_the_reference_finds_the_float64_minimizer(small_problem):
    reference = load_module(tiny_cell(WORKLOAD)["reference"])
    lambdas = [0.01, 0.1, 1, 10]
    fitted = reference.fit(small_problem, {"lambdas": lambdas}, jax.devices()[:1])
    assert fitted.shape == (4, 20) and fitted.dtype == np.float32
    for w, lam in zip(fitted, lambdas):
        exact = _newton_float64(small_problem["x"], small_problem["y"], lam)
        assert compare.rel_l2(w, exact) < 2e-5, lam
    # heavier regularization, smaller coefficients
    assert (np.diff(np.linalg.norm(fitted, axis=1)) < 0).all()


def test_the_references_evaluation_is_the_float64_objective(small_problem):
    reference = load_module(tiny_cell(WORKLOAD)["reference"])
    w = np.random.default_rng(6).standard_normal((2, 20)).astype(np.float32)
    got = reference.evaluate(small_problem, w, [0.5, 2.0])
    x, y = small_problem["x"].astype(np.float64), small_problem["y"].astype(np.float64)
    for k, lam in enumerate((0.5, 2.0)):
        m = x @ w[k].astype(np.float64)
        value = np.sum(np.log1p(np.exp(m)) - y * m) + 0.5 * lam * np.sum(w[k].astype(np.float64) ** 2)
        assert got["value"][k] == pytest.approx(value, rel=1e-13)
        gradient = x.T @ (1 / (1 + np.exp(-m)) - y) + lam * w[k].astype(np.float64)
        assert got["grad_norm"][k] == pytest.approx(np.linalg.norm(gradient), rel=1e-12)
        assert np.allclose(got["val_margin"][k], m[:50], rtol=0, atol=1e-13)


# -- correct, and shown to fail ------------------------------------------------------


def test_the_control_comes_out_not_correct():
    """The program with its bfloat16 feature path on: the objective value,
    the solve's gradient norm and the validation margins the episode read,
    AT ITS OWN coefficients, leave the float32 floor, each by more than ten
    times its limit."""
    compared = fit_and_compare(WORKLOAD, 21, "bfloat16")
    assert not compare.judge(compared), compared
    by_name = {n: (v, lim) for n, v, lim in compared}
    for lam in ("0.1", "1", "10", "100"):
        for kind in ("loss_own_coef_rel_gap", "grad_norm_own_coef_rel_gap",
                     "val_margin_own_coef_max_gap"):
            value, limit = by_name[f"lambda{lam}_{kind}"]
            assert value > 10 * limit, (lam, kind, value, limit)


def _start_returned(produced):
    """Every solve returns the zeros it started from."""
    return {**produced, "coefficients": np.zeros_like(produced["coefficients"])}


def _alter_one_coefficient(produced):
    coefficients = produced["coefficients"].copy()
    coefficients[2, 3] += 0.05
    return {**produced, "coefficients": coefficients}


def _misreport_a_value(produced):
    values = list(produced["values"])
    values[1] *= 1 + 2e-5
    return {**produced, "values": values}


def _misreport_a_gradient_norm(produced):
    norms = list(produced["gradient_norms"])
    norms[3] *= 1.01
    return {**produced, "gradient_norms": norms}


def _alter_a_margin_where_it_is_scored(produced):
    margins = produced["val_margin"].copy()
    margins[0, 17] += 1e-3
    return {**produced, "val_margin": margins}


def _one_lambda_short(produced):
    return {**produced, "lambdas": produced["lambdas"][:-1],
            "coefficients": produced["coefficients"][:-1]}


@pytest.mark.parametrize("break_it", [
    None, _start_returned, _alter_one_coefficient, _misreport_a_value,
    _misreport_a_gradient_norm, _alter_a_margin_where_it_is_scored,
    _one_lambda_short])
def test_a_run_with_the_timed_path_broken_is_not_correct(break_it, monkeypatch):
    found, result = run_with_the_timed_path_broken(
        WORKLOAD, break_it, monkeypatch, 2147483999)
    assert result["correct"] is (break_it is None), result["compared"]
    assert result["attempted"] == found["traffic"]["min_episodes"] and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "compared" and len(result["compared"]) in (10, 24)


def test_the_episode_itself_reads_what_correct_compares():
    """The validation margins are scored and read INSIDE the timed episode
    (model selection scores every λ), and ``verify`` scores nothing: with the
    validation block gone after the episode it compares the same numbers."""
    found = tiny_cell(WORKLOAD)
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    spans = Spans()
    cell = driver.Cell(found["config"], found["traffic"], 5, jax.devices()[:1], spans)
    produced = cell.episode()
    rows, lambdas = found["config"]["validation_rows"], found["config"]["lambdas"]
    assert produced["val_margin"].shape == (len(lambdas), rows)
    assert len(produced["gradient_norms"]) == len(lambdas)
    inside = [n for n, s, e in spans.closed if n in ("score", "read")]
    assert inside == ["score", "read"] and spans.closed[-1][0] == "episode"
    cell.val_features = None
    assert compare.judge(cell.verify(reference, produced))


def test_fit_s_is_the_windows_wall_time_over_its_episodes():
    """A stall inside the window moves it; the median episode does not."""
    driver = load_module(tiny_cell(WORKLOAD)["driver"])
    times = [2.0, 2.0, 2.0, 9.0]
    assert driver.Cell.end_to_end(None, times, 15.5) == {"fit_s": (15.5 / 4, "s")}


def test_the_drivers_recorder_keeps_what_train_glm_hands_it():
    driver = load_module(tiny_cell(WORKLOAD)["driver"])
    recorder = driver.SolveRecorder()
    assert recorder.record_solve("glm", "result", extra={"lambda": 10}) == {}
    assert recorder.heartbeat("glm", lam=10, n_lambdas=4) is None
    assert recorder.solves == [(10.0, "result")]


# -- the per-layer readers -------------------------------------------------------------


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(HERE, "data", "small_path_trace.json")) as f:
        raw = json.load(f)
    reduced = trace_reduce.reduce_trace({
        "devices": {int(k): {line: [tuple(e) for e in events]
                             for line, events in v.items()}
                    for k, v in raw["devices"].items()},
        "host": [tuple(e) for e in raw["host"]]})
    spans = Spans()
    spans.closed += [("episode", 1.0, 4.0), ("episode", 10.0, 12.0),
                     ("episode", 12.0, 14.5)]  # the first is the warm one
    return {"trace": reduced, "spans": spans, "window_start": 5.0,
            "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 7 * 2**30},
            "counters": {"compiles_in_window": 0,
                         "retrace_s": [(1.0, 9.0), (10.0, 0.4), (12.0, 0.6)]}}


def test_what_feeds_the_kernel_is_found_by_the_shape_it_is_called_with(ctx):
    reduced = ctx["trace"]
    assert reduced["window_s"] == pytest.approx(3000e-9)
    assert reduced["busy_s"] == pytest.approx((550 + 100 + 700 + 100) * 1e-9)
    assert reduced["kernel_calls"] == 3 and reduced["kernel_s"] == pytest.approx(600e-9)
    # two pads and the copy of a padded X; not the copy of the unpadded X,
    # not the pad after the window
    assert reduced["kernel_feed_s"] == pytest.approx((300 + 100 + 300) * 1e-9)
    assert trace_reduce.result_shape(
        "%pad.1 = bf16[1024,256]{1,0} pad(bf16[1000,200]{1,0} %x)") == (1024, 256, 2)
    assert trace_reduce.result_shape("%f = (f32[2,2]{1,0}, f32[]) fusion(...)") is None
    assert trace_reduce.result_shape("jit__step_impl(12)") is None


@pytest.mark.parametrize("name,expected", [
    ("episode_s.fit", 2.25),
    ("path_solver_evals", 1.5),  # 3 launches over 2 episodes
    ("sweeps_kernel_time_share_pct.fit", 100 * 600 / 1450),
    ("path_pad_time_share_pct", 100 * 700 / 1450),
    ("device_idle_pct.fit", 100 * (1 - 1450 / 3000)),
    ("peak_hbm_GiB.fit", 7.0),
    ("compiles_in_window.fit", 0),
    ("path_retrace_s", 0.5),  # the warm episode's 9 s left out
])
def test_path_reader_gives_the_known_value(name, expected, ctx):
    assert layer_metric_reader(name)(ctx) == pytest.approx(expected)


def test_the_kernels_roofline_share_comes_from_the_shapes_in_the_trace(ctx):
    from benchmark import roofline

    least = 3 * roofline.kernel_bytes(1024, 256, 4) / 819e9
    assert layer_metric_reader("sweeps_glm_kernel_roofline.fit")(ctx) == pytest.approx(
        100 * least / 600e-9)


@pytest.mark.parametrize("name", [
    "path_solver_evals", "sweeps_kernel_time_share_pct.fit", "path_pad_time_share_pct",
    "sweeps_glm_kernel_roofline.fit", "path_retrace_s", "episode_s.fit"])
def test_a_path_reader_with_nothing_to_read_returns_nothing(name, ctx):
    """No kernel in the trace (a CPU run, a program that turns it off), no
    seconds from the compile listener, no episode: the metric is left out,
    never reported as 0."""
    bare = dict(ctx, spans=Spans(), counters={"compiles_in_window": 0},
                trace=dict(ctx["trace"], kernel_calls=0, kernel_s=0.0,
                           kernel_bytes=0, kernel_feed_s=0.0))
    assert layer_metric_reader(name)(bare) is None


def test_every_per_layer_entry_of_the_cell_has_a_reader_that_was_tested():
    tested = {"trace_lower_s", "program_load_s",  # test_bm_program_trace.py
              "episode_s.fit", "path_solver_evals", "sweeps_kernel_time_share_pct.fit",
              "sweeps_glm_kernel_roofline.fit", "path_pad_time_share_pct",
              "device_idle_pct.fit", "peak_hbm_GiB.fit",
              "compiles_in_window.fit", "path_retrace_s"}
    listed = {m["name"] for m in load_manifest()["per_layer"]
              if WORKLOAD in m.get("workloads", ())}
    assert tested == listed
