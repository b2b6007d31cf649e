"""The cell ``game-ymusic-r2.sweeps``: its manifest entries, its generator, its
driver and what its comparison catches, its five readers and the Hessian
pass's roofline. Whatever needs a device runs at a tiny size on the CPU; the
readers run on a small synthetic trace.

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

from benchmark import datagen_ratings, newton_scopes, roofline_newton, step_scopes
from benchmark import manifest as M
from benchmark.manifest import (
    find_cell,
    layer_metric_reader,
    load_manifest,
    load_module,
    metrics_of,
)
from benchmark.spans import Spans
from photon_ml_tpu.telemetry import registry as registry_module
from photon_ml_tpu.telemetry.program_ledger import parse_instruction
from photon_ml_tpu.telemetry.registry import MetricsRegistry

CELL = "game-ymusic-r2.sweeps"
CONFIG = "game-ymusic-r2"
OWN_METRICS = {
    "sweeps_newton_lockstep_rounds": ("rounds", "lower", "program_counter", "solver"),
    "sweeps_newton_rejected_share_pct": ("%", "lower", "program_counter", "solver"),
    "step_newton_time_share_pct": ("%", "lower", "device_trace", "step"),
    "step_newton_hessian_time_share_pct": ("%", "lower", "device_trace", "step"),
    "sweeps_newton_hessian_roofline": ("%", "higher", "device_trace", "solver"),
}
#: the per-layer lists of the sweeps cells whose readers run unchanged here
JOINED = ("pack_s", "episode_s", "sweeps_solver_evals", "sweeps_kernel_time_share_pct",
          "sweeps_glm_kernel_roofline", "device_idle_pct", "peak_hbm_GiB",
          "compiles_in_window", "prog_sweep_s", "prog_place_s", "sweep_host_s",
          "validate_s", "pack_group_s", "trace_lower_s", "program_load_s",
          "sweeps_re_lockstep_trials")
#: lists an accepted test pins to the cells they have (tests/benchmark/
#: test_bm_step_scopes.py, test_bm_entry_scores.py): left as they are
PINNED = ("entry_scores_time_share_pct",) + tuple(
    f"step_{c}_time_share_pct" for c in step_scopes.CATEGORIES)
HERE = os.path.join(M.ROOT, "benchmark")
TINY = dict(rows=20480, validation_rows=1500,
            users=dict(count=150, min=20, max=20000, a=1.3),
            songs=dict(count=1200, min=1, max=12000, a=1.05),
            artists=dict(count=90, min=1, max=4096, a=1.0))
#: limits for the tiny size on the CPU, set as the chip's are: above what the
#: float32 run reads here (one reading each: every seed poses the same fit):
#: loss at own coefficients 1e-8, validation margins 3e-7, RMSE 3e-8, the
#: artists' ridge residual 5e-7 (a Hessian contracted from bfloat16 operands
#: reads 5e-5 and up); against the reference's fit loss 6e-7, RMSE 3e-6,
#: coefficients 6e-4, norms 2e-5.
TINY_LIMITS = {"loss_own_coef_rel_gap": 5e-7, "val_margin_own_coef_max_gap": 3e-5,
               "val_rmse_own_coef_gap": 2e-6, "artist_ridge_own_coef_residual": 5e-6,
               "loss_rel_gap": 1e-5, "val_rmse_gap": 3e-5,
               "fe_coef_rel_l2": 4e-3, "user_coef_rel_l2": 4e-3, "song_coef_rel_l2": 4e-3,
               "artist_coef_rel_l2": 4e-3, "norm_rel_gap": 5e-4}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny_cell() -> dict:
    found = find_cell(load_manifest(), CELL)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(copy.deepcopy(TINY))
    found["config"]["limits"] = copy.deepcopy(TINY_LIMITS)
    return found


# -- the manifest ------------------------------------------------------------------


def test_the_manifest_holds_the_cell(manifest):
    assert M.check_manifest(manifest) == []
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": CONFIG, "traffic": "sweeps-ratings",
                           "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert sorted(entry["reduced"]) == ["rows", "users", "validation_rows"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in end_to_end["train_rows_per_s"]["workloads"]
    assert {m["name"] for m in metrics_of(manifest, "end_to_end", CELL, set())} == {
        "train_rows_per_s", "setup_s"}
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("name", sorted(OWN_METRICS))
def test_the_manifest_holds_the_cells_own_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    unit, better, source, layer = OWN_METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "train_rows_per_s", "workloads": [CELL]}
    assert os.path.isfile(M.reader_file(name))


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_sweeps_cells_entry(manifest, name):
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert CELL in entry["workloads"] and "glmix-ml20m.sweeps" in entry["workloads"]


@pytest.mark.parametrize("name", PINNED)
def test_the_cell_joins_no_list_an_accepted_test_pins(manifest, name):
    assert CELL not in {m["name"]: m for m in manifest["per_layer"]}[name]["workloads"]


def test_the_cell_reports_the_joined_metrics_and_five_more(manifest):
    names = {m["name"] for m in metrics_of(
        manifest, "per_layer", CELL, {"train_rows_per_s", "setup_s"})}
    assert names == set(JOINED) | set(OWN_METRICS)


def test_the_configuration_states_source_cut_and_model(manifest, config):
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    facts = config["source_facts"]
    chips = config["deployment_chips"]
    assert chips in (192, 256)
    assert config["rows"] == facts["training_ratings"] // chips // 1024 * 1024
    assert config["users"]["count"] == round(facts["users"] / chips)
    assert config["validation_rows"] == 10 * config["users"]["count"]
    assert config["songs"]["count"] == facts["songs"]  # every song, no width cut
    with open(os.path.join(HERE, "configs", "glmix-ml20m.json")) as f:
        assert config["widths"] == json.load(f)["widths"]
    assert config["task"] == "LINEAR_REGRESSION" and config["evaluator"] == "RMSE"
    assert config["coordinates"] == ["global", "user", "song", "artist"]
    assert config["optimizer"] == {"type": "AUTO", "max_iterations": 10,
                                   "rel_function_tolerance": 1e-6}
    assert config["l2_weight"] == 1.0 and config["coordinate_descent_iterations"] == 3
    assert config["feature_dtype"] == "float32" and config["mesh"] == {"data": 1, "model": 1}
    assert config["bucket_ladder"] == [8, 32, 128, 512, 2048]
    assert config["assumed"] and "sized_by" in config


def test_every_limit_stands_beside_its_readings(config):
    readings = config["limit_readings"]
    assert "_origin" in readings
    for name, limit in config["limits"].items():
        assert 0 < limit < 1.0 and name in readings, name
        assert "sound" in readings[name] and "control" in readings[name], name


def test_the_reference_imports_nothing_from_the_program():
    reference = load_module(os.path.join(HERE, "references", CONFIG + ".py"))
    source = inspect.getsource(reference)
    assert "photon_ml_tpu" not in source.replace("photon-ml", "")
    assert 'default_matmul_precision("highest")' in source


# -- the generator -----------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_data():
    found = tiny_cell()
    return found["config"], datagen_ratings.make_ratings(found["config"], 3000000501)


def test_the_generators_sizes_sum_to_the_rows(tiny_data):
    cfg, data = tiny_data
    train = data["train"]
    for name, key in datagen_ratings.ENTITIES:
        sizes = data[name + "_sizes"]
        assert sizes.sum() == cfg["rows"] and len(sizes) == cfg[key]["count"]
        # an entity's rows in the arrays are its size, whatever id the seed gave it
        assert sorted(np.bincount(train[name], minlength=len(sizes))) == sorted(sizes)
    assert data["user_sizes"].min() >= cfg["users"]["min"]
    assert data["song_sizes"].min() >= cfg["songs"]["min"]


def test_a_label_is_one_of_the_five_ratings(tiny_data):
    _cfg, data = tiny_data
    for split in ("train", "validation"):
        y = data[split]["y"]
        assert y.dtype == np.float32 and set(np.unique(y)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    assert len(set(np.unique(data["train"]["y"]))) == 5


def test_a_song_has_one_artist_and_the_head_artists_are_over_the_rung(tiny_data):
    cfg, data = tiny_data
    for split in ("train", "validation"):
        song, artist = data[split]["song"], data[split]["artist"]
        first = {}
        assert all(first.setdefault(s, a) == a for s, a in zip(song.tolist(), artist.tolist()))
    by_song = datagen_ratings.artist_of_song(cfg)
    assert len(by_song) == cfg["songs"]["count"]
    assert np.bincount(by_song, minlength=cfg["artists"]["count"]).min() >= 1
    # drawn over the ranks, not contiguous in them
    assert (np.diff(by_song) != 0).mean() > 0.5
    assert data["artist_sizes"].max() > 2048 > np.median(data["artist_sizes"])


def test_the_validation_rows_are_ten_a_user_rating_pairs_the_user_trained_on(tiny_data):
    cfg, data = tiny_data
    train, val = data["train"], data["validation"]
    assert (np.bincount(val["user"], minlength=cfg["users"]["count"]) == 10).all()
    pairs = set(zip(train["user"].tolist(), train["song"].tolist()))
    assert set(zip(val["user"].tolist(), val["song"].tolist())) <= pairs
    with pytest.raises(ValueError, match="10 a user"):
        datagen_ratings.make_ratings({**cfg, "validation_rows": 1000}, 1)


def test_a_seed_names_the_entities_and_poses_the_same_fit(tiny_data):
    cfg, data = tiny_data
    other = datagen_ratings.make_ratings(cfg, 7)
    a, b = data["train"], other["train"]
    for name in ("x_global", "x_user", "x_item", "y"):
        assert np.array_equal(a[name], b[name])
    assert not np.array_equal(a["song"], b["song"])
    # a renaming: rows that shared a song still do
    rename = dict(zip(a["song"].tolist(), b["song"].tolist()))
    assert len(set(rename.values())) == len(rename)
    assert np.array_equal(np.vectorize(rename.get)(a["song"]), b["song"])


# -- the driver --------------------------------------------------------------------


@pytest.fixture(scope="module")
def sound_run():
    """One tiny episode, what it produced, and every number ``correct`` compares."""
    found = tiny_cell()
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 3000000502,
                       jax.devices()[:1], Spans())
    produced = cell.episode()
    resolved = [s.optimizer.optimizer_type.name for s in cell.program.re_specs]
    counters = cell.counters()
    kept = cell.kept_rows()
    packed = {t: [tuple(b.features.shape) for b in ds.buckets]
              for t, ds in cell.re_datasets.items()}
    rows_per_s = cell.end_to_end([0.5, 0.5], 1.0)
    data = cell.data
    compared = cell.verify(reference, produced)
    expected = reference.fit(data, found["config"], kept, jax.devices()[:1])
    return dict(found=found, driver=driver, reference=reference, produced=produced,
                compared=compared, expected=expected, data=data, kept=kept,
                resolved=resolved, counters=counters, packed=packed,
                rows_per_s=rows_per_s)


def test_a_sound_tiny_run_is_correct(sound_run):
    from benchmark import compare

    names = [name for name, _, _ in sound_run["compared"]]
    assert names[:5] == ["loss_own_coef_rel_gap", "val_margin_own_coef_max_gap",
                         "val_rmse_own_coef_gap", "artist_ridge_own_coef_residual",
                         "best_rmse_gap"]
    assert {f"{k}_coef_rel_l2" for k in ("fe", "user", "song", "artist")} <= set(names)
    assert {f"val_rmse_sweep{k}_gap" for k in (1, 2, 3)} <= set(names)
    assert compare.judge(sound_run["compared"])
    produced = sound_run["produced"]
    assert len(produced["losses"]) == len(produced["val_rmse"]) == 3
    assert produced["losses"][0] > produced["losses"][-1]
    assert produced["best_rmse"] == min(produced["val_rmse"])


def test_the_program_resolved_auto_and_the_driver_hands_over_the_buckets(sound_run):
    assert sound_run["resolved"] == ["NEWTON"] * 3
    shapes = [shape for t in ("user", "song", "artist") for shape in sound_run["packed"][t]]
    assert sound_run["counters"]["newton_buckets"] == [s + (4,) for s in shapes]
    assert {s[1] for s in shapes} <= {8, 32, 128, 512, 2048} and {s[2] for s in shapes} == {16}
    # the packer's cap selects in every coordinate: an entity keeps its rows up
    # to the top rung, and the reference is handed exactly those
    kept, data = sound_run["kept"], sound_run["data"]
    for t in ("user", "song", "artist"):
        assert kept[t].sum() == np.minimum(data[t + "_sizes"], 2048).sum() < len(kept[t])
    rows = sound_run["found"]["config"]["rows"]
    assert sound_run["rows_per_s"] == {"train_rows_per_s": (rows * 3 * 2 / 1.0, "rows/s")}


def test_a_program_without_the_newton_counts_is_refused_at_once(monkeypatch):
    from photon_ml_tpu.optim import common

    found = find_cell(load_manifest(), CELL)  # the FULL size: nothing may be made
    driver = load_module(found["driver"])
    monkeypatch.setattr(common, "SOLVER_COUNT_NAMES", common.SOLVER_COUNT_NAMES[:10])
    with pytest.raises(SystemExit, match="newton_lockstep_rounds"):
        driver.Cell(found["config"], found["traffic"], 1, jax.devices()[:1], Spans())


def _judge_with(sound_run, produced):
    from benchmark.compare import own_coefficient_comparisons

    limits = sound_run["found"]["config"]["limits"]
    evaluated = sound_run["reference"].evaluate(
        sound_run["data"], produced, sound_run["kept"], 1.0)
    return own_coefficient_comparisons(produced, evaluated, limits) + [
        ("val_rmse_own_coef_gap", abs(produced["val_rmse"][-1] - evaluated["val_rmse"]),
         limits["val_rmse_own_coef_gap"]),
        ("artist_ridge_own_coef_residual", evaluated["last_block_residual"],
         limits["artist_ridge_own_coef_residual"]),
    ] + sound_run["driver"].ratings_comparisons(produced, sound_run["expected"], limits)


def _failed(comparisons) -> set:
    return {name for name, value, limit in comparisons
            if not (np.isfinite(value) and value <= limit)}


def _produced_with_margins(sound_run):
    produced = copy.deepcopy(sound_run["produced"])
    produced["val_margin"] = sound_run["reference"].evaluate(
        sound_run["data"], produced)["val_margin"].astype(np.float32)
    return produced


def test_the_sound_run_judged_again_fails_nothing(sound_run):
    assert _failed(_judge_with(sound_run, _produced_with_margins(sound_run))) == set()


@pytest.mark.parametrize("table", ["user", "song", "artist"])
def test_a_coordinate_returned_at_its_start_is_caught(sound_run, table):
    produced = _produced_with_margins(sound_run)
    produced[table] = np.zeros_like(produced[table])
    failed = _failed(_judge_with(sound_run, produced))
    assert {f"{table}_coef_rel_l2", f"{table}_norm_rel_gap",
            "loss_own_coef_rel_gap", "val_margin_own_coef_max_gap"} <= failed


def test_a_dropped_sweep_and_a_misreported_rmse_are_caught(sound_run):
    produced = _produced_with_margins(sound_run)
    produced["losses"] = produced["losses"][:2]
    produced["val_rmse"] = [v + 1e-3 for v in produced["val_rmse"][:2]]
    failed = _failed(_judge_with(sound_run, produced))
    assert {"sweeps_missing", "val_rmse_sweep1_gap", "val_rmse_own_coef_gap"} <= failed


def test_a_hessian_contracted_from_bfloat16_operands_is_caught_by_the_ridge_residual(
        sound_run, monkeypatch):
    """The second control's arithmetic on the CPU: the lanes' ``X'DX`` as a TPU
    at default precision makes it (float32 operands rounded to bfloat16, sums in
    float32). Newton corrects itself in its next round, so the fit lands within
    every limit of kind (b); what it cannot reach is the last coordinate's own
    ridge systems, and that number, and no other, fails."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops import objective

    monkeypatch.setattr(objective, "_weighted_gram", lambda x, d2: jnp.matmul(
        x.T.astype(jnp.bfloat16), (d2[:, None] * x).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))
    found = tiny_cell()
    cell = sound_run["driver"].Cell(found["config"], found["traffic"], 3000000503,
                                    jax.devices()[:1], Spans())
    compared = cell.verify(sound_run["reference"], cell.episode())
    assert _failed(compared) == {"artist_ridge_own_coef_residual"}
    residual = dict((name, value) for name, value, _ in compared)[
        "artist_ridge_own_coef_residual"]
    sound = dict((name, value) for name, value, _ in sound_run["compared"])[
        "artist_ridge_own_coef_residual"]
    assert residual > 30 * sound


def test_the_references_programs_take_the_rows_as_arguments(sound_run):
    """A jitted function that closes over the feature block compiles it in as a
    constant: at the cell's size 3.73 GB of one, copied while the program is
    lowered, which ended the first chip run at the machine's 40 GiB of host
    memory (PERF.md 6, PR 50). JAX warns of constants over a threshold."""
    import warnings

    found = sound_run["found"]
    before = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 100_000)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were captured.*")
            # a fresh module: its jitted functions are traced and lowered anew
            reference = load_module(found["reference"])
            reference.fit(sound_run["data"], found["config"], sound_run["kept"],
                          jax.devices()[:1])
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", before)


def test_the_references_evaluate_is_float64_on_the_generators_rows(sound_run):
    data, produced = sound_run["data"], sound_run["produced"]
    got = sound_run["reference"].evaluate(data, produced)
    rows = slice(0, 500)
    split = data["validation"]
    margin = split["x_global"][rows].astype(np.float64) @ produced["fe"].astype(np.float64)
    for name, block in (("user", "x_user"), ("song", "x_item"), ("artist", "x_item")):
        margin += np.einsum("rd,rd->r", split[block][rows].astype(np.float64),
                            produced[name].astype(np.float64)[split[name][rows]])
    np.testing.assert_allclose(got["val_margin"][rows], margin, rtol=1e-12)
    diff = got["val_margin"] - split["y"]
    assert got["val_rmse"] == pytest.approx(np.sqrt(np.mean(diff * diff)), rel=1e-12)


# -- the readers -------------------------------------------------------------------


@pytest.fixture
def registry(monkeypatch):
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    return registry


def test_the_counter_readers_give_the_known_values(registry):
    registry.counter("train/sweeps").inc(12)  # a warm fit and three timed ones
    registry.counter("solver/newton_lockstep_rounds").inc(336)
    registry.counter("solver/newton_lane_rounds").inc(4000)
    registry.counter("solver/newton_rejected_rounds").inc(900)
    assert layer_metric_reader("sweeps_newton_lockstep_rounds")({}) == 28.0
    assert layer_metric_reader("sweeps_newton_rejected_share_pct")({}) == 22.5


@pytest.mark.parametrize("sweeps", [None, 0, 12])
@pytest.mark.parametrize("name", ["sweeps_newton_lockstep_rounds",
                                  "sweeps_newton_rejected_share_pct"])
def test_a_program_without_the_counters_gives_nothing(registry, name, sweeps):
    """The parent counts its sweeps and no round; a process that has not
    trained yet counts neither. Nothing is returned and nothing raises."""
    if sweeps is not None:
        registry.counter("train/sweeps").inc(sweeps)
    assert layer_metric_reader(name)({}) is None


STEP = "jit(_step_impl)/"
LANES = STEP + "re/song/solve/vmap()/while"
WHILE = "%while.4 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.2), body=%b"
RECORD = ({
    "while.4": ("(s32[],f32[8])while", LANES),
    "convolution.1": ("f32[8]convolution", LANES + "/body/newton/hessian/dot_general"),
    "fusion.2": ("f32[8]fusion", LANES + "/body/newton/hessian/mul"),
    "while.7": ("f32[8]while", LANES + "/body/newton/solve/while"),
    "fusion.3": ("f32[8]fusion", LANES + "/body/newton/shrink/vmap(glm/margins)/dot_general"),
    "fusion.4": ("f32[8]fusion", LANES + "/body/newton/gradient/cond/branch_1_fun/mul"),
    "fusion.5": ("f32[8]fusion", LANES + "/body/select_n"),
    "fusion.6": ("f32[8]fusion", STEP + "re/song/gather/gather"),
    "fusion.8": ("f32[8]fusion", STEP + "re/artist/solve/vmap()/newton/gradient/mul"),
    "fusion.9": ("f32[16]fusion", STEP + "fe/solve/while/body/lbfgs/line_search/mul"),
}, frozenset({"while.4"}))


def op(name, shape="f32[8]{0}", opcode="fusion"):
    return f"%{name} = {shape} {opcode}({shape} %p)"


def one_step(at=0.0):
    """The events of one step, 1,000 ns long, and its module event."""
    return [
        (op("fusion.6"), at + 0, 100),                                  # gather
        (op("fusion.8"), at + 100, 50),       # the artists' first value and gradient
        (WHILE, at + 200, 600),                                         # the songs' loop ...
        (op("fusion.2"), at + 200, 40),                                 # ... d2 * x
        (op("convolution.1", opcode="convolution"), at + 240, 160),     # ... the contraction
        (op("while.7", opcode="while"), at + 400, 100),                 # ... the elimination
        (op("copy.11", opcode="copy"), at + 420, 20),  # ...... a copy in it, no metadata
        (op("fusion.3"), at + 500, 150),                                # ... the candidates
        (op("fusion.4"), at + 650, 100),                                # ... the gradient
        (op("fusion.5"), at + 750, 50),                                 # ... a select, no scope
        (op("fusion.9", "f32[16]{0}"), at + 800, 100),                  # the fixed effect
        # 900 to 1,000: idle inside the step
    ], [(f"jit__step_impl({int(at)})", at, 1000)]


def trace_of(*devices, window=(0.0, 10000.0)):
    return {"devices": {k: {"ops": ops, "modules": modules}
                        for k, (ops, modules) in enumerate(devices)},
            "host": [("bench:window", window[0], window[1] - window[0])]}


def test_the_mark_names_the_coordinate_and_the_phase_and_leaves_the_rest():
    marked = newton_scopes.marked(RECORD[0])
    assert marked["convolution.1"][1].startswith("re/song~newton.hessian/" + STEP)
    assert marked["fusion.8"][1].startswith("re/artist~newton.gradient/")
    assert marked["fusion.3"][1].startswith("re/song~newton.shrink/")
    for name in ("while.4", "fusion.5", "fusion.6", "fusion.9"):
        assert marked[name] == RECORD[0][name]
    # the signature is the record's: the partition still holds events to it
    assert all(marked[k][0] == v[0] for k, v in RECORD[0].items())
    # a scope is matched as a scope, never inside another word or as the primitive
    odd = {"a": ("f32[8]fusion", STEP + "re/song/solve/my_newton/hessian/mul"),
           "b": ("f32[8]fusion", STEP + "re/song/solve/newton/hessians/mul"),
           "c": ("f32[8]fusion", STEP + "re/song/solve/newton/hessian"),
           "d": ("f32[8]fusion", None)}
    assert newton_scopes.marked(odd) == odd


def test_the_seconds_go_to_their_phase_and_the_step_keeps_its_categories():
    part = newton_scopes.seconds_by_phase(
        trace_of(one_step(1000.0)), RECORD, parse_instruction)
    ns = 1e-9
    assert part["seconds"] == pytest.approx({
        "hessian": 200 * ns, "solve": 100 * ns,  # the loop with the copy inside it
        "shrink": 150 * ns, "gradient": 150 * ns})
    assert part["by_coordinate"] == pytest.approx({
        ("re/song", "hessian"): 200 * ns, ("re/song", "solve"): 100 * ns,
        ("re/song", "shrink"): 150 * ns, ("re/song", "gradient"): 100 * ns,
        ("re/artist", "gradient"): 50 * ns})
    # what step_scopes files, with or without the mark: the phases lie in lane_update
    plain = step_scopes.partition(trace_of(one_step(1000.0)), RECORD, parse_instruction)
    assert part["categories"] == pytest.approx(plain["seconds"])
    assert part["categories"]["lane_update"] == pytest.approx(650 * ns)
    assert part["busy_s"] == pytest.approx(plain["busy_s"]) == pytest.approx(850 * ns)
    assert newton_scopes.share(part) == pytest.approx(100 * 600 / 850)
    assert newton_scopes.share(part, "hessian") == pytest.approx(100 * 200 / 850)


@pytest.mark.parametrize("what", ["no newton scope", "another program", "no step"])
def test_the_seconds_are_nothing_where_the_record_does_not_fit(what):
    instructions, loops = RECORD
    trace = trace_of(one_step(1000.0))
    if what == "no newton scope":  # a program whose lanes L-BFGS runs
        instructions = {k: (s, n.replace("newton/", "lbfgs/")) for k, (s, n) in
                        instructions.items()}
    elif what == "another program":  # the same names, other instructions
        instructions = {**instructions, "fusion.3": ("f32[99]fusion", instructions["fusion.3"][1])}
    else:
        trace = trace_of((one_step(1000.0)[0], [("jit_other(1)", 1000.0, 1000)]))
    assert newton_scopes.seconds_by_phase(trace, (instructions, loops), parse_instruction) is None
    assert newton_scopes.share(None) is None and newton_scopes.share(None, "hessian") is None


BUCKETS = [(100, 8, 16, 4), (10, 2048, 16, 4)]


@pytest.fixture
def traced(registry, monkeypatch):
    """A context as benchmark/run.py builds it, the partition stubbed: two
    sweeps in the window of six in the process, 2 rounds a bucket solve."""
    part = newton_scopes.seconds_by_phase(
        trace_of(one_step(1000.0)), RECORD, parse_instruction)
    monkeypatch.setattr(newton_scopes, "of_this_run", lambda: part)
    registry.counter("train/sweeps").inc(6)
    registry.counter("solver/newton_lockstep_rounds").inc(6 * 2 * len(BUCKETS))
    spans = [("train/sweep", 100.0 + 1000 * k, 900.0, 0, {}) for k in range(2)]
    return {"counters": {"newton_buckets": BUCKETS}, "device": {"kind": "TPU v5 lite"},
            "program_spans": {"window": [(0.0, 10000.0)], "spans": spans, "devices": {}}}, part


def test_the_three_trace_readers_give_the_known_values(traced):
    ctx, part = traced
    assert layer_metric_reader("step_newton_time_share_pct")(ctx) == pytest.approx(
        100 * 600 / 850)
    assert layer_metric_reader("step_newton_hessian_time_share_pct")(ctx) == pytest.approx(
        100 * 200 / 850)
    a_round = sum(e * cap * d * 4 + e * d * d * 4 for e, cap, d, _ in BUCKETS)
    least = 2 * 2 * a_round / 819e9  # two sweeps in the window, two rounds a bucket solve
    assert layer_metric_reader("sweeps_newton_hessian_roofline")(ctx) == pytest.approx(
        100 * least / part["seconds"]["hessian"])


@pytest.mark.parametrize("missing", ["partition", "buckets", "counter", "sweeps in the window"])
def test_the_roofline_reader_gives_nothing_where_a_part_is_missing(
        traced, registry, monkeypatch, missing):
    ctx, _part = traced
    if missing == "partition":
        monkeypatch.setattr(newton_scopes, "of_this_run", lambda: None)
    elif missing == "buckets":
        ctx["counters"] = {}
    elif missing == "counter":
        monkeypatch.setattr(registry_module, "_DEFAULT", MetricsRegistry())
    else:
        ctx["program_spans"]["spans"] = []
    assert layer_metric_reader("sweeps_newton_hessian_roofline")(ctx) is None


def test_of_this_run_is_nothing_on_the_cpu():
    assert newton_scopes.of_this_run() is None
    for name in ("step_newton_time_share_pct", "step_newton_hessian_time_share_pct"):
        assert layer_metric_reader(name)({}) is None


def test_the_roofline_counts_one_read_of_a_block_and_one_write_by_hand():
    # 3 lanes of 8 rows x 16 float32 features: 3 * 8 * 16 * 4 in, 3 * 16 * 16 * 4 out
    assert roofline_newton.hessian_bytes(3, 8, 16, 4) == 1536 + 3072
    assert roofline_newton.hessian_bytes(3, 8, 16, 2) == 768 + 3072  # bfloat16 in, float32 out
    # one sweep, two rounds a bucket solve, one bucket: at the peak in the least time -> 100
    least = 2 * 4608 / 819e9
    assert roofline_newton.newton_hessian_roofline_pct(
        2.0, [(3, 8, 16, 4)], 1, least, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline_newton.newton_hessian_roofline_pct(
        2.0, [(3, 8, 16, 4)], 3, 6 * least, "TPU v5 lite") == pytest.approx(50.0)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline_newton.newton_hessian_roofline_pct(2.0, [(3, 8, 16, 4)], 1, 1.0, "cpu")
