"""The four-chip cell ``glmix-ml20m-x4.sweeps`` at tiny size on four virtual
devices: a whole run through ``run_cell`` comes out correct with every
array shared by the four, the bfloat16 control does not, the two readers the
cell brings read what they say, and the manifest that holds it passes every
test a manifest has to pass."""

import copy
import json
import os

import jax
import pytest

from benchmark import compare, manifest as M, run, trace_reduce
from benchmark.manifest import find_cell, layer_metric_reader, load_manifest, load_module
from benchmark.spans import Spans
from bm_helpers import MANIFEST_ASSERTIONS

WORKLOAD = "glmix-ml20m-x4.sweeps"
HERE = os.path.dirname(os.path.abspath(__file__))
#: the one-chip cell's tiny size (bm_helpers.TINY), with the whole data
#: set's maxima; rows divide by four, as the cell's do
TINY = dict(rows=40000, validation_rows=4000,
            users=dict(count=300, min=20, max=9254, a=1.0),
            items=dict(count=250, min=1, max=67310, a=1.8))
#: limits for the tiny size on four virtual CPU devices, set as the chip's are:
#: above what the float32 run gives here, below the bfloat16 control's where
#: the control separates (one reading each: every seed poses the same fit):
#: loss at own coefficients f32 5.1e-8, bf16 2.5e-5; validation margins at own
#: coefficients f32 4.0e-7, bf16 6.3e-3; user_coef f32 2.8e-3 (the same data on
#: ONE device: 6.9e-4: where ten L-BFGS iterations stop turns on the order of
#: the float32 sums), bf16 1.2e-2; item_coef f32 2.0e-3 (one device 5.5e-4),
#: bf16 1.3e-2; loss f32 8.5e-6, bf16 1.3e-4; val_auc f32 1.7e-5, bf16 4.5e-5;
#: fe_coef f32 2.5e-4, bf16 1.4e-3; norm f32 1.1e-4, bf16 1.5e-3.
TINY_LIMITS = {"loss_own_coef_rel_gap": 4e-7, "val_margin_own_coef_max_gap": 3e-5,
               "loss_rel_gap": 4e-5, "val_auc_gap": 3.5e-5, "fe_coef_rel_l2": 8e-4,
               "user_coef_rel_l2": 6e-3, "item_coef_rel_l2": 5e-3,
               "norm_rel_gap": 4e-4}


def tiny_x4(**overrides) -> dict:
    found = find_cell(load_manifest(), WORKLOAD)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY)
    found["config"]["limits"] = copy.deepcopy(TINY_LIMITS)
    found["config"].update(overrides)
    return found


@pytest.fixture(scope="module")
def four_devices():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four (virtual) devices")
    return devices[:4]


def test_the_cell_asks_for_the_mesh_it_names():
    found = find_cell(load_manifest(), WORKLOAD)
    assert found["cell"]["chips"] == 4
    assert found["config"]["mesh"] == {"data": 4, "model": 1}
    assert found["config"]["rows"] == 4 * 4999168  # four one-chip shares
    assert found["config"]["rows"] % (4 * 1024) == 0  # each chip's X in whole kernel tiles
    assert found["traffic"]["kind"] == "game_sweeps_x4"
    one = find_cell(load_manifest(), "glmix-ml20m.sweeps")["config"]
    same = ("task", "widths", "bucket_ladder", "coordinate_descent_iterations",
            "optimizer", "l2_weight", "feature_dtype", "data_seed")
    assert {k: found["config"][k] for k in same} == {k: one[k] for k in same}
    # the two numbers that hold the precision are not loosened
    for name in ("val_margin_own_coef_max_gap", "loss_own_coef_rel_gap"):
        assert found["config"]["limits"][name] <= one["limits"][name]


def test_a_whole_run_on_four_devices_is_correct_and_shared(four_devices):
    from photon_ml_tpu.telemetry.registry import default_registry

    found = tiny_x4()
    line = run.run_cell(found, load_manifest(), seed=3000000029, seconds=0.0,
                        trace=False, devices=four_devices)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 3 and line["device"]["count"] == 4
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert len(line["compared"]) == 14
    # the fit went over the mesh: a quarter of every array on each device,
    # and the reader of the new metric says so
    assert layer_metric_reader("mesh_max_shard_share_pct")({}) == 25.0
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["mesh/placed_bytes/max_device"] < 1.01 * gauges[
        "mesh/placed_bytes/min_device"]


def test_the_control_comes_out_not_correct_on_four_devices(four_devices):
    """The program's own bfloat16 feature path, through the four-device
    driver: it fails the validation margins at its own coefficients."""
    found = tiny_x4(feature_dtype="bfloat16")
    driver, reference = load_module(found["driver"]), load_module(found["reference"])
    cell = driver.Cell(found["config"], found["traffic"], 21, four_devices, Spans())
    compared = cell.verify(reference, cell.episode())
    assert not compare.judge(compared), compared
    by_name = {n: (v, lim) for n, v, lim in compared}
    value, limit = by_name["val_margin_own_coef_max_gap"]
    assert value > 100 * limit, (value, limit)


def test_the_driver_is_the_one_chip_drivers_cell_with_another_set_up():
    found = find_cell(load_manifest(), WORKLOAD)
    driver = load_module(found["driver"])
    base = driver.base.Cell
    assert issubclass(driver.Cell, base)
    for name in ("episode", "end_to_end", "kept_rows", "verify", "validation_margins"):
        assert getattr(driver.Cell, name) is getattr(base, name), name


def test_collective_share_reads_the_instructions_by_name():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)
    trace = {"devices": {int(k): {line: [tuple(e) for e in events]
                                  for line, events in v.items()}
                         for k, v in raw["devices"].items()},
             "host": [tuple(e) for e in raw["host"]]}
    reduced = trace_reduce.reduce_trace(trace)
    read = layer_metric_reader("collective_time_share_pct")
    collective = dict(reduced["device_ops"])["all-reduce"]
    assert collective > 0
    assert read({"trace": reduced}) == pytest.approx(
        100 * collective / reduced["busy_s"])
    ops = [("fusion", 3.0), ("all-reduce", 0.5), ("all-gather-start", 0.25),
           ("all-gather-done", 0.25), ("collective-permute-done", 0.5),
           ("reduce-scatter", 0.25), ("all-to-all", 0.25),
           ("all-reduce-scatter-fusion", 9.0), ("_fused_padded", 1.0)]
    assert read({"trace": {"busy_s": 8.0, "device_ops": ops}}) == pytest.approx(25.0)
    # one chip: no such instruction, a share of nothing
    assert read({"trace": {"busy_s": 8.0, "device_ops": ops[:1]}}) == 0.0
    assert read({}) is None  # no trace: the line leaves the metric out


def test_mesh_share_reads_nothing_without_the_gauges(monkeypatch):
    from photon_ml_tpu.telemetry import registry

    read = layer_metric_reader("mesh_max_shard_share_pct")
    monkeypatch.setattr(registry, "_DEFAULT", registry.MetricsRegistry())
    assert read({}) is None
    registry.default_registry().gauge("mesh/entity_arrays/max_shard_fraction").set(1.0)
    registry.default_registry().gauge("mesh/sample_arrays/max_shard_fraction").set(0.25)
    assert read({}) == 100.0  # one array whole on one chip shows


@pytest.mark.parametrize("check", MANIFEST_ASSERTIONS, ids=lambda c: c.__name__)
def test_the_manifest_with_the_cell_passes_every_manifest_test(check):
    check(load_manifest(), M.ROOT)


def test_the_manifest_takes_the_cell_by_addition():
    manifest = load_manifest()
    assert M.check_manifest(manifest) == []
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert [w["name"] for w in manifest["workloads"]][-1] == WORKLOAD
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    reported = {m["name"] for m in M.metrics_of(manifest, "end_to_end", WORKLOAD, set())}
    assert reported == {"train_rows_per_s", "setup_s"}
    layers = [m["name"] for m in M.metrics_of(manifest, "per_layer", WORKLOAD, reported)]
    assert layers[-2:] == ["collective_time_share_pct", "mesh_max_shard_share_pct"]
    # every per-layer metric the one-chip GLMix cell reports is reported here
    one = [m["name"] for m in M.metrics_of(
        manifest, "per_layer", "glmix-ml20m.sweeps", reported)]
    assert layers[:-2] == one
    for m in manifest["per_layer"]:
        if WORKLOAD in m.get("workloads", ()):
            assert m["workloads"][-1] == WORKLOAD or m["workloads"] == [WORKLOAD]
