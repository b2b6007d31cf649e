"""The readers of the lanes' accounts (PR 52): each gives its ratio from the
program's counters, nothing where a counter is absent (a parent commit) or a
denominator is zero; their five entries list the cells they were asked for
and leave every other cell's per-layer set as it was."""

import os

import pytest

from benchmark.manifest import (
    check_manifest,
    layer_metric_reader,
    load_manifest,
    metrics_of,
    reader_file,
)
from photon_ml_tpu.telemetry import registry as registry_module
from photon_ml_tpu.telemetry.registry import MetricsRegistry

THREE = ["glmix-ml20m.sweeps", "glmix-ml20m-x4.sweeps", "game-ml20m-mf.sweeps"]
#: entry -> (unit, better, cells, numerator counter, denominator counter, scale)
ENTRIES = {
    "sweeps_re_lockstep_iterations": (
        "iters", "lower", THREE, "solver/lockstep_iterations", "train/sweeps", 1.0),
    "sweeps_re_lane_occupancy_pct": (
        "%", "higher", THREE, "solver/row_trials_wanted", "solver/row_trials_paid", 100.0),
    "sweeps_re_floor_exit_share_pct": (
        "%", "lower", THREE, "solver/floor_exits", "solver/line_searches", 100.0),
    "sweeps_re_lanes_at_cap_share_pct": (
        "%", "lower", THREE, "solver/lanes_max_iterations", "solver/lane_solves", 100.0),
    "sweeps_mf_lane_occupancy_pct": (
        "%", "higher", ["game-ml20m-mf.sweeps"], "solver/mf_row_trials_wanted",
        "solver/mf_row_trials_paid", 100.0),
}
PINNED = ("game-ymusic-r2.sweeps", "logistic-epsilon-enet.grid",
          "logistic-kdda-sparse.path")


@pytest.fixture
def registry(monkeypatch):
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    return registry


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_gives_the_ratio_of_its_two_counters(registry, name, capsys):
    *_, numerator, denominator, scale = ENTRIES[name]
    # a sweep's rows paid pass int32: the counters hold Python integers
    registry.counter(denominator).inc(12 * 2**31)
    registry.counter(numerator).inc(3 * 2**31)
    assert layer_metric_reader(name)({}) == pytest.approx(scale * 0.25, rel=1e-12)
    assert "lanes" not in capsys.readouterr().out  # no coordinate: no line


@pytest.mark.parametrize("filled", ["neither", "denominator", "numerator",
                                    "zero denominator"])
@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_gives_nothing_without_its_counters(registry, name, filled):
    """The parent keeps the sweeps, the searches and the floor exits and none
    of the rest; a process that has not trained keeps nothing. Nothing is
    returned and nothing raises."""
    *_, numerator, denominator, _scale = ENTRIES[name]
    if filled == "denominator":
        registry.counter(denominator).inc(7)
    elif filled == "numerator":
        registry.counter(numerator).inc(7)
    elif filled == "zero denominator":
        registry.counter(numerator).inc(0)
        registry.counter(denominator).inc(0)
    assert layer_metric_reader(name)({}) is None


def test_the_occupancys_reader_prints_every_coordinates_four_a_sweep(registry, capsys):
    registry.counter("train/sweeps").inc(4)
    for scope, paid, wanted in (("re/user", 4000, 1000), ("re/item", 800, 600),
                                ("mf/mf/row", 100, 10)):
        registry.counter(f"solver/{scope}/lockstep_trials").inc(40)
        registry.counter(f"solver/{scope}/lockstep_iterations").inc(20)
        registry.counter(f"solver/{scope}/row_trials_paid").inc(paid)
        registry.counter(f"solver/{scope}/row_trials_wanted").inc(wanted)
    registry.counter("solver/row_trials_paid").inc(4800)
    registry.counter("solver/row_trials_wanted").inc(1600)
    registry.counter("solver/lockstep_iterations").inc(36)
    registry.counter("solver/lane_solves").inc(80)
    registry.counter("solver/lanes_function_tolerance").inc(72)
    registry.counter("solver/lanes_search_failed").inc(8)
    registry.counter("solver/mf_lockstep_iterations").inc(20)
    registry.counter("solver/mf_lane_solves").inc(8)
    registry.counter("solver/mf_lanes_max_iterations").inc(8)
    registry.counter("solver/mf_row_trials_paid").inc(100)
    registry.counter("solver/mf_row_trials_wanted").inc(10)
    assert layer_metric_reader("sweeps_re_lane_occupancy_pct")({}) == pytest.approx(
        100.0 / 3)
    line = capsys.readouterr().out.strip()
    assert line.startswith("lanes (a sweep): ") and "\n" not in line
    by_name = {" ".join(part.split(" ", 2)[:2]) if part.startswith("family ")
               else part.split(" ", 1)[0]: part for part in line[17:].split(" | ")}
    assert list(by_name) == ["mf/mf/row", "re/item", "re/user", "family re_",
                             "family mf_"]
    assert by_name["re/user"] == (
        "re/user lockstep_trials=10 lockstep_iterations=5 row_trials_paid=1000 "
        "row_trials_wanted=250 occupancy=25.00%")
    assert by_name["re/item"].endswith("occupancy=75.00%")
    assert by_name["mf/mf/row"].endswith("occupancy=10.00%")
    assert by_name["family re_"] == (
        "family re_ lockstep_iterations=9 lane_solves=20 lanes_max_iterations=0 "
        "lanes_function_tolerance=18 lanes_gradient_tolerance=0 "
        "lanes_search_failed=2 occupancy=33.33%")
    assert by_name["family mf_"] == (
        "family mf_ lockstep_iterations=5 lane_solves=2 lanes_max_iterations=2 "
        "lanes_function_tolerance=0 lanes_gradient_tolerance=0 "
        "lanes_search_failed=0 occupancy=10.00%")


def test_the_five_entries_are_as_asked_and_the_manifest_passes():
    manifest = load_manifest()
    assert check_manifest(manifest) == []
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(ENTRIES)
    for entry in manifest["per_layer"][-5:]:
        unit, better, cells, *_ = ENTRIES[entry["name"]]
        assert entry == {
            "name": entry["name"], "unit": unit, "better": better,
            "source": "program_counter", "layer": "solver",
            "moves": "train_rows_per_s", "workloads": cells}
        assert os.path.isfile(reader_file(entry["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in load_manifest()["workloads"]])
def test_a_cell_reports_what_it_did_but_for_the_entries_that_list_it(cell):
    """No new entry goes without a ``workloads`` list, so a cell that none
    lists (the three whose sets accepted tests hold by equality among them)
    reports exactly the per-layer metrics it reported before."""
    manifest = load_manifest()
    before = dict(manifest, per_layer=manifest["per_layer"][:-5])
    reported = {m["name"] for m in metrics_of(manifest, "end_to_end", cell, set())}
    names = [m["name"] for m in metrics_of(manifest, "per_layer", cell, reported)]
    names_before = [m["name"] for m in metrics_of(before, "per_layer", cell, reported)]
    added = [name for name, (_u, _b, cells, *_rest) in ENTRIES.items() if cell in cells]
    assert names == names_before + added
    if cell in PINNED:
        assert added == [] and not set(names) & set(ENTRIES)
