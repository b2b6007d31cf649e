"""``entry_scores_time_share_pct``: the share of busy of the program that
fills a fit's empty carry (``module:jit__entry_scores_impl``, PR 48), on
recorded lists: what it reads, what it reads as nothing, that the step's
partition does not see that module, and the manifest's entry."""

import pytest

from benchmark import manifest as M, step_scopes
from benchmark.manifest import layer_metric_reader, load_manifest
from benchmark.trace_reduce import reduce_trace
from photon_ml_tpu.telemetry.program_ledger import parse_instruction

NAME = "entry_scores_time_share_pct"
SWEEPS_CELLS = ["glmix-ml20m.sweeps", "glmix-ml20m-x4.sweeps", "game-ml20m-mf.sweeps"]
STEP = "jit(_step_impl)/"
RECORD = ({"fusion.5": ("f32[16]fusion", STEP + "score/user/dot_general"),
           "fusion.2": ("f32[16]fusion", STEP + "re/user/gather/gather")},
          frozenset())


def op(name):
    return f"%{name} = f32[16]{{0}} fusion(f32[16]{{0}} %p)"


def a_fit(at=0.0, entry=True, late=0.0):
    """One device's events of a fit of two sweeps: the entry program (200
    ns, scoring under the names the step's scorings have), then two steps of
    400 ns, each a gather of 300 and a scoring of 100."""
    ops, modules = [], []
    if entry:
        ops.append((op("fusion.5"), at + late, 200))
        modules.append((f"jit__entry_scores_impl({int(at)})", at + late, 200))
    for start in (at + 300, at + 800):
        ops += [(op("fusion.2"), start + late, 300), (op("fusion.5"), start + 300 + late, 100)]
        modules.append((f"jit__step_impl({int(start)})", start + late, 400))
    return ops, modules


def trace_of(*devices, window=(0.0, 3000.0)):
    return {"devices": {k: {"ops": ops, "modules": modules}
                        for k, (ops, modules) in enumerate(devices)},
            "host": [("bench:window", window[0], window[1] - window[0])]}


def context(*devices, window=(0.0, 3000.0)):
    return {"trace": reduce_trace(trace_of(*devices, window=window))}


@pytest.mark.parametrize("devices, expected", [
    # 200 of 200 + 2 * 400 busy
    ((a_fit(),), 20.0),
    # averaged over the devices, as busy is: the second runs 10 ns behind
    ((a_fit(), a_fit(late=10.0)), 20.0),
    # two fits in the window: two entry programs
    ((tuple(a + b for a, b in zip(a_fit(), a_fit(at=1500.0))),), 20.0),
], ids=["one-device", "two-devices", "two-fits"])
def test_it_reads_the_entry_programs_share_of_busy(devices, expected):
    assert layer_metric_reader(NAME)(context(*devices)) == pytest.approx(expected)


def test_what_the_window_cuts_off_is_not_counted():
    """The entry program straddles the window's start: half of it is in."""
    read = layer_metric_reader(NAME)(context(a_fit(), window=(100.0, 3000.0)))
    assert read == pytest.approx(100.0 * 100 / 900)


@pytest.mark.parametrize("ctx", [
    context(a_fit(entry=False)),  # a parent commit: scoring is inside the step
    {},                           # an untraced run
    {"trace": None},
    {"trace": {"busy_s": 0.0, "device_modules": [("jit__entry_scores_impl", 1.0)]}},
    {"trace": {"busy_s": 1.0}},   # a reduction without modules
], ids=["no-entry-program", "no-trace", "trace-none", "nothing-busy", "no-modules"])
def test_it_reads_nothing_and_does_not_raise(ctx):
    assert layer_metric_reader(NAME)(ctx) is None


def test_the_steps_partition_does_not_see_the_entry_program():
    """Why the reader exists: ``step_scopes`` keeps to the ``jit__step_impl``
    events, so the entry program's scoring, the same instruction names and
    all, is in busy and in no ``step_*`` share. Scoring is the sum."""
    part = step_scopes.partition(trace_of(a_fit()), RECORD, parse_instruction)
    assert part["busy_s"] == pytest.approx(1000e-9)
    assert part["step_s"] == pytest.approx(800e-9)
    assert step_scopes.share(part, "score_scatter") == pytest.approx(20.0)
    assert layer_metric_reader(NAME)(context(a_fit())) + step_scopes.share(
        part, "score_scatter") == pytest.approx(40.0)


def test_the_manifest_holds_the_entry_for_the_three_sweeps_cells():
    manifest = load_manifest()
    assert M.check_manifest(manifest) == []
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "step",
                     "moves": "train_rows_per_s", "workloads": SWEEPS_CELLS}
    # beside the share it completes, wherever that one is listed
    beside = next(m for m in manifest["per_layer"]
                  if m["name"] == "step_score_scatter_time_share_pct")
    assert set(beside["workloads"]) <= set(entry["workloads"])
    assert beside["layer"] == entry["layer"] and beside["moves"] == entry["moves"]


@pytest.mark.parametrize("cell", SWEEPS_CELLS)
def test_each_sweeps_cell_reports_it_and_no_other_cell_does(cell):
    manifest = load_manifest()
    reported = {m["name"] for m in M.metrics_of(manifest, "end_to_end", cell, set())}
    assert NAME in {m["name"] for m in M.metrics_of(manifest, "per_layer", cell, reported)}
    others = [w["name"] for w in manifest["workloads"] if w["name"] not in SWEEPS_CELLS]
    assert others and all(
        NAME not in {m["name"] for m in M.metrics_of(
            manifest, "per_layer", other, {"fit_s", "setup_s"})} for other in others)
