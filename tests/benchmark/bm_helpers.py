"""Tiny-size configurations and loaders shared by the benchmark's tests."""

from __future__ import annotations

import copy

import json
import os

from benchmark import manifest as M
from benchmark.manifest import find_cell, load_manifest, load_module

TINY = {
    "glmix-ml20m.sweeps": dict(
        rows=40000, validation_rows=4000,
        users=dict(count=300, min=20, max=9254, a=1.0),
        items=dict(count=250, min=1, max=16828, a=1.8)),
    "logistic-epsilon.path": dict(
        rows=4000, validation_rows=1000, features=64, latent_factors=4),
}
#: limits for the tiny size on the CPU, set as the chip's are (PERF.md 2):
#: above the gap the float32 run gives here, below the bfloat16 control's
#: (every seed poses the same fit, so one reading each; 40000 rows): loss at
#: own coefficients f32 4.2e-8, bf16 1.4e-5; validation margins at own
#: coefficients f32 4.4e-7, bf16 6.9e-3; user_coef f32 7.7e-4, bf16 1.2e-2;
#: item_coef f32 5.5e-4, bf16 1.2e-2; loss f32 7.5e-6, bf16 1.1e-4; val_auc
#: f32 4.0e-6; fe_coef f32 2.9e-4; norm f32 1.9e-4 (the last three are held
#: against a dropped sweep or a state returned unchanged, which read 1).
TINY_LIMITS = {
    "glmix": {"loss_own_coef_rel_gap": 4e-7, "val_margin_own_coef_max_gap": 3e-5,
              "loss_rel_gap": 4e-5, "val_auc_gap": 4e-5, "fe_coef_rel_l2": 3e-3,
              "user_coef_rel_l2": 2.5e-3, "item_coef_rel_l2": 2.5e-3,
              "norm_rel_gap": 1e-3},
    # the λ-path at 4,000 x 64 on the CPU, one reading each (every seed poses
    # the same fit), largest over the four λ for float32, smallest for the
    # bfloat16 control: value at own coefficients f32 9.0e-8, bf16 1.4e-5;
    # the solve's gradient norm at own coefficients f32 1.3e-4, bf16 3.1e-2;
    # validation margins at own coefficients f32 2.8e-7, bf16 2.4e-3; value
    # against the reference's minimum f32 1.3e-7, bf16 1.35e-5; coef_rel_l2
    # f32 7.0e-4, bf16 1.6e-3 and val_auc f32 2.4e-5, bf16 0 to 1.2e-4 (not
    # separated: held against a solve that returns its start, which reads 1,
    # and an altered coefficient).
    "path": {"loss_own_coef_rel_gap": 1e-6, "grad_norm_own_coef_rel_gap": 2e-3,
             "val_margin_own_coef_max_gap": 3e-5,
             "coef_rel_l2": 2.5e-3, "loss_rel_gap": 2e-6, "val_auc_gap": 1e-4},
}
LIMITS_OF = {"glmix-ml20m.sweeps": "glmix", "logistic-epsilon.path": "path"}


def tiny_cell(workload: str, **overrides) -> dict:
    """find_cell's record of a real cell, its configuration cut to test size."""
    found = find_cell(load_manifest(), workload)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY[workload])
    found["config"]["limits"] = copy.deepcopy(TINY_LIMITS[LIMITS_OF[workload]])
    found["config"].update(overrides)
    return found


def driver_and_reference(found: dict):
    return load_module(found["driver"]), load_module(found["reference"])


def fit_and_compare(workload: str, seed: int, dtype: str = "float32") -> list:
    """One episode of the tiny cell and every number ``correct`` compares."""
    import jax

    from benchmark.spans import Spans

    found = tiny_cell(workload, feature_dtype=dtype)
    driver, reference = driver_and_reference(found)
    cell = driver.Cell(found["config"], found["traffic"], seed, jax.devices()[:1],
                       Spans())
    return cell.verify(reference, cell.episode())


def run_with_the_timed_path_broken(workload: str, break_it, monkeypatch, seed: int):
    """Everything of a run but the look for a chip, on the CPU, with what an
    episode produced passed through ``break_it`` (None: left sound). Returns
    (the cell as found, the result line)."""
    import jax

    import benchmark.manifest
    from benchmark import run

    found = tiny_cell(workload)
    driver = load_module(found["driver"])
    if break_it is not None:
        sound_episode = driver.Cell.episode

        def broken(self):
            self.last = break_it(sound_episode(self))
            return self.last

        monkeypatch.setattr(driver.Cell, "episode", broken)
        monkeypatch.setattr(benchmark.manifest, "load_module", lambda path: (
            driver if path == found["driver"] else load_module(path)))
    return found, run.run_cell(found, load_manifest(), seed=seed, seconds=0.0,
                               trace=False, devices=jax.devices()[:1])


# -- what every manifest has to satisfy, the committed one and any grown from
# -- it by addition (a later PR's): each takes (manifest, root of the tree
# -- that holds the files it names)

#: cells accepted so far: a later manifest may add to them, never drop one
ACCEPTED_CELLS = {"glmix-ml20m.sweeps", "logistic-epsilon.path"}
#: the per-layer metrics that read the program's own spans and counters (PR 24)
PROGRAM_METRICS = ("prog_sweep_s", "prog_place_s", "sweep_host_s", "validate_s",
                   "pack_group_s", "trace_lower_s", "program_load_s")


def assert_contract_and_cells(manifest: dict, root: str) -> None:
    assert M.check_manifest(manifest, root) == []
    assert len(json.dumps(manifest)) < 64 * 1024
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    names = [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names)
    assert ACCEPTED_CELLS <= set(names)
    here = os.path.join(root, "benchmark")
    for name in names:  # configuration, traffic, driver and reference are files
        found = find_cell(manifest, name, here=here)
        assert os.path.isfile(found["driver"]), found["driver"]
        assert os.path.isfile(found["reference"]), found["reference"]
        assert int(found["traffic"]["min_episodes"]) >= 1
        assert int(found["traffic"]["traced_episodes"]) >= 1


def assert_cells_report_what_their_metrics_move(manifest: dict, root: str) -> None:
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    here = os.path.join(root, "benchmark")
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in M.metrics_of(
            manifest, "end_to_end", cell["name"], set())}
        assert "setup_s" in reported and len(reported) >= 2
        layers = M.metrics_of(manifest, "per_layer", cell["name"], reported)
        assert layers, cell["name"]
        for m in layers:
            assert m["moves"] in reported and m["moves"] in e2e
            assert callable(M.layer_metric_reader(m["name"], here=here))


def _flat(limits):
    for v in limits.values():
        yield from (_flat(v) if isinstance(v, dict) else (v,))


def assert_configurations_state_source_cut_and_reference(manifest: dict,
                                                         root: str) -> None:
    for entry in manifest["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert cfg["assumed"] and os.path.isfile(os.path.join(root, cfg["reference"]))
        assert cfg["reference"].endswith(entry["name"] + ".py")
        assert all(v < 1.0 for v in _flat(cfg["limits"])), "a limit was left open"


def assert_program_metrics_are_entries(manifest: dict, root: str) -> None:
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert set(PROGRAM_METRICS) <= set(entries)
    for name in PROGRAM_METRICS:
        assert "glmix-ml20m.sweeps" in entries[name]["workloads"]
        assert entries[name]["unit"] == "s" and entries[name]["better"] == "lower"
        source = "program_counter" if entries[name]["moves"] == "setup_s" \
            else "program_span"
        assert entries[name]["source"] == source


MANIFEST_ASSERTIONS = (
    assert_contract_and_cells,
    assert_cells_report_what_their_metrics_move,
    assert_configurations_state_source_cut_and_reference,
    assert_program_metrics_are_entries,
)
