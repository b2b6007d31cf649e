"""BENCHMARK.json against the contract, and the harness driven by data: a
configuration, a mix and a per-layer metric dropped in as files are found
with no edit to any file that exists."""

import copy
import json
import os
import shutil

import pytest

from benchmark import manifest as M


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest()


def test_committed_manifest_passes(manifest):
    assert M.check_manifest(manifest) == []
    assert len(json.dumps(manifest)) < 64 * 1024
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert [w["name"] for w in manifest["workloads"]] == ["glmix-ml20m.sweeps"]


def _set(path, value):
    def edit(m):
        node = m
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return edit


@pytest.mark.parametrize("edit,expect", [
    (_set(("workloads", 0, "name"), "has space"), "is not a name"),
    (_set(("workloads", 0, "name"), "a/b"), "is not a name"),
    (_set(("end_to_end", 0, "unit"), "rows per s"), "unit"),
    (_set(("per_layer", 0, "unit"), "µs"), "unit"),
    (_set(("end_to_end", 0, "better"), "faster"), "better"),
    (_set(("end_to_end", 0, "bound"), 0.2), "bound"),
    (_set(("end_to_end", 0, "source"), "program_span"), "source"),
    (_set(("per_layer", 0, "source"), "guess"), "source"),
    (_set(("per_layer", 1, "moves"), "setup_s"), None),  # every cell reports it
    (lambda m: (m["end_to_end"].append(dict(m["end_to_end"][0], name="fit_s", workloads=[])),
                m["per_layer"][1].update(moves="fit_s")), "does not report fit_s"),
    (_set(("per_layer", 1, "moves"), "nothing"), "moves unknown"),
    (lambda m: [x.update(unit="share") for x in m["per_layer"]
                if x["name"].endswith("_roofline")], "a roofline share is in %"),
    (_set(("per_layer", 0, "name"), "no_such_reader"), "no reader"),
    (_set(("workloads", 0, "chips"), 2), "chips"),
    (lambda m: m["workloads"].extend(
        dict(m["workloads"][0], name=f"more{k}", traffic=f"mix{k}", chips=4)
        for k in (1, 2)), "ask for four chips"),
    (_set(("workloads", 0, "why"), "x" * 201), "why"),
    (lambda m: m["configs"].append(dict(m["configs"][0], name="orphan",
                                        file="benchmark/configs/orphan.json")),
     "has no cell"),
    (lambda m: m["configs"].append(dict(m["configs"][0], name="twin")), "share a file"),
    (_set(("configs", 0, "file"), "photon_ml_tpu/types.py"), "outside paths"),
    (_set(("configs", 0, "reduced"), ["rows 20M -> 5M"]), "is not a name"),
    (_set(("command",), ["python3", "bench.py"]), "outside paths"),
    (_set(("command",), ["python3", "../x.py"]), "leaves the repo"),
    (_set(("run_seconds",), 52), "run_seconds"),
    (_set(("end_to_end", 1, "workloads"), ["glmix-ml20m.sweeps"]), "setup_s"),
    (_set(("workloads", 0, "slo"), "p95"), "keys"),
    (_set(("per_layer", 0, "why"), "because"), "keys"),
])
def test_a_broken_manifest_is_refused(manifest, edit, expect):
    broken = copy.deepcopy(manifest)
    edit(broken)
    faults = M.check_manifest(broken)
    if expect is None:
        assert faults == []
    else:
        assert any(expect in f for f in faults), faults


def test_every_cell_reports_what_its_metrics_move(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in M.metrics_of(
            manifest, "end_to_end", cell["name"], set())}
        assert "setup_s" in reported and len(reported) >= 2
        layers = M.metrics_of(manifest, "per_layer", cell["name"], reported)
        assert layers, cell["name"]
        for m in layers:
            assert m["moves"] in reported and m["moves"] in e2e
            assert callable(M.layer_metric_reader(m["name"]))


def test_every_configuration_states_source_cut_and_reference(manifest):
    for entry in manifest["configs"]:
        with open(os.path.join(M.ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert cfg["assumed"] and os.path.isfile(os.path.join(M.ROOT, cfg["reference"]))
        assert cfg["reference"].endswith(entry["name"] + ".py")
        assert all(v < 1.0 for v in _flat(cfg["limits"])), "a limit was left open"


def _flat(limits):
    for v in limits.values():
        yield from (_flat(v) if isinstance(v, dict) else (v,))


def test_new_files_and_one_entry_make_a_cell(manifest, tmp_path):
    """A later PR's cell: a configuration, a mix, a reference, a per-layer
    metric — files only — and one entry each in the manifest. The harness
    finds all of them by name and runs the cell."""
    import jax

    from benchmark import run
    from bm_helpers import TINY, TINY_LIMITS

    tree = tmp_path / "benchmark"
    shutil.copytree(os.path.join(M.ROOT, "benchmark"), tree,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (tree / p).read_bytes() for p in
              ("run.py", "manifest.py", "drivers/game_sweeps.py")}
    cfg = json.load(open(tree / "configs" / "glmix-ml20m.json"))
    cfg.update(TINY["glmix-ml20m.sweeps"], name="glmix-small",
               limits=TINY_LIMITS["glmix"])
    (tree / "configs" / "glmix-small.json").write_text(json.dumps(cfg))
    (tree / "traffic" / "once.json").write_text(json.dumps(
        {"kind": "game_sweeps", "loop": "closed", "min_episodes": 1,
         "traced_episodes": 1}))
    shutil.copy(tree / "references" / "glmix-ml20m.py",
                tree / "references" / "glmix-small.py")
    (tree / "layer_metrics" / "read_s.py").write_text(
        "def read(ctx):\n    return sum(ctx['spans'].durations('read'))\n")
    grown = copy.deepcopy(manifest)
    grown["configs"].append({"name": "glmix-small", "source": "test", "reduced": [],
                             "file": "benchmark/configs/glmix-small.json", "why": "t"})
    grown["workloads"].append({"name": "glmix-small.once", "config": "glmix-small",
                               "traffic": "once", "chips": 1, "why": "test"})
    grown["per_layer"].append({
        "name": "read_s", "unit": "s", "better": "lower", "source": "program_span",
        "layer": "trainer", "moves": "train_rows_per_s",
        "workloads": ["glmix-small.once"]})
    grown["end_to_end"][0]["workloads"].append("glmix-small.once")

    found = M.find_cell(grown, "glmix-small.once", here=str(tree))
    assert found["config"]["name"] == "glmix-small"
    assert found["driver"] == str(tree / "drivers" / "game_sweeps.py")
    names = [m["name"] for m in M.metrics_of(
        grown, "per_layer", "glmix-small.once", {"train_rows_per_s", "setup_s"})]
    assert "read_s" in names and "pack_s" not in names
    result = run.run_cell(found, grown, seed=9, seconds=0.0, trace=False,
                          devices=jax.devices()[:1])
    assert result["correct"] is True and result["attempted"] == 1
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
    from benchmark.spans import Spans

    spans = Spans()
    spans.closed.append(("read", 1.0, 1.25))
    assert M.layer_metric_reader("read_s", here=str(tree))({"spans": spans}) == 0.25
    assert {p: (tree / p).read_bytes() for p in before} == before
