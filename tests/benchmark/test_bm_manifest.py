"""BENCHMARK.json against the contract, and the harness driven by data: a
configuration, a mix and a per-layer metric dropped in as files are found
with no edit to any file that exists."""

import copy
import json
import os
import shutil

import pytest

from benchmark import manifest as M
from bm_helpers import MANIFEST_ASSERTIONS, TINY, TINY_LIMITS


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest()


@pytest.mark.parametrize("holds", MANIFEST_ASSERTIONS, ids=lambda f: f.__name__)
def test_committed_manifest(holds, manifest):
    holds(manifest, M.ROOT)


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
    (lambda m: (m["end_to_end"].append(dict(m["end_to_end"][0], name="other_s", workloads=[])),
                m["per_layer"][1].update(moves="other_s")), "does not report other_s"),
    (_set(("per_layer", 1, "moves"), "nothing"), "moves unknown"),
    (lambda m: [x.update(unit="share") for x in m["per_layer"]
                if x["name"].endswith("_roofline")], "a roofline share is in %"),
    (_set(("per_layer", 0, "name"), "no_such_reader"), "no reader"),
    (_set(("per_layer", 0, "name"), "no_such_reader.fit"), "no reader"),
    (_set(("per_layer", 0, "name"), "device_idle_pct.again"), None),
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


def _grow(manifest, tree):
    """The manifest as a later PR leaves it: one more configuration, cell,
    end-to-end metric and per-layer entry, APPENDED, and the files they name
    added under ``tree`` (a copy of ``benchmark/``). Nothing that was there
    is edited."""
    cfg = json.load(open(tree / "configs" / "glmix-ml20m.json"))
    cfg.update(TINY["glmix-ml20m.sweeps"], name="glmix-small", source="test",
               reduced={}, limits=TINY_LIMITS["glmix"],
               reference="benchmark/references/glmix-small.py")
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
    grown["end_to_end"].append({
        "name": "episodes_per_s", "unit": "1/s", "better": "higher", "bound": 0.02,
        "source": "host_clock", "workloads": ["glmix-small.once"]})
    # a quantity that has a reader already, moving the new end-to-end metric:
    # an entry `<quantity>.<suffix>` and no file
    grown["per_layer"].append({
        "name": "device_idle_pct.once", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "episodes_per_s",
        "workloads": ["glmix-small.once"]})
    grown["per_layer"].append({
        "name": "read_s", "unit": "s", "better": "lower", "source": "host_clock",
        "layer": "trainer", "moves": "episodes_per_s",
        "workloads": ["glmix-small.once"]})
    return grown


@pytest.fixture
def tree(tmp_path):
    """A copy of ``benchmark/`` for a later PR's files; tmp_path is its root."""
    shutil.copytree(os.path.join(M.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "benchmark"


def test_a_manifest_grown_by_addition_passes_every_manifest_test(manifest, tree):
    """What keeps a pin from coming back: the COMMITTED manifest, grown as a
    later PR grows it, against every manifest-level assertion of this
    directory. An assertion that pins the list of cells, the order or the
    end of ``per_layer``, or one entry's ``workloads`` to a single cell,
    fails here."""
    grown = _grow(manifest, tree)
    assert grown["per_layer"][-1]["name"] == "read_s"  # appended, as the contract asks
    for holds in MANIFEST_ASSERTIONS:
        holds(grown, str(tree.parent))
    # and an accepted cell may take a later metric too
    entry = next(m for m in grown["per_layer"] if m["name"] == "prog_sweep_s")
    entry["workloads"].append("glmix-small.once")
    grown["end_to_end"][0]["workloads"].append("glmix-small.once")
    for holds in MANIFEST_ASSERTIONS:
        holds(grown, str(tree.parent))


def test_new_files_and_one_entry_make_a_cell(manifest, tree):
    """A later PR's cell: a configuration, a mix, a reference, a per-layer
    metric (files only) and one entry each in the manifest. The harness
    finds all of them by name and runs the cell."""
    import jax

    from benchmark import run

    before = {p: (tree / p).read_bytes() for p in
              ("run.py", "manifest.py", "drivers/game_sweeps.py")}
    grown = _grow(manifest, tree)
    grown["end_to_end"][0]["workloads"].append("glmix-small.once")

    found = M.find_cell(grown, "glmix-small.once", here=str(tree))
    assert found["config"]["name"] == "glmix-small"
    assert found["driver"] == str(tree / "drivers" / "game_sweeps.py")
    names = [m["name"] for m in M.metrics_of(
        grown, "per_layer", "glmix-small.once", {"episodes_per_s", "setup_s"})]
    assert names == ["device_idle_pct.once", "read_s"]
    assert M.reader_file("device_idle_pct.once", here=str(tree)) == str(
        tree / "layer_metrics" / "device_idle_pct.py")
    assert not (tree / "layer_metrics" / "device_idle_pct.once.py").exists()
    result = run.run_cell(found, grown, seed=9, seconds=0.0, trace=False,
                          devices=jax.devices()[:1])
    assert result["correct"] is True and result["attempted"] == 1
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
    from benchmark.spans import Spans

    spans = Spans()
    spans.closed.append(("read", 1.0, 1.25))
    assert M.layer_metric_reader("read_s", here=str(tree))({"spans": spans}) == 0.25
    assert {p: (tree / p).read_bytes() for p in before} == before
