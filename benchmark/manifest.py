"""``BENCHMARK.json`` and the files it names: loading, lookup by name, and
the checks a manifest has to pass before anything runs. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file of its own, found here by the name in the manifest — a later PR adds a
cell by adding files and one entry, and edits nothing that exists."""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str):
    """A module from a file whose name need not be an identifier."""
    name = "benchmark_file_" + re.sub(r"\W", "_", os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, workload: str, here: str = HERE) -> dict:
    """Everything one cell names, loaded: its entry, the configuration file's
    contents, the traffic file's contents, and the paths of its driver and
    reference."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(os.path.dirname(here), config_entry["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(here, "traffic", cell["traffic"] + ".json")
    with open(traffic_path) as f:
        traffic = json.load(f)
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "driver": os.path.join(here, "drivers", traffic["kind"] + ".py"),
        "reference": os.path.join(here, "references", cell["config"] + ".py"),
    }


def metrics_of(manifest: dict, group: str, workload: str, reported: set) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` a cell has to report: a
    metric with a ``workloads`` key where it lists the cell; one without
    wherever the cell reports the end-to-end metric it moves."""
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def reader_file(name: str, here: str = HERE) -> str:
    """The file that reads a per-layer metric: ``layer_metrics/<name>.py``,
    or for a name ``<quantity>.<suffix>`` the quantity's. An entry names ONE
    end-to-end metric it moves, so a quantity read in cells that report
    different ones is an entry for each (``device_idle_pct`` moves
    ``train_rows_per_s``, ``device_idle_pct.fit`` moves ``fit_s``): entries,
    not files."""
    return os.path.join(here, "layer_metrics", name.split(".", 1)[0] + ".py")


def layer_metric_reader(name: str, here: str = HERE):
    return load_module(reader_file(name, here)).read


def check_manifest(manifest: dict, root: str = ROOT) -> list[str]:
    """Faults of a manifest against the benchmark's contract, as text; empty
    when it passes. What only a chip run can show is not checked here."""
    faults: list[str] = []

    def name_ok(value, what):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{what}: {value!r} is not a name")

    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        faults.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return faults
    paths = manifest["paths"]
    for word in manifest["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command word {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in paths):
            faults.append(f"command names {word!r} outside paths")
    if not 1 <= manifest["run_seconds"] <= 51:
        faults.append("run_seconds outside 1..51")
    configs = {}
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok(c["name"], "config name")
        for key in c["reduced"]:
            name_ok(key, f"config {c['name']} reduced key")
        if not any(c["file"].startswith(p + "/") for p in paths):
            faults.append(f"config {c['name']}: file outside paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: no file {c['file']}")
        if c["name"] in configs:
            faults.append(f"config {c['name']} twice")
        configs[c["name"]] = c
    if len({c["file"] for c in manifest["configs"]}) != len(configs):
        faults.append("two configurations share a file")
    cells, pairs = {}, set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            name_ok(w[key], f"workload {key}")
        if w["config"] not in configs:
            faults.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            faults.append(f"workload {w['name']}: why is not one line of <= 200")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            faults.append(f"workload {w['name']} twice")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        traffic = os.path.join(root, paths[0], "traffic", w["traffic"] + ".json")
        if not os.path.isfile(traffic):
            faults.append(f"workload {w['name']}: no traffic file {traffic}")
    for c in configs:
        if not any(w["config"] == c for w in cells.values()):
            faults.append(f"config {c} has no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} of {len(cells)} cells ask for four chips")
    e2e = {}
    for m in manifest["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}:
            faults.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
        name_ok(m["name"], "end_to_end name")
        if not UNIT.match(m["unit"]):
            faults.append(f"end_to_end {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"end_to_end {m['name']}: better {m['better']!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end_to_end {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.1:
            faults.append(f"end_to_end {m['name']}: bound {m['bound']}")
        for w in m.get("workloads", ()):
            if w not in cells:
                faults.append(f"end_to_end {m['name']}: unknown cell {w}")
        if m["name"] in e2e:
            faults.append(f"end_to_end {m['name']} twice")
        e2e[m["name"]] = m
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        faults.append("every cell has to report setup_s")

    def reports(cell: str) -> set:
        return {m["name"] for m in e2e.values()
                if "workloads" not in m or cell in m["workloads"]}

    seen = set(e2e)
    for m in manifest["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}:
            faults.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
        name_ok(m["name"], "per_layer name")
        if not UNIT.match(m["unit"]):
            faults.append(f"per_layer {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"per_layer {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            faults.append(f"per_layer {m['name']}: source {m['source']!r}")
        if m["moves"] not in e2e:
            faults.append(f"per_layer {m['name']}: moves unknown {m['moves']}")
        for w in m.get("workloads", ()):
            if w not in cells:
                faults.append(f"per_layer {m['name']}: unknown cell {w}")
            elif m["moves"] not in reports(w):
                faults.append(
                    f"per_layer {m['name']}: cell {w} does not report {m['moves']}")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            faults.append(f"per_layer {m['name']}: a roofline share is in %")
        if m["name"] in seen:
            faults.append(f"metric {m['name']} twice")
        seen.add(m["name"])
        reader = reader_file(m["name"], os.path.join(root, paths[0]))
        if not os.path.isfile(reader):
            faults.append(f"per_layer {m['name']}: no reader {reader}")
    for cell in cells:
        if len(reports(cell)) < 2:
            faults.append(f"cell {cell} reports no end-to-end metric beside setup_s")
        if not metrics_of(manifest, "per_layer", cell, reports(cell)):
            faults.append(f"cell {cell} reports no per-layer metric")
    return faults
