"""Which way the package's arrows point, written down once.

``LAYERS`` is the one table: the top-level units of ``photon_ml_tpu`` (its
directories and its three modules), lowest layer first. A unit may import
from the layers BELOW its own and from nothing else in the package. The
imports that break this today are listed by name in ``KNOWN_BACK_EDGES``,
each with the ROADMAP debt that owns it and its cheapest repair. A case
fails on an upward import that is not listed AND on a listed one that no
longer exists, so the list can only shrink: whoever repairs an arrow takes
its line out, and whoever adds one has to write it down here to get past.

Read with ``ast`` from the files, function-level imports included (most of
the listed ones sit inside functions to break the cycle at run time);
nothing is imported. There is no allow-by-comment and no option.
"""

from __future__ import annotations

import ast
import functools
import pathlib

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = "photon_ml_tpu"

#: lowest first; the units of one layer do not import each other
LAYERS = (
    ("types",),
    ("util", "native"),
    ("telemetry",),
    ("resilience", "evaluation", "sampling", "projector"),
    ("data",),
    ("ops", "models"),
    ("optim", "io"),
    ("algorithm",),
    ("parallel",),
    ("estimators", "transformers", "serving", "diagnostics"),
    ("hyperparameter",),
    ("cli",),
)
RANK = {unit: rank for rank, layer in enumerate(LAYERS) for unit in layer}

#: (importing module, imported unit) -> the debt that owns it (ROADMAP.md)
#: and what the import is for
KNOWN_BACK_EDGES = {
    ("util.timed", "telemetry"):
        "D10: timed() files its seconds in the registry and opens a span; "
        "it is telemetry's, not util's",
    ("telemetry.solver_trace", "optim"):
        "D10: an adapter from optim's LaneTrace(s) to journal rows; it "
        "belongs above optim/",
    ("telemetry.tracing", "resilience"):
        "D10: publish_trace / finalize_trace catch ExchangeTimeout; the "
        "error type belongs below telemetry/",
    ("resilience.recovery", "io"):
        "D10: run_with_recovery names io.checkpoint.DivergenceError; the "
        "error type belongs with resilience/errors.py",
    ("data.game_data", "ops"):
        "D10: host_factors / host_shifts of ops/normalization are host "
        "numpy over a data-layer context",
    ("data.game_data", "parallel"):
        "D10: build_random_effect_dataset(mesh=) reaches for "
        "parallel.mesh.place (PR 27); pass the placed arrays in, not the "
        "mesh",
    ("io.partitioned_reader", "parallel"):
        "D10: defaults its exchange to parallel.multihost."
        "SingleProcessExchange; the exchange protocol belongs below io/",
    ("io.score_writer", "parallel"):
        "D10: the same SingleProcessExchange default",
    ("algorithm.lane_scheduler", "parallel"):
        "D2/D10: the SPMD rescue block calls parallel.multihost."
        "assemble_partitioned",
    ("algorithm.streaming_game", "parallel"):
        "D3/D10: the streamed trainer is built from parallel.distributed's "
        "step specs and GameTrainState",
    ("algorithm.lane_search", "estimators"):
        "D10: _objective_for_batch, a private helper of the layer that "
        "dispatches to it; it belongs in ops/",
    ("algorithm.refresh", "estimators"):
        "D10: the coordinate-config classes; they are data about a fit "
        "and belong below algorithm/",
}


def _unit_files(unit: str) -> list[pathlib.Path]:
    root = REPO_ROOT / PACKAGE
    if (root / f"{unit}.py").is_file():
        return [root / f"{unit}.py"]
    return sorted((root / unit).rglob("*.py"))


def _module_name(path: pathlib.Path, root: pathlib.Path) -> str:
    parts = path.relative_to(root).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _imported_modules(path: pathlib.Path, module: str) -> list[tuple[str, int]]:
    """Every dotted name the file imports, absolute, with its line: plain
    and ``from`` imports at any depth of nesting, relative ones resolved
    against ``module`` (the file's own dotted name)."""
    found = []
    package = module if path.name == "__init__.py" else module.rpartition(".")[0]
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.Import):
            found += [(alias.name, node.lineno) for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                anchor = package.split(".")
                anchor = anchor[:len(anchor) - (node.level - 1)]
                base = ".".join(anchor + ([base] if base else []))
            # ``from pkg import name``: name may itself be a module
            found += [(f"{base}.{alias.name}", node.lineno)
                      for alias in node.names]
    return found


@functools.cache
def _sibling_imports(unit: str) -> dict[tuple[str, str], list[int]]:
    """(importing module, imported unit) -> lines, for every import in
    ``unit`` of another unit of the package."""
    edges: dict[tuple[str, str], list[int]] = {}
    root = REPO_ROOT / PACKAGE
    for path in _unit_files(unit):
        module = _module_name(path, root)
        for name, line in _imported_modules(path, f"{PACKAGE}.{module}"):
            head, _, rest = name.partition(".")
            target = rest.split(".")[0]
            if head == PACKAGE and target in RANK and target != unit:
                edges.setdefault((module, target), []).append(line)
    return edges


def test_the_table_names_every_unit_of_the_package():
    root = REPO_ROOT / PACKAGE
    on_disk = {
        p.stem if p.is_file() else p.name
        for p in root.iterdir()
        if (p.suffix == ".py" and p.stem != "__init__")
        or (p.is_dir() and (p / "__init__.py").is_file())
    }
    assert on_disk == set(RANK)
    assert len(RANK) == sum(len(layer) for layer in LAYERS)  # each unit once
    assert {unit for _, unit in KNOWN_BACK_EDGES} <= set(RANK)


@pytest.mark.parametrize("unit", sorted(RANK, key=lambda u: (RANK[u], u)))
def test_a_unit_imports_only_from_the_layers_below_it(unit):
    upward = {
        edge: lines for edge, lines in _sibling_imports(unit).items()
        if RANK[edge[1]] >= RANK[unit]
    }
    listed = {
        edge for edge in KNOWN_BACK_EDGES
        if edge[0].split(".")[0] == unit
    }
    unlisted = {e: upward[e] for e in upward.keys() - listed}
    assert not unlisted, (
        f"{unit} (layer {RANK[unit]}) imports upward or sideways, at "
        f"(module, unit) -> lines {unlisted}: import from a lower layer, "
        "or move what is needed down"
    )
    repaired = listed - upward.keys()
    assert not repaired, (
        f"{sorted(repaired)} no longer import upward: take them out of "
        "KNOWN_BACK_EDGES (and out of ROADMAP D10)"
    )


def _imports_under(directory: pathlib.Path) -> dict[str, list[str]]:
    """imported dotted name -> ``file:line`` sites, over a directory."""
    sites: dict[str, list[str]] = {}
    for path in sorted(directory.rglob("*.py")):
        module = _module_name(path, directory.parent)
        for name, line in _imported_modules(path, module):
            sites.setdefault(name, []).append(
                f"{path.relative_to(REPO_ROOT)}:{line}")
    return sites


def _of(sites: dict[str, list[str]], prefix: str) -> dict[str, list[str]]:
    return {name: where for name, where in sites.items()
            if name == prefix or name.startswith(prefix + ".")}


def test_the_package_imports_no_tool_that_stands_beside_it():
    """The library is what the benchmark, the dev tools and the chip smoke
    drive; it reaches for none of them (``bench`` went in PR 29 and must
    not come back as an import)."""
    sites = _imports_under(REPO_ROOT / PACKAGE)
    for outsider in ("bench", "benchmark", "dev", "chip_smoke"):
        assert not _of(sites, outsider), outsider


def test_the_benchmark_imports_no_operator_tooling():
    """``benchmark/`` is the yardstick: it reads the program and its
    counters, never the doctor or the findings the doctor prints."""
    sites = _imports_under(REPO_ROOT / "benchmark")
    assert _of(sites, PACKAGE), "the walk found the benchmark's imports"
    assert not _of(sites, "dev")
    assert not _of(sites, f"{PACKAGE}.telemetry.verdicts")
