"""The readers of the program's own spans and counters: on a small recorded
program trace (``data/small_program_trace.json``, in ``program_trace.load``'s
format: two fits of two sweeps inside the window, a warm fit before it, one
after it, a span of another host thread, one device), on a real xplane file
the CPU's profiler writes, and on a program that has none of it."""

import json
import os

import pytest

from benchmark import manifest as M
from benchmark import program_trace
from benchmark.manifest import layer_metric_reader
from photon_ml_tpu.telemetry import registry as registry_module
from photon_ml_tpu.telemetry.registry import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


@pytest.fixture(scope="module")
def raw():
    with open(os.path.join(HERE, "data", "small_program_trace.json")) as f:
        doc = json.load(f)
    return {"window": [tuple(w) for w in doc["window"]],
            "spans": [tuple(s) for s in doc["spans"]],
            "devices": {int(k): [tuple(e) for e in v]
                        for k, v in doc["devices"].items()}}


@pytest.fixture
def program_registry(monkeypatch):
    """A default registry of the test's own, filled as the program's
    ``Timed`` blocks and compile listener fill theirs."""
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    for name, values in {
        "timing/pack/group_entities": (2.5, 1.75),  # two coordinates packed
        "timing/pack/dataset": (4.0, 3.0),
        "jax/trace_seconds": (1.5, 0.25),
        "jax/lower_seconds": (0.75,),
        "jax/cache_load_seconds": (9.0, 8.0),
        "jax/backend_compile_seconds": (9.5, 8.25, 0.5),
    }.items():
        for v in values:
            registry.histogram(name).observe(v)
    return registry


#: what each reader gives on the recorded trace and the filled registry
KNOWN = {
    "prog_sweep_s": 2000 * NS,      # sweeps of 2000, 2200, 2000, 2000 ns
    "prog_place_s": 400 * NS,       # fit start to first sweep: 300, 500
    "sweep_host_s": 265 * NS,       # sweep less its two waits: 250 280 290 250
    "validate_s": 325 * NS,         # 300, 400, 350, 300
    "pack_group_s": 4.25,
    "trace_lower_s": 2.5,
    "program_load_s": 18.25,        # the backend-compile events; loads inside
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_the_known_value(name, raw, program_registry):
    value = layer_metric_reader(name)({"program_spans": raw})
    assert value == pytest.approx(KNOWN[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_nothing_for_a_program_without_spans_or_counters(
        name, raw, monkeypatch):
    """A parent commit from before the spans: its trace holds the window and
    the device's operations and no ``photon:`` event, its registry none of
    the metrics. Nothing is returned and nothing raises."""
    monkeypatch.setattr(registry_module, "_DEFAULT", MetricsRegistry())
    bare = dict(raw, spans=[])
    assert layer_metric_reader(name)({"program_spans": bare}) is None


def test_program_load_needs_the_listener_that_files_cache_loads(
        raw, monkeypatch):
    """Backend-compile seconds alone (a ledger run of the parent has them)
    do not make the metric: it is new with the cache's own events."""
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    registry.histogram("jax/backend_compile_seconds").observe(3.0)
    assert layer_metric_reader("program_load_s")({"program_spans": raw}) is None
    registry.histogram("jax/cache_load_seconds")  # the listener's, still empty
    assert layer_metric_reader("program_load_s")({"program_spans": raw}) == 3.0


@pytest.mark.parametrize("workload,names", [
    ("glmix-ml20m.sweeps", {"pack_s", "pack_group_s", "trace_lower_s", "program_load_s"}),
    ("logistic-epsilon.path", {"trace_lower_s", "program_load_s"}),
])
def test_what_moves_setup_is_read_where_setup_ends(workload, names, program_registry):
    """A fit that traces its solves anew inside the window adds to the
    process's seconds: the set-up metrics keep what they read before it."""
    from benchmark import run
    from benchmark.manifest import load_manifest
    from benchmark.spans import Spans

    spans = Spans()
    spans.closed.append(("pack", 1.0, 3.0))
    at_setup = run.setup_layer_metrics(load_manifest(), workload, spans)
    assert set(at_setup) == names
    assert at_setup["trace_lower_s"] == 2.5 and at_setup["program_load_s"] == 18.25
    program_registry.histogram("jax/trace_seconds").observe(0.6)  # the window's
    assert layer_metric_reader("trace_lower_s")({}) == 3.1
    assert at_setup["trace_lower_s"] == 2.5


def test_only_spans_inside_the_window_are_kept(raw):
    summary = program_trace.summarize(raw)
    assert summary["window_s"] == pytest.approx(9900 * NS)
    fits = program_trace.each(summary, "train/fit")
    assert [f[4]["fit"] for f in fits] == [1, 2]  # not the warm fit, not fit 3
    assert fits[0][4] == {"fit": 1, "sweeps": 2, "mesh": "1x1"}
    assert len(program_trace.durations(summary, "train/sweep")) == 4
    # spans of one fit, by containment on its thread
    assert [s[4]["sweep"] for s in
            program_trace.inside(summary, fits[1], "train/sweep")] == [1, 2]


def test_self_time_is_a_spans_own_part(raw):
    own = program_trace.summarize(raw)["self_s"]
    # a sweep's own part: what its five children leave (10 + 10 + 50 + 10)
    assert own["train/sweep"] == pytest.approx(80 * NS)
    # a step's own part: what its dispatch leaves (4 x 20)
    assert own["train/step"] == pytest.approx(80 * NS)
    assert own["dispatch/train/step"] == pytest.approx((80 + 100 + 80 + 80) * NS)
    assert own["train/validate"] == 0.0
    assert own["serve/other"] == pytest.approx(200 * NS)  # its own thread
    # fit 1: 4800 - 200 - 2000 - 2200 - 250; fit 2: 4800 - 300 - 4000 - 250
    assert own["train/fit"] == pytest.approx((150 + 250) * NS)


def test_idle_stretches_go_to_the_innermost_program_span(raw):
    summary = program_trace.summarize(raw)
    idle = {k: round(v / NS) for k, v in summary["idle_s"].items()}
    assert idle == {
        "train/shard_inputs": 370 + 1120,   # 50..420 and 4490..5610
        "train/validate/score": 70 + 80 + 60 + 70,
        "dispatch/train/step": 240,         # 2290..2530: inside the step
        "train/sweep": 270,                 # 7340..7610, between two children
        "train/result_state": 560,          # 9390..9950 (the window's end)
    }
    assert list(summary["idle_s"])[0] == "train/shard_inputs"  # largest first
    busy = sum(b for _, a, b in raw["devices"][0] if a < 9950)
    assert sum(idle.values()) == 9900 - busy


def test_a_trace_without_a_window_reads_as_nothing(raw):
    assert program_trace.summarize(dict(raw, window=[])) is None
    assert program_trace.of({"program_spans": dict(raw, window=[])}) is None


def test_the_run_s_xplane_file_is_found_and_parsed_once(
        tmp_path, monkeypatch, capsys):
    """A real file, as the CPU's profiler writes it: the benchmark's window
    span and the program's spans through the seam, attributes as stats."""
    import jax

    from photon_ml_tpu.telemetry.tracing import span

    monkeypatch.setattr(program_trace, "WORK_DIR", str(tmp_path))
    monkeypatch.setattr(program_trace, "_parsed", {})
    assert program_trace.of({}) is None  # an untraced run: no file, no metric
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace-cell"), profiler_options=options)
    try:
        with span("train/fit", fit=1):  # before the window: not kept
            pass
        with jax.profiler.TraceAnnotation("bench:window"):
            with span("train/fit", fit=2, sweeps=1, mesh="1x1"):
                with span("train/sweep", sweep=1):
                    with span("train/loss_wait"):
                        pass
    finally:
        jax.profiler.stop_trace()
    summary = program_trace.of({})
    assert [(s[0], s[4]) for s in summary["spans"]] == [
        ("train/fit", {"fit": 2, "sweeps": 1, "mesh": "1x1"}),
        ("train/sweep", {"sweep": 1}), ("train/loss_wait", {})]
    assert summary["idle_s"] == {}  # no device plane on the CPU
    assert layer_metric_reader("prog_sweep_s")({}) == pytest.approx(
        summary["spans"][1][2] * NS)
    assert program_trace.of({}) is summary
    printed = capsys.readouterr().out
    assert printed.count("second parse of the trace") == 1


def test_new_metrics_are_in_the_manifest_and_it_still_passes():
    """By membership: where in ``per_layer`` they stand, what follows them and
    which other cells list them is a later PR's to add to."""
    from bm_helpers import PROGRAM_METRICS, assert_program_metrics_are_entries

    manifest = M.load_manifest()
    assert M.check_manifest(manifest) == []
    assert set(PROGRAM_METRICS) == set(KNOWN)
    assert_program_metrics_are_entries(manifest, M.ROOT)
