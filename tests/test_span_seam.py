"""The one span seam (telemetry/tracing.py ``span``) and what is hung on it:
the profiler's trace and the ring from one call, parents and inherited
identifiers, open spans on the failure path, ``Timed``, the spans and
counters of ``train_distributed``, the packer and the jit dispatch, and the
compile listener's five JAX events. All on the CPU: the CPU backend records
host annotations in the xplane's ``/host:CPU`` plane as the TPU's does."""

import glob
import json
import logging
import os
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.telemetry import probes, tracing
from photon_ml_tpu.telemetry.registry import MetricsRegistry, default_registry
from photon_ml_tpu.telemetry.tracing import (
    Tracer,
    flush_trace_best_effort,
    install_tracer,
    span,
    uninstall_tracer,
)
from photon_ml_tpu.util.timed import Timed

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def tracer():
    t = install_tracer(Tracer(rank=0))
    try:
        yield t
    finally:
        uninstall_tracer()


class profiler_session:
    """A profiler session as the benchmark starts one (no tracer, Python
    tracer off); ``host_events`` afterwards: (name, stats) of /host:CPU."""

    def __init__(self, directory):
        self.directory = str(directory)
        self.host_events: list[tuple[str, dict]] = []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    self.host_events += [
                        (e.name, dict(e.stats)) for e in line.events
                        if e.name.startswith(("photon:", "unit"))]
        return False

    def named(self, name):
        return [stats for n, stats in self.host_events if n == name]


# -- the seam's three states ---------------------------------------------------


def test_off_is_one_shared_null_object():
    assert tracing.current_tracer() is None
    assert span("unit/a", k=1) is span("unit/b")
    with span("unit/a") as s:
        assert s is span("unit/b")


def test_session_without_tracer_puts_the_span_in_the_host_plane(tmp_path):
    with profiler_session(tmp_path) as session:
        with span("unit/outer", fit=7, sweeps=3, mesh="4x2"):
            with span("unit/inner", sweep=1):
                pass
    assert session.named("photon:unit/outer") == [
        {"fit": 7, "sweeps": 3, "mesh": "4x2"}]
    assert session.named("photon:unit/inner") == [{"sweep": 1}]


def test_tracer_and_session_feed_both_sinks(tmp_path, tracer):
    with profiler_session(tmp_path) as session:
        with span("unit/both", k=2):
            pass
    assert session.named("photon:unit/both") == [{"k": 2}]
    assert [(e.name, e.attrs) for e in tracer.events()] == [
        ("unit/both", {"k": 2})]


def test_tracer_without_session_records_the_ring_only(tracer):
    with span("unit/ring"):
        pass
    (event,) = tracer.events()
    assert event.name == "unit/ring" and event.parent is None
    assert event.attrs is None and event.dur >= 0.0


# -- parents, identifiers, open spans -----------------------------------------


def test_ring_events_carry_parent_and_inherited_identifiers(tracer):
    with span("train/fit", fit=7, sweeps=2):
        with span("train/sweep", sweep=1):
            with span("train/step"):
                pass
            with span("train/step", sweep=99):  # an own value wins
                pass
        with span("train/result_state"):
            pass
    events = {(e.name, e.start): e for e in tracer.events()}
    by_name: dict = {}
    for e in tracer.events():
        by_name.setdefault(e.name, []).append(e)
    (fit,), (sweep,) = by_name["train/fit"], by_name["train/sweep"]
    assert fit.parent is None and fit.attrs == {"fit": 7, "sweeps": 2}
    assert sweep.parent == ("train/fit", fit.start)
    assert sweep.attrs == {"fit": 7, "sweep": 1}  # sweeps is not inherited
    first, second = by_name["train/step"]
    assert first.parent == second.parent == ("train/sweep", sweep.start)
    assert first.attrs == {"fit": 7, "sweep": 1}
    assert second.attrs == {"fit": 7, "sweep": 99}
    # the sweep's identifier ends with the sweep
    (result,) = by_name["train/result_state"]
    assert result.attrs == {"fit": 7} and result.parent[0] == "train/fit"
    assert (result.parent[0], result.parent[1]) in events


def test_chrome_export_carries_parent_in_args(tracer):
    with span("unit/outer", fit=3):
        with span("unit/inner"):
            pass
    rows = {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]
            if e.get("ph") == "X"}
    assert rows["unit/inner"]["args"] == {
        "fit": 3, "parent": "unit/outer", "parent_ts": rows["unit/outer"]["ts"]}
    assert "parent" not in rows["unit/outer"]["args"]


def test_failure_path_writes_the_spans_still_open(tmp_path, tracer):
    with span("train/fit", fit=1):
        with span("train/shard_inputs"):
            with span("train/shard/buckets"):
                # what a driver's teardown does while the run is dying here
                flush_trace_best_effort(tracer, tmp_path, gather=False)
    doc = json.load(open(tmp_path / "trace-00000.json"))
    begun = [e for e in doc["traceEvents"] if e["ph"] == "B"]
    assert [e["name"] for e in begun] == [
        "train/fit", "train/shard_inputs", "train/shard/buckets"]
    assert begun[2]["args"]["parent"] == "train/shard_inputs"
    assert begun[2]["args"]["fit"] == 1 and "dur" not in begun[2]
    assert tracer.open_spans() == []  # all left by now


def test_a_raising_span_is_recorded_and_leaves_the_stack(tracer):
    with pytest.raises(KeyError):
        with span("unit/outer"):
            with span("unit/raises"):
                raise KeyError("x")
    assert tracer.open_spans() == []
    errors = {e.name: e.attrs for e in tracer.events()}
    assert errors == {"unit/raises": {"error": "KeyError"},
                      "unit/outer": {"error": "KeyError"}}
    with span("unit/after"):
        pass
    assert [e.parent for e in tracer.events() if e.name == "unit/after"] == [None]


def test_every_thread_has_its_own_stack(tracer):
    inside, release = threading.Event(), threading.Event()

    def worker():
        with span("unit/worker", fit=2):
            inside.set()
            release.wait(10)

    t = threading.Thread(target=worker)
    t.start()
    assert inside.wait(10)
    with span("unit/main"):
        pass
    open_now = tracer.open_spans()
    release.set()
    t.join(10)
    assert not t.is_alive()
    assert [(e.name, e.attrs) for e in open_now] == [("unit/worker", {"fit": 2})]
    (main,) = [e for e in tracer.events() if e.name == "unit/main"]
    assert main.parent is None and main.attrs is None


# -- Timed ---------------------------------------------------------------------


@pytest.mark.parametrize("label,event", [
    ("unit read phase", "photon:phase/unit read phase"),
    ("unit/namespaced", "photon:unit/namespaced"),
])
def test_timed_emits_one_annotation_from_the_seam(tmp_path, label, event):
    with profiler_session(tmp_path) as session:
        with Timed(label, logging.DEBUG, re_type="user"):
            pass
    mine = [n for n, _ in session.host_events if "unit" in n]
    assert mine == [event]  # one event per block, and not the bare label too
    assert session.named(event) == [{"re_type": "user"}]
    assert default_registry().histogram("timing/" + label).count >= 1


def test_timed_logs_the_open_phase_at_debug(caplog, tracer):
    with caplog.at_level(logging.DEBUG, logger="photon_ml_tpu.timing"):
        with Timed("unit hang here", logging.DEBUG, k=1):
            assert caplog.messages[-1] == "begin unit hang here"
    assert caplog.messages[-1].startswith("unit hang here took")
    (event,) = tracer.events()
    assert (event.name, event.cat, event.attrs) == (
        "phase/unit hang here", "phase", {"k": 1})


def test_only_the_seam_names_the_profilers_annotation():
    hits = [str(p.relative_to(REPO))
            for p in (REPO / "photon_ml_tpu").rglob("*.py")
            if "TraceAnnotation" in p.read_text()]
    assert hits == ["photon_ml_tpu/telemetry/tracing.py"]


# -- train_distributed ----------------------------------------------------------

SWEEPS = 3
ONCE_PER_FIT = [
    "train/fit", "train/pad", "train/prepare_inputs", "train/init_state",
    "train/prepare_validation", "train/shard_inputs", "train/shard/data",
    "train/shard/buckets", "train/shard/state", "train/shard_validation",
    "train/device_evaluators", "train/result_state",
]
ONCE_PER_SWEEP = [
    "train/sweep", "train/step", "train/loss_wait", "train/solver_counts",
    "train/train_metric", "train/validate", "train/validate/score", "train/validate/evaluate",
    "train/on_sweep", "dispatch/train/step",
]
SWEEP_CHILDREN = ["train/step", "train/loss_wait", "train/solver_counts",
                  "train/train_metric", "train/validate", "train/on_sweep"]


@pytest.fixture(scope="module")
def fits():
    """One tiny GLMix fit on a 4x2 mesh with validation and a training
    metric, run without a tracer and then with one."""
    from photon_ml_tpu.evaluation.evaluators import (
        EvaluationData,
        parse_evaluator,
    )
    from photon_ml_tpu.parallel.distributed import train_distributed
    from photon_ml_tpu.parallel.mesh import make_mesh
    from tests.test_distributed import _program, _toy_game_data

    dataset, re_datasets = _toy_game_data(np.random.default_rng(0))
    validation, _ = _toy_game_data(np.random.default_rng(1), n=40)
    eval_of = lambda ds: EvaluationData(  # noqa: E731
        labels=ds.host_array("labels"), offsets=ds.host_array("offsets"),
        weights=ds.host_array("weights"))
    program = _program()
    seen = []

    def fit():
        return train_distributed(
            program, dataset, re_datasets, mesh=make_mesh(data=4, model=2),
            num_iterations=SWEEPS, validation_dataset=validation,
            validation_evaluators=[parse_evaluator("AUC")],
            validation_eval_data=eval_of(validation),
            training_evaluator=parse_evaluator("AUC"),
            training_eval_data=eval_of(dataset),
            on_sweep=lambda done, total, loss: seen.append((done, loss)))

    plain = fit()
    counters = {name: default_registry().counter(name).value
                for name in ("train/fits", "train/sweeps", "train/rows",
                             "train/placed_bytes")}
    tracer = install_tracer(Tracer(rank=0))
    try:
        traced = fit()
    finally:
        uninstall_tracer()
    return {
        "plain": plain, "traced": traced, "events": list(tracer.events()),
        "rows": dataset.num_samples, "seen": seen,
        "counted": {name: default_registry().counter(name).value - before
                    for name, before in counters.items()},
    }


def _named(events, name):
    return [e for e in events if e.name == name]


@pytest.mark.parametrize("name", ONCE_PER_FIT)
def test_fit_marks_each_set_up_span_once(fits, name):
    assert len(_named(fits["events"], name)) == 1


@pytest.mark.parametrize("name", ONCE_PER_SWEEP)
def test_fit_marks_each_sweep_span_once_a_sweep(fits, name):
    found = _named(fits["events"], name)
    assert [e.attrs["sweep"] for e in found] == [1, 2, 3]
    assert len({e.attrs["fit"] for e in found}) == 1


def test_fit_span_names_the_fit(fits):
    (fit,) = _named(fits["events"], "train/fit")
    assert fit.attrs["sweeps"] == SWEEPS and fit.attrs["mesh"] == "4x2"
    assert fit.attrs["fit"] == default_registry().counter("train/fits").value
    # conditional spans stay out: no checkpointer, no down-sampling
    for absent in ("train/restore", "train/checkpoint", "train/down_sample"):
        assert not _named(fits["events"], absent)


def test_children_of_a_sweep_lie_inside_it_and_cover_it(fits):
    for sweep in _named(fits["events"], "train/sweep"):
        here = ("train/sweep", sweep.start)
        children = [e for e in fits["events"] if e.parent == here]
        assert [e.name for e in sorted(children, key=lambda e: e.start)] == \
            SWEEP_CHILDREN
        for child in children:
            assert sweep.start <= child.start
            assert child.start + child.dur <= sweep.start + sweep.dur + 1e-9
        assert sum(e.dur for e in children) >= 0.95 * sweep.dur
    # and the parents further in: a dispatch inside the step's span, the
    # placement's phases inside shard_inputs
    assert {e.parent[0] for e in _named(fits["events"], "dispatch/train/step")} \
        == {"train/step"}
    assert {e.parent[0] for e in _named(fits["events"], "dispatch/train/score")} \
        == {"train/train_metric", "train/validate/score"}
    for phase in ("data", "buckets", "state"):
        (event,) = _named(fits["events"], "train/shard/" + phase)
        assert event.parent[0] == "train/shard_inputs"


def test_counters_count_the_work(fits):
    assert fits["counted"]["train/fits"] == 1
    assert fits["counted"]["train/sweeps"] == SWEEPS
    assert fits["counted"]["train/rows"] == fits["rows"] * SWEEPS
    assert fits["counted"]["train/placed_bytes"] > 0


def test_a_tracer_changes_no_bit_of_the_fit(fits):
    plain, traced = fits["plain"], fits["traced"]
    assert plain.losses == traced.losses
    assert plain.metric_history == traced.metric_history
    for a, b in zip(jax.tree_util.tree_leaves(plain.state),
                    jax.tree_util.tree_leaves(traced.state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the observer saw every sweep of both fits, with the loss as a float
    assert [done for done, _ in fits["seen"]] == [1, 2, 3, 1, 2, 3]
    assert [loss for _, loss in fits["seen"]] == plain.losses + traced.losses


# -- the packer -------------------------------------------------------------------


def test_packer_is_timed_without_a_tracer_and_spans_its_buckets(tracer):
    from photon_ml_tpu.data.game_data import build_random_effect_dataset
    from tests.test_distributed import _toy_game_data

    dataset, _ = _toy_game_data(np.random.default_rng(3))
    names = ("pack/dataset", "pack/entity_counts", "pack/group_entities")
    before = {n: default_registry().histogram("timing/" + n).count for n in names}
    already = len(list(tracer.events()))  # the toy data packs its own views
    packed = build_random_effect_dataset(
        dataset, "user", "per_entity", bucket_sizes=(4, 64))
    for n in names:
        assert default_registry().histogram("timing/" + n).count == before[n] + 1
    events = list(tracer.events())[already:]
    (whole,) = _named(events, "pack/dataset")
    assert whole.attrs == {"re_type": "user"}
    buckets = _named(events, "pack/bucket")
    assert [(e.attrs["cap"], e.attrs["entities"]) for e in buckets] == [
        (int(b.labels.shape[1]), int(b.labels.shape[0])) for b in packed.buckets]
    assert {e.parent[0] for e in buckets} == {"pack/dataset"}
    for name in ("pack/entity_counts", "pack/group_entities"):
        (event,) = _named(events, name)
        assert event.parent[0] == "pack/dataset"


# -- the compile listener ----------------------------------------------------------

EVENTS = [
    ("/jax/core/compile/jaxpr_trace_duration", probes.TRACE_SECONDS_METRIC),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration",
     probes.LOWER_SECONDS_METRIC),
    ("/jax/core/compile/backend_compile_duration",
     probes.COMPILE_SECONDS_METRIC),
    ("/jax/compilation_cache/cache_retrieval_time_sec",
     probes.CACHE_LOAD_SECONDS_METRIC),
]


@pytest.fixture
def listened():
    registry = MetricsRegistry()
    probes.install_compile_listener(registry)
    return registry


@pytest.mark.parametrize("event,metric", EVENTS)
def test_listener_files_each_duration_under_its_metric(listened, event, metric):
    assert listened.histogram(metric).count == 0  # there from the install on
    jax.monitoring.record_event_duration_secs(event, 0.25)
    jax.monitoring.record_event_duration_secs(event, 0.5)
    assert listened.histogram(metric).total == pytest.approx(0.75)
    others = {m for _, m in EVENTS} - {metric}
    assert all(listened.histogram(m).count == 0 for m in others)
    assert listened.counter(probes.COMPILE_COUNT_METRIC).value == (
        2 if metric == probes.COMPILE_SECONDS_METRIC else 0)


@pytest.mark.parametrize("event,metric", [
    ("/jax/compilation_cache/cache_hits", probes.CACHE_HITS_METRIC),
    ("/jax/compilation_cache/cache_misses", probes.CACHE_MISSES_METRIC),
])
def test_listener_counts_the_caches_answers(listened, event, metric):
    jax.monitoring.record_event(event)
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert listened.counter(metric).value == 1
    assert listened.snapshot()["counters"] == {
        probes.COMPILE_COUNT_METRIC: 0,
        probes.CACHE_HITS_METRIC: int(metric == probes.CACHE_HITS_METRIC),
        probes.CACHE_MISSES_METRIC: int(metric == probes.CACHE_MISSES_METRIC)}


def test_a_trace_inside_a_trace_is_not_counted_twice(listened):
    """JAX reports every function traced inside another (a nested jit, each
    jnp wrapper) with an event of its own, inside the outer one's seconds."""
    reported = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: reported.append(secs)
        if name == "/jax/core/compile/jaxpr_trace_duration" else None)

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    jnp.ones(5)  # the array's own little programs, before the count starts
    start = listened.histogram(probes.TRACE_SECONDS_METRIC).total
    del reported[:]
    outer(jnp.ones(5)).block_until_ready()
    counted = listened.histogram(probes.TRACE_SECONDS_METRIC).total - start
    assert len(reported) > 1  # nested traces did report
    assert counted == pytest.approx(max(reported))  # only the outermost counts
    assert counted < sum(reported)


def test_a_first_labelled_call_raises_trace_seconds_without_a_driver():
    from photon_ml_tpu.telemetry.program_ledger import ledger_jit

    fn = ledger_jit(lambda x: jnp.cos(x) + 3, label="unit/first_call")
    histogram = default_registry().histogram(probes.TRACE_SECONDS_METRIC)
    before, calls = histogram.total, histogram.count
    fn(jnp.ones(7)).block_until_ready()
    assert histogram.count > calls and histogram.total > before
    again = histogram.count
    fn(jnp.ones(7)).block_until_ready()  # cached: nothing traced
    assert histogram.count == again


def test_dispatch_span_wraps_top_level_calls_only(tracer):
    from photon_ml_tpu.telemetry.program_ledger import ledger_jit

    inner = ledger_jit(lambda x: x * 2, label="unit/inner")
    outer = ledger_jit(lambda x: inner(x) + 1, label="unit/outer")
    outer(jnp.ones(3)).block_until_ready()
    outer(jnp.ones(3)).block_until_ready()
    names = [e.name for e in tracer.events()]
    assert names == ["dispatch/unit/outer", "dispatch/unit/outer"]  # inner inlines


# -- the offline digest --------------------------------------------------------------


def test_trace_summary_takes_self_time_from_parent_or_containment(tmp_path, tracer):
    from dev import trace_summary

    with span("unit/outer"):
        with span("unit/inner"):
            pass
    doc = tracer.chrome_trace()
    events = [dict(e, end=e["ts"] + e["dur"])
              for e in doc["traceEvents"] if e["ph"] == "X"]
    outer = next(e for e in events if e["name"] == "unit/outer")
    inner = next(e for e in events if e["name"] == "unit/inner")
    # a child that fills its parent to the tick: by containment the two
    # cannot be told apart (the ring files the child first, so it is taken
    # for the parent); the recorded parent settles it
    inner.update(ts=outer["ts"], dur=outer["dur"], end=outer["end"])
    with_parent = trace_summary.self_times(events)
    assert with_parent["unit/outer"]["self_s"] == 0.0
    assert with_parent["unit/inner"]["self_s"] == pytest.approx(outer["dur"] / 1e6)
    older = [dict(e, args={}) for e in events]  # a file from before parents
    by_containment = trace_summary.self_times(older)
    assert by_containment["unit/inner"]["self_s"] == 0.0
    assert sum(r["self_s"] for r in by_containment.values()) == pytest.approx(
        outer["dur"] / 1e6)
