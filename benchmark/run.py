#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix, driver, reference and per-layer
metric readers by the names in ``BENCHMARK.json``; makes its inputs from
``--seed``; warms every shape the cell uses (set-up); measures whole
episodes until ``--seconds`` have passed and at least the traffic's
``min_episodes`` are done; then, outside the window, holds what the LAST
timed episode produced against the configuration's plain reference. The
last line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: every number compared beside its limit, which are also the
run's last lines on standard error).

A driver (``drivers/<kind>.py``, named by the traffic file's ``kind``) gives
``Cell(config, traffic, seed, devices, spans)`` with ``episode()`` (the timed
path; what it produced stays in ``last``), ``end_to_end(episode_seconds,
window_seconds)`` -> {name: (value, unit)}, ``counters()`` -> the program's
own counts for the per-layer readers (``read_counters`` is set on traced
runs: a count that costs a host read is taken only then) and
``verify(reference, produced)`` -> [(name, value, limit)].

It refuses to run without the accelerator the cell asks for (exit 2, no
result line): a time from a CPU is not a device metric.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
#: work files of a run (the trace), inside the checkout and git-ignored
WORK_DIR = os.path.join(ROOT, ".bench_work")
#: fixed path: the directory is part of the cache's key. Set before jax is
#: imported, for the program too (its compile_cache helper takes the
#: variable and sets no path of its own).
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "benchmark")
EXIT_NO_CHIP = 2


def configure_jax():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    # store every program, however quickly it compiled (PERF.md 6, PR 21
    # finding 8: with the default floor only 11 of 94 programs were stored)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


#: seconds inside ``jax.devices()``: the TPU runtime's own start. Taken OUT of
#: ``setup_s`` and printed beside it: 6 s on a fresh machine, up to 15 s after
#: processes that used the chip, none of it this repository's code (PERF.md
#: 2), where a dense cell's whole set-up is 9 s.
_chip_start_s = 0.0


def accelerator(chips: int):
    """The devices of the run, or None when the cell's chips are not there."""
    global _chip_start_s
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()
    _chip_start_s = time.perf_counter() - t0
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices[:chips]


class CompileCounter:
    """Programs the backend really compiled: misses of the persistent cache,
    by JAX's own monitoring events. (``requests`` also counts the look-ups
    that hit: a program that re-traces a solve on every call asks again
    every time, and is answered from the cache.)"""

    def __init__(self):
        import jax.monitoring

        self.count = self.requests = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, *_args, **_kwargs) -> None:
        if name == "/jax/compilation_cache/cache_misses":
            self.count += 1
        elif name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    if any(p is None for p in peaks):
        # the CPU backend keeps no such statistic (tests drive run_cell
        # there); on the accelerator its absence is an error, not a zero
        if devices[0].platform == "tpu":
            raise RuntimeError("a device reports no peak_bytes_in_use")
        peaks = [0]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def measure(cell, spans, seconds: float, min_episodes: int):
    """Whole episodes, back to back, until the time is up and enough are
    done (``seconds`` 0: exactly ``min_episodes``): each episode's wall
    seconds, and the wall seconds of the window they fill, first start to
    last end. Never divides by ``seconds``."""
    times: list[float] = []
    with spans.span("window"):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds or len(times) < min_episodes:
            t0 = time.perf_counter()
            cell.episode()
            times.append(time.perf_counter() - t0)
        wall = time.perf_counter() - start
    return times, wall


def setup_layer_metrics(manifest: dict, workload: str, spans) -> dict:
    """The cell's per-layer metrics that move ``setup_s``, read where set-up
    ends: a program that traces or loads inside the window too (a fit that
    builds its solves anew on every call) would add the window's seconds to
    a reading taken after it."""
    from benchmark.manifest import layer_metric_reader, metrics_of

    return {m["name"]: layer_metric_reader(m["name"])({"spans": spans, "counters": {}})
            for m in metrics_of(manifest, "per_layer", workload, {"setup_s"})
            if m["moves"] == "setup_s"}


def run_cell(found: dict, manifest: dict, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Everything of a run but the look for a chip; returns the result line."""
    import jax

    from benchmark import compare
    from benchmark.manifest import layer_metric_reader, load_module, metrics_of
    from benchmark.spans import Spans

    workload = found["cell"]["name"]
    spans = Spans()
    compiles = CompileCounter()
    driver = load_module(found["driver"])
    cell = driver.Cell(found["config"], found["traffic"], seed, devices, spans)
    with spans.span("warm"):
        cell.episode()
    cell.read_counters = trace  # counters that cost a host read: traced runs only
    setup_s = time.perf_counter() - _PROCESS_START - _chip_start_s
    setup_metrics = setup_layer_metrics(manifest, workload, spans) if trace else {}
    compiles_before, requests_before = compiles.count, compiles.requests
    trace_dir = os.path.join(WORK_DIR, "trace-" + workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the spans are TraceAnnotations
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    window_start = time.perf_counter()
    try:
        # a traced window is a few episodes, whatever --seconds says: traces
        # are large, and the per-layer metrics want steady episodes, not many
        times, wall = (
            measure(cell, spans, 0.0, int(found["traffic"]["traced_episodes"]))
            if trace else
            measure(cell, spans, seconds, int(found["traffic"]["min_episodes"])))
    finally:
        if trace:
            jax.profiler.stop_trace()
    produced = cell.last
    device = device_record(devices)  # the program's peak, before the reference

    e2e = {name: {"value": value, "unit": unit}
           for name, (value, unit) in cell.end_to_end(times, wall).items()}
    e2e["setup_s"] = {"value": setup_s, "unit": "s"}
    warm_start = next(s for n, s, _ in spans.closed if n == "warm")
    print("set-up spans: " + " ".join(
        f"{n}={e - s:.2f}" for n, s, e in spans.closed
        if n == "warm" or s < warm_start), flush=True)
    print(f"compiled in the window: {compiles.count - compiles_before} "
          f"(cache look-ups {compiles.requests - requests_before})", flush=True)
    print(f"episodes {len(times)} in {wall:.4f} s: "
          + " ".join(f"{t:.4f}" for t in times), flush=True)
    slow_from = 1.5 * sorted(times)[len(times) // 2]
    for name, start, end in spans.closed:  # what a far-off episode spent its time in
        if name == "episode" and start >= window_start and end - start > slow_from:
            inner = [(n, e - s) for n, s, e in spans.closed
                     if n != "episode" and start <= s and e <= end]
            print(f"far-off episode of {end - start:.2f} s: " + " ".join(
                f"{n}={d:.2f}" for n, d in inner), flush=True)
    result = {"attempted": len(times), "failed": 0, "device": device}
    if trace:
        from benchmark.trace_reduce import load_xplane, reduce_trace

        reduced = reduce_trace(load_xplane(trace_dir))
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        print("trace: " + json.dumps({k: v for k, v in reduced.items()
                                      if not isinstance(v, list)}), flush=True)
        context = {
            "trace": reduced, "spans": spans, "window_start": window_start,
            "device": device,
            "counters": {**cell.counters(),
                         "compiles_in_window": compiles.count - compiles_before},
        }
        metrics = {}
        for m in metrics_of(manifest, "per_layer", workload, set(e2e)):
            value = (setup_metrics[m["name"]] if m["name"] in setup_metrics
                     else layer_metric_reader(m["name"])(context))
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": [["module:" + k, v] for k, v in reduced["device_modules"][:4]]
            + [["op:" + k, v] for k, v in reduced["device_ops"][:6]],
            "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:10]],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        wanted = {m["name"] for m in metrics_of(manifest, "end_to_end", workload, set())}
        result["metrics"] = {k: v for k, v in e2e.items() if k in wanted}

    reference = load_module(found["reference"])
    t0 = time.perf_counter()
    comparisons = cell.verify(reference, produced)
    print(f"reference and comparison: {time.perf_counter() - t0:.1f} s; host peak "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} GiB",
          flush=True)
    result["correct"] = compare.judge(comparisons)
    # every number compared beside its limit, last in the line
    result["compared"] = {
        name: {"value": float(value) if math.isfinite(value) else None,  # JSON has no NaN
               "limit": limit}
        for name, value, limit in comparisons}
    return {k: result[k] for k in ("correct", "attempted", "failed", "metrics",
                                   "device", "breakdown", "compared") if k in result}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark.manifest import find_cell, load_manifest

    manifest = load_manifest()
    found = find_cell(manifest, args.workload)
    configure_jax()
    devices = accelerator(int(found["cell"]["chips"]))
    if devices is None:
        print(f"no accelerator: cell {args.workload} needs "
              f"{found['cell']['chips']} TPU chip(s); refusing to measure",
              file=sys.stderr)
        return EXIT_NO_CHIP
    # what no cell can shorten: interpreter and imports (in setup_s), the chip
    print(f"process start to devices: {time.perf_counter() - _PROCESS_START:.2f} s, "
          f"of which the chip's start, not in setup_s: {_chip_start_s:.2f}", flush=True)
    result = run_cell(found, manifest, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
