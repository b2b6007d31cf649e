#!/usr/bin/env python3
"""The readings a cell's limits and bound are set from (PERF.md 2), in one
process so that compiled programs are shared. Not part of a benchmark run.

For each of ``--seeds``: the SOUND program at the cell's own size, a warm
episode and ``--episodes`` timed ones (the seed's rate and episode times),
optionally one traced episode (``--trace-evals 1``: launches of the GLM
kernel per sweep), then every number ``correct`` compares. For each of
``--control-seeds``: the control, the program with its own bfloat16 feature
path on (the nearest precision below the float32 the configuration
states), one episode, compared the same way; the reference's own fit is
made for the first control seed only, the others read the numbers that do
not need it.

Prints one JSON line per fit, no limit applied.

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 --control-seeds 1,2,3
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def traced_kernel_calls(run, cell, spans) -> float:
    """GLM-kernel launches of one traced episode."""
    import jax

    from benchmark.trace_reduce import load_xplane, reduce_trace

    trace_dir = os.path.join(run.WORK_DIR, "trace-readings")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        run.measure(cell, spans, 0.0, 1)
    finally:
        jax.profiler.stop_trace()
    calls = reduce_trace(load_xplane(trace_dir))["kernel_calls"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    return calls


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.manifest import find_cell, load_manifest, load_module
    from benchmark.spans import Spans

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--episodes", type=int, default=3)
    parser.add_argument("--trace-evals", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    found = find_cell(load_manifest(), args.workload)
    run.configure_jax()
    devices = run.accelerator(int(found["cell"]["chips"]))
    if devices is None:
        print("no accelerator: refusing to take readings", file=sys.stderr)
        return run.EXIT_NO_CHIP
    driver = load_module(found["driver"])
    reference = load_module(found["reference"])

    plan = [(int(s), "float32") for s in args.seeds.split(",") if s] + [
        (int(s), "bfloat16") for s in args.control_seeds.split(",") if s]
    controls_fitted = 0
    for seed, dtype in plan:
        config = copy.deepcopy(found["config"])
        config["feature_dtype"] = dtype
        sound = dtype == "float32"
        spans = Spans()
        t0 = time.perf_counter()
        cell = driver.Cell(config, found["traffic"], seed, devices, spans)
        cell.episode()
        line = {"workload": args.workload, "seed": seed, "fit": "program-" + dtype,
                "setup_s": round(time.perf_counter() - t0, 2)}
        if sound:
            times, wall = run.measure(cell, spans, 0.0, args.episodes)
            line["episodes_s"] = [round(t, 4) for t in times]
            line.update({k: v for k, (v, _) in cell.end_to_end(times, wall).items()})
            if args.trace_evals:
                line["kernel_calls_per_episode"] = traced_kernel_calls(run, cell, spans)
        fit = sound or controls_fitted == 0
        controls_fitted += not sound
        t1 = time.perf_counter()
        compared = cell.verify(reference, cell.last, fit=fit)
        line["compare_s"] = round(time.perf_counter() - t1, 2)
        line["compared"] = {name: value for name, value, _ in compared}
        print(json.dumps(line), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
