#!/usr/bin/env python3
"""A benchmark cell at a share of its size and on a mesh of one's choosing,
with the instruments that name where a stuck run sits. Not a benchmark run:
it measures nothing the ledger takes. It is how the four-chip cell was
brought up (PERF.md 6, PR 27) and how to bring up the next one.

    chiprun --chips 4 --timeout 420 -- timeout 360 python3 dev/x4_rehearsal.py \\
        --workload glmix-ml20m-x4.sweeps --rows-fraction 0.25 --data 4 --trace 1

``--rows-fraction`` cuts rows, validation rows, users and the largest item
together (an item's rows shrink with the rows); ``--data`` is the mesh's
"data" axis and the number of chips asked for. At a quarter of the rows on
``--data 1`` the four-chip GLMix cell is the one-chip cell through the
four-chip driver.

What a stuck call leaves behind, on standard error, ``--watchdog`` seconds
after the start: the spans that are open (``Tracer.open_spans``), then every
thread's Python frames (``faulthandler``), then the process exits with 3. The
``photon_ml_tpu.timing`` logger runs at DEBUG all along, so the last
``begin`` line says what was entered last.
"""

from __future__ import annotations

import argparse
import copy
import faulthandler
import json
import logging
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def scaled(config: dict, fraction: float, data: int) -> dict:
    config = copy.deepcopy(config)
    tile = 1024 * data  # each chip's X in whole kernel tiles
    config["rows"] = max(tile, int(round(config["rows"] * fraction / tile)) * tile)
    config["validation_rows"] = max(
        data, int(round(config["validation_rows"] * fraction / data)) * data)
    config["users"]["count"] = max(1, int(config["users"]["count"] * fraction))
    config["items"]["max"] = max(int(config["items"]["min"]),
                                 int(round(config["items"]["max"] * fraction)))
    config["mesh"] = {"data": data, "model": 1}
    return config


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rows-fraction", type=float, default=1.0)
    parser.add_argument("--data", type=int, default=1)
    parser.add_argument("--seed", type=int, default=27001)
    parser.add_argument("--seconds", type=float, default=0.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--watchdog", type=float, default=330.0)
    args = parser.parse_args(argv)

    logging.basicConfig(stream=sys.stderr, level=logging.INFO,
                        format="%(relativeCreated)8.0f ms %(name)s %(message)s")
    logging.getLogger("photon_ml_tpu.timing").setLevel(logging.DEBUG)

    from benchmark import run
    from benchmark.manifest import find_cell, load_manifest
    from photon_ml_tpu.telemetry.tracing import Tracer, install_tracer

    tracer = install_tracer(Tracer(rank=0))

    def stuck() -> None:
        print(f"watchdog: {args.watchdog:.0f} s gone; open spans: "
              f"{tracer.open_spans()}", file=sys.stderr, flush=True)
        faulthandler.dump_traceback(all_threads=True)
        os._exit(3)

    timer = threading.Timer(args.watchdog, stuck)
    timer.daemon = True
    timer.start()

    manifest = load_manifest()
    found = find_cell(manifest, args.workload)
    found["config"] = scaled(found["config"], args.rows_fraction, args.data)
    print("rehearsal of", args.workload, "at", json.dumps(
        {k: found["config"][k] for k in ("rows", "validation_rows", "users",
                                         "items", "mesh")}), flush=True)
    run.configure_jax()
    devices = run.accelerator(args.data)
    if devices is None:
        print(f"no accelerator with {args.data} chip(s)", file=sys.stderr)
        return run.EXIT_NO_CHIP
    result = run.run_cell(found, manifest, args.seed, args.seconds,
                          bool(args.trace), devices)
    timer.cancel()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
