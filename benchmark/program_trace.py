"""The program's own spans, read from the profiler's trace of the run.

The program marks time through one seam (``photon_ml_tpu/telemetry/
tracing.py`` ``span``): inside a profiler session every span is a host event
``photon:<name>`` whose attributes are the event's stats, on the clock the
device's ``XLA Ops`` are on. A traced run of the benchmark starts that
session itself, so its xplane file holds the program's spans beside the
benchmark's ``bench:`` ones. This module turns that file into plain lists
(``load``), and those into what the per-layer readers ask for
(``summarize``): the spans inside ``bench:window`` with their attributes,
self time by span name, and the device's idle stretches by the innermost
program span the host was in. The arithmetic runs the same on a small
recorded trace kept with the tests (``ctx["program_spans"]``).

A program that has no such spans (a parent commit from before them) gives a
trace with none: every function here then returns nothing and raises
nothing, and the readers leave their metric out.

Counters come from the program's default metrics registry (``total``): the
``timing/`` histograms ``Timed`` always fills, and the compile listener's
``jax/`` seconds. A metric that does not exist there reads as nothing.
"""

from __future__ import annotations

import bisect
import glob
import os
import statistics
import time

from benchmark.manifest import ROOT
from benchmark.trace_reduce import DEVICE_PLANE, union_intervals

PREFIX = "photon:"
WINDOW = "bench:window"
#: where benchmark/run.py lets the profiler write (trace-<workload>/...)
WORK_DIR = os.path.join(ROOT, ".bench_work")
OUTSIDE = "outside-any-program-span"

_parsed: dict = {}  # xplane path -> summary: one parse per process


def newest_xplane() -> "str | None":
    files = glob.glob(os.path.join(
        WORK_DIR, "trace-*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> dict:
    """{"window": [(start, dur)], "spans": [(name, start, dur, lane, attrs)],
    "devices": {k: [(name, start, dur)]}} from one xplane file, in ns: the
    ``bench:window`` events, the ``photon:`` events (prefix taken off, lane =
    the host thread's line), and every device's ``XLA Ops`` events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {"window": [], "spans": [], "devices": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["devices"][int(m.group(1))] = [
                        ("", float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for lane, line in enumerate(plane.lines):
                for e in line.events:
                    name = e.name
                    if name == WINDOW:
                        out["window"].append(
                            (float(e.start_ns), float(e.duration_ns)))
                    elif name.startswith(PREFIX):
                        out["spans"].append(
                            (name[len(PREFIX):], float(e.start_ns),
                             float(e.duration_ns), lane, dict(e.stats)))
    return out


def _self_times(spans) -> dict:
    """{name: seconds} — each span's duration less the part its direct
    children cover (spans of one lane nest: a stack sweep in start order)."""
    total: dict[str, float] = {}
    lanes: dict[int, list] = {}
    for span in spans:
        lanes.setdefault(span[3], []).append(span)
    for lane in lanes.values():
        lane.sort(key=lambda s: (s[1], -s[2]))
        stack: list[list] = []  # [end, name, self so far]

        def close(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                _, name, own = stack.pop()
                total[name] = total.get(name, 0.0) + max(0.0, own)

        for name, start, dur, _, _ in lane:
            close(start)
            if stack:
                stack[-1][2] -= dur
            stack.append([start + dur, name, dur])
        close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


def summarize(raw: dict) -> "dict | None":
    """What the readers ask for, from ``load``'s lists; None when the trace
    holds no window or no program span inside it."""
    if not raw["window"]:
        return None
    lo = min(s for s, _ in raw["window"])
    hi = max(s + d for s, d in raw["window"])
    spans = sorted((s for s in raw["spans"] if lo <= s[1] and s[1] + s[2] <= hi),
                   key=lambda s: s[1])
    if not spans:
        return None
    # the innermost program span at every instant: between two neighbouring
    # span edges it is one span, the shortest that covers the stretch
    cuts = sorted({s[1] for s in spans} | {s[1] + s[2] for s in spans})
    owners = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(d, name) for name, s, d, _, _ in spans if s <= a and b <= s + d]
        owners.append(min(covering)[1] if covering else OUTSIDE)
    idle: dict[str, float] = {}
    devices = {k: v for k, v in raw["devices"].items() if v}
    for ops in devices.values():
        busy = [(max(a, lo), min(b, hi)) for a, b in union_intervals(ops)
                if b > lo and a < hi]
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):  # the idle stretches
            if b <= a:
                continue
            i = bisect.bisect_right(cuts, 0.5 * (a + b)) - 1
            owner = owners[i] if 0 <= i < len(owners) else OUTSIDE
            idle[owner] = idle.get(owner, 0.0) + (b - a)
    n = max(1, len(devices))
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": spans,
        "self_s": _self_times(spans),
        "idle_s": dict(sorted(((k, v / 1e9 / n) for k, v in idle.items()),
                              key=lambda kv: -kv[1])),
    }


def of(ctx: dict) -> "dict | None":
    """The summary of this run's program spans: from ``ctx["program_spans"]``
    where a test supplies ``load``'s lists, else from the newest xplane file
    under the work directory (parsed once per process, after the window)."""
    if "program_spans" in ctx:
        return summarize(ctx["program_spans"])
    path = newest_xplane()
    if path is None:
        return None
    if path not in _parsed:
        t0 = time.perf_counter()
        summary = _parsed[path] = summarize(load(path))
        print(f"program spans: second parse of the trace "
              f"{time.perf_counter() - t0:.2f} s; idle seconds by program span: "
              + (" ".join(f"{k}={v:.4f}" for k, v in summary["idle_s"].items())
                 if summary else "no program span in the window"), flush=True)
    return _parsed[path]


# -- what the readers compute -------------------------------------------------


def each(summary: "dict | None", name: str) -> list:
    """The spans of that name inside the window; none without a summary."""
    return [] if summary is None else [s for s in summary["spans"] if s[0] == name]


def durations(summary: "dict | None", name: str) -> list[float]:
    """Seconds of every span of that name inside the window."""
    return [s[2] / 1e9 for s in each(summary, name)]


def median_of(values) -> "float | None":
    values = list(values)
    return statistics.median(values) if values else None


def inside(summary: dict, outer, name: str) -> list:
    """The spans of that name that lie inside the span ``outer``."""
    _, lo, dur, lane, _ = outer
    return [s for s in each(summary, name)
            if s[3] == lane and lo <= s[1] and s[1] + s[2] <= lo + dur]


def total(metric: str) -> "float | None":
    """The total of a histogram, or the value of a counter, in the program's
    default registry; None where the program keeps no such metric."""
    from photon_ml_tpu.telemetry.registry import default_registry

    snapshot = default_registry().snapshot()
    if metric in snapshot["histograms"]:
        return float(snapshot["histograms"][metric]["total"])
    if metric in snapshot["counters"]:
        return float(snapshot["counters"][metric])
    return None
