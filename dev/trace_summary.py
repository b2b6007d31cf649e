#!/usr/bin/env python
"""Offline run-trace digest: merge per-rank Chrome-trace files and print
the slow-run report a human pastes into an issue.

Input: a ``--trace-dir`` directory (or explicit ``trace-*.json`` paths)
written by ``telemetry/tracing.py``. Output, to stdout:

1. **Top spans by self-time** — per span name, total duration minus the
   time spent in directly nested spans on the same (rank, thread) lane,
   so an epoch that spends all its time inside accumulate steps does not
   double-count. This is where the wall-clock went.
2. **Per-rank exchange-wait table** — for every exchange tag (digit runs
   collapsed, so per-step/per-seq tags pool), each rank's total blocking
   wait plus the named straggler: the rank that arrived LAST (least
   wait — everyone else's wait is caused by it) or never arrived at all
   (wedged/crashed). This is WHO the wall-clock went to.

These files are the device-free per-rank timeline on the host's
``perf_counter``; the same spans stand in the profiler's trace
(``--profile-dir``) beside the device's operations, on the device's clock.

    python dev/trace_summary.py /path/to/trace-dir [--top 15]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict

# the merge rules live with the tracer so online (run-end exchange) and
# offline (this tool) reports cannot drift — incl. which span names count
# as exchange waits
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from photon_ml_tpu.telemetry.tracing import (  # noqa: E402
    _WAIT_SPAN_NAMES,
    normalize_tag,
    straggler_report,
)


def load_trace_events(path: str) -> list[dict]:
    """One file's complete ("X") events, with ``end`` precomputed."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        ev = dict(ev)
        ev["end"] = ev["ts"] + ev["dur"]
        out.append(ev)
    return out


def find_trace_files(paths: "list[str]") -> list[str]:
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(os.path.join(p, "trace-*.json"))))
        else:
            files.append(p)
    if not files:
        raise FileNotFoundError(f"no trace-*.json under {paths!r}")
    return files


def self_times(events: "list[dict]") -> dict[str, dict]:
    """Per span name: {"total_s", "self_s", "count"} — self time excludes
    directly nested spans on the same (pid, tid) lane; a child subtracts
    from its immediate parent only. A lane whose events record their
    ``parent`` (``args.parent`` / ``args.parent_ts``, written since the span
    seam keeps a stack of open spans) is resolved by it; a lane of an older
    file by containment (stack sweep over start-ordered intervals)."""
    lanes: dict[tuple, list[dict]] = defaultdict(list)
    for ev in events:
        lanes[(ev["pid"], ev["tid"])].append(ev)
    stats: dict[str, dict] = defaultdict(
        lambda: {"total_s": 0.0, "self_s": 0.0, "count": 0}
    )
    for lane in lanes.values():
        selfs: dict[int, float] = {id(ev): ev["dur"] for ev in lane}
        if any("parent" in (ev.get("args") or {}) for ev in lane):
            at = {(ev["name"], ev["ts"]): ev for ev in lane}
            for ev in lane:
                args = ev.get("args") or {}
                parent = at.get((args.get("parent"), args.get("parent_ts")))
                if parent is not None:
                    selfs[id(parent)] -= ev["dur"]
        else:
            lane.sort(key=lambda e: (e["ts"], -e["dur"]))
            stack: list[dict] = []
            for ev in lane:
                while stack and stack[-1]["end"] <= ev["ts"]:
                    stack.pop()
                if stack:
                    selfs[id(stack[-1])] -= ev["dur"]
                stack.append(ev)
        for ev in lane:
            row = stats[ev["name"]]
            row["total_s"] += ev["dur"] / 1e6
            row["self_s"] += max(0.0, selfs[id(ev)]) / 1e6
            row["count"] += 1
    return dict(stats)


def exchange_wait_tables(events: "list[dict]") -> dict[int, dict]:
    """{rank: {tag: {"count", "wait_s", "max_s"}}} from merged events —
    the offline twin of tracing.exchange_wait_tables (rank from the span's
    ``rank`` arg, falling back to the file's pid)."""
    tables: dict[int, dict] = {}
    for ev in events:
        if ev["name"] not in _WAIT_SPAN_NAMES:
            continue
        args = ev.get("args") or {}
        rank = int(args.get("rank", ev["pid"]))
        tag = normalize_tag(str(args.get("tag", "")))
        row = tables.setdefault(rank, {}).setdefault(
            tag, {"count": 0, "wait_s": 0.0, "max_s": 0.0}
        )
        dur_s = ev["dur"] / 1e6
        row["count"] += 1
        row["wait_s"] += dur_s
        row["max_s"] = max(row["max_s"], dur_s)
    return tables


def format_report(events: "list[dict]", *, top: int = 15) -> str:
    lines: list[str] = []
    stats = self_times(events)
    ranked = sorted(stats.items(), key=lambda kv: -kv[1]["self_s"])[:top]
    lines.append(f"top {len(ranked)} spans by self-time")
    lines.append(f"{'span':<36} {'self s':>10} {'total s':>10} {'count':>8}")
    for name, row in ranked:
        lines.append(
            f"{name:<36} {row['self_s']:>10.3f} {row['total_s']:>10.3f} "
            f"{row['count']:>8d}"
        )

    tables = exchange_wait_tables(events)
    if tables:
        report = straggler_report(tables)
        n = report["num_ranks"]
        lines.append("")
        lines.append("per-rank exchange wait (s) — straggler = rank others "
                     "waited for (least wait / never arrived)")
        header = f"{'tag':<40}" + "".join(
            f"{f'rank {r}':>10}" for r in range(n)
        ) + "  straggler"
        lines.append(header)
        for row in report["tags"]:
            waits = "".join(
                f"{'-':>10}" if w is None else f"{w:>10.3f}"
                for w in row["wait_s"]
            )
            who = (
                "-" if row["straggler_rank"] is None
                else f"rank {row['straggler_rank']} ({row['reason']})"
            )
            lines.append(f"{row['tag']:<40}{waits}  {who}")
    else:
        lines.append("")
        lines.append("no exchange spans (single-rank or untraced exchanges)")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="+",
                   help="trace dir(s) or trace-*.json files")
    p.add_argument("--top", type=int, default=15,
                   help="how many spans in the self-time table")
    args = p.parse_args(argv)
    events: list[dict] = []
    for f in find_trace_files(args.paths):
        events.extend(load_trace_events(f))
    print(format_report(events, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
