#!/usr/bin/env python
"""Run doctor: one offline findings report over what a run left behind.

A reader of run journals, program-ledger rows and per-rank traces. Takes a
directory holding any mix of JSONL run journals (``run-journal.jsonl`` and
friends — with ``--live`` also their crash-durable ``.partial`` stage
files) and per-rank ``trace-*.json`` files, and emits ONE report:

- registry-counter cross-checks from each journal's snapshot
  (telemetry/verdicts.py: overlap_fraction ~ 0 with prefetch on, high
  serve pad fraction, quarantined blocks, preemption restarts, exhausted
  restart budgets) plus the last heartbeat cursor and failure rows of a
  crashed/in-flight run;
- the per-program compiled-program ledger table (ISSUE 13): per labeled
  jit program — calls, compiles, recompiles, signatures, compile seconds,
  flops, peak bytes, with each label's LAST recompile attribution (the
  exact signature leaves that changed), plus heartbeat staleness and
  hbm/compile drift so a wedged run is distinguishable from a slow one;
- the lanes' accounts of a fused fit (``lane_counts`` rows, one a sweep,
  parallel/distributed.py): per coordinate the LAST sweep's buckets, lanes
  and valid ones, lock-step trials and outer trips, the rows the searches
  paid for against the rows a live lane wanted, the lanes by why they
  stopped, and the trials and that share sweep by sweep;
- the straggler table from the per-rank trace files (dev/trace_summary.py
  machinery — online and offline reports share one implementation);
- the cross-rank coordinated-recovery table (ISSUE 15): per-rank
  restarts/aborts/generations merged over EVERY rank's journal, the
  restart-storm pathology naming a flapping culprit rank, and (with
  ``--live``) the last abort marker seen.

It says nothing about speed: that is ``benchmark/run.py`` in the cells of
``BENCHMARK.json``, with the numbers in ``PERF.md``. A directory that holds
none of the files above (only ``BENCH_r*.json`` captures of the pre-chip
regime, say) is reported as holding nothing the doctor reads.

Exit status: 0, unless ``--strict`` is given and a finding has status
``pathology`` or ``warning`` (an unclosed journal, a failed run, a
recompile storm, a restart storm, ...): then 1.

Point it at a run's ``--telemetry-dir`` / ``--trace-dir``:

    python -m dev.doctor [RUN_DIR] [--live] [--strict] [--json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from photon_ml_tpu.telemetry import verdicts  # noqa: E402
from photon_ml_tpu.telemetry.journal import (  # noqa: E402
    JOURNAL_PARTIAL_SUFFIX as PARTIAL_SUFFIX,
    heartbeat_cursor,
    read_journal,
)

#: journal basenames the doctor looks for (plus their .partial stages)
JOURNAL_GLOB = "*.jsonl"


def _find_journals(directory: str, live: bool) -> list[str]:
    paths = sorted(glob.glob(os.path.join(directory, JOURNAL_GLOB)))
    if live:
        finalized = {os.path.basename(p) for p in paths}
        for p in sorted(glob.glob(
            os.path.join(directory, JOURNAL_GLOB + PARTIAL_SUFFIX)
        )):
            # a finalized journal supersedes its own leftover stage file
            if os.path.basename(p)[: -len(PARTIAL_SUFFIX)] not in finalized:
                paths.append(p)
    return paths


def _journal_section(path: str, live: bool) -> tuple[list, list[str], list]:
    """(findings, report lines, parsed records) for one journal file."""
    records = read_journal(path, tolerant=True)
    lines = [f"-- {os.path.basename(path)}: {len(records)} row(s)"]
    findings = verdicts.journal_findings(records)
    if records:
        last = records[-1]
        age = time.time() - float(last.get("ts", time.time()))
        if path.endswith(PARTIAL_SUFFIX) or live:
            lines.append(
                f"   last row: kind={last.get('kind')} seq={last.get('seq')} "
                f"({age:.1f}s ago)"
            )
        heartbeats = [r for r in records if r.get("kind") == "heartbeat"]
        if heartbeats:
            hb = heartbeats[-1]
            lines.append(f"   last heartbeat: {heartbeat_cursor(hb)}")
            if path.endswith(PARTIAL_SUFFIX) or live:
                # staleness is a LIVE signal: a wedged run's newest
                # heartbeat goes stale while a merely slow run's keeps
                # advancing — meaningless for a finalized journal, whose
                # age just says when the run happened
                staleness = time.time() - float(hb.get("ts", time.time()))
                lines.append(
                    f"   heartbeat staleness: {staleness:.1f}s since the "
                    f"newest of {len(heartbeats)} heartbeat(s) "
                    f"(seq {hb.get('seq')})"
                )
                drift = _heartbeat_drift(heartbeats)
                if drift:
                    lines.append(f"   heartbeat drift: {drift}")
    lines.extend(_ledger_table(records))
    lines.extend(_lane_table(records))
    return findings, lines, records


def _heartbeat_drift(heartbeats: list) -> str:
    """first -> last movement of the device-memory and compile-count
    snapshots heartbeat rows carry (ISSUE 13): live-HBM drift and a mid-run
    compile storm both show up here before the run ends."""
    first, last = heartbeats[0], heartbeats[-1]
    parts = []
    if first.get("hbm_bytes") is not None or last.get("hbm_bytes") is not None:
        parts.append(
            f"hbm_bytes {first.get('hbm_bytes')} -> {last.get('hbm_bytes')}"
        )
    if first.get("compiles") is not None or last.get("compiles") is not None:
        parts.append(
            f"compiles {first.get('compiles')} -> {last.get('compiles')}"
        )
    return ", ".join(parts)


def _ledger_table(records: list) -> list[str]:
    """The per-program ledger table (ISSUE 13): one row per labeled
    program from the journal's program_compile/program_signature/
    program_recompile rows, with each label's last recompile attribution
    underneath — the 'compile count went up' number next to its cause."""
    per_label: dict[str, dict] = {}
    for r in records:
        kind = r.get("kind")
        if kind not in ("program_compile", "program_signature",
                        "program_recompile"):
            continue
        label = str(r.get("label"))
        ent = per_label.setdefault(label, {
            "compiles": 0, "recompiles": 0, "compile_s": 0.0,
            "flops": None, "peak_bytes": None, "forecast": None,
            "attribution": None,
        })
        if kind == "program_recompile":
            ent["recompiles"] += 1
            ent["attribution"] = r.get("summary")
            continue
        if kind == "program_compile":
            ent["compiles"] += int(r.get("compiles") or 0)
            ent["compile_s"] += float(r.get("compile_seconds") or 0.0)
        cost = r.get("cost") or {}
        if cost.get("flops") is not None:
            ent["flops"] = cost["flops"]
        mem = r.get("memory") or {}
        peak = mem.get("peak_memory_in_bytes", mem.get("temp_size_in_bytes"))
        if peak is not None:
            ent["peak_bytes"] = peak
        if r.get("hbm_forecast_bytes") is not None:
            ent["forecast"] = r["hbm_forecast_bytes"]
    if not per_label:
        return []
    # calls/signatures ride the final metrics snapshot when one was taken
    metrics = next((r for r in reversed(records) if r.get("kind") == "metrics"),
                   None)
    snapshot = (metrics or {}).get("snapshot") or {}
    counters = snapshot.get("counters") or {}
    gauges = snapshot.get("gauges") or {}

    def _fmt(v, unit=""):
        return "-" if v is None else f"{v:g}{unit}"

    lines = [f"   program ledger ({len(per_label)} labeled program(s)):"]
    header = (f"   {'label':<38} {'calls':>6} {'compiles':>8} "
              f"{'recomp':>6} {'sigs':>5} {'compile_s':>9} "
              f"{'flops':>10} {'peak_B':>10} {'fcast_B':>10}")
    lines.append(header)
    for label in sorted(per_label):
        ent = per_label[label]
        calls = counters.get(f"xla/{label}/calls")
        sigs = gauges.get(f"xla/{label}/signatures")
        lines.append(
            f"   {label:<38} {_fmt(calls):>6} {ent['compiles']:>8} "
            f"{ent['recompiles']:>6} {_fmt(sigs):>5} "
            f"{ent['compile_s']:>9.3f} {_fmt(ent['flops']):>10} "
            f"{_fmt(ent['peak_bytes']):>10} {_fmt(ent['forecast']):>10}"
        )
        if ent["attribution"]:
            lines.append(f"      last recompile: {ent['attribution']}")
    return lines


#: the columns of a ``lane_counts`` bucket that :func:`_lane_table` sums
_LANE_COUNTS = (
    "lane_solves", "lockstep_trials", "lockstep_iterations",
    "lanes_max_iterations", "lanes_function_tolerance",
    "lanes_gradient_tolerance", "lanes_search_failed")


def _lane_table(records: list) -> list[str]:
    """The lanes' accounts by coordinate, from the journal's ``lane_counts``
    rows (one a fused sweep: every bucket solve's counts,
    optim/common.BUCKET_COUNT_NAMES, with its lanes and cap): the newest
    sweep in full and, from every row, the lock-step trials and the share
    wanted of paid by sweep (the newest dozen). Rows paid = lock-step trials
    x lanes x cap, wanted = the valid lanes' own trials x cap: the share
    says how much of a search's work any lane asked for."""
    rows = [r for r in records if r.get("kind") == "lane_counts"]
    if not rows:
        return []

    def by_coordinate(row) -> dict[str, dict]:
        totals: dict[str, dict] = {}
        for b in row.get("buckets") or []:
            t = totals.setdefault(str(b.get("coordinate")), dict.fromkeys(
                ("buckets", "paid", "wanted", "lanes", *_LANE_COUNTS), 0))
            t["buckets"] += 1
            t["lanes"] += int(b["lanes"])
            t["paid"] += int(b["lockstep_trials"]) * int(b["lanes"]) * int(b["cap"])
            t["wanted"] += int(b["lane_trials"]) * int(b["cap"])
            for key in _LANE_COUNTS:
                t[key] += int(b.get(key) or 0)
        return totals

    def share(t) -> str:
        return f"{100.0 * t['wanted'] / t['paid']:.2f}%" if t["paid"] else "-"

    sweeps = [by_coordinate(r) for r in rows]
    lines = [f"   lanes, sweep {rows[-1].get('sweep')} of {len(rows)} journaled "
             "(trials and trips in lock-step; stops: cap/function/gradient/failed):"]
    for name, t in sweeps[-1].items():
        lines.append(
            f"   {name:<24} buckets {t['buckets']:>3} lanes {t['lanes']:>8} "
            f"valid {t['lane_solves']:>8} trials {t['lockstep_trials']:>6} "
            f"trips {t['lockstep_iterations']:>5} wanted/paid {share(t)} "
            "stops " + "/".join(str(t[k]) for k in _LANE_COUNTS[3:]))
        past = [s[name] for s in sweeps[-12:] if name in s]
        lines.append(
            f"   {'':<24} by sweep: trials "
            + " ".join(str(p["lockstep_trials"]) for p in past)
            + "; wanted/paid " + " ".join(share(p) for p in past))
    return lines


def _trace_section(directory: str) -> list[str]:
    try:
        from dev import trace_summary
    except ImportError:  # running as a loose script next to trace_summary
        import trace_summary  # type: ignore[no-redef]
    files = sorted(glob.glob(os.path.join(directory, "trace-*.json")))
    if not files:
        return []
    events: list[dict] = []
    unreadable: list[str] = []
    for f in files:
        try:
            events.extend(trace_summary.load_trace_events(f))
        except (OSError, ValueError):
            # a SIGKILL'd rank can leave a torn trace file — keep the
            # healthy ranks' evidence, name the torn one
            unreadable.append(os.path.basename(f))
    lines = [f"-- {len(files)} trace file(s), {len(events)} event(s)"]
    if unreadable:
        lines.append(f"   unreadable (torn mid-write?): {unreadable}")
    if events:
        lines.extend(trace_summary.format_report(events, top=5).splitlines())
    return lines


def run_doctor(
    directory: str,
    *,
    live: bool = False,
    strict: bool = False,
) -> tuple[int, list, str]:
    """The doctor's whole pass: returns (exit_code, findings, report_text).

    Importable so tests judge findings structurally; ``main`` wraps it.
    """
    lines: list[str] = [f"run doctor: {os.path.abspath(directory)}"]
    findings: list = []

    journal_paths = _find_journals(directory, live)
    merged_records: list = []
    if journal_paths:
        lines.append("")
        lines.append("== run journals ==")
        for path in journal_paths:
            try:
                jf, jl, records = _journal_section(path, live)
            except OSError as e:
                lines.append(f"-- {path}: unreadable ({e})")
                continue
            merged_records.extend(records)
            findings.extend(jf)
            lines.extend(jl)
            for v in jf:
                lines.append(v.line())

    # coordinated recovery is a CROSS-journal story (ISSUE 15): the
    # per-rank restart table and the restart-storm attribution only make
    # sense over every rank's journal merged
    coord = verdicts.coordination_findings(merged_records)
    if coord:
        lines.append("")
        lines.append("== coordinated recovery ==")
        findings.extend(coord)
        for v in coord:
            lines.append(v.line())
    if live:
        marker = verdicts.last_abort_marker(merged_records)
        if marker is not None:
            lines.append(
                "   last abort marker: "
                f"kind={marker.get('kind')} rank={marker.get('rank')} "
                f"origin_rank={marker.get('origin_rank', marker.get('rank'))} "
                f"generation={marker.get('generation')} "
                f"cause={marker.get('origin_cause', marker.get('cause'))}"
            )

    trace_lines = _trace_section(directory)
    if trace_lines:
        lines.append("")
        lines.append("== traces ==")
        lines.extend(trace_lines)

    if not journal_paths and not trace_lines:
        lines.append("(no run journals or trace files here: nothing the "
                     "doctor reads)")

    gating = [
        v for v in findings
        if v.status in (verdicts.PATHOLOGY, verdicts.WARNING)
    ]
    lines.append("")
    if gating:
        lines.append(f"PATHOLOGIES/WARNINGS ({len(gating)}, "
                     "exit 1 under --strict):")
        for v in gating:
            lines.append(f"  {v.metric} [{v.rule}]: {v.detail}")
    else:
        lines.append("PATHOLOGIES/WARNINGS: none")
    return (1 if strict and gating else 0), findings, "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("directory", nargs="?", default=".",
                   help="run directory (journals + traces); default: cwd")
    p.add_argument("--live", action="store_true",
                   help="also tail crash-durable .partial journal stages "
                        "(a wedged run's evidence before close)")
    p.add_argument("--strict", action="store_true",
                   help="the gate: exit 1 iff a finding has status "
                        "pathology or warning (without it the exit is 0)")
    p.add_argument("--json", action="store_true",
                   help="emit findings as one JSON object instead of text")
    args = p.parse_args(argv)
    code, findings, text = run_doctor(
        args.directory, live=args.live, strict=args.strict
    )
    if args.json:
        print(json.dumps({
            "exit_code": code,
            "findings": [vars(v) for v in findings],
        }, indent=2))
    else:
        print(text)
    return code


if __name__ == "__main__":
    sys.exit(main())
