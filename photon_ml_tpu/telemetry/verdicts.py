"""Findings over what a run leaves behind: run-journal rows, the program
ledger's rows, and the cross-rank recovery rows, each judged by a named
check with the counter's value in the detail.

No reference analogue: the reference ships no run diagnostics beyond the
Spark UI. dev/doctor.py is the reader; the checks live here so tests and
other tools can call them on parsed rows without a directory.

- :func:`journal_findings` cross-checks ONE journal's registry snapshot
  (overlap_fraction ~ 0 with prefetch on, high serve pad_fraction,
  quarantined blocks, preemption restarts, exhausted restart budgets, the
  journaled straggler table, a journal that never closed) and, through
  :func:`_ledger_findings`, the program ledger's compile pathologies —
  recompile storms with their attributed cause, signature churn,
  compile-dominated runs, HBM overcommit forecasts (ISSUE 13).
- :func:`coordination_findings` reads the MERGED rows of every rank's
  journal: the per-rank restart table and the restart-storm pathology
  (ISSUE 15); :func:`last_abort_marker` is what ``doctor --live`` prints
  while a run is wedged mid-restart.

Statuses: ``info`` is a fact the operator reads; ``warning`` and
``pathology`` are what ``dev.doctor --strict`` fails on.
"""

from __future__ import annotations

import dataclasses

from photon_ml_tpu.telemetry.journal import heartbeat_cursor

# verdict statuses
INFO = "info"
PATHOLOGY = "pathology"     # known bad signature, named cause
WARNING = "warning"


@dataclasses.dataclass
class Verdict:
    """One finding: a row/artifact/journal fact plus the rule that judged it."""

    metric: str
    rule: str
    status: str
    detail: str
    round: int | None = None

    def line(self) -> str:
        tag = f"r{self.round}" if self.round is not None else "-"
        return f"{self.status.upper():10s} {tag:>4s}  {self.metric}: {self.detail}"


# -- run-journal cross-checks ------------------------------------------------

#: serve/pad_fraction above this wastes most of every micro-batch on pads
PAD_FRACTION_HIGH = 0.5

#: program-ledger pathology thresholds (ISSUE 13; telemetry/program_ledger):
#: a storm is REDUNDANT compiles — compiles beyond the label's distinct
#: signature count, i.e. the same program compiled again (a program
#: instance rebuilt per step, or executable-cache eviction). Healthy
#: bounded ladders can never trip this no matter how many coordinates
#: share a label (serving's 3 shape buckets, the 5 RE entity caps, one
#: ladder per coordinate): every warm-up compile mints a NEW signature,
#: so compiles == signatures and the redundancy is zero.
RECOMPILE_STORM_REDUNDANT_MIN = 3
#: distinct signatures under one label at/past this is churn — each one is
#: a resident executable and a paid compile. A WARNING, not a pathology:
#: a label shared across coordinates/buckets legitimately carries one
#: signature per (coordinate, bucket) pair — compare the count against
#: your configured ladder before acting
SIGNATURE_CHURN_MIN = 8
#: fraction of run wall-clock spent in backend compiles past which the run
#: is compile-dominated (a cold persistent cache or per-shape recompiles
#: are the usual causes); only judged on runs longer than the floor, so tiny
#: fixture runs don't all report it
COMPILE_DOMINATED_FRACTION = 0.5
COMPILE_DOMINATED_MIN_ELAPSED_S = 30.0


def _last_row(records: list, kind: str) -> dict | None:
    for row in reversed(records):
        if row.get("kind") == kind:
            return row
    return None


def journal_findings(records: list) -> list:
    """Registry-counter cross-checks over parsed run-journal rows (the
    doctor's journal half): every check is a named signature from the
    measured-facts list, with the counter value in the detail."""
    findings: list[Verdict] = []
    if not records:
        return findings
    config = _last_row(records, "config") or {}
    metrics = _last_row(records, "metrics") or {}
    snapshot = metrics.get("snapshot") or {}
    counters = snapshot.get("counters") or {}
    gauges = snapshot.get("gauges") or {}

    closed = _last_row(records, "journal_close") is not None
    hb = _last_row(records, "heartbeat")
    if not closed:
        detail = "journal never finalized — the run crashed or is in flight"
        if hb is not None:
            detail += f"; last heartbeat cursor {heartbeat_cursor(hb)}"
        findings.append(Verdict("journal", "journal-finalized", WARNING,
                                detail))
    failure = _last_row(records, "run_failure")
    if failure is not None:
        findings.append(Verdict(
            "journal", "run-failure", WARNING,
            f"run failed: {failure.get('error')} "
            f"(transient={failure.get('transient')}, "
            f"preemption={failure.get('preemption')}, "
            f"restarts_used={failure.get('restarts_used')})",
        ))

    overlap = gauges.get("stream/overlap_fraction")
    chunks = gauges.get("stream/chunks_per_epoch")
    prefetch_on = config.get("streaming_prefetch", True)
    if (
        overlap is not None and overlap < 0.01
        and prefetch_on and (chunks or 0) > 1
    ):
        findings.append(Verdict(
            "stream/overlap_fraction", "overlap-with-prefetch-on", PATHOLOGY,
            f"overlap_fraction={overlap:g} with prefetch on over "
            f"{int(chunks)} chunks/epoch — decode hid nothing; expected "
            "only when compute contends for the decoding host cores (the "
            "CPU mesh), not on a chip",
        ))
    pad = gauges.get("serve/pad_fraction")
    if pad is not None and pad > PAD_FRACTION_HIGH:
        findings.append(Verdict(
            "serve/pad_fraction", "pad-fraction-high", WARNING,
            f"pad_fraction={pad:g}: most scored rows are padding — shrink "
            "the micro-batch shape buckets toward the real request sizes",
        ))
    quarantined = counters.get("resilience/quarantined_blocks", 0)
    if quarantined:
        findings.append(Verdict(
            "resilience/quarantined_blocks", "quarantine-nonzero", WARNING,
            f"{quarantined} corrupt block(s) quarantined (skip-and-count; "
            "spans in the quarantined_block journal rows)",
        ))
    preemptions = counters.get("resilience/preemptions", 0)
    restores = counters.get("resilience/checkpoint_restores", 0)
    if preemptions or restores:
        findings.append(Verdict(
            "resilience/preemptions", "preemption-restarts", INFO,
            f"{preemptions} preemption(s), {restores} checkpoint "
            f"restore(s), {counters.get('resilience/epochs_resumed', 0)} "
            "epochs/sweeps not redone",
        ))
    giveups = counters.get("resilience/giveups", 0)
    if giveups:
        findings.append(Verdict(
            "resilience/giveups", "restart-budget-exhausted", WARNING,
            f"{giveups} giveup(s): the restart budget ran out — the run "
            "ended on an error recovery could not absorb",
        ))
    findings.extend(_ledger_findings(records, counters, gauges, snapshot))
    straggler = _last_row(records, "straggler_report")
    if straggler is not None:
        # the PR 9 shape: {"num_ranks": N, "tags": [{tag, wait_s, count,
        # missing_ranks, straggler_rank, reason}, ...]} sorted worst-first
        tags = straggler.get("tags") or []
        named = [
            f"{t.get('tag')}: rank {t.get('straggler_rank')} "
            f"({t.get('reason')})"
            for t in tags
            if t.get("straggler_rank") is not None
        ][:5]
        findings.append(Verdict(
            "straggler_report", "straggler-attribution",
            WARNING if any(
                t.get("reason") == "never_arrived" for t in tags
            ) else INFO,
            f"straggler table over {len(tags)} exchange tag(s): "
            + ("; ".join(named) if named else "no stragglers named"),
        ))
    return findings


def _ledger_findings(records: list, counters: dict, gauges: dict,
                     snapshot: dict) -> list:
    """Program-ledger pathologies (ISSUE 13) over the journal's metrics
    snapshot + program_* rows: recompile storms (with the last attributed
    cause), signature churn, compile-seconds-dominated runs, and HBM
    overcommit forecasts."""
    findings: list[Verdict] = []
    last_attribution: dict[str, str] = {}
    for row in records:
        if row.get("kind") == "program_recompile" and row.get("label"):
            last_attribution[row["label"]] = str(row.get("summary"))
    for key, value in sorted(counters.items()):
        # NB "/recompiles" also endswith "/compiles" — exclude it first
        if (
            not key.startswith("xla/")
            or not key.endswith("/compiles")
            or key.endswith("/recompiles")
        ):
            continue
        label = key[len("xla/"):-len("/compiles")]
        sigs = gauges.get(f"xla/{label}/signatures")
        if sigs is None:
            continue
        redundant = value - int(sigs)
        if redundant >= RECOMPILE_STORM_REDUNDANT_MIN:
            cause = last_attribution.get(label)
            findings.append(Verdict(
                key, "recompile-storm", PATHOLOGY,
                f"{value} compiles for only {int(sigs)} distinct "
                f"signature(s) under '{label}' — the same program "
                f"recompiled {redundant} time(s): a program instance is "
                "being rebuilt per step, or the executable cache is "
                "thrashing"
                + (f"; last attribution: {cause}" if cause else ""),
            ))
    for key, value in sorted(gauges.items()):
        if not (key.startswith("xla/") and key.endswith("/signatures")):
            continue
        label = key[len("xla/"):-len("/signatures")]
        if value is not None and value >= SIGNATURE_CHURN_MIN:
            findings.append(Verdict(
                key, "signature-churn", WARNING,
                f"{int(value)} distinct signatures under '{label}' — each "
                "is a paid compile and a resident executable; bound the "
                "input shapes (power-of-two buckets)",
            ))
    compile_s = (
        (snapshot.get("histograms") or {})
        .get("jax/backend_compile_seconds") or {}
    ).get("total")
    elapsed_ms = records[-1].get("elapsed_ms") if records else None
    if (
        compile_s is not None and elapsed_ms
        and elapsed_ms / 1e3 >= COMPILE_DOMINATED_MIN_ELAPSED_S
        and compile_s >= COMPILE_DOMINATED_FRACTION * elapsed_ms / 1e3
    ):
        findings.append(Verdict(
            "jax/backend_compile_seconds", "compile-dominated", WARNING,
            f"{compile_s:.1f}s of backend compiles in a "
            f"{elapsed_ms / 1e3:.1f}s run "
            f"(>= {COMPILE_DOMINATED_FRACTION:.0%}) — the run is paying "
            "compiles, not compute; check the recompile attributions "
            "above / warm the signatures up front",
        ))
    overcommitted: set[str] = set()
    for row in records:
        if row.get("kind") != "program_compile":
            continue
        forecast = row.get("hbm_forecast_bytes")
        limit = row.get("device_bytes_limit")
        label = row.get("label")
        if (
            forecast is not None and limit is not None
            and forecast > limit and label not in overcommitted
        ):
            overcommitted.add(label)
            findings.append(Verdict(
                f"xla/{label}/hbm_forecast_bytes", "hbm-overcommit-forecast",
                WARNING,
                f"'{label}' forecasts {forecast / 1e9:.2f} GB resident+temp "
                f"against a {limit / 1e9:.2f} GB device limit — the next "
                "dispatch risks an OOM; shrink the batch/bucket or shard "
                "the params",
            ))
    return findings


def coordination_findings(records: list) -> list:
    """Cross-rank coordinated-recovery findings (ISSUE 15) over the
    MERGED journal rows of every rank's journal in a run directory: the
    per-rank restart table (restarts / aborts observed / aborts written /
    generations, from ``coordinated_restart`` / ``peer_abort`` /
    ``abort_written`` rows) and the RESTART-STORM pathology — the job's
    shared budget exhausted with the SAME culprit rank attributed every
    time, which names the rank to drain/replace instead of a generic
    "budget ran out"."""
    findings: list[Verdict] = []
    per_rank: dict[int, dict] = {}

    def ent(rank) -> dict | None:
        if rank is None:
            return None
        return per_rank.setdefault(int(rank), {
            "restarts": 0, "aborts_observed": 0, "aborts_written": 0,
            "blamed": 0, "max_generation": 0,
        })

    origins: list = []
    origin_generations: set = set()
    exhausted_rows: list[dict] = []
    for row in records:
        kind = row.get("kind")
        if kind == "coordinated_restart":
            e = ent(row.get("rank"))
            if e is not None:
                e["restarts"] += 1
                e["max_generation"] = max(
                    e["max_generation"], int(row.get("generation") or 0)
                )
            if row.get("origin_rank") is not None:
                origins.append(int(row["origin_rank"]))
                # every rank journals the SAME restart: distinct
                # generations count actual restarts, not rank-rows
                origin_generations.add(int(row.get("generation") or 0))
                blamed = ent(row["origin_rank"])
                blamed["blamed"] += 1
            if row.get("exhausted"):
                exhausted_rows.append(row)
        elif kind == "peer_abort":
            e = ent(row.get("rank"))
            if e is not None:
                e["aborts_observed"] += 1
        elif kind == "abort_written":
            e = ent(row.get("rank"))
            if e is not None:
                e["aborts_written"] += 1
        elif kind == "run_failure" and row.get("origin_rank") is not None:
            if row.get("restarts_used") is not None and row.get(
                "max_restarts"
            ) is not None and int(row["restarts_used"]) >= int(
                row["max_restarts"]
            ):
                exhausted_rows.append(row)
    if not per_rank:
        return findings
    table = "; ".join(
        f"rank {r}: restarts={e['restarts']} "
        f"aborts_observed={e['aborts_observed']} "
        f"aborts_written={e['aborts_written']} blamed={e['blamed']} "
        f"max_gen={e['max_generation']}"
        for r, e in sorted(per_rank.items())
    )
    findings.append(Verdict(
        "coordination", "cross-rank-restart-table", INFO,
        f"coordinated recovery over {len(per_rank)} rank(s): {table}",
    ))
    if exhausted_rows and origins and len(set(origins)) == 1:
        culprit = origins[0]
        findings.append(Verdict(
            "coordination", "restart-storm", PATHOLOGY,
            f"restart budget exhausted with rank {culprit} attributed as "
            f"the origin of every coordinated restart "
            f"({len(origin_generations)} restart generation(s)) — one "
            "flapping rank is burning the JOB's shared budget; "
            "drain/replace that worker before re-running",
        ))
    return findings


def last_abort_marker(records: list) -> dict | None:
    """The newest abort attribution seen in the merged journal rows — a
    ``peer_abort`` (observer side) or ``abort_written`` (culprit side)
    row. Newest by (generation, wall clock), NOT by file-concatenation
    order: the merge walks per-rank journals one at a time, so the last
    row read can be a stale rank's. What ``doctor --live`` prints while a
    run is wedged mid-restart."""
    last = None
    last_key = None
    for row in records:
        if row.get("kind") not in ("peer_abort", "abort_written"):
            continue
        key = (
            int(row.get("generation") or -1),
            float(row.get("ts") or 0.0),
        )
        if last_key is None or key >= last_key:
            last, last_key = row, key
    return last

