"""Per-bench-row win criteria + known-pathology diagnostics: the rules a
run is judged by, as code instead of BASELINE.md prose.

No reference analogue: the reference ships no benchmark governance at all;
this registry encodes the TPU rebuild's own measured-facts discipline
(CLAUDE.md / BASELINE.md): every bench row carries its SAME-RUN baseline
embedded in its unit (chips vary run to run — absolute numbers never
compare across rounds), so each row is self-judging once the unit is
parsed (telemetry/bench_history.py). Snap ML (arXiv:1803.06333) treats
measured hierarchy-level throughput as a control signal; here the measured
rows are the control signal for the repo's own perf claims.

Three layers:

- :func:`rule` registers one win criterion per row key. dev/lint_parity.py
  check 12 statically cross-checks this registry against
  ``bench.sample_report()`` — a new bench row without a registered verdict
  rule fails the lint, so "what does winning mean" can never again live
  only in prose.
- :func:`judge_row` / :func:`judge_artifact` produce :class:`Verdict`
  records (win / regression / flat / info / pathology / no-evidence), with
  the two measured pathology signatures named with their known causes: a
  NEGATIVE MARGINAL (K-spread too small against the ~100 ms dispatch
  jitter — the BENCH_r03 signature) and a ~40x SAME-RUN BLOWOUT (a Pallas
  call vmap-batched into a serial per-lane loop, or host contention from a
  concurrent CPU job — both measured, CLAUDE.md).
- :func:`journal_findings` cross-checks a run journal's registry snapshot
  (overlap_fraction ~ 0 with prefetch on, high serve pad_fraction,
  quarantined blocks, preemption restarts, stragglers, and the program
  ledger's compile pathologies — recompile storms with their attributed
  cause, signature churn, compile-dominated runs, HBM overcommit
  forecasts; ISSUE 13) and :func:`history_findings` reads cross-round
  trends (improvements, plateaus) in the direction each rule declares.

Statuses: only ``regression`` (a row losing its win criterion) fails a
doctor run by default — pathologies and warnings are findings the operator
reads, because historical artifacts legitimately carry them (r04/r05
``parsed: null``).
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Callable

from photon_ml_tpu.telemetry.bench_history import (
    BenchArtifact,
    BenchHistory,
    BenchRow,
    MultichipArtifact,
    calibration_fraction,
)
from photon_ml_tpu.telemetry.journal import heartbeat_cursor

# verdict statuses
WIN = "win"
REGRESSION = "regression"   # lost its win criterion -> nonzero doctor exit
FLAT = "flat"
INFO = "info"
PATHOLOGY = "pathology"     # known bad-measurement signature, named cause
WARNING = "warning"
NO_EVIDENCE = "no-evidence"

#: same-run ratio beyond which a loss is reported as the measured
#: contention/vmapped-Pallas blowout instead of a plain regression
BLOWOUT_RATIO = 10.0

#: tolerance band for same-run ms comparisons (spread jitter)
FLAT_BAND = 0.02

NEGATIVE_MARGINAL_CAUSE = (
    "negative marginal — K_hi-K_lo differencing spread too small against "
    "the ~100 ms dispatch jitter (the BENCH_r03 signature); widen the K "
    "spread so device time dwarfs the jitter"
)
BLOWOUT_CAUSE = (
    "same-run blowout >= 10x — known causes: a Pallas kernel vmap-batched "
    "into a serial per-lane loop (measured 40x; lint check 6) or host "
    "contention from a concurrent CPU job corrupting the marginal "
    "(measured 40x on an r4 λ-grid trial; CLAUDE.md)"
)


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


@dataclasses.dataclass
class Rule:
    pattern: str                #: exact metric key, or a ``prefix*`` glob
    name: str                   #: short rule id printed in reports
    judge: Callable             #: (row, artifact) -> Verdict
    higher_better: bool | None  #: cross-round trend direction (None = n/a)
    doc: str


_RULES: list[Rule] = []


def rule(pattern: str, *, name: str, higher_better: bool | None = None,
         doc: str = ""):
    """Register one win criterion. ``pattern`` is the bench row key (or a
    ``prefix*`` glob for row families); string literals only — lint check
    12 reads them statically against ``bench.sample_report()``."""

    def deco(fn: Callable) -> Callable:
        _RULES.append(Rule(pattern=pattern, name=name, judge=fn,
                           higher_better=higher_better, doc=doc or fn.__doc__ or ""))
        return fn

    return deco


def rule_for(metric: str) -> Rule | None:
    """Exact key first, then glob families."""
    for r in _RULES:
        if r.pattern == metric:
            return r
    for r in _RULES:
        if r.pattern.endswith("*") and fnmatch.fnmatch(metric, r.pattern):
            return r
    return None


def registered_rules() -> list[Rule]:
    return list(_RULES)


def _negative_marginal(row: BenchRow) -> bool:
    values = [row.value] + [s for s in row.spread if isinstance(s, (int, float))]
    return any(v is not None and v <= 0 for v in values)


def _verdict(row, rule_name, status, detail, art=None):
    return Verdict(metric=row.metric, rule=rule_name, status=status,
                   detail=detail, round=None if art is None else art.round)


def _same_run_lower(row, art, baseline_ms, *, rule_name, baseline_label):
    """Shared same-run 'ON must beat its embedded OFF' comparison for
    ms-valued rows; names the blowout pathology when the loss is ~40x."""
    if baseline_ms is None:
        return _verdict(
            row, rule_name, NO_EVIDENCE,
            f"unit embeds no {baseline_label} (value {row.value})", art,
        )
    if row.value is None:
        return _verdict(row, rule_name, NO_EVIDENCE, "row has no value", art)
    ratio = row.value / baseline_ms if baseline_ms else float("inf")
    detail = (
        f"{row.value:g} ms vs same-run {baseline_label} {baseline_ms:g} ms "
        f"({ratio:.2f}x)"
    )
    if ratio >= BLOWOUT_RATIO:
        return _verdict(row, rule_name, REGRESSION,
                        f"{detail} — {BLOWOUT_CAUSE}", art)
    if ratio < 1.0 - FLAT_BAND:
        return _verdict(row, rule_name, WIN, detail, art)
    if ratio <= 1.0 + FLAT_BAND:
        return _verdict(row, rule_name, FLAT, detail, art)
    return _verdict(row, rule_name, REGRESSION, detail, art)


# -- per-row rules (BASELINE.md same-run criteria, as code) ------------------


@rule("glm_lambda_grid_example_iters_per_sec", name="primary-positive",
      higher_better=True,
      doc="primary λ-grid throughput; judged across rounds by history, "
          "within a round only for presence + vs_baseline > 1")
def _judge_primary(row: BenchRow, art: BenchArtifact) -> Verdict:
    vs = art.vs_baseline
    detail = f"{row.value:g} ex*it/s" + (
        f", {vs:g}x scipy grid" if vs is not None else ""
    )
    if vs is not None and vs <= 1.0:
        return _verdict(row, "primary-positive", REGRESSION,
                        detail + " — TPU grid no faster than host scipy", art)
    return _verdict(row, "primary-positive", INFO, detail, art)


@rule("fe_hot_loop_stream_gbps", name="calibration-probe", higher_better=None,
      doc="the same-run normalizer every bandwidth fraction divides by; "
          "never compared across rounds (chip lottery)")
def _judge_stream(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _verdict(row, "calibration-probe", INFO,
                    f"stream probe {row.value:g} GB/s (this run's chip)", art)


@rule("fe_hot_loop_hbm_gbps_*", name="hot-loop-cal-fraction",
      higher_better=None,  # absolute GB/s never compare across rounds
      doc="single-pass kernel rows must hold ~1x the same-run stream "
          "probe (the r4 study); the 2-pass autodiff row is informational; "
          "no cross-round trend — the chip pool swings absolutes")
def _judge_hot_loop(row: BenchRow, art: BenchArtifact) -> Verdict:
    frac = calibration_fraction(art, row)
    if frac is None:
        return _verdict(row, "hot-loop-cal-fraction", NO_EVIDENCE,
                        f"{row.value:g} GB/s, no same-run stream probe", art)
    detail = f"{row.value:g} GB/s = {frac:.2f}x same-run stream probe"
    if row.metric.endswith("autodiff_xla"):
        # 2 X passes by construction: ~0.5x is the expected shape
        return _verdict(row, "hot-loop-cal-fraction", INFO, detail, art)
    if frac >= 1.0:
        return _verdict(row, "hot-loop-cal-fraction", WIN, detail, art)
    if frac >= 0.8:
        return _verdict(row, "hot-loop-cal-fraction", FLAT, detail, art)
    return _verdict(
        row, "hot-loop-cal-fraction", REGRESSION,
        detail + " — the single-pass kernel should sustain ~1x the probe "
                 "(1.10x measured r4/r5)", art,
    )


@rule("fused_game_sweep_ms", name="sweep-baseline", higher_better=False,
      doc="the unscheduled-LBFGS sweep: the same-run baseline the newton/"
          "scheduled rows are judged against")
def _judge_sweep(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _verdict(row, "sweep-baseline", INFO,
                    f"{row.value:g} ms/sweep (same-run baseline row)", art)


@rule("fused_game_sweep_newton_ms", name="newton-beats-lbfgs",
      higher_better=False,
      doc="Newton REs must beat the same-run LBFGS sweep (r5: 18 vs 48 ms)")
def _judge_newton(row: BenchRow, art: BenchArtifact) -> Verdict:
    base = art.row("fused_game_sweep_ms")
    return _same_run_lower(
        row, art, None if base is None else base.value,
        rule_name="newton-beats-lbfgs",
        baseline_label="fused_game_sweep_ms",
    )


@rule("fused_game_sweep_scheduled_ms", name="scheduled-beats-unscheduled",
      higher_better=False,
      doc="probe/rescue scheduling must beat the same-run unscheduled "
          "sweep on this warm-started bench (expected to lose only cold)")
def _judge_scheduled(row: BenchRow, art: BenchArtifact) -> Verdict:
    base = art.row("fused_game_sweep_ms")
    return _same_run_lower(
        row, art, None if base is None else base.value,
        rule_name="scheduled-beats-unscheduled",
        baseline_label="fused_game_sweep_ms",
    )


@rule("sparse_giant_fe_entry_iters_per_sec", name="ell-throughput",
      higher_better=True,
      doc="the d=1e7 ELL row; bounded by the ~7-12 ns/element per-index "
          "rate, so cross-round plateau is the expected shape (history "
          "names it); hybrid is the lever, not reordering")
def _judge_ell(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _verdict(row, "ell-throughput", INFO,
                    f"{row.value:g} entry-iters/s (ELL layout)", art)


@rule("sparse_giant_fe_hybrid", name="hybrid-beats-ell", higher_better=False,
      doc="hybrid ms/iter must beat the ELL ms/iter embedded in its unit "
          "(same Zipfian data, same process — the r6 criterion)")
def _judge_hybrid(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _same_run_lower(
        row, art, row.parsed_unit.get("ell_ms"),
        rule_name="hybrid-beats-ell", baseline_label="embedded ELL",
    )


@rule("sparse_giant_fe_composed", name="composed-beats-ell-unscheduled",
      higher_better=False,
      doc="the hybrid+scheduled sweep must beat the embedded same-run "
          "ELL+unscheduled sweep (the ISSUE 6 criterion)")
def _judge_composed(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _same_run_lower(
        row, art, row.parsed_unit.get("ell_unscheduled_ms"),
        rule_name="composed-beats-ell-unscheduled",
        baseline_label="embedded ELL-unscheduled",
    )


@rule("sparse_1e8_fe_tron_ms_per_iter", name="tron-1e8", higher_better=False,
      doc="d=1e8 TRON row; r6 redefined it onto Zipf+hybrid, so r5-and-"
          "earlier values are not comparable (BASELINE.md)")
def _judge_tron(row: BenchRow, art: BenchArtifact) -> Verdict:
    return _verdict(row, "tron-1e8", INFO,
                    f"{row.value:g} ms/TRON-iter (Zipf+hybrid since r6; "
                    "earlier rounds not comparable)", art)


@rule("stream_fe_chunked", name="prefetch-on-beats-off", higher_better=False,
      doc="prefetch-ON ms/epoch must beat the same-run OFF embedded in the "
          "unit; overlap ~0 with a win absent is the hid-nothing pathology")
def _judge_stream_chunked(row: BenchRow, art: BenchArtifact) -> Verdict:
    v = _same_run_lower(
        row, art, row.parsed_unit.get("off_ms"),
        rule_name="prefetch-on-beats-off", baseline_label="prefetch-OFF",
    )
    overlap = row.parsed_unit.get("overlap")
    if overlap is not None and overlap < 0.01 and v.status != WIN:
        v = dataclasses.replace(
            v, status=PATHOLOGY,
            detail=v.detail + (
                " — overlap_fraction ~ 0: prefetch hid nothing; expected "
                "only when compute contends for the decoding host cores "
                "(the CPU mesh), not on a chip, where decode should hide "
                "behind the transfer and the device step"
            ),
        )
    return v


@rule("stream_game_duhl", name="duhl-fewer-visits", higher_better=False,
      doc="DuHL must reach tolerance in strictly fewer RE chunk visits "
          "than the same-run uniform sweep (v-pair in the unit; CPU "
          "anchor v62/128)")
def _judge_duhl(row: BenchRow, art: BenchArtifact) -> Verdict:
    u = row.parsed_unit
    vo, vu = u.get("visits_ordered"), u.get("visits_uniform")
    if vo is None or vu is None:
        return _verdict(row, "duhl-fewer-visits", NO_EVIDENCE,
                        "unit embeds no v<ordered>/<uniform> pair", art)
    detail = f"v{vo}/{vu} chunk visits to tolerance"
    so, su = u.get("sweeps_ordered"), u.get("sweeps_uniform")
    if so is not None and su is not None:
        detail += f", sw{so}/{su}"
        if so > su:
            return _verdict(
                row, "duhl-fewer-visits", REGRESSION,
                detail + " — DuHL took MORE sweeps than uniform: the "
                "importance ranking pinned the wrong chunks (rank on "
                "movement+gradient after warmup_sweeps, never on "
                "first-solve movement — the measured 12-vs-8 failure)", art,
            )
    if vo < vu:
        return _verdict(row, "duhl-fewer-visits", WIN, detail, art)
    return _verdict(
        row, "duhl-fewer-visits", REGRESSION,
        detail + " — the working set saved nothing over uniform", art,
    )


@rule("stream_game_ranks", name="rank-reads-strict-subset",
      higher_better=False,
      doc="multi-rank partitioned streamed GAME (ISSUE 17): max per-rank "
          "decoded payload bytes must be STRICTLY smaller than the global "
          "input bytes (rb<rank>/<input>MB pair) — the I/O the partition "
          "exists to save. Wall ms/sweep on virtual ranks is "
          "thread-serialized on one host and is informational only; the "
          "same-run single-rank sweep ms (1rk) gives its scale")
def _judge_stream_ranks(row: BenchRow, art: BenchArtifact) -> Verdict:
    u = row.parsed_unit
    rank_mb, input_mb = u.get("rank_payload_mb"), u.get("input_mb")
    if rank_mb is None or input_mb is None:
        return _verdict(row, "rank-reads-strict-subset", NO_EVIDENCE,
                        "unit embeds no rb<rank>/<input>MB pair", art)
    detail = f"max per-rank payload {rank_mb:g} MB of {input_mb:g} MB input"
    one_rank = u.get("one_rank_ms")
    if one_rank is not None and row.value is not None:
        detail += (f"; {row.value:g} ms/sweep vs same-run single-rank "
                   f"{one_rank:g} (informational — virtual ranks "
                   f"serialize)")
    if 0 < rank_mb < input_mb:
        return _verdict(row, "rank-reads-strict-subset", WIN, detail, art)
    return _verdict(
        row, "rank-reads-strict-subset", REGRESSION,
        detail + " — a rank decoded the whole input: the partitioned "
        "plan assigned it every covering block (ISSUE 17's point is that "
        "it must not)", art,
    )


@rule("serve_microbatch", name="batched-beats-unbatched", higher_better=True,
      doc="micro-batched scores/sec must beat the same-run one-request-"
          "per-dispatch rate embedded in the unit (~14x on the CPU mesh)")
def _judge_serve(row: BenchRow, art: BenchArtifact) -> Verdict:
    base = row.parsed_unit.get("unbatched_rate")
    if base is None:
        return _verdict(row, "batched-beats-unbatched", NO_EVIDENCE,
                        "unit embeds no same-run unbatched rate", art)
    if row.value is None:
        return _verdict(row, "batched-beats-unbatched", NO_EVIDENCE,
                        "row has no value", art)
    ratio = row.value / base if base else float("inf")
    detail = f"{row.value:g} sc/s vs unbatched {base:g} ({ratio:.1f}x)"
    if ratio > 1.0:
        return _verdict(row, "batched-beats-unbatched", WIN, detail, art)
    return _verdict(
        row, "batched-beats-unbatched", REGRESSION,
        detail + " — the micro-batch loop must beat one-request-per-"
        "dispatch or serving has no reason to exist", art,
    )


@rule("refresh_incremental", name="refresh-beats-full-retrain",
      higher_better=False,
      doc="incremental refresh ms must beat the same-run full retrain "
          "embedded in the unit, with STRICTLY fewer RE lane-solves "
          "(ln<solved>/<total> pair) — a refresh that re-solves every "
          "lane saved nothing (ISSUE 14)")
def _judge_refresh(row: BenchRow, art: BenchArtifact) -> Verdict:
    u = row.parsed_unit
    v = _same_run_lower(
        row, art, u.get("full_ms"),
        rule_name="refresh-beats-full-retrain",
        baseline_label="full retrain",
    )
    solved, total = u.get("lanes_solved"), u.get("lanes_total")
    if solved is not None and total is not None:
        v = dataclasses.replace(v, detail=v.detail + f", ln{solved}/{total}")
        if solved >= total and v.status in (WIN, FLAT):
            return dataclasses.replace(
                v, status=REGRESSION,
                detail=v.detail + " — the refresh re-solved every RE lane: "
                "the selection policy saved nothing (check "
                "gradient_tolerance / the declared changed-entity set)",
            )
    return v


@rule("search_throughput", name="tournament-beats-sequential",
      higher_better=True,
      doc="GP tournament configs/sec must beat the same-run one-config-"
          "per-solve sequential rate embedded in the unit (seq token) — "
          "vmapped lanes are the ONLY reason the search driver exists "
          "(ISSUE 20); wall rates never compare across rounds")
def _judge_search(row: BenchRow, art: BenchArtifact) -> Verdict:
    base = row.parsed_unit.get("seq_rate")
    if base is None:
        return _verdict(row, "tournament-beats-sequential", NO_EVIDENCE,
                        "unit embeds no same-run sequential rate", art)
    if row.value is None:
        return _verdict(row, "tournament-beats-sequential", NO_EVIDENCE,
                        "row has no value", art)
    ratio = row.value / base if base else float("inf")
    detail = f"{row.value:g} cfg/s vs sequential {base:g} ({ratio:.1f}x)"
    if ratio > 1.0:
        return _verdict(row, "tournament-beats-sequential", WIN, detail, art)
    return _verdict(
        row, "tournament-beats-sequential", REGRESSION,
        detail + " — the vmapped tournament must beat one-config-per-"
        "solve or the search driver has no reason to exist", art,
    )


# -- judging entry points ----------------------------------------------------


def judge_row(row: BenchRow, artifact: BenchArtifact) -> Verdict:
    """One row -> one verdict: negative-marginal pathology first, then the
    registered win criterion (rows without a rule report as such — lint
    check 12 keeps that set empty for sample_report rows)."""
    if _negative_marginal(row):
        return _verdict(row, "negative-marginal", PATHOLOGY,
                        NEGATIVE_MARGINAL_CAUSE, artifact)
    r = rule_for(row.metric)
    if row.value is None and r is not None:
        # a null-valued row reaches no criterion (and the per-rule detail
        # formatters assume a number) — the doctor must read sick runs
        return _verdict(row, r.name, NO_EVIDENCE,
                        "row carries no value", artifact)
    if r is None:
        return _verdict(
            row, "unregistered", WARNING,
            "no verdict rule registered for this row — add one in "
            "telemetry/verdicts.py (lint check 12)", artifact,
        )
    return r.judge(row, artifact)


def judge_artifact(artifact: BenchArtifact) -> list:
    """Row verdicts + artifact-level capture health for one round."""
    verdicts: list[Verdict] = []
    if artifact.rc not in (0, None):
        verdicts.append(Verdict(
            metric="artifact", rule="bench-exit-code", status=REGRESSION,
            detail=f"bench.py exited rc={artifact.rc}", round=artifact.round,
        ))
    if not artifact.parsed_ok:
        verdicts.append(Verdict(
            metric="artifact", rule="parsed-non-null", status=PATHOLOGY,
            detail=(
                "driver captured parsed:null — the JSON line overran the "
                "2,000-byte tail (the BENCH_r04/r05 regression; "
                f"test_bench_line.py pins <=1999 B); {len(artifact.rows)} "
                "row(s) salvaged from the truncated tail, primary metric "
                "lost" if artifact.primary is None else
                "driver captured parsed:null but the full report was "
                "salvaged from the tail"
            ),
            round=artifact.round,
        ))
    for row in artifact.all_rows:
        verdicts.append(judge_row(row, artifact))
    return verdicts


def judge_multichip(artifact: MultichipArtifact) -> Verdict:
    if artifact.skipped:
        return Verdict("multichip", "multichip-ok", INFO,
                       "dryrun skipped this round", round=artifact.round)
    if artifact.ok and artifact.rc == 0:
        return Verdict("multichip", "multichip-ok", WIN,
                       f"dryrun_multichip ok on {artifact.n_devices} devices",
                       round=artifact.round)
    return Verdict("multichip", "multichip-ok", REGRESSION,
                   f"dryrun_multichip failed (rc={artifact.rc})",
                   round=artifact.round)


# -- cross-round history -----------------------------------------------------

#: a first->last ratio past this (in the rule's better direction) is an
#: improvement finding; within FLAT of 1.0 over the trailing window is a
#: plateau finding
IMPROVEMENT_RATIO = 1.25
PLATEAU_BAND = 0.05
PLATEAU_WINDOW = 3


def history_findings(history: BenchHistory) -> list:
    """Cross-round trends per metric, in each rule's declared direction.

    Values still only compare across rounds LOOSELY (chip lottery swings
    absolutes ~25%+); the thresholds are set so only trend-scale moves
    (the r1->r3 λ-grid 3x) and genuine plateaus report.
    """
    findings: list[Verdict] = []
    metrics: list[str] = []
    for art in history.artifacts:
        for row in art.all_rows:
            if row.metric not in metrics:
                metrics.append(row.metric)
    for metric in metrics:
        series = history.series(metric)
        if len(series) < 2:
            continue
        r = rule_for(metric)
        higher_better = r.higher_better if r is not None else None
        (r0, first), (r1, last) = series[0], series[-1]
        if higher_better is not None and first.value:
            ratio = last.value / first.value
            improved = (
                ratio >= IMPROVEMENT_RATIO if higher_better
                else ratio <= 1.0 / IMPROVEMENT_RATIO
            )
            if improved:
                findings.append(Verdict(
                    metric=metric, rule="history-improvement", status=INFO,
                    detail=(
                        f"improved {first.value:g} (r{r0}) -> "
                        f"{last.value:g} (r{r1}), "
                        f"{max(ratio, 1 / ratio):.2f}x"
                    ),
                ))
        if len(series) >= PLATEAU_WINDOW:
            tail = [row.value for _, row in series[-PLATEAU_WINDOW:]]
            lo, hi = min(tail), max(tail)
            if lo > 0 and hi / lo <= 1.0 + PLATEAU_BAND:
                since = series[-PLATEAU_WINDOW][0]
                findings.append(Verdict(
                    metric=metric, rule="history-plateau", status=INFO,
                    detail=(
                        f"plateau at ~{tail[-1]:g} since r{since} "
                        f"(last {PLATEAU_WINDOW} rounds within "
                        f"{PLATEAU_BAND:.0%})"
                    ),
                ))
    return findings


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


def regressions(verdicts: list) -> list:
    return [v for v in verdicts if v.status == REGRESSION]
