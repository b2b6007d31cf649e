"""ISSUE 12: the evidence-analysis layer — bench-history parsing, verdict
rules, the run doctor CLI, the bench sidecar, journal heartbeats, and the
crash-durable flush's observe-only pin.

The regression-pin half runs dev/doctor.py over a SYNTHETIC five-round
history written into ``tmp_path`` in the shapes the driver's artifacts took
(whole parsed lines in rounds 1-3, ``parsed: null`` with a truncated
2,000-byte tail in rounds 4-5, legacy verbose units) and asserts the
verdicts: the λ-grid improvement, the null captures flagged, the sparse
plateau. The values are invented; no checked-in measurement is read.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402  (imports no jax at load)
from dev.doctor import run_doctor  # noqa: E402
from photon_ml_tpu.telemetry import bench_history, verdicts  # noqa: E402
from photon_ml_tpu.telemetry.journal import (  # noqa: E402
    RunJournal,
    read_journal,
)


# ---------------------------------------------------------------------------
# a synthetic five-round driver history
# ---------------------------------------------------------------------------

_GRID = "glm_lambda_grid_example_iters_per_sec"
_SPARSE = "sparse_giant_fe_entry_iters_per_sec"


def _legacy_row(metric, value, unit, spread=None):
    row = {"metric": metric, "value": value, "unit": unit}
    if spread is not None:
        row["spread"] = spread
    return row


def _kernel_unit(fraction):
    return ("achieved GB/s of ACTUAL bytes per value+grad eval (1 fused f32 X "
            "pass/eval; 0.350 ms/eval), marginal over 240 extra evals, "
            "median-of-3; one-f32-pass-equivalent fraction of the same-run "
            f"stream rate: {fraction:.2f}")


def _sparse_row(value, ms):
    return _legacy_row(
        _SPARSE, value,
        "nonzero-entries x L-BFGS-iters/sec, sparse FE d=1e+07 (n=524288, "
        "nnz=18874368, logistic, ELL padded-row layout; marginal over 12 "
        f"extra iterations, {ms:.2f} ms/iter)")


def write_history(directory) -> str:
    """BENCH_r01-r05 + MULTICHIP_r01-r05 in ``directory``; returns it."""
    def bench(n, report, *, truncate=False):
        line = json.dumps(report)
        tail = "WARNING: platform is experimental\n" + line
        if truncate:  # the line overran the driver's 2,000-byte tail
            tail = line[line.index('"extra_metrics"') + 40:]
        with open(os.path.join(directory, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"n": n, "cmd": "python bench.py", "rc": 0,
                       "tail": tail,
                       "parsed": None if truncate else report}, f)

    def grid(value, extras=None):
        report = {"metric": _GRID, "value": value,
                  "unit": "examples x L-BFGS-iters/sec over a 32-lane "
                          "vmapped lambda grid", "vs_baseline": 100.0}
        if extras is not None:
            report["extra_metrics"] = extras
        return report

    stream = _legacy_row(
        "fe_hot_loop_stream_gbps", 700.0,
        "same-run calibration: one [n, d]-matvec X read per step")
    bench(1, grid(2.0e8))
    bench(2, grid(4.0e8, [_sparse_row(2.6e7, 725.0)]))
    bench(3, grid(6.0e8, [stream, _sparse_row(5.00e7, 377.0)]))
    bench(4, grid(5.0e8, [
        stream,
        _legacy_row("fe_hot_loop_hbm_gbps_pallas_kernel", 770.0,
                    _kernel_unit(1.10), [760.0, 790.0]),
        _sparse_row(5.02e7, 376.0),
    ]), truncate=True)
    bench(5, grid(5.0e8, [
        stream,
        _legacy_row("fused_game_sweep_ms", 50.0,
                    "marginal ms per fused GAME CD sweep; median-of-3",
                    [48.0, 60.0]),
        _legacy_row("fused_game_sweep_newton_ms", 20.0,
                    "same sweep with the RE coordinates on the batched-"
                    "Newton solver; median-of-3", [15.0, 24.0]),
        _sparse_row(4.98e7, 379.0),
    ]), truncate=True)
    for n in range(1, 6):
        ok = n > 1
        with open(os.path.join(directory, f"MULTICHIP_r{n:02d}.json"), "w") as f:
            json.dump({"n_devices": 8, "rc": 0 if ok else 1, "ok": ok,
                       "skipped": False,
                       "tail": "" if ok else "ValueError: cannot reshape "
                               "array of size 1 into shape (4,2)\n"}, f)
    return str(directory)


@pytest.fixture()
def history_dir(tmp_path):
    return write_history(tmp_path)


# ---------------------------------------------------------------------------
# unit-grammar parsing (telemetry/bench_history.py)
# ---------------------------------------------------------------------------


class TestUnitParsing:
    def test_compact_grammar_fields(self):
        cases = {
            ("sparse_giant_fe_hybrid",
             "ms/it d=1e7 zipf 17M hot256 cov0.62 ELLsr 644"):
                {"ell_ms": 644.0, "hot_cols": 256, "coverage": 0.62},
            ("sparse_giant_fe_composed",
             "ms/sw d=1e6 zipf hot256 cov0.58 sch-p2 ELLunsr 103"):
                {"ell_unscheduled_ms": 103.0},
            ("stream_fe_chunked", "ms/ep ON 8ch zdec OFF710 ovl0.03"):
                {"off_ms": 710.0, "overlap": 0.03, "chunks": 8},
            ("stream_game_duhl", "ms/sw v62/128 sw8/8 OFF140"):
                {"visits_ordered": 62, "visits_uniform": 128,
                 "sweeps_ordered": 8, "sweeps_uniform": 8, "off_ms": 140.0},
            ("serve_microbatch", "sc/s p95 11ms 1/dsp sr 3400"):
                {"p95_ms": 11.0, "unbatched_rate": 3400.0},
            ("fe_hot_loop_hbm_gbps_pallas_kernel", "1 pass dflt 1.10xcal"):
                {"cal_fraction": 1.10},
        }
        for (metric, unit), expected in cases.items():
            parsed = bench_history.parse_unit(metric, unit)
            for k, v in expected.items():
                assert parsed.get(k) == v, (metric, k, parsed)

    def test_legacy_verbose_grammar(self):
        parsed = bench_history.parse_unit(
            "fe_hot_loop_hbm_gbps_pallas_kernel",
            "achieved GB/s ... one-f32-pass-equivalent fraction of the "
            "same-run stream rate: 1.10",
        )
        assert parsed["cal_fraction"] == 1.10
        parsed = bench_history.parse_unit(
            "sparse_giant_fe_entry_iters_per_sec",
            "nonzero-entries x L-BFGS-iters/sec ... 375.77 ms/iter, "
            "median-of-3",
        )
        assert parsed["ms_per_iter"] == 375.77

    def test_every_sample_report_unit_parses_its_criterion_fields(self):
        """The compact units bench.py emits TODAY carry the fields their
        own verdict rules need — the grammar and the builders can't drift."""
        report = bench.sample_report()
        by_metric = {r["metric"]: r for r in report["extra_metrics"]}
        need = {
            "sparse_giant_fe_hybrid": "ell_ms",
            "sparse_giant_fe_composed": "ell_unscheduled_ms",
            "stream_fe_chunked": "off_ms",
            "stream_game_duhl": "visits_ordered",
            "serve_microbatch": "unbatched_rate",
            "search_throughput": "seq_rate",
        }
        for metric, field in need.items():
            parsed = bench_history.parse_unit(
                metric, by_metric[metric]["unit"]
            )
            assert field in parsed, (metric, by_metric[metric]["unit"])
        # the r20 line-budget trim moved the hot-loop cal fraction out of
        # the unit: its rule now rides calibration_fraction's documented
        # fallback — value / same-run stream-probe row
        art = bench_history.BenchArtifact(
            path="sample", round=None, rc=0, parsed_ok=True,
            rows=[
                bench_history.BenchRow.from_report_row(r)
                for r in report["extra_metrics"]
            ],
        )
        frac = bench_history.calibration_fraction(
            art, art.row("fe_hot_loop_hbm_gbps_pallas_kernel")
        )
        assert frac == pytest.approx(
            art.row("fe_hot_loop_hbm_gbps_pallas_kernel").value
            / art.row("fe_hot_loop_stream_gbps").value
        )


# ---------------------------------------------------------------------------
# artifact loading + tail salvage
# ---------------------------------------------------------------------------


class TestArtifactLoading:
    def test_parsed_artifact_loads_rows(self, history_dir):
        art = bench_history.load_bench_artifact(
            os.path.join(history_dir, "BENCH_r03.json")
        )
        assert art.parsed_ok and art.round == 3
        assert art.primary.metric == "glm_lambda_grid_example_iters_per_sec"
        assert art.row("fe_hot_loop_stream_gbps").value == pytest.approx(700.0)

    def test_parsed_null_artifact_salvages_tail_rows(self, history_dir):
        """The truncated-capture shape: parsed null, but the trailing row
        objects are whole inside the 2,000-byte tail."""
        art = bench_history.load_bench_artifact(
            os.path.join(history_dir, "BENCH_r04.json")
        )
        assert not art.parsed_ok and art.source == "tail-salvage"
        assert art.primary is None  # truncation eats the line's head
        metrics = [r.metric for r in art.rows]
        assert "fe_hot_loop_hbm_gbps_pallas_kernel" in metrics
        assert "sparse_giant_fe_entry_iters_per_sec" in metrics
        row = art.row("fe_hot_loop_hbm_gbps_pallas_kernel")
        assert row.salvaged and row.value == pytest.approx(770.0)
        # the verbose legacy unit still yields the calibration fraction
        assert row.parsed_unit["cal_fraction"] == pytest.approx(1.10)

    def test_history_series_across_rounds(self, history_dir):
        hist = bench_history.load_history(history_dir)
        assert [a.round for a in hist.artifacts] == [1, 2, 3, 4, 5]
        series = hist.series("sparse_giant_fe_entry_iters_per_sec")
        assert [r for r, _ in series] == [2, 3, 4, 5]


# ---------------------------------------------------------------------------
# verdict rules
# ---------------------------------------------------------------------------


def _artifact_with(rows, round=6):
    art = bench_history.BenchArtifact(
        path="<test>", round=round, rc=0, parsed_ok=True, rows=[
            bench_history.BenchRow.from_report_row(r) for r in rows
        ],
    )
    return art


class TestVerdictRules:
    def test_every_sample_report_metric_has_a_rule(self):
        """Runtime complement of lint check 12."""
        report = bench.sample_report()
        for row in [report] + report["extra_metrics"]:
            assert verdicts.rule_for(row["metric"]) is not None, row["metric"]

    def test_hybrid_win_and_regression(self):
        win = _artifact_with([{
            "metric": "sparse_giant_fe_hybrid", "value": 330.0,
            "spread": [328.0, 335.0],
            "unit": "ms/it d=1e7 zipf 17M hot256 cov0.62 ELLsr 644",
        }])
        v = verdicts.judge_row(win.rows[0], win)
        assert v.status == verdicts.WIN
        lose = _artifact_with([{
            "metric": "sparse_giant_fe_hybrid", "value": 800.0,
            "spread": [790.0, 820.0],
            "unit": "ms/it d=1e7 zipf 17M hot256 cov0.62 ELLsr 644",
        }])
        v = verdicts.judge_row(lose.rows[0], lose)
        assert v.status == verdicts.REGRESSION
        assert v.rule == "hybrid-beats-ell"

    def test_blowout_names_known_causes(self):
        art = _artifact_with([{
            "metric": "sparse_giant_fe_hybrid", "value": 9000.0,
            "spread": [8900.0, 9100.0],
            "unit": "ms/it d=1e7 zipf 17M hot256 cov0.62 ELLsr 644",
        }])
        v = verdicts.judge_row(art.rows[0], art)
        assert v.status == verdicts.REGRESSION
        assert "vmap-batched" in v.detail and "contention" in v.detail

    def test_negative_marginal_pathology(self):
        art = _artifact_with([{
            "metric": "fused_game_sweep_ms", "value": -3.2,
            "spread": [-5.0, 2.0], "unit": "ms/sw FE d256 2REs",
        }])
        v = verdicts.judge_row(art.rows[0], art)
        assert v.status == verdicts.PATHOLOGY
        assert "dispatch jitter" in v.detail

    def test_duhl_and_serve_criteria(self):
        art = _artifact_with([
            {"metric": "stream_game_duhl", "value": 120.0, "spread": [],
             "unit": "ms/sw v62/128 sw8/8 OFF140"},
            {"metric": "serve_microbatch", "value": 48000.0, "spread": [],
             "unit": "sc/s p95 11ms 1/dsp sr 3400"},
        ])
        assert verdicts.judge_row(art.rows[0], art).status == verdicts.WIN
        assert verdicts.judge_row(art.rows[1], art).status == verdicts.WIN
        worse = _artifact_with([
            {"metric": "stream_game_duhl", "value": 120.0, "spread": [],
             "unit": "ms/sw v128/128 sw8/8 OFF140"},
            {"metric": "serve_microbatch", "value": 3000.0, "spread": [],
             "unit": "sc/s p95 11ms 1/dsp sr 3400"},
        ])
        assert verdicts.judge_row(worse.rows[0], worse).status == \
            verdicts.REGRESSION
        assert verdicts.judge_row(worse.rows[1], worse).status == \
            verdicts.REGRESSION

    def test_overlap_zero_with_no_win_is_pathology(self):
        art = _artifact_with([{
            "metric": "stream_fe_chunked", "value": 712.0, "spread": [],
            "unit": "ms/ep ON 8ch zdec OFF710 ovl0.00",
        }])
        v = verdicts.judge_row(art.rows[0], art)
        assert v.status == verdicts.PATHOLOGY
        assert "hid nothing" in v.detail


# ---------------------------------------------------------------------------
# the doctor over a five-round history (the regression pin)
# ---------------------------------------------------------------------------


class TestDoctorOverHistory:
    def test_reproduces_known_history_and_exits_zero(self, history_dir):
        code, findings, text = run_doctor(history_dir)
        assert code == 0  # historical pathologies never fail the run
        # λ-grid 200M -> 600M improvement detected
        improvements = [
            v for v in findings
            if v.rule == "history-improvement"
            and v.metric == "glm_lambda_grid_example_iters_per_sec"
        ]
        assert improvements and "3.00x" in improvements[0].detail
        # r04/r05 parsed:null flagged by name
        nulls = [v for v in findings if v.rule == "parsed-non-null"]
        assert sorted(v.round for v in nulls) == [4, 5]
        assert all(v.status == verdicts.PATHOLOGY for v in nulls)
        # sparse ELL plateau reported
        plateaus = [
            v for v in findings
            if v.rule == "history-plateau"
            and v.metric == "sparse_giant_fe_entry_iters_per_sec"
        ]
        assert plateaus and "plateau" in plateaus[0].detail
        # the newton same-run win judged from salvaged r05 rows
        assert any(
            v.rule == "newton-beats-lbfgs" and v.status == verdicts.WIN
            for v in findings
        )
        assert "REGRESSIONS: none" in text

    def test_module_cli_entrypoint(self, history_dir):
        """`python -m dev.doctor DIR` (the acceptance invocation) exits 0
        over the history and prints the verdict table."""
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m", "dev.doctor", history_dir],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "parsed:null" in proc.stdout
        assert "REGRESSIONS: none" in proc.stdout


class TestDoctorRegressionFixture:
    def _write_artifact(self, path, rows, round=6):
        report = {
            "metric": "glm_lambda_grid_example_iters_per_sec",
            "value": 6.0e8, "spread": [5.9e8, 6.1e8],
            "unit": "ex*it/s", "vs_baseline": 250.0,
            "extra_metrics": rows,
        }
        with open(path, "w") as f:
            json.dump({
                "n": round, "cmd": "python bench.py", "rc": 0,
                "tail": json.dumps(report), "parsed": report,
            }, f)

    def test_synthetic_regression_exits_nonzero_naming_row_and_rule(
        self, tmp_path
    ):
        """A hybrid row SLOWER than its embedded same-run ELL: the doctor
        must exit nonzero and name both the row and the rule."""
        self._write_artifact(str(tmp_path / "BENCH_r06.json"), [{
            "metric": "sparse_giant_fe_hybrid", "value": 800.0,
            "spread": [790.0, 820.0],
            "unit": "ms/it d=1e7 zipf 17M hot256 cov0.62 ELLsr 644",
        }])
        code, findings, text = run_doctor(str(tmp_path))
        assert code == 1
        assert "sparse_giant_fe_hybrid" in text
        assert "hybrid-beats-ell" in text

    def test_null_valued_row_reports_no_evidence_not_crash(self, tmp_path):
        """A sick artifact with value:null rows must be readable: every
        rule reports no-evidence instead of crashing a formatter."""
        self._write_artifact(str(tmp_path / "BENCH_r06.json"), [
            {"metric": m, "value": None, "spread": [], "unit": "u"}
            for m in ("fe_hot_loop_stream_gbps", "fused_game_sweep_ms",
                      "sparse_giant_fe_entry_iters_per_sec",
                      "sparse_1e8_fe_tron_ms_per_iter")
        ])
        code, findings, text = run_doctor(str(tmp_path))
        assert code == 0
        assert sum(1 for v in findings
                   if v.status == verdicts.NO_EVIDENCE) >= 4

    def test_current_multichip_failure_gates_exit_despite_sidecar(
        self, tmp_path
    ):
        """A failing CURRENT-round dryrun fails the doctor even when a
        sidecar is present (the sidecar never carries multichip evidence)."""
        with open(tmp_path / "MULTICHIP_r06.json", "w") as f:
            json.dump({"n_devices": 8, "rc": 1, "ok": False,
                       "skipped": False, "tail": ""}, f)
        bench.write_sidecar(
            {"metric": "glm_lambda_grid_example_iters_per_sec",
             "value": 6e8, "spread": [], "unit": "u", "vs_baseline": 2.0,
             "extra_metrics": []},
            str(tmp_path),
        )
        code, findings, text = run_doctor(str(tmp_path))
        assert code == 1
        assert "multichip-ok" in text

    def test_regression_in_stale_round_does_not_fail_current(self, tmp_path):
        """Only the CURRENT round's losses drive the exit code: an old
        round's regression is history, not a gate."""
        bad = [{
            "metric": "sparse_giant_fe_hybrid", "value": 800.0,
            "spread": [], "unit": "ELLsr 644",
        }]
        good = [{
            "metric": "sparse_giant_fe_hybrid", "value": 330.0,
            "spread": [], "unit": "ELLsr 644",
        }]
        self._write_artifact(str(tmp_path / "BENCH_r06.json"), bad, round=6)
        self._write_artifact(str(tmp_path / "BENCH_r07.json"), good, round=7)
        code, findings, text = run_doctor(str(tmp_path))
        assert code == 0


# ---------------------------------------------------------------------------
# bench sidecar (satellite 1)
# ---------------------------------------------------------------------------


class TestBenchSidecar:
    def test_sidecar_written_and_preferred(self, tmp_path):
        report = bench.sample_report()
        path = bench.write_sidecar(report, str(tmp_path),
                                   config={"n": 1, "d": 2})
        assert os.path.basename(path) == bench_history.SIDECAR_FILENAME
        art = bench_history.load_sidecar(path)
        assert art.source == "sidecar" and art.parsed_ok
        assert [r.metric for r in art.rows] == [
            r["metric"] for r in report["extra_metrics"]
        ]
        # rows carry pre-parsed units (structure, not regex, for the doctor)
        with open(path) as f:
            raw = json.load(f)
        hyb = next(r for r in raw["report"]["extra_metrics"]
                   if r["metric"] == "sparse_giant_fe_hybrid")
        assert "ell_ms" in hyb["parsed_unit"]
        # the doctor prefers it over any BENCH_r*.json in the same dir
        hist = bench_history.load_history(str(tmp_path))
        assert hist.latest is hist.sidecar
        _code, _findings, text = run_doctor(str(tmp_path))
        assert "sidecar" in text

    def test_sidecar_does_not_change_the_line_contract(self):
        """Writing the sidecar happens AFTER render_report; the ONE JSON
        line is byte-identical with or without PHOTON_TELEMETRY_DIR."""
        report = bench.sample_report()
        line = bench.render_report(report)
        assert len(line.encode()) < bench.MAX_LINE_BYTES
        assert json.loads(line) == report  # sidecar adds nothing to it


# ---------------------------------------------------------------------------
# journal heartbeats + durable flush (the observe-only pin)
# ---------------------------------------------------------------------------


def _stream_fixture(n=64, d=6, chunk=16, seed=0):
    from photon_ml_tpu.io.stream_reader import ArrayChunkSource

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    wt = rng.normal(size=d).astype(np.float32)
    y = (x @ wt + 0.1 * rng.normal(size=n)).astype(np.float32)
    return ArrayChunkSource(x, y, chunk_rows=chunk)


def _train_streaming(telemetry=None):
    from photon_ml_tpu.estimators import train_glm_streaming
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
    from photon_ml_tpu.types import TaskType

    return train_glm_streaming(
        _stream_fixture(),
        TaskType.LINEAR_REGRESSION,
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=6
        ),
        regularization_weights=(0.1, 1.0),
        telemetry=telemetry,
    )


class TestJournalHeartbeats:
    def test_heartbeat_rows_carry_cursor_and_counter_deltas(self, tmp_path):
        from photon_ml_tpu.telemetry import MetricsRegistry

        reg = MetricsRegistry()
        with RunJournal(tmp_path, rank=0) as j:
            reg.counter("solver/x/solves").inc(3)
            j.heartbeat(registry=reg, stage="s1", sweep=1)
            reg.counter("solver/x/solves").inc(2)
            reg.gauge("stream/overlap_fraction").set(0.4)
            j.heartbeat(registry=reg, stage="s1", sweep=2)
        records = read_journal(j.path)
        beats = [r for r in records if r["kind"] == "heartbeat"]
        assert beats[0]["counter_deltas"] == {"solver/x/solves": 3}
        assert beats[1]["counter_deltas"] == {"solver/x/solves": 2}
        assert beats[1]["gauges"]["stream/overlap_fraction"] == 0.4
        assert beats[1]["sweep"] == 2

    def test_streaming_solve_emits_epoch_heartbeats(self, tmp_path):
        from photon_ml_tpu.telemetry import SolverTelemetry, default_registry

        journal = RunJournal(tmp_path, rank=0)
        telemetry = SolverTelemetry(
            journal=journal, registry=default_registry()
        )
        _train_streaming(telemetry)
        journal.close()
        beats = [r for r in read_journal(journal.path)
                 if r["kind"] == "heartbeat"]
        assert beats, "streaming solve emitted no heartbeats"
        assert all(b["stage"] == "glm_streaming" for b in beats)
        assert beats[-1]["epochs"] >= 1
        assert beats[-1]["lam_index"] == 1  # reached the second λ

    def test_cd_sweeps_emit_heartbeats(self, tmp_path):
        """The GAME CD loop heartbeats once per sweep."""
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.estimators import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            RandomEffectCoordinateConfig,
        )
        from photon_ml_tpu.algorithm.coordinates import (
            CoordinateOptimizationConfig,
        )
        from photon_ml_tpu.optim.optimizer import (
            OptimizerConfig,
            OptimizerType,
        )
        from photon_ml_tpu.telemetry import SolverTelemetry, default_registry
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(0)
        n, d = 96, 5
        users = np.array([f"u{i}" for i in rng.integers(0, 6, size=n)])
        ds = build_game_dataset(
            labels=rng.normal(size=n).astype(np.float32),
            feature_shards={
                "global": rng.normal(size=(n, d)).astype(np.float32),
                "per": rng.normal(size=(n, 3)).astype(np.float32),
            },
            entity_keys={"user": users},
        )
        journal = RunJournal(tmp_path, rank=0)
        telemetry = SolverTelemetry(
            journal=journal, registry=default_registry()
        )
        opt = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=3
            ),
            l2_weight=0.1,
        )
        GameEstimator(
            task=TaskType.LINEAR_REGRESSION,
            coordinate_configs={
                "fe": FixedEffectCoordinateConfig("global", opt),
                "re": RandomEffectCoordinateConfig("user", "per", opt),
            },
            num_iterations=2,
            telemetry=telemetry,
        ).fit(ds)
        journal.close()
        beats = [r for r in read_journal(journal.path)
                 if r["kind"] == "heartbeat" and r["stage"] == "game_cd"]
        assert [b["sweep"] for b in beats] == [1, 2]


class TestDurableFlushObserveOnly:
    def test_durable_on_vs_off_is_bitwise_on_streaming_solve(self, tmp_path):
        """The PR 9 discipline: flushing observes, never gates — the
        instrumented streaming solve's models are BITWISE identical with
        the durable journal, the legacy spool journal, and no journal."""
        from photon_ml_tpu.telemetry import SolverTelemetry, default_registry

        def run(durable):
            d = tmp_path / f"j-{durable}"
            journal = RunJournal(d, rank=0, durable=durable)
            telemetry = SolverTelemetry(
                journal=journal, registry=default_registry()
            )
            models = _train_streaming(telemetry)
            journal.close()
            return models

        base = _train_streaming(None)
        on = run(True)
        off = run(False)
        for lam in (0.1, 1.0):
            want = np.asarray(base[lam].coefficients.means)
            np.testing.assert_array_equal(
                want, np.asarray(on[lam].coefficients.means)
            )
            np.testing.assert_array_equal(
                want, np.asarray(off[lam].coefficients.means)
            )

    def test_durable_stage_readable_before_close_and_atomic_publish(
        self, tmp_path
    ):
        j = RunJournal(tmp_path, rank=0, durable=True)
        j.record("config", a=1)
        # BEFORE close: the stage file is already fsync'd and parseable
        assert os.path.exists(j.partial_path)
        assert not os.path.exists(j.path)
        records = read_journal(j.partial_path, tolerant=True)
        assert [r["kind"] for r in records] == ["journal_open", "config"]
        j.close()
        # AFTER close: atomic publish, stage gone, same rows + close row
        assert not os.path.exists(j.partial_path)
        kinds = [r["kind"] for r in read_journal(j.path)]
        assert kinds == ["journal_open", "config", "journal_close"]

    def test_tolerant_read_skips_torn_final_row(self, tmp_path):
        j = RunJournal(tmp_path, rank=0, durable=True)
        j.record("config", a=1)
        # simulate the SIGKILL-mid-write shape: a torn trailing row
        with open(j.partial_path, "a") as f:
            f.write('{"kind": "heartbeat", "seq"')
        records = read_journal(j.partial_path, tolerant=True)
        assert [r["kind"] for r in records] == ["journal_open", "config"]
        with pytest.raises(json.JSONDecodeError):
            read_journal(j.partial_path)
        j.close()

    def test_non_durable_path_unchanged(self, tmp_path):
        """durable=False keeps the legacy tmp-spool shape: nothing in the
        destination directory until close()."""
        target = tmp_path / "out"
        j = RunJournal(target, rank=0, durable=False)
        j.record("config", a=1)
        assert not os.path.exists(target)  # not even the directory
        j.close()
        assert os.path.exists(j.path)
        assert [r["kind"] for r in read_journal(j.path)] == [
            "journal_open", "config", "journal_close",
        ]


class TestJournalFindings:
    def test_overlap_zero_with_prefetch_on_flagged(self):
        records = [
            {"kind": "config", "streaming_prefetch": True},
            {"kind": "metrics", "snapshot": {
                "counters": {},
                "gauges": {"stream/overlap_fraction": 0.0,
                           "stream/chunks_per_epoch": 8},
            }},
            {"kind": "journal_close"},
        ]
        findings = verdicts.journal_findings(records)
        assert any(v.rule == "overlap-with-prefetch-on"
                   and v.status == verdicts.PATHOLOGY for v in findings)

    def test_quarantine_and_preemption_counters_reported(self):
        records = [
            {"kind": "metrics", "snapshot": {
                "counters": {"resilience/quarantined_blocks": 3,
                             "resilience/preemptions": 1,
                             "resilience/checkpoint_restores": 1,
                             "resilience/epochs_resumed": 7},
                "gauges": {},
            }},
            {"kind": "journal_close"},
        ]
        findings = verdicts.journal_findings(records)
        rules = {v.rule for v in findings}
        assert "quarantine-nonzero" in rules
        assert "preemption-restarts" in rules

    def test_straggler_report_row_named(self):
        """The PR 9 journaled straggler table surfaces rank + reason."""
        records = [
            {"kind": "straggler_report", "num_ranks": 2, "tags": [
                {"tag": "hybrid_hot/*", "wait_s": [0.4, 0.01],
                 "count": [1, 1], "missing_ranks": [],
                 "straggler_rank": 1, "reason": "least_wait"},
            ]},
            {"kind": "journal_close"},
        ]
        findings = verdicts.journal_findings(records)
        v = next(v for v in findings if v.rule == "straggler-attribution")
        assert "rank 1" in v.detail and "hybrid_hot" in v.detail
        # a never-arrived rank elevates to warning
        records[0]["tags"][0]["reason"] = "never_arrived"
        findings = verdicts.journal_findings(records)
        v = next(v for v in findings if v.rule == "straggler-attribution")
        assert v.status == verdicts.WARNING

    def test_unclosed_journal_names_last_heartbeat(self):
        records = [
            {"kind": "journal_open"},
            {"kind": "heartbeat", "stage": "glm_streaming", "epochs": 4,
             "seq": 1, "ts": 0.0, "elapsed_ms": 1.0},
        ]
        findings = verdicts.journal_findings(records)
        v = next(v for v in findings if v.rule == "journal-finalized")
        assert "epochs" in v.detail and "4" in v.detail
