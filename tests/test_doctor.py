"""The run doctor and what it reads: journal heartbeats, the crash-durable
flush's observe-only pin, the journal findings, and dev/doctor.py itself
over directories of journals (its exit code, ``--strict``, ``--json``, the
module CLI).

The doctor's program-ledger table, its ``--live`` lines and the cross-rank
recovery section are held by tests/test_program_ledger.py,
tests/test_resilience.py and tests/test_coordinated.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dev.doctor import main as doctor_main, run_doctor  # noqa: E402
from photon_ml_tpu.telemetry import verdicts  # noqa: E402
from photon_ml_tpu.telemetry.journal import (  # noqa: E402
    RunJournal,
    read_journal,
)


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


# ---------------------------------------------------------------------------
# dev/doctor.py over a directory
# ---------------------------------------------------------------------------


def _journal_dir(directory, *, closed: bool) -> str:
    """One journal with a heartbeat: published whole, or left as the
    ``.jsonl`` a run that died after its rows would leave (no close row)."""
    journal = RunJournal(directory, rank=0)
    journal.record("config", streaming_prefetch=True)
    journal.heartbeat(stage="glm_streaming", epochs=4)
    journal.close()
    if not closed:
        rows = [r for r in read_journal(journal.path)
                if r["kind"] != "journal_close"]
        with open(journal.path, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return str(directory)


class TestDoctorOverDirectory:
    def test_pre_chip_captures_are_not_read(self, tmp_path):
        """``BENCH_r*`` / ``MULTICHIP_r*`` files were the pre-chip regime's
        evidence (PR 29 took their reader out). A directory of them holds
        nothing the doctor reads: no error, nothing judged, even a capture
        that used to fail."""
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(
            {"n_rounds": 1, "rc": 0, "parsed": None, "tail": "x" * 2000}))
        (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
            {"n_devices": 8, "rc": 1, "ok": False, "skipped": False}))
        for strict in (False, True):
            code, findings, text = run_doctor(str(tmp_path), strict=strict)
            assert code == 0 and findings == []
            assert "nothing the doctor reads" in text
            assert "BENCH_r01" not in text and "MULTICHIP_r01" not in text

    @pytest.mark.parametrize("strict", [False, True])
    def test_closed_clean_journal_exits_zero(self, tmp_path, strict):
        code, findings, text = run_doctor(
            _journal_dir(tmp_path, closed=True), strict=strict)
        assert code == 0
        assert [v for v in findings if v.status != verdicts.INFO] == []
        assert "run-journal.jsonl: 4 row(s)" in text
        assert "'stage': 'glm_streaming', 'epochs': 4" in text
        assert "PATHOLOGIES/WARNINGS: none" in text

    @pytest.mark.parametrize("strict,want", [(False, 0), (True, 1)])
    def test_unclosed_journal_fails_only_the_strict_gate(
        self, tmp_path, strict, want
    ):
        """What the parent did, pinned: a warning is reported either way
        and decides the exit code under ``--strict`` alone."""
        code, findings, text = run_doctor(
            _journal_dir(tmp_path, closed=False), strict=strict)
        assert code == want
        (v,) = findings
        assert (v.rule, v.status) == ("journal-finalized", verdicts.WARNING)
        assert "journal [journal-finalized]: journal never finalized" in text

    def test_main_json_carries_exit_code_and_finding_fields(
        self, tmp_path, capsys
    ):
        code = doctor_main(
            [_journal_dir(tmp_path, closed=False), "--strict", "--json"])
        report = json.loads(capsys.readouterr().out)
        assert code == 1 and report["exit_code"] == 1
        (finding,) = report["findings"]
        assert {"metric", "rule", "status", "detail"} <= set(finding)
        assert finding["metric"] == "journal"
        assert finding["rule"] == "journal-finalized"
        assert finding["status"] == "warning"
        assert "last heartbeat cursor" in finding["detail"]

    def test_module_cli_entrypoint(self, tmp_path):
        """``python -m dev.doctor DIR --json`` from the repo root: the
        operator's invocation."""
        proc = subprocess.run(
            [sys.executable, "-m", "dev.doctor",
             _journal_dir(tmp_path, closed=True), "--json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert json.loads(proc.stdout) == {"exit_code": 0, "findings": []}
