"""Compiled-program ledger (ISSUE 13): registration wrapper, recompile
attribution, cost/memory degrade, doctor table + pathologies, heartbeat
snapshots.

Contracts pinned here:

- ledger OFF (the default) is inert: ledger_jit sites dispatch straight
  through, and instrumented paths (streaming solve, serving replay) are
  BITWISE identical with a ledger installed vs not (observes, never gates);
- a forced signature change journals a program_recompile row naming the
  exact differing leaves (shape/dtype/static), and weak-typed scalar VALUE
  changes never churn the signature set (they never recompile);
- cost/memory analysis unavailability degrades to None fields without
  raising into the dispatch path (the CPU-backend shape);
- dev/doctor.py renders the per-program ledger table and the
  recompile-storm pathology fires on a storm fixture;
- heartbeat rows carry live-HBM + compile-count snapshots and the doctor
  reports heartbeat staleness.
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.telemetry.journal import RunJournal, heartbeat_cursor
from photon_ml_tpu.telemetry.program_ledger import (
    ProgramLedger,
    build_signature,
    current_ledger,
    diff_signatures,
    install_ledger,
    ledger_active,
    ledger_jit,
    uninstall_ledger,
)
from photon_ml_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture
def ledger(tmp_path):
    journal = RunJournal(tmp_path / "tele", rank=0)
    led = install_ledger(
        ProgramLedger(registry=MetricsRegistry(), journal=journal)
    )
    try:
        yield led
    finally:
        uninstall_ledger()
        journal.close()


def _journal_rows(led):
    led.journal.close()
    return RunJournal.read(led.journal.path)


def _program_rows(led, kind=None):
    rows = [r for r in _journal_rows(led)
            if r["kind"].startswith("program")]
    if kind is not None:
        rows = [r for r in rows if r["kind"] == kind]
    return rows


# ---------------------------------------------------------------------------
# wrapper basics
# ---------------------------------------------------------------------------


class TestWrapper:
    def test_off_by_default_passthrough(self):
        assert not ledger_active()
        assert current_ledger() is None
        f = ledger_jit(lambda x: x * 2, label="unit/off")
        np.testing.assert_array_equal(np.asarray(f(np.ones(3))), 2 * np.ones(3))
        assert f.label == "unit/off"

    def test_decorator_with_partial_and_statics(self, ledger):
        from functools import partial

        @partial(ledger_jit, label="unit/static_deco",
                 static_argnames=("mode",))
        def g(x, *, mode):
            return x + (1.0 if mode == "a" else 2.0)

        out = g(np.zeros(2, np.float32), mode="a")
        np.testing.assert_array_equal(np.asarray(out), np.ones(2))
        assert ledger.signature_count("unit/static_deco") == 1

    def test_under_trace_bypasses_observation(self, ledger):
        import jax

        inner = ledger_jit(lambda x: x + 1, label="unit/inner")

        @jax.jit
        def outer(x):
            return inner(x) * 2

        outer(np.ones(2, np.float32))
        # the inner call inlined into the outer trace: no separate
        # dispatched program, so the ledger must not count it
        assert "unit/inner" not in ledger.labels()

    def test_failure_path_still_records(self, ledger):
        f = ledger_jit(lambda x: x.reshape(-1, 3), label="unit/fail")
        with pytest.raises(TypeError):
            f(np.ones(4, np.float32))  # 4 does not reshape to (-1, 3)
        snap = ledger.snapshot()
        assert snap["unit/fail"]["calls"] == 1


# ---------------------------------------------------------------------------
# signatures + attribution
# ---------------------------------------------------------------------------


class TestSignatures:
    def test_diff_names_shape_change(self):
        a = build_signature((np.ones((4, 2), np.float32),), {})
        b = build_signature((np.ones((6, 2), np.float32),), {})
        (change,) = diff_signatures(a, b)
        assert change["field"] == "shape"
        assert change["old"] == [4, 2] and change["new"] == [6, 2]

    def test_diff_names_dtype_and_static(self):
        a = build_signature((np.ones(3, np.float32),), {"mode": "a"},
                            static_argnames=("mode",))
        b = build_signature((np.ones(3, np.float64),), {"mode": "b"},
                            static_argnames=("mode",))
        fields = {c["field"] for c in diff_signatures(a, b)}
        assert fields == {"dtype", "static"}

    def test_weak_scalars_share_one_signature(self):
        a = build_signature((np.ones(3, np.float32), 2.0), {})
        b = build_signature((np.ones(3, np.float32), 3.0), {})
        assert a.key == b.key  # value changes never recompile

    def test_recompile_row_names_changed_leaf(self, ledger):
        f = ledger_jit(lambda x: x * 2, label="unit/attr")
        f(np.ones(16384, np.float32))
        f(np.ones(16000, np.float32))
        (row,) = _program_rows(ledger, "program_recompile")
        assert row["label"] == "unit/attr"
        (change,) = row["changed"]
        assert change["field"] == "shape"
        assert change["old"] == [16384] and change["new"] == [16000]
        assert "16384" in row["summary"] and "16000" in row["summary"]

    def test_static_arg_recompile_attributed(self, ledger):
        f = ledger_jit(lambda x, *, mode: x + len(mode),
                       label="unit/static", static_argnames=("mode",))
        f(np.ones(2, np.float32), mode="a")
        f(np.ones(2, np.float32), mode="bb")
        (row,) = _program_rows(ledger, "program_recompile")
        (change,) = row["changed"]
        assert change["field"] == "static"
        assert change["leaf"] == "mode"

    def test_weak_scalar_value_change_no_recompile_row(self, ledger):
        f = ledger_jit(lambda x, k: x * k, label="unit/weak")
        f(np.ones(4, np.float32), 2.0)
        f(np.ones(4, np.float32), 3.0)
        assert _program_rows(ledger, "program_recompile") == []
        assert ledger.signature_count("unit/weak") == 1

    def test_signature_count_monotone_past_eviction(self, tmp_path):
        """The diff cache evicts past max_signatures but the signatures
        gauge stays EXACT (monotone): unbounded-shape churn must never
        read as redundant compiles (executable thrash) in the doctor's
        storm math."""
        from photon_ml_tpu.telemetry import verdicts

        journal = RunJournal(tmp_path, rank=0)
        reg = MetricsRegistry()
        led = install_ledger(ProgramLedger(
            registry=reg, journal=journal, max_signatures=2,
        ))
        try:
            f = ledger_jit(lambda x: x + 1, label="unit/churny")
            for n in range(8, 14):  # 6 distinct shapes, cache holds 2
                f(np.ones(n, np.float32))
        finally:
            uninstall_ledger()
        assert led.signature_count("unit/churny") == 6
        snap = reg.snapshot()
        assert snap["gauges"]["xla/unit/churny/signatures"] == 6
        journal.record_metrics(reg.snapshot())
        journal.close()
        findings = verdicts.journal_findings(RunJournal.read(journal.path))
        # 6 compiles / 6 distinct signatures: zero redundancy — no storm
        assert not [v for v in findings if v.rule == "recompile-storm"]

    def test_analyze_cost_opt_out(self, tmp_path):
        journal = RunJournal(tmp_path, rank=0)
        led = install_ledger(ProgramLedger(
            registry=MetricsRegistry(), journal=journal, analyze_cost=False,
        ))
        try:
            f = ledger_jit(lambda x: x @ x, label="unit/nocost")
            f(np.ones((4, 4), np.float32))
        finally:
            uninstall_ledger()
        (row,) = [r for r in _journal_rows(led)
                  if r["kind"] == "program_compile"]
        assert row["cost"] is None  # pure bookkeeping: no AOT lower ran

    def test_counters_and_snapshot(self, ledger):
        f = ledger_jit(lambda x: x + 1, label="unit/counts")
        for n in (8, 8, 16):
            f(np.ones(n, np.float32))
        snap = ledger.snapshot()["unit/counts"]
        assert snap["calls"] == 3
        assert snap["compiles"] == 2
        assert snap["recompiles"] == 1
        assert snap["signatures"] == 2
        reg = ledger.registry.snapshot()
        assert reg["counters"]["xla/unit/counts/calls"] == 3
        assert reg["counters"]["xla/unit/counts/compiles"] == 2
        assert reg["gauges"]["xla/unit/counts/signatures"] == 2
        # compile seconds histogram accumulated per compile
        assert reg["histograms"]["xla/unit/counts/compile_seconds"]["count"] == 2


# ---------------------------------------------------------------------------
# cost / memory analysis
# ---------------------------------------------------------------------------


class TestAnalysis:
    def test_cost_analysis_on_new_signature(self, ledger):
        f = ledger_jit(lambda x: x @ x, label="unit/cost")
        f(np.ones((8, 8), np.float32))
        (row,) = _program_rows(ledger, "program_compile")
        # CPU backend implements HLO cost analysis: flops present; memory
        # is None because analyze_memory defaults OFF
        assert row["cost"] is not None and row["cost"]["flops"] > 0
        assert row["memory"] is None

    def test_memory_analysis_opt_in(self, tmp_path):
        journal = RunJournal(tmp_path / "t2", rank=0)
        led = install_ledger(ProgramLedger(
            registry=MetricsRegistry(), journal=journal, analyze_memory=True,
        ))
        try:
            f = ledger_jit(lambda x: x * 2, label="unit/mem")
            f(np.ones(4, np.float32))
        finally:
            uninstall_ledger()
        (row,) = [r for r in _journal_rows(led)
                  if r["kind"] == "program_compile"]
        assert isinstance(row["memory"], dict)
        assert "argument_size_in_bytes" in row["memory"]

    def test_unavailable_analysis_degrades_to_none(self, ledger):
        class NoAOT:
            """A jitted program whose AOT surface is unimplemented — the
            backend-without-analysis shape."""

            def lower(self, *a, **k):
                raise NotImplementedError("no AOT on this backend")

            def __call__(self, x):
                return x * 2

        out = ledger.observed_call(NoAOT(), "unit/degrade",
                                   (np.ones(3, np.float32),), {})
        np.testing.assert_array_equal(np.asarray(out), 2 * np.ones(3))
        rows = _program_rows(ledger)
        (row,) = [r for r in rows if r["label"] == "unit/degrade"]
        assert row["cost"] is None
        assert row.get("memory") is None


# ---------------------------------------------------------------------------
# ledger off is bitwise (observes, never gates)
# ---------------------------------------------------------------------------


class TestOffBitwise:
    def test_streaming_solve_identical_with_and_without_ledger(self):
        """The instrumented streaming path (ledger-labeled accumulate
        steps driven by the host-loop solver) trains BITWISE identically
        with a ledger installed vs not."""
        from photon_ml_tpu.estimators import train_glm_streaming
        from photon_ml_tpu.io.stream_reader import ArrayChunkSource
        from photon_ml_tpu.optim.optimizer import (
            OptimizerConfig,
            OptimizerType,
        )
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(7)
        n, d = 48, 5
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (x @ rng.normal(size=d) + 0.1 * rng.normal(size=n)).astype(
            np.float32
        )
        opt = OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=6
        )

        def fit():
            models = train_glm_streaming(
                ArrayChunkSource(x, y, chunk_rows=16),
                TaskType.LINEAR_REGRESSION, optimizer=opt,
                regularization_weights=(0.5,),
            )
            return np.asarray(models[0.5].coefficients.means)

        baseline = fit()
        led = install_ledger(ProgramLedger(registry=MetricsRegistry()))
        try:
            observed = fit()
        finally:
            uninstall_ledger()
        # the observed run really crossed the labeled streaming program
        assert "streaming/accumulate_value_grad" in led.labels()
        np.testing.assert_array_equal(baseline, observed)

    def test_serving_replay_identical_with_and_without_ledger(self):
        """The resident scorer's padded micro-batch replay scores BITWISE
        identically with a ledger installed vs not, and the ledger-backed
        compiled-signature gauge matches the bucket set."""
        from test_serving import _dense_fixture

        from photon_ml_tpu.data.game_data import slice_game_dataset
        from photon_ml_tpu.serving import ResidentScorer
        from photon_ml_tpu.telemetry import serving_counters
        from photon_ml_tpu.telemetry.registry import default_registry

        ds, model = _dense_fixture(n=64, seed=3, d=8)
        requests = [slice_game_dataset(ds, i, i + 3) for i in (0, 7, 21)]

        scorer = ResidentScorer(model, shapes=(16, 64))
        baseline = [scorer.score(r) for r in requests]

        serving_counters.reset_serving_metrics()
        led = install_ledger(ProgramLedger(registry=MetricsRegistry()))
        try:
            scorer2 = ResidentScorer(model, shapes=(16, 64))
            observed = [scorer2.score(r) for r in requests]
        finally:
            uninstall_ledger()
        for a, b in zip(baseline, observed):
            np.testing.assert_array_equal(a, b)
        assert "serve/score" in led.labels()
        gauge = default_registry().gauge(
            serving_counters.COMPILED_SIGNATURES
        ).value
        assert gauge == led.signature_count("serve/score")


# ---------------------------------------------------------------------------
# doctor integration: ledger table + recompile-storm pathology
# ---------------------------------------------------------------------------


class TestDoctorLedger:
    def _storm_dir(self, tmp_path):
        from photon_ml_tpu.telemetry import verdicts  # noqa: F401

        journal = RunJournal(tmp_path, rank=0)
        reg = MetricsRegistry()
        led = install_ledger(ProgramLedger(registry=reg, journal=journal))
        try:
            label = "streaming/accumulate_value_grad"
            f = ledger_jit(lambda x: x * 2, label=label)
            # a shape change first: the attribution rows must name leaves
            f(np.ones(16384, np.float32))
            f(np.ones(16000, np.float32))
            # then the storm shape: the program REBUILT per step — fresh
            # jit instances recompile the SAME signature (redundant
            # compiles, which no healthy bucket ladder ever produces)
            for _ in range(4):
                g = ledger_jit(lambda x: x * 2, label=label)
                g(np.ones(16000, np.float32))
        finally:
            uninstall_ledger()
        journal.record_metrics(reg.snapshot())
        journal.close()
        return tmp_path

    def test_doctor_renders_table_and_storm_fires(self, tmp_path):
        from dev.doctor import run_doctor

        directory = self._storm_dir(tmp_path)
        code, findings, text = run_doctor(str(directory))
        assert code == 0  # pathologies report, only regressions gate
        assert "program ledger" in text
        assert "streaming/accumulate_value_grad" in text
        assert "last recompile:" in text
        storm = [v for v in findings if v.rule == "recompile-storm"]
        assert storm and storm[0].status == "pathology"
        assert "streaming/accumulate_value_grad" in storm[0].detail
        # the finding names the redundancy and the attributed cause
        assert "rebuilt per step" in storm[0].detail
        assert "last attribution" in storm[0].detail
        # the journal's shape-change attribution row names the leaves
        rows = RunJournal.read(os.path.join(directory, "run-journal.jsonl"))
        recompiles = [r for r in rows if r["kind"] == "program_recompile"]
        assert any(
            c["field"] == "shape" and c["old"] == [16384]
            and c["new"] == [16000]
            for r in recompiles for c in r["changed"]
        )

    def test_storm_fails_doctor_under_strict(self, tmp_path):
        from dev.doctor import run_doctor

        directory = self._storm_dir(tmp_path)
        code, _, _ = run_doctor(str(directory), strict=True)
        assert code == 1

    def test_signature_churn_warning(self):
        from photon_ml_tpu.telemetry import verdicts

        records = [{"kind": "metrics", "seq": 0, "elapsed_ms": 10.0,
                    "snapshot": {
                        "counters": {},
                        "gauges": {"xla/serve/score/signatures": 9},
                        "histograms": {},
                    }}]
        findings = verdicts.journal_findings(records)
        churn = [v for v in findings if v.rule == "signature-churn"]
        assert churn and "serve/score" in churn[0].detail

    def test_hbm_overcommit_forecast_warning(self):
        from photon_ml_tpu.telemetry import verdicts

        records = [{
            "kind": "program_compile", "seq": 0, "elapsed_ms": 5.0,
            "label": "serve/score", "compiles": 1,
            "hbm_forecast_bytes": 20e9, "device_bytes_limit": 16e9,
        }]
        findings = verdicts.journal_findings(records)
        over = [v for v in findings
                if v.rule == "hbm-overcommit-forecast"]
        assert over and "serve/score" in over[0].detail

    def test_compile_dominated_warning_gated_on_elapsed(self):
        from photon_ml_tpu.telemetry import verdicts

        def records(elapsed_s, compile_s):
            return [{"kind": "metrics", "seq": 0,
                     "elapsed_ms": elapsed_s * 1e3,
                     "snapshot": {
                         "counters": {}, "gauges": {},
                         "histograms": {"jax/backend_compile_seconds": {
                             "count": 3, "total": compile_s}},
                     }}]

        hot = verdicts.journal_findings(records(60.0, 40.0))
        assert any(v.rule == "compile-dominated" for v in hot)
        # tiny fixture runs never report it (elapsed floor)
        cold = verdicts.journal_findings(records(5.0, 4.0))
        assert not any(v.rule == "compile-dominated" for v in cold)


# ---------------------------------------------------------------------------
# heartbeat satellites: hbm/compile snapshots + doctor staleness
# ---------------------------------------------------------------------------


class TestHeartbeatSnapshots:
    def test_heartbeat_carries_hbm_and_compiles(self, tmp_path):
        import jax

        from photon_ml_tpu.telemetry.probes import (
            COMPILE_COUNT_METRIC,
            install_compile_listener,
        )

        # the HBM probe only reads an ALREADY-initialized backend (a
        # heartbeat never forces one); training loops guarantee this,
        # the fixture does it explicitly
        jax.local_devices()
        reg = MetricsRegistry()
        install_compile_listener(reg)
        reg.counter(COMPILE_COUNT_METRIC).inc(7)
        with RunJournal(tmp_path, rank=0) as j:
            j.heartbeat(registry=reg, stage="sweep", sweep=2)
        (hb,) = [r for r in RunJournal.read(j.path)
                 if r["kind"] == "heartbeat"]
        assert isinstance(hb["hbm_bytes"], int)
        assert hb["compiles"] >= 7
        # the snapshots are journal bookkeeping, not the caller's cursor
        assert heartbeat_cursor(hb) == {"stage": "sweep", "sweep": 2}

    def test_doctor_reports_heartbeat_staleness_live_only(self, tmp_path):
        import jax

        from dev.doctor import run_doctor

        jax.local_devices()  # drift needs the hbm snapshot (see above)
        with RunJournal(tmp_path, rank=0) as j:
            j.heartbeat(stage="epoch", epoch=1)
            j.heartbeat(stage="epoch", epoch=2)
        # staleness is a LIVE signal (wedged vs slow): --live reports it,
        # a plain pass over a finalized journal must not imply a wedge
        code, _, text = run_doctor(str(tmp_path))
        assert code == 0
        assert "heartbeat staleness:" not in text
        code, _, text = run_doctor(str(tmp_path), live=True)
        assert code == 0
        assert "heartbeat staleness:" in text
        assert "2 heartbeat(s)" in text
        assert "heartbeat drift:" in text


# ---------------------------------------------------------------------------
# telemetry-dir export surface
# ---------------------------------------------------------------------------


class TestExports:
    def test_package_exports(self):
        import photon_ml_tpu.telemetry as t

        for name in ("ProgramLedger", "ledger_jit", "install_ledger",
                     "uninstall_ledger", "current_ledger", "ledger_active"):
            assert hasattr(t, name)

    def test_journal_rows_json_roundtrip(self, ledger):
        f = ledger_jit(lambda x: x + 1, label="unit/json")
        f(np.ones((2, 3), np.float32))
        (row,) = [r for r in _program_rows(ledger, "program_compile")
                  if r["label"] == "unit/json"]
        sig = row["signature"]
        (leaf,) = sig["leaves"]
        assert leaf["shape"] == [2, 3]
        assert leaf["dtype"] == "float32"
        assert leaf["kind"] == "array"
