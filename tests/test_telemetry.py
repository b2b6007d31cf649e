"""Telemetry layer: registry semantics, JSONL journal, rank gating, solver
tracing, probes, and the --telemetry-dir driver contract.

Reference parity targets: PhotonLogger.scala:34-90 (spool + publish-on-close
semantics, level restoration), OptimizationStatesTracker.scala:82-101
(per-solve convergence reporting), event/ (emitter wiring).
"""

from __future__ import annotations

import json
import logging
import math
import os

import numpy as np
import pytest

from photon_ml_tpu.telemetry import (
    CompileMonitor,
    MetricsRegistry,
    RunJournal,
    SolverTelemetry,
    lane_summary,
    solver_result_row,
)
from photon_ml_tpu.telemetry.journal import json_safe


class TestRegistry:
    def test_counter(self):
        reg = MetricsRegistry()
        c = reg.counter("a")
        c.inc()
        c.inc(4)
        assert reg.counter("a").value == 5  # get-or-create returns the same
        assert reg.snapshot()["counters"]["a"] == 5

    def test_gauge(self):
        reg = MetricsRegistry()
        assert reg.gauge("g").value is None
        reg.gauge("g").set(3)
        reg.gauge("g").set(7.5)  # last write wins
        assert reg.snapshot()["gauges"]["g"] == 7.5

    def test_histogram_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        for v in range(1, 101):  # 1..100
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100
        assert s["total"] == pytest.approx(5050.0)
        assert s["mean"] == pytest.approx(50.5)
        assert s["min"] == 1.0 and s["max"] == 100.0
        assert s["p50"] == 50.0  # nearest-rank
        assert s["p95"] == 95.0

    def test_histogram_empty(self):
        s = MetricsRegistry().histogram("h").summary()
        assert s["count"] == 0 and math.isnan(s["p50"])

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.histogram("x")

    def test_remove_prefix(self):
        reg = MetricsRegistry()
        reg.counter("timing/a")
        reg.counter("other/b")
        reg.remove_prefix("timing/")
        snap = reg.snapshot()["counters"]
        assert "timing/a" not in snap and "other/b" in snap


class TestTimedIntoRegistry:
    def test_timing_summary_distribution_fields(self):
        from photon_ml_tpu.util import Timed
        from photon_ml_tpu.util.timed import reset_timings, timing_summary

        reset_timings()
        for _ in range(3):
            with Timed("t9-phase", log_level=logging.DEBUG):
                pass
        summary = timing_summary()["t9-phase"]
        # superset of the pre-telemetry {count, total, mean} shape
        assert summary["count"] == 3
        assert summary["total"] == pytest.approx(
            summary["mean"] * 3, rel=1e-6
        )
        assert summary["min"] <= summary["p50"] <= summary["p95"] <= summary["max"]
        reset_timings()
        assert "t9-phase" not in timing_summary()


class TestRunJournal:
    def test_round_trip_and_atomic_finalize(self, tmp_path):
        out = tmp_path / "tele"
        j = RunJournal(out, rank=0)
        j.record("config", lam=np.float32(0.5), n=np.int64(3),
                 arr=np.arange(3), bad=float("nan"), name="x")
        # spool only: the journal must not exist before close (atomic
        # publish like PhotonLogger)
        assert not os.path.exists(j.path)
        j.close()
        rows = RunJournal.read(j.path)
        kinds = [r["kind"] for r in rows]
        assert kinds == ["journal_open", "config", "journal_close"]
        cfg = rows[1]
        assert cfg["lam"] == 0.5 and cfg["n"] == 3
        assert cfg["arr"] == [0, 1, 2]
        assert cfg["bad"] is None  # NaN -> strict-JSON null
        # every line independently parseable (the JSONL contract)
        with open(j.path) as f:
            for line in f:
                json.loads(line)

    def test_close_idempotent_and_inert_after(self, tmp_path):
        j = RunJournal(tmp_path, rank=0)
        j.close()
        j.close()
        j.record("late", x=1)  # no-op, no crash
        assert len(RunJournal.read(j.path)) == 2

    def test_rank_gating_with_collectives(self, tmp_path):
        """Only rank 0 writes; a collective over the 8-device mesh still
        runs regardless of journal activity (the journal never gates
        device code — CLAUDE.md multi-process rules)."""
        import jax
        import jax.numpy as jnp

        worker = RunJournal(tmp_path / "w", rank=1)
        chief = RunJournal(tmp_path / "c", rank=0)
        assert not worker.active and chief.active
        for j in (worker, chief):
            # unconditional telemetry calls on EVERY rank, as drivers do
            j.record("convergence", iterations=3)
            # ... interleaved with collective work on all 8 devices
            total = jax.pmap(
                lambda x: jax.lax.psum(x, "data"), axis_name="data"
            )(jnp.ones((8,)))
            assert float(total[0]) == 8.0
            j.close()
        assert not os.path.exists(tmp_path / "w" / "run-journal.jsonl")
        assert os.path.exists(chief.path)

    def test_none_directory_inert(self):
        j = RunJournal(None)
        j.record("x")
        j.close()
        assert j.path is None

    def test_json_safe_enums_and_dataclasses(self):
        import dataclasses
        import enum

        class E(enum.Enum):
            A = 1

        @dataclasses.dataclass
        class D:
            v: float

        assert json_safe({"e": E.A, "d": D(v=1.5), "t": (1, 2)}) == {
            "e": "A", "d": {"v": 1.5}, "t": [1, 2]
        }


def _tiny_solve(max_iter=25, tolerance=1e-7):
    import jax.numpy as jnp

    from photon_ml_tpu.optim.lbfgs import minimize_lbfgs

    def vg(w):
        v = 0.5 * jnp.vdot(w - 1.0, w - 1.0)
        return v, w - 1.0

    return minimize_lbfgs(vg, jnp.zeros(4), max_iter=max_iter,
                          tolerance=tolerance)


class TestSolverTrace:
    def test_solver_result_row(self):
        row = solver_result_row(_tiny_solve())
        assert row["iterations"] >= 1
        assert isinstance(row["reason"], str) and row["reason"] != "NOT_CONVERGED"
        assert row["converged"] is True
        assert row["value_history"][0] >= row["value_history"][-1]

    def test_lane_summary_tallies_and_max_iter_pathology(self):
        import jax

        # tolerance=0 forces every lane to a non-gradient stop; max_iter=3
        # makes "lanes pay max_iter / line search" visible in the tally
        results = jax.vmap(lambda s: _tiny_solve(max_iter=3, tolerance=0.0))(
            np.arange(5)
        )
        s = lane_summary(results)
        assert s["num_lanes"] == 5
        assert sum(s["reasons"].values()) == 5
        assert (
            s["lanes_at_max_iterations"] + s["lanes_not_converged"]
            + sum(k for r, k in s["reasons"].items()
                  if r not in ("MAX_ITERATIONS", "NOT_CONVERGED"))
            == 5
        )

    def test_record_coordinate_dispatch(self, tmp_path):
        from photon_ml_tpu.optim.common import LaneTrace

        j = RunJournal(tmp_path, rank=0)
        tel = SolverTelemetry(journal=j)
        tel.record_coordinate("fe", 0, _tiny_solve())
        trace = LaneTrace(
            iterations=np.array([3, 25, 25]),
            reason=np.array([2, 1, 1]),
            value=np.array([0.1, 0.2, 0.3]),
            gradient_norm=np.array([1e-8, 1.0, 1.0]),
            valid=np.array([True, True, False]),  # padding lane dropped
        )
        tel.record_coordinate("re", 1, trace)
        tel.record_coordinate("locked", 2, None, metrics={"AUC": 0.5})
        j.close()
        rows = RunJournal.read(j.path)
        by_kind = {}
        for r in rows:
            by_kind.setdefault(r["kind"], []).append(r)
        assert by_kind["convergence"][0]["coordinate"] == "fe"
        lanes = by_kind["convergence_lanes"][0]
        assert lanes["num_lanes"] == 2  # padding lane masked out
        assert lanes["reasons"] == {
            "FUNCTION_VALUES_WITHIN_TOLERANCE": 1, "MAX_ITERATIONS": 1
        }
        assert lanes["lanes_at_max_iterations"] == 1
        assert by_kind["coordinate_update"][0]["evaluation"] == {"AUC": 0.5}

    def test_train_glm_grid_lane_rows(self, tmp_path, rng):
        from tests.conftest import make_classification

        from photon_ml_tpu.data.batch import LabeledPointBatch
        from photon_ml_tpu.estimators import train_glm_grid
        from photon_ml_tpu.types import TaskType

        x, y, _ = make_classification(rng, n=120, d=5)
        j = RunJournal(tmp_path, rank=0)
        train_glm_grid(
            LabeledPointBatch.create(x, y), TaskType.LOGISTIC_REGRESSION,
            regularization_weights=(0.1, 1.0, 10.0),
            telemetry=SolverTelemetry(journal=j),
        )
        j.close()
        rows = RunJournal.read(j.path)
        conv = [r for r in rows if r["kind"] == "convergence"]
        assert [r["lambda"] for r in conv] == [0.1, 1.0, 10.0]
        assert all(r["iterations"] >= 1 and isinstance(r["reason"], str)
                   for r in conv)
        tally = [r for r in rows if r["kind"] == "convergence_lanes"][0]
        assert tally["num_lanes"] == 3


class TestProbes:
    def test_compile_monitor_counts_fresh_jit(self):
        import jax
        import jax.numpy as jnp

        with CompileMonitor() as cm:
            # a fresh closure => a genuinely new executable every run
            salt = np.random.default_rng().integers(1 << 30)
            jax.jit(lambda x: x * 2 + int(salt))(jnp.ones(3)).block_until_ready()
        assert cm.count >= 1
        assert cm.seconds > 0

    @pytest.mark.parametrize("metric", [
        "jax/trace_seconds",
        "jax/lower_seconds",
        "jax/backend_compile_seconds",
    ])
    def test_listener_histogram_rises_across_one_fresh_jit(self, metric):
        """The three histograms the benchmark's program-cost metrics read
        (PERF.md §3), fed by a real compile and not a replayed event."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.telemetry import install_compile_listener

        registry = MetricsRegistry()
        install_compile_listener(registry)
        operand = jnp.ones(3)  # its own little programs, before the reading
        histogram = registry.histogram(metric)
        count, total = histogram.count, histogram.total
        salt = np.random.default_rng().integers(1 << 30)
        jax.jit(lambda x: x * 3 + int(salt))(operand).block_until_ready()
        assert histogram.count > count
        assert histogram.total > total

    def test_compile_listener_installs_once_per_registry(self):
        """jax.monitoring has no unregister: a second install on the same
        registry must add no second listener, or every event counts twice."""
        import jax.monitoring

        from photon_ml_tpu.telemetry import install_compile_listener
        from photon_ml_tpu.telemetry.probes import COMPILE_COUNT_METRIC

        registry = MetricsRegistry()
        install_compile_listener(registry)
        install_compile_listener(registry)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.5
        )
        assert registry.counter(COMPILE_COUNT_METRIC).value == 1
        assert registry.histogram("jax/backend_compile_seconds").total == 0.5

    def test_live_buffer_bytes(self):
        import jax.numpy as jnp

        from photon_ml_tpu.telemetry import live_buffer_bytes

        keep = jnp.ones((1024,), jnp.float32)
        assert live_buffer_bytes() >= keep.nbytes


class TestEventEmitter:
    def test_unregister_idempotent(self):
        from photon_ml_tpu.util import EventEmitter

        emitter = EventEmitter()
        listener = lambda e: None  # noqa: E731
        emitter.unregister(listener)  # never registered: no-op
        emitter.register(listener)
        emitter.unregister(listener)
        emitter.unregister(listener)  # already removed: no-op


class TestPhotonLoggerLevels:
    def test_close_restores_captured_levels(self, tmp_path):
        from photon_ml_tpu.util import PhotonLogger

        captured = logging.getLogger("photon_ml_tpu")
        prior = captured.level
        try:
            captured.setLevel(logging.WARNING)
            log = PhotonLogger(tmp_path / "job.log", level=logging.DEBUG)
            assert captured.level == logging.DEBUG  # lowered while attached
            log.close()
            assert captured.level == logging.WARNING  # restored, not leaked
        finally:
            captured.setLevel(prior)


class TestGameCoordinateTelemetry:
    def test_cd_loop_emits_per_coordinate_rows(self, tmp_path, rng):
        from photon_ml_tpu.algorithm.coordinates import (
            CoordinateOptimizationConfig,
        )
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.estimators import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            RandomEffectCoordinateConfig,
        )
        from photon_ml_tpu.optim.optimizer import OptimizerConfig
        from photon_ml_tpu.types import TaskType

        n, d_fe, d_re = 300, 5, 3
        users = np.array([f"u{i}" for i in rng.integers(0, 8, size=n)])
        x_fe = rng.normal(size=(n, d_fe))
        x_re = rng.normal(size=(n, d_re))
        y = x_fe @ rng.normal(size=d_fe) + 0.1 * rng.normal(size=n)
        ds = build_game_dataset(
            labels=y,
            feature_shards={"global": x_fe, "per_entity": x_re},
            entity_keys={"user": users},
        )
        opt = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=8), l2_weight=1.0
        )
        journal = RunJournal(tmp_path, rank=0)
        est = GameEstimator(
            task=TaskType.LINEAR_REGRESSION,
            coordinate_configs={
                "fe": FixedEffectCoordinateConfig("global", opt),
                "per-user": RandomEffectCoordinateConfig(
                    "user", "per_entity", opt
                ),
            },
            num_iterations=2,
            telemetry=SolverTelemetry(journal=journal),
        )
        est.fit(ds)
        journal.close()
        rows = RunJournal.read(journal.path)
        conv = [r for r in rows if r["kind"] == "convergence"]
        # FE coordinate: one row per outer iteration
        fe_rows = [r for r in conv if r["coordinate"] == "fe"]
        assert [r["outer_iteration"] for r in fe_rows] == [0, 1]
        assert all(r["iterations"] >= 1 for r in fe_rows)
        # RE coordinate: per-entity lanes + a reason tally per iteration
        tallies = [r for r in rows if r["kind"] == "convergence_lanes"]
        assert [t["outer_iteration"] for t in tallies] == [0, 1]
        assert all(t["coordinate"] == "per-user" for t in tallies)
        assert all(t["num_lanes"] == 8 for t in tallies)  # 8 users, no padding
        assert all(sum(t["reasons"].values()) == t["num_lanes"]
                   for t in tallies)


class TestGLMDriverTelemetry:
    def test_driver_run_produces_parseable_journal(self, tmp_path, rng):
        """The PR acceptance contract: a CPU-mesh GLM driver run with
        --telemetry-dir yields a parseable JSONL journal with >= 1
        phase-timing record, per-λ convergence rows carrying iteration
        counts and convergence reasons, and a compile-count gauge — and
        the driver emits OptimizationLogEvents (it had no event wiring)."""
        from photon_ml_tpu.cli import glm_driver
        from photon_ml_tpu.util.events import OptimizationLogEvent

        n, d = 200, 6
        w = rng.normal(size=d)
        base = tmp_path / "data"
        for split, nn in (("train", n), ("val", 80)):
            lines = []
            for _ in range(nn):
                x = rng.normal(size=d)
                label = "+1" if rng.random() < 1 / (1 + np.exp(-(x @ w))) else "-1"
                lines.append(
                    label + " " + " ".join(
                        f"{j + 1}:{x[j]:.6f}" for j in range(d)
                    )
                )
            (base / split).mkdir(parents=True, exist_ok=True)
            (base / split / "data.libsvm").write_text("\n".join(lines))

        seen_events = []
        glm_driver.events.register(seen_events.append)
        try:
            glm_driver.main([
                "--input-data-path", str(base / "train" / "data.libsvm"),
                "--validation-data-path", str(base / "val" / "data.libsvm"),
                "--output-dir", str(tmp_path / "out"),
                "--task-type", "LOGISTIC_REGRESSION",
                "--regularization-weights", "0.1,1",
                "--input-format", "libsvm",
                "--max-iterations", "30",
                "--telemetry-dir", str(tmp_path / "tele"),
            ])
        finally:
            glm_driver.events.unregister(seen_events.append)

        rows = RunJournal.read(tmp_path / "tele" / "run-journal.jsonl")
        kinds = {r["kind"] for r in rows}
        assert {"config", "phase_timing", "convergence", "gauge"} <= kinds
        phases = {r["name"] for r in rows if r["kind"] == "phase_timing"}
        assert "glm train" in phases
        conv = [r for r in rows if r["kind"] == "convergence"]
        assert sorted(r["lambda"] for r in conv) == [0.1, 1.0]
        assert all(
            r["iterations"] >= 1 and isinstance(r["reason"], str)
            and r["coordinate"] == "glm"
            for r in conv
        )
        gauges = {
            r["name"]: r["value"] for r in rows if r["kind"] == "gauge"
        }
        assert "jax/backend_compile_count" in gauges
        # the registry snapshot is persisted (solver tallies + timings)
        snapshots = [r for r in rows if r["kind"] == "metrics"]
        assert len(snapshots) == 1
        assert any(k.startswith("solver/")
                   for k in snapshots[0]["snapshot"]["counters"])
        # OptimizationLogEvents now flow from the GLM driver
        opt_events = [e for e in seen_events
                      if isinstance(e, OptimizationLogEvent)]
        assert len(opt_events) == 2
        assert {e.metrics["lambda"] for e in opt_events} == {0.1, 1.0}

    def test_failed_run_still_publishes_journal_with_timings(self, tmp_path):
        """A failed driver run's journal — the one that most needs phase
        attribution — still publishes with phase timings and gauges."""
        from photon_ml_tpu.cli import glm_driver

        with pytest.raises(Exception):
            glm_driver.main([
                "--input-data-path", str(tmp_path / "does-not-exist"),
                "--output-dir", str(tmp_path / "out"),
                "--task-type", "LOGISTIC_REGRESSION",
                "--input-format", "libsvm",
                "--telemetry-dir", str(tmp_path / "tele"),
            ])
        rows = RunJournal.read(tmp_path / "tele" / "run-journal.jsonl")
        kinds = {r["kind"] for r in rows}
        assert {"config", "phase_timing", "gauge", "metrics"} <= kinds
