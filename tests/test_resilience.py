"""Chaos suite: every injected fault class either recovers with the right
answer or fails fast with an attributed error — never a hang.

Drives the resilience layer (photon_ml_tpu/resilience/) end to end on the
virtual CPU mesh with dev/faultinject.py injectors: flaky-then-succeeding
callables, truncated/corrupted Avro blocks, mid-save crashes, withheld
exchange keys, NaN-poisoned coordinate updates. The reference has no
analogue — its fault tolerance is Spark lineage recompute (SURVEY.md §5);
these tests pin the explicit contract that replaces it.

No pytest-timeout in this environment: boundedness is enforced by the
operations' OWN deadlines (exchange timeouts of well under a second, retry
budgets with no-op sleeps) plus bounded thread joins — a regression that
reintroduces an unbounded wait fails the join assertion, not the CI clock.
"""

import json
import os
import threading

import numpy as np
import pytest

from dev import faultinject
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.resilience import (
    ExchangeTimeout,
    RetryPolicy,
    Transience,
    TransientError,
    classify_exception,
    run_with_recovery,
)
from photon_ml_tpu.telemetry import resilience_counters as rc

pytestmark = pytest.mark.chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_SLEEP = lambda _: None  # noqa: E731


def _policy(**kw):
    kw.setdefault("sleep", NO_SLEEP)
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------------
# classifier + RetryPolicy
# ---------------------------------------------------------------------------


class TestClassifier:
    def test_connection_and_timeout_types_are_transient(self):
        for exc in (
            ConnectionError("x"),
            ConnectionResetError("x"),
            TimeoutError("x"),
            BrokenPipeError("x"),
            OSError(110, "Connection timed out"),
            TransientError("forced"),
            RuntimeError("UNAVAILABLE: socket closed"),
            RuntimeError("DEADLINE_EXCEEDED while fetching"),
        ):
            assert classify_exception(exc) is Transience.TRANSIENT, exc

    def test_programming_errors_are_fatal(self):
        for exc in (
            ValueError("bad shape"),
            KeyError("missing"),
            RuntimeError("something exploded"),
        ):
            assert classify_exception(exc) is Transience.FATAL, exc

    def test_unavailable_with_address_is_transient(self):
        exc = RuntimeError("UNAVAILABLE: ipv4:10.0.0.2:41352: connection reset")
        assert classify_exception(exc) is Transience.TRANSIENT

    def test_device_oom_is_fatal_despite_resource_exhausted(self):
        from photon_ml_tpu.resilience import fatal_hint

        exc = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "8589934592 bytes"
        )
        assert classify_exception(exc) is Transience.FATAL
        assert "deterministic" in fatal_hint(exc)
        # the quota/rate-limit shape stays transient
        quota = RuntimeError("RESOURCE_EXHAUSTED: quota exceeded for resource")
        assert classify_exception(quota) is Transience.TRANSIENT

    def test_read_merged_rejects_bad_on_corrupt(self, tmp_path):
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            read_merged,
        )

        path = tmp_path / "x.avro"
        _write(str(path))
        cfg = {"g": FeatureShardConfiguration(feature_bags=("features",))}
        with pytest.raises(ValueError, match="on_corrupt"):
            read_merged(path, cfg, on_corrupt="Quarantine")

    def test_exchange_timeout_is_fatal(self):
        exc = ExchangeTimeout("tag", missing_ranks=(2,), key="k", rank=0)
        assert classify_exception(exc) is Transience.FATAL
        assert "rank(s) 2" in str(exc) and "'k'" in str(exc)


class TestRetryPolicy:
    def test_flaky_callable_recovers_and_counts(self):
        fn = faultinject.flaky(2, ConnectionError, result=42)
        before = rc.retries()
        assert _policy(max_attempts=3).call(fn) == 42
        assert fn.calls == 3
        assert rc.retries() - before == 2

    def test_fatal_error_not_retried(self):
        fn = faultinject.flaky(1, lambda: ValueError("deterministic"))
        with pytest.raises(ValueError):
            _policy(max_attempts=5).call(fn)
        assert fn.calls == 1

    def test_budget_exhaustion_counts_giveup(self):
        fn = faultinject.flaky(99, ConnectionError)
        before = rc.giveups()
        with pytest.raises(ConnectionError):
            _policy(max_attempts=3).call(fn)
        assert fn.calls == 3
        assert rc.giveups() - before == 1

    def test_jitter_is_deterministic_and_backoff_bounded(self):
        p = _policy(base_delay=0.2, multiplier=2.0, max_delay=1.0)
        delays = [p.delay(a, "key") for a in range(6)]
        assert delays == [p.delay(a, "key") for a in range(6)]  # stable
        assert all(d <= 1.0 * (1 + p.jitter) for d in delays)
        assert delays[1] > delays[0]  # actually backs off
        # different call keys decorrelate
        assert p.delay(0, "key") != p.delay(0, "other-key")


# ---------------------------------------------------------------------------
# corrupt-input quarantine
# ---------------------------------------------------------------------------

SCHEMA = {
    "type": "record",
    "name": "R",
    "fields": [
        {"name": "label", "type": "double"},
        {"name": "features", "type": {
            "type": "array",
            "items": {
                "type": "record", "name": "F",
                "fields": [
                    {"name": "name", "type": "string"},
                    {"name": "term", "type": "string"},
                    {"name": "value", "type": "double"},
                ],
            },
        }},
    ],
}


def _records(n):
    return [
        {
            "label": float(i),
            "features": [
                {"name": f"f{j}", "term": "", "value": float(i * 10 + j)}
                for j in range(3)
            ],
        }
        for i in range(n)
    ]


def _write(path, n=30, codec="deflate", block_records=10):
    avro_io.write_container(
        path, SCHEMA, _records(n), codec=codec, block_records=block_records
    )


class TestQuarantine:
    def test_clean_file_identical_in_both_modes(self, tmp_path):
        path = tmp_path / "clean.avro"
        _write(path)
        strict = list(avro_io.read_container(path))
        loose = list(avro_io.read_container(path, on_corrupt="quarantine"))
        assert strict == loose == _records(30)

    @pytest.mark.parametrize("codec", ["null", "deflate"])
    def test_corrupt_payload_block_skipped_and_counted(self, tmp_path, codec):
        path = str(tmp_path / "c.avro")
        _write(path, codec=codec)
        # 16 bytes of 0xFF: lands on a varint position (an endless
        # continuation -> "varint too long") even under the null codec,
        # where 8 bytes would only garble a double silently
        faultinject.corrupt_avro_block(path, block=1, nbytes=16)
        with pytest.raises((avro_io.AvroError, EOFError, Exception)):
            list(avro_io.read_container(path))
        before = rc.quarantined_blocks()
        out = list(avro_io.read_container(path, on_corrupt="quarantine"))
        assert out == _records(30)[:10] + _records(30)[20:]
        assert rc.quarantined_blocks() - before == 1
        events = rc.drain_quarantine_events()
        assert events and events[-1]["path"] == path
        assert events[-1]["byte_end"] > events[-1]["byte_start"]

    def test_truncated_final_block_quarantined(self, tmp_path):
        path = str(tmp_path / "t.avro")
        _write(path)
        faultinject.truncate_avro_block(path, block=-1)
        out = list(avro_io.read_container(path, on_corrupt="quarantine"))
        assert out == _records(30)[:20]
        assert len(avro_io.validate_container(path)) == 1

    def test_broken_sync_loses_exactly_the_unreachable_span(self, tmp_path):
        path = str(tmp_path / "s.avro")
        _write(path)
        faultinject.break_avro_sync(path, block=0)
        # block 0 decodes but its trailer is gone -> resync lands after
        # block 1's trailer: blocks 0 and 1 quarantined, block 2 recovered
        out = list(avro_io.read_container(path, on_corrupt="quarantine"))
        assert out == _records(30)[20:]
        rc.drain_quarantine_events()

    def test_block_range_reader_quarantines_payload_rot(self, tmp_path):
        path = str(tmp_path / "b.avro")
        _write(path)
        faultinject.corrupt_avro_block(path, block=1, nbytes=16)
        index = avro_io.scan_block_index(path, on_corrupt="quarantine")
        assert len(index) == 3  # framing intact; rot is payload-level
        got = list(
            avro_io.read_container_block_range(
                path, 0, 3, index=index, on_corrupt="quarantine"
            )
        )
        assert got == _records(30)[:10] + _records(30)[20:]
        rc.drain_quarantine_events()

    def test_read_merged_quarantine_recovers_and_default_raises(self, tmp_path):
        from photon_ml_tpu.io.data_reader import (
            FeatureShardConfiguration,
            read_merged,
        )

        data_dir = tmp_path / "d"
        os.makedirs(data_dir)
        _write(str(data_dir / "part-00000.avro"))
        faultinject.truncate_avro_block(
            str(data_dir / "part-00000.avro"), block=-1
        )
        cfg = {"global": FeatureShardConfiguration(feature_bags=("features",))}
        with pytest.raises(Exception):
            read_merged(data_dir, cfg)
        before = rc.quarantined_blocks()
        result = read_merged(data_dir, cfg, on_corrupt="quarantine")
        assert result.dataset.num_samples == 20  # 3rd block quarantined
        np.testing.assert_array_equal(
            np.asarray(result.dataset.labels), np.arange(20.0)
        )
        assert rc.quarantined_blocks() - before >= 1
        rc.drain_quarantine_events()


# ---------------------------------------------------------------------------
# exchange deadlines (withheld keys / absent ranks)
# ---------------------------------------------------------------------------


def _run_captured(fn, timeout=10.0):
    """Run fn in a thread with a bounded join; return its exception."""
    box = {}

    def target():
        try:
            fn()
            box["error"] = None
        except BaseException as e:  # captured for the test to assert on
            box["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "operation exceeded its bounded deadline (hang)"
    return box["error"]


class TestExchangeDeadlines:
    def test_withheld_allgather_times_out_attributed(self):
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        group = InProcessExchange.create_group(2, timeout=0.4)
        # rank 1 never publishes (simulated crash): rank 0's read must
        # fail attributed, not hang
        error = _run_captured(
            lambda: group[0].allgather("partitioned_read/train", {"n": 1})
        )
        assert isinstance(error, ExchangeTimeout)
        assert error.missing_ranks == (1,)
        assert "partitioned_read/train" in str(error)
        assert "rank(s) 1" in str(error)

    def test_score_writer_barrier_deadline(self, tmp_path):
        from photon_ml_tpu.io.score_writer import ShardedScoreWriter
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        group = InProcessExchange.create_group(2, timeout=0.4)
        writer = ShardedScoreWriter(tmp_path / "scores", exchange=group[0])
        error = _run_captured(
            lambda: writer.write(np.zeros(4), uids=np.arange(4))
        )
        assert isinstance(error, ExchangeTimeout)
        assert "score_writer/dir" in str(error)

    def test_kv_exchange_deadline_names_key_and_rank(self):
        from photon_ml_tpu.parallel.multihost import DistributedKVExchange

        class FakeClient:
            def __init__(self):
                self.store = {}

            def key_value_set(self, k, v):
                self.store[k] = v

            def blocking_key_value_get(self, k, timeout_ms):
                if k in self.store:
                    return self.store[k]
                raise RuntimeError("DEADLINE_EXCEEDED: timed out")

            def wait_at_barrier(self, bid, timeout_ms):
                return None

            def key_value_delete(self, k):
                self.store.pop(k, None)

        ex = DistributedKVExchange(
            timeout_ms=300, client=FakeClient(), rank=0, num_ranks=2,
            retry=_policy(max_attempts=2),
        )
        error = _run_captured(lambda: ex.allgather("meta", {"x": 1}))
        assert isinstance(error, ExchangeTimeout)
        assert error.missing_ranks == (1,)  # rank 1 never published
        assert "photon/xchg/" in error.key and error.key.endswith("/1")

    def test_kv_set_retries_transient_then_succeeds(self):
        from photon_ml_tpu.parallel.multihost import DistributedKVExchange

        class FlakySetClient:
            def __init__(self):
                self.store = {}
                self.failures = 1

            def key_value_set(self, k, v):
                if self.failures:
                    self.failures -= 1
                    raise RuntimeError("UNAVAILABLE: connection reset")
                self.store[k] = v

            def blocking_key_value_get(self, k, timeout_ms):
                # single-rank group: only our own key is read back
                return self.store[k]

            def wait_at_barrier(self, bid, timeout_ms):
                return None

            def key_value_delete(self, k):
                self.store.pop(k, None)

        client = FlakySetClient()
        ex = DistributedKVExchange(
            timeout_ms=300, client=client, rank=0, num_ranks=1,
            retry=_policy(max_attempts=3),
        )
        assert ex.allgather("meta", {"x": 1}) == [{"x": 1}]
        assert client.failures == 0

    def test_withheld_hot_ranking_allgather_times_out_attributed(
        self, tmp_path
    ):
        """The composed-path seam (ISSUE 6): the global hot-column ranking
        rides the SAME exchange deadlines as the vocab exchanges — a rank
        that crashes before publishing its nnz histogram surfaces on every
        other rank as a rank-attributed ExchangeTimeout naming the
        hybrid_hot tag, within the bounded deadline, never a hang."""
        from test_composed_path import _shard_configs, _write_input

        from photon_ml_tpu.io.partitioned_reader import read_partitioned
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        path = _write_input(tmp_path, num_files=2, rows_per_file=8)
        group = InProcessExchange.create_group(2, timeout=0.4)
        # rank 1 participates in the vocab/index-map exchanges but
        # crashes at the hot-ranking allgather
        exchanges = [
            group[0],
            faultinject.WithholdingExchange(group[1], ("hybrid_hot",)),
        ]
        boxes = [{} for _ in range(2)]

        def run(r):
            try:
                read_partitioned(
                    path, _shard_configs(), exchange=exchanges[r],
                    random_effect_id_columns=("userId",),
                )
                boxes[r]["error"] = None
            except BaseException as e:  # asserted on below
                boxes[r]["error"] = e

        threads = [threading.Thread(target=run, args=(r,), daemon=True)
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20.0)
            assert not t.is_alive(), "partitioned read hung"
        assert isinstance(boxes[1]["error"], faultinject.InjectedCrash)
        error = boxes[0]["error"]
        assert isinstance(error, ExchangeTimeout)
        assert error.missing_ranks == (1,)
        assert "hybrid_hot" in str(error)


# ---------------------------------------------------------------------------
# run tracing under faults (ISSUE 9)
# ---------------------------------------------------------------------------


class TestTracingChaos:
    def test_wedged_rank_named_in_straggler_report_and_traces_publish(
        self, tmp_path
    ):
        """A WithholdingExchange-wedged rank shows up in the straggler
        report as the named slowest rank on the withheld tag: the healthy
        ranks' wait spans are recorded as the bounded ExchangeTimeout
        surfaces (the span closes on the exception), so the report comes
        from local tables alone — no further collectives on the failure
        path — and the trace files still publish. Hang-free via the
        sub-second exchange deadline."""
        from photon_ml_tpu.parallel.multihost import InProcessExchange
        from photon_ml_tpu.telemetry.tracing import (
            Tracer,
            exchange_wait_tables,
            install_tracer,
            publish_trace,
            straggler_report,
            uninstall_tracer,
        )

        tracer = install_tracer(Tracer(rank=0))
        try:
            group = InProcessExchange.create_group(3, timeout=0.4)
            exchanges = [
                group[0],
                faultinject.WithholdingExchange(group[1], ("hybrid_hot",)),
                group[2],
            ]
            boxes = [{} for _ in range(3)]

            def run(r):
                try:
                    exchanges[r].allgather("hybrid_hot/game/f", {"r": r})
                    boxes[r]["error"] = None
                except BaseException as e:  # asserted on below
                    boxes[r]["error"] = e

            threads = [threading.Thread(target=run, args=(r,), daemon=True)
                       for r in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10.0)
                assert not t.is_alive(), "withheld allgather hung"
            assert isinstance(boxes[1]["error"], faultinject.InjectedCrash)
            for r in (0, 2):
                assert isinstance(boxes[r]["error"], ExchangeTimeout)

            # straggler attribution BEFORE any run-end merge collective:
            # the wedged rank never recorded a wait on the tag, the
            # healthy ranks each recorded ~the deadline with the timeout
            # error attached
            tables = exchange_wait_tables(tracer)
            assert "hybrid_hot/game/f" not in tables.get(1, {})
            report = straggler_report(tables, num_ranks=3)
            row = next(
                t for t in report["tags"] if t["tag"] == "hybrid_hot/game/f"
            )
            assert row["straggler_rank"] == 1
            assert row["reason"] == "never_arrived"
            assert row["missing_ranks"] == [1]
            for r in (0, 2):
                assert 0.3 <= row["wait_s"][r] < 5.0  # bounded, not a hang

            # failure-path publication: the timeline still lands, valid
            # Chrome-trace JSON with the recorded exchange waits
            path = publish_trace(tracer, tmp_path / "traces")
            assert os.path.basename(path) == "trace-00000.json"
            with open(path) as f:
                doc = json.load(f)
            xevents = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
            waits = [e for e in xevents
                     if e["name"] == "exchange/allgather"
                     and e["args"].get("tag") == "hybrid_hot/game/f"]
            assert len(waits) == 2  # the two healthy ranks
            assert {e["args"]["error"] for e in waits} == {"ExchangeTimeout"}
            assert not [
                e for e in os.listdir(tmp_path / "traces")
                if e.endswith(".tmp")
            ]
        finally:
            uninstall_tracer()


# ---------------------------------------------------------------------------
# checkpoint atomicity + intact-step fallback
# ---------------------------------------------------------------------------


class TestCheckpointResilience:
    def test_crash_between_temp_write_and_replace_is_atomic(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ck = TrainingCheckpointer(tmp_path / "ck")
        ck.save(1, {"w": np.arange(3.0)}, {"note": "good"})
        with faultinject.crash_before_replace():
            with pytest.raises(faultinject.InjectedCrash):
                ck.save(2, {"w": np.full(3, 2.0)}, {"note": "doomed"})
        # no partial step dirs, no leaked temp dirs
        entries = sorted(os.listdir(tmp_path / "ck"))
        assert entries == ["step_00000001"]
        restored = ck.restore()
        assert restored.step == 1
        np.testing.assert_array_equal(restored.arrays["w"], np.arange(3.0))

    def test_restore_falls_back_to_newest_intact_step(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ck = TrainingCheckpointer(tmp_path / "ck", max_to_keep=5)
        for step in (1, 2, 3):
            ck.save(step, {"w": np.full(2, float(step))}, {})
        faultinject.corrupt_checkpoint_step(ck.directory, 3, "arrays.npz")
        faultinject.corrupt_checkpoint_step(ck.directory, 2, "meta.json")
        restored = ck.restore()
        assert restored.step == 1
        np.testing.assert_array_equal(restored.arrays["w"], np.ones(2))
        # an explicitly-requested corrupt step still raises (no silent
        # substitution)
        with pytest.raises(Exception):
            ck.restore(step=3)

    def test_prune_never_deletes_last_loadable_step(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ck = TrainingCheckpointer(tmp_path / "ck", max_to_keep=10)
        for step in (1, 2, 3, 4):
            ck.save(step, {"w": np.full(2, float(step))}, {})
        faultinject.corrupt_checkpoint_step(ck.directory, 3, "arrays.npz")
        faultinject.corrupt_checkpoint_step(ck.directory, 4, "arrays.npz")
        tight = TrainingCheckpointer(tmp_path / "ck", max_to_keep=2)
        tight._prune()
        # naive pruning would keep only {3, 4} — both corrupt; the newest
        # loadable step (2) must survive
        assert 2 in tight.steps()
        assert tight.restore().step == 2

    def test_restore_counter_journaled(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ck = TrainingCheckpointer(tmp_path / "ck")
        ck.save(1, {"w": np.zeros(2)}, {})
        before = rc.checkpoint_restores()
        ck.restore()  # direct restore does not count...
        assert rc.checkpoint_restores() == before
        # ...the CD-loop resume site does (tested in TestNanPoisonRecovery)


# ---------------------------------------------------------------------------
# NaN-poisoned lane -> DivergenceError -> checkpoint-restore recovery
# ---------------------------------------------------------------------------


def _mixed_data(rng, n_users=6, per_user=5, d_global=3, d_user=2):
    from photon_ml_tpu.data.game_data import build_game_dataset

    n = n_users * per_user
    user_ids = np.repeat(np.arange(n_users), per_user)
    xg = rng.normal(size=(n, d_global))
    xu = rng.normal(size=(n, d_user))
    y = (
        xg @ rng.normal(size=d_global)
        + np.einsum("nd,nd->n", xu, rng.normal(size=(n_users, d_user))[user_ids])
        + 0.05 * rng.normal(size=n)
    )
    return build_game_dataset(
        labels=y,
        feature_shards={"global": xg, "per_user": xu},
        entity_keys={"userId": user_ids},
        dtype=np.float64,
    )


def _estimator(ckpt=None, resume=True):
    from photon_ml_tpu.algorithm.coordinates import CoordinateOptimizationConfig
    from photon_ml_tpu.estimators import (
        FixedEffectCoordinateConfig,
        GameEstimator,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
    from photon_ml_tpu.types import TaskType

    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=25
        ),
        l2_weight=0.1,
    )
    return GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig("global", opt),
            "per-user": RandomEffectCoordinateConfig("userId", "per_user", opt),
        },
        num_iterations=1,
        checkpointer=ckpt,
        resume=resume,
    )


class TestNanPoisonRecovery:
    def test_poisoned_lane_recovers_bitwise_via_checkpoint(self, rng, tmp_path):
        from photon_ml_tpu.algorithm.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.io.checkpoint import (
            DivergenceError,
            TrainingCheckpointer,
        )

        dataset = _mixed_data(rng)
        baseline = _estimator().fit(dataset)

        restores0, retries0 = rc.checkpoint_restores(), rc.retries()
        ckpt_dir = tmp_path / "ck"

        def attempt(restart):
            return _estimator(
                TrainingCheckpointer(ckpt_dir), resume=True
            ).fit(dataset)

        with faultinject.poison_coordinate_updates(
            RandomEffectCoordinate, times=1
        ):
            # sanity: without recovery the poison is a DivergenceError
            with pytest.raises(DivergenceError):
                _estimator(TrainingCheckpointer(tmp_path / "nock")).fit(dataset)

        with faultinject.poison_coordinate_updates(
            RandomEffectCoordinate, times=1
        ):
            result = run_with_recovery(
                attempt,
                max_restarts=2,
                checkpointer=TrainingCheckpointer(ckpt_dir),
                description="chaos config",
            )

        # recovery resumed from the post-'fixed' checkpoint and re-ran the
        # per-user update clean: the final model must be BITWISE the
        # uninjected run's (lossless npz round-trip + deterministic solve)
        np.testing.assert_array_equal(
            np.asarray(result.model.models["fixed"].glm.coefficients.means),
            np.asarray(baseline.model.models["fixed"].glm.coefficients.means),
        )
        np.testing.assert_array_equal(
            np.asarray(result.model.models["per-user"].coefficients),
            np.asarray(baseline.model.models["per-user"].coefficients),
        )
        assert rc.checkpoint_restores() - restores0 >= 1
        assert rc.retries() - retries0 >= 1

    def test_divergence_without_checkpoint_fails_fast(self, rng, tmp_path):
        from photon_ml_tpu.algorithm.coordinates import FixedEffectCoordinate
        from photon_ml_tpu.io.checkpoint import DivergenceError

        dataset = _mixed_data(rng)

        def attempt(restart):
            return _estimator().fit(dataset)

        # poison the FIRST coordinate: no checkpoint exists yet, so this
        # deterministic divergence must propagate (re-running from scratch
        # would diverge identically), not burn restarts
        with faultinject.poison_coordinate_updates(
            FixedEffectCoordinate, times=99
        ):
            with pytest.raises(DivergenceError):
                run_with_recovery(attempt, max_restarts=3, checkpointer=None)

    def test_transient_failure_restarts_from_scratch(self):
        calls = {"n": 0}

        def attempt(restart):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("connection dropped")
            return "done"

        assert run_with_recovery(attempt, max_restarts=2) == "done"
        assert calls["n"] == 2


# ---------------------------------------------------------------------------
# driver-level: quarantine + journaled resilience counters
# ---------------------------------------------------------------------------


class TestDriverQuarantineJournal:
    @pytest.fixture()
    def corrupt_train_dir(self, tmp_path):
        from photon_ml_tpu.io import photon_schemas as schemas

        data_dir = tmp_path / "train"
        os.makedirs(data_dir)
        rng = np.random.default_rng(7)
        w = rng.normal(size=3)
        records = []
        for i in range(120):
            x = rng.normal(size=3)
            records.append(
                {
                    "uid": str(i),
                    "label": float(x @ w + 0.05 * rng.normal()),
                    "features": [
                        {"name": f"f{j}", "term": "", "value": float(x[j])}
                        for j in range(3)
                    ],
                    "weight": 1.0,
                    "offset": 0.0,
                    "metadataMap": None,
                }
            )
        path = str(data_dir / "part-00000.avro")
        avro_io.write_container(
            path, schemas.TRAINING_EXAMPLE_AVRO, records, block_records=40
        )
        faultinject.truncate_avro_block(path, block=-1)
        return data_dir

    def test_training_driver_quarantines_and_journals(
        self, corrupt_train_dir, tmp_path
    ):
        from photon_ml_tpu.cli import game_training_driver
        from photon_ml_tpu.telemetry import JOURNAL_FILENAME, RunJournal

        args = [
            "--input-data-path", str(corrupt_train_dir),
            "--root-output-dir", str(tmp_path / "out"),
            "--task-type", "LINEAR_REGRESSION",
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=0.1,max.iter=15",
            "--telemetry-dir", str(tmp_path / "tel"),
        ]
        # strict default fails on the torn block
        with pytest.raises(Exception):
            game_training_driver.main(args)
        summary = game_training_driver.main(
            args + ["--override-output", "--on-corrupt", "quarantine"]
        )
        assert summary["num_configurations"] == 1
        rows = RunJournal.read(str(tmp_path / "tel" / JOURNAL_FILENAME))
        kinds = [r["kind"] for r in rows]
        assert "quarantined_block" in kinds
        snapshot = [r for r in rows if r["kind"] == "metrics"][-1]["snapshot"]
        assert snapshot["counters"]["resilience/quarantined_blocks"] >= 1

    def test_scoring_driver_journals_failure_path(self, tmp_path):
        from photon_ml_tpu.cli import game_scoring_driver
        from photon_ml_tpu.telemetry import JOURNAL_FILENAME, RunJournal

        with pytest.raises(Exception):
            game_scoring_driver.run(
                input_data_path=str(tmp_path / "missing"),
                model_input_dir=str(tmp_path / "no-model"),
                output_dir=str(tmp_path / "out"),
                feature_shards={},
                telemetry_dir=str(tmp_path / "tel"),
            )
        # the journal survived the failure with the metrics snapshot
        rows = RunJournal.read(str(tmp_path / "tel" / JOURNAL_FILENAME))
        assert any(r["kind"] == "metrics" for r in rows)

    def test_quarantine_events_are_json_safe(self, tmp_path):
        path = str(tmp_path / "x.avro")
        _write(path)
        faultinject.corrupt_avro_block(path, block=0)
        list(avro_io.read_container(path, on_corrupt="quarantine"))
        events = rc.drain_quarantine_events()
        assert events
        json.dumps(events)  # journal rows must be strict JSON


# ---------------------------------------------------------------------------
# Out-of-core streaming epochs (io/stream_reader.py): the prefetch pipeline
# ---------------------------------------------------------------------------


class TestStreamingChaos:
    """The chunk-prefetch pipeline under injected faults: transient decode
    errors heal via RetryPolicy, a truncated mid-epoch block fails FAST
    with the chunk attributed (or quarantines when opted in), and a wedged
    or dead producer surfaces within the pipeline's own bounded timeouts —
    never a hang (no pytest-timeout exists to save these)."""

    def _chunk_source(self, tmp_path, *, on_corrupt="raise"):
        from photon_ml_tpu.io.stream_reader import (
            AvroChunkSource,
            DenseRecordAssembler,
        )
        from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
        from photon_ml_tpu.io.stream_reader import build_streaming_index_maps

        path = str(tmp_path / "s.avro")
        _write(path)  # 30 records, 3 blocks of 10
        cfg = {"features": FeatureShardConfiguration(
            feature_bags=("features",), has_intercept=False)}
        imaps = build_streaming_index_maps([path], cfg)
        source = AvroChunkSource(
            [path],
            DenseRecordAssembler(imaps["features"], cfg["features"]),
            chunk_records=10,
            on_corrupt=on_corrupt,
        )
        return path, source

    def test_truncated_mid_epoch_block_fails_fast_attributed(self, tmp_path):
        import time

        from photon_ml_tpu.io.stream_reader import (
            ChunkPrefetcher,
            StreamDecodeError,
        )

        path, source = self._chunk_source(tmp_path)
        assert source.num_chunks == 3
        # torn AFTER planning: the epoch is mid-flight when decode hits it
        faultinject.truncate_avro_block(path, block=1)
        t0 = time.perf_counter()
        got = []
        with pytest.raises(StreamDecodeError, match=r"chunk 1") as ei:
            with ChunkPrefetcher(
                source, prefetch=True, retry_policy=_policy(),
                chunk_timeout=10.0,
            ) as chunks:
                for batch in chunks:
                    got.append(batch)
        elapsed = time.perf_counter() - t0
        assert elapsed < 8.0, f"not fail-fast: {elapsed:.1f}s"
        assert len(got) == 1  # the intact chunk before the tear arrived
        assert "runs=" in str(ei.value)  # file/block-span attribution

    def test_truncated_mid_epoch_block_quarantines_when_opted_in(
            self, tmp_path):
        from photon_ml_tpu.io.stream_reader import ChunkPrefetcher

        path, source = self._chunk_source(tmp_path, on_corrupt="quarantine")
        faultinject.truncate_avro_block(path, block=1)
        before = rc.quarantined_blocks()
        true_rows = 0
        with ChunkPrefetcher(
            source, prefetch=True, retry_policy=_policy(),
        ) as chunks:
            for batch in chunks:
                true_rows += int((np.asarray(batch.weights) != 0).sum())
        # the tear costs exactly the unreachable span; intact data survives
        assert true_rows == 10
        assert rc.quarantined_blocks() > before
        rc.drain_quarantine_events()

    def test_transient_decode_failure_retries_and_heals(self):
        from photon_ml_tpu.io.stream_reader import (
            ArrayChunkSource,
            ChunkPrefetcher,
        )

        x = np.arange(40.0).reshape(20, 2)
        y = np.zeros(20)
        source = ArrayChunkSource(
            x, y, chunk_rows=5,
            decode_hook=faultinject.flaky(failures=2),
        )
        before = rc.retries()
        n = 0
        with ChunkPrefetcher(
            source, prefetch=True, retry_policy=_policy(max_attempts=3),
        ) as chunks:
            for _ in chunks:
                n += 1
        assert n == 4  # every chunk arrived; the flaky window healed
        assert rc.retries() - before == 2

    def test_fatal_decode_failure_surfaces_attributed_and_joins(self):
        import time

        from photon_ml_tpu.io.stream_reader import (
            ArrayChunkSource,
            ChunkPrefetcher,
            StreamDecodeError,
        )

        def boom():
            raise ValueError("bad bytes")  # classified FATAL: no retry

        x = np.arange(40.0).reshape(20, 2)
        source = ArrayChunkSource(x, np.zeros(20), chunk_rows=5,
                                  decode_hook=boom)
        t0 = time.perf_counter()
        pf = ChunkPrefetcher(source, prefetch=True, retry_policy=_policy())
        with pytest.raises(StreamDecodeError, match="chunk 0"):
            with pf:
                for _ in pf:
                    pass
        assert time.perf_counter() - t0 < 5.0
        assert pf._thread is None  # close() joined and cleared the producer

    def test_wedged_decode_times_out_within_bound(self):
        import time

        from photon_ml_tpu.io.stream_reader import (
            ArrayChunkSource,
            ChunkPrefetcher,
            StreamDecodeError,
        )

        x = np.arange(40.0).reshape(20, 2)
        source = ArrayChunkSource(
            x, np.zeros(20), chunk_rows=5,
            decode_hook=lambda: time.sleep(1.0),
        )
        t0 = time.perf_counter()
        with pytest.raises(StreamDecodeError, match="wedged"):
            with ChunkPrefetcher(
                source, prefetch=True, retry_policy=_policy(),
                chunk_timeout=0.2,
            ) as chunks:
                for _ in chunks:
                    pass
        # consumer bound (0.2 s) + bounded join over the 1 s sleeper
        assert time.perf_counter() - t0 < 4.0


# ---------------------------------------------------------------------------
# Crash-safe resume for the production path (ISSUE 8): epoch-granular
# streaming checkpoints + exchange-consistent partitioned checkpointing
# ---------------------------------------------------------------------------


def _stream_fixture(hook=None, n=64, d=6, chunk=16, seed=0):
    from photon_ml_tpu.io.stream_reader import ArrayChunkSource

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    wt = rng.normal(size=d).astype(np.float32)
    y = (x @ wt + 0.1 * rng.normal(size=n)).astype(np.float32)
    return ArrayChunkSource(x, y, chunk_rows=chunk, decode_hook=hook)


def _stream_opt(max_iter=6):
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

    return OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=max_iter
    )


class TestPreemptionClassification:
    def test_device_loss_shapes_are_transient_preemptions(self):
        from photon_ml_tpu.resilience import is_preemption

        e = faultinject.device_loss_error()
        assert classify_exception(e) is Transience.TRANSIENT
        assert is_preemption(e)
        # the same shape wrapped by the stream pipeline stays attributed
        wrapped = RuntimeError(
            f"streaming epoch failed decoding chunk 3: RuntimeError: {e}"
        )
        assert classify_exception(wrapped) is Transience.TRANSIENT
        assert is_preemption(wrapped)

    def test_preemption_is_a_subset_of_transient(self):
        from photon_ml_tpu.resilience import is_preemption

        # fatal-despite-the-smell: an OOM mentioning a device is NOT a
        # preemption (retrying re-allocates identically)
        oom = RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "on the lost device"
        )
        assert classify_exception(oom) is Transience.FATAL
        assert not is_preemption(oom)
        # ordinary flaky I/O is transient but not a preemption
        assert not is_preemption(ConnectionError("connection reset"))
        # a BARE socket-closed drop is transient but deliberately not
        # tallied as a preemption: a dropped coordinator or filesystem
        # connection reads the same (resilience/errors.py rationale)
        bare = RuntimeError("INTERNAL: Socket closed")
        assert classify_exception(bare) is Transience.TRANSIENT
        assert not is_preemption(bare)


class TestCrashSafeStreamingResume:
    """ISSUE 8 acceptance, streaming half: a run killed mid-epoch resumes
    via run_with_recovery — skipping completed λs/epochs — and matches the
    uninterrupted run BITWISE (one eval path: the dense streaming
    accumulator; the solver state round-trips through numpy exactly)."""

    LAMS = (0.1, 1.0)

    def _train(self, checkpointer=None, hook=None):
        from photon_ml_tpu.estimators import train_glm_streaming
        from photon_ml_tpu.types import TaskType

        return train_glm_streaming(
            _stream_fixture(hook),
            TaskType.LINEAR_REGRESSION,
            optimizer=_stream_opt(),
            regularization_weights=self.LAMS,
            checkpointer=checkpointer,
        )

    def test_crash_mid_epoch_resumes_and_matches_bitwise(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import SolverCheckpointer

        loads = {"n": 0}
        base = self._train(hook=lambda: loads.__setitem__("n", loads["n"] + 1))
        assert loads["n"] > 4  # the fixture really streams epochs

        ck = SolverCheckpointer(tmp_path / "ck")
        before = (rc.checkpoint_restores(), rc.preemptions(),
                  rc.epochs_resumed())
        # crash halfway through the run's chunk decodes — mid-epoch,
        # mid-λ-grid — with the device-loss/preemption shape
        with faultinject.crash_after_chunks(loads["n"] // 2) as crash:
            models = run_with_recovery(
                lambda restart: self._train(checkpointer=ck),
                max_restarts=2,
                checkpointer=ck,
                description="streaming chaos",
            )
        assert crash["fired"], "the injected crash never happened"
        for lam in self.LAMS:
            np.testing.assert_array_equal(
                np.asarray(base[lam].coefficients.means),
                np.asarray(models[lam].coefficients.means),
            )
        # resume evidence: restored a checkpoint, skipped epochs, and the
        # failure shape was tallied as a preemption
        assert rc.checkpoint_restores() > before[0]
        assert rc.preemptions() > before[1]
        assert rc.epochs_resumed() > before[2]

    def test_checkpointing_on_is_bitwise_checkpointing_off(self, tmp_path):
        """The observer observes, never rewrites: a checkpointed run's
        models equal the un-checkpointed run's bitwise (checkpointing OFF
        — the default — is trivially today's path; ON must not perturb)."""
        from photon_ml_tpu.io.checkpoint import SolverCheckpointer

        base = self._train()
        ck = SolverCheckpointer(tmp_path / "ck")
        withck = self._train(checkpointer=ck)
        for lam in self.LAMS:
            np.testing.assert_array_equal(
                np.asarray(base[lam].coefficients.means),
                np.asarray(withck[lam].coefficients.means),
            )
        assert ck.latest_step() is not None  # it really checkpointed

    def test_fingerprint_mismatch_fails_fast_named(self, tmp_path):
        from photon_ml_tpu.estimators import train_glm_streaming
        from photon_ml_tpu.io.checkpoint import SolverCheckpointer
        from photon_ml_tpu.types import TaskType

        ck = SolverCheckpointer(tmp_path / "ck")
        self._train(checkpointer=ck)
        with pytest.raises(ValueError, match="fingerprint.*lambdas"):
            train_glm_streaming(
                _stream_fixture(),
                TaskType.LINEAR_REGRESSION,
                optimizer=_stream_opt(),
                regularization_weights=(0.25,),
                checkpointer=ck,
            )

    def test_solver_state_of_another_layout_fails_fast_named(self, tmp_path):
        """A mid-solve snapshot whose state fields are not the state class's
        (one written while the L-BFGS history was circular has ``head``) is
        refused by name: never rebuilt, never read as the ordered layout."""
        import dataclasses
        from typing import Any

        from photon_ml_tpu.io.checkpoint import SolverCheckpointer

        ck = SolverCheckpointer(tmp_path / "ck")
        saves = []
        save_progress = ck.save_progress

        def recording_save(**kw):
            saves.append(kw)
            return save_progress(**kw)

        ck.save_progress = recording_save
        self._train(checkpointer=ck)
        mid_solve = next(kw for kw in saves if kw["solver_state"] is not None)

        state = mid_solve["solver_state"]
        names = [f.name for f in dataclasses.fields(state)]
        assert "head" not in names and {"s_hist", "y_hist", "rho", "count"} <= set(names)
        circular = dataclasses.make_dataclass(
            "CircularState", [(name, Any) for name in names + ["head"]]
        )(*(getattr(state, name) for name in names), np.int32(0))

        old = SolverCheckpointer(tmp_path / "old")
        old.save_progress(**{**mid_solve, "solver_state": circular})
        with pytest.raises(
            ValueError,
            match=r"only in the checkpoint: \['head'\].*fresh checkpoint directory",
        ):
            self._train(checkpointer=old)
        # the same snapshot under the state class's own fields resumes
        own = SolverCheckpointer(tmp_path / "own")
        own.save_progress(**mid_solve)
        assert set(self._train(checkpointer=own)) == set(self.LAMS)

    @pytest.mark.parametrize("written_as", ["row", "slab"])
    def test_solver_state_of_the_other_history_form_fails_fast_named(
            self, tmp_path, monkeypatch, written_as):
        """A mid-solve snapshot whose ``s_hist`` has the OTHER form's shape for
        its d (a slot is a row of ``[m, d]`` below ``optim/lbfgs.SLAB_MIN_DIM``
        and whole tiles ``[m, R, 128]`` from it on; the edge is moved here, as
        a later version might move it) is refused with the field and both
        shapes named: never folded, never read as if it fitted. Under the rule
        it was written under it resumes to the uninterrupted run's models."""
        import re

        from photon_ml_tpu.io.checkpoint import SolverCheckpointer
        from photon_ml_tpu.optim import lbfgs

        edge = {"row": lbfgs.SLAB_MIN_DIM, "slab": 1}
        other = {"row": "slab", "slab": "row"}[written_as]
        monkeypatch.setattr(lbfgs, "SLAB_MIN_DIM", edge[written_as])

        ck = SolverCheckpointer(tmp_path / "ck")
        saves = []
        save_progress = ck.save_progress

        def recording_save(**kw):
            saves.append(kw)
            return save_progress(**kw)

        ck.save_progress = recording_save
        base = self._train(checkpointer=ck)
        mid_solve = next(kw for kw in saves if kw["solver_state"] is not None)
        saved = tuple(mid_solve["solver_state"].s_hist.shape)
        m, d = saved[0], int(mid_solve["solver_state"].w.shape[0])
        forms = {"row": (m, d), "slab": (m, 8, 128)}
        assert saved == forms[written_as]

        own = SolverCheckpointer(tmp_path / "own")
        own.save_progress(**mid_solve)
        resumed = self._train(checkpointer=own)
        for lam in self.LAMS:
            np.testing.assert_array_equal(
                np.asarray(base[lam].coefficients.means),
                np.asarray(resumed[lam].coefficients.means),
            )

        monkeypatch.setattr(lbfgs, "SLAB_MIN_DIM", edge[other])
        moved = SolverCheckpointer(tmp_path / "moved")
        moved.save_progress(**mid_solve)
        with pytest.raises(
            ValueError,
            match=r"resume_state\.s_hist has shape " + re.escape(str(forms[written_as]))
            + r".* is stored as " + re.escape(str(forms[other]))
            + r".*fresh checkpoint directory",
        ):
            self._train(checkpointer=moved)


def _partitioned_fixture(num_ranks=2, n=32, d=4, seed=1):
    """In-memory dense-FE partitioned GAME fixture: ``num_ranks`` equal
    row blocks of one tiny regression problem (no Avro, no REs — the
    cheapest real train_partitioned invocation)."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_data import GameDataset

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ rng.normal(size=d) + 0.1 * rng.normal(size=n)).astype(np.float32)
    nb = n // num_ranks

    def block(r):
        lo = r * nb
        return GameDataset(
            unique_ids=np.arange(lo, lo + nb),
            labels=jnp.asarray(y[lo:lo + nb]),
            offsets=jnp.zeros(nb, jnp.float32),
            weights=jnp.ones(nb, jnp.float32),
            feature_shards={"global": jnp.asarray(x[lo:lo + nb])},
            entity_idx={},
            entity_vocabs={},
        )

    return {r: (block(r), {}) for r in range(num_ranks)}


def _partitioned_program(max_iter=4):
    from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        GameTrainProgram,
    )
    from photon_ml_tpu.types import TaskType

    return GameTrainProgram(
        TaskType.LINEAR_REGRESSION,
        FixedEffectStepSpec(
            "global",
            OptimizerConfig(max_iterations=max_iter),
            l2_weight=0.5,
        ),
        (),
    )


class TestCrashSafePartitionedResume:
    """ISSUE 8 acceptance, partitioned half: a virtual-rank partitioned
    run killed mid-sweep by a simulated pool preemption resumes via
    run_with_recovery and matches the uninterrupted run bitwise; a resume
    under a changed rank count fails fast with the fingerprint named."""

    def test_preemption_mid_sweep_resumes_and_matches_bitwise(
            self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
        from photon_ml_tpu.parallel.distributed import (
            GameTrainProgram,
            train_partitioned,
        )
        from photon_ml_tpu.parallel.multihost import make_hybrid_mesh

        mesh = make_hybrid_mesh(data=8, model=1)
        parts = _partitioned_fixture()
        prog = _partitioned_program()
        ref = train_partitioned(prog, parts, mesh, 2, num_iterations=3)

        ck = TrainingCheckpointer(tmp_path / "pck")
        before = (rc.checkpoint_restores(), rc.preemptions())
        with faultinject.preempt_after_calls(
            GameTrainProgram, "step", 2
        ) as crash:
            res = run_with_recovery(
                lambda restart: train_partitioned(
                    prog, parts, mesh, 2, num_iterations=3, checkpointer=ck
                ),
                max_restarts=2,
                checkpointer=ck,
                description="partitioned chaos",
            )
        assert crash["fired"], "the injected preemption never happened"
        np.testing.assert_array_equal(
            np.asarray(res.state.fe_coefficients),
            np.asarray(ref.state.fe_coefficients),
        )
        np.testing.assert_array_equal(res.losses, ref.losses)
        assert rc.checkpoint_restores() > before[0]
        assert rc.preemptions() > before[1]

    def test_rank_count_change_fails_fast_with_fingerprint(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
        from photon_ml_tpu.parallel.distributed import train_partitioned
        from photon_ml_tpu.parallel.multihost import make_hybrid_mesh

        mesh = make_hybrid_mesh(data=8, model=1)
        prog = _partitioned_program()
        ck = TrainingCheckpointer(tmp_path / "pck")
        train_partitioned(
            prog, _partitioned_fixture(num_ranks=2), mesh, 2,
            num_iterations=1, checkpointer=ck,
        )
        with pytest.raises(ValueError, match="fingerprint") as ei:
            train_partitioned(
                prog, _partitioned_fixture(num_ranks=1), mesh, 1,
                num_iterations=1, checkpointer=ck,
            )
        # the differing agreement fields are NAMED (rank count + geometry)
        assert "num_ranks" in str(ei.value)

    def test_freezing_schedulers_reject_checkpointing_up_front(
            self, tmp_path):
        """Cross-sweep active sets (frozen lanes) are scheduler-internal
        state the checkpoint cannot capture — the combination fails fast
        with the alternative named, before any sweep runs."""
        import types

        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
        from photon_ml_tpu.optim.optimizer import LaneSchedulerConfig
        from photon_ml_tpu.parallel.distributed import train_partitioned
        from photon_ml_tpu.parallel.multihost import make_hybrid_mesh

        freezer = types.SimpleNamespace(config=LaneSchedulerConfig(
            probe_iterations=1,
            freeze_coefficient_tolerance=1e-3,
            freeze_gradient_tolerance=1e-3,
        ))
        with pytest.raises(ValueError, match="freeze"):
            train_partitioned(
                _partitioned_program(), _partitioned_fixture(),
                make_hybrid_mesh(data=8, model=1), 2,
                num_iterations=1,
                schedulers={"userId": freezer},
                checkpointer=TrainingCheckpointer(tmp_path / "fck"),
            )

    def test_normalization_digest_distinguishes_statistics(self):
        """The streaming fingerprint's normalization field is a CONTENT
        digest — different factor/shift arrays must differ (the class
        name cannot: every non-NONE type builds NormalizationContext)."""
        import jax.numpy as jnp

        from photon_ml_tpu.estimators import _normalization_digest
        from photon_ml_tpu.ops.normalization import NormalizationContext

        a = NormalizationContext(factors=jnp.asarray([1.0, 2.0]))
        b = NormalizationContext(factors=jnp.asarray([1.0, 3.0]))
        c = NormalizationContext(factors=jnp.asarray([1.0, 2.0]),
                                 shifts=jnp.asarray([0.5, 0.5]))
        assert _normalization_digest(None) is None
        assert _normalization_digest(a) == _normalization_digest(a)
        assert _normalization_digest(a) != _normalization_digest(b)
        assert _normalization_digest(a) != _normalization_digest(c)

    def test_commit_barrier_is_rank_attributed_not_a_hang(self, tmp_path):
        """The exchange-consistent commit: both ranks present -> exactly
        one step dir, written by rank 0; a withheld rank -> the writer
        fails with a rank-attributed ExchangeTimeout WITHIN the exchange's
        sub-second deadline, never a hang, and no checkpoint commits."""
        from photon_ml_tpu.io.checkpoint import (
            TrainingCheckpointer,
            commit_checkpoint,
        )
        from photon_ml_tpu.parallel.multihost import InProcessExchange

        arrays = {"fe_coefficients": np.zeros(3, np.float32)}

        # happy path: every rank calls, rank 0 writes
        exchanges = InProcessExchange.create_group(2, timeout=5.0)
        cks = [TrainingCheckpointer(tmp_path / "bck") for _ in range(2)]
        paths = [None, None]

        def commit(r):
            paths[r] = commit_checkpoint(
                cks[r], 1, arrays, {"losses": []}, exchange=exchanges[r]
            )

        threads = [threading.Thread(target=commit, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        assert paths[0] is not None and paths[1] is None
        assert cks[0].latest_step() == 1

        # withheld rank: the present rank's pre-commit barrier deadline
        # fires attributed; nothing new commits
        exchanges = InProcessExchange.create_group(2, timeout=0.3)
        ck = TrainingCheckpointer(tmp_path / "bck2")

        def withheld():
            commit_checkpoint(
                ck, 1, arrays, {"losses": []}, exchange=exchanges[0]
            )

        err = _run_captured(withheld, timeout=5.0)
        assert isinstance(err, ExchangeTimeout)
        assert "1" in str(err.missing_ranks) or 1 in err.missing_ranks
        assert ck.latest_step() is None


class TestGLMDriverRecovery:
    """The GLM driver's new --checkpoint-dir/--max-restarts wiring: a
    streaming driver run killed mid-epoch restarts through
    run_with_recovery, resumes from the solver checkpoint, succeeds, and
    journals the restart + the resilience/* counters."""

    def _input_dir(self, tmp_path):
        from photon_ml_tpu.io import photon_schemas as schemas

        data_dir = tmp_path / "train"
        os.makedirs(data_dir, exist_ok=True)
        rng = np.random.default_rng(5)
        w = rng.normal(size=3)
        records = []
        for i in range(80):
            x = rng.normal(size=3)
            records.append({
                "uid": str(i),
                "label": float(x @ w + 0.05 * rng.normal()),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[j])}
                    for j in range(3)
                ],
                "weight": 1.0, "offset": 0.0, "metadataMap": None,
            })
        avro_io.write_container(
            str(data_dir / "part-00000.avro"),
            schemas.TRAINING_EXAMPLE_AVRO, records, block_records=20,
        )
        return data_dir

    def test_streaming_driver_crash_restarts_and_journals(self, tmp_path):
        from photon_ml_tpu.cli import glm_driver
        from photon_ml_tpu.telemetry import JOURNAL_FILENAME, RunJournal

        args = [
            "--input-data-path", str(self._input_dir(tmp_path)),
            "--output-dir", str(tmp_path / "out"),
            "--task-type", "LINEAR_REGRESSION",
            "--regularization-weights", "0.1",
            "--max-iterations", "4",
            "--streaming-chunks", "20",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--telemetry-dir", str(tmp_path / "tel"),
        ]
        # the uninterrupted solve costs ~20 chunk decodes (5 epochs x 4
        # chunks); crashing at 12 lands mid-solve AFTER the first
        # iteration's checkpoint, so the restart truly RESUMES
        with faultinject.crash_after_chunks(12) as crash:
            result = glm_driver.main(args)
        assert crash["fired"]
        assert result.models  # the run completed after the restart
        rows = RunJournal.read(str(tmp_path / "tel" / JOURNAL_FILENAME))
        kinds = [r["kind"] for r in rows]
        assert "resilience_restart" in kinds
        restart = [r for r in rows if r["kind"] == "resilience_restart"][0]
        assert restart["preemption"] is True
        snapshot = [r for r in rows if r["kind"] == "metrics"][-1]["snapshot"]
        assert snapshot["counters"]["resilience/preemptions"] >= 1
        assert snapshot["counters"]["resilience/epochs_resumed"] >= 1

    def test_checkpoint_dir_requires_streaming(self, tmp_path):
        from photon_ml_tpu.cli.glm_driver import GLMDriverParams, run
        from photon_ml_tpu.types import TaskType

        with pytest.raises(ValueError, match="streaming-chunks"):
            run(GLMDriverParams(
                input_data_path=str(tmp_path / "x"),
                output_dir=str(tmp_path / "out"),
                task_type=TaskType.LINEAR_REGRESSION,
                checkpoint_dir=str(tmp_path / "ck"),
            ))


class TestServingChaos:
    """The resident serving loop under injected faults (ISSUE 10): a
    poisoned request fails TYPED and ATTRIBUTED while the loop keeps
    serving every healthy request, and a wedged consumer surfaces as the
    serving layer's own bounded-deadline timeout — hang-free, because no
    pytest-timeout exists to save these."""

    def _fixture(self, n=24, seed=0, d=6):
        from photon_ml_tpu.data.game_data import (
            build_game_dataset,
            slice_game_dataset,
        )
        from photon_ml_tpu.models.coefficients import Coefficients
        from photon_ml_tpu.models.game import FixedEffectModel, GameModel
        from photon_ml_tpu.models.glm import GeneralizedLinearModel
        from photon_ml_tpu.serving import ResidentScorer
        from photon_ml_tpu.types import TaskType
        import jax.numpy as jnp

        r = np.random.default_rng(seed)
        ds = build_game_dataset(
            labels=r.normal(size=n).astype(np.float32),
            feature_shards={"g": r.normal(size=(n, d)).astype(np.float32)},
        )
        model = GameModel(models={
            "fe": FixedEffectModel(
                glm=GeneralizedLinearModel(
                    Coefficients(
                        means=jnp.asarray(r.normal(size=d).astype(np.float32))
                    ),
                    TaskType.LINEAR_REGRESSION,
                ),
                feature_shard_id="g",
            ),
        })
        scorer = ResidentScorer(model, shapes=(16, 64))
        requests = [slice_game_dataset(ds, lo, lo + 4)
                    for lo in range(0, n, 4)]
        return ds, model, scorer, requests

    def test_poisoned_request_fails_attributed_loop_survives(self):
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.serving import MicroBatchServer, RequestError
        from photon_ml_tpu.telemetry import serving_counters
        from photon_ml_tpu.telemetry.registry import default_registry

        ds, model, scorer, requests = self._fixture()
        ref = {id(r): scorer.score(r) for r in requests}
        r = np.random.default_rng(9)
        # wrong feature width: concat rejects it, then scoring it alone
        # fails — either way it is THIS request's failure
        poison = build_game_dataset(
            labels=r.normal(size=4).astype(np.float32),
            feature_shards={"g": r.normal(size=(4, 3)).astype(np.float32)},
        )
        serving_counters.reset_serving_metrics()
        with MicroBatchServer(scorer, max_wait_ms=20) as server:
            futures = [(req, server.submit(req)) for req in requests[:3]]
            poison_future = server.submit(poison, request_id="poisoned-req")
            futures += [(req, server.submit(req)) for req in requests[3:]]
            # every healthy request resolves with correct scores
            for req, fut in futures:
                np.testing.assert_array_equal(fut.result(20), ref[id(req)])
            with pytest.raises(RequestError, match="poisoned-req") as ei:
                poison_future.result(20)
            # the loop is still serving AFTER the poison
            after = server.submit(requests[0])
            np.testing.assert_array_equal(
                after.result(20), ref[id(requests[0])]
            )
        assert default_registry().counter(
            serving_counters.REQUEST_FAILURES
        ).value == 1
        assert ei.value.__cause__ is not None

    def test_wedged_consumer_times_out_typed_hang_free(self):
        import threading
        import time as _time

        from photon_ml_tpu.serving import MicroBatchServer, ServeTimeout

        _, _, scorer, requests = self._fixture()
        release = threading.Event()

        class WedgedScorer:
            shapes = scorer.shapes

            def score(self, dataset):
                # wedge until the test releases it (bounded so a broken
                # release path still cannot hang the suite)
                release.wait(timeout=5.0)
                return scorer.score(dataset)

        server = MicroBatchServer(WedgedScorer(), max_wait_ms=1.0)
        server.start()
        try:
            t0 = _time.perf_counter()
            fut = server.submit(requests[0])
            with pytest.raises(ServeTimeout, match="no result within"):
                fut.result(0.3)
            elapsed = _time.perf_counter() - t0
            assert elapsed < 2.0, f"not bounded: {elapsed:.1f}s"
        finally:
            release.set()
            server.stop()
        # after release the wedged dispatch completed; the future resolved
        # late rather than never (stop() never left it hanging)
        assert fut.done()

    def test_stopped_server_fails_stragglers_typed(self):
        from photon_ml_tpu.serving import MicroBatchServer, ServeError

        _, _, scorer, requests = self._fixture()
        server = MicroBatchServer(scorer, max_wait_ms=1.0)
        server.start()
        server.stop()
        with pytest.raises(ServeError, match="not running"):
            server.submit(requests[0])


def _streamed_game_fixture(seed=4):
    """Entity-blocked in-memory GAME fixture for the streamed-GAME chaos
    tests (algorithm/streaming_game.py)."""
    from photon_ml_tpu.io.stream_reader import GameArrayChunkSource

    rng = np.random.default_rng(seed)
    n, d_fe, d_re, n_users = 96, 5, 3, 6
    ents = np.sort(rng.integers(0, n_users, size=n)).astype(np.int32)
    x_fe = rng.normal(size=(n, d_fe)).astype(np.float32)
    x_re = rng.normal(size=(n, d_re)).astype(np.float32)
    y = (x_fe.sum(1) + 0.1 * rng.normal(size=n)).astype(np.float32)
    return GameArrayChunkSource(
        features={"g": x_fe, "p": x_re}, labels=y,
        entity_idx={"user": ents}, chunk_records=24, cluster_by="user",
    )


def _streamed_game_program(schedule=None, seed=4):
    from photon_ml_tpu.algorithm.streaming_game import StreamingGameProgram
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.parallel.distributed import (
        FixedEffectStepSpec,
        RandomEffectStepSpec,
    )
    from photon_ml_tpu.types import TaskType

    opt = OptimizerConfig(max_iterations=4)
    return StreamingGameProgram(
        TaskType.LINEAR_REGRESSION, _streamed_game_fixture(seed),
        FixedEffectStepSpec("g", opt, l2_weight=0.5),
        (RandomEffectStepSpec("user", "p", opt, l2_weight=1.0),),
        schedule=schedule,
    )


class TestCrashSafeStreamedGameResume:
    """ISSUE 11 chaos acceptance: a streamed-GAME run killed mid-sweep by
    a simulated pool preemption resumes via run_with_recovery BITWISE
    equal to the uninterrupted run; the checkpoint fingerprint covers the
    chunk plan AND the schedule mode/budget, so a restore under a
    different working-set budget fails fast naming it."""

    SWEEPS = 4

    def test_preemption_mid_sweep_resumes_and_matches_bitwise(
            self, tmp_path):
        from photon_ml_tpu.algorithm.streaming_game import (
            StreamingGameProgram,
        )
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ref = _streamed_game_program().train(num_sweeps=self.SWEEPS)

        ck = TrainingCheckpointer(tmp_path / "sgck")
        before = (rc.checkpoint_restores(), rc.preemptions())
        with faultinject.preempt_after_calls(
            StreamingGameProgram, "_sweep", 2
        ) as crash:
            res = run_with_recovery(
                lambda restart: _streamed_game_program().train(
                    num_sweeps=self.SWEEPS, checkpointer=ck
                ),
                max_restarts=2,
                checkpointer=ck,
                description="streamed game chaos",
            )
        assert crash["fired"], "the injected preemption never happened"
        np.testing.assert_array_equal(
            np.asarray(res.state.fe_coefficients),
            np.asarray(ref.state.fe_coefficients),
        )
        np.testing.assert_array_equal(
            np.asarray(res.state.re_tables["user"]),
            np.asarray(ref.state.re_tables["user"]),
        )
        np.testing.assert_array_equal(res.losses, ref.losses)
        assert rc.checkpoint_restores() > before[0]
        assert rc.preemptions() > before[1]

    def test_checkpointing_on_is_bitwise_checkpointing_off(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        base = _streamed_game_program().train(num_sweeps=self.SWEEPS)
        ck = TrainingCheckpointer(tmp_path / "sgck2")
        withck = _streamed_game_program().train(
            num_sweeps=self.SWEEPS, checkpointer=ck
        )
        np.testing.assert_array_equal(
            np.asarray(base.state.fe_coefficients),
            np.asarray(withck.state.fe_coefficients),
        )
        np.testing.assert_array_equal(base.losses, withck.losses)
        assert ck.latest_step() is not None

    def test_duhl_resume_replays_schedule_bitwise(self, tmp_path):
        """DuHL schedule state (importances, cursor, warmup progress)
        rides the checkpoint: the resumed run replays the identical chunk
        plans, so results stay bitwise."""
        from photon_ml_tpu.algorithm.streaming_game import (
            DuHLChunkSchedule,
            DuHLScheduleConfig,
            StreamingGameProgram,
        )
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        def sched(chunks=4):
            return DuHLChunkSchedule(
                DuHLScheduleConfig(working_set_chunks=2), chunks
            )

        def program():
            p = _streamed_game_program()
            p.schedule = sched(p.source.num_chunks)
            return p

        ref = program().train(num_sweeps=self.SWEEPS)
        ck = TrainingCheckpointer(tmp_path / "dck")
        with faultinject.preempt_after_calls(
            StreamingGameProgram, "_sweep", 3
        ) as crash:
            res = run_with_recovery(
                lambda restart: program().train(
                    num_sweeps=self.SWEEPS, checkpointer=ck
                ),
                max_restarts=2,
                checkpointer=ck,
                description="streamed game duhl chaos",
            )
        assert crash["fired"]
        np.testing.assert_array_equal(res.losses, ref.losses)
        np.testing.assert_array_equal(
            np.asarray(res.state.re_tables["user"]),
            np.asarray(ref.state.re_tables["user"]),
        )

    def test_schedule_budget_change_fails_fast_named(self, tmp_path):
        from photon_ml_tpu.algorithm.streaming_game import (
            DuHLChunkSchedule,
            DuHLScheduleConfig,
        )
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ck = TrainingCheckpointer(tmp_path / "fck")
        p = _streamed_game_program()
        p.schedule = DuHLChunkSchedule(
            DuHLScheduleConfig(working_set_chunks=2), p.source.num_chunks
        )
        p.train(num_sweeps=2, checkpointer=ck)
        p2 = _streamed_game_program()
        p2.schedule = DuHLChunkSchedule(
            DuHLScheduleConfig(working_set_chunks=3), p2.source.num_chunks
        )
        with pytest.raises(ValueError, match="working_set_chunks"):
            p2.train(num_sweeps=2, checkpointer=ck)

    def test_chunk_plan_change_fails_fast_named(self, tmp_path):
        from photon_ml_tpu.algorithm.streaming_game import (
            StreamingGameProgram,
        )
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
        from photon_ml_tpu.io.stream_reader import GameArrayChunkSource
        from photon_ml_tpu.optim.optimizer import OptimizerConfig
        from photon_ml_tpu.parallel.distributed import (
            FixedEffectStepSpec,
            RandomEffectStepSpec,
        )
        from photon_ml_tpu.types import TaskType

        ck = TrainingCheckpointer(tmp_path / "pck")
        _streamed_game_program().train(num_sweeps=1, checkpointer=ck)
        # same data, different chunk budget -> different plan fingerprint
        rng = np.random.default_rng(4)
        n = 96
        ents = np.sort(rng.integers(0, 6, size=n)).astype(np.int32)
        src = GameArrayChunkSource(
            features={
                "g": rng.normal(size=(n, 5)).astype(np.float32),
                "p": rng.normal(size=(n, 3)).astype(np.float32),
            },
            labels=rng.normal(size=n).astype(np.float32),
            entity_idx={"user": ents}, chunk_records=48, cluster_by="user",
        )
        opt = OptimizerConfig(max_iterations=4)
        p2 = StreamingGameProgram(
            TaskType.LINEAR_REGRESSION, src,
            FixedEffectStepSpec("g", opt, l2_weight=0.5),
            (RandomEffectStepSpec("user", "p", opt, l2_weight=1.0),),
        )
        with pytest.raises(ValueError, match="num_chunks|chunk_rows"):
            p2.train(num_sweeps=1, checkpointer=ck)


# ---------------------------------------------------------------------------
# ISSUE 12: crash-durable journals + the run doctor on a killed run
# ---------------------------------------------------------------------------


class TestJournalCrashDurability:
    """A run killed mid-epoch must leave a READABLE journal (the
    incremental append-fsync stage file), and dev/doctor.py on the partial
    run must name the last completed epoch and the failure row. Hang-free:
    nothing here waits on anything unbounded — the SIGKILL test polls a
    file with a hard deadline."""

    def test_killed_streaming_run_journal_names_epoch_and_failure(
        self, tmp_path
    ):
        """Streaming run crashes mid-epoch below the restart budget: the
        durable stage file survives WITHOUT close() (the SIGKILL shape —
        no finalize ran) and the doctor's --live report names the last
        heartbeat's epoch cursor and the run_failure row."""
        from dev.doctor import run_doctor
        from photon_ml_tpu.estimators import train_glm_streaming
        from photon_ml_tpu.telemetry import (
            RunJournal,
            SolverTelemetry,
            default_registry,
            read_journal,
        )
        from photon_ml_tpu.types import TaskType

        journal = RunJournal(tmp_path, durable=True)
        telemetry = SolverTelemetry(
            journal=journal, registry=default_registry()
        )

        def attempt(restart, _telemetry=None):
            return train_glm_streaming(
                _stream_fixture(),
                TaskType.LINEAR_REGRESSION,
                optimizer=_stream_opt(),
                regularization_weights=(0.1, 1.0),
                telemetry=_telemetry,
            )

        # size the crash to land mid-run but AFTER at least one completed
        # outer iteration (== several epochs), so an epoch heartbeat exists
        loads = {"n": 0}
        train_glm_streaming(
            _stream_fixture(
                hook=lambda: loads.__setitem__("n", loads["n"] + 1)
            ),
            TaskType.LINEAR_REGRESSION,
            optimizer=_stream_opt(),
            regularization_weights=(0.1, 1.0),
        )
        assert loads["n"] > 8

        with faultinject.crash_after_chunks(loads["n"] // 2) as crash:
            with pytest.raises(Exception):
                # zero restarts: recovery journals the terminal
                # run_failure row and re-raises (the give-up path)
                run_with_recovery(
                    lambda restart: attempt(restart, telemetry),
                    max_restarts=0, journal=journal,
                    description="doctor chaos",
                )
        assert crash["fired"]
        # NO journal.close(): a SIGKILL'd process never finalizes — the
        # fsync'd stage file alone must carry the evidence
        partial = journal.partial_path
        assert os.path.exists(partial)
        records = read_journal(partial, tolerant=True)
        kinds = [r["kind"] for r in records]
        assert "heartbeat" in kinds and "run_failure" in kinds
        hb = [r for r in records if r["kind"] == "heartbeat"][-1]
        assert hb["stage"] == "glm_streaming"
        assert hb["epochs"] >= 1  # the last completed epoch cursor
        code, findings, text = run_doctor(str(tmp_path), live=True)
        assert "last heartbeat" in text and "epochs" in text
        assert any(v.rule == "run-failure" for v in findings)
        assert any(v.rule == "journal-finalized" for v in findings)
        # a crashed run is a warning: it fails only the --strict gate
        assert code == 0
        journal.close()  # cleanup; also proves close-after-crash is safe

    def test_sigkilled_process_leaves_parseable_journal(self, tmp_path):
        """A REAL SIGKILL: a subprocess append-fsyncs heartbeat rows into
        the durable stage, the parent kills it cold, and the stage parses
        (tolerantly — at most the mid-write row is lost). Bounded by a
        hard 30 s poll deadline, no pytest-timeout needed."""
        import signal
        import subprocess
        import sys
        import time

        script = (
            "import sys, time\n"
            f"sys.path.insert(0, {repr(str(REPO_ROOT))})\n"
            "from photon_ml_tpu.telemetry.journal import RunJournal\n"
            f"j = RunJournal({repr(str(tmp_path))}, rank=0)\n"
            "for i in range(10000):\n"
            "    j.heartbeat(stage='loop', epoch=i)\n"
            "    time.sleep(0.005)\n"
        )
        proc = subprocess.Popen([sys.executable, "-c", script])
        partial = os.path.join(
            str(tmp_path), "run-journal.jsonl.partial"
        )
        deadline = time.monotonic() + 30.0
        try:
            while time.monotonic() < deadline:
                if os.path.exists(partial) and os.path.getsize(partial) > 200:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("journal stage never appeared within 30s")
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        from photon_ml_tpu.telemetry import read_journal

        records = read_journal(partial, tolerant=True)
        assert records and records[0]["kind"] == "journal_open"
        beats = [r for r in records if r["kind"] == "heartbeat"]
        assert beats, "no heartbeat survived the SIGKILL"
        # rows are whole JSON objects (fsync'd per row): every parsed row
        # carries the stamped fields
        for r in records:
            assert {"kind", "seq", "ts", "elapsed_ms"} <= set(r)


# ---------------------------------------------------------------------------
# incremental refresh + zero-downtime swap (ISSUE 14)
# ---------------------------------------------------------------------------


def _refresh_fixture(rng, n_users=8, n_items=6, per_ent=6):
    """Two-RE GAME fixture for mid-refresh preemption: the refresh walks
    [fixed(carried), per-user, per-item], so a preemption after the first
    RE update lands MID-refresh with a checkpoint behind it."""
    from photon_ml_tpu.data.game_data import build_game_dataset

    n = n_users * per_ent
    users = np.repeat(np.arange(n_users), per_ent)
    items = rng.integers(0, n_items, size=n)
    xg = rng.normal(size=(n, 3))
    xu = rng.normal(size=(n, 2))
    xi = rng.normal(size=(n, 2))
    wu = rng.normal(size=(n_users, 2))
    wi = rng.normal(size=(n_items, 2))
    noise = 0.05 * rng.normal(size=n)

    def dataset(wu_tab, wi_tab):
        y = (
            xg @ np.array([1.0, -0.5, 0.25])
            + np.einsum("nd,nd->n", xu, wu_tab[users])
            + np.einsum("nd,nd->n", xi, wi_tab[items])
            + noise
        )
        return build_game_dataset(
            labels=y,
            feature_shards={"global": xg, "per_user": xu, "per_item": xi},
            entity_keys={"userId": users, "itemId": items},
            dtype=np.float64,
        )

    wu2, wi2 = wu.copy(), wi.copy()
    wu2[1] *= -2.0
    wi2[2] *= -2.0
    return dataset(wu, wi), dataset(wu2, wi2)


def _refresh_estimator(ckpt=None, resume=True):
    from photon_ml_tpu.algorithm.coordinates import (
        CoordinateOptimizationConfig,
    )
    from photon_ml_tpu.estimators import (
        FixedEffectCoordinateConfig,
        GameEstimator,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.optim.optimizer import OptimizerConfig
    from photon_ml_tpu.types import TaskType

    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=25), l2_weight=0.1
    )
    return GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectCoordinateConfig("global", opt),
            "per-user": RandomEffectCoordinateConfig(
                "userId", "per_user", opt
            ),
            "per-item": RandomEffectCoordinateConfig(
                "itemId", "per_item", opt
            ),
        },
        # enough sweeps that the resident model sits near the JOINT
        # optimum — the gradient screen then sees only real change
        num_iterations=4,
        checkpointer=ckpt,
        resume=resume,
    )


class TestRefreshChaos:
    def test_preemption_mid_refresh_resumes_bitwise(self, rng, tmp_path):
        """A pool preemption between the two RE coordinate updates
        restarts via run_with_recovery; the resumed refresh fast-forwards
        past the checkpointed coordinate and finishes BITWISE identical to
        an uninterrupted refresh (lossless npz round-trip + deterministic
        compacted solves)."""
        from photon_ml_tpu.algorithm.coordinates import (
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.algorithm.refresh import RefreshPolicy
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        ds0, ds1 = _refresh_fixture(rng)
        resident = _refresh_estimator().fit(ds0).model
        policy = RefreshPolicy(gradient_tolerance=5e-2)
        baseline = _refresh_estimator().refresh(ds1, resident, policy)
        assert 0 < baseline.lanes_solved < baseline.lanes_total

        restores0, retries0 = rc.checkpoint_restores(), rc.retries()
        ck = TrainingCheckpointer(tmp_path / "refresh-ck")

        def attempt(restart):
            return _refresh_estimator().refresh(
                ds1, resident, policy, checkpointer=ck
            )

        with faultinject.preempt_after_calls(
            RandomEffectCoordinate, "update_model", 1
        ):
            result = run_with_recovery(
                attempt,
                max_restarts=2,
                checkpointer=ck,
                description="refresh chaos",
            )
        for cid in ("per-user", "per-item"):
            np.testing.assert_array_equal(
                np.asarray(result.model.models[cid].coefficients),
                np.asarray(baseline.model.models[cid].coefficients),
            )
        np.testing.assert_array_equal(
            np.asarray(result.model.models["fixed"].glm.coefficients.means),
            np.asarray(baseline.model.models["fixed"].glm.coefficients.means),
        )
        assert result.lanes_solved == baseline.lanes_solved
        assert rc.checkpoint_restores() - restores0 >= 1
        assert rc.retries() - retries0 >= 1

    def test_layout_changing_swap_live_server_keeps_serving(self, rng):
        """A layout-changing swap against a LIVE MicroBatchServer is
        rejected typed (the differing leaves named) and the loop keeps
        serving afterwards — counter-asserted on both sides."""
        from photon_ml_tpu.data.game_data import slice_game_dataset
        from photon_ml_tpu.serving import (
            MicroBatchServer,
            ModelSwapError,
            ResidentScorer,
        )
        from photon_ml_tpu.telemetry import serving_counters
        from photon_ml_tpu.telemetry.registry import default_registry

        ds0, ds1 = _refresh_fixture(rng)
        resident = _refresh_estimator().fit(ds0).model
        # a layout-changing "refresh": drop a coordinate entirely
        from photon_ml_tpu.models.game import GameModel

        wrong = GameModel(models={
            cid: m for cid, m in resident.models.items() if cid != "per-item"
        })
        serving_counters.reset_serving_metrics()
        reg = default_registry()
        scorer = ResidentScorer(resident, shapes=(16,))
        with MicroBatchServer(scorer, max_wait_ms=5) as server:
            before = server.submit(slice_game_dataset(ds0, 0, 4)).result(30)
            with pytest.raises(ModelSwapError, match="per-item"):
                server.swap_model(wrong)
            # the loop is still serving the resident model, bitwise
            after = server.submit(slice_game_dataset(ds0, 0, 4)).result(30)
        np.testing.assert_array_equal(before, after)
        assert reg.counter(serving_counters.SWAP_REJECTED).value == 1
        assert reg.counter(serving_counters.MODEL_SWAPS).value == 0
        assert reg.counter(serving_counters.REQUESTS).value == 2
        assert reg.counter(serving_counters.REQUEST_FAILURES).value == 0
