"""Multi-rank out-of-core GAME (ISSUE 17): --streaming-chunks x
--partitioned-io as ONE legal, tested, recoverable configuration.

Virtual ranks (threads + InProcessExchange) drive the real composed path:
``plan_partitioned_game_stream`` agrees one entity-granular chunk plan
over the exchange, per-rank ``StreamingGameProgram`` sweeps combine FE
partial sums in rank order, solve only rank-local entity buckets, sync
the RE tables post-sweep, and drive ONE global DuHL schedule from the
allgathered importance signal. The correctness backbone:

- the two-rank partitioned streamed run matches the single-rank streamed
  run to float round-off (losses + FE coefficients + RE tables), and both
  ranks finish with bitwise-identical global state;
- composed sharding invariance: the partitioned run on an 8-device mesh
  matches the unsharded single-rank run;
- DuHL pin/evict decisions are identical on every rank every sweep (the
  rank-local-ranking footgun, arXiv:1702.07005 applied per ISSUE 11);
- chaos: a withheld importance allgather surfaces as a rank-attributed
  ExchangeTimeout; a disagreed chunk plan fails fast naming the field; a
  rank killed mid-sweep coordinates an all-rank rollback that finishes
  BITWISE equal to the uninterrupted run; a checkpoint restored under
  different rank geometry fails fast naming "partition".

No pytest-timeout in this container: boundedness rides the exchanges' own
deadlines plus bounded thread joins (test_resilience.py rule).
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import numpy as np
import pytest

from dev import faultinject
from photon_ml_tpu.algorithm.streaming_game import (
    DuHLChunkSchedule,
    DuHLScheduleConfig,
    StreamingGameProgram,
)
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
from photon_ml_tpu.io.stream_reader import (
    GameAvroChunkSource,
    plan_partitioned_game_stream,
    scan_game_stream,
)
from photon_ml_tpu.optim.optimizer import OptimizerConfig
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    RandomEffectStepSpec,
)
from photon_ml_tpu.parallel.multihost import InProcessExchange
from photon_ml_tpu.resilience import ExchangeTimeout
from photon_ml_tpu.types import TaskType
from test_streaming_game import _avro_game_records, _write_avro

NUM_RANKS = 2
CHUNK_RECORDS = 40
SWEEPS = 2


def _cfg():
    return {"global": FeatureShardConfiguration(feature_bags=("features",))}


def _run_ranks(n, fn, timeout=300.0):
    """Run ``fn(rank)`` on n threads; bounded join (hang = failure)."""
    results, errors = [None] * n, [None] * n

    def work(r):
        try:
            results[r] = fn(r)
        except Exception as e:  # surfaced to the asserting test
            errors[r] = e

    threads = [threading.Thread(target=work, args=(r,), daemon=True)
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), (
        "a partitioned streamed-GAME path exceeded its bounded deadline "
        "(hang)"
    )
    return results, errors


def _plan(path, exchange, chunk_records=CHUNK_RECORDS,
          schedule_budget=None):
    return plan_partitioned_game_stream(
        path, _cfg(), ("userId",),
        exchange=exchange,
        chunk_records=chunk_records,
        cluster_by="userId",
        schedule_budget=schedule_budget,
        dtype=np.float64,
    )


def _single_source(path, chunk_records=CHUNK_RECORDS):
    """The single-rank streamed reference build (scan + clustered source,
    the pre-ISSUE-17 driver path) over the SAME input."""
    files = avro_io.list_avro_files(path)
    maps, vocabs, keys, indexes, _scalars = scan_game_stream(
        files, _cfg(), ("userId",), cluster_by="userId", dtype=np.float64
    )
    source = GameAvroChunkSource(
        files, _cfg(), maps,
        chunk_records=chunk_records,
        random_effect_id_columns=("userId",),
        entity_vocabs=vocabs,
        cluster_by="userId",
        cluster_keys=keys,
        indexes=indexes,
        dtype=np.float64,
    )
    return source, maps, vocabs


def _program(source, vocabs, *, partition=None, exchange=None,
             schedule=None, mesh=None, max_iter=4):
    opt = OptimizerConfig(max_iterations=max_iter)
    return StreamingGameProgram(
        TaskType.LINEAR_REGRESSION, source,
        FixedEffectStepSpec("global", opt, l2_weight=0.1),
        (RandomEffectStepSpec("userId", "global", opt, l2_weight=1.0),),
        num_entities={"userId": len(vocabs["userId"])},
        schedule=schedule,
        exchange=exchange,
        partition=partition,
        mesh=mesh,
    )


@pytest.fixture(scope="module")
def avro_path(tmp_path_factory):
    return _write_avro(
        tmp_path_factory.mktemp("ranks"), _avro_game_records()
    )


@pytest.fixture(scope="module")
def single_rank_ref(avro_path):
    source, _maps, vocabs = _single_source(avro_path)
    return _program(source, vocabs).train(num_sweeps=SWEEPS)


# ---------------------------------------------------------------------------
# the agreed plan
# ---------------------------------------------------------------------------


class TestPartitionedPlan:
    def test_two_rank_plan_agrees_and_covers(self, avro_path):
        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)
        results, errors = _run_ranks(
            NUM_RANKS, lambda r: _plan(avro_path, group[r])
        )
        assert errors == [None, None], errors
        (s0, m0, v0, p0), (s1, m1, v1, p1) = results
        # every partition field except the rank slot is identical
        assert dataclasses.replace(p0, rank=0) == dataclasses.replace(
            p1, rank=0
        )
        assert (p0.rank, p1.rank) == (0, 1)
        # chunk ranges partition [0, num_chunks) contiguously
        assert p0.chunk_ranges[0][0] == 0
        assert p0.chunk_ranges[-1][1] == p0.num_chunks
        for (_, hi), (lo, _) in zip(p0.chunk_ranges, p0.chunk_ranges[1:]):
            assert hi == lo
        # each rank's local source holds exactly its slice
        for src, part in ((s0, p0), (s1, p1)):
            lo, hi = part.chunk_range()
            assert src.num_chunks == hi - lo
        assert s0.total_records + s1.total_records == p0.total_records
        # per-rank payloads are strictly smaller than the whole input —
        # the I/O the partition exists to save
        for b in p0.payload_bytes:
            assert 0 < b < p0.input_bytes
        # the agreed maps/vocabs equal the single-rank scan's (sorted
        # distinct keys — both builders converge on the same universe)
        _sref, mref, vref = _single_source(avro_path)
        assert dict(m0["global"]) == dict(mref["global"])
        assert dict(m1["global"]) == dict(mref["global"])
        np.testing.assert_array_equal(v0["userId"], vref["userId"])
        np.testing.assert_array_equal(v1["userId"], vref["userId"])
        # global plan geometry matches the single-rank clustered plan
        assert p0.num_chunks == _sref.num_chunks
        assert p0.total_records == _sref.total_records

    def test_disagreed_plan_fails_fast_naming_field(self, avro_path):
        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)
        results, errors = _run_ranks(
            NUM_RANKS,
            lambda r: _plan(
                avro_path, group[r],
                chunk_records=CHUNK_RECORDS if r == 0 else 24,
            ),
        )
        assert results == [None, None]
        for e in errors:
            assert isinstance(e, RuntimeError)
            assert "chunk_records" in str(e)
            assert "disagree" in str(e)


# ---------------------------------------------------------------------------
# parity: partitioned == single-rank streamed
# ---------------------------------------------------------------------------


class TestPartitionedParity:
    def _train_two_ranks(self, path, group, meshes=None):
        def run(r):
            source, _maps, vocabs, partition = _plan(path, group[r])
            program = _program(
                source, vocabs, partition=partition, exchange=group[r],
                mesh=meshes[r] if meshes is not None else None,
            )
            return program.train(num_sweeps=SWEEPS)

        return _run_ranks(NUM_RANKS, run)

    def test_two_rank_matches_single_rank_streamed(
            self, avro_path, single_rank_ref):
        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)
        results, errors = self._train_two_ranks(avro_path, group)
        assert errors == [None, None], errors
        # every rank finishes with the COMPLETE global model (the re_sync
        # contract) — bitwise identical across ranks
        np.testing.assert_array_equal(
            np.asarray(results[0].state.fe_coefficients),
            np.asarray(results[1].state.fe_coefficients),
        )
        np.testing.assert_array_equal(
            np.asarray(results[0].state.re_tables["userId"]),
            np.asarray(results[1].state.re_tables["userId"]),
        )
        np.testing.assert_array_equal(results[0].losses, results[1].losses)
        # ...and matches the single-rank streamed run to float round-off
        # (the only difference is the chunked/rank-order summation order)
        for res in results:
            np.testing.assert_allclose(
                np.asarray(res.state.fe_coefficients),
                np.asarray(single_rank_ref.state.fe_coefficients),
                rtol=1e-9, atol=1e-12,
            )
            np.testing.assert_allclose(
                np.asarray(res.state.re_tables["userId"]),
                np.asarray(single_rank_ref.state.re_tables["userId"]),
                rtol=1e-9, atol=1e-12,
            )
            np.testing.assert_allclose(
                res.losses, single_rank_ref.losses, rtol=1e-9
            )
        # each rank decoded strictly less than the whole input
        # (bytes_decoded is the chunk-load evidence)
        for res in results:
            assert res.chunk_loads > 0

    def test_composed_sharding_invariance(self, avro_path, single_rank_ref):
        """1 == many devices THROUGH the partitioned composition: each
        rank's FE epochs place chunks over its OWN mesh (disjoint 4-device
        halves of the virtual 8 — ranks never share devices, the
        production topology) and must still reproduce the unsharded
        single-rank fit."""
        from jax.sharding import Mesh

        devices = jax.devices()
        meshes = [
            Mesh(np.asarray(devices[4 * r:4 * r + 4]).reshape(4), ("data",))
            for r in range(NUM_RANKS)
        ]
        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)
        results, errors = self._train_two_ranks(avro_path, group,
                                                meshes=meshes)
        assert errors == [None, None], errors
        for res in results:
            np.testing.assert_allclose(
                np.asarray(res.state.fe_coefficients),
                np.asarray(single_rank_ref.state.fe_coefficients),
                rtol=1e-9, atol=1e-12,
            )
            np.testing.assert_allclose(
                np.asarray(res.state.re_tables["userId"]),
                np.asarray(single_rank_ref.state.re_tables["userId"]),
                rtol=1e-9, atol=1e-12,
            )


# ---------------------------------------------------------------------------
# one global DuHL schedule
# ---------------------------------------------------------------------------


class TestGlobalDuHLSchedule:
    def test_pin_evict_identical_on_every_rank(self, avro_path):
        """The working set is a pure function of the ALLGATHERED
        importance signal: every rank's schedule makes the same pin/evict
        decisions every sweep, and the terminal schedule states agree
        exactly (rank-local ranking is the measured 12-vs-8-sweeps
        footgun this pins against)."""
        budget = {"working_set": 2, "tail_chunks": 1}
        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)

        def run(r):
            source, _maps, vocabs, partition = _plan(
                avro_path, group[r], schedule_budget=budget
            )
            schedule = DuHLChunkSchedule(
                DuHLScheduleConfig(
                    working_set_chunks=budget["working_set"],
                    tail_chunks_per_sweep=budget["tail_chunks"],
                ),
                partition.num_chunks,
            )
            program = _program(
                source, vocabs, partition=partition, exchange=group[r],
                schedule=schedule,
            )
            pinned_log = []
            program.train(
                num_sweeps=4,
                on_sweep=lambda s, t, l: pinned_log.append(
                    sorted(schedule.pinned())
                ),
            )
            return pinned_log, schedule.state_dict()

        results, errors = _run_ranks(NUM_RANKS, run)
        assert errors == [None, None], errors
        (log0, state0), (log1, state1) = results
        assert len(log0) == 4
        assert log0 == log1
        assert state0 == state1
        # the schedule actually narrowed to a working set post-warmup
        assert 0 < len(log0[-1]) <= budget["working_set"]


# ---------------------------------------------------------------------------
# chaos: withheld collectives, coordinated rollback, fingerprint guard
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestPartitionedChaos:
    def test_withheld_importance_allgather_attributed(self, avro_path):
        """A rank that dies before publishing the DuHL importance signal
        surfaces on the healthy rank as a rank-attributed ExchangeTimeout
        naming the tag and the missing rank — bounded by the exchange's
        own deadline, never a hang."""
        group = InProcessExchange.create_group(NUM_RANKS, timeout=3.0)

        def run(r):
            source, _maps, vocabs, partition = _plan(avro_path, group[r])
            exchange = group[r]
            if r == 1:
                exchange = faultinject.WithholdingExchange(
                    group[r], withhold=("duhl_importance",)
                )
            program = _program(
                source, vocabs, partition=partition, exchange=exchange
            )
            return program.train(num_sweeps=SWEEPS)

        results, errors = _run_ranks(NUM_RANKS, run)
        assert results == [None, None]
        assert isinstance(errors[1], faultinject.InjectedCrash)
        assert isinstance(errors[0], ExchangeTimeout)
        assert "duhl_importance" in errors[0].tag
        assert 1 in errors[0].missing_ranks

    def test_rank_kill_mid_sweep_coordinated_rollback_bitwise(
            self, avro_path, tmp_path):
        """ISSUE 17 chaos acceptance: rank 1 dies at the sweep-2
        checkpoint commit; CoordinatedRecovery rolls EVERY rank back to
        the published barrier-committed step and the finished run is
        BITWISE equal to the uninterrupted two-rank run, with the culprit
        named in the healthy rank's journal."""
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer
        from photon_ml_tpu.resilience import (
            CoordinatedRecovery,
            run_with_recovery,
        )
        from photon_ml_tpu.telemetry import RunJournal

        sweeps = 3
        # uninterrupted two-rank reference
        ref_group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)

        def ref_run(r):
            source, _maps, vocabs, partition = _plan(avro_path, ref_group[r])
            program = _program(
                source, vocabs, partition=partition, exchange=ref_group[r]
            )
            return program.train(num_sweeps=sweeps)

        refs, ref_errors = _run_ranks(NUM_RANKS, ref_run)
        assert ref_errors == [None, None], ref_errors

        group = InProcessExchange.create_group(NUM_RANKS, timeout=5.0)
        killer = faultinject.die_at_barrier(
            group[1], "checkpoint_commit/2", rank=1
        )
        exchanges = [group[0], killer]
        cks = [TrainingCheckpointer(tmp_path / "ck")
               for _ in range(NUM_RANKS)]
        journals = [
            RunJournal(tmp_path / f"journal-r{r}", rank=0)
            for r in range(NUM_RANKS)
        ]
        coords = [
            CoordinatedRecovery(
                exchanges[r], max_restarts=2, checkpointer=cks[r],
                journal=journals[r],
            )
            for r in range(NUM_RANKS)
        ]

        def run(r):
            def attempt(restart):
                # every attempt re-plans over the exchange — the restart
                # generation resynchronizes the per-rank call sequences,
                # so the replanned agreement is part of the rollback
                source, _maps, vocabs, partition = _plan(
                    avro_path, exchanges[r]
                )
                program = _program(
                    source, vocabs, partition=partition,
                    exchange=exchanges[r],
                )
                return program.train(
                    num_sweeps=sweeps,
                    checkpointer=cks[r],
                    resume_step=coords[r].resume_step,
                )

            return run_with_recovery(
                attempt,
                checkpointer=cks[r],
                journal=journals[r],
                description=f"partitioned streamed rank {r}",
                coordinator=coords[r],
            )

        results, errors = _run_ranks(NUM_RANKS, run)
        for j in journals:
            j.close()
        assert killer.state["fired"] == 1
        assert errors == [None, None], errors
        for r in range(NUM_RANKS):
            np.testing.assert_array_equal(
                np.asarray(results[r].state.fe_coefficients),
                np.asarray(refs[0].state.fe_coefficients),
            )
            np.testing.assert_array_equal(
                np.asarray(results[r].state.re_tables["userId"]),
                np.asarray(refs[0].state.re_tables["userId"]),
            )
            np.testing.assert_array_equal(results[r].losses, refs[0].losses)
        from test_coordinated import _read_rows

        rows0 = _read_rows(tmp_path / "journal-r0")
        aborts0 = [row for row in rows0 if row.get("kind") == "peer_abort"]
        assert aborts0 and aborts0[0]["origin_rank"] == 1

    def test_restore_under_different_rank_geometry_fails_fast(
            self, avro_path, tmp_path):
        """A checkpoint written by the two-rank partitioned run restored
        by a single-rank program must fail fast naming the differing
        fingerprint field ("partition"), never silently resume."""
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        group = InProcessExchange.create_group(NUM_RANKS, timeout=60.0)
        ck_dir = tmp_path / "geo-ck"

        def run(r):
            source, _maps, vocabs, partition = _plan(avro_path, group[r])
            program = _program(
                source, vocabs, partition=partition, exchange=group[r]
            )
            return program.train(
                num_sweeps=1, checkpointer=TrainingCheckpointer(ck_dir)
            )

        _results, errors = _run_ranks(NUM_RANKS, run)
        assert errors == [None, None], errors
        source, _maps, vocabs = _single_source(avro_path)
        program = _program(source, vocabs)
        with pytest.raises(ValueError, match="partition"):
            program.train(
                num_sweeps=SWEEPS, checkpointer=TrainingCheckpointer(ck_dir)
            )


# ---------------------------------------------------------------------------
# streamed validation scoring (the ISSUE 17 rider)
# ---------------------------------------------------------------------------


class TestStreamedValidationScoring:
    def test_streamed_scores_match_in_core_score_dataset(self, avro_path):
        """score_game_stream is the out-of-core twin of
        ``GameModel.score_dataset(ds) + ds.offsets`` (the driver's
        validation semantics): same model, same input, chunk-wise streamed
        scores match the in-core path to float round-off — and the
        ``return_scalars`` pass hands back the exact [n] evaluation
        scalars without a second read."""
        from photon_ml_tpu.algorithm.streaming_game import score_game_stream
        from photon_ml_tpu.io.data_reader import read_merged
        from photon_ml_tpu.models.coefficients import Coefficients
        from photon_ml_tpu.models.game import (
            FixedEffectModel,
            GameModel,
            RandomEffectModel,
        )
        from photon_ml_tpu.models.glm import GeneralizedLinearModel
        from photon_ml_tpu.parallel.distributed import GameTrainState

        full = read_merged(
            avro_path, _cfg(), random_effect_id_columns=("userId",),
            dtype=np.float64,
        )
        ds = full.dataset
        rng = np.random.default_rng(3)
        d = full.index_maps["global"].size
        fe_w = rng.normal(size=d)
        re_table = rng.normal(size=(len(ds.entity_vocabs["userId"]), d))
        model = GameModel(models={
            "global": FixedEffectModel(
                glm=GeneralizedLinearModel(
                    Coefficients(means=fe_w), TaskType.LINEAR_REGRESSION
                ),
                feature_shard_id="global",
            ),
            "per-user": RandomEffectModel(
                coefficients=re_table,
                entity_keys=ds.entity_vocabs["userId"],
                random_effect_type="userId",
                feature_shard_id="global",
                task=TaskType.LINEAR_REGRESSION,
            ),
        })
        expected = np.asarray(model.score_dataset(ds)) + np.asarray(
            ds.offsets
        )

        source, maps, vocabs = _single_source(avro_path)
        # both builders converge on the same sorted universes, so the
        # random params mean the same coordinates on both paths
        assert dict(maps["global"]) == dict(full.index_maps["global"])
        np.testing.assert_array_equal(
            vocabs["userId"], ds.entity_vocabs["userId"]
        )
        state = GameTrainState(
            fe_coefficients=fe_w, re_tables={"userId": re_table}
        )
        scores, scalars = score_game_stream(
            state, source, TaskType.LINEAR_REGRESSION, "global",
            {"userId": "global"}, return_scalars=True,
        )
        np.testing.assert_allclose(scores, expected, rtol=1e-9, atol=1e-12)
        np.testing.assert_array_equal(
            scalars["labels"], np.asarray(ds.labels)
        )
        np.testing.assert_array_equal(
            scalars["offsets"], np.asarray(ds.offsets)
        )
        np.testing.assert_array_equal(
            scalars["weights"], np.asarray(ds.weights)
        )
