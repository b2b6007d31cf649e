"""Incremental GAME retrain (ISSUE 14, algorithm/refresh.py): the refresh
must match a full warm-started retrain within tolerance on an
entities-changed fixture while solving STRICTLY fewer RE lanes
(telemetry-counted), carry unselected entities' table rows over BITWISE,
fail fast (naming fields) on a layout/λ mismatch, and leave the plain
full-fit path untouched (refresh-off is the existing code path — the
selection seam only activates through set_refresh_selection)."""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.algorithm.coordinates import CoordinateOptimizationConfig
from photon_ml_tpu.algorithm.refresh import (
    RefreshFingerprintError,
    RefreshPolicy,
    check_refresh_fingerprint,
    expected_fingerprint,
    model_fingerprint,
    select_refresh_entities,
)
from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.estimators import (
    FixedEffectCoordinateConfig,
    GameEstimator,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.optim.optimizer import OptimizerConfig
from photon_ml_tpu.telemetry import refresh_counters
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.types import TaskType

N, D_FE, D_RE, N_ENT = 384, 8, 4, 12
#: the gradient screen of these tests. The float32 resident fit leaves its
#: least-converged unchanged entity at 1.0e-2 to 1.1e-2 (which side of 1e-2
#: is rounding: PR 25 moved it across); changed entities sit above 1
SCREEN = 3e-2


def _fixture(seed=0, changed=(), scale=-2.0):
    """(resident dataset, refresh dataset, vocab-row indices of changed
    entities): FIXED noise, so unchanged entities' rows are identical
    across both datasets and only real change moves the gradient."""
    rng = np.random.default_rng(seed)
    users = np.array([f"u{i:02d}" for i in rng.integers(0, N_ENT, size=N)])
    ent = np.array([int(u[1:]) for u in users])
    x_fe = rng.normal(size=(N, D_FE)).astype(np.float32)
    x_re = rng.normal(size=(N, D_RE)).astype(np.float32)
    w_fe = rng.normal(size=D_FE).astype(np.float32)
    w_re = rng.normal(size=(N_ENT, D_RE)).astype(np.float32)
    noise = 0.05 * rng.normal(size=N)

    def labels(w_tab):
        return (
            x_fe @ w_fe + (x_re * w_tab[ent]).sum(1) + noise
        ).astype(np.float32)

    def dataset(y):
        return build_game_dataset(
            labels=y,
            feature_shards={"g": x_fe, "u": x_re},
            entity_keys={"userId": users},
        )

    ds0 = dataset(labels(w_re))
    w_re2 = w_re.copy()
    w_re2[list(changed)] *= scale
    ds1 = dataset(labels(w_re2))
    vocab = np.asarray(ds0.entity_vocabs["userId"])
    changed_rows = np.flatnonzero(
        np.isin(vocab, np.array([f"u{i:02d}" for i in changed]))
    )
    return ds0, ds1, changed_rows


def _estimator(max_iter=20, num_iterations=2, **kw):
    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=max_iter), l2_weight=1.0
    )
    return GameEstimator(
        task=TaskType.LINEAR_REGRESSION,
        coordinate_configs={
            "fe": FixedEffectCoordinateConfig(
                feature_shard_id="g", optimization=opt
            ),
            "re": RandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard_id="u",
                optimization=opt,
            ),
        },
        num_iterations=num_iterations,
        **kw,
    )


class TestIncrementalRefresh:
    def test_matches_full_retrain_fewer_lanes_bitwise_carryover(self):
        refresh_counters.reset_refresh_metrics()
        est = _estimator()
        ds0, ds1, changed_rows = _fixture(changed=(1, 4, 7))
        resident = est.fit(ds0).model
        result = est.refresh(
            ds1, resident, RefreshPolicy(gradient_tolerance=SCREEN)
        )
        # strictly fewer RE lane-solves than the full fit, counted
        assert 0 < result.lanes_solved < result.lanes_total
        reg = default_registry()
        assert reg.counter(refresh_counters.LANES_SOLVED).value == \
            result.lanes_solved
        assert reg.counter(refresh_counters.LANES_TOTAL).value == \
            result.lanes_total
        # the gradient screen found exactly the changed entities
        old = np.asarray(resident.get("re").coefficients)
        new = np.asarray(result.model.get("re").coefficients)
        moved = np.flatnonzero((old != new).any(axis=1))
        assert set(moved) <= set(changed_rows)
        # unselected entities carried over BITWISE
        untouched = np.setdiff1d(np.arange(N_ENT), moved)
        assert np.array_equal(old[untouched], new[untouched])
        # FE carried over bitwise (not refreshed by default)
        assert np.array_equal(
            np.asarray(resident.get("fe").glm.coefficients.means),
            np.asarray(result.model.get("fe").glm.coefficients.means),
        )
        # within tolerance of the full warm-started retrain
        full = est.fit(ds1, initial_model=resident).model
        sc_r = np.asarray(result.model.score_dataset(ds1))
        sc_f = np.asarray(full.score_dataset(ds1))
        scale = np.abs(sc_f).max()
        assert np.abs(sc_r - sc_f).max() <= 0.05 * scale

    def test_unchanged_data_refreshes_nothing(self):
        est = _estimator()
        ds0, _, _ = _fixture()
        resident = est.fit(ds0).model
        result = est.refresh(
            ds0, resident, RefreshPolicy(gradient_tolerance=SCREEN)
        )
        assert result.lanes_solved == 0
        assert np.array_equal(
            np.asarray(resident.get("re").coefficients),
            np.asarray(result.model.get("re").coefficients),
        )

    def test_declared_entities_solve_without_gradient_screen(self):
        est = _estimator()
        ds0, ds1, changed_rows = _fixture(changed=(2, 9))
        resident = est.fit(ds0).model
        result = est.refresh(
            ds1, resident,
            RefreshPolicy(
                gradient_tolerance=None,
                changed_entities={"userId": ("u02", "u09")},
            ),
        )
        assert result.lanes_changed == 2
        assert result.lanes_gradient == 0
        assert result.lanes_solved == 2

    def test_refresh_fixed_effects_opt_in(self):
        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(3,))
        resident = est.fit(ds0).model
        result = est.refresh(
            ds1, resident,
            RefreshPolicy(gradient_tolerance=SCREEN,
                          refresh_fixed_effects=True),
        )
        assert result.coordinate_stats["fe"] == {
            "refreshed": True, "kind": "fe",
        }
        # the FE re-solved (warm-started) against refreshed residuals
        assert not np.array_equal(
            np.asarray(resident.get("fe").glm.coefficients.means),
            np.asarray(result.model.get("fe").glm.coefficients.means),
        )

    def test_plain_path_untouched_after_refresh(self):
        """Refresh-off is the existing code path: a coordinate that just
        ran a refresh produces the SAME full update as one that never
        did (the selection seam cleans up after itself)."""
        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(1,))
        resident = est.fit(ds0).model
        est.refresh(ds1, resident, RefreshPolicy(gradient_tolerance=SCREEN))
        after = est.fit(ds1, initial_model=resident)
        fresh = _estimator().fit(ds1, initial_model=resident)
        assert np.array_equal(
            np.asarray(after.model.get("re").coefficients),
            np.asarray(fresh.model.get("re").coefficients),
        )
        assert np.array_equal(
            np.asarray(after.model.get("fe").glm.coefficients.means),
            np.asarray(fresh.model.get("fe").glm.coefficients.means),
        )

    def test_select_refresh_entities_units(self):
        est = _estimator()
        ds0, ds1, changed_rows = _fixture(changed=(5,))
        resident = est.fit(ds0).model
        _seq, coords = est._build_coordinates(ds1, resident)
        partial = coords["fe"].score(resident.get("fe"))
        sel, stats = select_refresh_entities(
            coords["re"], resident.get("re"), partial,
            RefreshPolicy(gradient_tolerance=SCREEN),
        )
        assert set(np.flatnonzero(sel)) == set(changed_rows)
        assert stats["gradient"] == len(changed_rows)
        assert stats["changed"] == 0

    def test_checkpoint_resume_bitwise(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(1, 6))
        resident = est.fit(ds0).model
        policy = RefreshPolicy(gradient_tolerance=SCREEN)
        uninterrupted = est.refresh(ds1, resident, policy)

        # a partial refresh: checkpoint after the carried FE only, then
        # "crash" (simulated by a fresh call that resumes)
        ck = TrainingCheckpointer(tmp_path / "refresh")
        resumed = est.refresh(ds1, resident, policy, checkpointer=ck)
        assert ck.latest_step() is not None
        # resume from the COMPLETE checkpoint: fast-forwards everything,
        # returns the checkpointed model bitwise
        again = est.refresh(ds1, resident, policy, checkpointer=ck)
        for cid in ("fe", "re"):
            a = resumed.model.get(cid)
            b = again.model.get(cid)
            u = uninterrupted.model.get(cid)
            for x, y in ((a, b), (a, u)):
                if cid == "re":
                    assert np.array_equal(np.asarray(x.coefficients),
                                          np.asarray(y.coefficients))
                else:
                    assert np.array_equal(
                        np.asarray(x.glm.coefficients.means),
                        np.asarray(y.glm.coefficients.means),
                    )

    def test_no_resume_recomputes_against_new_data(self, tmp_path):
        """A COMPLETED refresh checkpoint in the same directory must not
        silently serve yesterday's model: resume=False re-runs against
        today's data (the daily-refresh discipline)."""
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(2,))
        _, ds2, _ = _fixture(changed=(2, 8), scale=-3.0)
        resident = est.fit(ds0).model
        policy = RefreshPolicy(gradient_tolerance=SCREEN)
        ck = TrainingCheckpointer(tmp_path / "refresh")
        day1 = est.refresh(ds1, resident, policy, checkpointer=ck)
        # resume=True against NEW data fast-forwards to day 1's model
        stale = est.refresh(ds2, resident, policy, checkpointer=ck)
        assert np.array_equal(
            np.asarray(stale.model.get("re").coefficients),
            np.asarray(day1.model.get("re").coefficients),
        )
        # resume=False actually refreshes against ds2
        fresh = est.refresh(
            ds2, resident, policy, checkpointer=ck, resume=False
        )
        assert fresh.lanes_solved > day1.lanes_solved
        assert not np.array_equal(
            np.asarray(fresh.model.get("re").coefficients),
            np.asarray(day1.model.get("re").coefficients),
        )

    def test_checkpoint_fingerprint_guard(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import TrainingCheckpointer

        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(1,))
        resident = est.fit(ds0).model
        ck = TrainingCheckpointer(tmp_path / "refresh")
        est.refresh(
            ds1, resident, RefreshPolicy(gradient_tolerance=SCREEN),
            checkpointer=ck, fingerprint={"re/lambda": 1.0},
        )
        with pytest.raises(RefreshFingerprintError, match="re/lambda"):
            est.refresh(
                ds1, resident, RefreshPolicy(gradient_tolerance=SCREEN),
                checkpointer=ck, fingerprint={"re/lambda": 9.0},
            )

    def test_missing_coordinate_fails_fast(self):
        est = _estimator()
        ds0, ds1, _ = _fixture(changed=(1,))
        resident = est.fit(ds0).model
        from photon_ml_tpu.models.game import GameModel

        partial_model = GameModel(models={"fe": resident.get("fe")})
        with pytest.raises(RefreshFingerprintError, match="'re'"):
            est.refresh(ds1, partial_model,
                        RefreshPolicy(gradient_tolerance=SCREEN))


class TestRefreshFingerprint:
    def test_agreement_passes_and_mismatch_names_fields(self):
        est = _estimator()
        ds0, _, _ = _fixture()
        resident = est.fit(ds0).model
        seq = ["fe", "re"]
        rw = {"fe": 1.0, "re": 1.0}
        expected = expected_fingerprint(
            ds0, est.coordinate_configs, seq, reg_weights=rw
        )
        check_refresh_fingerprint(
            model_fingerprint(resident, seq, reg_weights=rw), expected
        )
        with pytest.raises(RefreshFingerprintError, match="fe/lambda"):
            check_refresh_fingerprint(
                model_fingerprint(resident, seq,
                                  reg_weights={"fe": 2.0, "re": 1.0}),
                expected,
            )
        # a layout change (different entity-vocab size) is named too
        wrong = model_fingerprint(resident, seq, reg_weights=rw)
        wrong["re/entities"] = N_ENT + 1
        with pytest.raises(RefreshFingerprintError, match="re/entities"):
            check_refresh_fingerprint(wrong, expected)


class TestRefreshDriver:
    @pytest.fixture(scope="class")
    def trained(self, tmp_path_factory):
        from photon_ml_tpu.cli import game_training_driver
        from tests.test_cli import _write_game_avro

        base = tmp_path_factory.mktemp("refresh-driver")
        _write_game_avro(base / "train", 300, seed=0)
        game_training_driver.main([
            "--input-data-path", str(base / "train"),
            "--root-output-dir", str(base / "out"),
        ] + self._common())
        return base

    @staticmethod
    def _common():
        return [
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=1.0,max.iter=10",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,"
            "random.effect.type=userId,reg.weights=0.1,max.iter=10",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ]

    def test_refresh_mode_end_to_end(self, trained, tmp_path):
        import os

        from photon_ml_tpu.cli import game_training_driver

        s = game_training_driver.main([
            "--input-data-path", str(trained / "train"),
            "--root-output-dir", str(tmp_path / "refreshed"),
            "--model-input-dir", str(trained / "out" / "best"),
            "--incremental-refresh",
            "--refresh-gradient-tolerance", "0",
            "--refresh-changed-entities", "userId=u1|u3",
        ] + self._common())
        info = s["incremental_refresh"]
        assert info["lanes_changed"] == 2
        assert info["lanes_solved"] == 2
        assert 0 < info["lanes_solved"] < info["lanes_total"]
        assert info["coordinates"]["fe"] == {"refreshed": False}
        assert os.path.isdir(tmp_path / "refreshed" / "best")

    def test_refresh_mode_fingerprint_guard(self, trained, tmp_path):
        from photon_ml_tpu.cli import game_training_driver

        args = [
            "--input-data-path", str(trained / "train"),
            "--root-output-dir", str(tmp_path / "bad"),
            "--model-input-dir", str(trained / "out" / "best"),
            "--incremental-refresh",
            "--feature-shard-configurations",
            "name=global,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=fe,feature.shard=global,reg.weights=7.0,max.iter=10",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,"
            "random.effect.type=userId,reg.weights=0.1,max.iter=10",
            "--task-type", "LINEAR_REGRESSION",
            "--coordinate-descent-iterations", "1",
        ]
        with pytest.raises(RefreshFingerprintError, match="fe/lambda"):
            game_training_driver.main(args)

    def test_refresh_mode_validation(self, tmp_path):
        from photon_ml_tpu.cli import game_training_driver

        with pytest.raises(ValueError, match="resident model"):
            game_training_driver.main([
                "--input-data-path", str(tmp_path / "x"),
                "--root-output-dir", str(tmp_path / "y"),
                "--incremental-refresh",
            ] + self._common())
        with pytest.raises(ValueError, match="incremental-refresh"):
            game_training_driver.main([
                "--input-data-path", str(tmp_path / "x"),
                "--root-output-dir", str(tmp_path / "y"),
                "--refresh-changed-entities", "userId=u1",
            ] + self._common())
