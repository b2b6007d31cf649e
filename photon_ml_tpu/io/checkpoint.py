"""Mid-training checkpoint / resume for GAME coordinate descent.

The reference has **no** mid-training checkpoints: recovery is Spark lineage
recompute plus coarse warm-start from models saved per optimization config
(SURVEY.md §5; GameTrainingDriver.scala:748-815, GameEstimator.scala:392-411).
This module goes beyond it with first-class checkpoint/resume:

- ``TrainingCheckpointer`` writes one atomic step directory per save
  (``step_<k>/`` with ``arrays.npz`` + ``meta.json`` + per-coordinate entity
  key vocabularies), prunes to ``max_to_keep``, and restores the latest
  intact step. Atomicity = write to a temp dir, ``os.replace`` into place —
  a crash mid-save never corrupts the latest good checkpoint.
- ``run_coordinate_descent(..., checkpointer=...)`` (algorithm/
  coordinate_descent.py) saves after every coordinate update and fast-
  forwards past completed updates on resume.
- ``train_distributed(..., checkpointer=...)`` (parallel/distributed.py)
  saves the mesh-sharded ``GameTrainState`` per CD sweep; arrays are pulled
  to host with ``jax.device_get`` (works for sharded arrays — all shards on
  this host are gathered) and re-sharded on restore by the caller's
  ``shard_inputs``.
- ``SolverCheckpointer`` extends the same atomic contract to STREAMING
  solves (``estimators.train_glm_streaming``): the ``host_loop`` solver
  bodies run from Python with host-visible state, so the full optimizer
  state struct + λ-grid position + epoch cursor persist at every epoch
  boundary and a killed run fast-forwards past completed λs and resumes
  MID-SOLVE — the workload most likely to run for hours on a preemptible
  pool no longer restarts from scratch.
- ``commit_checkpoint`` is the ONE write site for training loops
  (dev/lint_parity.py check 10): rank-0-gated per the multi-process
  convention, and — when a ``MetadataExchange`` is attached — gated by
  its rank-attributed deadline barriers so a checkpoint commits only when
  EVERY rank reached the same step (exchange-consistent; a wedged rank
  surfaces as an ``ExchangeTimeout`` naming it, never a torn commit).

Checkpoints are plain numpy + JSON: portable across backends (save on TPU,
restore on CPU), no framework version pinning, diffable metadata.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import shutil
import tempfile
import zipfile
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    DatumScoringModel,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.models.matrix_factorization import MatrixFactorizationModel
from photon_ml_tpu.types import TaskType

_STEP_PREFIX = "step_"
_META_FILE = "meta.json"
_ARRAYS_FILE = "arrays.npz"


@dataclasses.dataclass
class Checkpoint:
    """One restored checkpoint: step id, array pytree, JSON metadata."""

    step: int
    arrays: dict[str, np.ndarray]
    meta: dict[str, Any]


class TrainingCheckpointer:
    """Atomic, pruned, numbered checkpoints under one directory.

    Layout::

        <directory>/
          step_00000007/
            arrays.npz     flat {key: array} — numeric state
            meta.json      structure + scalars (task types, shard ids, ...)
          step_00000008/
            ...

    ``save`` never leaves a partially-written ``step_*`` dir: content goes to
    a ``tmp.*`` sibling first and is renamed into place, then older steps are
    pruned down to ``max_to_keep``.
    """

    def __init__(self, directory: str | os.PathLike, *, max_to_keep: int = 3):
        self.directory = str(directory)
        self.max_to_keep = max(1, int(max_to_keep))
        os.makedirs(self.directory, exist_ok=True)

    # -- core save/restore ---------------------------------------------------

    def save(self, step: int, arrays: Mapping[str, np.ndarray], meta: dict) -> str:
        step_dir = os.path.join(self.directory, f"{_STEP_PREFIX}{step:08d}")
        tmp_dir = tempfile.mkdtemp(prefix="tmp.", dir=self.directory)
        try:
            host_arrays = {k: np.asarray(jax.device_get(v)) for k, v in arrays.items()}
            np.savez(os.path.join(tmp_dir, _ARRAYS_FILE), **host_arrays)
            with open(os.path.join(tmp_dir, _META_FILE), "w") as f:
                json.dump({"step": step, **meta}, f, indent=2, default=str)
            if os.path.isdir(step_dir):
                shutil.rmtree(step_dir)
            os.replace(tmp_dir, step_dir)
        except BaseException:
            shutil.rmtree(tmp_dir, ignore_errors=True)
            raise
        self._prune()
        return step_dir

    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith(_STEP_PREFIX):
                path = os.path.join(self.directory, name)
                # intact = both files present (a partially-pruned or
                # partially-deleted dir must not be offered for restore)
                if os.path.isfile(os.path.join(path, _META_FILE)) and os.path.isfile(
                    os.path.join(path, _ARRAYS_FILE)
                ):
                    try:
                        out.append(int(name[len(_STEP_PREFIX):]))
                    except ValueError:
                        continue
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def newest_loadable_step(self) -> int | None:
        """Newest step that passes the cheap integrity probe — what
        coordinated rollback (resilience/coordinated.py) resolves on rank
        0 and publishes to every rank: barrier-committed saves are the
        only writers here, so the newest INTACT step is by construction a
        step every rank completed. None when no step would load."""
        for step in reversed(self.steps()):
            if self._loadable(step):
                return step
        return None

    #: everything a truncated/garbled step file can raise during load:
    #: zip directory damage (BadZipFile), npz entry damage (zlib via
    #: ValueError/OSError), meta damage (JSONDecodeError is a ValueError)
    _CORRUPT_ERRORS = (
        OSError,
        EOFError,
        ValueError,
        KeyError,
        zipfile.BadZipFile,
    )

    def _load(self, step: int) -> Checkpoint:
        step_dir = os.path.join(self.directory, f"{_STEP_PREFIX}{step:08d}")
        with open(os.path.join(step_dir, _META_FILE)) as f:
            meta = json.load(f)
        with np.load(os.path.join(step_dir, _ARRAYS_FILE), allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
        return Checkpoint(step=step, arrays=arrays, meta=meta)

    def restore(self, step: int | None = None) -> Checkpoint | None:
        """Restore ``step`` (default: NEWEST step that actually loads).

        A ``step_<k>/`` dir whose ``arrays.npz`` or ``meta.json`` is
        truncated/garbled (external damage — the atomic save never
        produces one) is skipped with a warning and the next older step is
        tried, so resume degrades to the newest INTACT step instead of
        aborting. Returns None when no step loads; raises ValueError for
        an explicitly-requested step that is missing, and the underlying
        error for one that is present but corrupt (an explicit request
        must not silently resolve to a different step).
        """
        if step is not None:
            if step not in self.steps():
                raise ValueError(
                    f"checkpoint step {step} not found (intact steps: "
                    f"{self.steps()})"
                )
            return self._load(step)
        for candidate in reversed(self.steps()):
            try:
                return self._load(candidate)
            except self._CORRUPT_ERRORS as e:
                logger.warning(
                    "checkpoint step %d at %s is corrupt (%s: %s); falling "
                    "back to the previous step",
                    candidate, self.directory, type(e).__name__, e,
                )
        return None

    def _loadable(self, step: int) -> bool:
        """Cheap integrity probe for pruning decisions: meta parses and the
        npz's zip central directory (stored at end of file — the first
        casualty of truncation) reads. Full CRC verification is restore's
        job; pruning must not re-read multi-GB arrays."""
        step_dir = os.path.join(self.directory, f"{_STEP_PREFIX}{step:08d}")
        try:
            with open(os.path.join(step_dir, _META_FILE)) as f:
                json.load(f)
            with zipfile.ZipFile(os.path.join(step_dir, _ARRAYS_FILE)) as z:
                z.namelist()
            return True
        except self._CORRUPT_ERRORS:
            return False

    def _prune(self) -> None:
        steps = self.steps()
        doomed = steps[: -self.max_to_keep]
        if not doomed:
            return
        kept = steps[-self.max_to_keep:]
        if not any(self._loadable(s) for s in kept):
            # every kept step is damaged: protect the newest loadable step
            # among the prune candidates — pruning must never delete the
            # last checkpoint a resume could actually restore
            for s in reversed(doomed):
                if self._loadable(s):
                    logger.warning(
                        "keeping checkpoint step %d beyond max_to_keep=%d: "
                        "it is the newest loadable step (%s newer steps "
                        "are corrupt)",
                        s, self.max_to_keep, len(kept),
                    )
                    doomed = [d for d in doomed if d != s]
                    break
        for s in doomed:
            shutil.rmtree(
                os.path.join(self.directory, f"{_STEP_PREFIX}{s:08d}"),
                ignore_errors=True,
            )


def commit_checkpoint(
    checkpointer,
    step: int,
    arrays: Mapping[str, np.ndarray],
    meta: dict,
    *,
    exchange=None,
) -> str | None:
    """The ONE checkpoint write site for training loops: rank-0-gated and
    (with an exchange) barrier-committed. dev/lint_parity.py check 10
    statically bans direct ``checkpointer.save(...)`` calls in parallel/
    and algorithm/ so multi-rank write sites cannot drift from this
    contract.

    EVERY rank must call (the barriers are collective-like; the state
    gathers feeding ``arrays`` already are). Protocol:

    1. pre-commit barrier — the checkpoint commits only when every rank
       reached this step with its collectives complete (a rank that
       crashed or wedged surfaces as a rank-attributed
       ``resilience.errors.ExchangeTimeout`` within the exchange deadline,
       never a checkpoint torn across ranks' notions of progress);
    2. rank 0 writes through the atomic temp-dir + ``os.replace`` save
       (the multi-process convention: only rank 0 touches shared output
       directories);
    3. post-commit barrier — no rank runs ahead (and possibly fails,
       triggering a restore) while the publish is still in flight.

    ``exchange=None`` is the single-caller mode: the ``jax.process_index()
    == 0`` gate alone, no barriers — exactly the pre-existing
    ``train_distributed`` behavior (and a no-op gate single-process).
    Returns the step directory path on the writing rank, None elsewhere.
    """
    from photon_ml_tpu.telemetry import tracing

    if checkpointer is None:
        return None
    if exchange is None:
        if jax.process_index() == 0:
            with tracing.span("checkpoint/write", cat="checkpoint",
                              step=step):
                return checkpointer.save(step, arrays, meta)
        return None
    # the commit span brackets both barriers (their waits are recorded by
    # the exchange's own spans, tag checkpoint_commit/*) + the rank-0
    # write; spans observe, never gate — the barrier sequence is identical
    # with tracing off
    with tracing.span("checkpoint/commit", cat="checkpoint", step=step,
                      rank=exchange.rank):
        exchange.barrier(f"checkpoint_commit/{step}/ready")
        path = None
        if exchange.rank == 0:
            with tracing.span("checkpoint/write", cat="checkpoint",
                              step=step, rank=exchange.rank):
                path = checkpointer.save(step, arrays, meta)
        exchange.barrier(f"checkpoint_commit/{step}/published")
        return path


# -- GAME model (de)serialization to flat array dicts -------------------------


def game_model_to_arrays(model: GameModel) -> tuple[dict[str, np.ndarray], dict]:
    """Flatten a GameModel into (arrays, structure-metadata) for checkpointing."""
    arrays: dict[str, np.ndarray] = {}
    coords_meta: dict[str, dict] = {}
    for cid, sub in model.models.items():
        if isinstance(sub, FixedEffectModel):
            arrays[f"{cid}/means"] = np.asarray(sub.glm.coefficients.means)
            if sub.glm.coefficients.variances is not None:
                arrays[f"{cid}/variances"] = np.asarray(sub.glm.coefficients.variances)
            coords_meta[cid] = {
                "kind": "fixed",
                "feature_shard_id": sub.feature_shard_id,
                "task": sub.glm.task.name,
            }
        elif isinstance(sub, RandomEffectModel):
            arrays[f"{cid}/coefficients"] = np.asarray(sub.coefficients)
            arrays[f"{cid}/entity_keys"] = np.asarray(sub.entity_keys)
            if sub.variances is not None:
                arrays[f"{cid}/variances"] = np.asarray(sub.variances)
            coords_meta[cid] = {
                "kind": "random",
                "random_effect_type": sub.random_effect_type,
                "feature_shard_id": sub.feature_shard_id,
                "task": sub.task.name,
            }
        elif isinstance(sub, MatrixFactorizationModel):
            arrays[f"{cid}/row_factors"] = np.asarray(sub.row_factors)
            arrays[f"{cid}/col_factors"] = np.asarray(sub.col_factors)
            arrays[f"{cid}/row_keys"] = np.asarray(sub.row_keys)
            arrays[f"{cid}/col_keys"] = np.asarray(sub.col_keys)
            coords_meta[cid] = {
                "kind": "matrix_factorization",
                "row_effect_type": sub.row_effect_type,
                "col_effect_type": sub.col_effect_type,
                "task": sub.task.name,
            }
        else:
            raise TypeError(f"Cannot checkpoint sub-model type {type(sub)!r}")
    return arrays, {"coordinates": coords_meta, "order": list(model.models)}


def _with_prefix(arrays: Mapping[str, np.ndarray], prefix: str) -> dict[str, np.ndarray]:
    return {f"{prefix}{k}": v for k, v in arrays.items()}


def _strip_prefix(arrays: Mapping[str, np.ndarray], prefix: str) -> dict[str, np.ndarray]:
    return {k[len(prefix):]: v for k, v in arrays.items() if k.startswith(prefix)}


def pack_cd_state(
    model: GameModel,
    best_model: GameModel | None,
    best_metric: float,
    metric_history: list[dict],
) -> tuple[dict[str, np.ndarray], dict]:
    """Flatten full coordinate-descent progress (current + best model) for save."""
    arrays, model_meta = game_model_to_arrays(model)
    out = _with_prefix(arrays, "model/")
    meta: dict[str, Any] = {
        "model": model_meta,
        "best_metric": None if np.isnan(best_metric) else float(best_metric),
        "metric_history": metric_history,
    }
    if best_model is not None:
        best_arrays, best_meta = game_model_to_arrays(best_model)
        out.update(_with_prefix(best_arrays, "best/"))
        meta["best"] = best_meta
    return out, meta


def unpack_cd_state(
    ckpt: Checkpoint,
) -> tuple[GameModel, GameModel | None, float, list[dict]]:
    """Inverse of :func:`pack_cd_state`."""
    model = game_model_from_arrays(_strip_prefix(ckpt.arrays, "model/"), ckpt.meta["model"])
    best_model = None
    if "best" in ckpt.meta and ckpt.meta["best"] is not None:
        best_model = game_model_from_arrays(
            _strip_prefix(ckpt.arrays, "best/"), ckpt.meta["best"]
        )
    raw = ckpt.meta.get("best_metric")
    best_metric = float("nan") if raw is None else float(raw)
    return model, best_model, best_metric, list(ckpt.meta.get("metric_history", []))


class DivergenceError(RuntimeError):
    """Raised when training state goes non-finite (failure detection).

    The reference relies on Spark lineage recompute and has no divergence
    handling (SURVEY.md §5); here a non-finite coordinate update is caught at
    the CD level so the driver can restore the last good checkpoint instead
    of silently training on NaNs.
    """


def game_model_from_arrays(
    arrays: Mapping[str, np.ndarray], meta: Mapping[str, Any]
) -> GameModel:
    """Inverse of :func:`game_model_to_arrays`."""
    models: dict[str, DatumScoringModel] = {}
    coords_meta = meta["coordinates"]
    for cid in meta["order"]:
        info = coords_meta[cid]
        task = TaskType[info["task"]]
        variances = arrays.get(f"{cid}/variances")
        if info["kind"] == "fixed":
            glm = GeneralizedLinearModel(
                coefficients=Coefficients(
                    means=arrays[f"{cid}/means"], variances=variances
                ),
                task=task,
            )
            models[cid] = FixedEffectModel(
                glm=glm, feature_shard_id=info["feature_shard_id"]
            )
        elif info["kind"] == "random":
            models[cid] = RandomEffectModel(
                coefficients=arrays[f"{cid}/coefficients"],
                entity_keys=arrays[f"{cid}/entity_keys"],
                random_effect_type=info["random_effect_type"],
                feature_shard_id=info["feature_shard_id"],
                task=task,
                variances=variances,
            )
        elif info["kind"] == "matrix_factorization":
            models[cid] = MatrixFactorizationModel(
                row_factors=arrays[f"{cid}/row_factors"],
                col_factors=arrays[f"{cid}/col_factors"],
                row_effect_type=info["row_effect_type"],
                col_effect_type=info["col_effect_type"],
                row_keys=arrays[f"{cid}/row_keys"],
                col_keys=arrays[f"{cid}/col_keys"],
                task=task,
            )
        else:
            raise ValueError(f"Unknown checkpoint coordinate kind {info['kind']!r}")
    return GameModel(models=models)


def latest_trained_model(checkpointer: TrainingCheckpointer) -> "tuple[GameModel, int] | None":
    """(current GameModel, step) from the newest intact checkpoint under
    ``checkpointer`` — the warm-start re-entry hook for incremental
    refresh (algorithm/refresh.py): a daily-refresh driver can resume
    straight from PR 8 training checkpoints without a saved model
    directory. Handles both checkpoint layouts that carry a full model:
    CD-state checkpoints (``pack_cd_state`` — the "model/" prefix) and
    incremental-refresh checkpoints (bare ``game_model_to_arrays``
    layout). Returns None when the directory holds no loadable step;
    raises ValueError for a checkpoint kind that carries no model (e.g. a
    streaming solver-progress checkpoint) — the operator must point at the
    training run's CD checkpoints instead."""
    ckpt = checkpointer.restore()
    if ckpt is None:
        return None
    if ckpt.meta.get("kind") == "incremental_refresh":
        return (
            game_model_from_arrays(ckpt.arrays, ckpt.meta["model"]),
            ckpt.step,
        )
    if "model" in ckpt.meta and any(
        k.startswith("model/") for k in ckpt.arrays
    ):
        model = game_model_from_arrays(
            _strip_prefix(ckpt.arrays, "model/"), ckpt.meta["model"]
        )
        return model, ckpt.step
    raise ValueError(
        f"checkpoint step {ckpt.step} at {checkpointer.directory} carries "
        f"no GAME model (kind={ckpt.meta.get('kind')!r}); point the "
        "refresh at the training run's coordinate-descent checkpoint "
        "directory or pass a saved model directory"
    )


def fingerprint_mismatch(saved: dict | None, expected: dict) -> str | None:
    """None when the fingerprints agree; otherwise a human-readable
    clause NAMING the differing fields with both sides' values — the one
    formatter every fingerprint-guarded restore (SolverCheckpointer,
    train_partitioned) raises with, so the attribution format cannot
    drift between consumers."""
    saved = saved or {}
    if saved == expected:
        return None
    diff = sorted(
        k for k in set(saved) | set(expected)
        if saved.get(k) != expected.get(k)
    )
    return (
        f"differs on {diff}: checkpoint="
        f"{ {k: saved.get(k) for k in diff} }, this run="
        f"{ {k: expected.get(k) for k in diff} }"
    )


# -- streaming solver-state checkpoints ---------------------------------------


@dataclasses.dataclass
class SolverProgress:
    """One restored streaming-solve position.

    lam_index:    index into the SORTED λ grid of the in-flight solve
                  (== len(grid) when the run died after the last λ).
    iteration:    outer solver iteration the state was saved at.
    epochs_total: chunked epochs consumed by COMPLETED λs (never redone).
    epochs_lambda: epochs consumed by the in-flight λ up to the save.
    completed:    [(λ, solve-space coefficients)] for finished λs, in grid
                  order — both the models already trained and the warm
                  start for the λ after them.
    state_arrays: the in-flight solver state's field arrays (None when the
                  save landed exactly on a λ boundary).
    """

    lam_index: int
    iteration: int
    epochs_total: int
    epochs_lambda: int
    completed: list
    state_arrays: dict | None


class SolverCheckpointer:
    """Epoch-granular checkpoints for host-loop streaming solves.

    Persists, through the same atomic temp-dir + ``os.replace`` contract
    as :class:`TrainingCheckpointer` (which it wraps), everything a killed
    ``train_glm_streaming`` run needs to resume without redoing work:
    the full optimizer state struct of the in-flight solve (every field of
    ``optim``'s LBFGS/OWLQN/TRON state dataclasses — history buffers,
    trust-region radius, iteration/reason scalars), the λ-grid position,
    the epoch cursor, and the completed λs' solve-space coefficients.

    A ``fingerprint`` (λ grid, optimizer, dimensions, chunk plan) rides
    every save; a restore under a different fingerprint FAILS FAST with
    the differing fields named instead of silently resuming a
    mismatched solve — the same pin-the-agreement rule the partitioned
    checkpoint applies to its layout exchange.

    Step ids encode (λ index, iteration) monotonically, so
    ``TrainingCheckpointer``'s newest-intact-step restore (with its
    corrupt-step fallback and prune protections) applies unchanged.

    The state's arrays are saved in the SHAPE the solver keeps them in:
    an L-BFGS / OWL-QN history slot is a row of ``[m, d]`` below
    ``optim/lbfgs.SLAB_MIN_DIM`` and whole (8, 128) tiles, ``[m, R, 128]``
    with d padded to ``R * 128``, from it on. A snapshot whose ``s_hist`` /
    ``y_hist`` have the other form's shape for this d (the edge moved
    between versions) is REFUSED by the solver on entry, the field and both
    shapes named (``optim/lbfgs.require_history_form``): like a snapshot
    with other fields, it is never folded or read as if it fitted.
    """

    #: step = lam_index * STRIDE + iteration + 1 — monotone across the
    #: run as long as a single solve stays under STRIDE iterations
    STEP_STRIDE = 1_000_000

    def __init__(self, directory: str | os.PathLike, *, max_to_keep: int = 3,
                 save_every: int = 1):
        #: iteration cadence for mid-solve snapshots: the state is
        #: model-sized (d·(2m+4) floats for LBFGS, d rounded up to whole
        #: 1,024-float tiles in each of the 2m history slots from
        #: optim/lbfgs.SLAB_MIN_DIM on: ~1 GB at d=10⁷ m=10 in float32),
        #: so giant-d runs widen this instead of paying a blocking
        #: np.savez every iteration; λ-boundary snapshots always save
        self.save_every = max(1, int(save_every))
        self._inner = TrainingCheckpointer(directory, max_to_keep=max_to_keep)
        self.directory = self._inner.directory

    def latest_step(self) -> int | None:
        """Duck-compatible with TrainingCheckpointer for
        resilience.recovery.run_with_recovery's has-a-checkpoint test."""
        return self._inner.latest_step()

    def newest_loadable_step(self) -> int | None:
        """Duck-compatible with TrainingCheckpointer for coordinated
        rollback's rank-0 step resolution."""
        return self._inner.newest_loadable_step()

    def save_progress(
        self,
        *,
        fingerprint: dict,
        lam_index: int,
        iteration: int,
        epochs_total: int,
        epochs_lambda: int,
        completed,
        solver_state=None,
    ) -> str:
        """Persist one epoch-boundary snapshot (see class docstring).

        Every snapshot is SELF-CONTAINED — completed λs' coefficients are
        re-written each time even though they no longer change. This is
        deliberate: restore falls back across steps on corruption and
        prune deletes old steps freely, which cross-step references would
        break (a referenced step could be pruned or damaged out from
        under a newer snapshot). The cost is bounded by the grid size and
        amortized by ``save_every`` — widen the cadence at giant d rather
        than sharing state across steps."""
        arrays: dict[str, np.ndarray] = {}
        lams = []
        for i, (lam, w) in enumerate(completed):
            lams.append(float(lam))
            arrays[f"completed/{i:04d}"] = np.asarray(w)
        state_fields: list[str] = []
        if solver_state is not None:
            for f in dataclasses.fields(solver_state):
                state_fields.append(f.name)
                arrays[f"state/{f.name}"] = np.asarray(
                    jax.device_get(getattr(solver_state, f.name))
                )
        meta = {
            "kind": "solver_progress",
            "fingerprint": fingerprint,
            "lam_index": int(lam_index),
            "iteration": int(iteration),
            "epochs_total": int(epochs_total),
            "epochs_lambda": int(epochs_lambda),
            "completed_lambdas": lams,
            "state_fields": state_fields,
        }
        step = int(lam_index) * self.STEP_STRIDE + int(iteration) + 1
        return self._inner.save(step, arrays, meta)

    def restore_progress(self, fingerprint: dict) -> SolverProgress | None:
        """Newest intact snapshot, or None. Raises ValueError (attributed:
        the differing fingerprint fields are named) when the checkpoint
        was written under a different solve configuration."""
        ckpt = self._inner.restore()
        if ckpt is None:
            return None
        if ckpt.meta.get("kind") != "solver_progress":
            raise ValueError(
                f"checkpoint at {self.directory} is not a streaming-solver "
                f"checkpoint (kind={ckpt.meta.get('kind')!r}); use a fresh "
                "checkpoint directory"
            )
        mismatch = fingerprint_mismatch(ckpt.meta.get("fingerprint"),
                                        fingerprint)
        if mismatch is not None:
            raise ValueError(
                f"streaming checkpoint at {self.directory} was written "
                f"under a different solve fingerprint ({mismatch}); resume "
                "with the original λ grid/optimizer/input, or use a fresh "
                "checkpoint directory"
            )
        completed = [
            (float(lam), ckpt.arrays[f"completed/{i:04d}"])
            for i, lam in enumerate(ckpt.meta.get("completed_lambdas", []))
        ]
        state_fields = ckpt.meta.get("state_fields") or []
        state_arrays = (
            {name: ckpt.arrays[f"state/{name}"] for name in state_fields}
            if state_fields else None
        )
        return SolverProgress(
            lam_index=int(ckpt.meta["lam_index"]),
            iteration=int(ckpt.meta["iteration"]),
            epochs_total=int(ckpt.meta.get("epochs_total", 0)),
            epochs_lambda=int(ckpt.meta.get("epochs_lambda", 0)),
            completed=completed,
            state_arrays=state_arrays,
        )

    def solver_state(self, cls, state_arrays: dict):
        """The solver state class ``cls`` rebuilt from a restored snapshot's
        ``state_arrays``. Raises ValueError naming the differing fields when
        the snapshot holds another version's state (one written before the
        L-BFGS history was kept in order has ``head``): a field list that
        differs means a layout that differs, and no snapshot is converted
        or reused under another layout."""
        saved = set(state_arrays)
        expected = {f.name for f in dataclasses.fields(cls)}
        if saved != expected:
            raise ValueError(
                f"streaming checkpoint at {self.directory} holds a solver "
                f"state whose fields are not {cls.__name__}'s (only in the "
                f"checkpoint: {sorted(saved - expected)}, only in this "
                f"version: {sorted(expected - saved)}): it was written by "
                "another version of the solver; use a fresh checkpoint "
                "directory"
            )
        return cls(**{k: jnp.asarray(v) for k, v in state_arrays.items()})
