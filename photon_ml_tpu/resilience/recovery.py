"""Driver-level crash-safe recovery: restore-and-resume instead of abort.

No reference analogue as code: the reference driver aborts on any
exception and relies on Spark lineage + coarse per-configuration model
re-use for recovery (GameTrainingDriver.scala:748-815 saves models per
optimization config; there is no mid-sweep resume). Here the training
sweep owns real mid-training checkpoints (io/checkpoint.py), so a
mid-sweep failure that is either

- a :class:`~photon_ml_tpu.io.checkpoint.DivergenceError` (non-finite
  coordinate update) with an intact checkpoint to fall back to, or
- a classified-transient error (lost device, flaky filesystem —
  resilience/errors.classify_exception)

restarts the attempt instead of aborting: the re-created estimator
resumes from the latest intact checkpoint (run_coordinate_descent's
fast-forward) and the run continues. Restarts are capped by
``max_restarts``; exhaustion re-raises after counting a
``resilience/giveups``. Every restart counts on ``resilience/retries``
and journals a ``resilience_restart`` row; the checkpoint restore itself
counts on ``resilience/checkpoint_restores`` (incremented at the restore
site in algorithm/coordinate_descent.py).

MULTI-RANK runs attach a ``resilience.coordinated.CoordinatedRecovery``
(ISSUE 15): ``ExchangeTimeout`` and ``PeerAbort`` — always fatal on
their own — become recoverable VIA COORDINATION, every restart is an
all-rank rollback to the last barrier-committed checkpoint, and the
restart budget is the coordinator's SHARED generation count (a flapping
rank burns the JOB's budget, never a per-process one). The give-up
``run_failure`` row then names the originating rank + cause, so the
blamed rank is attributed identically from every rank's journal.
"""

from __future__ import annotations

import logging
from typing import Callable

from photon_ml_tpu.resilience.errors import (
    ExchangeTimeout,
    PeerAbort,
    Transience,
    classify_exception,
    fatal_hint,
    is_preemption,
)
from photon_ml_tpu.telemetry import resilience_counters

logger = logging.getLogger(__name__)


def run_with_recovery(
    fn: Callable[[int], object],
    *,
    max_restarts: int = 2,
    checkpointer=None,
    classify: Callable = classify_exception,
    journal=None,
    description: str = "training",
    coordinator=None,
):
    """Run ``fn(restart_index)`` with capped restore-and-resume restarts.

    fn: one full attempt; receives the 0-based restart index (the driver
        uses it to force ``resume=True`` on restarts even when the user
        passed ``--no-resume`` for the first attempt).
    checkpointer: optional ``io.checkpoint.TrainingCheckpointer``. A
        DivergenceError is only recoverable when a checkpoint step exists
        to restore (re-running a deterministic divergence from scratch
        would fail identically); transient errors restart either way.
    journal: optional ``telemetry.RunJournal`` for ``resilience_restart``
        rows.
    coordinator: optional ``resilience.coordinated.CoordinatedRecovery``
        — multi-rank mode. The coordinator's ``max_restarts`` (the SHARED
        job budget: the restart generation every rank agrees on) replaces
        the per-process ``max_restarts`` argument; ``ExchangeTimeout``
        and ``PeerAbort`` become recoverable; every restart first posts
        an abort marker for this rank's own failures (so peers fail fast
        attributed), then rendezvouses all ranks on the coordinated
        rollback. Detached (None) keeps the pre-existing single-process
        contract bit-for-bit.
    """
    from photon_ml_tpu.io.checkpoint import DivergenceError

    if coordinator is not None:
        max_restarts = coordinator.max_restarts
    restart = 0
    while True:
        try:
            return fn(restart)
        except Exception as e:  # classified below; broad by design
            transient = classify(e) is Transience.TRANSIENT
            has_checkpoint = (
                checkpointer is not None
                and checkpointer.latest_step() is not None
            )
            divergent = isinstance(e, DivergenceError)
            coordination_only = coordinator is not None and isinstance(
                e, (ExchangeTimeout, PeerAbort)
            )
            recoverable = (
                transient
                or (divergent and has_checkpoint)
                or coordination_only
            )
            # origin attribution rides the journal even on paths that never
            # reach the coordinator (e.g. a PeerAbort with no coordinator
            # attached, which stays fatal): the blamed rank must read the
            # same from every journal
            origin_rank = getattr(e, "origin_rank", None)
            origin_cause = getattr(e, "cause", None) if isinstance(
                e, PeerAbort
            ) else None
            decision = None
            if recoverable and coordinator is not None:
                # this rank's OWN failure: attribute it to the peers
                # before restarting (turns their deadline waits into
                # immediate PeerAborts naming this rank). Coordination
                # failures (PeerAbort/ExchangeTimeout) are someone
                # else's — never re-abort on them.
                if not isinstance(e, (PeerAbort, ExchangeTimeout)):
                    coordinator.post_abort(e)
                try:
                    decision = coordinator.coordinated_restart(e)
                except Exception as rendezvous_error:
                    # the rendezvous itself failed (a rank is truly gone,
                    # not restarting): the job dies attributed to the
                    # rendezvous failure, with the original error noted
                    if journal is not None:
                        journal.record(
                            "run_failure",
                            description=description,
                            error=repr(rendezvous_error),
                            original_error=repr(e),
                            transient=False,
                            divergent=divergent,
                            preemption=False,
                            restarts_used=restart,
                            max_restarts=max_restarts,
                            origin_rank=getattr(
                                rendezvous_error, "origin_rank", None
                            ),
                            origin_cause=None,
                        )
                    resilience_counters.record_giveup()
                    logger.error(
                        "%s: coordinated restart rendezvous failed (%r) "
                        "after %r; giving up",
                        description, rendezvous_error, e,
                    )
                    raise
                origin_rank = decision.origin_rank
                origin_cause = decision.origin_cause
            exhausted = (
                decision.exhausted if decision is not None
                else restart >= max_restarts
            )
            if not recoverable or exhausted:
                if journal is not None:
                    # the run's terminal failure row (ISSUE 12): what
                    # dev/doctor.py names when a crashed run's journal —
                    # finalized by the driver's failure path, or the
                    # crash-durable stage of one that never closed — is
                    # read back. With a coordinator the originating rank +
                    # cause ride along (ISSUE 15), so the blamed rank is
                    # attributed identically from every rank's journal.
                    journal.record(
                        "run_failure",
                        description=description,
                        error=repr(e),
                        transient=transient,
                        divergent=divergent,
                        preemption=is_preemption(e),
                        restarts_used=(
                            decision.restarts_used if decision is not None
                            else restart
                        ),
                        max_restarts=max_restarts,
                        origin_rank=origin_rank,
                        origin_cause=origin_cause,
                    )
                if recoverable:
                    resilience_counters.record_giveup()
                    logger.error(
                        "%s: restart budget (%d) exhausted; giving up on %r",
                        description, max_restarts, e,
                    )
                elif divergent and not has_checkpoint:
                    logger.error(
                        "%s: diverged with no checkpoint to restore "
                        "(enable --checkpoint-dir for mid-sweep recovery): %r",
                        description, e,
                    )
                else:
                    hint = fatal_hint(e)
                    if hint is not None:
                        logger.error("%s: fatal failure %r. Hint: %s",
                                     description, e, hint)
                raise
            restart = (
                decision.generation if decision is not None else restart + 1
            )
            resilience_counters.record_retry()
            # a device-loss / pool-preemption shape gets its own tally:
            # the counter that says the POOL (not flaky I/O) is exercising
            # the checkpoint cadence
            preempted = is_preemption(e)
            if preempted:
                resilience_counters.record_preemption()
            logger.warning(
                "%s: %s failure (%r) — restart %d/%d%s",
                description,
                (
                    "transient" if transient
                    else "coordination" if coordination_only
                    else "divergence"
                ),
                e,
                restart,
                max_restarts,
                (
                    f", resuming from checkpoint step "
                    f"{checkpointer.latest_step()}"
                    if has_checkpoint
                    else ", retrying from scratch"
                ),
            )
            if journal is not None:
                journal.record(
                    "resilience_restart",
                    description=description,
                    restart=restart,
                    max_restarts=max_restarts,
                    transient=transient,
                    divergent=divergent,
                    preemption=preempted,
                    resumed_from_step=(
                        decision.step if decision is not None
                        else checkpointer.latest_step()
                        if has_checkpoint else None
                    ),
                    origin_rank=origin_rank,
                    origin_cause=origin_cause,
                    error=repr(e),
                )
