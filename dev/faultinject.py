#!/usr/bin/env python
"""Fault-injection harness for the resilience layer's chaos suite.

Context-manager/callable injectors that manufacture the failure classes
ISSUE 3 names — each maps to a recovery path the chaos tests
(tests/test_resilience.py) drive end to end on the virtual CPU mesh:

- :func:`flaky` / :class:`FlakyCallable` — fails N times then succeeds
  (the transient-I/O shape; exercises RetryPolicy).
- :func:`truncate_avro_block` / :func:`corrupt_avro_block` /
  :func:`break_avro_sync` — in-place container damage (exercises the
  quarantine readers in io/avro.py).
- :func:`crash_before_replace` — raises between the checkpoint's temp-dir
  write and its ``os.replace`` publish (exercises save atomicity).
- :func:`corrupt_checkpoint_step` — truncates a saved ``step_*`` dir's
  files (exercises restore's newest-intact-step fallback).
- :class:`WithholdingExchange` — a MetadataExchange wrapper whose rank
  never publishes selected tags (exercises ExchangeTimeout attribution).
- :func:`die_at_barrier` / :class:`BarrierKiller` — a rank-targeted kill:
  withhold the matching exchange op, then raise a classified-transient
  preemption in THAT rank only (exercises peer-abort attribution +
  coordinated rollback; ISSUE 15). ``times=None`` makes the rank FLAP
  (dies every attempt — exercises shared-budget exhaustion).
- :func:`abort_marker_corruptor` — garbles every abort marker a rank
  posts (exercises the unattributed-but-bounded PeerAbort path).
- :func:`poison_coordinate_updates` — NaN-poisons the first K model
  updates of one coordinate class (exercises DivergenceError +
  checkpoint-restore recovery).
- :func:`crash_after_chunks` — kills the run mid-streaming-epoch after N
  accumulated chunk decodes (exercises SolverCheckpointer resume through
  run_with_recovery; ISSUE 8).
- :func:`preempt_after_calls` / :func:`device_loss_error` — a simulated
  pool preemption: a classified-transient device-loss error after N
  jitted steps of any method (exercises preemption classification +
  exchange-consistent partitioned checkpoint resume; ISSUE 8).

Dev-tooling, not shipped API: lives next to dev/lint_parity.py and is
imported only by tests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable


class InjectedCrash(RuntimeError):
    """The harness's stand-in for a hard process death at a chosen point."""


@dataclasses.dataclass
class FlakyCallable:
    """Calls ``fn`` but raises ``exc_factory()`` for the first
    ``failures`` invocations — the flaky-then-succeeding callable."""

    fn: Callable
    failures: int
    exc_factory: Callable[[], BaseException] = ConnectionError
    calls: int = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc_factory()
        return self.fn(*args, **kwargs)


def flaky(failures: int, exc_factory=ConnectionError, result=None):
    """A FlakyCallable returning ``result`` once the failures run out."""
    return FlakyCallable(fn=lambda: result, failures=failures,
                         exc_factory=exc_factory)


# ---------------------------------------------------------------------------
# Avro container damage (in place, on a copy the test owns)
# ---------------------------------------------------------------------------


def _block_span(path: str | os.PathLike, block: int) -> tuple[int, int, int]:
    """(payload_offset, payload_size, record_count) of block ``block``."""
    from photon_ml_tpu.io.avro import scan_block_index

    index = scan_block_index(path)
    n_records, size, offset = index[block]
    return offset, size, n_records


def truncate_avro_block(path: str | os.PathLike, block: int = -1) -> None:
    """Cut the file mid-way through ``block``'s payload (default: last
    block) — the torn-write / partial-copy shape."""
    from photon_ml_tpu.io.avro import scan_block_index

    index = scan_block_index(path)
    offset, size, _ = _block_span(path, block % len(index))
    with open(path, "r+b") as f:
        f.truncate(offset + max(size // 2, 1))


def corrupt_avro_block(path: str | os.PathLike, block: int = 0,
                       nbytes: int = 8) -> None:
    """Overwrite the first ``nbytes`` of ``block``'s payload with 0xFF —
    bit-rot inside an intact frame (framing/sync stay valid)."""
    offset, size, _ = _block_span(path, block)
    with open(path, "r+b") as f:
        f.seek(offset)
        f.write(b"\xff" * min(nbytes, size))


def break_avro_sync(path: str | os.PathLike, block: int = 0) -> None:
    """Destroy the 16-byte sync marker TRAILING ``block`` — the following
    block becomes unreachable (resync skips to the next intact marker)."""
    offset, size, _ = _block_span(path, block)
    with open(path, "r+b") as f:
        f.seek(offset + size)
        f.write(b"\xaa" * 16)


# ---------------------------------------------------------------------------
# Checkpoint damage
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def crash_before_replace():
    """Patch ``os.replace`` to raise InjectedCrash — the save dies after
    its temp-dir write, before the atomic publish (the window the
    checkpointer's atomicity contract covers). Module-global patch;
    restore is guaranteed on exit."""
    real = os.replace

    def boom(*args, **kwargs):
        raise InjectedCrash(
            "injected crash between temp-dir write and os.replace"
        )

    os.replace = boom
    try:
        yield
    finally:
        os.replace = real


def corrupt_checkpoint_step(directory: str | os.PathLike, step: int,
                            target: str = "arrays.npz") -> None:
    """Truncate ``step_<k>/<target>`` to half — external damage to a
    PUBLISHED checkpoint (the atomic save never produces this; a torn
    disk/copy does)."""
    path = os.path.join(str(directory), f"step_{step:08d}", target)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(size // 2, 1))


# ---------------------------------------------------------------------------
# Simulated preemption / mid-epoch crash (ISSUE 8)
# ---------------------------------------------------------------------------


def device_loss_error() -> RuntimeError:
    """The device-loss shape a preemptible pool produces: jaxlib surfaces
    it as a RuntimeError (XlaRuntimeError) whose TYPE carries no signal —
    only the message does. Classified TRANSIENT (restart-worthy) and
    ``resilience.errors.is_preemption``-positive."""
    return RuntimeError(
        "INTERNAL: TPU device lost: worker preempted by the pool "
        "scheduler; Socket closed"
    )


@contextlib.contextmanager
def crash_after_chunks(n: int, exc_factory=device_loss_error):
    """Kill the streaming pipeline after its ``n``-th successful chunk
    decode — the mid-epoch crash of a preemptible run. Patches
    ``ChunkPrefetcher._load_timed`` (below the retry policy, so the error
    surfaces undamped); fires ONCE, so a restarted attempt heals — the
    resume-skips-completed-work assertion is then meaningful. Yields the
    counter dict (tests assert ``fired`` to prove the crash happened)."""
    from photon_ml_tpu.io.stream_reader import ChunkPrefetcher

    real = ChunkPrefetcher._load_timed
    state = {"loads": 0, "fired": False}

    def wrapped(self, spec):
        state["loads"] += 1
        if not state["fired"] and state["loads"] > n:
            state["fired"] = True
            raise exc_factory()
        return real(self, spec)

    ChunkPrefetcher._load_timed = wrapped
    try:
        yield state
    finally:
        ChunkPrefetcher._load_timed = real


@contextlib.contextmanager
def preempt_after_calls(obj, method: str, n: int,
                        exc_factory=device_loss_error):
    """Simulated pool preemption: patch ``obj.method`` (a class or an
    instance — e.g. ``GameTrainProgram.step``, the fused sweep's jitted
    step) to raise a classified-transient device-loss error after ``n``
    successful calls. Fires ONCE (the preempted worker comes back), so a
    recovery restart completes. Yields the counter dict."""
    real = getattr(obj, method)
    state = {"calls": 0, "fired": False}

    def wrapped(*args, **kwargs):
        state["calls"] += 1
        if not state["fired"] and state["calls"] > n:
            state["fired"] = True
            raise exc_factory()
        return real(*args, **kwargs)

    setattr(obj, method, wrapped)
    try:
        yield state
    finally:
        setattr(obj, method, real)


# ---------------------------------------------------------------------------
# Exchange withholding
# ---------------------------------------------------------------------------


class WithholdingExchange:
    """Wraps a MetadataExchange; this rank never publishes (never calls)
    exchanges whose tag contains any of ``withhold`` — simulating a rank
    that crashed or skipped a collective. The OTHER ranks' deadline then
    fires a rank-attributed ExchangeTimeout naming this rank."""

    def __init__(self, inner, withhold: tuple[str, ...]):
        self._inner = inner
        self._withhold = tuple(withhold)
        self.rank = inner.rank
        self.num_ranks = inner.num_ranks

    def _withheld(self, tag: str) -> bool:
        return any(w in tag for w in self._withhold)

    def allgather(self, tag: str, payload) -> list:
        if self._withheld(tag):
            raise InjectedCrash(
                f"rank {self.rank} withheld allgather {tag!r}"
            )
        return self._inner.allgather(tag, payload)

    def barrier(self, tag: str) -> None:
        if self._withheld(tag):
            raise InjectedCrash(
                f"rank {self.rank} withheld barrier {tag!r}"
            )
        return self._inner.barrier(tag)


# ---------------------------------------------------------------------------
# Rank-targeted kills + abort-marker damage (ISSUE 15 coordinated recovery)
# ---------------------------------------------------------------------------


class BarrierKiller:
    """Wraps a MetadataExchange: when THIS wrapper's rank is the targeted
    rank and an exchange op's tag contains ``tag``, the op is WITHHELD
    (never reaches the transport — the rank's key/barrier arrival simply
    never happens) and ``exc_factory()`` is raised in that rank — the
    withhold-then-raise-preemption shape of a pool reclaiming one worker
    mid-protocol. Other ranks and other tags pass through untouched.

    ``times=1`` (default) fires once, so the coordinated rollback's next
    attempt heals — the resume-bitwise assertion is then meaningful.
    ``times=None`` makes the rank FLAP (dies at the same tag every
    attempt) — the shared-restart-budget exhaustion fixture.

    The coordinator-facing surface (``set_generation`` / ``post_abort`` /
    ``pending_abort`` / ``generation``) passes through, so a killed rank's
    ``run_with_recovery(coordinator=...)`` path works unmodified.
    """

    def __init__(self, inner, tag: str, rank: int, *, times: "int | None" = 1,
                 exc_factory: Callable[[], BaseException] = None):
        self._inner = inner
        self._tag = str(tag)
        self._target = int(rank)
        self._times = times
        self._exc_factory = exc_factory or device_loss_error
        self.state = {"fired": 0}

    @property
    def rank(self) -> int:
        return self._inner.rank

    @property
    def num_ranks(self) -> int:
        return self._inner.num_ranks

    @property
    def generation(self):
        return self._inner.generation

    def set_generation(self, generation: int) -> None:
        self._inner.set_generation(generation)

    def post_abort(self, info) -> None:
        self._inner.post_abort(info)

    def pending_abort(self):
        return self._inner.pending_abort()

    def _maybe_die(self, tag: str) -> None:
        if (
            self._inner.rank == self._target
            and self._tag in tag
            and (self._times is None or self.state["fired"] < self._times)
        ):
            self.state["fired"] += 1
            raise self._exc_factory()

    def allgather(self, tag: str, payload) -> list:
        self._maybe_die(tag)
        return self._inner.allgather(tag, payload)

    def barrier(self, tag: str) -> None:
        self._maybe_die(tag)
        return self._inner.barrier(tag)


def die_at_barrier(exchange, tag: str, rank: int, *,
                   times: "int | None" = 1,
                   exc_factory=None) -> BarrierKiller:
    """Kill ``rank`` at its next exchange op whose tag contains ``tag``:
    the op is withheld and a classified-transient preemption raised in
    that rank only (see :class:`BarrierKiller`). Pass ``times=None`` for
    a flapping rank."""
    return BarrierKiller(exchange, tag, rank, times=times,
                         exc_factory=exc_factory)


@contextlib.contextmanager
def abort_marker_corruptor(exchange):
    """Patch ``exchange.post_abort`` so every marker this rank writes is
    garbled bytes-of-a-string instead of the attributed dict — the torn-
    write shape. Peers must STILL fail bounded and typed (a PeerAbort
    with ``origin_rank=None`` naming the unparseable marker), never hang
    out the deadline. Yields a counter dict (``posted``)."""
    real = exchange.post_abort
    state = {"posted": 0}

    def corrupted(info):
        state["posted"] += 1
        real("\xff\x00 corrupt abort marker (injected)")

    exchange.post_abort = corrupted
    try:
        yield state
    finally:
        exchange.post_abort = real


# ---------------------------------------------------------------------------
# NaN poisoning
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def poison_coordinate_updates(coordinate_cls, times: int = 1):
    """Patch ``coordinate_cls.update_model`` so its first ``times`` calls
    return a NaN-poisoned model — a diverged-lane stand-in the CD loop's
    finite check must catch as DivergenceError. Subsequent calls behave
    normally (so a checkpoint-restore retry succeeds)."""
    import numpy as np

    real = coordinate_cls.update_model
    state = {"remaining": int(times)}

    def poisoned(self, model, partial_scores):
        out_model, info = real(self, model, partial_scores)
        if state["remaining"] > 0:
            state["remaining"] -= 1
            poisoned_model = _nan_poison_model(out_model, np)
            return poisoned_model, info
        return out_model, info

    coordinate_cls.update_model = poisoned
    try:
        yield state
    finally:
        coordinate_cls.update_model = real


def _nan_poison_model(model, np):
    """A copy of ``model`` with its leading coefficient array set to NaN
    (enough for the coordinate's re-score to go non-finite)."""
    import dataclasses as dc

    from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel

    if isinstance(model, FixedEffectModel):
        coeffs = model.glm.coefficients
        means = np.full_like(np.asarray(coeffs.means), np.nan)
        return dc.replace(
            model,
            glm=dc.replace(
                model.glm, coefficients=dc.replace(coeffs, means=means)
            ),
        )
    if isinstance(model, RandomEffectModel):
        poisoned = np.full_like(np.asarray(model.coefficients), np.nan)
        return dc.replace(model, coefficients=poisoned)
    raise TypeError(f"cannot NaN-poison model type {type(model)!r}")
