"""Rank-0-only JSONL run journal, atomically finalized into the output dir.

Reference parity: photon-lib util/PhotonLogger.scala:34-90 (spool locally,
publish to the final destination on close) crossed with
PhotonOptimizationLogEvent / OptimizationStatesTracker.scala:82-101 (the
structured per-coordinate optimization telemetry the reference emitted to
external listeners). Here both become one machine-parseable artifact: every
driver/estimator phase appends typed records (phase timings,
convergence rows, config summaries) to a local spool
file, and ``close()`` moves it atomically to ``<dir>/run-journal.jsonl``.

Multi-process discipline (CLAUDE.md): only rank 0 touches shared output
directories, while collectives must still run on EVERY rank — so a journal
constructed on rank > 0 is inert (all methods are no-ops) and callers never
need to branch on rank themselves (which would tempt them to skip
collectives inside ``if journal:`` blocks).

Crash durability (ISSUE 12): with ``durable=True`` (the default) the spool
IS the staged file ``<dir>/<filename>.partial`` and every row is
append-fsync'd, so a SIGKILL'd run leaves a readable journal for
``dev/doctor.py --live`` to tail; ``close()`` still publishes atomically
(``os.replace`` of the stage onto the final name — readers of the final
path never see a torn file). Flushing is observe-only: durable on/off
changes nothing about what callers compute (pinned bitwise on an
instrumented streaming solve, tests/test_doctor.py). Heartbeat rows
(:meth:`RunJournal.heartbeat`) carry a training cursor plus registry
counter DELTAS since the previous heartbeat — the live progress signal a
wedged production run is diagnosed by.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import math
import os
import tempfile
import threading
import time

JOURNAL_FILENAME = "run-journal.jsonl"
#: suffix of the crash-durable stage file a live/killed run is readable at
JOURNAL_PARTIAL_SUFFIX = ".partial"


def _process_index() -> int:
    """Current rank; 0 when jax is absent or uninitialized (single host)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def json_safe(obj):
    """Recursively coerce to strict-JSON values: numpy/jax scalars and
    arrays, enums, dataclasses; NaN/Inf become None (the driver summary
    convention, cli/game_training_driver.py)."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, enum.Enum):
        return obj.name
    if isinstance(obj, dict):
        return {str(k): json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [json_safe(v) for v in obj]
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return json_safe(dataclasses.asdict(obj))
    # numpy / jax scalars and arrays without importing either eagerly
    item = getattr(obj, "item", None)
    shape = getattr(obj, "shape", None)
    if item is not None and shape == ():
        return json_safe(item())
    tolist = getattr(obj, "tolist", None)
    if tolist is not None:
        return json_safe(tolist())
    return str(obj)


#: fields the journal stamps onto every heartbeat row itself — everything
#: ELSE in the row is the caller's progress cursor (dev/doctor.py and
#: verdicts.journal_findings both print "where was the run" from it).
#: ``hbm_bytes`` and ``compiles`` are the ISSUE 13 drift snapshots: live
#: device-buffer bytes and the backend compile count, so ``doctor --live``
#: can show device-memory drift and mid-run compile storms on a wedged run.
_HEARTBEAT_BOOKKEEPING = frozenset(
    {"kind", "seq", "ts", "elapsed_ms", "counter_deltas", "gauges",
     "hbm_bytes", "compiles"}
)


def _live_hbm_bytes() -> "int | None":
    """Live device-buffer bytes for heartbeat rows; None unless a jax
    backend is ALREADY initialized. A heartbeat must never initialize one:
    journal-only processes exist (the SIGKILL chaos subprocess, doctor
    tooling), and on a TPU host the first device call claims the chip,
    which belongs to one process at a time — a bystander that touched it
    would take it from, or hang behind, the run it is observing. Merely
    having jax imported is not enough to know, hence the look at the
    bridge's own flag. Observe-only: training/scoring loops always have a
    live backend by their first heartbeat, so the field is only absent
    where probing would have been wrong anyway."""
    import sys

    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not xb.backends_are_initialized():
        return None
    from photon_ml_tpu.telemetry.probes import live_buffer_bytes

    return live_buffer_bytes()


def heartbeat_cursor(row: dict) -> dict:
    """The caller-supplied progress cursor of one ``heartbeat`` journal row
    (stage, sweep/epoch/λ indices, ...) with the journal's own bookkeeping
    fields stripped."""
    return {k: v for k, v in row.items() if k not in _HEARTBEAT_BOOKKEEPING}


class RunJournal:
    """``with RunJournal(out_dir) as j: j.record("phase_timing", ...)``.

    Records are dicts with a ``kind`` plus caller fields; ``seq``, ``ts``
    (absolute wall clock) and ``elapsed_ms`` (monotonic since journal
    open — robust to host clock steps, correlates with trace spans) are
    stamped automatically. Inactive (rank > 0, or ``directory=None``)
    journals accept every call and write nothing.
    """

    def __init__(
        self,
        directory: str | os.PathLike | None,
        *,
        filename: str = JOURNAL_FILENAME,
        rank: int | None = None,
        durable: bool = True,
    ):
        self.directory = None if directory is None else str(directory)
        self.filename = filename
        self.rank = _process_index() if rank is None else int(rank)
        self.durable = bool(durable)
        self._seq = 0
        self._spool = None
        self._closed = False
        #: journals now have legitimate second writer threads (the serve
        #: driver's swap poller, the micro-batch consumer's ledger rows):
        #: the seq stamp + buffered write/flush/fsync must be one atomic
        #: unit or concurrent rows tear mid-file (read_journal only
        #: forgives a torn FINAL line)
        self._lock = threading.Lock()
        self._hb_counters: dict[str, int] = {}
        # monotonic anchor: rows carry elapsed_ms since journal open so
        # they order correctly across host clock steps and correlate with
        # trace spans (telemetry/tracing.py durations are perf_counter too)
        self._t0 = time.perf_counter()
        if self.active:
            if self.durable:
                # the spool IS the stage file, in the destination directory
                # (os.replace is atomic only within one filesystem): every
                # row is append-fsync'd below, so a killed run's journal is
                # readable at <dir>/<filename>.partial before publish
                os.makedirs(self.directory, exist_ok=True)
                self._spool = open(self.partial_path, "w")
            else:
                self._spool = tempfile.NamedTemporaryFile(
                    mode="w", suffix=".jsonl", prefix="photon-journal-",
                    delete=False,
                )
            self.record("journal_open", pid=os.getpid(), rank=self.rank)

    @property
    def active(self) -> bool:
        return self.directory is not None and self.rank == 0 and not self._closed

    @property
    def path(self) -> str | None:
        """Final journal path (exists only after ``close()``)."""
        if self.directory is None:
            return None
        return os.path.join(self.directory, self.filename)

    @property
    def partial_path(self) -> str | None:
        """The crash-durable stage file a live (or killed) durable run is
        readable at — what ``dev/doctor.py --live`` tails."""
        if self.directory is None:
            return None
        return os.path.join(
            self.directory, self.filename + JOURNAL_PARTIAL_SUFFIX
        )

    def record(self, kind: str, **fields) -> None:
        if not self.active:
            return
        payload = json_safe(fields)
        with self._lock:
            if not self.active:  # closed while we serialized
                return
            row = {
                "kind": kind,
                "seq": self._seq,
                # ts is the ONE sanctioned absolute wall-clock stamp (lint
                # check 11 allowlist); durations/ordering ride elapsed_ms
                "ts": time.time(),
                "elapsed_ms": round(
                    (time.perf_counter() - self._t0) * 1e3, 3
                ),
            }
            row.update(payload)
            self._seq += 1
            self._spool.write(json.dumps(row, allow_nan=False) + "\n")
            self._spool.flush()
            if self.durable:
                # append-fsync per row: a SIGKILL between rows loses at
                # most the row being written, never the file (journals are
                # low-rate — tens of rows plus heartbeats per run)
                os.fsync(self._spool.fileno())

    def record_timings(self, timings: dict[str, dict[str, float]]) -> None:
        """One ``phase_timing`` row per named phase — the shape
        ``util.timed.timing_summary()`` returns."""
        for name, summary in timings.items():
            self.record("phase_timing", name=name, **summary)

    def record_metrics(self, snapshot: dict) -> None:
        """Persist a full ``MetricsRegistry.snapshot()``."""
        self.record("metrics", snapshot=snapshot)

    def record_gauge(self, name: str, value) -> None:
        self.record("gauge", name=name, value=value)

    def heartbeat(self, *, registry=None, **cursor) -> None:
        """One periodic liveness row: the caller's progress cursor (sweep/
        epoch/λ index, dataset id, ...) plus the registry's counter DELTAS
        since the previous heartbeat (what moved, not the whole snapshot)
        and its current gauges. ``dev/doctor.py --live`` reads the last of
        these to say where a wedged run actually is. Observe-only: emitted
        from observers/loop tails, never gating any training work."""
        if not self.active:
            return
        fields = dict(cursor)
        hbm = _live_hbm_bytes()
        if hbm is not None:
            fields["hbm_bytes"] = hbm
        if registry is not None:
            snap = registry.snapshot()
            counters = {
                str(k): int(v) for k, v in (snap.get("counters") or {}).items()
            }
            # absolute compile-count snapshot (the delta alone cannot show
            # a storm's trajectory across heartbeats)
            from photon_ml_tpu.telemetry.probes import COMPILE_COUNT_METRIC

            if COMPILE_COUNT_METRIC in counters:
                fields["compiles"] = counters[COMPILE_COUNT_METRIC]
            deltas = {
                k: v - self._hb_counters.get(k, 0)
                for k, v in counters.items()
                if v != self._hb_counters.get(k, 0)
            }
            self._hb_counters = counters
            if deltas:
                fields["counter_deltas"] = deltas
            gauges = {
                k: v for k, v in (snap.get("gauges") or {}).items()
                if v is not None
            }
            if gauges:
                fields["gauges"] = gauges
        self.record("heartbeat", **fields)

    def close(self) -> None:
        """Atomically publish the spool as ``<directory>/<filename>``."""
        if self._closed or self._spool is None:
            self._closed = True
            return
        self.record("journal_close", records=self._seq)
        with self._lock:
            # a concurrent writer thread (swap poller) blocked on the lock
            # re-checks `active` after acquiring it, so nothing writes to
            # the spool once it is closed here
            self._closed = True
            self._spool.flush()
            os.fsync(self._spool.fileno())
            self._spool.close()
        if self.durable:
            # the spool IS the stage file in the destination directory:
            # publish is one atomic rename
            os.replace(self._spool.name, self.path)
            return
        os.makedirs(self.directory, exist_ok=True)
        # stage into the destination directory first: os.replace is atomic
        # only within one filesystem, and the spool lives in the system tmp
        fd, staged = tempfile.mkstemp(
            dir=self.directory, prefix=".journal-", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as dst, open(self._spool.name, "rb") as src:
                dst.write(src.read())
            os.replace(staged, self.path)
        except BaseException:
            if os.path.exists(staged):
                os.unlink(staged)
            raise
        finally:
            os.unlink(self._spool.name)

    def __enter__(self) -> "RunJournal":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    @staticmethod
    def read(path: str | os.PathLike) -> list[dict]:
        """Parse a finalized journal back into a list of record dicts."""
        return read_journal(path, tolerant=False)


def read_journal(path: str | os.PathLike, *, tolerant: bool = False) -> list[dict]:
    """Parse a JSONL journal. ``tolerant=True`` skips unparseable lines —
    the shape of a crash-durable ``.partial`` stage whose final row was cut
    mid-write by a SIGKILL (every earlier row is fsync'd whole)."""
    records: list[dict] = []
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                if not tolerant:
                    raise
    return records
