"""Cross-rank run tracing: host-side spans, Chrome-trace export, straggler
attribution.

Reference parity: photon-lib util/Timed.scala:21-34 (wall-clock phase
blocks) crossed with util/PhotonLogger.scala:34-90 (spool locally, publish
atomically) — extended past the reference: the reference's timings are
driver-local aggregates, while a composed multi-rank run here needs to know
*where the wall-clock went* (decode vs exchange wait vs device dispatch vs
checkpoint barrier) and *which rank* is the straggler. This module provides:

- ``span(name, **attrs)`` — THE seam: the only way the program marks
  time. One call feeds two sinks. (1) The profiler's trace: while a
  ``jax.profiler`` session is active (``--profile-dir``, or a benchmark
  that started one) the span is a ``TraceAnnotation`` named
  ``photon:<name>`` carrying ``attrs`` as the event's stats, so it lands in
  the xplane's ``/host:CPU`` plane beside the device's ``XLA Ops``, on ONE
  clock, whoever started the profiler. (2) The ring: with a ``Tracer``
  installed (``--trace-dir``) the span is also recorded as (name, category,
  start, duration, attrs, parent) into a per-thread ring buffer over
  ``time.perf_counter``. A per-thread stack of open spans gives every ring
  event its ``parent`` (the enclosing span's name and start) and the
  identifiers it inherits (``fit``, ``sweep``). Off (no tracer, no session)
  it returns a shared null object: one global read and one call of the
  profiler's own ``is_enabled`` test — no lock, nothing allocated that is
  kept (cost per call of the three states: PERF.md 3). Spans OBSERVE, never
  gate: instrumentation wraps existing calls with a timer and must never
  add, skip, reorder, or retry a collective (the PR 3 rule — one rank
  retrying an exchange desyncs SPMD). Attribute values reach the profiler
  as ``key=value`` text: keep ``#``, ``,`` and ``=`` out of them.
- Chrome-trace/Perfetto export: ``publish_trace`` writes
  ``trace-{rank:05d}.json`` (catapult event format: complete ``"X"``
  events, ``pid`` = rank, ``tid`` = thread) atomically into the trace dir
  under the multi-process rules — rank 0 mkdir, barrier, per-rank write
  (the ``io/score_writer.py`` carve-out). ``args`` carries the attrs plus
  ``parent`` / ``parent_ts``. On the FAILURE path the barrier is
  deadline-bounded and a timeout falls back to an unbarriered write, and
  the spans still OPEN are written as begin (``"B"``) events, so a run that
  dies inside ``train/shard/buckets`` shows it.
- Straggler attribution: every exchange op (``parallel/multihost.py``)
  records its blocking wait as a span carrying ``tag`` + ``rank``;
  ``exchange_wait_tables`` aggregates per-rank per-tag wait totals and
  ``straggler_report`` names, for every tag, the rank that arrived LAST
  (least wait — everyone else's wait is caused by it) or never arrived at
  all (a wedged/crashed rank: the other ranks' bounded deadlines fire, and
  the report names the missing rank from their recorded waits alone).
  ``gather_straggler_report`` merges the per-rank tables on every rank
  through the existing ``MetadataExchange`` at run end.

Which clock to read: inside a profiler session a span's ``photon:`` event
shares the device's clock, so span time may be read against device time
there (the device's idle stretches by program span, host time not spent
waiting for the device: benchmark/program_trace.py). The ring's times are
host ``perf_counter`` differences on a clock of its own — the device-free
per-rank timeline; hold them against other ring spans, not against the
xplane.
"""

from __future__ import annotations

import json
import logging
import os
import re
import tempfile
import threading
import time
from typing import Iterator, Mapping, NamedTuple

from jax.profiler import TraceAnnotation as _Annotation

logger = logging.getLogger(__name__)

#: a span's name in the profiler's trace is this + its name: what the
#: benchmark's reader selects the program's spans by
ANNOTATION_PREFIX = "photon:"
#: attrs a span hands down to every span opened inside it (ring events and
#: the Chrome export carry them): which fit, which sweep
INHERITED_IDS = ("fit", "sweep")

TRACE_FILE_FORMAT = "trace-{rank:05d}.json"

#: category carried by top-level exchange wait spans (allgather/barrier) —
#: the ONLY spans the straggler wait tables aggregate
EXCHANGE_CAT = "exchange"
#: category for point-to-point KV transport sub-operations (kv_get/kv_set):
#: visible in the timeline, excluded from the wait tables (their parent
#: allgather span already carries the full wait)
EXCHANGE_IO_CAT = "exchange_io"

#: span names aggregated into the per-tag exchange wait tables
_WAIT_SPAN_NAMES = frozenset({"exchange/allgather", "exchange/barrier"})

#: per-thread ring capacity (events); oldest events are overwritten —
#: bounded memory no matter how long a run traces
DEFAULT_CAPACITY = 65536


def _process_index() -> int:
    """Current rank; 0 when jax is absent or uninitialized (single host) —
    the journal's rank rule (telemetry/journal.py)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


class TraceEvent(NamedTuple):
    name: str
    cat: str
    start: float  # seconds since tracer start (perf_counter delta)
    dur: float  # seconds
    thread_id: int
    thread_name: str
    attrs: dict | None
    #: (name, start) of the span this one was opened inside, on its thread
    parent: tuple | None = None


class _Ring:
    """Fixed-capacity single-writer ring: the owning thread appends with no
    lock (plain list-slot assignment under the GIL); readers snapshot after
    the traced work quiesces."""

    __slots__ = ("items", "n", "cap")

    def __init__(self, capacity: int):
        self.items: list = [None] * capacity
        self.n = 0
        self.cap = capacity

    def append(self, item) -> None:
        self.items[self.n % self.cap] = item
        self.n += 1

    def snapshot(self) -> list:
        if self.n <= self.cap:
            return [e for e in self.items[: self.n]]
        k = self.n % self.cap
        return self.items[k:] + self.items[:k]

    @property
    def dropped(self) -> int:
        return max(0, self.n - self.cap)


class Tracer:
    """Collects spans from every thread of this process into per-thread
    ring buffers. One tracer per process (rank); install it with
    :func:`install_tracer` so the module-level :func:`span` hook feeds it.
    """

    def __init__(self, rank: int | None = None, *,
                 capacity: int = DEFAULT_CAPACITY):
        self.rank = _process_index() if rank is None else int(rank)
        self.capacity = max(16, int(capacity))
        self._t0_perf = time.perf_counter()
        # absolute wall anchor for cross-rank correlation with journal
        # ``ts`` rows (the ONE sanctioned absolute-timestamp read here —
        # dev/lint_parity.py check 11 allowlist; every duration in this
        # module is a perf_counter difference)
        self.wall_t0 = time.time()
        self._local = threading.local()
        #: (lane index, thread name, ring, stack of open-span frames)
        self._threads: list[tuple[int, str, _Ring, list]] = []
        self._lock = threading.Lock()  # lane registration + export only

    # -- recording (hot path: no locks) --------------------------------------

    def _lane(self) -> tuple[_Ring, list]:
        lane = getattr(self._local, "lane", None)
        if lane is None:
            lane = self._local.lane = (_Ring(self.capacity), [])
            t = threading.current_thread()
            with self._lock:
                # key by registration index, not thread ident: the OS
                # reuses idents, and two short-lived threads must not
                # merge into one timeline lane
                self._threads.append((len(self._threads), t.name, *lane))
        return lane

    def record(self, name: str, cat: str, t_start: float, dur: float,
               attrs: dict | None, parent: tuple | None = None) -> None:
        """t_start: absolute ``perf_counter`` reading at span entry."""
        self._lane()[0].append(
            (name, cat, t_start - self._t0_perf, dur, attrs, parent)
        )

    # -- reading --------------------------------------------------------------

    def events(self) -> Iterator[TraceEvent]:
        with self._lock:
            threads = list(self._threads)
        for tid, tname, ring, _ in threads:
            for name, cat, start, dur, attrs, parent in ring.snapshot():
                yield TraceEvent(name, cat, start, dur, tid, tname, attrs,
                                 parent)

    def open_spans(self) -> list[TraceEvent]:
        """The spans entered and not yet left, outermost first per thread
        (``dur`` = how long each has been open): what a run that died or
        hangs was inside."""
        now = time.perf_counter() - self._t0_perf
        with self._lock:
            threads = list(self._threads)
        return [
            TraceEvent(f.name, f.cat, f.start, now - f.start, tid, tname,
                       f.merged_attrs(), f.parent)
            for tid, tname, _, stack in threads
            for f in list(stack)  # the owning thread may push/pop meanwhile
        ]

    def dropped_events(self) -> int:
        with self._lock:
            return sum(ring.dropped for _, _, ring, _ in self._threads)

    # -- Chrome-trace export ---------------------------------------------------

    def chrome_trace(self) -> dict:
        """Catapult/Perfetto JSON object: complete ``"X"`` events with µs
        timestamps, ``pid`` = rank (a span's explicit ``rank=`` attr wins —
        virtual-rank tests separate lanes that way), ``tid`` = a small
        stable per-thread index with ``thread_name`` metadata."""
        from photon_ml_tpu.telemetry.journal import json_safe

        events: list[dict] = []
        pids: set[int] = {self.rank}
        with self._lock:
            threads = list(self._threads)
        for tid, tname, _, _ in threads:
            events.append({
                "ph": "M", "name": "thread_name", "pid": self.rank,
                "tid": tid, "args": {"name": tname},
            })

        def exported(ev: TraceEvent, ph: str) -> dict:
            pid = self.rank
            if ev.attrs and "rank" in ev.attrs:
                pid = int(ev.attrs["rank"])
                pids.add(pid)
            args = dict(ev.attrs or {})
            if ev.parent is not None:
                args["parent"], args["parent_ts"] = (
                    ev.parent[0], ev.parent[1] * 1e6)
            out = {"ph": ph, "name": ev.name, "cat": ev.cat,
                   "ts": ev.start * 1e6, "pid": pid, "tid": ev.thread_id,
                   "args": json_safe(args)}
            if ph == "X":
                out["dur"] = ev.dur * 1e6
            return out

        events += [exported(ev, "X") for ev in self.events()]
        # still open (the failure path, or a snapshot mid-run): begin events
        # with no end — the viewer draws them to the end of the trace
        events += [exported(ev, "B") for ev in self.open_spans()]
        meta = [
            {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": f"rank {pid}"}}
            for pid in sorted(pids)
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "rank": self.rank,
                "wall_t0": self.wall_t0,
                "dropped_events": self.dropped_events(),
            },
        }


# ---------------------------------------------------------------------------
# The module-level span hook (inert by default)
# ---------------------------------------------------------------------------


_TRACER: Tracer | None = None


class _NullSpan:
    """Shared do-nothing span: the off path allocates nothing per call
    beyond the keyword dict Python builds for the ``span(...)`` call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A span with a tracer installed: a frame on its thread's stack of
    open spans while it is open, a ring event when it closes, and the
    profiler's annotation as well while a session is active."""

    __slots__ = ("_tracer", "name", "cat", "attrs", "_t0", "start", "parent",
                 "ids", "_note", "_stack")

    def __init__(self, tracer: Tracer, name: str, cat: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def merged_attrs(self) -> dict | None:
        """Own attrs over the identifiers inherited from enclosing spans."""
        if not self.ids:
            return self.attrs or None
        return {**self.ids, **self.attrs}

    def __enter__(self):
        tracer = self._tracer
        _, stack = tracer._lane()
        attrs = self.attrs
        if stack:
            outer = stack[-1]
            self.parent = (outer.name, outer.start)
            ids = outer.ids
        else:
            self.parent = None
            ids = None
        own = {k: attrs[k] for k in INHERITED_IDS if k in attrs}
        self.ids = {**ids, **own} if ids and own else (own or ids)
        self._stack = stack
        self._note = None
        if _Annotation.is_enabled():
            self._note = _Annotation(ANNOTATION_PREFIX + self.name, **attrs)
            self._note.__enter__()
        self._t0 = time.perf_counter()
        self.start = self._t0 - tracer._t0_perf
        stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # left out of order (a generator's span)
            stack.remove(self)
        attrs = self.merged_attrs()
        if exc_type is not None:
            # the span records even when the traced call raises — an
            # ExchangeTimeout's wait leading up to the deadline is exactly
            # the straggler evidence
            attrs = dict(attrs) if attrs else {}
            attrs["error"] = exc_type.__name__
        self._tracer.record(self.name, self.cat, self._t0, dur, attrs,
                            self.parent)
        return False


def span(name: str, *, cat: str = "span", **attrs):
    """``with span("io/decode_chunk", chunk=3): ...`` — the program's one
    way to mark time. With a tracer installed: a ring event (and the
    profiler's annotation while a session is active). With only a profiler
    session: the ``photon:<name>`` annotation itself. With neither (the
    default): a shared null object."""
    tracer = _TRACER
    if tracer is not None:
        return _Span(tracer, name, cat, attrs)
    if _Annotation.is_enabled():
        return _Annotation(ANNOTATION_PREFIX + name, **attrs)
    return _NULL_SPAN


def tracing_active() -> bool:
    return _TRACER is not None


def current_tracer() -> Tracer | None:
    return _TRACER


def install_tracer(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-wide span sink. Returns it."""
    global _TRACER
    _TRACER = tracer
    return tracer


def uninstall_tracer() -> Tracer | None:
    """Remove (and return) the installed tracer — callers pair this with
    install in a try/finally so a failed run never leaks tracing into the
    next one."""
    global _TRACER
    tracer, _TRACER = _TRACER, None
    return tracer


# ---------------------------------------------------------------------------
# Straggler attribution
# ---------------------------------------------------------------------------

_DIGITS_RE = re.compile(r"\d+")


def normalize_tag(tag: str) -> str:
    """Aggregation key for exchange tags: digit runs collapse to ``*`` so
    per-step/per-seq tags (``checkpoint_commit/7/ready``) pool into one
    row instead of one row per step."""
    return _DIGITS_RE.sub("*", tag)


def exchange_wait_tables(tracer: Tracer) -> dict[int, dict[str, dict]]:
    """Per-rank per-tag exchange wait totals from this tracer's spans:
    ``{rank: {tag: {"count", "wait_s", "max_s"}}}``. Rank comes from each
    span's ``rank`` attr (the exchange objects stamp it), so one shared
    tracer over virtual in-process ranks separates correctly; a real
    multi-process tracer simply holds its own rank only."""
    tables: dict[int, dict[str, dict]] = {}
    for ev in tracer.events():
        if ev.name not in _WAIT_SPAN_NAMES:
            continue
        attrs = ev.attrs or {}
        rank = int(attrs.get("rank", tracer.rank))
        tag = normalize_tag(str(attrs.get("tag", "")))
        row = tables.setdefault(rank, {}).setdefault(
            tag, {"count": 0, "wait_s": 0.0, "max_s": 0.0}
        )
        row["count"] += 1
        row["wait_s"] += ev.dur
        row["max_s"] = max(row["max_s"], ev.dur)
    return tables


def straggler_report(
    tables: Mapping[int, Mapping[str, dict]],
    *,
    num_ranks: int | None = None,
) -> dict:
    """Merge per-rank wait tables into the diagnostic: for every exchange
    tag, who arrived last?

    The rank with the LEAST total wait arrived last (everyone else's wait
    on that tag is time spent waiting for it); a rank with NO entry for a
    tag the others waited on never arrived at all (crashed/wedged — the
    WithholdingExchange chaos shape), and is named ahead of any wait
    comparison. Single-rank tags are reported with no straggler.
    """
    if num_ranks is None:
        num_ranks = (max(tables) + 1) if tables else 1
    tags: set[str] = set()
    for table in tables.values():
        tags.update(table)
    rows = []
    for tag in sorted(tags):
        waits = []
        counts = []
        for r in range(num_ranks):
            entry = tables.get(r, {}).get(tag)
            waits.append(None if entry is None else entry["wait_s"])
            counts.append(0 if entry is None else entry["count"])
        present = [r for r in range(num_ranks) if waits[r] is not None]
        missing = [r for r in range(num_ranks) if waits[r] is None]
        if missing and present:
            straggler, reason = missing[0], "never_arrived"
        elif len(present) > 1:
            straggler = min(present, key=lambda r: waits[r])
            reason = "least_wait"
        else:
            straggler, reason = None, "single_rank"
        rows.append({
            "tag": tag,
            "wait_s": waits,
            "count": counts,
            "missing_ranks": missing if present else [],
            "straggler_rank": straggler,
            "reason": reason,
        })
    # the tags costing the run the most wait first — the line a human
    # pastes into a slow-run report
    rows.sort(key=lambda r: -sum(w or 0.0 for w in r["wait_s"]))
    return {"num_ranks": num_ranks, "tags": rows}


def gather_straggler_report(tracer: Tracer, exchange) -> dict:
    """Run-end merge through the existing ``MetadataExchange``: every rank
    sends ITS per-tag wait table + ring-drop count (one model-free small
    payload), every rank computes the same merged report (SPMD discipline
    — every rank must call; rank 0 is the one that journals it). The
    per-rank ``dropped_events`` list makes ring-buffer truncation visible
    in the report itself: a rank whose early exchange spans were evicted
    undercounts its waits, and the reader must know."""
    local = exchange_wait_tables(tracer).get(exchange.rank, {})
    gathered = exchange.allgather(
        "trace/straggler_table",
        {"table": local, "dropped": tracer.dropped_events()},
    )
    tables = {r: g["table"] for r, g in enumerate(gathered)}
    report = straggler_report(tables, num_ranks=exchange.num_ranks)
    report["dropped_events"] = [int(g["dropped"]) for g in gathered]
    return report


# ---------------------------------------------------------------------------
# Publication (score-writer directory discipline, journal atomicity)
# ---------------------------------------------------------------------------


def trace_path(directory: str | os.PathLike, rank: int) -> str:
    return os.path.join(str(directory), TRACE_FILE_FORMAT.format(rank=rank))


def publish_trace(tracer: Tracer, directory: str | os.PathLike, *,
                  exchange=None) -> str:
    """Atomically write this rank's ``trace-{rank:05d}.json``.

    Multi-rank (an exchange with num_ranks > 1): rank 0 creates the
    directory, a barrier, then EVERY rank writes its own part file —
    the ``io/score_writer.py`` carve-out to the rank-0-only rule; ranks
    never write each other's files. The barrier rides the exchange's
    bounded deadline: on the failure path (some rank already dead) the
    ``ExchangeTimeout`` is logged and the write proceeds unbarriered
    (``makedirs(exist_ok=True)``) so a crash still publishes a readable
    timeline — trace parts are per-rank files, so the fallback cannot
    collide.
    """
    from photon_ml_tpu.resilience.errors import ExchangeTimeout

    directory = str(directory)
    if exchange is not None and exchange.num_ranks > 1:
        if exchange.rank == 0:
            os.makedirs(directory, exist_ok=True)
        try:
            exchange.barrier("trace/output_dir")
        except ExchangeTimeout as e:
            logger.warning(
                "trace publish barrier timed out (%s); publishing "
                "unbarriered — some rank likely died, its trace part may "
                "be missing", e,
            )
    os.makedirs(directory, exist_ok=True)
    path = trace_path(directory, tracer.rank)
    payload = json.dumps(tracer.chrome_trace())
    fd, staged = tempfile.mkstemp(
        dir=directory, prefix=f".trace-{tracer.rank:05d}-", suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w") as f:
            f.write(payload)
        os.replace(staged, path)
    except BaseException:
        if os.path.exists(staged):
            os.unlink(staged)
        raise
    return path


def finalize_trace(tracer: Tracer, directory: str | os.PathLike, *,
                   exchange=None, gather: bool = True) -> dict:
    """The drivers' one flush call: publish this rank's trace file, then
    build the straggler report — merged across ranks through the exchange
    on the success path (``gather=True`` with a multi-rank exchange), from
    this tracer's local tables otherwise (single process, or the failure
    path where another collective could hang on the dead rank). On a
    MIXED-outcome run (this rank succeeded, another died before its
    run-end trace collectives) the merge allgather's bounded
    ``ExchangeTimeout`` degrades to the local report — it must never mask
    a successful result. Callers journal the returned report BEFORE
    closing the journal, so spans are flushed to disk first and a crash
    leaves a readable timeline."""
    from photon_ml_tpu.resilience.errors import ExchangeTimeout

    publish_trace(tracer, directory,
                  exchange=exchange if gather else None)
    if gather and exchange is not None and exchange.num_ranks > 1:
        try:
            return gather_straggler_report(tracer, exchange)
        except ExchangeTimeout as e:
            logger.warning(
                "straggler merge timed out (%s); reporting this rank's "
                "local wait tables only", e,
            )
    # local fallback: report over the ranks this tracer actually OBSERVED
    # (all of them for a shared virtual-rank tracer; just this rank on a
    # real multi-process run — never blame unobserved peers as
    # "never_arrived" when their tables simply did not merge). A PARTIAL
    # report is flagged so the reader knows to merge the per-rank trace
    # FILES offline (dev/trace_summary.py) for the full picture.
    tables = exchange_wait_tables(tracer)
    report = straggler_report(tables)
    report["dropped_events"] = [tracer.dropped_events()]
    if exchange is not None and exchange.num_ranks > len(tables):
        # keep report["num_ranks"] == the universe its wait_s lists are
        # indexed by (the observed ranks); the true rank count rides a
        # separate field
        report["partial"] = True
        report["observed_ranks"] = sorted(tables)
        report["expected_num_ranks"] = exchange.num_ranks
    return report


def flush_trace_best_effort(tracer: Tracer, directory: str | os.PathLike, *,
                            exchange=None, gather: bool = True,
                            journal=None) -> dict | None:
    """Driver-teardown wrapper around :func:`finalize_trace` that NEVER
    raises: tracing is observability — a publication error (unwritable
    trace dir, a dead KV coordinator) in a ``finally`` would otherwise
    replace the run's own outcome and skip the journal rows that follow
    (the failure-path journal is the artifact that most needs to
    survive). The swallow is reviewed: every error is logged with its
    traceback (dev/lint_parity.py check 5 allowlist)."""
    try:
        report = finalize_trace(tracer, directory, exchange=exchange,
                                gather=gather)
        if journal is not None:
            journal.record("straggler_report", **report)
        return report
    except Exception:
        logger.exception(
            "trace publication failed; continuing teardown (the run's own "
            "outcome and journal take precedence)"
        )
        return None
