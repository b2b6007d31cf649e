"""Compiled-program ledger: per-program compile, cost, and HBM accounting
with recompile attribution.

No reference analogue: Photon-ML's unit of execution observability is the
Spark task (SURVEY.md §5); here the whole perf story rides a bounded set of
module-level jitted programs (streaming accumulators, vmapped bucket
solvers, serving shape buckets), so the compiled PROGRAM becomes the
first-class observed object — the DrJAX framing (arXiv:2403.07128: the
single traced program is the unit of system reasoning) crossed with
Snap ML's memory-hierarchy budgeting (arXiv:1803.06333).

Design (ISSUE 13):

- ``ledger_jit(fn, label=...)`` wraps ``jax.jit`` with a STABLE LABEL.
  Inert null-object by default (the tracing discipline — telemetry/
  tracing.py): with no ledger installed the wrapper is one global read +
  a passthrough call; installing a :class:`ProgramLedger` turns every
  labeled call into an observation. Observes, NEVER gates: the wrapped
  program dispatches exactly as the raw jit would — same arguments, same
  donation, same order (ledger on/off is pinned bitwise by
  tests/test_program_ledger.py).
- **Compile detection is a scoped compile-counter delta** around each
  dispatch (probes.install_compile_listener feeds the counter; the repo's
  dispatch model is single-consumer, so the delta attributes cleanly).
  This catches every real compile — new shapes, fresh program instances,
  evicted executables — without guessing from the signature cache.
- **Signatures** record every argument leaf's aval (shape, dtype,
  sharding), weak-typed python scalars (whose VALUE changes never
  recompile — they are deliberately not part of the signature), and
  static args (described by value for simple types, by type+hash
  otherwise — matching jit's own static-arg cache semantics, where a
  fresh instance with identity hash IS a new cache entry).
- **Recompile attribution is the headline**: a compile under a label that
  already compiled diffs the new signature against the previous compiled
  one and journals the exact differing leaves — turning "compile count
  went up" into "arg3.features: shape (16384, 8) -> (16000, 8) at
  streaming/accumulate_value_grad".
- **Cost analysis is free; memory analysis is not.** ``Lowered.
  cost_analysis()`` is an HLO-level analysis with NO backend compile
  (measured on this stack), so it runs for every new signature.
  ``Compiled.memory_analysis()`` requires an AOT ``lowered.compile()``.
  Under jax 0.9.0 the dispatch that follows reuses that executable (one
  backend-compile event, measured), so the AOT compile IS the program's
  compile and is counted as such; it stays opt-in
  (``analyze_memory=True``). Both degrade
  gracefully to None fields where the backend doesn't implement them
  (the CPU mesh), never raising into the dispatch path.
- **HBM forecast**: with memory analysis on, each compile row carries
  ``hbm_forecast_bytes`` = resident placed params (the layout-keyed
  cache's ``serve/resident_params_bytes`` gauge when fed, else the live
  device-buffer bytes probe) + the program's temp bytes, against the
  device's ``bytes_limit`` where the backend reports one —
  verdicts._ledger_findings turns forecast > limit into a finding.

Calls made while a jax trace is in flight bypass the ledger entirely: an
inner jitted step invoked during an outer trace inlines into the outer
program — it is not a separately dispatched program, and observing it
would double-count.

Every top-level call of a labelled program is also a span
``dispatch/<label>`` on the one span seam (telemetry/tracing.py), ledger or
no ledger: the host's side of a dispatch (signature look-up, a trace and
compile on the first call, the enqueue) in the profiler's trace beside the
device's work. And the first ``ledger_jit`` of a process installs the
compile listener (probes.install_compile_listener) on the default
registry, so a library user has trace / lower / cache-load / compile
seconds and the cache's hits and misses without a driver.

**What a scope holds** (``compiled_scopes``): a profiler's device events
name compiled instructions and carry none of their metadata, so which
``jax.named_scope`` an event ran under can only be read from the compiled
program's text. ``ledger_jit``'s wrapper remembers, for its label, the
abstract signature of the last call that TRACED (nothing on a call that
dispatches, and never an array); ``compiled_scopes(label)`` compiles from it
(jit's own caches or the compile cache answer) and returns every
instruction's signature and ``op_name``. Called by whoever reads a trace,
after the fact; an untraced run never calls it.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import re
import threading
import typing
import weakref

from photon_ml_tpu.telemetry.tracing import span

logger = logging.getLogger(__name__)

#: registry namespace for every per-label metric the ledger emits
LEDGER_METRIC_PREFIX = "xla/"

#: journal row kinds (dev/doctor.py's ledger table reads all three)
COMPILE_ROW = "program_compile"
RECOMPILE_ROW = "program_recompile"
SIGNATURE_ROW = "program_signature"

#: signatures retained per label for diffing; the oldest fall off (the
#: bounded-signature discipline is the point — a label that outgrows this
#: is itself the signature-churn pathology)
MAX_SIGNATURES_PER_LABEL = 64

#: cost_analysis keys worth journaling (the per-opcode utilization{...}
#: expansions are dropped — rows must stay small)
_COST_KEYS = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")

#: CompiledMemoryStats attributes journaled when memory analysis runs
_MEMORY_ATTRS = (
    "generated_code_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "temp_size_in_bytes",
    "peak_memory_in_bytes",
)


# ---------------------------------------------------------------------------
# Signatures
# ---------------------------------------------------------------------------

#: leaf kinds
ARRAY = "array"
WEAK = "weak"
STATIC = "static"


def _describe_static(v) -> str:
    """Stable description of a static argument, matching jit's cache
    semantics: simple values by repr (value-equal -> same entry), rich
    objects by type + hash (a default identity hash means a fresh instance
    IS a new jit cache entry, and the ledger must say so)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return repr(v)
    if isinstance(v, tuple):
        return "(" + ", ".join(_describe_static(x) for x in v) + ")"
    try:
        h = hash(v)
    except TypeError:
        return f"{type(v).__qualname__}@{id(v):#x}"
    return f"{type(v).__qualname__}#{h}"


def _describe_leaf(v) -> tuple:
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        sharding = getattr(v, "sharding", None)
        return (
            ARRAY,
            tuple(int(s) for s in shape),
            str(dtype),
            None if sharding is None else str(sharding),
        )
    if isinstance(v, (bool, int, float, complex)):
        # traced weak-typed scalar: its VALUE never keys the jit cache
        return (WEAK, type(v).__name__)
    return (STATIC, _describe_static(v))


def _path_str(path) -> str:
    """['arg0'].features-style keys, compactly joined with dots."""
    parts = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "name", None)
        if key is None:
            key = getattr(entry, "idx", None)
        parts.append(str(key) if key is not None else str(entry))
    return ".".join(parts)


@dataclasses.dataclass(frozen=True)
class ProgramSignature:
    """One call's argument signature: dynamic leaves (path -> aval
    description) + static args (name -> description)."""

    leaves: tuple  # ((path, desc-tuple), ...)
    static: tuple  # ((name, description), ...)

    @property
    def key(self):
        return (self.leaves, self.static)

    def to_json(self) -> dict:
        return {
            "leaves": [
                {"path": p, "kind": d[0],
                 **({"shape": list(d[1]), "dtype": d[2], "sharding": d[3]}
                    if d[0] == ARRAY else {"value": d[1]})}
                for p, d in self.leaves
            ],
            "static": [{"name": n, "value": s} for n, s in self.static],
        }


def build_signature(args, kwargs, static_argnums=(), static_argnames=()) -> ProgramSignature:
    import jax

    dyn: dict = {}
    statics: list = []
    nums = set(static_argnums or ())
    names = set(static_argnames or ())
    for i, a in enumerate(args):
        if i in nums:
            statics.append((f"arg{i}", _describe_static(a)))
        else:
            dyn[f"arg{i}"] = a
    for k, v in kwargs.items():
        if k in names:
            statics.append((k, _describe_static(v)))
        else:
            dyn[k] = v
    leaves = tuple(
        (_path_str(path), _describe_leaf(leaf))
        for path, leaf in jax.tree_util.tree_flatten_with_path(dyn)[0]
    )
    return ProgramSignature(leaves=leaves, static=tuple(sorted(statics)))


_ARRAY_FIELDS = (("shape", 1), ("dtype", 2), ("sharding", 3))


def diff_signatures(old: ProgramSignature, new: ProgramSignature) -> list[dict]:
    """The differing leaves between two signatures — the attribution a
    recompile row carries. Each change names the leaf path, the field
    (shape/dtype/sharding/kind/presence/static) and old -> new values."""
    changes: list[dict] = []
    o, n = dict(old.leaves), dict(new.leaves)
    for path in sorted(o.keys() | n.keys()):
        a, b = o.get(path), n.get(path)
        if a == b:
            continue
        if a is None or b is None:
            changes.append({"leaf": path, "field": "presence",
                            "old": None if a is None else list(a),
                            "new": None if b is None else list(b)})
            continue
        if a[0] != b[0]:
            changes.append({"leaf": path, "field": "kind",
                            "old": a[0], "new": b[0]})
            continue
        if a[0] == ARRAY:
            for field, idx in _ARRAY_FIELDS:
                if a[idx] != b[idx]:
                    changes.append({
                        "leaf": path, "field": field,
                        "old": list(a[idx]) if field == "shape" else a[idx],
                        "new": list(b[idx]) if field == "shape" else b[idx],
                    })
        else:
            changes.append({"leaf": path, "field": a[0],
                            "old": a[1], "new": b[1]})
    os_, ns_ = dict(old.static), dict(new.static)
    for name in sorted(os_.keys() | ns_.keys()):
        if os_.get(name) != ns_.get(name):
            changes.append({"leaf": name, "field": "static",
                            "old": os_.get(name), "new": ns_.get(name)})
    return changes


def diff_summary(changes: list[dict], limit: int = 4) -> str:
    """One human line per recompile row: 'leaf: field old -> new; ...'."""
    if not changes:
        return ("signature identical to the previous compile — a fresh "
                "program instance or an evicted executable recompiled the "
                "same shapes")
    parts = [
        f"{c['leaf']}: {c['field']} {c['old']} -> {c['new']}"
        for c in changes[:limit]
    ]
    if len(changes) > limit:
        parts.append(f"(+{len(changes) - limit} more)")
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------


class _LabelRecord:
    __slots__ = ("signatures", "order", "last_compiled", "calls", "compiles",
                 "recompiles", "distinct")

    def __init__(self):
        self.signatures: dict = {}  # key -> ProgramSignature
        self.order: list = []  # keys, oldest first (bounded eviction)
        self.last_compiled: ProgramSignature | None = None
        self.calls = 0
        self.compiles = 0
        self.recompiles = 0
        #: MONOTONE distinct-signature count: eviction bounds the diff
        #: cache above, never this — the signatures gauge and the doctor's
        #: redundancy math (compiles - signatures) must stay exact past
        #: max_signatures, or an unbounded-shape churn run would read as
        #: executable thrash
        self.distinct = 0


class ProgramLedger:
    """Per-label compile/cost/HBM accounting over ledger_jit call sites.

    registry: metrics sink (default: the process registry) —
    ``xla/<label>/{calls,compiles,recompiles}`` counters,
    ``xla/<label>/compile_seconds`` histogram, ``xla/<label>/{signatures,
    flops,bytes_accessed,temp_bytes,peak_bytes,hbm_forecast_bytes}``
    gauges. journal: optional RunJournal — compile/recompile/signature
    rows land there (inert on worker ranks, the journal's own rule).
    analyze_cost: ``Lowered.cost_analysis()`` per NEW signature (default
    on) — no backend compile, but the AOT ``lower()`` it needs re-traces
    the program once per signature on the host (AOT lowering does not
    share the dispatch path's trace); turn it off to make the ledger pure
    bookkeeping on runs where tracing the biggest programs twice matters.
    analyze_memory: opt-in ``Compiled.memory_analysis()`` — AOT-compiles
    each new signature before its first dispatch (which then reuses the
    executable under jax 0.9.0).
    """

    def __init__(self, *, registry=None, journal=None,
                 analyze_cost: bool = True,
                 analyze_memory: bool = False,
                 max_signatures: int = MAX_SIGNATURES_PER_LABEL):
        from photon_ml_tpu.telemetry.registry import default_registry

        self.registry = registry or default_registry()
        self.journal = journal
        self.analyze_cost = bool(analyze_cost)
        self.analyze_memory = bool(analyze_memory)
        self.max_signatures = int(max_signatures)
        #: free-form run phase ("warm"/"replay"/...) stamped on rows —
        #: serve_driver sets it so a replay compile is attributed to the
        #: replay, not just to the label
        self.phase: str | None = None
        self._labels: dict[str, _LabelRecord] = {}
        self._lock = threading.Lock()

    # -- introspection -------------------------------------------------------

    def set_phase(self, phase: str | None) -> None:
        self.phase = phase

    def labels(self) -> list[str]:
        with self._lock:
            return sorted(self._labels)

    def signature_count(self, label: str) -> int:
        """Distinct signatures observed under ``label`` — monotone (the
        diff cache's eviction never shrinks it)."""
        with self._lock:
            rec = self._labels.get(label)
            return 0 if rec is None else rec.distinct

    def snapshot(self) -> dict:
        """{label: {calls, compiles, recompiles, signatures}} — what
        serve_driver folds into its summary."""
        with self._lock:
            return {
                label: {
                    "calls": rec.calls,
                    "compiles": rec.compiles,
                    "recompiles": rec.recompiles,
                    "signatures": rec.distinct,
                }
                for label, rec in sorted(self._labels.items())
            }

    # -- observation ---------------------------------------------------------

    def _metric(self, label: str, name: str) -> str:
        return f"{LEDGER_METRIC_PREFIX}{label}/{name}"

    def observed_call(self, jitted, label: str, args, kwargs,
                      static_argnums=(), static_argnames=()):
        """Dispatch ``jitted(*args, **kwargs)`` under observation. The
        dispatch itself is untouched; everything else is bookkeeping on
        the host, recorded on success AND failure paths."""
        from photon_ml_tpu.telemetry import probes

        probes.install_compile_listener(self.registry)
        sig = build_signature(args, kwargs, static_argnums, static_argnames)
        with self._lock:
            rec = self._labels.setdefault(label, _LabelRecord())
            is_new = sig.key not in rec.signatures
        # snapshot BEFORE the analysis: with analyze_memory its AOT compile
        # is the one the dispatch below reuses, i.e. this program's compile
        counter = self.registry.counter(probes.COMPILE_COUNT_METRIC)
        seconds = self.registry.histogram(probes.COMPILE_SECONDS_METRIC)
        c0, s0 = counter.value, seconds.total
        analysis = None
        if is_new:
            # args are still alive here (before any donation) — lowering
            # needs only their avals, but never touch them post-dispatch
            analysis = self._analyze(jitted, args, kwargs)
        error = None
        try:
            return jitted(*args, **kwargs)
        except Exception as e:
            error = type(e).__name__
            raise
        finally:
            self._record(
                label, sig, is_new, analysis,
                compiles=counter.value - c0,
                compile_seconds=seconds.total - s0,
                error=error,
            )

    def _analyze(self, jitted, args, kwargs) -> dict:
        """Lower the call for cost analysis (no backend compile) and, when
        opted in, AOT-compile for memory analysis. A capability probe:
        every failure IS the answer (None fields), logged at debug and
        never raised into the dispatch path (reviewed broad except —
        dev/lint_parity.py check 5 allowlist)."""
        from photon_ml_tpu.telemetry import probes

        out: dict = {"cost": None, "memory": None, "hbm_forecast_bytes": None,
                     "device_bytes_limit": None}
        if not (self.analyze_cost or self.analyze_memory):
            return out
        try:
            lowered = jitted.lower(*args, **kwargs)
        except Exception:
            logger.debug("program ledger: lower() unavailable", exc_info=True)
            return out
        try:
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if cost:
                out["cost"] = {
                    k: float(cost[k]) for k in _COST_KEYS if k in cost
                }
        except Exception:
            logger.debug("program ledger: cost_analysis unavailable",
                         exc_info=True)
        if not self.analyze_memory:
            return out
        try:
            mem = lowered.compile().memory_analysis()
            memory = {
                a: int(getattr(mem, a))
                for a in _MEMORY_ATTRS
                if getattr(mem, a, None) is not None
            }
            out["memory"] = memory or None
        except Exception:
            logger.debug("program ledger: memory_analysis unavailable",
                         exc_info=True)
            return out
        temp = (out["memory"] or {}).get("temp_size_in_bytes")
        peak = (out["memory"] or {}).get("peak_memory_in_bytes", temp)
        if peak is not None:
            resident = self._resident_bytes()
            if resident is not None:
                out["hbm_forecast_bytes"] = int(resident) + int(peak)
        out["device_bytes_limit"] = probes.device_memory_limit_bytes()
        return out

    def refeed_resident_forecast(self, label: str) -> int | None:
        """Recompute ``xla/<label>/hbm_forecast_bytes`` from the CURRENT
        resident placed-params bytes plus the label's recorded peak — the
        hot-swap hook (serving/resident.py): a same-layout model swap
        triggers no compile, so without this the forecast gauge would keep
        pricing the STALE model's resident bytes. Returns the new forecast,
        or None when either input is unknown (no memory analysis ran, or
        nothing feeds the resident gauge); journals a
        ``program_forecast_refeed`` row when it changes."""
        peak = self.registry.gauge(self._metric(label, "peak_bytes")).value
        if peak is None:
            peak = self.registry.gauge(self._metric(label, "temp_bytes")).value
        resident = self._resident_bytes()
        if peak is None or resident is None:
            return None
        forecast = int(resident) + int(peak)
        self.registry.gauge(
            self._metric(label, "hbm_forecast_bytes")
        ).set(forecast)
        if self.journal is not None:
            self.journal.record(
                "program_forecast_refeed", label=label, phase=self.phase,
                resident_bytes=int(resident), peak_bytes=int(peak),
                hbm_forecast_bytes=forecast,
            )
        return forecast

    def _resident_bytes(self) -> int | None:
        """Resident placed-params bytes: the layout-keyed cache's gauge
        when someone feeds it (parallel/scoring.py), else the live
        device-buffer probe."""
        from photon_ml_tpu.telemetry import serving_counters

        gauge = self.registry.gauge(
            serving_counters.RESIDENT_PARAMS_BYTES
        ).value
        if gauge is not None:
            return int(gauge)
        from photon_ml_tpu.telemetry.probes import live_buffer_bytes

        return live_buffer_bytes()

    def _record(self, label: str, sig: ProgramSignature, is_new: bool,
                analysis: dict | None, *, compiles: int,
                compile_seconds: float, error: str | None) -> None:
        reg = self.registry
        with self._lock:
            rec = self._labels[label]
            rec.calls += 1
            prior = rec.last_compiled
            if prior is None:
                # the program may have compiled before this ledger was
                # installed — attribute against the most recent OTHER
                # cached signature rather than dropping the diff
                for key in reversed(rec.order):
                    if key != sig.key:
                        prior = rec.signatures[key]
                        break
            if is_new and sig.key not in rec.signatures:
                rec.distinct += 1
                rec.signatures[sig.key] = sig
                rec.order.append(sig.key)
                while len(rec.order) > self.max_signatures:
                    del rec.signatures[rec.order.pop(0)]
            if compiles > 0:
                rec.compiles += compiles
                rec.last_compiled = sig
                if prior is not None:
                    rec.recompiles += 1
            num_signatures = rec.distinct
            recompiled = compiles > 0 and prior is not None
        reg.counter(self._metric(label, "calls")).inc()
        reg.gauge(self._metric(label, "signatures")).set(num_signatures)
        if compiles <= 0:
            if is_new and self.journal is not None:
                # observed without a compile: the program was already
                # cached (ledger installed mid-run) — still worth a row so
                # the doctor table covers it
                self.journal.record(
                    SIGNATURE_ROW, label=label, phase=self.phase,
                    signature=sig.to_json(),
                    cost=None if analysis is None else analysis["cost"],
                )
            return
        reg.counter(self._metric(label, "compiles")).inc(compiles)
        reg.histogram(self._metric(label, "compile_seconds")).observe(
            compile_seconds
        )
        if recompiled:
            reg.counter(self._metric(label, "recompiles")).inc()
        cost = memory = forecast = limit = None
        if analysis is not None:
            cost = analysis["cost"]
            memory = analysis["memory"]
            forecast = analysis["hbm_forecast_bytes"]
            limit = analysis["device_bytes_limit"]
            if cost is not None:
                for key, name in (("flops", "flops"),
                                  ("bytes accessed", "bytes_accessed")):
                    if key in cost:
                        reg.gauge(self._metric(label, name)).set(cost[key])
            if memory is not None:
                for attr, name in (("temp_size_in_bytes", "temp_bytes"),
                                   ("peak_memory_in_bytes", "peak_bytes"),
                                   ("argument_size_in_bytes",
                                    "argument_bytes"),
                                   ("output_size_in_bytes", "output_bytes")):
                    if attr in memory:
                        reg.gauge(self._metric(label, name)).set(memory[attr])
            if forecast is not None:
                reg.gauge(
                    self._metric(label, "hbm_forecast_bytes")
                ).set(forecast)
        if self.journal is None:
            return
        if recompiled:
            changes = diff_signatures(prior, sig)
            self.journal.record(
                RECOMPILE_ROW, label=label, phase=self.phase,
                changed=changes, summary=diff_summary(changes),
                compiles=compiles,
                compile_seconds=round(compile_seconds, 6), error=error,
            )
        self.journal.record(
            COMPILE_ROW, label=label, phase=self.phase,
            new_signature=is_new, signature=sig.to_json(),
            compiles=compiles, compile_seconds=round(compile_seconds, 6),
            cost=cost, memory=memory, hbm_forecast_bytes=forecast,
            device_bytes_limit=limit, error=error,
        )


# ---------------------------------------------------------------------------
# The module-level hook (inert by default) + the registration wrapper
# ---------------------------------------------------------------------------

_LEDGER: ProgramLedger | None = None


def ledger_active() -> bool:
    return _LEDGER is not None


def current_ledger() -> ProgramLedger | None:
    return _LEDGER


def install_ledger(ledger: ProgramLedger) -> ProgramLedger:
    """Make ``ledger`` the process-wide sink for ledger_jit sites."""
    global _LEDGER
    _LEDGER = ledger
    return ledger


def uninstall_ledger() -> ProgramLedger | None:
    """Remove (and return) the installed ledger — drivers pair this with
    install in a try/finally so a failed run never leaks observation into
    the next one."""
    global _LEDGER
    ledger, _LEDGER = _LEDGER, None
    return ledger


# ---------------------------------------------------------------------------
# What a label compiled: instruction names, signatures and scopes
# ---------------------------------------------------------------------------


class CompiledScopes(typing.NamedTuple):
    """``instructions``: {instruction name: (signature, op_name)} for every
    instruction of a compiled program whose metadata names an ``op_name``
    (the ``jax.named_scope``s it was traced under, then the primitive);
    ``entry_loops``: the names among them that are ``while`` loops of the
    ENTRY computation (each runs once an execution of the program)."""

    instructions: dict
    entry_loops: frozenset


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+ = .*?)(?:, metadata=\{[^}]*op_name="([^"]*)"|$)',
    re.MULTILINE)
_LAYOUT_OR_COMMENT = re.compile(r"\{[^{}]*\}|/\*.*?\*/|\s")
_CUT_LAYOUT_OR_COMMENT = re.compile(r"(\{[^{}]*|/\*[^/]*)$")


def parse_instruction(text: str) -> "tuple[str, str, bool]":
    """(name, signature, whole) of an instruction's text, as a compiled
    program's text and a profiler's device event both print it: ``%while.9 =
    (s32[], f32[8,32]{1,0}) while(...)`` -> ``("while.9",
    "(s32[],f32[8,32])while", True)``. The signature is the result shape and
    the opcode; layouts, index comments and blanks go. The profiler cuts a
    long event name short (a loop's tuple of shapes runs to thousands of
    characters): then no ``opcode(`` follows the shape, ``whole`` is False
    and the signature is what is left of it, a PREFIX of the whole one."""
    name, _, rest = text.partition(" = ")
    depth = 0
    for end, char in enumerate(rest):  # the result shape may be a tuple
        depth += (char == "(") - (char == ")")
        if char == " " and depth == 0:
            break
    else:
        end = len(rest)
    opcode, whole, _ = rest[end:].lstrip().partition("(")
    shape = _LAYOUT_OR_COMMENT.sub("", rest[:end])
    if not opcode:  # cut short: drop the layout or comment the cut fell in
        shape = _CUT_LAYOUT_OR_COMMENT.sub("", shape)
    return name.strip().lstrip("%"), shape + opcode, bool(whole)


def scopes_of_text(hlo_text: str) -> CompiledScopes:
    """The record of one compiled program's text (``Compiled.as_text()``)."""

    def with_metadata(text: str) -> dict:
        out = {}
        for line, op_name in _INSTRUCTION.findall(text):
            if op_name:
                name, sig, _ = parse_instruction(line)
                out[name] = (sig, op_name)
        return out

    entry = hlo_text.partition("\nENTRY ")[2].partition("\n}")[0]
    return CompiledScopes(with_metadata(hlo_text), frozenset(
        name for name, (sig, _) in with_metadata(entry).items()
        if sig.endswith(")while")))


@dataclasses.dataclass
class _TracedCall:
    """The last call under a label that traced: the jitted function, held
    weakly (a program that was collected reads as nothing), its arguments as
    an abstract signature, and what it compiled to once someone asked."""

    jitted: weakref.ref
    args: tuple
    kwargs: dict
    scopes: "CompiledScopes | None" = None


#: label -> its last traced call; a process holds a few dozen labels
_TRACED: "dict[str, _TracedCall]" = {}


def _abstract(leaf):
    """An array leaf as its ``jax.ShapeDtypeStruct`` (with the sharding AND
    the layout of a committed ``jax.Array`` and the weak type of a Python
    scalar's array: what jit lowers by; a dense batch placed row-major,
    ``data/batch.in_kernel_layout``, is compiled for as it lies, and the
    record has to be of THAT program); anything else as it is."""
    import jax

    shape, dtype = getattr(leaf, "shape", None), getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return leaf
    placement = None
    if isinstance(leaf, jax.Array) and leaf.committed:
        # a donated (deleted) array reports no layout: its sharding alone
        lies = leaf.format
        placement = leaf.sharding if lies.layout is None else lies
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=placement,
        weak_type=getattr(leaf, "weak_type", False))


def _remember_trace(label: str, jitted, args, kwargs) -> None:
    import jax

    def forget(ref, records=_TRACED):  # the statics do not outlive their program
        if label in records and records[label].jitted is ref:
            del records[label]

    args, kwargs = jax.tree_util.tree_map(_abstract, (args, kwargs))
    _TRACED[label] = _TracedCall(weakref.ref(jitted, forget), args, kwargs)


def compiled_scopes(label: str) -> "CompiledScopes | None":
    """What the program last traced under ``label`` compiled to: see
    :class:`CompiledScopes`. Lowers and compiles from the remembered
    abstract signature (in the process that ran it the lowering and the
    executable are jit's cached ones; elsewhere the compile cache answers),
    parses the text once and keeps the result for the process. None where
    nothing was traced under the label, or the program that was is gone."""
    call = _TRACED.get(label)
    if call is None:
        return None
    if call.scopes is None:
        jitted = call.jitted()
        if jitted is None:
            return None
        text = jitted.lower(*call.args, **call.kwargs).compile().as_text()
        call.scopes = scopes_of_text(text or "")
    return call.scopes


def _as_tuple(v) -> tuple:
    if v is None:
        return ()
    if isinstance(v, (tuple, list)):
        return tuple(v)
    return (v,)


def ledger_jit(fn=None, *, label: str, **jit_kwargs):
    """``jax.jit`` with a stable program label the ledger observes by.

    Drop-in at every hot jit site (dev/lint_parity.py check 13 makes the
    labeling structural in algorithm/, serving/ and parallel/): identical
    dispatch semantics — all ``jit_kwargs`` (static_argnums/names,
    donate_argnums, ...) pass straight through — plus, when a ledger is
    installed, per-call compile/cost/signature observation. Usable bare
    or through ``partial`` as a decorator. Calls made while a jax trace
    is in flight bypass observation (an inlined inner step is not a
    dispatched program).
    """
    if fn is None:
        return functools.partial(ledger_jit, label=label, **jit_kwargs)
    import jax

    from photon_ml_tpu.telemetry import probes

    probes.install_compile_listener()
    # .here: the function ran in this thread, i.e. jit traced it there (a
    # trace runs in the thread of the call that misses jit's cache)
    traced = threading.local()

    @functools.wraps(fn)
    def traced_fn(*args, **kwargs):
        traced.here = True
        return fn(*args, **kwargs)

    jitted = jax.jit(traced_fn, **jit_kwargs)
    static_argnums = _as_tuple(jit_kwargs.get("static_argnums"))
    static_argnames = _as_tuple(jit_kwargs.get("static_argnames"))
    dispatch = "dispatch/" + label
    is_top_level = jax.core.trace_ctx.is_top_level

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not is_top_level():  # inlined in an outer trace: not a program
            return jitted(*args, **kwargs)
        # only a trace inside THIS call counts: not what a ``.lower()`` or an
        # inlined call left behind in this thread
        traced.here = False
        with span(dispatch):
            try:
                ledger = _LEDGER
                if ledger is None:
                    return jitted(*args, **kwargs)
                return ledger.observed_call(
                    jitted, label, args, kwargs, static_argnums, static_argnames
                )
            finally:
                if traced.here:  # shapes and shardings outlive a donation
                    _remember_trace(label, jitted, args, kwargs)

    wrapper.label = label
    wrapper.jitted = jitted
    # preserve the jit AOT surface: callers inspect programs via
    # .lower(...).compile().as_text() (HLO pins in tests) and the ledger
    # must not take that away
    wrapper.lower = jitted.lower
    for name in ("trace", "eval_shape", "clear_cache"):
        attr = getattr(jitted, name, None)
        if attr is not None:
            setattr(wrapper, name, attr)
    return wrapper
