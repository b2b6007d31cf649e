"""The fused step's device seconds by phase: a partition of every busy instant
inside the ``jit__step_impl`` module events of ``bench:window`` into seven
categories, by the ``jax.named_scope`` the instruction that ran was traced
under. The seven ``step_*_time_share_pct`` readers each take one share of it.

The profiler's device events name compiled instructions and carry none of
their metadata (``layer_metrics/mf_time_share_pct.py`` found that on the
chip), so the scopes come from the program's own record of what it compiled,
``program_ledger.compiled_scopes("train/step")``: {instruction name:
(signature, op_name)} and the ENTRY computation's loops. An instant of busy
time belongs to the INNERMOST event that covers it (the one that started
last: a ``while``, ``conditional`` or ``call`` keeps only what its body's
events leave uncovered), and through that event's instruction to a category
by the scopes in its ``op_name``, the primitive's own name at its end taken
off. An instruction without metadata (a compiler-made copy in a loop body)
takes the ``op_name`` of the event that encloses it in time, so only
top-level instructions without metadata are ``unscoped``. A fusion carries
its root's ``op_name``: what rides in another phase's fusion is timed there.

The text is of a second compile, answered from the compile cache. Were it
another program its instruction numbers would name other instructions, so
every event of a recorded name is held to the record's SIGNATURE (result
shape + opcode; a name the profiler cut short, to the prefix it kept) and
every ENTRY loop to an event in every step of the window: on a mismatch the
partition is nothing, never a share. Nothing too without a device plane (the
CPU), without a step in the window, or with a program that keeps no such
record (a parent commit).

Categories, first match wins (``RULES``): ``fe`` (``fe/solve``,
``extra_fe/``: the kernel, its solver and the ``psum``), ``lane_search``
(``lbfgs/line_search`` under ``re/`` or ``mf/``), ``gather`` (a bucket's
offsets, warm-start rows and, for the factorization, the other side's factor
rows), ``score_scatter`` (``scatter``, ``score/``: the solved rows into the
table and the coordinate's margins anew), ``lane_update`` (anything else
under ``re/`` or ``mf/``: direction, history, the selects, the stop tests,
the loops' own overhead), ``residual`` (``residual``, ``loss``),
``unscoped``. Their sum is the step's busy seconds.

Within a category the printed table goes by coordinate and by the solver's
PHASE: the innermost of ``lbfgs/direction`` (the two-loop recursion),
``lbfgs/history`` (the pairs' shift), ``lbfgs/line_search`` and ``solve`` (what
is left of a solver's body: the keep-or-not selects, the stop tests, the
loops' own overhead), so that ``lane_update`` and ``fe`` read split three and
four ways.
"""
import bisect
import os
import re
import time

from benchmark import program_trace
from benchmark.trace_reduce import (
    _clip,
    instruction,
    load_xplane,
    union_intervals,
    window_of,
)

STEP_MODULE = "jit__step_impl"
LABEL = "train/step"
UNSCOPED = "unscoped"


def _scope(*names: str) -> str:
    """A pattern for any of the scopes wherever it stands in an ``op_name``:
    after ``/`` or ``(`` or at the start, before ``/`` or ``)`` or the end
    (``.../vmap(lbfgs/line_search)/...``, ``.../while/body/lbfgs/...``), and
    never inside another word (``score/user`` is not ``re/user``) nor as a
    jitted function's name (``jit(loss)``)."""
    return (r"(?<![^/(])(?<!jit\()(?:" + "|".join(re.escape(n) for n in names)
            + r")(?![^/)])")


RULES = tuple((category, re.compile(pattern)) for category, pattern in (
    ("fe", _scope("fe/solve", "extra_fe")),
    ("lane_search", _scope("re", "mf") + ".*" + _scope("lbfgs/line_search")),
    ("gather", _scope("gather")),
    ("score_scatter", _scope("scatter", "score")),
    ("lane_update", _scope("re", "mf")),
    ("residual", _scope("residual", "loss")),
))
CATEGORIES = tuple(category for category, _ in RULES) + (UNSCOPED,)
#: whose phase it is, for the printed table
_COORDINATE = re.compile(
    r"(?<![^/(])(?<!jit\()(?:mf/[^/()]+/(?:row|col)|(?:re|score|extra_fe)/[^/()]+"
    r"|fe(?=/solve)|residual|loss)(?![^/)])")
#: and which part of a solver, the innermost of these
_PHASE = re.compile(_scope("solve", "lbfgs/direction", "lbfgs/history",
                           "lbfgs/line_search"))

_parsed: dict = {}  # xplane path -> partition: one a process


def category(op_name: "str | None") -> str:
    """The category of an instruction by its ``op_name``, the last component
    (the primitive: ``.../score/user/gather`` is a gather IN a scoring, not
    the scope ``gather``) taken off."""
    scopes = (op_name or "").rpartition("/")[0]
    for name, pattern in RULES:
        if pattern.search(scopes):
            return name
    return UNSCOPED


def coordinate(op_name: "str | None") -> str:
    found = _COORDINATE.search((op_name or "").rpartition("/")[0])
    return found.group(0) if found else "-"


def phase(op_name: "str | None") -> str:
    """The innermost solver scope of an ``op_name``, "-" outside a solver."""
    found = _PHASE.findall((op_name or "").rpartition("/")[0])
    return found[-1] if found else "-"


def partition(trace: dict, record, parse) -> "dict | None":
    """{"busy_s", "step_s", "devices", "seconds": {category: s},
    "by_coordinate": {(category, coordinate, phase): s}, "unscoped":
    {instruction: s}}, seconds a device, from ``load_xplane``'s lists, the program's record
    (instructions, entry loops) and its parse of an instruction's text; None
    where the module's docstring says so."""
    instructions, entry_loops = record
    entry_loops = frozenset(entry_loops)
    lo, hi = window_of(trace)
    devices = [dev for dev in trace["devices"].values() if dev["ops"]]
    # (category, coordinate, phase) or (unscoped, instruction) -> ns
    seconds: dict = {}
    busy = 0.0
    steps_seen = matched = 0
    known: dict = {}  # an event's text -> (instruction name, its record or None)
    keys: dict = {}  # op_name -> (category, coordinate, phase)
    for dev in devices:
        ops = _clip(dev["ops"], lo, hi)
        busy += sum(b - a for a, b in union_intervals(ops))
        steps = sorted((s, s + d) for text, s, d in _clip(dev["modules"], lo, hi)
                       if text.startswith(STEP_MODULE))
        starts = [s for s, _ in steps]
        loops_seen = [set() for _ in steps]
        stack: list = []  # [end, key, op_name] of the events open at the cursor
        cursor = lo

        def advance(to: float) -> None:
            nonlocal cursor
            while stack and stack[-1][0] <= to:
                end, key, _ = stack.pop()
                if end > cursor:
                    seconds[key] = seconds.get(key, 0.0) + end - cursor
                    cursor = end
            if stack and to > cursor:
                key = stack[-1][1]
                seconds[key] = seconds.get(key, 0.0) + to - cursor
            cursor = max(cursor, to)

        for text, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
            step = bisect.bisect_right(starts, start) - 1
            if step < 0 or start >= steps[step][1]:
                continue  # another program's: names are one program's only
            if text not in known:
                name, signature, whole = parse(text)
                recorded = instructions.get(name)
                if recorded is not None and not (
                        signature == recorded[0] if whole
                        else recorded[0].startswith(signature)):
                    return None  # the record is of another program
                known[text] = (name, recorded)
            name, recorded = known[text]
            advance(start)
            if recorded is not None:
                matched += 1
                op_name = recorded[1]
                if name in entry_loops:
                    loops_seen[step].add(name)
            else:  # no metadata: the enclosing event's
                op_name = stack[-1][2] if stack else None
            if op_name not in keys:
                keys[op_name] = (
                    category(op_name), coordinate(op_name), phase(op_name))
            key = keys[op_name]
            if key[0] == UNSCOPED:
                key = (UNSCOPED, instruction(text))
            stack.append([start + dur, key, op_name])
        advance(hi)
        if any(entry_loops - seen for seen in loops_seen):
            return None
        steps_seen += len(steps)
    if not steps_seen or not matched:
        return None
    ns = 1e9 * len(devices)
    by_category = dict.fromkeys(CATEGORIES, 0.0)
    for key, value in seconds.items():
        by_category[key[0]] += value / ns
    return {"busy_s": busy / ns, "step_s": sum(by_category.values()),
            "devices": len(devices), "seconds": by_category,
            "by_coordinate": {key: value / ns for key, value in seconds.items()
                              if key[0] != UNSCOPED},
            "unscoped": {key[1]: value / ns for key, value in seconds.items()
                         if key[0] == UNSCOPED}}


def _report(part: dict, load_s: float, record_s: float, partition_s: float) -> None:
    busy = part["busy_s"]
    print(f"step scopes: trace loaded in {load_s:.2f} s, compiled_scopes in "
          f"{record_s:.2f} s, partition in {partition_s:.2f} s; step "
          f"{part['step_s']:.4f} s of busy {busy:.4f} s a device "
          f"({100 * part['step_s'] / busy:.2f} %), {part['devices']} "
          "device plane(s); seconds a device by category: "
          + " ".join(f"{k}={v:.4f}" for k, v in part["seconds"].items()), flush=True)
    print("step scopes by coordinate and phase: " + " ".join(
        f"{':'.join(key)}={value:.4f}" for key, value in sorted(
            part["by_coordinate"].items(), key=lambda kv: -kv[1])), flush=True)
    print("step scopes, unscoped instructions: " + " ".join(
        f"{name}={value:.4f}" for name, value in sorted(
            part["unscoped"].items(), key=lambda kv: -kv[1])[:12]), flush=True)


def of_this_run() -> "dict | None":
    """The partition of this run, from the newest xplane file under the work
    directory and the program's own record: once a process, after the window."""
    from photon_ml_tpu.telemetry import program_ledger

    compiled_scopes = getattr(program_ledger, "compiled_scopes", None)
    if compiled_scopes is None:  # a program from before the record
        return None
    path = program_trace.newest_xplane()
    if path is None:
        return None
    if path not in _parsed:
        t0 = time.perf_counter()
        # <trace dir>/plugins/profile/<time>/<host>.xplane.pb
        trace = load_xplane(os.path.normpath(os.path.join(path, *[".."] * 4)))
        t1 = time.perf_counter()
        record = None
        if any(dev["ops"] for dev in trace["devices"].values()):
            record = compiled_scopes(LABEL)
        t2 = time.perf_counter()
        part = _parsed[path] = None if record is None else partition(
            trace, record, program_ledger.parse_instruction)
        if part is not None:
            _report(part, t1 - t0, t2 - t1, time.perf_counter() - t2)
    return _parsed[path]


def share(part: "dict | None", name: str) -> "float | None":
    """A category's seconds over busy, in percent, averaged over the device
    planes as ``busy_s`` is; None where there is no partition."""
    return None if part is None else 100.0 * part["seconds"][name] / part["busy_s"]
