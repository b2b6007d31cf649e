"""A λ grid's fit as lanes, its device seconds by where they go: a partition
of every busy instant inside the ``jit__jitted_grid_solve`` module events of
``bench:window`` by the ``jax.named_scope`` the instruction that ran was
traced under: ``glm/margins`` (``ops/objective.py``: the lanes' product over
the feature block, ``[n, d] x [d, lanes]``; the gradient's product carries
the same scope inside ``transpose(jvp(...))``), ``optim/owlqn.py``'s
``owlqn/pseudo_gradient`` and ``owlqn/line_search`` and ``optim/lbfgs.py``'s
``lbfgs/direction`` and ``lbfgs/history``.

The way of ``benchmark/path_sparse_scopes.py``, ``benchmark/path_scopes.py``
and ``benchmark/step_scopes.py``, which say why: the profiler's device events
name compiled instructions and carry no metadata, so the scopes come from the
program's own record of what it compiled,
``program_ledger.compiled_scopes("glm/grid_solve")``; an instant belongs to
the INNERMOST event that covers it (a ``while`` keeps what its body's events
leave uncovered); an instruction without metadata takes the ``op_name`` of
the event that encloses it; every event of a recorded name is held to the
record's SIGNATURE and on a mismatch the partition is nothing, never a share.
The SCOPE decides, and nothing is looked up by an instruction's name first
(PERF.md 7 row 9): a kernel that one day stands under ``glm/margins`` is the
evaluation's, whatever it is called.

Categories, by the INNERMOST of the five scopes in an ``op_name``:
``margins`` (``glm/margins`` outside a transpose: ``X W``), ``margins_t``
(``glm/margins`` behind ``transpose(``: ``X' R``, the gradient), ``search``
(``owlqn/line_search`` outside the product's scope: the loss and its
derivative over the ``[n, lanes]`` rows, the trial point and its projection,
the tests), ``pseudo_gradient`` (``owlqn/pseudo_gradient``: the orthant and
the direction's constraint too), ``history`` (``lbfgs/direction`` and
``lbfgs/history``), ``other`` (the first evaluation's row work, the stop
tests, the compiler's own copies, the relayout of X at entry). Their sum is
the solves' busy seconds.

Nothing without a device plane (the CPU), without a grid solve in the window,
with a program that keeps no record (a parent commit), or with a solve that
holds no ``glm/margins`` instruction (a program from before the scope).
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

GRID_MODULE = "jit__jitted_grid_solve"
LABEL = "glm/grid_solve"
CATEGORY_OF = {
    "glm/margins": "margins", "owlqn/line_search": "search",
    "owlqn/pseudo_gradient": "pseudo_gradient",
    "lbfgs/history": "history", "lbfgs/direction": "history",
}
CATEGORIES = ("margins", "margins_t", "search", "pseudo_gradient", "history",
              "other")
EVALUATION = ("margins", "margins_t")
SOLVER = ("search", "pseudo_gradient", "history")
#: a scope wherever it stands in an ``op_name``: after ``/`` or ``(`` or at
#: the start, before ``/`` or ``)`` or the end
_SCOPE = re.compile(r"(?<![^/(])(?:" + "|".join(map(re.escape, CATEGORY_OF))
                    + r")(?![^/)])")
#: the product's scope inside the wrapper autodiff gives a transposed equation
_TRANSPOSED = re.compile(r"transpose\((?:[^()]*\()*(?:[^()]*/)?glm/margins(?![^/)])")

_parsed: dict = {}  # xplane path -> partition: one a process


def category(op_name: "str | None") -> str:
    """The category of the innermost of the five scopes in an ``op_name`` (its
    last component, the primitive's own name, taken off); ``other`` outside
    all five."""
    path = (op_name or "").rpartition("/")[0]
    found = _SCOPE.findall(path)
    if not found:
        return "other"
    if found[-1] == "glm/margins" and _TRANSPOSED.search(path):
        return "margins_t"
    return CATEGORY_OF[found[-1]]


def partition(trace: dict, record, parse) -> "dict | None":
    """{"busy_s", "solve_s", "devices", "seconds": {category: s},
    "by_instruction": {(category, instruction): s}, "evaluation_events"},
    seconds a device, from ``load_xplane``'s lists, the program's record
    (instructions, entry loops) and its parse of an instruction's text; None
    where the module's docstring says so."""
    instructions = record[0]
    lo, hi = window_of(trace)
    devices = [dev for dev in trace["devices"].values() if dev["ops"]]
    seconds = dict.fromkeys(CATEGORIES, 0.0)
    by_instruction: dict = {}
    busy = 0.0
    solves_seen = evaluation_events = 0
    known: dict = {}  # an event's text -> its category; None: the enclosing event's
    for dev in devices:
        ops = _clip(dev["ops"], lo, hi)
        busy += sum(b - a for a, b in union_intervals(ops))
        solves = sorted((s, s + d) for text, s, d in _clip(dev["modules"], lo, hi)
                        if text.startswith(GRID_MODULE))
        starts = [s for s, _ in solves]
        inside = [(text, start, dur) for text, start, dur in ops
                  if (k := bisect.bisect_right(starts, start) - 1) >= 0
                  and start < solves[k][1]]
        stack: list = []  # [end, category, instruction] of the events open at the cursor
        cursor = lo

        def credit(top, span: float) -> None:
            seconds[top[1]] += span
            by_instruction[top[1], top[2]] = by_instruction.get((top[1], top[2]), 0.0) + span

        def advance(to: float) -> None:
            nonlocal cursor
            while stack and stack[-1][0] <= to:
                top = stack.pop()
                if top[0] > cursor:
                    credit(top, top[0] - cursor)
                    cursor = top[0]
            if stack and to > cursor:
                credit(stack[-1], to - cursor)
            cursor = max(cursor, to)

        for text, start, dur in sorted(inside, key=lambda e: (e[1], -e[2])):
            if text not in known:
                name, signature, whole = parse(text)
                recorded = instructions.get(name)
                if recorded is not None and not (
                        signature == recorded[0] if whole
                        else recorded[0].startswith(signature)):
                    return None  # the record is of another program
                known[text] = category(recorded[1]) if recorded else None
            advance(start)
            key = known[text]
            if key is None:  # no metadata: the enclosing event's
                key = stack[-1][1] if stack else "other"
            evaluation_events += key in EVALUATION
            stack.append([start + dur, key, instruction(text)])
        advance(hi)
        solves_seen += len(solves)
    if not solves_seen or not evaluation_events:
        return None
    ns = 1e9 * len(devices)
    by_category = {key: value / ns for key, value in seconds.items()}
    return {"busy_s": busy / ns, "solve_s": sum(by_category.values()),
            "devices": len(devices), "seconds": by_category,
            "by_instruction": {key: value / ns for key, value in by_instruction.items()},
            "evaluation_events": evaluation_events / len(devices)}


def _report(part: dict, load_s: float, record_s: float, partition_s: float) -> None:
    busy = part["busy_s"]
    print(f"grid scopes: trace loaded in {load_s:.2f} s, compiled_scopes in "
          f"{record_s:.2f} s, partition in {partition_s:.2f} s; grid solves "
          f"{part['solve_s']:.4f} s of busy {busy:.4f} s "
          f"({100 * part['solve_s'] / busy:.2f} %), {part['evaluation_events']:g} events "
          "under glm/margins; seconds by category: "
          + " ".join(f"{k}={v:.4f}" for k, v in part["seconds"].items()), flush=True)
    largest = sorted(part["by_instruction"].items(), key=lambda kv: -kv[1])[:16]
    print("grid scopes, the largest by category and instruction: "
          + " ".join(f"{c}:{i}={v:.4f}" for (c, i), v in largest), flush=True)


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


def evaluations_in_window(ctx) -> "tuple[int, int]":
    """(lock-step evaluations, the lanes' own evaluations summed) of the
    window's episodes, by the driver's count (``counters()
    ["grid_evaluations"]``: (episode start, lock-step, own))."""
    mine = [(lock, own) for start, lock, own in ctx["counters"].get("grid_evaluations", ())
            if start >= ctx["window_start"]]
    return sum(lock for lock, _ in mine), sum(own for _, own in mine)
