"""A trust-region Newton path solve's device seconds by where they go: a
partition of every busy instant inside the ``jit__jitted_path_solve`` module
events of ``bench:window`` by the ``jax.named_scope`` the instruction that ran
was traced under (``optim/tron.py`` enters ``tron/cg``, ``tron/hv`` and
``tron/update``). ``path_hv_time_share_pct`` and ``path_hv_roofline`` read the
seconds under ``tron/hv``, the Hessian-vector products.

The way of ``benchmark/step_scopes.py``, which says why: the profiler's device
events name compiled instructions and carry no metadata, so the scopes come
from the program's own record of what it compiled,
``program_ledger.compiled_scopes("glm/path_solve")``; an instant belongs to the
INNERMOST event that covers it (a ``while`` keeps what its body's events leave
uncovered); an instruction without metadata takes the ``op_name`` of the event
that encloses it; every event of a recorded name is held to the record's
SIGNATURE and on a mismatch the partition is nothing, never a share.

Categories: ``hv`` (the innermost ``tron/`` scope of the ``op_name`` is
``tron/hv``), ``cg`` (``tron/cg``: the CG's vector work and its loop),
``update`` (``tron/update``), ``kernel`` (the GLM kernel's launches: a round's
value and gradient), ``copy_x`` (an instruction outside every scope whose
result is a 2-D array as large as the kernel's X operand: the relayout of X at
a solve's entry), ``other``. Their sum is the solves' busy seconds.

Nothing without a device plane (the CPU), without a path solve in the window,
with a program that keeps no record (a parent commit), or with a solve that
holds no ``tron/hv`` instruction (another solver).
"""
import bisect
import os
import re
import time

from benchmark import program_trace
from benchmark.trace_reduce import (
    KERNEL,
    _clip,
    instruction,
    kernel_operand,
    load_xplane,
    result_shape,
    union_intervals,
    window_of,
)

PATH_MODULE = "jit__jitted_path_solve"
LABEL = "glm/path_solve"
CATEGORIES = ("hv", "cg", "update", "kernel", "copy_x", "other")
#: a ``tron/`` scope wherever it stands in an ``op_name``: after ``/`` or
#: ``(`` or at the start, before ``/`` or ``)`` or the end
_TRON = re.compile(r"(?<![^/(])tron/(hv|cg|update)(?![^/)])")

_parsed: dict = {}  # xplane path -> partition: one a process


def tron_scope(op_name: "str | None") -> "str | None":
    """The innermost ``tron/`` scope of an ``op_name`` (its last component,
    the primitive's own name, taken off), None outside all three."""
    found = _TRON.findall((op_name or "").rpartition("/")[0])
    return found[-1] if found else None


def partition(trace: dict, record, parse) -> "dict | None":
    """{"busy_s", "solve_s", "devices", "seconds": {category: s}, "hv_events"},
    seconds a device, from ``load_xplane``'s lists, the program's record
    (instructions, entry loops) and its parse of an instruction's text; None
    where the module's docstring says so."""
    instructions = record[0]
    lo, hi = window_of(trace)
    devices = [dev for dev in trace["devices"].values() if dev["ops"]]
    seconds = dict.fromkeys(CATEGORIES, 0.0)
    busy = 0.0
    solves_seen = hv_events = 0
    known: dict = {}  # an event's text -> its category; None: the enclosing event's
    for dev in devices:
        ops = _clip(dev["ops"], lo, hi)
        busy += sum(b - a for a, b in union_intervals(ops))
        solves = sorted((s, s + d) for text, s, d in _clip(dev["modules"], lo, hi)
                        if text.startswith(PATH_MODULE))
        starts = [s for s, _ in solves]
        inside = [(text, start, dur) for text, start, dur in ops
                  if (k := bisect.bisect_right(starts, start) - 1) >= 0
                  and start < solves[k][1]]
        # the X the kernel was called with: what a relayout copy of it makes
        fed = {kernel_operand(text) for text, _, _ in inside
               if KERNEL.search(instruction(text))} - {None}
        stack: list = []  # [end, category] of the events open at the cursor
        cursor = lo

        def advance(to: float) -> None:
            nonlocal cursor
            while stack and stack[-1][0] <= to:
                end, key = stack.pop()
                if end > cursor:
                    seconds[key] += end - cursor
                    cursor = end
            if stack and to > cursor:
                seconds[stack[-1][1]] += to - cursor
            cursor = max(cursor, to)

        for text, start, dur in sorted(inside, key=lambda e: (e[1], -e[2])):
            if text not in known:
                name, signature, whole = parse(text)
                recorded = instructions.get(name)
                if recorded is not None and not (
                        signature == recorded[0] if whole
                        else recorded[0].startswith(signature)):
                    return None  # the record is of another program
                scope = tron_scope(recorded[1]) if recorded else None
                if KERNEL.search(instruction(text)):
                    key = "kernel"
                elif scope is not None:
                    key = scope
                elif recorded is None:
                    key = None  # no metadata: the enclosing event's
                else:
                    key = "other"
                if key in (None, "other") and result_shape(text) in fed:
                    key = "copy_x"
                known[text] = key
            advance(start)
            key = known[text]
            if key is None:
                key = stack[-1][1] if stack else "other"
            hv_events += key == "hv"
            stack.append([start + dur, key])
        advance(hi)
        solves_seen += len(solves)
    if not solves_seen or not hv_events:
        return None
    ns = 1e9 * len(devices)
    by_category = {key: value / ns for key, value in seconds.items()}
    return {"busy_s": busy / ns, "solve_s": sum(by_category.values()),
            "devices": len(devices), "seconds": by_category,
            "hv_events": hv_events / len(devices)}


def _report(part: dict, load_s: float, record_s: float, partition_s: float) -> None:
    busy = part["busy_s"]
    print(f"path scopes: trace loaded in {load_s:.2f} s, compiled_scopes in "
          f"{record_s:.2f} s, partition in {partition_s:.2f} s; path solves "
          f"{part['solve_s']:.4f} s of busy {busy:.4f} s "
          f"({100 * part['solve_s'] / busy:.2f} %), {part['hv_events']:g} events "
          "under tron/hv; seconds by category: "
          + " ".join(f"{k}={v:.4f}" for k, v in part["seconds"].items()), flush=True)


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
