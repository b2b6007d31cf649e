"""The fused step's device seconds under ``optim/newton.py``'s four scopes:
``newton/hessian`` (the ``[e, d, cap] x [e, cap, d]`` contraction and what
feeds it), ``newton/solve`` (the elimination), ``newton/shrink`` (the
candidates' value pass) and ``newton/gradient`` (the value and gradient at the
accepted point), by random-effect coordinate.

No partition of its own: the seconds are ``benchmark/step_scopes.partition``'s
(the innermost event owns an instant, an instruction without metadata takes
its enclosing event's ``op_name``, every event of a recorded name is held to
the record's signature and every ENTRY loop to an event in every step, a
mismatch is nothing and never a share), called on the program's own record of
what it compiled with ONE thing changed: an instruction traced under
``newton/<phase>`` has ``<its coordinate>~newton.<phase>/`` put in front of its
``op_name``. That partition files seconds by (category, coordinate, solver
phase) and finds the coordinate by the FIRST ``re/<name>`` in an ``op_name``,
so the mark comes back as the coordinate of the key and the phases are read
off the keys; the category (``lane_update``) and every other instruction's key
are what ``step_scopes`` gives them.

Nothing without a device plane (the CPU), without a step in the window, with a
program that keeps no record (a parent commit), or with a step that holds no
instruction under a ``newton/`` scope (a program from before them, or one
whose lanes another solver runs).
"""
import os
import re
import time

from benchmark import program_trace, step_scopes
from benchmark.trace_reduce import load_xplane

PHASES = ("hessian", "solve", "shrink", "gradient")
MARK = "~newton."
#: the innermost ``newton/<phase>`` of an ``op_name``, wherever it stands
_NEWTON = re.compile(r"(?<![^/(])newton/(" + "|".join(PHASES) + r")(?![^/)])")

_parsed: dict = {}  # xplane path -> seconds: one a process


def marked(instructions: dict) -> dict:
    """The record's {instruction: (signature, op_name)} with the mark in front
    of every ``op_name`` under a ``newton/`` scope (the primitive's own name,
    its last component, is no scope)."""
    out = {}
    for name, (signature, op_name) in instructions.items():
        found = _NEWTON.findall((op_name or "").rpartition("/")[0])
        if found:
            coordinate = step_scopes.coordinate(op_name)
            if not coordinate.startswith("re/"):
                coordinate = "re/-"  # a Newton solve outside a random effect
            op_name = f"{coordinate}{MARK}{found[-1]}/{op_name}"
        out[name] = (signature, op_name)
    return out


def seconds_by_phase(trace: dict, record, parse) -> "dict | None":
    """{"busy_s", "step_s", "seconds": {phase: s}, "by_coordinate":
    {(coordinate, phase): s}, "categories": the step's seconds by
    ``step_scopes``' seven categories}, seconds a device, from
    ``load_xplane``'s lists, the program's record and its parse of an
    instruction's text; None where the module's docstring says so."""
    instructions, entry_loops = record
    instructions = marked(instructions)
    if not any(MARK in op_name for _, op_name in instructions.values() if op_name):
        return None
    part = step_scopes.partition(trace, (instructions, entry_loops), parse)
    if part is None:
        return None
    seconds = dict.fromkeys(PHASES, 0.0)
    by_coordinate: dict = {}
    for (_category, coordinate, _phase), value in part["by_coordinate"].items():
        if MARK in coordinate:
            name, phase = coordinate.split(MARK)
            seconds[phase] += value
            by_coordinate[name, phase] = by_coordinate.get((name, phase), 0.0) + value
    return {"busy_s": part["busy_s"], "step_s": part["step_s"], "seconds": seconds,
            "by_coordinate": by_coordinate, "categories": part["seconds"]}


def of_this_run() -> "dict | None":
    """This run's seconds, from the newest xplane file under the work directory
    and the program's own record: once a process, after the window."""
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
        record = None
        if any(dev["ops"] for dev in trace["devices"].values()):
            record = compiled_scopes(step_scopes.LABEL)
        part = _parsed[path] = None if record is None else seconds_by_phase(
            trace, record, program_ledger.parse_instruction)
        if part is not None:
            print(f"newton scopes: read in {time.perf_counter() - t0:.2f} s; the step "
                  f"{part['step_s']:.4f} s of busy {part['busy_s']:.4f} s a device, by "
                  "category: "
                  + " ".join(f"{k}={v:.4f}" for k, v in part["categories"].items())
                  + "; under newton/: "
                  + " ".join(f"{k}={v:.4f}" for k, v in part["seconds"].items())
                  + "; by coordinate: " + " ".join(
                      f"{c}:{p}={v:.4f}" for (c, p), v in sorted(
                          part["by_coordinate"].items(), key=lambda kv: -kv[1])),
                  flush=True)
    return _parsed[path]


def share(part: "dict | None", *phases: str) -> "float | None":
    """The phases' seconds (all four where none is named) over busy, in
    percent; None where there is nothing to read."""
    if part is None:
        return None
    return 100.0 * sum(part["seconds"][p] for p in phases or PHASES) / part["busy_s"]
