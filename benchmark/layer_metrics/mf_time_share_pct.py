"""Share of the device's busy seconds inside the window spent in the matrix-
factorization coordinate's instructions, in percent.

What marks an instruction as the coordinate's (found on the chip, PR 35): the
program runs every half-step under ``jax.named_scope("mf/<name>/<side>")``,
so the scope is part of the ``op_name`` in the metadata of every HLO
instruction it traced; but the profiler's device events carry NO such
metadata (their only stats are an offset and a duration; their name is the
instruction's text without its metadata). So the driver reads the scope
where it IS written, in the text of the compiled step
(``scoped_instructions``, handed over as the counter
``mf_scoped_instructions``), and this reader takes an event of a device
plane's ``XLA Ops`` line as the coordinate's when its instruction's NAME
(``%while.6556``) is one of those and it ran inside a ``jit__step_impl``
module event (names are unique within one program only). The coordinate's
seconds are the union of those events (a ``while`` of the coordinate spans
its body, whose compiler-made copies carry no metadata); busy is the union
of all ``XLA Ops`` events inside ``bench:window``, as ``device_idle_pct``
takes it; both averaged over the devices that ran anything.

The reader opens the run's xplane file itself (``program_trace.newest_xplane``,
through ``trace_reduce.load_xplane``, whose lists the arithmetic runs on).

The text read is of a SECOND compile of the step (the executable that ran is
not to be had from a ``jax.jit``), answered from the compile cache. Were it
another program (a missed cache, another layout), its instruction numbers
would name other instructions. So a scoped name is held to its SIGNATURE, the
result shape and the opcode (``signature``), in the trace too (the profiler
cuts a long event name short: such an event is held to the prefix it kept):
one event of a scoped name with another signature, or one of the step's own
scoped loops
(the ``while`` of every bucket's half-step, in the ENTRY computation: each
runs once a sweep) with no event in a step of the window, and the reader
gives nothing rather than a share of the wrong instructions.

A program whose step holds no such scope (a parent commit, a cell with no such
coordinate) reads nothing.
"""
import bisect
import os
import re

from benchmark import program_trace
from benchmark.trace_reduce import _clip, load_xplane, union_intervals, window_of

SCOPE = re.compile(r"(^|/)mf/[^/]+/(row|col)(/|$)")
STEP_MODULE = "jit__step_impl"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+ = .*?)(?:, metadata=\{[^}]*op_name="([^"]*)"|$)',
    re.MULTILINE)
_LAYOUT_OR_COMMENT = re.compile(r"\{[^{}]*\}|/\*.*?\*/|\s")
_CUT_LAYOUT_OR_COMMENT = re.compile(r"(\{[^{}]*|/\*[^/]*)$")


def _parse(text: str) -> "tuple[str, str, bool]":
    """(name, signature, whole) of an instruction's text. The profiler cuts a
    long event name short (a loop's tuple of shapes runs to thousands of
    characters): then no ``opcode(`` follows the shape, ``whole`` is False and
    the signature is what is left of it, a PREFIX of the whole one."""
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


def signature(text: str) -> "tuple[str, str]":
    """(name, result shape + opcode) of an instruction's text, as the compiled
    program's text and a device event's name both print it: ``%while.9 =
    (s32[], f32[8,32]{1,0}) while(...)`` -> ``("while.9",
    "(s32[],f32[8,32])while")``. Layouts, index comments and blanks go."""
    return _parse(text)[:2]


def scoped_instructions(hlo_text: str) -> "tuple[dict, frozenset]":
    """({name: signature} of the instructions of a compiled program whose
    ``op_name`` holds the coordinate's scope, the names among them that are
    ``while`` loops of the ENTRY computation)."""

    def scoped(text: str) -> dict:
        return dict(signature(line) for line, op_name in _INSTRUCTION.findall(text)
                    if op_name and SCOPE.search(op_name))

    entry = hlo_text.partition("\nENTRY ")[2].partition("\n}")[0]
    return scoped(hlo_text), frozenset(
        name for name, sig in scoped(entry).items() if sig.endswith(")while"))


def share(trace: dict, scoped: dict, loops=frozenset()) -> "float | None":
    """The percentage, from ``load_xplane``'s lists; None without a device
    that ran anything, without one event of a scoped name, or where the
    scoped names are not this trace's program's (the module's docstring)."""
    lo, hi = window_of(trace)
    busy = ours = 0.0
    for dev in trace["devices"].values():
        ops = [(*_parse(text), s, d) for text, s, d in _clip(dev["ops"], lo, hi)]
        busy += sum(b - a for a, b in union_intervals([(e[0], *e[3:]) for e in ops]))
        steps = sorted((s, s + d) for text, s, d in dev["modules"]
                       if text.startswith(STEP_MODULE))
        starts = [s for s, _ in steps]

        def in_step(start: float) -> bool:
            i = bisect.bisect_right(starts, start) - 1
            return i >= 0 and start < steps[i][1]

        mine = [e for e in ops if e[0] in scoped and in_step(e[3])]
        if any(sig != scoped[name] if whole else not scoped[name].startswith(sig)
               for name, sig, whole, _, _ in mine):
            return None
        if mine and set(loops) - {e[0] for e in mine}:
            return None
        ours += sum(b - a for a, b in union_intervals([(e[0], *e[3:]) for e in mine]))
    if not ours or not busy:
        return None
    return 100.0 * ours / busy


def read(ctx):
    counters = ctx.get("counters", {})
    scoped = counters.get("mf_scoped_instructions")
    loops = counters.get("mf_scoped_loops", frozenset())
    if not scoped:
        return None
    if "mf_trace" in ctx:  # a test's recorded lists
        return share(ctx["mf_trace"], scoped, loops)
    path = program_trace.newest_xplane()
    if path is None:
        return None
    # <trace dir>/plugins/profile/<time>/<host>.xplane.pb
    return share(load_xplane(os.path.normpath(os.path.join(path, *[".."] * 4))),
                 scoped, loops)
