"""From a profiler trace to numbers. The xplane file is first turned into
plain lists (``load_xplane``), so that the arithmetic below runs the same on
a small recorded trace kept with the tests.

Trace layout (TPU v5e, jax 0.9; read off a real trace in PR 23): one plane
per device named ``/device:TPU:<k>``. Its line ``XLA Ops`` holds one event
per executed HLO instruction, NAMED BY THE INSTRUCTION'S TEXT
(``%fusion.781 = (f32[17700]{...}, ...) fusion(f32[...] %param, ...)``), a
``while`` among them spanning the ops of its body; ``XLA Modules`` one
event per executed program (``jit__step_impl(<id>)``). The
plane ``/host:CPU`` holds the Python thread's ``TraceAnnotation`` events.
All on one clock, in ns.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}
#: the fused GLM kernel is a custom call named after its jitted wrapper
KERNEL = re.compile(r"_fused_padded|pallas")
#: instructions that only span the ops of their bodies: counting their time
#: would count that work twice
CONTAINERS = {"while", "conditional", "call"}
SPAN_PREFIX = "bench:"
NAME_CHARS = 400  # enough of an instruction's text for its first operands
_OPERAND = re.compile(r"(f32|bf16|f16|f64)\[(\d+),(\d+)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8}


def load_xplane(trace_dir: str) -> dict:
    """{"devices": {k: {"ops": [(text, start, dur)], "modules": [...]}},
    "host": [(name, start, dur)]} from the newest trace
    under the directory."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    dev[key] = [(e.name[:NAME_CHARS], float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [(e.name, float(e.start_ns), float(e.duration_ns))
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
    return out


def instruction(text: str) -> str:
    """``%multiply_reduce_fusion.413 = ...`` -> ``multiply_reduce_fusion``;
    ``jit__step_impl(4567)`` -> ``jit__step_impl``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", re.sub(r"\(\d+\)$", "", name))


def _shape(m) -> "tuple[int, int, int] | None":
    return (int(m.group(2)), int(m.group(3)), _ITEMSIZE[m.group(1)]) if m else None


def kernel_operand(text: str) -> "tuple[int, int, int] | None":
    """(n_pad, d_pad, itemsize) of the X a GLM kernel call was made with:
    the first 2-D operand of its custom call."""
    return _shape(_OPERAND.search(text.split("custom-call(", 1)[-1]))


def result_shape(text: str) -> "tuple[int, int, int] | None":
    """(rows, columns, itemsize) of the 2-D array an instruction produces;
    None for any other result (a tuple, a vector, a scalar)."""
    return _shape(_OPERAND.match(text.split(" = ", 1)[-1]))


def union_intervals(events) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of (name, start, dur) events."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def window_of(trace: dict, span: str = "bench:window") -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not spans:
        raise ValueError(f"the trace holds no {span} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(events, lo: float, hi: float):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce_trace(trace: dict) -> dict:
    """Busy and idle of the window, per-instruction and per-module device
    seconds, kernel seconds, calls and bytes, and idle gaps by host span —
    device numbers averaged over the devices that ran anything."""
    from benchmark.roofline import kernel_bytes, kernel_flops

    lo, hi = window_of(trace)
    devices = {k: v for k, v in trace["devices"].items() if v["ops"]}
    if not devices:
        raise ValueError("no operation ran on a device inside the trace")
    n = len(devices)
    busy = kernel = feed = 0.0
    kernel_calls = op_events = 0
    k_bytes = k_flops = 0
    by_op: dict[str, float] = {}
    by_module: dict[str, float] = {}
    gaps: dict[str, float] = {}
    host = [(name, s, s + d) for name, s, d in trace["host"]]
    for dev in devices.values():
        ops = [(instruction(t), s, d, t) for t, s, d in _clip(dev["ops"], lo, hi)]
        merged = union_intervals([e[:3] for e in ops])
        busy += sum(b - a for a, b in merged)
        leaves = [e for e in ops if e[0] not in CONTAINERS]
        op_events += len(leaves)
        fed = set()  # the padded X shapes the kernel was called with
        for name, _, dur, text in leaves:
            by_op[name] = by_op.get(name, 0.0) + dur
            if KERNEL.search(name):
                kernel += dur
                kernel_calls += 1
                shape = kernel_operand(text)
                if shape is not None:
                    fed.add(shape)
                    k_bytes += kernel_bytes(*shape)
                    k_flops += kernel_flops(shape[0], shape[1])
        # what makes the kernel's X: any other instruction whose result is an
        # array of exactly a shape the kernel was called with (the pad of X
        # to the kernel's tiles, a copy of it)
        if fed:
            feed += sum(dur for name, _, dur, text in leaves
                        if not KERNEL.search(name) and result_shape(text) in fed)
        for text, _, dur in _clip(dev["modules"], lo, hi):
            key = instruction(text)
            by_module[key] = by_module.get(key, 0.0) + dur
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):  # the idle stretches
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            inside = [(e - s, name) for name, s, e in host if s <= mid < e]
            owner = min(inside)[1] if inside else "outside-any-span"
            gaps[owner] = gaps.get(owner, 0.0) + (b - a)
    ns = 1e9 * n

    def ranked(table: dict) -> list:
        return sorted(((k, v / ns) for k, v in table.items()), key=lambda kv: -kv[1])

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / ns,
        "devices": n,
        "kernel_s": kernel / ns,
        "kernel_calls": kernel_calls / n,
        "kernel_bytes": k_bytes / n,
        "kernel_flops": k_flops / n,
        "kernel_feed_s": feed / ns,
        "op_events": op_events / n,
        "device_ops": ranked(by_op),
        "device_modules": ranked(by_module),
        "idle_gaps": ranked(gaps),
    }
