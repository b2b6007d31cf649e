"""The Newton lanes' Hessian pass against its HBM roofline: the window's
sweeps x the mean lock-step rounds of a bucket solve (the program's
``solver/newton_lockstep_rounds`` over its sweeps and the buckets a sweep
solves) x the bytes ONE read of every bucket's ``[e, cap, d]`` block and one
write of its ``[e, d, d]`` result take (``benchmark/roofline_newton.py``; the
blocks' shapes are the packed buckets', handed over by the driver), over the
peak, against the device seconds under ``newton/hessian``
(``benchmark/newton_scopes.py``). Nothing where any of them is missing."""
from benchmark import newton_scopes, program_trace
from benchmark.roofline_newton import newton_hessian_roofline_pct


def read(ctx):
    buckets = ctx["counters"].get("newton_buckets")
    rounds = program_trace.total("solver/newton_lockstep_rounds")
    sweeps = program_trace.total("train/sweeps")
    in_window = len(program_trace.each(program_trace.of(ctx), "train/sweep"))
    part = newton_scopes.of_this_run()
    if not buckets or not rounds or not sweeps or not in_window or part is None:
        return None
    if not part["seconds"]["hessian"]:
        return None
    return newton_hessian_roofline_pct(
        rounds / sweeps / len(buckets), buckets, in_window,
        part["seconds"]["hessian"], ctx["device"]["kind"])
