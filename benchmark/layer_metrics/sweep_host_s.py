"""Median per sweep of ``train/sweep`` less the spans inside it in which the
host waits for the device (``train/loss_wait``, ``train/validate/evaluate``):
host seconds of a sweep not spent waiting, the floor the host sets once the
device is faster."""
from benchmark import program_trace

WAITS = ("train/loss_wait", "train/validate/evaluate")


def read(ctx):
    summary = program_trace.of(ctx)
    return program_trace.median_of(
        (sweep[2] - sum(w[2] for name in WAITS
                        for w in program_trace.inside(summary, sweep, name))) / 1e9
        for sweep in program_trace.each(summary, "train/sweep"))
