"""Median seconds of the program's own ``train/sweep`` span: one coordinate-
descent sweep as ``train_distributed`` marks it, from the step's dispatch to
the end of the caller's ``on_sweep``."""
from benchmark import program_trace


def read(ctx):
    return program_trace.median_of(
        program_trace.durations(program_trace.of(ctx), "train/sweep"))
