"""Median seconds of the program's ``train/validate`` span: scoring the
validation split after a sweep and reducing its metrics to scalars."""
from benchmark import program_trace


def read(ctx):
    return program_trace.median_of(
        program_trace.durations(program_trace.of(ctx), "train/validate"))
