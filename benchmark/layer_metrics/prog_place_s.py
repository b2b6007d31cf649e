"""Median per fit of the seconds from the start of the program's ``train/fit``
span to the start of its first ``train/sweep``: padding, preparing and placing
inputs and state, by the program's own marks."""
from benchmark import program_trace


def read(ctx):
    summary = program_trace.of(ctx)
    gaps = []
    for fit in program_trace.each(summary, "train/fit"):
        sweeps = program_trace.inside(summary, fit, "train/sweep")
        if sweeps:
            gaps.append((min(s[1] for s in sweeps) - fit[1]) / 1e9)
    return program_trace.median_of(gaps)
