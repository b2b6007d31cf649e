"""Median host seconds from an episode's start to its first sweep: placement of
inputs and state on the mesh (inside the timed episode)."""
import statistics


def read(ctx):
    spans = ctx["spans"].durations("place", ctx["window_start"])
    return statistics.median(spans) if spans else None
