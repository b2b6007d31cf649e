"""Median host seconds of one coordinate-descent sweep, validation scoring
and its host read included."""
import statistics


def read(ctx):
    spans = ctx["spans"].durations("sweep", ctx["window_start"])
    return statistics.median(spans) if spans else None
