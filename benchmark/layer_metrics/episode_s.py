"""Median host seconds of one whole episode (placement, sweeps, read) in the
window: the steady statistic beside `train_rows_per_s`, which is taken over
all the window's time and so shows a stall that this median ignores."""
import statistics


def read(ctx):
    spans = ctx["spans"].durations("episode", ctx["window_start"])
    return statistics.median(spans) if spans else None
