"""Median per episode of the seconds JAX spent tracing Python into jaxprs and
lowering them inside the window (the program's compile listener's
``jax/trace_seconds`` + ``jax/lower_seconds``, taken by the driver round every
timed fit): what a fit pays for building its solves anew on every call.
Nothing where the program files no such seconds."""
import statistics


def read(ctx):
    seconds = [s for start, s in ctx["counters"].get("retrace_s", ())
               if start >= ctx["window_start"]]
    return statistics.median(seconds) if seconds else None
