"""Objective evaluations of the fixed-effect solve per sweep: launches of the
GLM kernel in the traced window over the sweeps it held (a count)."""


def read(ctx):
    sweeps = len(ctx["spans"].durations("sweep", ctx["window_start"]))
    calls = ctx["trace"]["kernel_calls"]
    return calls / sweeps if sweeps and calls else None
