"""Objective evaluations of one λ-path fit: launches of the GLM kernel in the
traced window over the episodes it held (a count)."""


def read(ctx):
    episodes = len(ctx["spans"].durations("episode", ctx["window_start"]))
    calls = ctx["trace"]["kernel_calls"]
    return calls / episodes if episodes and calls else None
