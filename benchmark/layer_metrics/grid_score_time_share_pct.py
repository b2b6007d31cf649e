"""Share of the device's busy seconds inside the window spent scoring the
validation block, once a lane (``GeneralizedLinearModel.score``: the eager
``features @ means``, module ``jit_matmul``), in percent. Nothing in a run
without a grid fit's counts or without such a module in the window."""
SCORE_MODULE = "jit_matmul"


def read(ctx):
    if "grid_operand" not in ctx["counters"]:
        return None
    seconds = dict(ctx["trace"]["device_modules"]).get(SCORE_MODULE)
    return None if seconds is None else 100.0 * seconds / ctx["trace"]["busy_s"]
