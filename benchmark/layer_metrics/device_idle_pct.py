"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
