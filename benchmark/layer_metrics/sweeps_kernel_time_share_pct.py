"""Share of the device's busy time spent in the fused GLM kernel."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["kernel_calls"]:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
