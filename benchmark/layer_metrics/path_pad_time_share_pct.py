"""Share of the device's busy time spent MAKING the kernel's X operand: the
instructions (the pad of X to the kernel's tiles, a copy) whose result is an
array of exactly the padded shape the kernel was called with, from the
trace. Nothing where no kernel ran or nothing fed it."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["kernel_calls"] or not trace.get("kernel_feed_s"):
        return None
    return 100.0 * trace["kernel_feed_s"] / trace["busy_s"]
