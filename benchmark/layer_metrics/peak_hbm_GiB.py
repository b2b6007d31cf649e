"""Peak bytes in use on the fullest chip after the window, in GiB."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 2.0**30
