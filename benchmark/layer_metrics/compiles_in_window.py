"""Programs compiled inside the measured window: misses of the persistent
compile cache (should be 0; look-ups that hit are not compiles)."""


def read(ctx):
    return ctx["counters"]["compiles_in_window"]
