"""Share of the devices' busy seconds inside the window that the fused step
spends in instructions that carry no scope: top-level instructions without
metadata, which the compiler made (copies, the collectives it put in), and the
few the step traces outside every scope (the counts' sums); in percent. One of
the seven shares of ``benchmark/step_scopes.py``, which says how an event
finds its category and what reads as nothing (no device plane, a program
without the record, a text that is not the trace's program's)."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.share(step_scopes.of_this_run(), "unscoped")
