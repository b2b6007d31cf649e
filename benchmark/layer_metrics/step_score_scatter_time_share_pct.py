"""Share of the devices' busy seconds inside the window that the fused step
spends in writing a coordinate's solved rows back and scoring it anew: scopes
``scatter`` (``table.at[rows].set`` of every bucket) and
``score/<coordinate>`` (each coordinate's margins over all the rows, at the
step's entry and after its solve); in percent. One of the seven shares of
``benchmark/step_scopes.py``, which says how an event finds its category and
what reads as nothing (no device plane, a program without the record, a text
that is not the trace's program's)."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.share(step_scopes.of_this_run(), "score_scatter")
