"""Share of the devices' busy seconds inside the window that the fused step
spends in the rest of the entity lanes' solves: anything under ``re/<type>``
or ``mf/<name>/<side>`` that is no search, gather or scatter (the two-loop
recursion, the history's shift, the keep-or-not selects, the stop tests, the
first evaluation, the loops' own overhead); in percent. One of the seven
shares of ``benchmark/step_scopes.py``, which says how an event finds its
category and what reads as nothing (no device plane, a program without the
record, a text that is not the trace's program's)."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.share(step_scopes.of_this_run(), "lane_update")
