"""Share of the devices' busy seconds inside the window that the fused step
spends in the entity lanes' line searches: scope ``lbfgs/line_search`` under
``re/<type>`` or ``mf/<name>/<side>``, the lock-step trials' value and
gradient and the search's own loop; in percent. One of the seven shares of
``benchmark/step_scopes.py``, which says how an event finds its category and
what reads as nothing (no device plane, a program without the record, a text
that is not the trace's program's)."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.share(step_scopes.of_this_run(), "lane_search")
