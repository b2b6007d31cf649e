"""Share of the random-effect lane solves that stopped at ``max_iterations``
(neither tolerance met, no failed search), in percent:
``solver/lanes_max_iterations`` over ``solver/lane_solves`` (the valid lanes
of every bucket solve), all sweeps of the process. "Every lane pays
max_iter" is the pathology a too-tight tolerance makes of a warm-started
float32 lane; near zero at the accepted cells' ``rel_function_tolerance``.
Nothing on a program without the counters or before any lane was solved."""
from benchmark import program_trace


def read(ctx):
    at_cap = program_trace.total("solver/lanes_max_iterations")
    solves = program_trace.total("solver/lane_solves")
    if at_cap is None or not solves:
        return None
    return 100.0 * at_cap / solves
