"""Share of the device's busy seconds inside the window spent in the lanes'
two products over the feature block: the instructions the compiled
``glm/grid_solve`` files under the scope ``glm/margins`` and under its
transpose, in percent (``benchmark/grid_scopes.py`` says how an event finds
its scope and what reads as nothing)."""
from benchmark import grid_scopes


def read(ctx):
    part = grid_scopes.of_this_run()
    if part is None:
        return None
    return 100.0 * sum(part["seconds"][k] for k in grid_scopes.EVALUATION) / part["busy_s"]
