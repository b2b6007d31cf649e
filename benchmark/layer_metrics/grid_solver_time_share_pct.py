"""Share of the device's busy seconds inside the window spent in the lanes'
solver outside the objective's products: the instructions the compiled
``glm/grid_solve`` files under ``owlqn/line_search`` (the loss over the
``[n, lanes]`` rows, the trial point, the tests), ``owlqn/pseudo_gradient``,
``lbfgs/direction`` and ``lbfgs/history``, in percent
(``benchmark/grid_scopes.py``)."""
from benchmark import grid_scopes


def read(ctx):
    part = grid_scopes.of_this_run()
    if part is None:
        return None
    return 100.0 * sum(part["seconds"][k] for k in grid_scopes.SOLVER) / part["busy_s"]
