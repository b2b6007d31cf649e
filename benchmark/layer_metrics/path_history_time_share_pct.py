"""Share of the device's busy seconds inside the window spent on the L-BFGS
history of the path's solves: the instructions the compiled ``glm/path_solve``
files under ``lbfgs/history`` (the pairs' dense shift) and ``lbfgs/direction``
(the two-loop recursion), in percent (``benchmark/path_sparse_scopes.py``):
what PERF.md's formula of 2 m d floats read and written an iteration
predicts at a d where the history is memory traffic."""
from benchmark import path_sparse_scopes


def read(ctx):
    part = path_sparse_scopes.of_this_run()
    return None if part is None else 100.0 * part["seconds"]["history"] / part["busy_s"]
