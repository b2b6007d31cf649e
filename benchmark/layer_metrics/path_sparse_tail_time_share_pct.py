"""Share of the device's busy seconds inside the window spent in the sparse
tail of the path's solves: the instructions the compiled ``glm/path_solve``
files under ``sparse/tail_margins`` and ``sparse/tail_gradient`` (the ELL
block's and the flat overflow's gathers, row sums and scatter-adds), in
percent (``benchmark/path_sparse_scopes.py`` says how an event finds its scope
and what reads as nothing)."""
from benchmark import path_sparse_scopes


def read(ctx):
    part = path_sparse_scopes.of_this_run()
    if part is None:
        return None
    seconds = part["seconds"]
    return 100.0 * (seconds["tail_margins"] + seconds["tail_gradient"]) / part["busy_s"]
