"""Share of the device's busy seconds inside the window spent in the
Hessian-vector products of the path's solves: the instructions the compiled
``glm/path_solve`` files under the scope ``tron/hv``, in percent
(``benchmark/path_scopes.py`` says how an event finds its scope and what
reads as nothing: no device plane, a program without the record or without
the scope)."""
from benchmark import path_scopes


def read(ctx):
    part = path_scopes.of_this_run()
    return None if part is None else 100.0 * part["seconds"]["hv"] / part["busy_s"]
