"""Share of the device's busy seconds inside the window spent in the hybrid
layout's dense head: the instructions the compiled ``glm/path_solve`` files
under ``sparse/head`` (both dots over the [n, k_hot] block and the k_hot-sized
gather and scatter), in percent (``benchmark/path_sparse_scopes.py``)."""
from benchmark import path_sparse_scopes


def read(ctx):
    part = path_sparse_scopes.of_this_run()
    return None if part is None else 100.0 * part["seconds"]["head"] / part["busy_s"]
