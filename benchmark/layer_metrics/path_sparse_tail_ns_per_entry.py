"""What the sparse tail costs an entry: the device seconds under
``sparse/tail_margins`` and ``sparse/tail_gradient``
(``benchmark/path_sparse_scopes.py``) over the tail's entries (padding apart;
the layout's own count, handed over by the driver) x 2 passes (the margins'
gather, the gradient's scatter-add) x the window's evaluations (the driver's
count), in ns. Nothing where any of them is missing."""
from benchmark import path_sparse_scopes


def read(ctx):
    counters = ctx["counters"]
    evaluations = path_sparse_scopes.evaluations_in_window(ctx)
    part = path_sparse_scopes.of_this_run()
    if part is None or not evaluations or not counters.get("tail_entries"):
        return None
    seconds = part["seconds"]["tail_margins"] + part["seconds"]["tail_gradient"]
    return 1e9 * seconds / (counters["tail_entries"] * 2 * evaluations)
