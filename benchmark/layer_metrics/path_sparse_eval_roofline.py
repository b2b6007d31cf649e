"""An evaluation's share of its HBM roofline: the window's evaluations (the
driver's count) times the LEAST bytes one takes (``benchmark/roofline_sparse.py``:
every entry's index and value once, ``w`` and the gradient, the rows'
vectors; the counts are the data's, handed over by the driver), over the peak,
against the device seconds under ``sparse/*`` (``benchmark/path_sparse_scopes.py``).
Nothing where either is missing."""
from benchmark import path_sparse_scopes
from benchmark.roofline_sparse import eval_roofline_pct


def read(ctx):
    counters = ctx["counters"]
    evaluations = path_sparse_scopes.evaluations_in_window(ctx)
    part = path_sparse_scopes.of_this_run()
    if part is None or not evaluations or "sparse_shape" not in counters:
        return None
    seconds = sum(part["seconds"][key] for key in path_sparse_scopes.SPARSE)
    return eval_roofline_pct(evaluations, *counters["sparse_shape"], seconds,
                             ctx["device"]["kind"])
