"""Hessian-vector products of one λ-path fit under a trust-region Newton
solver: median per episode of the sum, over the fit's solves, of the
``SolverResult.line_search_trials`` the episode already reads (``optim/tron.py``
files a round's CG steps there), handed over by the driver's ``counters()``.
0 for a program whose TRON counts nothing (a parent commit); nothing where
the driver hands over no such count."""
import statistics


def read(ctx):
    counts = [n for start, n in ctx["counters"].get("hv_products", ())
              if start >= ctx["window_start"]]
    return statistics.median(counts) if counts else None
