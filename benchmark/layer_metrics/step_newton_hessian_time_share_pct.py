"""Share of the devices' busy seconds inside the window that the fused step
spends under ``newton/hessian``: the lanes' ``[e, d, cap] x [e, cap, d]``
contraction (``ops/objective._weighted_gram``, precision "highest") and what
feeds it; in percent. One of ``benchmark/newton_scopes.py``'s four phases."""
from benchmark import newton_scopes


def read(ctx):
    return newton_scopes.share(newton_scopes.of_this_run(), "hessian")
