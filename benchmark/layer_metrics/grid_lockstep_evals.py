"""Evaluations of the WHOLE lane block in one fit of a λ grid (what the device
pays: one at the lanes' shared start, then trip by trip as many as the slowest
live lane's search asks): median per episode of the count the driver takes
from the lanes' ``SolverResult.line_search_trials``
(``drivers/glm_grid.py`` ``lockstep_evaluations``, handed over by
``counters()``). Nothing where the driver hands over no such count."""
import statistics


def read(ctx):
    counts = [lock for start, lock, _ in ctx["counters"].get("grid_evaluations", ())
              if start >= ctx["window_start"]]
    return statistics.median(counts) if counts else None
