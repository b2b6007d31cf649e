"""Share of the devices' busy seconds inside the window that the fused step
spends under ``optim/newton.py``'s four scopes (``newton/hessian``,
``newton/solve``, ``newton/shrink``, ``newton/gradient``), all random-effect
coordinates summed; in percent. They lie inside ``step_lane_update``'s share
(``benchmark/step_scopes.py`` files them there); ``benchmark/newton_scopes.py``
says how the seconds are found and what reads as nothing."""
from benchmark import newton_scopes


def read(ctx):
    return newton_scopes.share(newton_scopes.of_this_run())
