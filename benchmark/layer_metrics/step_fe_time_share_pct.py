"""Share of the devices' busy seconds inside the window that the fused step
spends in the fixed effects' solves: scopes ``fe/solve`` and
``extra_fe/<name>/solve``, the GLM kernel's launches, the un-vmapped solver
round them and, across chips, the gradient's ``psum``; in percent. One of the
seven shares of ``benchmark/step_scopes.py``, which says how an event finds
its category and what reads as nothing (no device plane, a program without the
record, a text that is not the trace's program's)."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.share(step_scopes.of_this_run(), "fe")
