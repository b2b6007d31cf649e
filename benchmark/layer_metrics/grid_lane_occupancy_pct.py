"""How much of what the device pays any lane wanted: the lanes' OWN
evaluations summed over the lanes, over lanes x the lock-step evaluations of
the window's fits, in percent (``drivers/glm_grid.py`` ``counters()``). 100
where every lane asks for every evaluation of the block; what the stopped-lane
rule moves, and a scheduler that retires lanes would. Nothing where the driver
hands over no such counts."""
from benchmark import grid_scopes


def read(ctx):
    lockstep, own = grid_scopes.evaluations_in_window(ctx)
    if not lockstep or "grid_operand" not in ctx["counters"]:
        return None
    lanes = ctx["counters"]["grid_operand"][3]
    return 100.0 * own / (lanes * lockstep)
