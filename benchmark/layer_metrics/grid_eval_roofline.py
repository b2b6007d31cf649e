"""The lanes' evaluations against their roofline: the window's LOCK-STEP
evaluations (the program's count, ``grid_lockstep_evals``' source) times what
ONE read of X, the vectors and the two products' flops take at the published
peaks (``benchmark/roofline_grid.py``; the operand's shape is the placed
batch's, handed over by the driver), against the device seconds under
``glm/margins`` and its transpose (``benchmark/grid_scopes.py``). Nothing
where either is missing or no evaluation was counted."""
from benchmark import grid_scopes
from benchmark.roofline_grid import grid_eval_roofline_pct


def read(ctx):
    lockstep, _ = grid_scopes.evaluations_in_window(ctx)
    part = grid_scopes.of_this_run()
    if not lockstep or part is None or "grid_operand" not in ctx["counters"]:
        return None
    seconds = sum(part["seconds"][k] for k in grid_scopes.EVALUATION)
    return grid_eval_roofline_pct(lockstep, *ctx["counters"]["grid_operand"],
                                  seconds, ctx["device"]["kind"])
