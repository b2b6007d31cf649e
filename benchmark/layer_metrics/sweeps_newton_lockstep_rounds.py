"""Newton rounds a sweep's vmapped random-effect solves ran in lock-step (for
every bucket solve the rounds of its slowest lane, all buckets of all
coordinates summed): the program's ``solver/newton_lockstep_rounds`` counter
over its ``train/sweeps``, all sweeps of the process. A ridge lane costs the
exact step and one check, so with B buckets a sweep this reads about 2 B, and
``max_iterations`` x B where the lanes have no stop at the float's floor
(PERF.md 6, PR 50). Nothing on a program without the counter."""
from benchmark import program_trace


def read(ctx):
    rounds = program_trace.total("solver/newton_lockstep_rounds")
    sweeps = program_trace.total("train/sweeps")
    if rounds is None or not sweeps:
        return None
    return rounds / sweeps
