"""Line-search trials a sweep's vmapped matrix-factorization half-steps ran in
lock-step (iteration by iteration the slowest lane's count, all bucket solves
of both sides summed): the program's ``solver/mf_lockstep_trials`` counter
over its ``train/sweeps``, all sweeps of the process. Beside
``sweeps_re_lockstep_trials``, which counts the random effects alone. A
program without the counter (a parent commit) reads nothing."""
from benchmark import program_trace


def read(ctx):
    trials = program_trace.total("solver/mf_lockstep_trials")
    sweeps = program_trace.total("train/sweeps")
    if trials is None or not sweeps:
        return None
    return trials / sweeps
