"""Line-search trials a sweep's vmapped random-effect solves ran in lock-step
(iteration by iteration the slowest lane's count, all bucket solves of both
coordinates summed): the program's ``solver/lockstep_trials`` counter over
its ``train/sweeps``, all sweeps of the process."""
from benchmark import program_trace


def read(ctx):
    trials = program_trace.total("solver/lockstep_trials")
    sweeps = program_trace.total("train/sweeps")
    if trials is None or not sweeps:
        return None
    return trials / sweeps
