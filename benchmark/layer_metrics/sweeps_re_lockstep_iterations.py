"""Outer iterations a sweep's vmapped random-effect solves ran in lock-step
(for every bucket solve the trips of its slowest lane, padding or not, all
bucket solves of the coordinates summed: each trip costs EVERY lane of its
bucket a two-loop recursion, a history shift and a search, live or not): the
program's ``solver/lockstep_iterations`` counter over its ``train/sweeps``,
all sweeps of the process. Nothing on a program without the counter."""
from benchmark import program_trace


def read(ctx):
    trips = program_trace.total("solver/lockstep_iterations")
    sweeps = program_trace.total("train/sweeps")
    if trips is None or not sweeps:
        return None
    return trips / sweeps
