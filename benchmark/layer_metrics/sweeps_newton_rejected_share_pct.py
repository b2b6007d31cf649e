"""Share of the valid lanes' Newton rounds that accepted NO step-shrink
candidate (``solver/newton_rejected_rounds`` over ``solver/newton_lane_rounds``,
all sweeps of the process), in percent. A ridge lane's second round stands at
its minimum and about half of them read no candidate strictly lower: some 25 %
is rounding at the float's floor, not overshoot; a share that climbs is the
Levenberg damping at work. Nothing on a program without the counters, or
before any lane has run a round."""
from benchmark import program_trace


def read(ctx):
    rejected = program_trace.total("solver/newton_rejected_rounds")
    rounds = program_trace.total("solver/newton_lane_rounds")
    if rejected is None or not rounds:
        return None
    return 100.0 * rejected / rounds
