"""Of the rows the factorization's half-steps' line searches passed over, the
share a live lane asked for, in percent: ``solver/mf_row_trials_wanted`` over
``solver/mf_row_trials_paid`` (``sweeps_re_lane_occupancy_pct``'s ratio over
the ``mf_`` family: every bucket of both sides, every alternation), all sweeps
of the process. Nothing on a program without the counters (a parent, a
program with no such coordinate)."""
from benchmark import program_trace


def read(ctx):
    wanted = program_trace.total("solver/mf_row_trials_wanted")
    paid = program_trace.total("solver/mf_row_trials_paid")
    if wanted is None or not paid:
        return None
    return 100.0 * wanted / paid
