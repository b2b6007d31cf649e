"""Share of the random-effect lanes' line searches that the float's floor
ended (``optim/common.LINE_SEARCH_FLOOR_K``: a search whose claimable
decrease fell under an ulp of the value, not one its tests accepted), in
percent: ``solver/floor_exits`` over ``solver/line_searches`` (valid lanes,
one search an iteration; both counted since PR 25), all sweeps of the
process. Nothing on a program without the counters or before any search."""
from benchmark import program_trace


def read(ctx):
    floored = program_trace.total("solver/floor_exits")
    searches = program_trace.total("solver/line_searches")
    if floored is None or not searches:
        return None
    return 100.0 * floored / searches
