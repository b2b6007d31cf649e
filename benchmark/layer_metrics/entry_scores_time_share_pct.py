"""Share of the devices' busy seconds inside the window spent in the ENTRY
program, ``module:jit__entry_scores_impl``: every coordinate's margins over
all the rows at a fit's start state, which fills the empty carry of a fit's
first sweep (``GameTrainProgram._carried``; the later sweeps start from the
margins the last one ended with and score nothing at their entry); in
percent, averaged over the devices like ``busy_s``. It is the same scoring
that ``step_score_scatter_time_share_pct`` reads inside the fused step, run
as a program of its own and so outside ``benchmark/step_scopes.py``'s sight,
which keeps to the ``jit__step_impl`` events: **scoring costs the sum of the
two.** Nothing where no such module ran in the window: a program from before
the carry (a parent commit scores at every step's entry, inside the step),
no device plane."""

MODULE = "jit__entry_scores_impl"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = dict(trace.get("device_modules", ())).get(MODULE)
    return None if seconds is None else 100.0 * seconds / trace["busy_s"]
