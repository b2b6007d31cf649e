"""Of the rows the random-effect lanes' line searches passed over, the share
a live lane asked for, in percent: ``solver/row_trials_wanted`` (a bucket's
valid lanes' own trials x its ``cap``) over ``solver/row_trials_paid`` (its
lock-step trials x ``e x cap``: every lane, padding too, rides every trial of
its bucket's slowest search), all sweeps of the process. A trial in the
widest bucket weighs what it costs, a hundred times one in the narrowest.
100 where every lane wants every trial; what retiring or ordering lanes
moves. Nothing on a program without the counters or before a trial was paid.

The run also prints ``lanes:``, every coordinate's four counters a sweep
(``solver/<re|mf>/.../lockstep_trials|lockstep_iterations|row_trials_paid|
row_trials_wanted``) with its occupancy, and every family's (``solver/``,
``solver/mf_``) outer trips in lock-step, lanes by why they stopped and
occupancy: users against items without a scratch manifest."""
from benchmark import program_trace

COUNTS = ("lockstep_trials", "lockstep_iterations", "row_trials_paid",
          "row_trials_wanted")
FAMILY = ("lockstep_iterations", "lane_solves", "lanes_max_iterations",
          "lanes_function_tolerance", "lanes_gradient_tolerance",
          "lanes_search_failed")


def occupancy(prefix: str):
    """100 x wanted over paid of the family or coordinate ``prefix`` names."""
    wanted = program_trace.total(prefix + "row_trials_wanted")
    paid = program_trace.total(prefix + "row_trials_paid")
    if wanted is None or not paid:
        return None
    return 100.0 * wanted / paid


def lanes_line() -> "str | None":
    from photon_ml_tpu.telemetry.registry import default_registry

    counters = default_registry().snapshot()["counters"]
    sweeps = counters.get("train/sweeps")
    coordinates = sorted({key[:-len("lockstep_trials")] for key in counters
                          if key.startswith(("solver/re/", "solver/mf/"))
                          and key.endswith("/lockstep_trials")})
    if not sweeps or not coordinates:
        return None
    parts = []
    for prefix in coordinates:
        share = occupancy(prefix)
        parts.append(
            prefix[len("solver/"):-1] + " " + " ".join(
                f"{name}={counters.get(prefix + name, 0) / sweeps:.6g}"
                for name in COUNTS)
            + (f" occupancy={share:.2f}%" if share is not None else ""))
    for family in ("", "mf_"):
        if "solver/" + family + "lane_solves" in counters:
            share = occupancy("solver/" + family)
            parts.append(
                "family " + (family or "re_") + " " + " ".join(
                    f"{name}={counters.get('solver/' + family + name, 0) / sweeps:.6g}"
                    for name in FAMILY)
                + (f" occupancy={share:.2f}%" if share is not None else ""))
    return "lanes (a sweep): " + " | ".join(parts)


def read(ctx):
    line = lanes_line()
    if line is not None:
        print(line, flush=True)
    return occupancy("solver/")
