"""Incremental-refresh telemetry: lane-selection counters + swap evidence.

No reference analogue as code: the reference's partial retraining
(CoordinateDescent.scala:44-49) locks whole coordinates and leaves no
evidence of what it saved; the refresh policy (algorithm/refresh.py) selects
at ENTITY granularity, so the acceptance criterion — strictly fewer RE
lane-solves than the full fit — must be COUNTED, not asserted in prose.
These metrics are that count: how many lanes each refresh selected (and
why), how many it carried over untouched, per coordinate and per run.

Names are constants so producers (algorithm/refresh.py) and consumers
(tests, journals, cli/game_training_driver.py) cannot drift —
the same contract as telemetry/serving_counters.py.
"""

from __future__ import annotations

from photon_ml_tpu.telemetry.registry import default_registry

#: prefix shared by every refresh metric (reset_refresh_metrics)
REFRESH_METRIC_PREFIX = "refresh/"
#: valid RE lanes the refresh could have re-solved (the full fit's count)
LANES_TOTAL = "refresh/lanes_total"
#: lanes the policy actually re-solved — the acceptance criterion is
#: lanes_solved < lanes_total, strictly
LANES_SOLVED = "refresh/lanes_solved"
#: lanes selected because their entity was DECLARED changed (new data)
LANES_CHANGED = "refresh/lanes_changed"
#: lanes selected because their resident-solution gradient exceeded the
#: policy tolerance (catches undeclared drift)
LANES_GRADIENT = "refresh/lanes_gradient"
#: coordinates whose entities were (partially) re-solved
COORDINATES_REFRESHED = "refresh/coordinates_refreshed"
#: coordinates carried over untouched (fixed effects, MF, no selection)
COORDINATES_CARRIED = "refresh/coordinates_carried"


def reset_refresh_metrics(registry=None) -> None:
    """Drop per-run refresh metrics — the training driver calls this at
    run start next to ``reset_resilience_metrics``, so a journal snapshot
    carries only this run's selection evidence."""
    reg = registry or default_registry()
    reg.remove_prefix(REFRESH_METRIC_PREFIX)


def record_selection(*, lanes_total: int, lanes_solved: int,
                     lanes_changed: int, lanes_gradient: int) -> None:
    """One refreshed coordinate's selection outcome."""
    reg = default_registry()
    reg.counter(LANES_TOTAL).inc(int(lanes_total))
    reg.counter(LANES_SOLVED).inc(int(lanes_solved))
    reg.counter(LANES_CHANGED).inc(int(lanes_changed))
    reg.counter(LANES_GRADIENT).inc(int(lanes_gradient))
    reg.counter(COORDINATES_REFRESHED).inc()


def record_carried_coordinate(n: int = 1) -> None:
    default_registry().counter(COORDINATES_CARRIED).inc(int(n))


def selection_evidence() -> dict:
    """The counters as a summary dict (driver summaries)."""
    reg = default_registry()
    return {
        "lanes_total": int(reg.counter(LANES_TOTAL).value),
        "lanes_solved": int(reg.counter(LANES_SOLVED).value),
        "lanes_changed": int(reg.counter(LANES_CHANGED).value),
        "lanes_gradient": int(reg.counter(LANES_GRADIENT).value),
        "coordinates_refreshed": int(
            reg.counter(COORDINATES_REFRESHED).value
        ),
        "coordinates_carried": int(reg.counter(COORDINATES_CARRIED).value),
    }
