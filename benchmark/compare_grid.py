"""The comparisons that decide ``correct`` for a λ grid fitted as lanes
(``drivers/glm_grid.py``): for a hundred λ a number a lane a kind would be
seven hundred lines, so each KIND is judged by QUARTER of the grid (the
configuration's order, largest λ first): the number compared is the largest
reading among the quarter's lanes, beside the quarter's limit, and the lane
that gave it is printed. A kind's limit is one number, or one for each quarter
(``{"q1": ..., "q4": ...}``) where λ moves the reading by orders.

Kind (a), at the program's OWN coefficients, so that how far a solver got
does not enter: the value and the pseudo-gradient norm the solve reported and
the validation margins the episode's scoring read, against float64. Kind
(b), against the reference's minimizers: the objective reached, the
coefficient vector, the validation AUC, the count of non-zeros.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark.compare import auc, max_gap_over_rms, rel_gap

QUARTERS = ("q1", "q2", "q3", "q4")


def quarter_of(lane: int, lanes: int) -> str:
    return QUARTERS[min(4 * lane // lanes, 3)]


def _limit(limits: dict, kind: str, quarter: str) -> float:
    limit = limits[kind]
    return float(limit[quarter] if isinstance(limit, dict) else limit)


def coef_gap(produced: np.ndarray, expected: np.ndarray) -> float:
    """Norm of the difference against the larger of the two norms (float64):
    0 where both vectors are zero (the largest λ's minimizer), 1 where one is
    zero and the other is not."""
    a = np.asarray(produced, np.float64)
    b = np.asarray(expected, np.float64)
    scale = max(np.linalg.norm(a), np.linalg.norm(b))
    return float(np.linalg.norm(a - b) / scale) if scale > 0.0 else 0.0


def by_quarter(kind: str, readings: list, lambdas, limits: dict) -> list:
    """[(``<quarter>_<kind>``, the largest reading of the quarter's lanes,
    the quarter's limit)]; a reading that is not finite is its quarter's."""
    lanes = len(readings)
    out = []
    for quarter in QUARTERS:
        mine = [(lane, float(r)) for lane, r in enumerate(readings)
                if quarter_of(lane, lanes) == quarter]
        if not mine:
            continue
        lane, worst = max(mine, key=lambda kv: kv[1] if np.isfinite(kv[1]) else np.inf)
        print(f"compare: {quarter}_{kind} is lane {lane}'s, lambda {lambdas[lane]:g}",
              file=sys.stderr, flush=True)
        out.append((f"{quarter}_{kind}", worst, _limit(limits, kind, quarter)))
    return out


def own_coefficient_comparisons(produced: dict, evaluated: dict, limits: dict) -> list:
    """Kind (a): ``evaluated`` is the reference's ``evaluate`` at the
    program's own coefficients."""
    lambdas = produced["lambdas"]
    lanes = range(len(lambdas))
    return (
        by_quarter("loss_own_coef_rel_gap", [
            rel_gap(produced["values"][k], evaluated["value"][k]) for k in lanes],
            lambdas, limits)
        + by_quarter("grad_norm_own_coef_gap", [
            # against the larger of the float64 norm and α λ, the scale of the
            # optimality conditions: the largest λ's lanes end at a
            # pseudo-gradient of zero, where a relative gap means nothing
            abs(produced["gradient_norms"][k] - evaluated["grad_norm"][k])
            / max(evaluated["grad_norm"][k], produced["l1_weights"][k])
            for k in lanes], lambdas, limits)
        + by_quarter("val_margin_own_coef_max_gap", [
            # (the first lane's margins are zero on both sides and read 0)
            max_gap_over_rms(produced["val_margin"][k], evaluated["val_margin"][k])
            for k in lanes], lambdas, limits))


def minimizer_comparisons(produced: dict, expected: dict, val_labels: np.ndarray,
                          limits: dict) -> list:
    """Kind (b): ``expected`` holds the reference's ``coefficients`` and its
    ``evaluate`` at them; a lane that was dropped reads as missing."""
    lambdas = produced["lambdas"]
    if (len(produced["coefficients"]) != len(expected["coefficients"])
            or list(lambdas) != list(expected["lambdas"])):
        return [("lanes_missing", 1.0, 0.0)]
    lanes = range(len(lambdas))
    features = produced["coefficients"].shape[1]
    return (
        by_quarter("loss_rel_gap", [
            rel_gap(produced["values"][k], expected["value"][k]) for k in lanes],
            lambdas, limits)
        + by_quarter("coef_rel_l2", [
            coef_gap(produced["coefficients"][k], expected["coefficients"][k])
            for k in lanes], lambdas, limits)
        + by_quarter("val_auc_gap", [
            abs(auc(produced["val_margin"][k], val_labels)
                - auc(expected["val_margin"][k], val_labels)) for k in lanes],
            lambdas, limits)
        + by_quarter("nonzero_share_gap", [
            abs(int(np.count_nonzero(produced["coefficients"][k]))
                - int(expected["nonzeros"][k])) / features for k in lanes],
            lambdas, limits))
