"""The comparisons that decide ``correct``: program output against the
plain reference, one number each, each beside a limit of its own (the
limits stand in the configuration file, with their readings in PERF.md)."""

from __future__ import annotations

import sys

import numpy as np


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Exact area under the ROC curve, average ranks over ties."""
    from scipy.stats import rankdata

    ranks = rankdata(scores)
    pos = np.asarray(labels) > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def rel_gap(produced, expected) -> float:
    """|a - b| against |b|, for scalars."""
    return float(abs(float(produced) - float(expected)) / abs(float(expected)))


def rel_l2(produced: np.ndarray, expected: np.ndarray) -> float:
    """Norm of the difference against the reference's norm (f64)."""
    a = np.asarray(produced, np.float64)
    b = np.asarray(expected, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def max_gap_over_rms(produced, expected) -> float:
    """Widest gap between two sets of margins, against the rms of the
    expected ones (float64)."""
    ref = np.asarray(expected, np.float64)
    gap = np.abs(np.asarray(produced, np.float64) - ref)
    return float(gap.max() / max(np.sqrt(np.mean(ref * ref)), 1e-30))


def own_coefficient_comparisons(produced: dict, evaluated: dict, limits: dict) -> list:
    """What does not turn on how far a solver got: the loss the program
    reported after its last sweep, and the validation margins its scoring
    program gives at its final state, against what the reference computes in
    float64 from the generator's float32 rows AT THE PROGRAM'S OWN final
    coefficients. Sound float32 arithmetic sits at its rounding floor here;
    features held in a lower precision do not."""
    return [
        ("loss_own_coef_rel_gap", rel_gap(produced["losses"][-1], evaluated["loss"]),
         limits["loss_own_coef_rel_gap"]),
        ("val_margin_own_coef_max_gap",
         max_gap_over_rms(produced["val_margin"], evaluated["val_margin"]),
         limits["val_margin_own_coef_max_gap"]),
    ]


def glmix_comparisons(produced: dict, expected: dict, limits: dict) -> list:
    """Against the reference's own fit: per sweep the training loss and the
    validation AUC; the final coefficients of every coordinate; how far the
    fit moved from the zero state it starts in (a step that returns its
    state unchanged reads 1)."""
    out = []
    for k, (a, b) in enumerate(zip(produced["losses"], expected["losses"]), 1):
        out.append((f"loss_sweep{k}_rel_gap", rel_gap(a, b), limits["loss_rel_gap"]))
    for k, (a, b) in enumerate(zip(produced["val_auc"], expected["val_auc"]), 1):
        out.append((f"val_auc_sweep{k}_gap", abs(a - b), limits["val_auc_gap"]))
    if len(produced["losses"]) != len(expected["losses"]):
        out.append(("sweeps_missing", 1.0, 0.0))
    for name in ("fe", "user", "item"):
        out.append((f"{name}_coef_rel_l2", rel_l2(produced[name], expected[name]),
                    limits[f"{name}_coef_rel_l2"]))
        moved = np.linalg.norm(np.asarray(produced[name], np.float64))
        ref = np.linalg.norm(np.asarray(expected[name], np.float64))
        out.append((f"{name}_norm_rel_gap", abs(moved - ref) / ref,
                    limits["norm_rel_gap"]))
    return out


def judge(comparisons: list) -> bool:
    """Prints every number beside its limit, on standard error (a run's last
    lines there); True when all are inside."""
    ok = True
    for name, value, limit in comparisons:
        inside = bool(np.isfinite(value) and value <= limit)
        ok &= inside
        print(f"compare[{'ok' if inside else 'FAIL'}] {name}: {value:.6g} "
              f"(limit {limit:g})", file=sys.stderr, flush=True)
    return ok


def _limit(limits: dict, kind: str, lam) -> float:
    """A kind's limit: one number for every λ, or one for each λ."""
    limit = limits[kind]
    return float(limit[f"{lam:g}"] if isinstance(limit, dict) else limit)


def path_own_coefficient_comparisons(produced: dict, evaluated: dict,
                                     limits: dict) -> list:
    """A λ-path's numbers that do not turn on how far a solver got, for each
    λ: the objective value and the gradient norm the solve reported and the
    validation margins the episode's scoring read, against float64 AT THE
    PROGRAM'S OWN coefficients."""
    out = []
    for k, lam in enumerate(produced["lambdas"]):
        out += [
            (f"lambda{lam:g}_loss_own_coef_rel_gap",
             rel_gap(produced["values"][k], evaluated["value"][k]),
             _limit(limits, "loss_own_coef_rel_gap", lam)),
            (f"lambda{lam:g}_grad_norm_own_coef_rel_gap",
             rel_gap(produced["gradient_norms"][k], evaluated["grad_norm"][k]),
             _limit(limits, "grad_norm_own_coef_rel_gap", lam)),
            (f"lambda{lam:g}_val_margin_own_coef_max_gap",
             max_gap_over_rms(produced["val_margin"][k], evaluated["val_margin"][k]),
             _limit(limits, "val_margin_own_coef_max_gap", lam)),
        ]
    return out


def path_comparisons(produced: dict, expected: dict, val_labels: np.ndarray,
                     limits: dict) -> list:
    """Against the reference's exact minimizers, for each λ: the coefficient
    vector, the objective value reached (the reference's taken in float64 at
    its minimizer), the validation AUC; and that every λ of the path was
    fitted (a solve that returns its start unchanged reads 1 in the first)."""
    if len(produced["coefficients"]) != len(expected["coefficients"]):
        return [("lambdas_missing", 1.0, 0.0)]
    out = []
    for k, lam in enumerate(produced["lambdas"]):
        out += [
            (f"lambda{lam:g}_coef_rel_l2",
             rel_l2(produced["coefficients"][k], expected["coefficients"][k]),
             _limit(limits, "coef_rel_l2", lam)),
            (f"lambda{lam:g}_loss_rel_gap",
             rel_gap(produced["values"][k], expected["value"][k]),
             _limit(limits, "loss_rel_gap", lam)),
            (f"lambda{lam:g}_val_auc_gap",
             abs(auc(produced["val_margin"][k], val_labels)
                 - auc(expected["val_margin"][k], val_labels)),
             _limit(limits, "val_auc_gap", lam)),
        ]
    return out
