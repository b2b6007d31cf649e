"""The comparisons that decide ``correct``: program output against the
plain reference, one number each, each beside a limit of its own (the
limits stand in the configuration file, with their readings in PERF.md)."""

from __future__ import annotations

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


def own_coefficient_comparisons(produced: dict, evaluated: dict, limits: dict) -> list:
    """What does not turn on how far a solver got: the loss the program
    reported after its last sweep, and the validation margins its scoring
    program gives at its final state, against what the reference computes in
    float64 from the generator's float32 rows AT THE PROGRAM'S OWN final
    coefficients. Sound float32 arithmetic sits at its rounding floor here;
    features held in a lower precision do not."""
    ref = np.asarray(evaluated["val_margin"], np.float64)
    gap = np.abs(np.asarray(produced["val_margin"], np.float64) - ref)
    return [
        ("loss_own_coef_rel_gap", rel_gap(produced["losses"][-1], evaluated["loss"]),
         limits["loss_own_coef_rel_gap"]),
        ("val_margin_own_coef_max_gap",
         float(gap.max() / max(np.sqrt(np.mean(ref * ref)), 1e-30)),
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
    """Prints every number beside its limit; True when all are inside."""
    ok = True
    for name, value, limit in comparisons:
        inside = bool(np.isfinite(value) and value <= limit)
        ok &= inside
        print(f"compare[{'ok' if inside else 'FAIL'}] {name}: {value:.6g} "
              f"(limit {limit:g})", flush=True)
    return ok
