"""Tiny-size configurations and loaders shared by the benchmark's tests."""

from __future__ import annotations

import copy

from benchmark.manifest import find_cell, load_manifest, load_module

TINY = {
    "glmix-ml20m.sweeps": dict(
        rows=40000, validation_rows=4000,
        users=dict(count=300, min=20, max=9254, a=1.0),
        items=dict(count=250, min=1, max=16828, a=1.8)),
}
#: limits for the tiny size on the CPU, set as the chip's are (PERF.md 2):
#: above the gap the float32 run gives here, below the bfloat16 control's
#: (every seed poses the same fit, so one reading each; 40000 rows): loss at
#: own coefficients f32 4.2e-8, bf16 1.4e-5; validation margins at own
#: coefficients f32 4.4e-7, bf16 6.9e-3; user_coef f32 7.7e-4, bf16 1.2e-2;
#: item_coef f32 5.5e-4, bf16 1.2e-2; loss f32 7.5e-6, bf16 1.1e-4; val_auc
#: f32 4.0e-6; fe_coef f32 2.9e-4; norm f32 1.9e-4 (the last three are held
#: against a dropped sweep or a state returned unchanged, which read 1).
TINY_LIMITS = {
    "glmix": {"loss_own_coef_rel_gap": 4e-7, "val_margin_own_coef_max_gap": 3e-5,
              "loss_rel_gap": 4e-5, "val_auc_gap": 4e-5, "fe_coef_rel_l2": 3e-3,
              "user_coef_rel_l2": 2.5e-3, "item_coef_rel_l2": 2.5e-3,
              "norm_rel_gap": 1e-3},
}


def tiny_cell(workload: str, **overrides) -> dict:
    """find_cell's record of a real cell, its configuration cut to test size."""
    found = find_cell(load_manifest(), workload)
    found["config"] = copy.deepcopy(found["config"])
    found["config"].update(TINY[workload])
    found["config"]["limits"] = copy.deepcopy(TINY_LIMITS["glmix"])
    found["config"].update(overrides)
    return found


def driver_and_reference(found: dict):
    return load_module(found["driver"]), load_module(found["reference"])
