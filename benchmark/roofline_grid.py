"""Bytes and flops one LOCK-STEP evaluation of a λ grid's lanes has to move
and make, and the share of the roofline the measured seconds under the
evaluation's scopes reach.

The count is the ALGORITHM's, not an implementation's: value and gradient of
``lanes`` GLM objectives over one ``[rows, features]`` block cannot be made
without reading X once and need not read it twice (per row tile ``M = X W``,
``R = l'(M, y)``, ``G += X' R``), so an evaluation is held to ONE read of X,
the ``[rows]`` labels and weights, ``W`` in and ``G`` out, and to the two
products' ``4 * rows * features * lanes`` flops against the published
bfloat16 peak (no float32 matrix peak is published: a float32 product in six
bfloat16 passes shows as a sixth of it). An implementation that reads X twice
shows as a half; the yardstick does not move when the implementation does
(``benchmark/roofline_hv.py``'s words)."""

from __future__ import annotations

from benchmark.roofline import roofline_pct


def eval_bytes(rows: int, features: int, itemsize: int, lanes: int) -> int:
    """One evaluation of the block: X once, the [rows] labels and weights
    (float32), the [features, lanes] coefficients in and gradients out."""
    return rows * features * itemsize + 2 * rows * 4 + 2 * features * lanes * 4


def eval_flops(rows: int, features: int, lanes: int) -> int:
    """``X W`` and ``X' R``: a multiply and an add an element a lane, twice."""
    return 4 * rows * features * lanes


def grid_eval_roofline_pct(evaluations: float, rows: int, features: int,
                           itemsize: int, lanes: int, eval_seconds: float,
                           device_kind: str) -> float:
    """Least time the chip could take for ``evaluations`` lock-step
    evaluations (``roofline.roofline_pct``'s rule: the larger of their bytes
    over the peak bytes/s and their flops over the peak flop/s) over the
    measured seconds, in percent. Never clipped."""
    return roofline_pct(evaluations * eval_bytes(rows, features, itemsize, lanes),
                        evaluations * eval_flops(rows, features, lanes),
                        eval_seconds, device_kind)
