"""Bytes ONE value-and-gradient evaluation of a sparse GLM has to move, and the
share of the HBM roofline the measured seconds under ``sparse/*`` reach.

The count is the DATA's, whatever layout implements it: every (column, value)
entry is read once (a 4-byte index and a 4-byte value: the margins and the
gradient can share one read), the coefficients are read and the gradient is
written once (``2 d`` floats), and the rows' labels, weights and offsets are
read (``3 n`` floats). A hybrid layout's dense head reads ``n x k_hot`` floats
twice where this counts its entries once, and gathers and scatters move whole
memory transactions for four bytes: both show as a lower share. The yardstick
does not move when the implementation does."""

from __future__ import annotations

from benchmark.peaks import peaks_for


def eval_bytes(entries: int, rows: int, features: int) -> int:
    """One evaluation: the entries' indices and values once, ``w`` in and the
    gradient out, the rows' labels, weights and offsets."""
    return entries * 8 + (2 * features + 3 * rows) * 4


def eval_roofline_pct(evaluations: float, entries: int, rows: int, features: int,
                      sparse_seconds: float, device_kind: str) -> float:
    """Least time the chip could take for ``evaluations`` evaluations (their
    bytes over the peak bytes/s) over the measured seconds, in percent. Never
    clipped: a reading over 100 means time is missing, and has to show."""
    least = evaluations * eval_bytes(entries, rows, features) / peaks_for(
        device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / sparse_seconds
