"""Bytes a Hessian-vector product of a dense GLM has to move, and the share of
the HBM roofline the measured seconds under ``tron/hv`` reach.

The count is the ALGORITHM's, not an implementation's: ``(X' D X) v`` cannot be
made without reading X once, and need not read it twice (per row tile ``z = X
v``, ``u = d2 z``, ``acc += X' u``), so a product is held to ONE read of X, the
``[rows]`` vector of second derivatives and ``v``, and the ``[features]``
result it writes. An implementation that reads X two or three times shows as a
half or a third of the roofline; the yardstick does not move when the
implementation does."""

from __future__ import annotations

from benchmark.peaks import peaks_for


def hv_bytes(rows: int, features: int, itemsize: int) -> int:
    """One product: X once, the [rows] second derivatives (float32), ``v`` in
    and the [features] result out."""
    return rows * features * itemsize + rows * 4 + 2 * features * 4


def hv_roofline_pct(products: float, rows: int, features: int, itemsize: int,
                    hv_seconds: float, device_kind: str) -> float:
    """Least time the chip could take for ``products`` products (their bytes
    over the peak bytes/s; the arithmetic, 4 flops an element, is far under
    the vector unit's rate) over the measured seconds, in percent. Never
    clipped: a reading over 100 means time is missing, and has to show."""
    least = products * hv_bytes(rows, features, itemsize) / peaks_for(
        device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / hv_seconds
