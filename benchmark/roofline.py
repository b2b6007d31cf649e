"""Bytes the fused GLM kernel has to move per call, from the shapes it was
CALLED with (the trace names every kernel call by its instruction text, the
padded operand shapes in it — never from the configuration: a PR that stops
padding changes the shapes and with them the count), and the share of the
HBM roofline a measured kernel time reaches."""

from __future__ import annotations

from benchmark.peaks import peaks_for


def kernel_bytes(n_pad: int, d_pad: int, itemsize: int) -> int:
    """One evaluation reads X once, the [n_pad, 3] f32 aux block once and w,
    and writes the gradient: every byte the algorithm needs, no more."""
    return n_pad * d_pad * itemsize + n_pad * 3 * 4 + 2 * d_pad * 4


def kernel_flops(n_pad: int, d_pad: int) -> int:
    """Margins (multiply + add per element) and gradient (multiply + add)."""
    return 4 * n_pad * d_pad


def roofline_pct(total_bytes: float, total_flops: float, kernel_seconds: float,
                 device_kind: str) -> float:
    """Least time the chip could take (the larger of bytes over peak bytes/s
    and flops over peak flop/s) over the measured kernel time, in percent.
    Never clipped: a reading over 100 means bytes are counted too high or
    time is missing, and has to show."""
    peaks = peaks_for(device_kind)
    least = max(total_bytes / peaks["hbm_bytes_per_s"],
                total_flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_seconds


def kernel_roofline_from_trace(trace: dict, device_kind: str):
    """None when the trace holds no kernel call or no operand shapes."""
    if not trace.get("kernel_calls") or not trace.get("kernel_bytes"):
        return None
    return roofline_pct(trace["kernel_bytes"], trace["kernel_flops"],
                        trace["kernel_s"], device_kind)
