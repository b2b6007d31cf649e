"""The fused GLM kernel's share of its HBM roofline: the bytes its calls had to
move, from the operand shapes in the trace, over the peak, against its
measured device time."""
from benchmark.roofline import kernel_roofline_from_trace


def read(ctx):
    return kernel_roofline_from_trace(ctx["trace"], ctx["device"]["kind"])
