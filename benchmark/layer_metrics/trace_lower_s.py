"""Seconds the process has spent tracing Python into jaxprs (outermost traces
only) and lowering them to MLIR modules, by JAX's own monitoring events as the
program's compile listener files them (set-up: nothing compiles later)."""
from benchmark import program_trace


def read(ctx):
    parts = [program_trace.total("jax/trace_seconds"),
             program_trace.total("jax/lower_seconds")]
    return None if None in parts else sum(parts)
