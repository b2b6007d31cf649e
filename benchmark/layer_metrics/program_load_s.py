"""Seconds the process has spent getting executables for lowered programs:
JAX's backend-compile events, each of which spans the look-up in the
persistent cache and then either the read and deserialisation of a stored
executable (``jax/cache_load_seconds`` is that part, and is NOT added again)
or a compile. None where the program's listener does not file the cache's
events (a program from before them)."""
from benchmark import program_trace


def read(ctx):
    if program_trace.total("jax/cache_load_seconds") is None:
        return None
    return program_trace.total("jax/backend_compile_seconds")
