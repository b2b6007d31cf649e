"""Where the persistent XLA compile cache lives — decided in ONE place.

No reference analogue (the JVM had no ahead-of-time program cache to place).

The cache directory is part of the cache key's context and must not move
between runs, so it is never derived from ``tempfile``, a pid or the time:

- ``JAX_COMPILATION_CACHE_DIR`` set (the chip tool's machines set it; so may
  an operator): JAX reads it itself. This module sets NO path in code.
- unset: ``<checkout>/.jax_cache`` (git-ignored), derived from this
  package's location.

Called first thing by the four drivers' ``main()``, ``benchmark/run.py`` and
``chip_smoke.py`` — before the first compile. ``tests/conftest.py`` calls
:func:`disable_compile_cache` instead (see there for why).
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Idempotently point JAX at the cache directory; returns it."""
    import jax

    env = os.environ.get(_ENV)
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def disable_compile_cache() -> None:
    """Turn the persistent cache off for this process, whatever the
    environment says. The CPU test suite's and the CPU rehearsal's choice:
    full suite runs died of SIGSEGV inside ``compilation_cache.py``
    (``get_executable_and_time`` / ``put_executable_and_time``) with the
    cache on. The cause turned out to be the process running out of memory
    mappings (tests/conftest.py ``_release_compiled_programs`` fixes that —
    the compiler crashed the same way with the cache off), but a fresh
    checkout never has a warm cache to gain from, so it stays off there;
    whether it is safe to turn back on was not tested."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


def cache_dir_in_use() -> "str | None":
    """The directory JAX will actually cache into (None = caching off)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir
