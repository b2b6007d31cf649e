"""Test configuration: multi-device CPU mesh + float64 for numeric checks.

The JAX analogue of the reference's Spark local[*] harness
(photon-test-utils SparkTestUtils.scala:43-76): 8 virtual CPU devices via
--xla_force_host_platform_device_count, so every sharding/collective test
runs without TPU hardware (SURVEY.md §4).

Must run before jax initializes, hence the env mutation at import time.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Force CPU: a TPU host's ambient environment selects the chip
# (JAX_PLATFORMS=tpu,cpu there); tests must run on the 8-device virtual CPU
# mesh regardless, and child processes inherit the variable.
os.environ["JAX_PLATFORMS"] = "cpu"

# VERDICT r3 #9: the two-process e2e tests (test_multihost_e2e.py) are the
# only cross-process training evidence; run STRICT by default so a
# rendezvous regression fails the suite instead of silently skipping.
# Machines that genuinely cannot spawn the two workers opt out explicitly
# with PHOTON_ALLOW_MULTIHOST_SKIP=1.
if not os.environ.get("PHOTON_ALLOW_MULTIHOST_SKIP"):
    os.environ.setdefault("PHOTON_REQUIRE_MULTIHOST", "1")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
# NO persistent compile cache in the suite (util/compile_cache.py says why).
from photon_ml_tpu.util.compile_cache import disable_compile_cache  # noqa: E402

disable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # registered markers: the tier-1 command filters with -m 'not slow' and
    # the chaos suite (tests/test_resilience.py) tags its fault-injection
    # tests — registration keeps the suite warning-free under -q
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 suite (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection resilience test (dev/faultinject.py); "
        "must stay CPU-fast with bounded internal deadlines",
    )


def make_virtual_cpu_env(n_devices: int | None = None) -> dict:
    """Subprocess env for a virtual CPU mesh: force the CPU backend and pin
    the forced host device count (None = strip any inherited forcing, so
    the child sees exactly one device)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    if n_devices is not None:
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_classification(rng, n=200, d=8, dtype=np.float64):
    """Deterministic synthetic binary-classification data
    (reference SparkTestUtils generators)."""
    w_true = rng.normal(size=(d,))
    x = rng.normal(size=(n, d))
    logits = x @ w_true
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(dtype)
    return x.astype(dtype), y, w_true


def make_regression(rng, n=200, d=8, noise=0.1, dtype=np.float64):
    w_true = rng.normal(size=(d,))
    x = rng.normal(size=(n, d))
    y = x @ w_true + noise * rng.normal(size=n)
    return x.astype(dtype), y.astype(dtype), w_true


#: mappings at which compiled programs are dropped (the kernel's limit,
#: vm.max_map_count, is 65530; one test module can add ~15k)
_MAPPINGS_BUDGET = 30_000


def _mappings() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count, nothing to do
        return 0


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop compiled executables before the process runs out of mappings.

    Each XLA:CPU executable holds its JIT-ed code in its own memory
    mappings (~170 per test on the 8-device mesh) and jax's caches keep
    every executable alive, so a full run crossed the kernel's 65530
    mappings (vm.max_map_count) around its 480th test: an mmap inside the
    compiler — or inside the compile cache's (de)serializer — failed and
    the process died of SIGSEGV, taking every later test with it (the
    "cache-read segfault" that ended tier-1 runs near 57%). Checked when a
    module ends; clearing costs the next modules some recompiles, so it
    happens only past the budget."""
    yield
    if _mappings() > _MAPPINGS_BUDGET:
        jax.clear_caches()
        import gc

        gc.collect()
