"""util/compile_cache.py: the one place the persistent cache is placed.

Runs in subprocesses: the helper mutates process-wide jax config, and the
suite itself runs with the persistent cache off (conftest.py)."""

import os
import subprocess
import sys

from conftest import make_virtual_cpu_env

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "from photon_ml_tpu.util import compile_cache as cc\n"
    "import jax\n"
    "before = cc.cache_dir_in_use()\n"
    "print(before); print(cc.configure_compile_cache()); "
    "print(cc.cache_dir_in_use())\n"
)


def _probe(env_dir):
    env = make_virtual_cpu_env(None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    return out


def test_env_set_means_no_path_is_set_in_code(tmp_path):
    want = str(tmp_path / "elsewhere")
    before, returned, after = _probe(want)
    # JAX read the variable itself; the helper changed nothing
    assert before == returned == after == want


def test_unset_means_the_fixed_path_under_the_checkout():
    before, returned, after = _probe(None)
    assert before == "None"
    assert returned == after == os.path.join(REPO_ROOT, ".jax_cache")


def test_suite_runs_with_the_persistent_cache_off():
    from photon_ml_tpu.util.compile_cache import cache_dir_in_use

    assert cache_dir_in_use() is None
