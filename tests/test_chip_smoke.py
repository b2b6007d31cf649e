"""chip_smoke.py: the chip check must fail everywhere but on a chip.

The real run happens on a TPU through the chip tool; what the CPU suite can
pin is (a) a plain invocation without an accelerator exits non-zero naming
the platform and prints no result, (b) the same in a directory holding the
script and nothing else of the repo, and (c) the explicit CPU rehearsal mode
drives every leg at tiny size, passes, and still never prints ``ok``.
"""

import json
import os
import shutil
import subprocess
import sys

from conftest import make_virtual_cpu_env

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(args, cwd=REPO_ROOT, script=SCRIPT, timeout=900):
    env = make_virtual_cpu_env(None)  # one CPU device, like a chipless host
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_plain_invocation_without_a_chip_refuses():
    proc = _run([])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "'cpu'" in proc.stderr and "refusing" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_in_a_directory_fails_without_a_result(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run([], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0
    assert "photon_ml_tpu" in proc.stderr  # the import that cannot succeed
    assert '"ok"' not in proc.stdout


def test_cpu_rehearsal_passes_and_never_reads_as_a_pass():
    proc = _run(["--rehearse-cpu"])
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and "ok" not in last
    assert last["device"]["platform"] == "cpu"
    assert sorted(last["legs_passed"]) == ["glm", "glmix", "kernel", "serve"]
    checks = [l for l in lines if "[ok]" in l or "[FAIL]" in l]
    assert checks and all(l.startswith("REHEARSAL ") for l in checks)
    assert not [l for l in checks if "[FAIL]" in l]
    # the legs really ran: driver-vs-numpy loss, served == batch, the kernel
    for needle in ("numpy loss of the SAVED model", "served scores == batch",
                   "kernel d=512 bfloat16", "glm-grid: vmapped lanes"):
        assert any(needle in l for l in checks), needle
