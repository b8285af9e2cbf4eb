"""chip_smoke.py's refusal contract, checked without a chip: it exits
non-zero and prints no result when jax finds no accelerator (dying at
its device phase, so this is fast) and when it is run without the
checkout it drives."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_fails_at_the_device_phase_on_cpu():
    out = _run(SMOKE, REPO)
    assert out.returncode != 0, out.stdout + out.stderr
    # the message names the platform it found, on a line tagged with it
    assert "phase=device FAILED" in out.stdout
    assert "platform is 'cpu', not 'tpu'" in out.stdout
    assert "[chip_smoke cpu " in out.stdout
    # no later phase ran and no result object was printed
    assert "phase=kernels" not in out.stdout
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_without_the_checkout_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "keystone_tpu" in out.stderr
