"""chip_smoke.py has no way to pass off a TPU: on the CPU it stops at the
device report, prints no result line and exits non-zero naming the platform
it found."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.timeout(300)
def test_chip_smoke_on_cpu_exits_nonzero_naming_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=280,
    )
    assert proc.returncode != 0, proc.stdout
    assert "platform='cpu'" in proc.stderr and "not a TPU" in proc.stderr, proc.stderr[-800:]
    assert '"ok"' not in proc.stdout, proc.stdout
    # it stopped at phase 0: no trainer or server child was started
    assert "phase dv3" not in proc.stdout and "phase serve" not in proc.stdout
