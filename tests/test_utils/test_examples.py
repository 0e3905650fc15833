"""The examples/ must stay runnable: architecture_template drives the
player/buffer/trainer sub-mesh topology end-to-end on the virtual CPU mesh."""

import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.timeout(300)
def test_architecture_template_runs():
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "architecture_template.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
        env={
            "PATH": "/usr/bin:/bin",
            "PYTHONPATH": str(REPO),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "HOME": "/tmp",
        },
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "template ok" in proc.stdout
    assert "trainers: 7 devices" in proc.stdout
