"""Test harness: run everything on the CPU backend with 8 virtual devices so
multi-device mesh semantics are exercised without TPU hardware — the JAX
equivalent of the reference's Gloo-on-CPU distributed tests
(/root/reference/tests/test_algos/test_algos.py:16-38). The platform is
forced here (environment AND jax config) so the suite runs the same way on
a machine that has an accelerator; subprocesses inherit the environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("SHEEPRL_TPU_TEST", "1")

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# importing the package wires the persistent XLA compilation cache (honoring
# SHEEPRL_TPU_XLA_CACHE=0) and exports JAX_COMPILATION_CACHE_DIR so test
# SUBPROCESSES (CLI dry runs, flock actors) share one cache with the pytest
# process; identical-HLO graphs compile once per box, not once per process
import sheeprl_tpu  # noqa: F401

import jax

jax.config.update("jax_platforms", "cpu")


def _assert_cpu_backend() -> None:
    devices = jax.devices()
    assert devices[0].platform == "cpu", devices
    assert len(devices) == 8, devices


_assert_cpu_backend()


# ---------------------------------------------------------------------------
# Budget enforcement for the `timeout` marker. pytest-timeout is not in this
# image, so budgets are enforced with SIGALRM: the handler fires between
# Python bytecodes, which catches runaway Python loops, hung subprocess
# waits (EINTR) and stuck env workers. A single long-running C call (one XLA
# compile) defers the alarm until it returns — an accepted limitation, noted
# here so nobody mistakes this for a hard kill.
# ---------------------------------------------------------------------------
import signal

import pytest


class TestBudgetExceeded(BaseException):
    """BaseException so a library's broad `except Exception` cannot swallow
    the budget signal."""


@pytest.fixture(autouse=True)
def _enforce_timeout_marker(request):
    marker = request.node.get_closest_marker("timeout")
    if marker is None or not marker.args or not hasattr(signal, "SIGALRM"):
        yield
        return
    seconds = int(marker.args[0])

    def _expired(signum, frame):
        # re-arm before raising: if anything on the stack still manages to
        # absorb a BaseException, the budget keeps firing
        signal.alarm(30)
        raise TestBudgetExceeded(
            f"test exceeded its {seconds}s timeout budget"
        )

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(seconds)
    try:
        yield
    except TestBudgetExceeded:
        pytest.fail(f"test exceeded its {seconds}s timeout budget", pytrace=False)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _no_fault_plan_outlives_its_test():
    """`resilience.arm_faults` (and a main's `--faults`) exports the plan to
    the environment so that spawned env workers inherit it. A test that arms
    one used to leave it there — `monkeypatch.delenv(..., raising=False)` of
    a name that is unset records nothing to undo, and `reset_plan()` drops
    the parsed plan but not the variable — so the next in-process `main` of
    the same xdist worker re-armed it (`prepare_run` -> `arm_faults(None)`)
    and met an `env.step@3`, a `sigterm@3` or a `nan.grad@2` nobody asked
    for. Whatever a test does to the variable ends with the test."""
    from sheeprl_tpu.resilience import inject

    before = os.environ.get(inject.ENV_VAR)
    yield
    if os.environ.get(inject.ENV_VAR) != before:
        if before is None:
            del os.environ[inject.ENV_VAR]
        else:
            os.environ[inject.ENV_VAR] = before
        inject.reset_plan()
