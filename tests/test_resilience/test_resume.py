"""Crash-safe training receipts (ISSUE 12): the bit-exact SIGTERM resume
twin (jax-env PPO in subprocesses), the resumable rc contract, auto-resume
resolution, corrupt-checkpoint fallback, and the SAC sampler-state restore.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sheeprl_tpu import resilience
from sheeprl_tpu.resilience.guard import RC_PREEMPTED

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("SHEEPRL_TPU_FAULTS", raising=False)
    resilience.reset_plan()
    yield
    resilience.reset_plan()


def _events(log_dir):
    path = os.path.join(log_dir, "telemetry.jsonl")
    with open(path) as fh:
        return [json.loads(l) for l in fh if l.strip()]


def _run_ppo(extra, timeout=240):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    env.pop("SHEEPRL_TPU_FAULTS", None)
    # single-device children: the pytest process's 8-virtual-device XLA_FLAGS
    # would force num_envs % 8 == 0 on this tiny receipt
    env.pop("XLA_FLAGS", None)
    base = [
        sys.executable, "-m", "sheeprl_tpu", "ppo",
        "--env_backend", "jax", "--num_envs", "2", "--rollout_steps", "8",
        "--total_steps", "96", "--checkpoint_every", "2", "--seed", "3",
        "--test_episodes", "0",
    ]
    return subprocess.run(
        base + extra, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout,
    )


@pytest.mark.timeout(420)
def test_sigterm_resume_is_bit_exact_vs_uninterrupted_twin(tmp_path):
    """THE resume receipt: a jax-env PPO run killed by an injected SIGTERM
    at update 3 and resumed with --resume auto must land on the SAME final
    checkpoint — params, opt-state, loop PRNG and collector ring state — as
    its uninterrupted twin, bit for bit."""
    twin_a = str(tmp_path / "a")
    twin_b = str(tmp_path / "b")
    a = _run_ppo(["--root_dir", twin_a, "--run_name", "x"])
    assert a.returncode == 0, a.stderr[-2000:]

    b = _run_ppo(["--root_dir", twin_b, "--run_name", "x", "--faults", "sigterm@3"])
    assert b.returncode == RC_PREEMPTED, (b.returncode, b.stderr[-2000:])
    ev = _events(os.path.join(twin_b, "x"))
    names = [e["event"] for e in ev]
    assert "fault.injected" in names and "preempt.signal" in names
    preempt = [e for e in ev if e["event"] == "preempt"]
    assert preempt and preempt[0]["rc"] == RC_PREEMPTED
    assert preempt[0]["step"] == 3
    # the grace checkpoint of the in-flight step committed before exit
    assert os.path.isdir(os.path.join(twin_b, "x", "checkpoints", "ckpt_3"))

    c = _run_ppo(["--root_dir", twin_b, "--run_name", "x", "--resume", "auto"])
    assert c.returncode == 0, c.stderr[-2000:]
    ev = _events(os.path.join(twin_b, "x"))
    resume = [e for e in ev if e["event"] == "resume"]
    assert resume and resume[-1]["checkpoint"].endswith("ckpt_3")

    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    import jax

    final_a = load_checkpoint(os.path.join(twin_a, "x", "checkpoints", "ckpt_6"))
    final_c = load_checkpoint(os.path.join(twin_b, "x", "checkpoints", "ckpt_6"))
    leaves_a = jax.tree_util.tree_leaves(final_a)
    leaves_c = jax.tree_util.tree_leaves(final_c)
    assert len(leaves_a) == len(leaves_c)
    for x, y in zip(leaves_a, leaves_c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # deep state: loop PRNG + collector carry (env-state "ring head")
    ra = np.load(os.path.join(twin_a, "x", "checkpoints", "ckpt_6.resume.npz"))
    rc = np.load(os.path.join(twin_b, "x", "checkpoints", "ckpt_6.resume.npz"))
    assert sorted(ra.files) == sorted(rc.files)
    for k in ra.files:
        np.testing.assert_array_equal(ra[k], rc[k])


@pytest.mark.timeout(420)
def test_sigkill_has_no_grace_but_auto_resume_recovers(tmp_path):
    """The no-grace site: SIGKILL at step k leaves no grace checkpoint and
    no clean telemetry tail — auto-resume must recover from the last
    PERIODIC checkpoint and run to completion anyway."""
    from sheeprl_tpu.utils.checkpoint import list_checkpoints

    root = str(tmp_path / "k")
    # SIGKILL can land while an ASYNC periodic save is still an
    # orbax-checkpoint-tmp dir (observed: only ckpt_2's tmp dir on disk when
    # killing at step 4 on a busy box) — that save is simply LOST, which is
    # the point of validating commit markers on resume. ckpt_4's save begins
    # by draining ckpt_2's (one outstanding save), so by the kill at step 6
    # at least ckpt_2 is durably committed; ckpt_4 may or may not be.
    b = _run_ppo(["--root_dir", root, "--run_name", "x", "--faults", "sigkill@6"])
    assert b.returncode in (-9, 137), b.returncode
    ckdir = os.path.join(root, "x", "checkpoints")
    valid = list_checkpoints(ckdir)
    assert valid, os.listdir(ckdir)
    assert all(v.endswith(("ckpt_2", "ckpt_4")) for v in valid), valid

    c = _run_ppo(["--root_dir", root, "--run_name", "x", "--resume", "auto"])
    assert c.returncode == 0, c.stderr[-2000:]
    ev = _events(os.path.join(root, "x"))
    resume = [e for e in ev if e["event"] == "resume"]
    assert resume and resume[-1]["checkpoint"] == valid[0]
    assert os.path.isdir(os.path.join(root, "x", "checkpoints", "ckpt_6"))


# ---------------------------------------------------------------------------
# in-process receipts (no subprocess cost)
# ---------------------------------------------------------------------------


def test_resolve_resume_auto_picks_newest_valid_and_explicit_path(tmp_path):
    import jax.numpy as jnp

    from sheeprl_tpu.utils.checkpoint import save_checkpoint

    class _Args:
        resume = "auto"
        eval_only = False
        checkpoint_path = None
        root_dir = str(tmp_path)
        run_name = "r"
        env_id = "CartPole-v1"

    ckdir = tmp_path / "r" / "checkpoints"

    class _A:
        def as_dict(self):
            return {"seed": 0}

    save_checkpoint(str(ckdir / "ckpt_2"), {"x": jnp.ones(1)}, args=_A(), block=True)
    save_checkpoint(str(ckdir / "ckpt_5"), {"x": jnp.ones(1)}, args=_A(), block=True)
    # a partial write: directory without the orbax commit marker
    (ckdir / "ckpt_9").mkdir()
    args = _Args()
    found = resilience.resolve_resume(args, "ppo")
    assert found and found.endswith("ckpt_5")
    assert args.checkpoint_path == found
    # corrupt ckpt_9 was skipped and is NOT in the fallback list
    assert resilience.next_fallback(found).endswith("ckpt_2")
    assert resilience.next_fallback(resilience.next_fallback(found)) is None

    # explicit path mode
    args2 = _Args()
    args2.resume = str(ckdir / "ckpt_2")
    args2.checkpoint_path = None
    assert resilience.resolve_resume(args2, "ppo").endswith("ckpt_2")
    # unknown path rejects loudly
    args3 = _Args()
    args3.resume = str(tmp_path / "nope")
    args3.checkpoint_path = None
    with pytest.raises(ValueError, match="not a checkpoint directory"):
        resilience.resolve_resume(args3, "ppo")


def test_restore_falls_back_past_corrupt_arrays(tmp_path):
    """A checkpoint can pass the marker check yet hold truncated array
    bytes; load_checkpoint must fall back to the previous valid candidate
    of the auto-resume run instead of dying."""
    import jax.numpy as jnp

    from sheeprl_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    class _A:
        def as_dict(self):
            return {"seed": 0}

    ckdir = tmp_path / "r" / "checkpoints"
    save_checkpoint(str(ckdir / "ckpt_1"), {"x": jnp.arange(4.0)}, args=_A(), block=True)
    save_checkpoint(str(ckdir / "ckpt_2"), {"x": jnp.arange(4.0) * 2}, args=_A(), block=True)
    # corrupt ckpt_2's array payload (markers intact)
    for root, _dirs, files in os.walk(ckdir / "ckpt_2"):
        for f in files:
            if "METADATA" not in f and "manifest" not in f.lower():
                p = os.path.join(root, f)
                with open(p, "wb") as fh:
                    fh.write(b"garbage")

    class _Args:
        resume = "auto"
        eval_only = False
        checkpoint_path = None
        root_dir = str(tmp_path)
        run_name = "r"
        env_id = "CartPole-v1"

    args = _Args()
    found = resilience.resolve_resume(args, "ppo")
    assert found.endswith("ckpt_2")  # structurally valid, picked first
    restored = load_checkpoint(found)
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.arange(4.0))


def test_sac_resume_restores_sampler_and_buffer_state(tmp_path):
    """The SAC satellite: a resumed run's replay sampler continues the EXACT
    random stream — ring contents, positions, device key and numpy rng all
    round-trip through the checkpoint."""
    import jax.numpy as jnp

    from sheeprl_tpu.data import ReplayBuffer

    rb = ReplayBuffer(16, 2, storage="host", obs_keys=("observations",), seed=9)
    rng = np.random.default_rng(0)
    for _ in range(12):
        rb.add(
            {
                "observations": rng.normal(size=(1, 2, 3)).astype(np.float32),
                "actions": rng.normal(size=(1, 2, 1)).astype(np.float32),
                "rewards": rng.normal(size=(1, 2, 1)).astype(np.float32),
                "dones": np.zeros((1, 2, 1), np.float32),
            }
        )
    rb.sample(4)  # advance the sampler stream before checkpointing
    path = str(tmp_path / "buf.npz")
    rb.save(path)
    expected = [rb.sample(6) for _ in range(3)]  # the stream a live run draws

    rb2 = ReplayBuffer(16, 2, storage="host", obs_keys=("observations",), seed=9)
    rb2.load(path)
    assert rb2.pos == rb.pos and rb2.full == rb.full
    for want in expected:
        got = rb2.sample(6)
        for k in want:
            np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(got[k]))


def test_device_buffer_sampler_state_roundtrips(tmp_path):
    from sheeprl_tpu.data import ReplayBuffer

    rb = ReplayBuffer(8, 1, storage="device", obs_keys=("observations",), seed=4)
    for _ in range(6):
        rb.add({"observations": np.ones((1, 1, 2), np.float32)})
    rb.sample(2)
    path = str(tmp_path / "buf.npz")
    rb.save(path)
    want = np.asarray(rb.sample(3)["observations"])
    rb2 = ReplayBuffer(8, 1, storage="device", obs_keys=("observations",), seed=4)
    rb2.load(path)
    np.testing.assert_array_equal(np.asarray(rb2.sample(3)["observations"]), want)
