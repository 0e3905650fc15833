"""The unified cache-arming path + persistent-cache hit/miss counting."""

import os

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.compile import CacheStats, MIN_COMPILE_SECS, arm_compile_cache, cache_dir


@pytest.fixture
def restore_cache_config():
    """Snapshot/restore the three jax config knobs the helper touches, plus
    the env vars, so tests never leak cache state into the suite."""
    saved = {
        "dir": jax.config.jax_compilation_cache_dir,
        "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
        "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
        "env": {
            k: os.environ.get(k)
            for k in (
                "JAX_COMPILATION_CACHE_DIR",
                "SHEEPRL_TPU_XLA_CACHE",
            )
        },
    }
    yield
    jax.config.update("jax_compilation_cache_dir", saved["dir"])
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", saved["min_secs"]
    )
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", saved["min_bytes"]
    )
    for k, v in saved["env"].items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_env_var_places_cache_and_decision_store(tmp_path, restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR wins: the cache is armed there with the one
    compile-time floor, and the decision store follows the directory."""
    from sheeprl_tpu.compile import decisions

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    path = arm_compile_cache()
    assert path == str(tmp_path / "placed")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == MIN_COMPILE_SECS
    assert decisions.cache_path() == os.path.join(path, "decisions.json")

    # distributed_setup arms nothing of its own
    from sheeprl_tpu.parallel.mesh import distributed_setup

    distributed_setup()
    assert jax.config.jax_compilation_cache_dir == path

    os.environ["SHEEPRL_TPU_XLA_CACHE"] = "0"
    assert arm_compile_cache() is None


def test_default_is_absolute_in_checkout_from_any_cwd(tmp_path, restore_cache_config):
    """Unset: one absolute path inside the checkout, the same from two
    different working directories (fresh processes, the import-time arm)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    expected = os.path.join(repo, "logs", "jax_compile_cache")
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert cache_dir() == expected
    assert arm_compile_cache() == expected

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo
    code = (
        "import sheeprl_tpu, jax; from sheeprl_tpu.compile import decisions; "
        "print(jax.config.jax_compilation_cache_dir); print(decisions.cache_path())"
    )
    for cwd in (str(tmp_path), repo):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        assert out == [expected, os.path.join(expected, "decisions.json")], (cwd, out)


@pytest.mark.timeout(120)
def test_cache_hit_miss_counting(tmp_path, restore_cache_config):
    """Compile the same program twice (fresh jit objects, so no in-memory
    dispatch-cache reuse): first is a persistent-cache miss, second a hit.
    min_compile_secs=0 lets the tiny test graph qualify for caching."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    arm_compile_cache(min_compile_secs=0.0)
    stats = CacheStats().attach()

    def build():
        # non-trivial enough that XLA actually compiles a module
        return jax.jit(lambda x: jnp.tanh(x @ x.T).sum())

    x = jnp.ones((16, 16), jnp.float32)
    before = stats.snapshot()
    build()(x).block_until_ready()
    mid = stats.snapshot()
    build()(x).block_until_ready()
    after = stats.snapshot()
    stats.detach()
    assert mid["misses"] - before["misses"] >= 1
    assert mid["hits"] == before["hits"]
    assert after["hits"] - mid["hits"] >= 1
