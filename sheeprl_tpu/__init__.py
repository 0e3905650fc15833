"""sheeprl_tpu — a TPU-native distributed deep-RL framework.

A from-scratch JAX/XLA/Pallas re-design with the capability surface of
SheepRL (reference at /root/reference): self-contained algorithm tasks
(PPO coupled/decoupled/recurrent, SAC, SAC-AE, DroQ, DreamerV1/2/3,
Plan2Explore), dict-observation env pipelines, four replay-buffer semantics,
data-parallel and player/trainer topologies over device meshes, TensorBoard
metrics, and checkpoint/resume.
"""

import os as _os

__version__ = "0.1.0"


def _load_dotenv(path: str = ".env") -> None:
    """Load KEY=VALUE lines from a .env file into the environment without
    overriding existing variables (reference sheeprl/__init__.py:1-3 uses
    python-dotenv; stdlib parse here — the package is not in this image)."""
    try:
        with open(path) as fh:
            lines = fh.readlines()
    except OSError:
        return
    for line in lines:
        line = line.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        if line.startswith("export "):
            line = line[len("export "):]
        key, _, value = line.partition("=")
        key, value = key.strip(), value.strip().strip("'\"")
        if key:
            _os.environ.setdefault(key, value)


_load_dotenv()


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache, on by default (compiles are the
    dominant startup cost — the full-scale DreamerV3 step is ~30-40s per
    config — and the cache also dedupes identical-HLO graphs built by
    *different* Python closures within one process). The ONE arming path
    of the repo is `compile/cache.py`: `JAX_COMPILATION_CACHE_DIR` places
    the cache, unset it lives at `<checkout>/logs/jax_compile_cache`;
    `SHEEPRL_TPU_XLA_CACHE=0` disables it."""
    from .compile.cache import arm_compile_cache

    arm_compile_cache()


_enable_compilation_cache()
