"""Compile-latency subsystem (ISSUE 5): warm-start AOT compilation
overlapped with collection, one cache-arming path, and measured
partitioning of compile-pathological jits.

Import surface is jax-free at module load (the parent package arms the
persistent cache through here at import time, before jax config must be
touched); every jax import inside is lazy.
"""

from .cache import MIN_COMPILE_SECS, CacheStats, arm_compile_cache, cache_dir
from .decisions import (
    Decision,
    decide,
    decide_remat,
    decision_key,
    measured_probe,
    remat_enabled,
    remat_mode,
)
from .partition import (
    PartitionDecision,
    chunk_for_budget,
    compiled_memory_stats,
    decide_batch_chunk,
    ledger_entry,
    lowered_op_counts,
    predicted_cpu_compile_seconds,
)
from .plan import CaptureComplete, CompilePlan, DataEdge, WarmJit, avals_of, sds
from .specs import dict_obs_spec, dreamer_sample_spec

__all__ = [
    "dict_obs_spec",
    "dreamer_sample_spec",
    "MIN_COMPILE_SECS",
    "CacheStats",
    "CaptureComplete",
    "CompilePlan",
    "DataEdge",
    "Decision",
    "PartitionDecision",
    "WarmJit",
    "arm_compile_cache",
    "avals_of",
    "cache_dir",
    "chunk_for_budget",
    "compiled_memory_stats",
    "decide",
    "decide_batch_chunk",
    "decide_remat",
    "decision_key",
    "ledger_entry",
    "lowered_op_counts",
    "measured_probe",
    "predicted_cpu_compile_seconds",
    "remat_enabled",
    "remat_mode",
    "sds",
]
