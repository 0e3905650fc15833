"""What the compiler made of the replay ring's two device programs.

`AsyncReplayBuffer`'s add and sample are meant to touch the rows they write
and read and nothing else. Whether they do is decided by the compiler, from
the layout it gives the ring, and shows only in the optimised HLO: PR 27
found both programs copying a 3.9 GiB ring whole to reach 16 frames of it,
under a donation that was honoured all along. `report()` compiles both
programs for given shapes, on whatever backend is the default (or the
`sharding` given: a described TPU compiles in a sandbox without one), and
`faults()` holds the result to three rules:

  - the add aliases every ring it is given to its output;
  - neither program holds an instruction whose result has the element count
    of a lane-dense ring, other than the in-place update of the add (and
    what moves nothing: parameters, tuples, bitcasts);
  - neither program's temporaries reach 1 % of the ring.

Run by tier-1 on the CPU (`tests/test_data/test_buffers.py`) and by
`chip_smoke.py` on the chip at the benchmark cells' shapes.
"""

from __future__ import annotations

import re
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .buffers import AsyncReplayBuffer, _storage_item

__all__ = ["report", "faults"]

_MOVES_NOTHING = {"parameter", "get-tuple-element", "tuple", "bitcast"}
_UPDATES = {"scatter", "dynamic-update-slice"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-, %]+)\}?")


def _computations(text: str) -> dict[str, list[tuple[str, str, str, str]]]:
    """`computation -> [(instruction, result type, opcode, line)]`."""
    out: dict[str, list] = {}
    current = None
    for line in text.splitlines():
        line = line.strip()
        head = _COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if found and current is not None:
            current.append((*found.groups(), line))
    return out


def _counts(result_type: str) -> set[int]:
    return {
        int(np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64))
        for dims in _ARRAY.findall(result_type)
    }


def _called(line: str) -> list[str]:
    found = _CALLED.search(line)
    return re.findall(r"[\w.\-]+", found.group(1)) if found else []


def _ring_sized(text: str, counts: set[int], allow_update: bool) -> list[str]:
    """Instructions with a ring-sized result that are neither free nor (in
    the add) the in-place update: the update itself, or the fusion / loop
    whose own computation holds it."""
    comps = _computations(text)

    def updates(comp: str) -> bool:
        return any(
            (op in _UPDATES and _counts(rtype) & counts) or any(map(updates, _called(line)))
            for _, rtype, op, line in comps.get(comp, ())
        )

    return [
        f"{name} = {rtype} {op}"
        for rows in comps.values()
        for name, rtype, op, line in rows
        if op not in _MOVES_NOTHING
        and _counts(rtype) & counts
        and not (allow_update and (op in _UPDATES or any(map(updates, _called(line)))))
    ]


def report(
    capacity: int,
    n_envs: int,
    items: Mapping[str, tuple[tuple[int, ...], "np.dtype | str"]],
    *,
    batch: int,
    seq_len: int,
    n_samples: int = 1,
    data_len: int = 1,
    sharding=None,
) -> dict:
    """Compile the add (one full-width `add_direct` of `data_len` rows) and
    the sequential sample (`n_samples` x `batch` windows of `seq_len`) of a
    ring of `capacity` rows x `n_envs` holding `items` (`key -> (item shape,
    dtype)`), stored as `AsyncReplayBuffer` would store it. Nothing is
    allocated: the programs are compiled from shapes."""

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    store, row, logical, formats = {}, {}, [], {}
    ring_counts, ring_bytes = set(), 0
    for k, (item, dtype) in items.items():
        item = tuple(item)
        stored, formats[k] = _storage_item(item, dtype)
        store[k] = spec((capacity, n_envs, *stored), dtype)
        row[k] = spec((data_len, n_envs, *item), dtype)
        count = capacity * n_envs * int(np.prod(stored, dtype=np.int64))
        ring_bytes += count * jnp.dtype(dtype).itemsize
        if stored != item:
            logical.append((k, item))
            ring_counts.add(count)
    row["__idx__"] = spec((2 * n_envs,), jnp.int32)

    add = AsyncReplayBuffer._store_add_packed.lower(store, row, {}, (), data_len).compile()
    sample = AsyncReplayBuffer._store_sample.lower(
        store, spec((2,), jnp.uint32), spec((n_samples * batch + 3 * n_envs,), jnp.int32),
        n_samples=n_samples, seq_len=seq_len, sequential=True, sample_next_obs=False,
        obs_keys=(), items=tuple(logical),
    ).compile()

    out = {"store_bytes": ring_bytes, "formats": formats}
    for name, compiled, is_add in (("add", add, True), ("sample", sample, False)):
        text = compiled.as_text()
        memory = compiled.memory_analysis()
        aliased = re.search(r"input_output_alias=\{(.*?)\}, \w+=", text)
        out[name] = {
            "temp_bytes": int(memory.temp_size_in_bytes),
            "alias_bytes": int(memory.alias_size_in_bytes),
            "aliased_parameters": sorted(
                {int(p) for p in re.findall(r"\((\d+), \{", aliased.group(1))}
            ) if aliased else [],
            "ring_sized": _ring_sized(text, ring_counts, allow_update=is_add),
        }
    out["add"]["store_parameters"] = len(store)
    return out


def faults(rep: dict) -> list[str]:
    """What of `report()` breaks the rules above; empty when it holds."""
    found = []
    add = rep["add"]
    if len(add["aliased_parameters"]) != add["store_parameters"] or add["alias_bytes"] < rep["store_bytes"]:
        found.append(
            f"add aliases {len(add['aliased_parameters'])} of {add['store_parameters']} rings "
            f"({add['alias_bytes']} of {rep['store_bytes']} bytes)"
        )
    for name in ("add", "sample"):
        if rep[name]["ring_sized"]:
            found.append(f"{name} holds ring-sized instructions: {rep[name]['ring_sized']}")
        if rep[name]["temp_bytes"] >= 0.01 * rep["store_bytes"]:
            found.append(
                f"{name} needs {rep[name]['temp_bytes']} bytes of temporaries beside a ring of {rep['store_bytes']}"
            )
    return found
