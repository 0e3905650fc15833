"""Replay / rollout buffers, TPU-native.

Re-designs the reference's four TensorDict buffer semantics
(/root/reference/sheeprl/data/buffers.py) around two storage backends:

  - **device** (default): every key is a `jax.Array` ring `[capacity, n_envs,
    *item]` resident in HBM. `add` is a jitted, donated scatter
    (`.at[idx].set`) so the ring is updated in place without host round
    trips; `sample` is a jitted gather whose random indices are drawn with
    `jax.random` *on device*. Under a mesh the ring can be sharded on the
    env axis, making sampling a local gather + no collective.
    "In place" is the compiler's to grant, not the donation's: the TPU lays
    a `u8[rows, envs, 64, 64, 3]` ring out with the *row* axis in the lanes,
    and the scatter and gather over `(row, env)` then each copy the whole
    ring into a row-major layout first (and the scatter back). So
    `AsyncReplayBuffer` keeps an item of several axes that fills whole
    lanes with its axes folded into one (`_storage_item`), where both run
    on the ring as it lies; `data/store_check.py` holds the compiled
    programs to that.
  - **host**: numpy (optionally `np.memmap`) ring with identical index
    semantics, for capacities that exceed HBM (the reference's
    `memmap_buffer=True` pixel-Dreamer case); samples are assembled on host
    and handed to jit as one batch per train step.

Batches are plain `dict[str, array]` (a pytree) instead of TensorDicts.
Data layout is `[T, n_envs, *item]` on `add` and the reference's sampling
contracts are preserved:
  - `ReplayBuffer.sample` -> `[batch, *item]` uniform over valid entries,
    excluding the write head (buffers.py:153-194), with optional
    `next_{key}` synthesis from `idx+1 % capacity` (buffers.py:196-204);
  - `SequentialReplayBuffer.sample` -> `[n_samples, seq_len, batch, *item]`
    contiguous windows whose start indices avoid `[pos-seq_len, pos)` when
    full (buffers.py:287-316), each window drawn from a single env;
  - `EpisodeBuffer` stores whole episodes, evicts oldest first, and samples
    windows with optional `prioritize_ends` (buffers.py:351-534);
  - `AsyncReplayBuffer` keeps one independent buffer per env with per-env
    `add(data, indices)` (buffers.py:537-699).
"""

from __future__ import annotations

import json
import os
import shutil
import struct
import uuid
from functools import partial
from pathlib import Path
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .wire import WireFormatError, pack_tree, unpack_tree

__all__ = [
    "ReplayBuffer",
    "SequentialReplayBuffer",
    "EpisodeBuffer",
    "AsyncReplayBuffer",
    "stage_batch",
]

Batch = dict[str, np.ndarray]
DeviceBatch = dict[str, jax.Array]


def _rows(block: Mapping[str, "np.ndarray | jax.Array"]) -> list[dict]:
    """`block[k][i]` for every row `i` of a `[n_samples, ...]` block."""
    n_samples = next(iter(block.values())).shape[0]
    return [{k: v[i] for k, v in block.items()} for i in range(n_samples)]


@jax.jit
def _cut_rows(block: DeviceBatch) -> list[DeviceBatch]:
    return _rows(
        {
            k: v if v.dtype == jnp.uint8 else v.astype(jnp.float32)
            for k, v in block.items()
        }
    )


def stage_batch(
    local_data: Mapping[str, "np.ndarray | jax.Array"], *, to_host: bool = False
) -> "list[Batch] | list[DeviceBatch]":
    """Cut a sampled `[n_samples, ...]` block into the gradient loop's
    `n_samples` rows, one dict a train step: uint8 preserved (pixels
    normalize on device inside the train step), everything else cast to f32.

    Default (`to_host=False`): ONE compiled program per block casts and cuts
    every row on device (`_cut_rows`; `n_samples` is the block's leading
    dimension, so a block shape compiles once and no index travels). The
    gradient loop then only picks `rows[i]`: no program is enqueued between
    two train steps. Cutting the rows eagerly, a step at a time (`v[i]`), is
    a `slice` and a `squeeze` per key: ten enqueues of ~0.7 ms a train step
    with the chip idle (7.3 ms of a 38.6 ms iteration on the v5e's machine,
    PERF.md PR 34). A host block (host/memmap storage) rides the same call:
    its host->device DMA overlaps the in-flight update via JAX async
    dispatch. Block and rows live in HBM together until the caller drops
    the block; the rows are copies, as `v[i]`'s results were.

    `to_host=True` is for multi-process runs: `shard_batch`'s
    `make_array_from_process_local_data` path needs host numpy per row, so
    staging pulls the block to host once (one d2h for device-storage
    buffers) and the rows are numpy views of it."""
    if not to_host:
        return _cut_rows(dict(local_data))
    return _rows(
        {
            k: np.asarray(v).astype(
                np.float32 if v.dtype != np.uint8 else np.uint8, copy=False
            )
            for k, v in local_data.items()
        }
    )


def _as_time_env(data: Mapping[str, np.ndarray]) -> Batch:
    d = dict(data)
    shapes = {k: v.shape[:2] for k, v in d.items()}
    first = next(iter(shapes.values()))
    if any(s != first for s in shapes.values()):
        raise ValueError(f"inconsistent [T, n_envs] leading dims: {shapes}")
    return d


_WIDTH_GROUP = {1: "w1", 2: "w2", 4: "w4"}
# int32 as the 4-byte carrier, NOT float32: integer transfers are bit-exact
# by construction, while a backend that canonicalizes NaNs on transfer would
# corrupt int32 ring indices riding as arbitrary float32 bit patterns
# (ADVICE r3); float32 values bitcast back on device (_unpack_values), same
# scheme the blob transport uses (data/blob.py:152-174)
_GROUP_VIEW = {"w1": np.uint8, "w2": np.uint16, "w4": np.int32}


def _pack_host_values(data: Mapping[str, "np.ndarray | jax.Array"]):
    """Split an add batch into device-resident values (`direct` — e.g. the
    policy step's obs put, reused by the mains) and host values packed into
    ONE flat array per itemsize class: all 4-byte dtypes bit-viewed as
    int32, 1-byte as uint8, 2-byte as uint16 (64-bit values are cast to
    their 32-bit counterpart first — matching what the x64-disabled device
    store holds anyway). In the training loops' add path everything is
    float32/int32/uint8, so the whole row (indices included) rides at most
    two host->device transfers, usually one.
    Returns `(direct, packed, layout)`; the static `layout` of
    `(key, dtype_str, shape, offset, size)` rows unpacks on device."""
    direct: dict[str, jax.Array] = {}
    groups: dict[str, list[np.ndarray]] = {}
    offsets: dict[str, int] = {}
    layout: list[tuple] = []
    for k, v in data.items():
        if isinstance(v, jax.Array):
            direct[k] = v
            continue
        v = np.asarray(v)
        if v.dtype.itemsize == 8:  # x64 is disabled on device; match the store
            v = v.astype(np.float32 if v.dtype.kind == "f" else np.int32)
        ds = v.dtype.str
        g = _WIDTH_GROUP[v.dtype.itemsize]
        view = np.ascontiguousarray(v.reshape(-1)).view(_GROUP_VIEW[g])
        off = offsets.get(g, 0)
        groups.setdefault(g, []).append(view)
        layout.append((k, ds, v.shape, off, v.size))
        offsets[g] = off + v.size
    packed = {
        g: jnp.asarray(np.concatenate(parts)) for g, parts in groups.items()
    }
    return direct, packed, tuple(layout)


def _unpack_values(direct, packed, layout):
    """Device-side inverse of `_pack_host_values` (runs inside jit): slice
    each value out of its width-class blob and bitcast back to its true
    dtype — an exact bit-level roundtrip (bitcasts preserve arbitrary NaN
    payloads; transfers are raw bytes)."""
    data = dict(direct)
    for k, ds, shape, off, size in layout:
        dt = np.dtype(ds)
        seg = packed[_WIDTH_GROUP[dt.itemsize]][off : off + size]
        if seg.dtype != dt:
            seg = seg != 0 if dt == np.bool_ else jax.lax.bitcast_convert_type(seg, dt)
        data[k] = seg.reshape(shape)
    return data


def _pad_columns(value: np.ndarray, pad: int) -> np.ndarray:
    """`[T, k, *item]` -> `[T, k + pad, *item]`, the added columns zero."""
    value = np.asarray(value)
    zeros = np.zeros((value.shape[0], pad, *value.shape[2:]), value.dtype)
    return np.concatenate([value, zeros], axis=1)


def _encode_sample_state(state) -> np.ndarray:
    """Sampler-PRNG snapshot as a JSON byte buffer for `.npz` embedding
    (ISSUE 12): a resumed run continues the EXACT sample stream the
    interrupted one would have drawn. Arrays (the device sample key) are
    tagged; numpy bit-generator states are plain nested dicts of (big) ints,
    which JSON carries losslessly."""

    def enc(x):
        if isinstance(x, (np.ndarray, jax.Array)):
            a = np.asarray(x)
            return {"__nd__": a.tolist(), "__dt__": str(a.dtype)}
        raise TypeError(f"unserializable sampler-state leaf {type(x)!r}")

    blob = json.dumps(state, default=enc).encode()
    return np.frombuffer(blob, dtype=np.uint8)


def _decode_sample_state(arr: np.ndarray):
    def hook(d):
        if "__nd__" in d and "__dt__" in d:
            return jnp.asarray(np.asarray(d["__nd__"], dtype=d["__dt__"]))
        return d

    return json.loads(bytes(np.asarray(arr, dtype=np.uint8)).decode(), object_hook=hook)


# ---------------------------------------------------------------------------
# Wire round-trip (ISSUE 14): versioned pickle-free to_bytes()/from_bytes()
# on every buffer class — the flock transport's payload format, and the only
# serialization usable over a socket (save/load are .npz-file-only). Shared
# frame: magic(4) | u32 meta_json_len | meta_json | u64 sampler_len |
# sampler_json_bytes | class-specific payload (pack_tree blobs).
# ---------------------------------------------------------------------------

_WIRE_VERSION = 1
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


def _wire_frame(magic: bytes, meta: dict, sampler_state, payload: bytes) -> bytes:
    meta = dict(meta)
    meta["version"] = _WIRE_VERSION
    meta_b = json.dumps(meta).encode()
    sampler_b = _encode_sample_state(sampler_state).tobytes()
    return b"".join(
        [
            magic,
            _U32.pack(len(meta_b)),
            meta_b,
            _U64.pack(len(sampler_b)),
            sampler_b,
            payload,
        ]
    )


def _wire_unframe(magic: bytes, data: bytes, cls_name: str):
    """-> (meta, decoded_sampler_state, payload_bytes); strict on magic,
    version, and the concrete class name recorded at pack time."""
    if len(data) < 8 or data[:4] != magic:
        raise WireFormatError(f"bad buffer frame magic for {cls_name}")
    (meta_len,) = _U32.unpack_from(data, 4)
    off = 8 + meta_len
    if off + 8 > len(data):
        raise WireFormatError("truncated buffer frame meta")
    meta = json.loads(data[8:off].decode())
    if meta.get("version") != _WIRE_VERSION:
        raise WireFormatError(
            f"unsupported buffer wire version {meta.get('version')!r}"
        )
    if meta.get("class") != cls_name:
        raise WireFormatError(
            f"frame holds a {meta.get('class')!r}, not a {cls_name}"
        )
    (sampler_len,) = _U64.unpack_from(data, off)
    off += 8
    if off + sampler_len > len(data):
        raise WireFormatError("truncated buffer frame sampler state")
    sampler = _decode_sample_state(
        np.frombuffer(data, dtype=np.uint8, count=sampler_len, offset=off)
    )
    return meta, sampler, data[off + sampler_len :]


class ReplayBuffer:
    """Circular buffer `[capacity, n_envs]`; uniform sampling."""

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        storage: str = "device",
        memmap_dir: str | os.PathLike | None = None,
        obs_keys: Sequence[str] = ("observations",),
        seed: int = 0,
    ):
        if buffer_size <= 0:
            raise ValueError(f"buffer size must be > 0, got {buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"n_envs must be > 0, got {n_envs}")
        if storage not in ("device", "host"):
            raise ValueError(f"storage must be 'device' or 'host', got {storage!r}")
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._storage_kind = storage
        self._memmap_dir = Path(memmap_dir) if memmap_dir is not None else None
        if self._memmap_dir is not None:
            self._memmap_dir.mkdir(parents=True, exist_ok=True)
        self.obs_keys = tuple(obs_keys)
        self._buf: dict[str, np.ndarray] | dict[str, jax.Array] | None = None
        self._pos = 0
        self._full = False
        self._epoch = 0
        self._np_rng = np.random.default_rng(seed)
        self._key = jax.random.PRNGKey(seed)

    # -- properties mirroring the reference API ------------------------------
    @property
    def buffer(self):
        return self._buf

    @property
    def prefers_host_adds(self) -> bool:
        """True when `add` wants host numpy values (host/memmap storage:
        device arrays would force a blocking device->host pull per key).
        The mains consult this before reusing the policy step's device obs
        puts in `add`."""
        return self._storage_kind != "device"

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def full(self) -> bool:
        return self._full

    @property
    def is_device_backed(self) -> bool:
        return self._storage_kind == "device"

    @property
    def epoch(self) -> int:
        """Monotonic write counter, bumped by every ring mutation (add /
        set_at / __setitem__ / restore). The pipeline SamplePrefetcher's
        epoch-consistency guard compares epochs to decide whether a
        prefetched batch still reflects the current ring contents."""
        return self._epoch

    def get_sample_state(self):
        """Snapshot of the sampler's PRNG state (device key + numpy rng).
        The SamplePrefetcher rewinds to this on a discarded prefetch so the
        fresh resample draws the same key the synchronous path would have —
        the bit-exact half of the epoch-consistency guard."""
        return (self._key, self._np_rng.bit_generator.state)

    def set_sample_state(self, state) -> None:
        self._key = state[0]
        self._np_rng.bit_generator.state = state[1]

    @property
    def shape(self):
        if self._buf is None:
            return None
        return (self._buffer_size, self._n_envs)

    def __len__(self) -> int:
        return self._buffer_size

    def __getitem__(self, key: str):
        if self._buf is None:
            raise RuntimeError("buffer not initialized; add data first")
        return self._buf[key]

    def __setitem__(self, key: str, value) -> None:
        if self._buf is None:
            raise RuntimeError("buffer not initialized; add data first")
        expected = (self._buffer_size, self._n_envs)
        if tuple(value.shape[:2]) != expected:
            raise ValueError(f"value must have leading shape {expected}")
        if self._storage_kind == "device":
            self._buf[key] = jnp.asarray(value)
        else:
            self._buf[key][:] = np.asarray(value)
        self._epoch += 1

    @property
    def pos(self) -> int:
        return self._pos

    def set_at(self, key: str, time_idx: int, value) -> None:
        """Point row surgery: overwrite `[time_idx]` of one key — the env
        fault-tolerance rewrite of the last inserted row (reference
        dreamer_v3.py:565-573 patching dones/is_first after a restart)."""
        if self._buf is None:
            raise RuntimeError("buffer not initialized; add data first")
        if self._storage_kind == "device":
            self._buf[key] = self._buf[key].at[time_idx].set(value)
        else:
            self._buf[key][time_idx] = value
        self._epoch += 1

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- allocation ----------------------------------------------------------
    def _allocate(self, data: Batch) -> None:
        buf: dict = {}
        for k, v in data.items():
            item_shape = v.shape[2:]
            full_shape = (self._buffer_size, self._n_envs, *item_shape)
            if self._storage_kind == "device":
                buf[k] = jnp.zeros(full_shape, dtype=v.dtype)
            elif self._memmap_dir is not None:
                buf[k] = np.lib.format.open_memmap(
                    self._memmap_dir / f"{k}.npy",
                    mode="w+",
                    dtype=v.dtype,
                    shape=full_shape,
                )
            else:
                buf[k] = np.zeros(full_shape, dtype=v.dtype)
        self._buf = buf

    # -- add -----------------------------------------------------------------
    @staticmethod
    # sheeplint: disable=SL001 — this scatter compiles far below the cache's
    # compile-time floor, so it never produces a deserialized (heap-corrupting)
    # executable; un-donating it would copy the whole HBM ring per env step
    # (see utils/jit.py docstring)
    @partial(jax.jit, donate_argnums=0, static_argnums=(3, 4))
    def _device_add(buf, direct, packed, layout, data_len):
        """Append at the write head with ONE host->device transfer per width
        class (see `_pack_host_values`); the write position rides inside the
        packed group as `__pos__` instead of its own scalar put."""
        capacity = next(iter(buf.values())).shape[0]
        data = _unpack_values(direct, packed, layout)
        pos = data.pop("__pos__").reshape(())
        idxes = (pos + jnp.arange(data_len)) % capacity
        return {k: buf[k].at[idxes].set(data[k].astype(buf[k].dtype)) for k in buf}

    def add(self, data: Mapping[str, np.ndarray] | "ReplayBuffer") -> None:
        """Append `[T, n_envs]`-shaped rows at the write head, wrapping around
        (reference add semantics, buffers.py:99-151)."""
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if data is None:
            raise RuntimeError("data must not be None")
        data = _as_time_env(data)
        data_len, n_envs = next(iter(data.values())).shape[:2]
        if n_envs != self._n_envs:
            raise ValueError(f"expected n_envs={self._n_envs}, got {n_envs}")
        if data_len == 0:
            return
        if data_len > self._buffer_size:
            # only the last `capacity` rows survive a wrap anyway
            data = {k: v[-self._buffer_size :] for k, v in data.items()}
            data_len = self._buffer_size
        if self._buf is None:
            self._allocate(data)
        if self._storage_kind == "device":
            direct, packed, layout = _pack_host_values(
                {**data, "__pos__": np.int32(self._pos)}
            )
            self._buf = self._device_add(
                self._buf, direct, packed, layout, data_len
            )
        else:
            idxes = (self._pos + np.arange(data_len)) % self._buffer_size
            for k, v in data.items():
                self._buf[k][idxes] = v
        if self._pos + data_len >= self._buffer_size:
            self._full = True
        self._pos = (self._pos + data_len) % self._buffer_size
        self._epoch += 1

    # -- sampling ------------------------------------------------------------
    def _valid_ranges(self, exclude: int) -> tuple[int, int]:
        """Uniform sampling domain as (first_range_end, n_valid): indices
        `r < first_range_end` map to themselves, the rest shift past the
        write head (reference window rules, buffers.py:166-186)."""
        if self._full:
            first = self._pos - exclude
            second_end = (
                self._buffer_size if first >= 0 else self._buffer_size + first
            )
            first = max(first, 0)
            n_valid = first + (second_end - self._pos)
        else:
            first = self._pos - exclude
            n_valid = first
        if n_valid <= 0:
            raise RuntimeError(
                "not enough valid entries to sample; add more data first"
            )
        return first, n_valid

    @staticmethod
    @partial(jax.jit, static_argnames=("batch_size", "n_envs", "sample_next_obs", "obs_keys"))
    def _device_sample(
        buf, key, batch_size, n_envs, fnp, sample_next_obs, obs_keys
    ):
        """`fnp` packs (first, n_valid, pos) as one int32 put."""
        capacity = next(iter(buf.values())).shape[0]
        first, n_valid, pos = fnp[0], fnp[1], fnp[2]
        k1, k2 = jax.random.split(key)
        r = jax.random.randint(k1, (batch_size,), 0, n_valid)
        idx = jnp.where(r < first, r, r - first + pos)
        env_idx = jax.random.randint(k2, (batch_size,), 0, n_envs)
        out = {k: buf[k][idx, env_idx] for k in buf}
        if sample_next_obs:
            nxt = (idx + 1) % capacity
            for k in obs_keys:
                out[f"next_{k}"] = buf[k][nxt, env_idx]
        return out

    def can_sample(self, sample_next_obs: bool = False) -> bool:
        """Whether at least one index is currently in the valid sampling
        window (loops use this to gate the first updates, e.g. dry runs
        where the buffer holds a single row)."""
        if self._buf is None or (not self._full and self._pos == 0):
            return False
        try:
            self._valid_ranges(1 if sample_next_obs else 0)
        except RuntimeError:
            return False
        return True

    def sample(
        self, batch_size: int, sample_next_obs: bool = False, **_: object
    ) -> Batch:
        """Uniform batch `[batch_size, *item]`, excluding the write head; with
        `sample_next_obs`, also exclude `pos-1` and synthesize `next_*` keys
        (buffers.py:153-204)."""
        if batch_size <= 0:
            raise ValueError("batch_size must be > 0")
        if self._buf is None or (not self._full and self._pos == 0):
            raise RuntimeError("no samples in buffer; call add() first")
        first, n_valid = self._valid_ranges(1 if sample_next_obs else 0)
        if self._storage_kind == "device":
            return self._device_sample(
                self._buf,
                self._next_key(),
                batch_size,
                self._n_envs,
                jnp.asarray(np.array([first, n_valid, self._pos], np.int32)),
                sample_next_obs,
                self.obs_keys if sample_next_obs else (),
            )
        r = self._np_rng.integers(0, n_valid, size=batch_size)
        idx = np.where(r < first, r, r - first + self._pos)
        env_idx = self._np_rng.integers(0, self._n_envs, size=batch_size)
        out = {k: v[idx, env_idx] for k, v in self._buf.items()}
        if sample_next_obs:
            nxt = (idx + 1) % self._buffer_size
            for k in self.obs_keys:
                out[f"next_{k}"] = self._buf[k][nxt, env_idx]
        return out

    def to_state_dict(self) -> dict:
        """Serializable state for checkpointing (host numpy copies)."""
        buf = None
        if self._buf is not None:
            buf = {k: np.asarray(v) for k, v in self._buf.items()}
        return {
            "buf": buf,
            "pos": self._pos,
            "full": self._full,
            "buffer_size": self._buffer_size,
            "n_envs": self._n_envs,
        }

    def load_state_dict(self, state: dict) -> None:
        if state["buffer_size"] != self._buffer_size or state["n_envs"] != self._n_envs:
            raise ValueError("checkpointed buffer shape mismatch")
        if state["buf"] is not None:
            self._allocate({k: v[:1] for k, v in state["buf"].items()})
            if self._storage_kind == "device":
                self._buf = {k: jnp.asarray(v) for k, v in state["buf"].items()}
            else:
                for k, v in state["buf"].items():
                    self._buf[k][:] = v
        self._pos = int(state["pos"])
        self._full = bool(state["full"])
        self._epoch += 1

    def save(self, path: str) -> None:
        """Serialize the ring + head state to one `.npz` (the off-policy
        `checkpoint_buffer` path, reference callback.py:23-64)."""
        st = self.to_state_dict()
        np.savez(
            path,
            pos=st["pos"],
            full=st["full"],
            buffer_size=st["buffer_size"],
            n_envs=st["n_envs"],
            sampler_state=_encode_sample_state(self.get_sample_state()),
            **{f"buf_{k}": v for k, v in (st["buf"] or {}).items()},
        )

    def load(self, path: str) -> None:
        """Restore a ring saved with `save` into this (same-shape) buffer,
        including the sampler PRNG state when present (pre-ISSUE-12 files
        restore contents only)."""
        data = np.load(path)
        bufs = {k[4:]: data[k] for k in data.files if k.startswith("buf_")}
        self.load_state_dict(
            {
                "buf": bufs or None,
                "pos": int(data["pos"]),
                "full": bool(data["full"]),
                "buffer_size": int(data["buffer_size"]),
                "n_envs": int(data["n_envs"]),
            }
        )
        if "sampler_state" in data.files:
            self.set_sample_state(_decode_sample_state(data["sampler_state"]))

    # -- wire round-trip ------------------------------------------------------
    _WIRE_MAGIC = b"SRB1"

    def to_bytes(self) -> bytes:
        """Versioned pickle-free frame of the whole buffer — ring contents
        (bit-exact, via the width-class wire packing), head state, AND the
        sampler PRNG: `from_bytes` continues the exact sample stream."""
        st = self.to_state_dict()
        meta = {
            "class": type(self).__name__,
            "buffer_size": self._buffer_size,
            "n_envs": self._n_envs,
            "pos": st["pos"],
            "full": st["full"],
            "obs_keys": list(self.obs_keys),
            "has_buf": st["buf"] is not None,
        }
        payload = pack_tree(st["buf"]) if st["buf"] is not None else b""
        return _wire_frame(
            self._WIRE_MAGIC, meta, self.get_sample_state(), payload
        )

    @classmethod
    def from_bytes(
        cls,
        data: bytes,
        storage: str = "host",
        memmap_dir: str | os.PathLike | None = None,
    ) -> "ReplayBuffer":
        """Rebuild from a `to_bytes` frame. `storage` is receiver policy,
        not wire state (the flock replay service holds shards on host)."""
        meta, sampler, payload = _wire_unframe(
            cls._WIRE_MAGIC, data, cls.__name__
        )
        buf = cls(
            meta["buffer_size"],
            n_envs=meta["n_envs"],
            storage=storage,
            memmap_dir=memmap_dir,
            obs_keys=tuple(meta["obs_keys"]),
        )
        buf.load_state_dict(
            {
                "buf": unpack_tree(payload) if meta["has_buf"] else None,
                "pos": meta["pos"],
                "full": meta["full"],
                "buffer_size": meta["buffer_size"],
                "n_envs": meta["n_envs"],
            }
        )
        buf.set_sample_state(sampler)
        return buf


class SequentialReplayBuffer(ReplayBuffer):
    """Samples contiguous `[n_samples, seq_len, batch]` windows, each from a
    single env (buffers.py:219-348)."""

    def _seq_valid_ranges(self, sequence_length: int) -> tuple[int, int]:
        # a window of length L occupies L-1 successors of its start index, so
        # the start-validity window is exactly the base rule with exclude=L-1
        try:
            return self._valid_ranges(sequence_length - 1)
        except RuntimeError as e:
            raise ValueError(
                f"too long sequence_length ({sequence_length}) for buffer with "
                f"pos={self._pos}, full={self._full}"
            ) from e

    @staticmethod
    @partial(
        jax.jit,
        static_argnames=("batch_size", "n_samples", "seq_len", "n_envs", "sample_next_obs", "obs_keys"),
    )
    def _device_sample_seq(
        buf, key, batch_size, n_samples, seq_len, n_envs, fnp,
        sample_next_obs, obs_keys,
    ):
        """`fnp` packs (first, n_valid, pos) as one int32 put."""
        capacity = next(iter(buf.values())).shape[0]
        first, n_valid, pos = fnp[0], fnp[1], fnp[2]
        batch_dim = batch_size * n_samples
        k1, k2 = jax.random.split(key)
        r = jax.random.randint(k1, (batch_dim,), 0, n_valid)
        start = jnp.where(r < first, r, r - first + pos)
        idx = (start[:, None] + jnp.arange(seq_len)[None, :]) % capacity  # [BD, T]
        env_idx = jax.random.randint(k2, (batch_dim,), 0, n_envs)[:, None]
        out = {}
        for k in buf:
            v = buf[k][idx, env_idx]  # [BD, T, *item]
            item = v.shape[2:]
            v = v.reshape(n_samples, batch_size, seq_len, *item)
            out[k] = jnp.swapaxes(v, 1, 2)  # [n_samples, T, B, *item]
        if sample_next_obs:
            nxt = (idx + 1) % capacity
            for k in obs_keys:
                v = buf[k][nxt, env_idx]
                item = v.shape[2:]
                v = v.reshape(n_samples, batch_size, seq_len, *item)
                out[f"next_{k}"] = jnp.swapaxes(v, 1, 2)
        return out

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        sequence_length: int = 1,
        n_samples: int = 1,
        **_: object,
    ) -> Batch:
        batch_dim = batch_size * n_samples
        if batch_dim <= 0:
            raise ValueError("batch_size * n_samples must be > 0")
        if self._buf is None or (not self._full and self._pos == 0):
            raise RuntimeError("no samples in buffer; call add() first")
        if sequence_length > self._buffer_size:
            raise ValueError(f"too long sequence_length ({sequence_length})")
        first, n_valid = self._seq_valid_ranges(sequence_length)
        if self._storage_kind == "device":
            return self._device_sample_seq(
                self._buf,
                self._next_key(),
                batch_size,
                n_samples,
                sequence_length,
                self._n_envs,
                jnp.asarray(np.array([first, n_valid, self._pos], np.int32)),
                sample_next_obs,
                self.obs_keys if sample_next_obs else (),
            )
        r = self._np_rng.integers(0, n_valid, size=batch_dim)
        start = np.where(r < first, r, r - first + self._pos)
        idx = (start[:, None] + np.arange(sequence_length)[None, :]) % self._buffer_size
        env_idx = self._np_rng.integers(0, self._n_envs, size=batch_dim)[:, None]
        out = {}
        for k, v in self._buf.items():
            s = v[idx, env_idx]  # [BD, T, *item]
            s = s.reshape(n_samples, batch_size, sequence_length, *s.shape[2:])
            out[k] = np.swapaxes(s, 1, 2)
        if sample_next_obs:
            nxt = (idx + 1) % self._buffer_size
            for k in self.obs_keys:
                s = self._buf[k][nxt, env_idx]
                s = s.reshape(n_samples, batch_size, sequence_length, *s.shape[2:])
                out[f"next_{k}"] = np.swapaxes(s, 1, 2)
        return out


class EpisodeBuffer:
    """Stores whole episodes (host-side, variable length); samples fixed
    windows `[n_samples, seq_len, batch]` (buffers.py:351-534). Episode data
    arrives from the host env loop and leaves as one batch per train step, so
    host storage is the right residency; window gathers are numpy, the batch
    crosses to HBM once."""

    def __init__(
        self,
        buffer_size: int,
        sequence_length: int,
        memmap_dir: str | os.PathLike | None = None,
        seed: int = 0,
    ):
        if buffer_size <= 0:
            raise ValueError(f"buffer size must be > 0, got {buffer_size}")
        if sequence_length <= 0:
            raise ValueError(f"sequence length must be > 0, got {sequence_length}")
        if buffer_size < sequence_length:
            raise ValueError(
                f"sequence length ({sequence_length}) must not exceed buffer size ({buffer_size})"
            )
        self._buffer_size = buffer_size
        self._sequence_length = sequence_length
        self._buf: list[Batch] = []
        self._episode_dirs: list[Path | None] = []
        self._cum_lengths: list[int] = []
        self._memmap_dir = Path(memmap_dir) if memmap_dir is not None else None
        if self._memmap_dir is not None:
            self._memmap_dir.mkdir(parents=True, exist_ok=True)
        self._np_rng = np.random.default_rng(seed)
        self._epoch = 0

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def is_device_backed(self) -> bool:
        return False  # episodes live on host; prefetching gains no overlap

    def get_sample_state(self):
        return self._np_rng.bit_generator.state

    def set_sample_state(self, state) -> None:
        self._np_rng.bit_generator.state = state

    @property
    def buffer(self) -> list[Batch]:
        return self._buf

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def sequence_length(self) -> int:
        return self._sequence_length

    @property
    def full(self) -> bool:
        if not self._buf:
            return False
        return self._cum_lengths[-1] + self._sequence_length > self._buffer_size

    def __len__(self) -> int:
        return self._cum_lengths[-1] if self._buf else 0

    def __getitem__(self, i: int) -> Batch:
        return self._buf[i]

    def add(self, episode: Mapping[str, np.ndarray]) -> None:
        """Validates exactly-one-done-at-end, evicts oldest episodes (incl.
        their memmap files) to fit (buffers.py:433-489)."""
        episode = dict(episode)
        dones = np.asarray(episode["dones"]).reshape(-1)
        if int((dones != 0).sum()) != 1:
            raise RuntimeError(
                f"episode must contain exactly one done, got {int((dones != 0).sum())}"
            )
        if dones[-1] == 0:
            raise RuntimeError("the last step of an episode must be done")
        ep_len = dones.shape[0]
        if ep_len < self._sequence_length:
            raise RuntimeError(
                f"episode too short: {ep_len} < sequence_length {self._sequence_length}"
            )
        if ep_len > self._buffer_size:
            raise RuntimeError(
                f"episode too long: {ep_len} > buffer_size {self._buffer_size}"
            )
        if self.full or len(self) + ep_len > self._buffer_size:
            cum = np.array(self._cum_lengths)
            keep_from = int(((len(self) - cum + ep_len) <= self._buffer_size).argmax()) + 1
            for d in self._episode_dirs[:keep_from]:
                if d is not None and d.exists():
                    shutil.rmtree(d)
            self._buf = self._buf[keep_from:]
            self._episode_dirs = self._episode_dirs[keep_from:]
            cum = cum[keep_from:] - cum[keep_from - 1]
            self._cum_lengths = cum.tolist()
        self._cum_lengths.append(len(self) + ep_len)
        ep_dir: Path | None = None
        if self._memmap_dir is not None:
            ep_dir = self._memmap_dir / f"episode_{uuid.uuid4()}"
            ep_dir.mkdir(parents=True, exist_ok=True)
            stored = {}
            for k, v in episode.items():
                v = np.asarray(v)
                mm = np.lib.format.open_memmap(
                    ep_dir / f"{k}.npy", mode="w+", dtype=v.dtype, shape=v.shape
                )
                mm[:] = v
                stored[k] = mm
            episode = stored
        else:
            episode = {k: np.asarray(v) for k, v in episode.items()}
        self._buf.append(episode)
        self._episode_dirs.append(ep_dir)
        self._epoch += 1

    def sample(
        self,
        batch_size: int,
        n_samples: int = 1,
        prioritize_ends: bool = False,
        **_: object,
    ) -> Batch:
        """`[n_samples, seq_len, batch]` windows; `prioritize_ends` biases
        start indices toward episode tails (buffers.py:491-534)."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError("batch_size and n_samples must be > 0")
        if not self._buf:
            raise RuntimeError("no episodes in buffer; call add() first")
        batch_dim = batch_size * n_samples
        counts = np.bincount(
            self._np_rng.integers(0, len(self._buf), size=batch_dim),
            minlength=len(self._buf),
        )
        chunks: dict[str, list[np.ndarray]] = {k: [] for k in self._buf[0]}
        for i, n in enumerate(counts):
            if n == 0:
                continue
            ep = self._buf[i]
            ep_len = next(iter(ep.values())).shape[0]
            upper = ep_len - self._sequence_length + 1
            if prioritize_ends:
                upper += self._sequence_length
            starts = np.minimum(
                self._np_rng.integers(0, upper, size=(int(n), 1)),
                ep_len - self._sequence_length,
            )
            idx = starts + np.arange(self._sequence_length)[None, :]
            for k in chunks:
                chunks[k].append(np.asarray(ep[k])[idx])
        out = {}
        for k, parts in chunks.items():
            cat = np.concatenate(parts, axis=0)  # [BD, T, *item]
            cat = cat.reshape(n_samples, batch_size, self._sequence_length, *cat.shape[2:])
            out[k] = np.swapaxes(cat, 1, 2)  # [n_samples, T, B, *item]
        return out

    def to_state_dict(self) -> dict:
        return {
            "episodes": [{k: np.asarray(v) for k, v in ep.items()} for ep in self._buf],
            "buffer_size": self._buffer_size,
            "sequence_length": self._sequence_length,
        }

    def load_state_dict(self, state: dict) -> None:
        if (
            state["buffer_size"] != self._buffer_size
            or state["sequence_length"] != self._sequence_length
        ):
            raise ValueError("checkpointed episode buffer shape mismatch")
        self._buf = []
        self._episode_dirs = []
        self._cum_lengths = []
        for ep in state["episodes"]:
            self.add(ep)

    def save(self, path: str) -> None:
        """Serialize all episodes into one `.npz` (the Dreamer
        `checkpoint_buffer` path for `buffer_type=episode`)."""
        st = self.to_state_dict()
        flat: dict[str, np.ndarray] = {
            "n_episodes": np.int64(len(st["episodes"])),
            "buffer_size": np.int64(self._buffer_size),
            "sequence_length": np.int64(self._sequence_length),
        }
        for i, ep in enumerate(st["episodes"]):
            for k, v in ep.items():
                flat[f"ep{i}_{k}"] = v
        flat["sampler_state"] = _encode_sample_state(self.get_sample_state())
        np.savez(path, **flat)

    def load(self, path: str) -> None:
        data = np.load(path)
        if (
            int(data["buffer_size"]) != self._buffer_size
            or int(data["sequence_length"]) != self._sequence_length
        ):
            raise ValueError("checkpointed episode buffer shape mismatch")
        episodes: list[dict] = [{} for _ in range(int(data["n_episodes"]))]
        for name in data.files:
            if not name.startswith("ep"):
                continue
            idx, key = name[2:].split("_", 1)
            episodes[int(idx)][key] = data[name]
        self.load_state_dict(
            {
                "episodes": episodes,
                "buffer_size": self._buffer_size,
                "sequence_length": self._sequence_length,
            }
        )
        # restore AFTER the episode re-adds so any rng use during rebuild
        # cannot advance the checkpointed sampler stream
        if "sampler_state" in data.files:
            self.set_sample_state(_decode_sample_state(data["sampler_state"]))

    # -- wire round-trip ------------------------------------------------------
    _WIRE_MAGIC = b"SEB1"

    def to_bytes(self) -> bytes:
        """Versioned pickle-free frame: episodes as length-prefixed
        `pack_tree` blobs, plus the sampler PRNG state."""
        st = self.to_state_dict()
        meta = {
            "class": type(self).__name__,
            "buffer_size": self._buffer_size,
            "sequence_length": self._sequence_length,
            "n_episodes": len(st["episodes"]),
        }
        parts = []
        for ep in st["episodes"]:
            blob = pack_tree(ep)
            parts.append(_U64.pack(len(blob)) + blob)
        return _wire_frame(
            self._WIRE_MAGIC, meta, self.get_sample_state(), b"".join(parts)
        )

    @classmethod
    def from_bytes(
        cls, data: bytes, memmap_dir: str | os.PathLike | None = None
    ) -> "EpisodeBuffer":
        meta, sampler, payload = _wire_unframe(
            cls._WIRE_MAGIC, data, cls.__name__
        )
        buf = cls(
            meta["buffer_size"], meta["sequence_length"], memmap_dir=memmap_dir
        )
        episodes = []
        off = 0
        for _ in range(meta["n_episodes"]):
            if off + 8 > len(payload):
                raise WireFormatError("truncated episode payload")
            (blob_len,) = _U64.unpack_from(payload, off)
            off += 8
            episodes.append(unpack_tree(payload[off : off + blob_len]))
            off += blob_len
        buf.load_state_dict(
            {
                "episodes": episodes,
                "buffer_size": meta["buffer_size"],
                "sequence_length": meta["sequence_length"],
            }
        )
        # AFTER the re-adds, same ordering contract as load()
        buf.set_sample_state(sampler)
        return buf


_LANES = 128  # elements in one row of a TPU tile, for every dtype


def _storage_item(item: tuple[int, ...], dtype) -> tuple[tuple[int, ...], str]:
    """On-device storage shape of one ring item, and why: decided from the
    array alone. An item of several axes whose elements fill whole lanes is
    kept **lane-dense**, its axes folded into one (`u8[64,64,3]` as
    `u8[12288]`, 96 lanes): a ring row is then whole tile rows, and the
    row scatter and row gather over `(row, env)` run in place on the layout
    the ring already has. With the item's own axes kept, the TPU's default
    layout puts the ring's *row* axis in the lanes, and both index
    operations first relayout the whole ring to reach a row (PERF.md,
    PR 27). Every other item (a vector, a scalar, a frame that would leave a
    lane part empty: folding that one does not change its layout) is stored
    as is."""
    n = int(np.prod(item, dtype=np.int64))
    nbytes = n * np.dtype(dtype).itemsize
    if len(item) < 2:
        return item, f"as_is: item_bytes={nbytes}, one axis"
    if n % _LANES:
        return item, f"as_is: item_bytes={nbytes}, {n} elements fill no whole lanes"
    return (n,), "lane_dense"


class _AsyncEnvView:
    """Single-env handle into the unified device store of an
    `AsyncReplayBuffer`, exposing the slice of the `ReplayBuffer` surface the
    training loops use per env (`pos`/`full`/`buffer_size`/`set_at` for the
    crash-restart row surgery, reference dreamer_v3.py:565-573)."""

    __slots__ = ("_parent", "_env")

    def __init__(self, parent: "AsyncReplayBuffer", env: int):
        self._parent = parent
        self._env = env

    @property
    def pos(self) -> int:
        return int(self._parent._upos[self._env])

    @property
    def full(self) -> bool:
        return bool(self._parent._ufull[self._env])

    @property
    def buffer_size(self) -> int:
        return self._parent._buffer_size

    @property
    def buffer(self):
        self._parent._flush_staged()
        parent = self._parent
        if parent._store is None:
            return None
        return {
            k: parent._logical(k, v[:, self._env : self._env + 1])
            for k, v in parent._store.items()
        }

    def set_at(self, key: str, time_idx: int, value) -> None:
        self._parent._set_at(self._env, key, time_idx, value)


class AsyncReplayBuffer:
    """Per-env independent rings with `add(data, indices)` — envs that reset
    mid-step can append their reset records without touching the others
    (reference buffers.py:537-699).

    Storage backends:
      - **device**: ONE unified HBM store with a per-env write-head vector,
        logically `[capacity, n_envs, *item]` per key. `add` is a single
        jitted scatter at `(rows, env_cols)` and `sample` a single jitted
        gather for the whole batch — one dispatch each, instead of the
        n_envs-fan-out a buffer-per-env design pays (which dominates the
        end-to-end step time when host<->device latency is non-trivial).
        Per-env independence is index arithmetic: each env column has its
        own position/full state and sampling validity window.
        A key's *storage* shape is decided at allocation from its dtype and
        item shape alone (`_storage_item`, recorded as the `replay.store`
        telemetry event): pixels are kept lane-dense, `[capacity, n_envs,
        prod(item)]`, so that both programs touch only the rows they write
        and read; vectors and scalars are stored as they arrive. The
        logical shape is what every method takes and returns, and what all
        four checkpoint forms hold.
      - **host**/memmap: one numpy `ReplayBuffer` per env (adds are cheap on
        host; capacities beyond HBM).
    """

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        storage: str = "device",
        memmap_dir: str | os.PathLike | None = None,
        sequential: bool = False,
        obs_keys: Sequence[str] = ("observations",),
        seed: int = 0,
        split: str = "even",
        stage_rows: int | None = None,
    ):
        if buffer_size <= 0:
            raise ValueError(f"buffer size must be > 0, got {buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"n_envs must be > 0, got {n_envs}")
        if split not in ("even", "multinomial"):
            raise ValueError(f"split must be 'even' or 'multinomial', got {split!r}")
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._storage_kind = storage
        self._memmap_dir = Path(memmap_dir) if memmap_dir is not None else None
        self._sequential = sequential
        self._obs_keys = tuple(obs_keys)
        self._seed = seed
        self._split = split
        self._np_rng = np.random.default_rng(seed)
        # host path: one ReplayBuffer per env
        self._buf: list[ReplayBuffer] | None = None
        # device path: unified store + per-env head state
        self._store: dict[str, jax.Array] | None = None
        # logical item shape of every key the store keeps in another shape
        self._items: dict[str, tuple[int, ...]] = {}
        self._upos = np.zeros(n_envs, dtype=np.int64)
        self._ufull = np.zeros(n_envs, dtype=bool)
        self._epoch = 0
        # uncommitted reserve() head advance (see add_direct)
        self._pending_reserve: tuple[np.ndarray, int] | None = None
        self._key = jax.random.PRNGKey(seed)
        # device path: optional host-side staging of full-width adds —
        # staged rows flush as ONE batched scatter (one transfer per key
        # per flush) at the next sample/surgery/checkpoint access, instead
        # of one transfer per key per step. OFF by default (stage_rows=0):
        # the batched flush sits on the sample critical path, where per-step
        # adds overlap with policy-step compute; not measured on the chip.
        # Opt in via stage_rows or SHEEPRL_TPU_REPLAY_STAGE_ROWS.
        if stage_rows is None:
            stage_rows = int(os.environ.get("SHEEPRL_TPU_REPLAY_STAGE_ROWS", "0"))
        self._staged: list[dict[str, np.ndarray]] = []
        self._staged_rows = 0
        self._stage_start: np.ndarray | None = None
        # no clamp to buffer_size: _flush_staged trims over-capacity batches
        # to the last buffer_size rows with the correct start adjustment, so
        # a larger cap just means fewer flushes (the point of the feature)
        self._stage_cap = stage_rows

    @property
    def buffer(self):
        if self._storage_kind == "device":
            if self._store is None and not self._staged:
                return None
            return tuple(_AsyncEnvView(self, e) for e in range(self._n_envs))
        return tuple(self._buf) if self._buf is not None else None

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def prefers_host_adds(self) -> bool:
        """True when `add` wants host numpy values: host/memmap storage
        (device arrays would force a blocking device->host pull per key),
        or opt-in staging (which batches HOST rows and skips any add that
        carries a device array). The mains consult this before reusing the
        policy step's device obs puts in `add`."""
        return self._storage_kind != "device" or self._stage_cap > 0

    @property
    def is_device_backed(self) -> bool:
        return self._storage_kind == "device"

    @property
    def epoch(self) -> int:
        """Monotonic write counter (see ReplayBuffer.epoch): bumped by every
        add / add_direct commit / row surgery / restore, the pipeline
        SamplePrefetcher's epoch-consistency guard."""
        return self._epoch

    def get_sample_state(self):
        """Sampler PRNG snapshot (device key + numpy partition rng + the
        per-env sub-buffer states on the host path) — the rewind target for
        the SamplePrefetcher's discarded-prefetch path."""
        sub = (
            tuple(b.get_sample_state() for b in self._buf)
            if self._buf is not None
            else None
        )
        return (self._key, self._np_rng.bit_generator.state, sub)

    def set_sample_state(self, state) -> None:
        self._key = state[0]
        self._np_rng.bit_generator.state = state[1]
        if state[2] is not None and self._buf is not None:
            for b, s in zip(self._buf, state[2]):
                b.set_sample_state(s)

    @property
    def full(self):
        if self._storage_kind == "device":
            if self._store is None and not self._staged:
                return None
            return tuple(bool(f) for f in self._ufull)
        if self._buf is None:
            return None
        return tuple(b.full for b in self._buf)

    def __len__(self) -> int:
        return self._buffer_size

    # -- host path: one ReplayBuffer per env ---------------------------------
    def _ensure_buffers(self) -> None:
        if self._buf is not None:
            return
        cls = SequentialReplayBuffer if self._sequential else ReplayBuffer
        self._buf = [
            cls(
                self._buffer_size,
                n_envs=1,
                storage=self._storage_kind,
                memmap_dir=(
                    self._memmap_dir / f"env_{i}" if self._memmap_dir is not None else None
                ),
                obs_keys=self._obs_keys,
                seed=self._seed + i,
            )
            for i in range(self._n_envs)
        ]

    # -- device path: unified store ------------------------------------------
    def _plan_store(self, data: Mapping[str, "np.ndarray | jax.Array"]) -> dict:
        """Decide each key's on-device storage shape from what its
        `[T, n_envs, *item]` array itself shows (`_storage_item`), remember
        the logical item shape of every key stored otherwise, and record
        the decision once (`replay.store` telemetry event, beside
        `replay.transport`). Returns `key -> storage item shape`."""
        from ..telemetry.core import emit

        lead = (self._buffer_size, self._n_envs)
        plan, report = {}, {}
        self._items = {}
        for k, v in data.items():
            item, dtype = tuple(v.shape[2:]), np.dtype(v.dtype)
            plan[k], why = _storage_item(item, dtype)
            if plan[k] != item:
                self._items[k] = item
            report[k] = {
                "logical": [*lead, *item],
                "storage": [*lead, *plan[k]],
                "dtype": dtype.name,
                "bytes": int(np.prod(lead + plan[k], dtype=np.int64)) * dtype.itemsize,
                "format": why,
            }
        emit("replay.store", keys=report)
        return plan

    def _allocate_store(self, data: Batch) -> None:
        plan = self._plan_store(data)
        self._store = {
            k: jnp.zeros(
                (self._buffer_size, self._n_envs, *plan[k]), dtype=v.dtype
            )
            for k, v in data.items()
        }

    def _logical(self, key: str, stored):
        """A `[rows, envs, *storage item]` block of `key` (device or host)
        in the buffer's logical `[rows, envs, *item]` shape."""
        item = self._items.get(key)
        return stored if item is None else stored.reshape(*stored.shape[:2], *item)

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    @staticmethod
    # sheeplint: disable=SL001 — sub-cache-floor compile, never deserialized.
    # Donation lets the output take the ring's buffer; that the scatter then
    # writes its rows and copies nothing else is the storage shape's doing
    # (`_storage_item`), and `store_check.py` reads it off the compiled text
    @partial(jax.jit, donate_argnums=0, static_argnums=(3, 4))
    @jax.named_scope("replay/add")
    def _store_add_packed(store, direct, packed, layout, data_len):
        """Per-step scatter fed by ONE host->device transfer per width class
        (the write-head/env indices ride inside the packed group as
        `__idx__`) instead of one per key — in the hot loop the whole add is
        a single transfer plus the reused policy obs put.

        `direct` holds values already resident on device (the training loops
        reuse the policy step's obs put and its action output); `packed[g]`
        is the flat byte-view concatenation of the host values of width
        class `g`, unpacked by the static `layout` of
        `(key, dtype_str, shape, offset, size)` rows."""
        capacity = next(iter(store.values())).shape[0]
        data = _unpack_values(direct, packed, layout)
        idx = data.pop("__idx__")
        n_sel = idx.shape[0] // 2
        starts, cols = idx[:n_sel], idx[n_sel:]
        rows = (starts[None, :] + jnp.arange(data_len)[:, None]) % capacity

        def put(ring, value):
            # the value in the key's storage shape: a no-op for a key stored
            # as is, the item axes folded into one for a lane-dense key
            value = value.reshape(*value.shape[:2], *ring.shape[2:])
            return ring.at[rows, cols[None, :]].set(value.astype(ring.dtype))

        return {k: put(store[k], data[k]) for k in store}

    def _flush_staged(self) -> None:
        """Write all staged full-width rows with one scatter. Bookkeeping
        (`_upos`/`_ufull`) already advanced at stage time; rows are computed
        from the position snapshot taken when staging began."""
        if not self._staged:
            return
        staged, self._staged = self._staged, []
        start = self._stage_start
        self._stage_start = None
        self._staged_rows = 0
        data = {k: np.concatenate([d[k] for d in staged], axis=0) for k in staged[0]}
        total = next(iter(data.values())).shape[0]
        if total > self._buffer_size:
            start = (start + (total - self._buffer_size)) % self._buffer_size
            data = {k: v[-self._buffer_size :] for k, v in data.items()}
            total = self._buffer_size
        if self._store is None:
            self._allocate_store(data)
        self._store = self._packed_scatter(
            data, start, np.arange(self._n_envs, dtype=np.int64), total
        )

    def _set_at(self, env: int, key: str, time_idx: int, value) -> None:
        self._flush_staged()
        if self._store is None:
            raise RuntimeError("buffer not initialized; add data first")
        item = jnp.asarray(value).reshape(self._store[key].shape[2:])
        self._store[key] = self._store[key].at[time_idx, env].set(
            item.astype(self._store[key].dtype)
        )
        self._epoch += 1

    def add(self, data: Mapping[str, np.ndarray], indices: Sequence[int] | None = None) -> None:
        data = _as_time_env(dict(data))
        if indices is None:
            indices = range(self._n_envs)
        cols = np.asarray(list(indices), dtype=np.int64)
        data_len, width = next(iter(data.values())).shape[:2]
        if width != cols.size:
            raise ValueError(
                f"data has {width} env columns but {cols.size} indices given"
            )
        if data_len == 0 or cols.size == 0:
            return
        if self._storage_kind != "device":
            self._ensure_buffers()
            for col, env_idx in enumerate(cols):
                self._buf[env_idx].add({k: v[:, col : col + 1] for k, v in data.items()})
            self._epoch += 1
            return
        if data_len > self._buffer_size:
            data = {k: v[-self._buffer_size :] for k, v in data.items()}
            data_len = self._buffer_size
        if (
            self._stage_cap > 0
            and cols.size == self._n_envs
            and np.array_equal(cols, np.arange(self._n_envs))
            and all(isinstance(v, np.ndarray) for v in data.values())
        ):
            if self._staged and set(data) != set(self._staged[0]):
                self._flush_staged()
            if not self._staged:
                self._stage_start = self._upos.copy()
            # copy: add() has copy-in semantics (the unstaged path reads via
            # jnp.asarray immediately); callers mutate step rows in place
            # after add, which must not reach the deferred flush
            self._staged.append({k: np.array(v) for k, v in data.items()})
            self._staged_rows += data_len
            starts = self._upos
            self._ufull |= starts + data_len >= self._buffer_size
            self._upos = (starts + data_len) % self._buffer_size
            self._epoch += 1
            if self._staged_rows >= self._stage_cap:
                self._flush_staged()
            return
        self._flush_staged()
        if self._store is None:
            self._allocate_store(data)
        starts = self._upos[cols]
        if cols.size < self._n_envs:
            # one program for a reset add of any width: widen it to every
            # column; the added columns name the env index one past the ring,
            # so the scatter drops them (jax's default out-of-bounds mode)
            pad = self._n_envs - cols.size
            data = {k: _pad_columns(v, pad) for k, v in data.items()}
            scatter_starts = np.concatenate([starts, np.zeros(pad, np.int64)])
            scatter_cols = np.concatenate([cols, np.full(pad, self._n_envs, np.int64)])
        else:
            scatter_starts, scatter_cols = starts, cols
        self._store = self._packed_scatter(data, scatter_starts, scatter_cols, data_len)
        self._ufull[cols] |= starts + data_len >= self._buffer_size
        self._upos[cols] = (starts + data_len) % self._buffer_size
        self._epoch += 1

    def _packed_scatter(self, data, starts, cols, data_len):
        """Pack host values into one transfer per width class and scatter;
        values already on device (e.g. the policy step's obs put, reused by
        the mains) go straight into the scatter without another round-trip.
        The scatter indices ride the packed transfer as `__idx__`."""
        idx = np.concatenate([starts, cols]).astype(np.int32)
        direct, packed, layout = _pack_host_values({**data, "__idx__": idx})
        return self._store_add_packed(
            self._store, direct, packed, layout, data_len
        )

    # -- blob transport (zero-transfer adds) ----------------------------------
    def reserve(self, data_len: int = 1) -> np.ndarray:
        """Pick the write rows for a full-width `add_direct` and return
        `concat(starts, cols)` as int32 — the index vector that rides the
        step blob (`data/blob.py`) to the device, so the subsequent scatter
        needs NO host->device transfer of its own. The head advance is
        DEFERRED to `add_direct` (ADVICE r3): if codec.pack or the blob-step
        jit raises in between, the never-written row stays outside the
        sampler's valid window, and a retry `reserve()` reuses the same
        rows. reserve-then-add_direct must not interleave with other adds
        for the same rows."""
        if self._storage_kind != "device" or self._stage_cap > 0:
            raise RuntimeError(
                "reserve()/add_direct() require device storage without staging"
            )
        cols = np.arange(self._n_envs)
        starts = self._upos.copy()
        self._pending_reserve = (starts, int(data_len))
        return np.concatenate([starts, cols]).astype(np.int32)

    def add_direct(self, data: Mapping[str, jax.Array], idx: jax.Array, data_len: int = 1) -> None:
        """Scatter a full-width row whose values (and `idx`, from
        `reserve()` via the step blob) are ALREADY device-resident — the
        zero-transfer half of the blob transport. Shapes `[data_len,
        n_envs, *item]`, same contract as `add`. Commits the head advance
        `reserve()` deferred, so the row becomes sampleable only once its
        scatter has been dispatched."""
        pending = self._pending_reserve
        if pending is not None and pending[1] != data_len:
            raise ValueError(
                f"add_direct data_len {data_len} != reserved {pending[1]}"
            )
        if self._store is None:
            self._allocate_store(dict(data))
        self._store = self._store_add_packed(
            self._store, {**data, "__idx__": idx}, {}, (), data_len
        )
        if pending is not None:
            starts, reserved_len = pending
            self._ufull |= starts + reserved_len >= self._buffer_size
            self._upos = (starts + reserved_len) % self._buffer_size
            self._pending_reserve = None
        self._epoch += 1

    # -- sampling -------------------------------------------------------------
    def _partition(self, batch_size: int) -> np.ndarray:
        """Per-env sample counts. The default `split="even"` is a TPU-first
        redesign: every env contributes `B // n_envs` (remainder rotating),
        so gather shapes stay static under jit. The reference's multinomial
        bincount partition (buffers.py:687-693) remains available as
        `split="multinomial"` (with the unified device store its shapes are
        static too: counts only change the gather's env-index *contents*)."""
        if self._split == "even":
            base, rem = divmod(batch_size, self._n_envs)
            counts = np.full(self._n_envs, base, dtype=np.int64)
            if rem:
                start = int(self._np_rng.integers(0, self._n_envs))
                counts[(start + np.arange(rem)) % self._n_envs] += 1
            return counts
        return np.bincount(
            self._np_rng.integers(0, self._n_envs, size=batch_size),
            minlength=self._n_envs,
        )

    def _windows(self, exclude: int) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized per-env validity windows — the base `_valid_ranges`
        rule (buffers.py:166-186) over the position vector."""
        pos = self._upos
        cap = self._buffer_size
        first = pos - exclude
        second_end = np.where(first >= 0, cap, cap + first)
        n_valid = np.where(
            self._ufull, np.maximum(first, 0) + second_end - pos, first
        )
        return np.maximum(first, 0), n_valid

    @staticmethod
    @partial(
        jax.jit,
        static_argnames=(
            "n_samples", "seq_len", "sequential", "sample_next_obs", "obs_keys", "items",
        ),
    )
    @jax.named_scope("replay/sample")
    def _store_sample(
        store, key, packed_idx,
        n_samples, seq_len, sequential, sample_next_obs, obs_keys, items=(),
    ):
        """One gather for the whole batch: each output row draws a start
        index inside its env's validity window, windows index the ring
        modulo capacity, and the env column selects the ring. `packed_idx`
        is `concat(env_idx, first, n_valid, pos)` as int32 — one transfer
        for all four index vectors."""
        capacity, n_envs = next(iter(store.values())).shape[:2]
        bd = packed_idx.shape[0] - 3 * n_envs
        env_idx = packed_idx[:bd]
        first, n_valid, pos = (
            packed_idx[bd : bd + n_envs],
            packed_idx[bd + n_envs : bd + 2 * n_envs],
            packed_idx[bd + 2 * n_envs :],
        )
        nv = n_valid[env_idx]
        # exact integer sampling (matching the base ReplayBuffer paths):
        # float32-uniform scaling biases windows approaching 2^24 entries and
        # can never return the top index; maxval broadcasts per-row (>=1 so
        # a not-yet-valid env degenerates to index 0 instead of UB)
        r = jax.random.randint(key, (bd,), 0, jnp.maximum(nv, 1))
        f = first[env_idx]
        p = pos[env_idx]
        start = jnp.where(r < f, r, r - f + p)
        idx = (start[:, None] + jnp.arange(seq_len)) % capacity  # [BD, L]
        ecol = env_idx[:, None]
        logical = dict(items)

        def gather(k, ix):
            g = store[k][ix, ecol]  # [BD, L, *storage item]
            if k in logical:
                g = g.reshape(*g.shape[:2], *logical[k])  # [BD, L, *item]
            if not sequential:
                return g[:, 0]
            batch = bd // n_samples
            g = g.reshape(n_samples, batch, seq_len, *g.shape[2:])
            return jnp.swapaxes(g, 1, 2)  # [n_samples, L, B, *item]

        out = {k: gather(k, idx) for k in store}
        if sample_next_obs:
            nxt = (idx + 1) % capacity
            for k in obs_keys:
                out[f"next_{k}"] = gather(k, nxt)
        return out

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        sequence_length: int = 1,
        n_samples: int = 1,
        **_: object,
    ) -> Batch:
        """Partitions the batch across envs and samples each env's window
        (reference buffers.py:687-699); device storage runs the whole batch
        as one jitted gather."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError("batch_size and n_samples must be > 0")
        if self._storage_kind != "device":
            return self._sample_host(
                batch_size, sample_next_obs, sequence_length, n_samples
            )
        self._flush_staged()
        if self._store is None:
            raise RuntimeError("no samples in buffer; call add() first")
        if self._sequential and sequence_length > self._buffer_size:
            raise ValueError(f"too long sequence_length ({sequence_length})")
        counts = self._partition(batch_size)
        seq_len = sequence_length if self._sequential else 1
        exclude = (seq_len - 1) if self._sequential else (1 if sample_next_obs else 0)
        first, n_valid = self._windows(exclude)
        bad = (counts > 0) & (n_valid <= 0)
        if bad.any():
            if self._sequential:
                e = int(np.argmax(bad))
                raise ValueError(
                    f"too long sequence_length ({sequence_length}) for env "
                    f"{e} with pos={int(self._upos[e])}, full={bool(self._ufull[e])}"
                )
            raise RuntimeError(
                "not enough valid entries to sample; add more data first"
            )
        env_row = np.repeat(np.arange(self._n_envs, dtype=np.int32), counts)
        env_idx = np.tile(env_row, n_samples) if self._sequential else env_row
        return self._store_sample(
            self._store,
            self._next_key(),
            jnp.asarray(
                np.concatenate(
                    [env_idx, first, n_valid, self._upos]
                ).astype(np.int32)
            ),
            n_samples,
            seq_len,
            self._sequential,
            sample_next_obs,
            self._obs_keys if sample_next_obs else (),
            tuple(self._items.items()),
        )

    def _sample_host(
        self, batch_size: int, sample_next_obs: bool, sequence_length: int, n_samples: int
    ) -> Batch:
        if self._buf is None:
            raise RuntimeError("no samples in buffer; call add() first")
        counts = self._partition(batch_size)
        parts = []
        for b, n in zip(self._buf, counts):
            if n == 0:
                continue
            if self._sequential:
                parts.append(
                    b.sample(
                        int(n),
                        sample_next_obs=sample_next_obs,
                        sequence_length=sequence_length,
                        n_samples=n_samples,
                    )
                )
            else:
                parts.append(b.sample(int(n), sample_next_obs=sample_next_obs))
        axis = 2 if self._sequential else 0
        keys = parts[0].keys()
        return {k: np.concatenate([p[k] for p in parts], axis=axis) for k in keys}

    # -- checkpointing --------------------------------------------------------
    def to_state_dict(self) -> dict:
        """Per-env state list — one format for both storage backends (the
        device store serializes as per-env column slices)."""
        if self._storage_kind == "device":
            self._flush_staged()
            if self._store is None:
                empty = {
                    "buf": None, "pos": 0, "full": False,
                    "buffer_size": self._buffer_size, "n_envs": 1,
                }
                return {"buffers": [dict(empty) for _ in range(self._n_envs)]}
            host = {k: self._logical(k, np.asarray(v)) for k, v in self._store.items()}
            return {
                "buffers": [
                    {
                        "buf": {k: v[:, i : i + 1] for k, v in host.items()},
                        "pos": int(self._upos[i]),
                        "full": bool(self._ufull[i]),
                        "buffer_size": self._buffer_size,
                        "n_envs": 1,
                    }
                    for i in range(self._n_envs)
                ]
            }
        self._ensure_buffers()
        return {"buffers": [b.to_state_dict() for b in self._buf]}

    def load_state_dict(self, state: dict) -> None:
        self._flush_staged()
        # a reservation taken against the pre-restore head must not commit
        # over the restored one
        self._pending_reserve = None
        buffers = state["buffers"]
        if len(buffers) != self._n_envs:
            raise ValueError("checkpointed buffer n_envs mismatch")
        if self._storage_kind == "device":
            # mirror the host branch's per-env ReplayBuffer validation: each
            # entry must be a 1-env column or the concatenation below builds a
            # store whose env width differs from self._n_envs and only fails
            # later with an opaque shape error during add/sample
            for s in buffers:
                if s["buffer_size"] != self._buffer_size:
                    raise ValueError("checkpointed buffer shape mismatch")
                if s.get("n_envs", 1) != 1:
                    raise ValueError("checkpointed buffer entry n_envs != 1")
                if s["buf"] is not None and any(
                    v.shape[1] != 1 for v in s["buf"].values()
                ):
                    raise ValueError("checkpointed buffer env-width != 1")
            if all(s["buf"] is None for s in buffers):
                self._store = None
            else:
                # envs that never received data (buf=None) contribute a zero
                # column; their pos/full restore as 0/False below
                template = next(s["buf"] for s in buffers if s["buf"] is not None)
                plan = self._plan_store(template)
                self._store = {
                    k: jnp.asarray(
                        np.concatenate(
                            [
                                s["buf"][k]
                                if s["buf"] is not None
                                else np.zeros_like(template[k])
                                for s in buffers
                            ],
                            axis=1,
                        ).reshape(self._buffer_size, self._n_envs, *plan[k])
                    )
                    for k in template.keys()
                }
            self._upos = np.asarray([int(s["pos"]) for s in buffers], dtype=np.int64)
            self._ufull = np.asarray([bool(s["full"]) for s in buffers], dtype=bool)
            self._epoch += 1
            return
        self._ensure_buffers()
        for b, s in zip(self._buf, buffers):
            b.load_state_dict(s)
        self._epoch += 1

    def save(self, path: str) -> None:
        """Serialize all per-env rings into one `.npz` (the Dreamer
        `checkpoint_buffer` path, reference callback.py:23-64)."""
        st = self.to_state_dict()
        flat: dict[str, np.ndarray] = {
            "n_envs": np.int64(self._n_envs),
            "buffer_size": np.int64(self._buffer_size),
        }
        for i, s in enumerate(st["buffers"]):
            flat[f"b{i}_pos"] = np.int64(s["pos"])
            flat[f"b{i}_full"] = np.bool_(s["full"])
            for k, v in (s["buf"] or {}).items():
                flat[f"b{i}_buf_{k}"] = v
        flat["sampler_state"] = _encode_sample_state(self.get_sample_state())
        np.savez(path, **flat)

    def load(self, path: str) -> None:
        data = np.load(path)
        if int(data["n_envs"]) != self._n_envs:
            raise ValueError("checkpointed buffer n_envs mismatch")
        if int(data["buffer_size"]) != self._buffer_size:
            raise ValueError("checkpointed buffer shape mismatch")
        buffers = []
        for i in range(self._n_envs):
            prefix = f"b{i}_buf_"
            bufs = {k[len(prefix):]: data[k] for k in data.files if k.startswith(prefix)}
            buffers.append(
                {
                    "buf": bufs or None,
                    "pos": int(data[f"b{i}_pos"]),
                    "full": bool(data[f"b{i}_full"]),
                    "buffer_size": self._buffer_size,
                    "n_envs": 1,
                }
            )
        self.load_state_dict({"buffers": buffers})
        if "sampler_state" in data.files:
            self.set_sample_state(_decode_sample_state(data["sampler_state"]))

    # -- wire round-trip ------------------------------------------------------
    _WIRE_MAGIC = b"SAB1"

    def to_bytes(self) -> bytes:
        """Versioned pickle-free frame: one sub-frame per env column (meta +
        `pack_tree` ring blob), plus the full sampler state including the
        host path's per-env sub-sampler states."""
        st = self.to_state_dict()
        meta = {
            "class": type(self).__name__,
            "buffer_size": self._buffer_size,
            "n_envs": self._n_envs,
            "sequential": self._sequential,
            "split": self._split,
            "obs_keys": list(self._obs_keys),
            "seed": self._seed,
        }
        parts = []
        for s in st["buffers"]:
            sub = json.dumps(
                {
                    "pos": int(s["pos"]),
                    "full": bool(s["full"]),
                    "has_buf": s["buf"] is not None,
                }
            ).encode()
            blob = pack_tree(s["buf"]) if s["buf"] is not None else b""
            parts.append(_U32.pack(len(sub)) + sub + _U64.pack(len(blob)) + blob)
        return _wire_frame(
            self._WIRE_MAGIC, meta, self.get_sample_state(), b"".join(parts)
        )

    @classmethod
    def from_bytes(
        cls,
        data: bytes,
        storage: str = "host",
        memmap_dir: str | os.PathLike | None = None,
    ) -> "AsyncReplayBuffer":
        meta, sampler, payload = _wire_unframe(
            cls._WIRE_MAGIC, data, cls.__name__
        )
        buf = cls(
            meta["buffer_size"],
            n_envs=meta["n_envs"],
            storage=storage,
            memmap_dir=memmap_dir,
            sequential=meta["sequential"],
            obs_keys=tuple(meta["obs_keys"]),
            seed=meta["seed"],
            split=meta["split"],
        )
        buffers = []
        off = 0
        for _ in range(meta["n_envs"]):
            if off + 4 > len(payload):
                raise WireFormatError("truncated per-env payload")
            (sub_len,) = _U32.unpack_from(payload, off)
            off += 4
            sub = json.loads(payload[off : off + sub_len].decode())
            off += sub_len
            (blob_len,) = _U64.unpack_from(payload, off)
            off += 8
            ring = (
                unpack_tree(payload[off : off + blob_len])
                if sub["has_buf"]
                else None
            )
            off += blob_len
            buffers.append(
                {
                    "buf": ring,
                    "pos": sub["pos"],
                    "full": sub["full"],
                    "buffer_size": meta["buffer_size"],
                    "n_envs": 1,
                }
            )
        buf.load_state_dict({"buffers": buffers})
        buf.set_sample_state(sampler)
        return buf
