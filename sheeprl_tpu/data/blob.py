"""One-transfer step transport for the interaction hot loop.

A device-buffer step pays two host->device transfers: the policy obs put
and the replay add's packed floats+indices put. `StepBlobCodec` merges
them into one: the raw obs (uint8 pixels, float vectors/masks), the replay
row's host floats (rewards/dones/is_first), and the ring write-head
indices ride ONE int32 blob; the policy-step jit unpacks it on device
(bit-exact bitcasts, no value conversion) and the replay scatter consumes
the unpacked device arrays directly (`AsyncReplayBuffer.reserve` +
`add_direct`) — zero further transfers.

Layout (static per obs shapes + n_envs):

    [ 4-byte section: float32 values bit-viewed as int32, then the int32
      write-head indices ][ 1-byte section: uint8 values, zero-padded to
      a multiple of 4, bit-viewed as int32 ]

Byte order: numpy views on a little-endian host and XLA's
`bitcast_convert_type` (which defines the minor dimension as the
little-endian pieces of the wider element) agree, so the roundtrip is
bit-exact — asserted by `tests/test_data/test_blob.py`.

Pipeline ordering contract (ISSUE 4): with the latency-hiding pipeline on,
the loop dispatches the action indices' `copy_to_host_async`
(`ActionPipeline.dispatch`) BETWEEN the blob jit returning and
`rb.add_direct` — the copy then overlaps the replay scatter's dispatch —
and blocks on the host value only at `env.step`. `add_direct` commits the
`reserve()`d head advance and bumps `buffer.epoch`, which is exactly the
counter the `SamplePrefetcher` epoch-consistency guard reads: a sample
prefetched before the commit can never be served as if it contained the
row, because the commit advances the epoch past the prefetch's snapshot.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["StepBlobCodec", "verify_blob_roundtrip"]


def verify_blob_roundtrip(codec: "StepBlobCodec") -> bool:
    """One tiny live roundtrip asserting the pack -> device bitcast-unpack
    path is bit-exact ON THE CURRENT BACKEND. The CPU tests pin the
    little-endian semantics, but the accelerator lowering of the u8<->i32
    `bitcast_convert_type` can only be checked live — on a mismatch callers
    use the separate-puts transport instead of shipping corrupt rows. The
    transport chosen is recorded either way (`replay.transport` telemetry
    event, plus a RuntimeWarning on the downgrade); a pack/unpack that
    RAISES is a bug, not a backend disagreement, and propagates."""
    import warnings

    from ..telemetry.core import emit

    rng = np.random.default_rng(0)
    u8 = {k: rng.integers(0, 256, shape, dtype=np.uint8) for k, shape, _, _ in codec._u8}
    f32 = {
        k: rng.normal(size=shape).astype(np.float32)
        for k, shape, _, _ in codec._f32
    }
    idx = rng.integers(-(2**31), 2**31 - 1, codec.idx_len, dtype=np.int32)
    blob = codec.pack(u8, f32, idx)
    out_u8, out_f32, out_idx = jax.jit(codec.unpack)(jnp.asarray(blob))
    mismatch = None
    for k, v in u8.items():
        if not np.array_equal(np.asarray(out_u8[k]), v):
            mismatch = f"uint8 roundtrip mismatch on key {k!r}"
    for k, v in f32.items():
        if not np.array_equal(np.asarray(out_f32[k]).view(np.int32), v.view(np.int32)):
            mismatch = f"float32 bit roundtrip mismatch on key {k!r}"
    if not np.array_equal(np.asarray(out_idx), idx):
        mismatch = "int32 index roundtrip mismatch"
    if mismatch is None:
        emit("replay.transport", transport="blob", reason="roundtrip bit-exact")
        return True
    emit("replay.transport", transport="separate_puts", reason=mismatch)
    warnings.warn(
        f"step-blob transport disabled, falling back to separate "
        f"host->device puts: {mismatch}",
        RuntimeWarning,
        stacklevel=2,
    )
    return False


class StepBlobCodec:
    """Pack/unpack one interaction step into a single int32 blob.

    `u8_shapes` / `f32_shapes`: per-key value shapes WITHOUT the leading
    n_envs axis (e.g. `{"rgb": (64, 64, 3)}`); every value is transported
    at `[n_envs, *shape]`. `idx_len` is the length of the int32 index
    vector riding along (`2 * n_envs` for `concat(starts, cols)`)."""

    @classmethod
    def for_step(cls, obs, obs_keys, n_envs: int, float_keys):
        """Build the codec for an interaction-step row from the first
        observation's shapes/dtypes: uint8 obs keys go to the 1-byte
        section, everything else plus the `[n_envs, 1]` `float_keys`
        extras (rewards/dones/...) to the 4-byte section, and the ring
        write indices (`concat(starts, cols)`, len `2 * n_envs`) ride
        along. Returns `(codec, u8_keys, f32_obs_keys)` — the single
        construction shared by every main's blob path."""
        obs_keys = tuple(obs_keys)
        u8_keys = tuple(
            k for k in obs_keys if np.asarray(obs[k]).dtype == np.uint8
        )
        f32_obs_keys = tuple(k for k in obs_keys if k not in u8_keys)
        codec = cls(
            {k: np.asarray(obs[k]).shape[1:] for k in u8_keys},
            {
                **{k: np.asarray(obs[k]).shape[1:] for k in f32_obs_keys},
                **{k: (1,) for k in float_keys},
            },
            idx_len=2 * n_envs,
            n_envs=n_envs,
        )
        return codec, u8_keys, f32_obs_keys

    def __init__(
        self,
        u8_shapes: Mapping[str, Sequence[int]],
        f32_shapes: Mapping[str, Sequence[int]],
        idx_len: int,
        n_envs: int,
    ) -> None:
        self.n_envs = int(n_envs)
        self.idx_len = int(idx_len)
        self._f32 = []  # (key, shape, offset_in_elems, size_in_elems)
        off = 0
        for k, shape in f32_shapes.items():
            size = int(np.prod((n_envs, *shape)))
            self._f32.append((k, (n_envs, *tuple(int(s) for s in shape)), off, size))
            off += size
        self._idx_off = off
        self._n4 = off + self.idx_len  # elements in the 4-byte section
        self._u8 = []
        off = 0
        for k, shape in u8_shapes.items():
            size = int(np.prod((n_envs, *shape)))
            self._u8.append((k, (n_envs, *tuple(int(s) for s in shape)), off, size))
            off += size
        self._u8_bytes = off
        self._u8_padded = -(-off // 4) * 4
        self.blob_len = self._n4 + self._u8_padded // 4

    def pack(
        self,
        u8_values: Mapping[str, np.ndarray],
        f32_values: Mapping[str, np.ndarray],
        idx: np.ndarray,
    ) -> np.ndarray:
        """Host side: one int32 array ready for a single `jnp.asarray`."""
        blob = np.empty(self.blob_len, np.int32)
        w4 = blob[: self._n4]
        for k, shape, off, size in self._f32:
            v = np.asarray(f32_values[k])
            if v.dtype.kind not in "fiub":
                # non-numeric / complex inputs never convert meaningfully
                # (complex would silently drop its imaginary part)
                raise TypeError(
                    f"blob f32 section got dtype {v.dtype} for key {k!r}; "
                    "only float/int/uint/bool values are packable"
                )
            if v.dtype.kind in "iu" and v.size and int(v.ravel().max()) > 2**24:
                # the first integer that does NOT survive the float32
                # value-conversion is 2**24 + 1 (ADVICE r3) — unlike the
                # bit-exact packed-add path; small integer obs (e.g.
                # MineDojo's int32 equipment ids) convert exactly and pass
                raise TypeError(
                    f"blob f32 section got integer dtype {v.dtype} for key "
                    f"{k!r} with values > 2**24 that do not survive the "
                    "float32 conversion; convert explicitly (or keep them "
                    "uint8 to ride the bit-exact u8 section)"
                )
            if v.dtype.kind == "i" and v.size and int(v.ravel().min()) < -(2**24):
                raise TypeError(
                    f"blob f32 section got integer dtype {v.dtype} for key "
                    f"{k!r} with values < -(2**24) that do not survive the "
                    "float32 conversion; convert explicitly"
                )
            v = np.ascontiguousarray(v, np.float32).reshape(-1)
            w4[off : off + size] = v.view(np.int32)
        w4[self._idx_off :] = np.asarray(idx, np.int32).reshape(-1)
        tail = np.zeros(self._u8_padded, np.uint8)
        for k, shape, off, size in self._u8:
            tail[off : off + size] = np.ascontiguousarray(
                u8_values[k], np.uint8
            ).reshape(-1)
        blob[self._n4 :] = tail.view(np.int32)
        return blob

    def unpack(self, blob: jax.Array):
        """Device side (inside jit): `(u8_dict, f32_dict, idx)` — exact
        bit-level inverse of `pack`."""
        w4 = blob[: self._n4]
        f32 = {}
        for k, shape, off, size in self._f32:
            f32[k] = jax.lax.bitcast_convert_type(
                w4[off : off + size], jnp.float32
            ).reshape(shape)
        idx = w4[self._idx_off :]
        u8_flat = jax.lax.bitcast_convert_type(blob[self._n4 :], jnp.uint8).reshape(-1)
        u8 = {}
        for k, shape, off, size in self._u8:
            u8[k] = u8_flat[off : off + size].reshape(shape)
        return u8, f32, idx
