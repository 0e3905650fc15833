"""Buffer invariants, mirroring the reference's test strategy
(/root/reference/tests/test_data/): wrap-around add, pos/full invariants,
oversized inserts, sample-validity windows, memmap variants — for both the
HBM (device) and host storage backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data import (
    AsyncReplayBuffer,
    EpisodeBuffer,
    ReplayBuffer,
    SequentialReplayBuffer,
    stage_batch,
)
from sheeprl_tpu.telemetry.compile_tracker import CompileTracker

STORAGES = ["device", "host"]


def make_rows(t, n_envs, start=0):
    """rows with value = global step index, easy to assert on"""
    vals = (start + np.arange(t))[:, None, None] * np.ones((1, n_envs, 1), np.float32)
    return {"observations": vals, "dones": np.zeros((t, n_envs, 1), np.float32)}


@pytest.mark.parametrize("storage", STORAGES)
def test_add_and_pos_wraparound(storage):
    rb = ReplayBuffer(5, n_envs=2, storage=storage)
    rb.add(make_rows(3, 2))
    assert not rb.full
    rb.add(make_rows(3, 2, start=3))
    assert rb.full
    # pos wrapped to 1; slot 0 holds step 5
    obs = np.asarray(rb["observations"])
    assert obs[0, 0, 0] == 5.0
    assert obs[1, 0, 0] == 1.0  # not yet overwritten


@pytest.mark.parametrize("storage", STORAGES)
def test_oversized_add_keeps_last_rows(storage):
    rb = ReplayBuffer(4, n_envs=1, storage=storage)
    rb.add(make_rows(10, 1))
    assert rb.full
    obs = sorted(np.asarray(rb["observations"]).reshape(-1).tolist())
    assert obs == [6.0, 7.0, 8.0, 9.0]


@pytest.mark.parametrize("storage", STORAGES)
def test_sample_with_next_obs_excludes_last_written(storage):
    # reference semantics (buffers.py:166-186): with sample_next_obs=True the
    # entry at pos-1 is excluded (its successor at pos belongs to another
    # trajectory); without it, every slot is valid once full.
    rb = ReplayBuffer(5, n_envs=1, storage=storage)
    rb.add(make_rows(5, 1))  # full, pos=0
    rb.add(make_rows(1, 1, start=5))  # pos=1, slot0 overwritten with 5
    for _ in range(5):
        s = rb.sample(64, sample_next_obs=True)
        vals = np.asarray(s["observations"]).reshape(-1)
        # step 5 sits at slot pos-1=0 -> never sampled as current obs
        assert 5.0 not in vals
        assert set(np.unique(vals)).issubset({1.0, 2.0, 3.0, 4.0})
    # plain sampling may return every stored step
    s = rb.sample(256)
    assert set(np.unique(np.asarray(s["observations"]).reshape(-1))) == {
        1.0, 2.0, 3.0, 4.0, 5.0,
    }


@pytest.mark.parametrize("storage", STORAGES)
def test_sample_next_obs(storage):
    rb = ReplayBuffer(6, n_envs=1, storage=storage)
    rb.add(make_rows(4, 1))
    s = rb.sample(32, sample_next_obs=True)
    obs = np.asarray(s["observations"]).reshape(-1)
    nxt = np.asarray(s["next_observations"]).reshape(-1)
    np.testing.assert_allclose(nxt, obs + 1.0)


def test_sample_empty_raises():
    rb = ReplayBuffer(4)
    with pytest.raises(RuntimeError):
        rb.sample(1)
    with pytest.raises(ValueError):
        rb.sample(0)


def test_host_memmap_storage(tmp_path):
    rb = ReplayBuffer(8, n_envs=1, storage="host", memmap_dir=tmp_path / "rb")
    rb.add(make_rows(4, 1))
    assert (tmp_path / "rb" / "observations.npy").exists()
    s = rb.sample(8)
    assert s["observations"].shape == (8, 1)


@pytest.mark.parametrize("storage", STORAGES)
def test_sequential_sample_contiguity(storage):
    rb = SequentialReplayBuffer(16, n_envs=2, storage=storage)
    rb.add(make_rows(10, 2))
    s = rb.sample(4, sequence_length=5, n_samples=3)
    obs = np.asarray(s["observations"])
    assert obs.shape == (3, 5, 4, 1)
    # windows are consecutive steps
    diffs = np.diff(obs[..., 0], axis=1)
    np.testing.assert_allclose(diffs, 1.0)


@pytest.mark.parametrize("storage", STORAGES)
def test_sequential_validity_window_when_full(storage):
    rb = SequentialReplayBuffer(8, n_envs=1, storage=storage)
    rb.add(make_rows(8, 1))  # full, pos=0
    rb.add(make_rows(2, 1, start=8))  # pos=2: slots [0,1] = 8,9
    seq_len = 3
    for _ in range(5):
        s = rb.sample(16, sequence_length=seq_len)
        obs = np.asarray(s["observations"])[..., 0]  # [1, T, B]
        starts = obs[0, 0, :]
        # start index cannot fall in (pos - seq_len, pos) = slots {0,1} invalid
        # region in *slot* space; in value space all windows must be contiguous
        diffs = np.diff(obs[0], axis=0)
        np.testing.assert_allclose(diffs, 1.0)
        # windows never span the write head: values 8,9 can only appear at the
        # tail of a window ending at slot pos-1
        assert not np.any(starts == 1.0)


def test_sequential_too_long_sequence_raises():
    rb = SequentialReplayBuffer(8, n_envs=1)
    rb.add(make_rows(3, 1))
    with pytest.raises(ValueError):
        rb.sample(1, sequence_length=4)


def make_episode(length, n_keys=1, start=0):
    ep = {
        "observations": (start + np.arange(length, dtype=np.float32))[:, None],
        "dones": np.zeros((length, 1), np.float32),
    }
    ep["dones"][-1] = 1.0
    return ep


class TestEpisodeBuffer:
    def test_add_validations(self):
        eb = EpisodeBuffer(16, sequence_length=4)
        bad = make_episode(6)
        bad["dones"][2] = 1.0
        with pytest.raises(RuntimeError):
            eb.add(bad)
        no_end = make_episode(6)
        no_end["dones"][-1] = 0.0
        with pytest.raises(RuntimeError):
            eb.add(no_end)
        with pytest.raises(RuntimeError):
            eb.add(make_episode(2))  # too short
        with pytest.raises(RuntimeError):
            eb.add(make_episode(20))  # too long

    def test_eviction_keeps_capacity(self):
        eb = EpisodeBuffer(12, sequence_length=3)
        for i in range(5):
            eb.add(make_episode(5, start=10 * i))
        assert len(eb) <= 12
        # oldest episodes evicted: first remaining episode starts at >= 10
        assert eb[0]["observations"][0, 0] >= 10.0

    def test_sample_shapes_and_windows(self):
        eb = EpisodeBuffer(64, sequence_length=4)
        eb.add(make_episode(10))
        eb.add(make_episode(8, start=100))
        s = eb.sample(6, n_samples=2)
        assert s["observations"].shape == (2, 4, 6, 1)
        diffs = np.diff(s["observations"][..., 0], axis=1)
        np.testing.assert_allclose(diffs, 1.0)

    def test_prioritize_ends_hits_tail(self):
        eb = EpisodeBuffer(64, sequence_length=4, seed=1)
        eb.add(make_episode(32))
        s = eb.sample(256, prioritize_ends=True)
        # with prioritization the final window [28..31] should appear often
        starts = s["observations"][0, 0, :, 0]
        assert (starts == 28.0).mean() > 0.10

    def test_memmap_episode_eviction_cleans_files(self, tmp_path):
        eb = EpisodeBuffer(10, sequence_length=3, memmap_dir=tmp_path / "eb")
        for i in range(4):
            eb.add(make_episode(5, start=10 * i))
        dirs = list((tmp_path / "eb").iterdir())
        # capacity 10 fits two 5-step episodes
        assert len(dirs) == 2


class TestAsyncReplayBuffer:
    @pytest.mark.parametrize("storage", STORAGES)
    def test_per_env_add_with_indices(self, storage):
        arb = AsyncReplayBuffer(8, n_envs=3, storage=storage, sequential=True)
        arb.add(make_rows(4, 3))
        # add one extra row only to env 1
        arb.add(make_rows(1, 1, start=100), indices=[1])
        s = arb.sample(8, sequence_length=2, n_samples=1)
        assert s["observations"].shape == (1, 2, 8, 1)

    @pytest.mark.parametrize("storage", STORAGES)
    def test_sample_partition(self, storage):
        arb = AsyncReplayBuffer(16, n_envs=4, storage=storage, sequential=False)
        arb.add(make_rows(8, 4))
        s = arb.sample(32)
        assert s["observations"].shape == (32, 1)

    def test_even_split_static_shapes(self):
        # the default partition draws B // n_envs from every env (remainder
        # rotating), so per-env gather shapes stay static under jit
        arb = AsyncReplayBuffer(16, n_envs=4, storage="host", sequential=False)
        arb.add(make_rows(8, 4))
        # spy on the per-env sample sizes actually requested
        requested: list[tuple[int, ...]] = []
        originals = [b.sample for b in arb.buffer]
        for b, orig in zip(arb.buffer, originals):
            def spied(n, *a, _orig=orig, **kw):
                requested.append(n)
                return _orig(n, *a, **kw)
            b.sample = spied
        for _ in range(20):
            s = arb.sample(8)
            assert s["observations"].shape == (8, 1)
        # divisible batch: every env contributes exactly B // n_envs
        assert set(requested) == {2}
        # indivisible batch: per-env counts are only floor/floor+1 — at most
        # two distinct shapes ever reach the jitted gather
        requested.clear()
        for _ in range(20):
            arb.sample(5)
        assert set(requested) <= {1, 2}
        assert sum(requested) == 20 * 5

    def test_multinomial_split_still_available(self):
        arb = AsyncReplayBuffer(
            16, n_envs=4, storage="host", sequential=False, split="multinomial"
        )
        arb.add(make_rows(8, 4))
        s = arb.sample(32)
        assert s["observations"].shape == (32, 1)
        with pytest.raises(ValueError, match="split"):
            AsyncReplayBuffer(16, n_envs=4, split="bogus")


@pytest.mark.parametrize("storage", STORAGES)
def test_state_dict_roundtrip(storage):
    rb = ReplayBuffer(6, n_envs=2, storage=storage)
    rb.add(make_rows(4, 2))
    state = rb.to_state_dict()
    rb2 = ReplayBuffer(6, n_envs=2, storage=storage)
    rb2.load_state_dict(state)
    assert rb2.full == rb.full
    np.testing.assert_allclose(
        np.asarray(rb2["observations"]), np.asarray(rb["observations"])
    )
    s = rb2.sample(4)
    assert s["observations"].shape == (4, 1)


class TestAsyncUnifiedDeviceStore:
    """Invariants specific to the unified-HBM AsyncReplayBuffer backend:
    one scatter/gather dispatch for all envs, with per-env independence
    expressed as index arithmetic."""

    def test_env_isolation_and_contiguity(self):
        # env e's stream is e*100 + step: every sampled window must be a
        # contiguous run from a single env
        arb = AsyncReplayBuffer(16, n_envs=4, storage="device", sequential=True)
        t = 10
        obs = (
            np.arange(t)[:, None, None]
            + 100.0 * np.arange(4)[None, :, None]
        ).astype(np.float32)
        arb.add({"observations": obs})
        s = np.asarray(
            arb.sample(12, sequence_length=3, n_samples=2)["observations"]
        )  # [2, 3, 12, 1]
        assert s.shape == (2, 3, 12, 1)
        envs = s // 100.0
        assert (envs == envs[:, :1]).all(), "window crossed env columns"
        steps = s % 100.0
        assert np.allclose(np.diff(steps, axis=1), 1.0), "window not contiguous"

    def test_window_excludes_write_head_after_wrap(self):
        # after wrapping, sequences must never span the write head (stale
        # next to fresh data)
        arb = AsyncReplayBuffer(8, n_envs=2, storage="device", sequential=True)
        t = 13  # wraps: pos=5, live steps 5..12
        obs = np.arange(t, dtype=np.float32)[:, None, None] * np.ones(
            (1, 2, 1), np.float32
        )
        arb.add({"observations": obs})
        for _ in range(20):
            s = np.asarray(
                arb.sample(8, sequence_length=3, n_samples=1)["observations"]
            )
            assert np.allclose(np.diff(s, axis=1), 1.0), (
                "sampled window crossed the write head"
            )

    def test_per_env_heads_advance_independently(self):
        arb = AsyncReplayBuffer(8, n_envs=3, storage="device", sequential=True)
        arb.add({"observations": np.zeros((2, 3, 1), np.float32)})
        arb.add({"observations": np.ones((3, 2, 1), np.float32)}, indices=[0, 2])
        assert [b.pos for b in arb.buffer] == [5, 2, 5]
        assert arb.full == (False, False, False)

    def test_next_obs_synthesis_non_sequential(self):
        arb = AsyncReplayBuffer(16, n_envs=2, storage="device", sequential=False)
        t = 6
        obs = np.arange(t, dtype=np.float32)[:, None, None] * np.ones(
            (1, 2, 1), np.float32
        )
        arb.add({"observations": obs})
        s = arb.sample(8, sample_next_obs=True)
        assert np.allclose(
            np.asarray(s["next_observations"]), np.asarray(s["observations"]) + 1.0
        )

    def test_sequential_insufficient_raises(self):
        arb = AsyncReplayBuffer(8, n_envs=2, storage="device", sequential=True)
        arb.add({"observations": np.zeros((2, 2, 1), np.float32)})
        with pytest.raises(ValueError, match="too long sequence_length"):
            arb.sample(4, sequence_length=4, n_samples=1)

    def test_staged_adds_match_unstaged(self):
        # full-width adds stage host-side and flush as one scatter; the
        # store contents must be identical to per-add scatters across
        # interleaved full/subset adds, wrap-around and row surgery
        def run(stage_cap):
            arb = AsyncReplayBuffer(8, n_envs=3, storage="device",
                                    sequential=True, seed=7,
                                    stage_rows=stage_cap)
            step = 0
            for _ in range(5):  # 15 rows through an 8-ring: wraps twice
                for _ in range(3):
                    row = np.full((1, 3, 1), step, np.float32) + np.arange(
                        3, dtype=np.float32
                    ).reshape(1, 3, 1) * 100.0
                    arb.add({"observations": row})
                    step += 1
                arb.add(
                    {"observations": np.full((1, 1, 1), 999.0, np.float32)},
                    indices=[1],
                )
            arb.buffer[2].set_at("observations", 3, np.float32(-5.0))
            st = arb.to_state_dict()
            return [
                (s["pos"], s["full"], np.asarray(s["buf"]["observations"]))
                for s in st["buffers"]
            ]

        staged, unstaged = run(64), run(0)  # 0 == staging off (direct path)
        for (p_a, f_a, b_a), (p_b, f_b, b_b) in zip(staged, unstaged):
            assert p_a == p_b and f_a == f_b
            np.testing.assert_array_equal(b_a, b_b)

    def test_staging_flush_bounds_and_overflow(self):
        # a single flush holding more rows than the ring must keep only the
        # last buffer_size rows AND land them at the slots sequential
        # per-add scatters would have used (the flush trims + advances its
        # start positions; reachable only when multi-row adds push one
        # staged batch past capacity)
        arb = AsyncReplayBuffer(4, n_envs=2, storage="device", sequential=False,
                                stage_rows=4)
        for base in (0.0, 3.0):  # two 3-row adds: one flush of 6 rows > 4
            rows = (base + np.arange(3, dtype=np.float32)).reshape(3, 1, 1)
            arb.add({"observations": np.broadcast_to(rows, (3, 2, 1))})
        assert arb._staged_rows == 0  # cap (=buffer_size) forced the flush
        assert [b.pos for b in arb.buffer] == [2, 2]
        assert arb.full == (True, True)
        ring = np.asarray(arb.buffer[0].buffer["observations"])[:, 0, 0]
        # rows 2..5 survive; ring slot = step % 4 -> [4, 5, 2, 3]
        assert ring.tolist() == [4.0, 5.0, 2.0, 3.0]

    def test_staged_rows_copy_on_add(self):
        # add() has copy-in semantics: mutating the caller's array after
        # add must not change what a later flush writes
        arb = AsyncReplayBuffer(8, n_envs=1, storage="device", sequential=False,
                                stage_rows=64)
        row = np.full((1, 1, 1), 7.0, np.float32)
        arb.add({"observations": row})
        row[:] = -1.0  # mutate before any flush
        ring = np.asarray(arb.buffer[0].buffer["observations"])
        assert ring[0, 0, 0] == 7.0

    def test_cross_storage_checkpoint_roundtrip(self):
        # host-saved rings restore into a device store and vice versa
        src = AsyncReplayBuffer(8, n_envs=2, storage="host", sequential=True)
        src.add({"observations": np.arange(10, dtype=np.float32)[:, None, None]
                 * np.ones((1, 2, 1), np.float32)})
        src.save("/tmp/arb_cross.npz")
        dst = AsyncReplayBuffer(8, n_envs=2, storage="device", sequential=True)
        dst.load("/tmp/arb_cross.npz")
        assert [b.pos for b in dst.buffer] == [b.pos for b in src.buffer]
        s = dst.sample(4, sequence_length=2, n_samples=1)
        assert np.asarray(s["observations"]).shape == (1, 2, 4, 1)

    def test_partial_env_checkpoint_restores_into_device_store(self):
        # only env 0 ever wrote: host-saved mixed (populated/empty) per-env
        # rings must restore into the unified device store
        src = AsyncReplayBuffer(8, n_envs=3, storage="host", sequential=True)
        src.add(
            {"observations": np.arange(4, dtype=np.float32)[:, None, None]},
            indices=[0],
        )
        src.save("/tmp/arb_partial.npz")
        dst = AsyncReplayBuffer(8, n_envs=3, storage="device", sequential=True)
        dst.load("/tmp/arb_partial.npz")
        assert [b.pos for b in dst.buffer] == [4, 0, 0]
        # the per-env view exposes only its own column
        col = dst.buffer[0].buffer["observations"]
        assert col.shape == (8, 1, 1)
        assert np.asarray(col)[:4, 0, 0].tolist() == [0.0, 1.0, 2.0, 3.0]
        assert np.asarray(dst.buffer[1].buffer["observations"]).max() == 0.0


class TestPackedDeviceAdds:
    """Round-3 transfer packing: the device add ships ONE host->device
    transfer per dtype group (plus packed indices), and values already on
    device (the mains reuse the policy step's obs put) scatter directly."""

    def test_device_resident_values_scatter_directly(self):
        arb = AsyncReplayBuffer(8, n_envs=2, storage="device", sequential=True,
                                obs_keys=("rgb",))
        rgb = np.arange(2 * 4, dtype=np.uint8).reshape(1, 2, 4)
        arb.add({
            "rgb": jnp.asarray(rgb),  # device-resident (direct path)
            "rewards": np.ones((1, 2, 1), np.float32),  # host (packed path)
        })
        ring = np.asarray(arb.buffer[0].buffer["rgb"])
        assert ring.dtype == np.uint8
        assert ring[0, 0].tolist() == rgb[0, 0].tolist()
        assert np.asarray(arb.buffer[1].buffer["rewards"])[0, 0, 0] == 1.0

    def test_mixed_dtype_groups_pack_and_unpack(self):
        arb = AsyncReplayBuffer(8, n_envs=3, storage="device", sequential=True,
                                obs_keys=("rgb",))
        rng = np.random.default_rng(0)
        data = {
            "rgb": rng.integers(0, 255, (2, 3, 5), dtype=np.uint8),
            "vec": rng.normal(size=(2, 3, 4)).astype(np.float32),
            "rewards": rng.normal(size=(2, 3, 1)).astype(np.float32),
        }
        arb.add(data)
        for k, v in data.items():
            ring = np.stack(
                [np.asarray(arb.buffer[e].buffer[k])[:2, 0] for e in range(3)],
                axis=1,
            )
            np.testing.assert_array_equal(ring, v)

    def test_width_class_packing_is_bit_exact(self):
        # the packed transfer bit-views int32 as float32 (one transfer per
        # width class, not per dtype) — the roundtrip must preserve every
        # bit pattern, including ones that alias NaNs/infs/subnormals
        from sheeprl_tpu.data.buffers import _pack_host_values, _unpack_values

        evil_i32 = np.array(
            [0, -1, 2**31 - 1, -(2**31), 0x7F800001, 0x7FC00000],
            np.int32,
        )
        evil_f32 = np.array(
            [0.0, -0.0, np.inf, -np.inf, np.nan, np.float32(1e-45)], np.float32
        )
        data = {
            "i": evil_i32.reshape(1, 6),
            "f": evil_f32.reshape(1, 6),
            "u8": np.arange(256, dtype=np.uint8).reshape(1, 256),
            "b": np.array([[True, False, True]]),
            "i64": np.array([[7, -9]], np.int64),
        }
        direct, packed, layout = _pack_host_values(data)
        assert not direct and len(packed) == 2  # one 4-byte + one 1-byte blob
        out = _unpack_values(direct, packed, layout)
        np.testing.assert_array_equal(np.asarray(out["i"]), data["i"])
        np.testing.assert_array_equal(
            np.asarray(out["f"]).view(np.int32), evil_f32.view(np.int32)[None]
        )
        np.testing.assert_array_equal(np.asarray(out["u8"]), data["u8"])
        np.testing.assert_array_equal(np.asarray(out["b"]), data["b"])
        np.testing.assert_array_equal(
            np.asarray(out["i64"]), data["i64"].astype(np.int32)
        )

    def test_subset_indices_through_packed_path(self):
        arb = AsyncReplayBuffer(8, n_envs=3, storage="device", sequential=True)
        arb.add({"observations": np.zeros((1, 3, 1), np.float32)})
        arb.add({"observations": np.full((1, 2, 1), 9.0, np.float32)},
                indices=[0, 2])
        assert [b.pos for b in arb.buffer] == [2, 1, 2]
        assert np.asarray(arb.buffer[2].buffer["observations"])[1, 0, 0] == 9.0
        assert np.asarray(arb.buffer[1].buffer["observations"])[1, 0, 0] == 0.0

    def test_prefers_host_adds(self):
        dev = AsyncReplayBuffer(8, n_envs=1, storage="device")
        host = AsyncReplayBuffer(8, n_envs=1, storage="host")
        staged = AsyncReplayBuffer(8, n_envs=1, storage="device", stage_rows=16)
        assert not dev.prefers_host_adds
        assert host.prefers_host_adds
        assert staged.prefers_host_adds


# ---------------------------------------------------------------------------
# PR 27: the device store against a plain numpy ring, bit for bit. The store
# keeps an item of several axes that fills whole lanes (`u8[64,64,3]`) with
# its axes folded into one, every other item as it arrives; nothing of that
# may show through add / sample / row surgery / any checkpoint form.
# ---------------------------------------------------------------------------

RING_ITEMS = {
    "u8_64x64x3": ((64, 64, 3), np.uint8),   # lane-dense: 12288 = 96 x 128
    "u8_5x7x3": ((5, 7, 3), np.uint8),       # 105 elements fill no lane
    "f32_18": ((18,), np.float32),
    "f32_1": ((1,), np.float32),
}
RING_ENVS = [1, 4, 16]
CAP = 12


def _stamped(rng, item, dtype, t, envs, serial):
    """`[t, len(envs), *item]` of random bits whose first element counts the
    rows written (mod 251), so a misplaced row cannot pass by chance."""
    shape = (t, len(envs), *item)
    if dtype == np.uint8:
        v = rng.integers(0, 256, shape, dtype=np.uint8)
    else:
        v = rng.normal(size=shape).astype(dtype)
    flat = v.reshape(t, len(envs), -1)
    flat[:, :, 0] = ((serial + np.arange(t))[:, None] * 7 + np.asarray(envs)[None, :]) % 251
    return v


class _NumpyRing:
    """The semantics of `AsyncReplayBuffer`, written out: one ring per env,
    each with its own head."""

    def __init__(self, capacity, n_envs):
        self.cap, self.n_envs = capacity, n_envs
        self.buf = None
        self.pos = np.zeros(n_envs, np.int64)
        self.full = np.zeros(n_envs, bool)

    def add(self, data, envs=None):
        envs = range(self.n_envs) if envs is None else envs
        if self.buf is None:
            self.buf = {
                k: np.zeros((self.cap, self.n_envs, *v.shape[2:]), v.dtype) for k, v in data.items()
            }
        for col, e in enumerate(envs):
            for t in range(next(iter(data.values())).shape[0]):
                for k, v in data.items():
                    self.buf[k][self.pos[e], e] = v[t, col]
                self.pos[e] += 1
                if self.pos[e] == self.cap:
                    self.pos[e], self.full[e] = 0, True

    def starts(self, draws, env_idx, exclude):
        """The window start of each output row from its uniform draw `r` in
        `[0, n_valid)`: valid starts are those whose window of `exclude + 1`
        rows does not cross the env's write head."""
        out = []
        for r, e in zip(draws, env_idx):
            first = max(self.pos[e] - exclude, 0)
            out.append(r if r < first else r - first + self.pos[e])
        return np.asarray(out)

    def n_valid(self, exclude):
        first = self.pos - exclude
        second_end = np.where(first >= 0, self.cap, self.cap + first)
        return np.where(self.full, np.maximum(first, 0) + second_end - self.pos, first)


def _ring_of(rb):
    st = rb.to_state_dict()["buffers"]
    return {k: np.concatenate([s["buf"][k] for s in st], axis=1) for k in st[0]["buf"]}


def _assert_same_ring(rb, ref):
    got = _ring_of(rb)
    assert set(got) == set(ref.buf)
    for k in got:
        assert got[k].dtype == ref.buf[k].dtype and got[k].shape == ref.buf[k].shape
        np.testing.assert_array_equal(got[k].view(np.uint8), ref.buf[k].view(np.uint8), err_msg=k)
    assert [b.pos for b in rb.buffer] == ref.pos.tolist()
    assert list(rb.full) == ref.full.tolist()


def _filled(item_name, n_envs, sequential=True, seed=3):
    """A device buffer and its numpy twin after full-width adds past the
    wrap and reset rows for a subset of envs (heads no longer equal)."""
    item, dtype = RING_ITEMS[item_name]
    rng = np.random.default_rng(seed)
    rb = AsyncReplayBuffer(CAP, n_envs=n_envs, storage="device", sequential=sequential,
                           obs_keys=("obs",), seed=seed)
    ref = _NumpyRing(CAP, n_envs)
    serial = 0
    for t in (5, 1, 9):  # 15 rows: wraps a ring of 12
        data = {"obs": _stamped(rng, item, dtype, t, range(n_envs), serial),
                "rewards": _stamped(rng, (1,), np.float32, t, range(n_envs), serial)}
        rb.add(data)
        ref.add(data)
        serial += t
    subset = sorted({0, n_envs - 1, n_envs // 2})[: max(1, n_envs // 2)]
    data = {"obs": _stamped(rng, item, dtype, 2, subset, serial),
            "rewards": _stamped(rng, (1,), np.float32, 2, subset, serial)}
    rb.add(data, indices=subset)
    ref.add(data, subset)
    return rb, ref, rng, serial + 2


def ring_cases(f):
    f = pytest.mark.parametrize("item_name", list(RING_ITEMS))(f)
    return pytest.mark.parametrize("n_envs", RING_ENVS)(f)


class TestDeviceStoreAgainstNumpyRing:
    @ring_cases
    def test_add_and_reset_rows(self, item_name, n_envs):
        rb, ref, _, _ = _filled(item_name, n_envs)
        _assert_same_ring(rb, ref)
        # the per-env view shows the env's own column in the logical shape
        item, _ = RING_ITEMS[item_name]
        col = rb.buffer[n_envs - 1].buffer["obs"]
        assert col.shape == (CAP, 1, *item)
        np.testing.assert_array_equal(np.asarray(col)[:, 0], ref.buf["obs"][:, n_envs - 1])

    @ring_cases
    @pytest.mark.parametrize("data_len", [1, 8])
    def test_add_direct(self, item_name, n_envs, data_len):
        item, dtype = RING_ITEMS[item_name]
        rng = np.random.default_rng(data_len)
        rb = AsyncReplayBuffer(CAP, n_envs=n_envs, storage="device", sequential=True, obs_keys=("obs",))
        ref = _NumpyRing(CAP, n_envs)
        for i in range(3 if data_len == 8 else 14):  # both cross the ring's end
            data = {"obs": _stamped(rng, item, dtype, data_len, range(n_envs), i * data_len),
                    "rewards": _stamped(rng, (1,), np.float32, data_len, range(n_envs), i)}
            idx = rb.reserve(data_len)
            rb.add_direct({k: jnp.asarray(v) for k, v in data.items()}, jnp.asarray(idx), data_len)
            ref.add(data)
        _assert_same_ring(rb, ref)

    @ring_cases
    def test_set_at(self, item_name, n_envs):
        item, dtype = RING_ITEMS[item_name]
        rb, ref, rng, _ = _filled(item_name, n_envs)
        env = n_envs - 1
        value = _stamped(rng, item, dtype, 1, [env], 99)[0, 0]
        rb.buffer[env].set_at("obs", 7, value)
        ref.buf["obs"][7, env] = value
        _assert_same_ring(rb, ref)

    @ring_cases
    @pytest.mark.parametrize("n_samples", [1, 4])
    def test_sequential_sample_across_the_wrap(self, item_name, n_envs, n_samples):
        import jax

        rb, ref, _, _ = _filled(item_name, n_envs)
        batch, seq_len = 16, 5
        _, sub = jax.random.split(rb.get_sample_state()[0])
        env_idx = np.tile(np.repeat(np.arange(n_envs), batch // n_envs), n_samples)
        n_valid = ref.n_valid(seq_len - 1)
        draws = np.asarray(jax.random.randint(sub, (env_idx.size,), 0, np.maximum(n_valid[env_idx], 1)))
        starts = ref.starts(draws, env_idx, seq_len - 1)
        rows = (starts[:, None] + np.arange(seq_len)) % CAP  # [n_samples*batch, L]
        assert (rows[:, 1:] < rows[:, :-1]).any(), "no window wrapped: the case tests nothing"
        out = rb.sample(batch, sequence_length=seq_len, n_samples=n_samples)
        for k in ("obs", "rewards"):
            want = ref.buf[k][rows, env_idx[:, None]]  # [n_samples*batch, L, *item]
            want = want.reshape(n_samples, batch, seq_len, *want.shape[2:]).swapaxes(1, 2)
            got = np.asarray(out[k])
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8), err_msg=k)

    @ring_cases
    def test_sample_next_obs(self, item_name, n_envs):
        import jax

        rb, ref, _, _ = _filled(item_name, n_envs, sequential=False)
        batch = 16
        _, sub = jax.random.split(rb.get_sample_state()[0])
        env_idx = np.repeat(np.arange(n_envs), batch // n_envs)
        n_valid = ref.n_valid(1)
        draws = np.asarray(jax.random.randint(sub, (batch,), 0, np.maximum(n_valid[env_idx], 1)))
        rows = ref.starts(draws, env_idx, 1)
        out = rb.sample(batch, sample_next_obs=True)
        assert set(out) == {"obs", "rewards", "next_obs"}
        np.testing.assert_array_equal(np.asarray(out["obs"]), ref.buf["obs"][rows, env_idx])
        np.testing.assert_array_equal(np.asarray(out["next_obs"]), ref.buf["obs"][(rows + 1) % CAP, env_idx])
        np.testing.assert_array_equal(np.asarray(out["rewards"]), ref.buf["rewards"][rows, env_idx])

    @ring_cases
    @pytest.mark.parametrize("form", ["state_dict", "npz", "bytes"])
    def test_checkpoint_round_trip(self, item_name, n_envs, form, tmp_path):
        rb, ref, rng, serial = _filled(item_name, n_envs)
        if form == "state_dict":
            back = AsyncReplayBuffer(CAP, n_envs=n_envs, storage="device", sequential=True, obs_keys=("obs",))
            back.load_state_dict(rb.to_state_dict())
            back.set_sample_state(rb.get_sample_state())
        elif form == "npz":
            rb.save(str(tmp_path / "ring.npz"))
            back = AsyncReplayBuffer(CAP, n_envs=n_envs, storage="device", sequential=True, obs_keys=("obs",))
            back.load(str(tmp_path / "ring.npz"))
        else:
            back = AsyncReplayBuffer.from_bytes(rb.to_bytes(), storage="device")
        _assert_same_ring(back, ref)
        # and it goes on as the original does: same windows, then the same adds
        a = rb.sample(16, sequence_length=4, n_samples=2)
        b = back.sample(16, sequence_length=4, n_samples=2)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)
        item, dtype = RING_ITEMS[item_name]
        data = {"obs": _stamped(rng, item, dtype, 3, range(n_envs), serial),
                "rewards": _stamped(rng, (1,), np.float32, 3, range(n_envs), serial)}
        back.add(data)
        ref.add(data)
        _assert_same_ring(back, ref)

    @pytest.mark.parametrize("item_name", list(RING_ITEMS))
    def test_the_parents_on_disk_layout_loads_and_is_what_is_saved(self, item_name, tmp_path):
        """A checkpoint as the parent commit wrote it, built here by hand from
        the layout `to_state_dict` documents: per env `b<i>_pos`, `b<i>_full`
        and one `[capacity, 1, *item]` array per key."""
        n_envs = 4
        item, dtype = RING_ITEMS[item_name]
        rng = np.random.default_rng(1)
        ref = _NumpyRing(CAP, n_envs)
        ref.add({"obs": _stamped(rng, item, dtype, 17, range(n_envs), 0),
                 "rewards": _stamped(rng, (1,), np.float32, 17, range(n_envs), 0)})
        flat = {"n_envs": np.int64(n_envs), "buffer_size": np.int64(CAP)}
        for i in range(n_envs):
            flat[f"b{i}_pos"] = np.int64(ref.pos[i])
            flat[f"b{i}_full"] = np.bool_(ref.full[i])
            for k, v in ref.buf.items():
                flat[f"b{i}_buf_{k}"] = v[:, i : i + 1]
        np.savez(tmp_path / "parent.npz", **flat)

        rb = AsyncReplayBuffer(CAP, n_envs=n_envs, storage="device", sequential=True, obs_keys=("obs",))
        rb.load(str(tmp_path / "parent.npz"))
        _assert_same_ring(rb, ref)
        rb.save(str(tmp_path / "again.npz"))
        again = np.load(tmp_path / "again.npz")
        assert set(again.files) == set(flat) | {"sampler_state"}
        for k, v in flat.items():
            assert again[k].shape == np.shape(v) and again[k].dtype == np.asarray(v).dtype, k
            np.testing.assert_array_equal(again[k], v, err_msg=k)
        for s in rb.to_state_dict()["buffers"]:
            assert s["buf"]["obs"].shape == (CAP, 1, *item) and s["n_envs"] == 1

    def test_same_seed_draws_the_parents_windows(self):
        """Values recorded from the parent commit (PR 26) for this seed and
        this history: the sampler's PRNG stream and index rule are unchanged."""
        rb = AsyncReplayBuffer(12, n_envs=4, storage="device", sequential=True, seed=5)
        obs = (np.arange(17)[:, None, None] + 1000 * np.arange(4)[None, :, None]).astype(np.float32)
        rb.add({"observations": obs})
        rb.add({"observations": (500 + np.arange(2)[:, None, None]
                                 + 1000 * np.array([1, 3])[None, :, None]).astype(np.float32)}, indices=[1, 3])
        two = np.asarray(rb.sample(8, sequence_length=5, n_samples=2)["observations"])[..., 0].astype(int)
        one = np.asarray(rb.sample(8, sequence_length=5, n_samples=1)["observations"])[..., 0].astype(int)
        assert two[:, 0].tolist() == [[5, 7, 1014, 1013, 2006, 2007, 3007, 3008],
                                      [8, 12, 1014, 1013, 2012, 2010, 3008, 3013]]
        assert two[1, -1].tolist() == [12, 16, 1501, 1500, 2016, 2014, 3012, 3500]
        assert one[0, 0].tolist() == [8, 9, 1008, 1013, 2007, 2012, 3009, 3011]
        assert one[0, -1].tolist() == [12, 13, 1012, 1500, 2011, 2016, 3013, 3015]
        flat = AsyncReplayBuffer(16, n_envs=2, storage="device", sequential=False, seed=11)
        flat.add({"observations": (np.arange(9)[:, None, None]
                                   + 100 * np.arange(2)[None, :, None]).astype(np.float32)})
        s = flat.sample(6, sample_next_obs=True)
        assert np.asarray(s["observations"])[..., 0].astype(int).tolist() == [0, 0, 6, 106, 103, 100]

    def test_the_format_is_decided_from_the_array_and_recorded(self, monkeypatch):
        from sheeprl_tpu.data.buffers import _storage_item
        from sheeprl_tpu.telemetry import core

        assert _storage_item((64, 64, 3), np.uint8) == ((12288,), "lane_dense")
        assert _storage_item((16, 8), np.float32) == ((128,), "lane_dense")
        for item, dtype in (((5, 7, 3), np.uint8), ((18,), np.float32), ((1,), np.float32), ((256,), np.float32)):
            stored, why = _storage_item(item, dtype)
            assert stored == item and why.startswith(f"as_is: item_bytes={int(np.prod(item)) * np.dtype(dtype).itemsize}")

        class Recorder:
            events = []

            def event(self, name, **data):
                self.events.append((name, data))

        monkeypatch.setattr(core, "_active", [Recorder()])
        rb = AsyncReplayBuffer(8, n_envs=2, storage="device", sequential=True)
        rb.add({"rgb": np.zeros((1, 2, 64, 64, 3), np.uint8), "vec": np.zeros((1, 2, 18), np.float32)})
        rb.add({"rgb": np.zeros((1, 2, 64, 64, 3), np.uint8), "vec": np.zeros((1, 2, 18), np.float32)})
        assert rb._store["rgb"].shape == (8, 2, 12288) and rb._store["vec"].shape == (8, 2, 18)
        (name, data), = Recorder.events  # once, at allocation
        assert name == "replay.store"
        assert data["keys"]["rgb"] == {
            "logical": [8, 2, 64, 64, 3], "storage": [8, 2, 12288], "dtype": "uint8",
            "bytes": 8 * 2 * 12288, "format": "lane_dense",
        }
        assert data["keys"]["vec"]["storage"] == [8, 2, 18] and data["keys"]["vec"]["bytes"] == 8 * 2 * 18 * 4
        assert data["keys"]["vec"]["format"].startswith("as_is: item_bytes=72")


# the ring's two device programs, as compiled (sheeprl_tpu/data/store_check.py)


@pytest.mark.parametrize("n_envs,n_samples,data_len", [(4, 4, 1), (16, 1, 1), (4, 1, 8)])
def test_compiled_add_and_sample_touch_their_rows_only(n_envs, n_samples, data_len):
    """The optimised HLO, on this backend: the add aliases every ring to its
    output, and nothing but that in-place update has a result of the pixel
    ring's size. Compiled from shapes: a 768 MiB ring costs nothing here."""
    from chip_smoke import RING_ITEMS  # the five keys of the benchmark's cells
    from sheeprl_tpu.data import store_check

    rep = store_check.report(
        65536 // n_envs, n_envs, RING_ITEMS, batch=16, seq_len=8, n_samples=n_samples, data_len=data_len
    )
    assert rep["formats"]["rgb"] == "lane_dense"
    assert rep["add"]["aliased_parameters"] == [0, 1, 2, 3, 4]
    assert rep["add"]["ring_sized"] == [] and rep["sample"]["ring_sized"] == []
    assert store_check.faults(rep) == []


def test_store_check_names_a_whole_ring_copy():
    """The parent's compiled add, cut to its ring-sized lines (PR 27, v5e):
    the copy in, the scatter fusion, the copy back."""
    from sheeprl_tpu.data import store_check

    ring = "u8[21504,16,64,64,3]"
    text = f"""HloModule jit__store_add_packed, is_scheduled=true, input_output_alias={{ {{0}}: (0, {{}}, may-alias) }}, entry_computation_layout={{...}}

%fused_computation.2 (param_0.2: {ring}, param_1: s32[16,2], param_2: u8[16,1,1,64,64,3]) -> {ring} {{
  %param_0.2 = {ring}{{3,2,4,1,0:T(8,128)(4,1)}} parameter(0)
  ROOT %scatter.10 = {ring}{{3,2,4,1,0:T(8,128)(4,1)}} scatter(%param_0.2, %param_1, %param_2), update_window_dims={{1,2,3}}, to_apply=%region_2.5
}}

ENTRY %main.7 (store.1: {ring}, idx.1: s32[32]) -> {ring} {{
  %store.1 = {ring}{{0,3,4,2,1:T(8,128)(4,1)}} parameter(0)
  %copy.8 = {ring}{{3,2,4,1,0:T(8,128)(4,1)}} copy(%store.1), sharding={{replicated}}
  %fusion.2 = {ring}{{3,2,4,1,0:T(8,128)(4,1)}} fusion(%copy.8, %copy-done.2, %bitcast.17), kind=kCustom, calls=%fused_computation.2
  ROOT %copy.11 = {ring}{{0,3,4,2,1:T(8,128)(4,1)}} copy(%fusion.2)
}}
"""
    count = {21504 * 16 * 64 * 64 * 3}
    assert store_check._ring_sized(text, count, allow_update=True) == [
        f"copy.8 = {ring}{{3,2,4,1,0:T(8,128)(4,1)}} copy", f"copy.11 = {ring}{{0,3,4,2,1:T(8,128)(4,1)}} copy",
    ]
    # in the sample nothing may be ring-sized, an update neither
    assert len(store_check._ring_sized(text, count, allow_update=False)) == 4
    rep = {
        "store_bytes": 100, "formats": {},
        "add": {"temp_bytes": 200, "alias_bytes": 100, "aliased_parameters": [0], "store_parameters": 1,
                "ring_sized": ["copy.8"]},
        "sample": {"temp_bytes": 0, "alias_bytes": 0, "aliased_parameters": [], "ring_sized": []},
    }
    found = store_check.faults(rep)
    assert len(found) == 2 and "copy.8" in found[0] and "temporaries" in found[1]
    rep["add"].update(aliased_parameters=[], ring_sized=[], temp_bytes=0)
    assert "aliases 0 of 1" in store_check.faults(rep)[0]


# ---- stage_batch: the sampled block cut into the gradient loop's rows --------

@pytest.fixture
def compiles():
    """The package's compile counter (`jax.monitoring`'s backend-compile
    events, a cache load among them): `flush()["compiles"]` since the last."""
    tracker = CompileTracker().attach()
    yield tracker
    tracker.detach()


def sampled_block(n_samples, width):
    """A `[n_samples, T, B, ...]` block with every dtype a ring or a host
    buffer hands over: uint8 pixels, float32, and a float64 and an int64
    leaf that staging casts. `width` makes the shapes the case's own, so the
    first call meets a cold jit cache."""
    rng = np.random.default_rng(n_samples * 100 + width)
    lead = (n_samples, 3, 2)
    return {
        "rgb": rng.integers(0, 256, (*lead, width, 4, 3), dtype=np.uint8),
        "actions": rng.standard_normal((*lead, width)).astype(np.float32),
        "rewards": rng.standard_normal((*lead, 1)),  # float64
        "dones": rng.integers(0, 2, (*lead, 1)),  # int64
    }


@pytest.mark.parametrize("n_samples", [1, 4])
@pytest.mark.parametrize("on_device", [False, True], ids=["numpy", "jax_array"])
@pytest.mark.parametrize("to_host", [False, True], ids=["device_rows", "host_rows"])
def test_stage_batch_rows_equal_the_blocks_rows(n_samples, on_device, to_host):
    block = sampled_block(n_samples, width=5)
    given = {k: jnp.asarray(v) for k, v in block.items()} if on_device else block
    rows = stage_batch(given, to_host=to_host)
    assert len(rows) == n_samples
    for i, row in enumerate(rows):
        assert set(row) == set(block)
        for k, v in row.items():
            assert isinstance(v, np.ndarray if to_host else jax.Array), (k, type(v))
            want = np.uint8 if k == "rgb" else np.float32
            assert v.dtype == want and v.shape == block[k].shape[1:]
            np.testing.assert_array_equal(np.asarray(v), block[k][i].astype(want))
    if to_host and not on_device:
        # a host block's rows are views: nothing is copied a train step
        assert all(np.shares_memory(rows[i][k], block[k]) for i in range(n_samples) for k in ("rgb", "actions"))


@pytest.mark.parametrize("n_samples", [1, 4])
@pytest.mark.parametrize("on_device", [False, True], ids=["numpy", "jax_array"])
def test_stage_batch_is_one_program_compiled_once(n_samples, on_device, compiles):
    """Cut eagerly, a row is a `slice` and a `squeeze` per key: 2 x keys
    programs a train step. One program cuts the whole block, and a second
    block of the same shapes compiles nothing."""
    width = 7 + 2 * on_device  # shapes no other case has compiled
    block = sampled_block(n_samples, width)
    given = {k: jnp.asarray(v) for k, v in block.items()} if on_device else block
    compiles.flush()
    rows = stage_batch(given)
    jax.block_until_ready(rows)
    assert compiles.flush()["compiles"] == 1
    for _ in range(3):
        rows = stage_batch(given)
        _ = [rows[i] for i in range(n_samples)]  # what the gradient loop does
    jax.block_until_ready(rows)
    assert compiles.flush()["compiles"] == 0
    np.testing.assert_array_equal(np.asarray(rows[-1]["rgb"]), block["rgb"][-1])


# ---- a reset add: k of n_envs columns, one program for every k ---------------

def reset_rows(width, base):
    """`width` columns of one step: pixels stored lane-dense, and a float."""
    item = (4, 4, 3)
    rgb = (np.arange(width * int(np.prod(item))).reshape(1, width, *item) + base) % 251
    return {"rgb": rgb.astype(np.uint8), "rewards": np.full((1, width, 1), base, np.float32)}


@pytest.mark.parametrize("k", [1, 2, 16])
def test_a_reset_add_writes_the_old_scatters_bytes_with_one_program(k, compiles):
    """An add of the k environments that ended an episode is widened to all
    16 columns, the added ones dropped by the scatter: the ring, `_upos` and
    `_ufull` equal what the k-column scatter left, and no width compiles a
    program of its own."""
    n_envs, capacity = 16, 5
    new, old = (AsyncReplayBuffer(capacity, n_envs=n_envs) for _ in range(2))
    for t in range(4):  # a ring about to wrap
        for rb in (new, old):
            rb.add(reset_rows(n_envs, 10 * t))
    cols = np.sort(np.random.default_rng(k).choice(n_envs, k, replace=False))
    for rb in (new, old):
        rb._upos[cols[: k // 2]] = capacity - 1  # some columns wrap, some fill
    data = reset_rows(k, 200)
    jax.block_until_ready(new._store)
    compiles.flush()
    new.add(data, cols.tolist())
    jax.block_until_ready(new._store)
    assert compiles.flush()["compiles"] == 0  # the full-width add's program
    # the k-column scatter the add was before
    starts = old._upos[cols]
    old._store = old._packed_scatter(data, starts, cols, 1)
    old._ufull[cols] |= starts + 1 >= capacity
    old._upos[cols] = (starts + 1) % capacity
    for key in old._store:
        np.testing.assert_array_equal(np.asarray(new._store[key]), np.asarray(old._store[key]), err_msg=key)
    np.testing.assert_array_equal(new._upos, old._upos)
    np.testing.assert_array_equal(new._ufull, old._ufull)
    compiles.flush()  # the k-column scatter compiled its own
    for width in (1, 2, 7, 15):  # every other width meets the same program
        new.add(reset_rows(width, width), list(range(width)))
    jax.block_until_ready(new._store)
    assert compiles.flush()["compiles"] == 0
