"""The names the program carries onto the device, held without a chip.

A kernel's `pallas_call(name=)` becomes its HLO instruction's name, which is
the name of its events in a profiler trace; a `jax.named_scope` reaches the
`op_name` of every operation traced under it. The benchmark's kernel readers
find a family by the first, and a device trace can be split by region with
the second. The kernels are compiled at DreamerV3-S widths for a described
`v5e:2x2` (no chip attached: on-chip-measurement guide, section 2), the train
step is lowered at tiny widths on the CPU.

The topology is described inside a fixture, never while a module is imported,
and every compile runs in this process: only one process may hold libtpu.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sheeprl_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def real_kernels():
    """The kernels as a TPU traces them, whatever an earlier test of this
    worker forced."""
    was = pk._FORCED, pk._INTERPRET
    pk.set_pallas(True, interpret=False)
    yield
    pk.set_pallas(*was)


def kernel_instructions(compiled) -> set[str]:
    """Names of the Mosaic custom-calls in a compiled program, less their `.N`."""
    found = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"", compiled.as_text())
    return set(found)


# DreamerV3-S: B=16 rows in the scan, T*B=1024 in the imagination and the
# heads, 512-wide dense / recurrent, CNN x32, 255 bins
B, ROWS, H, BINS = 16, 1024, 512, 255


def _gru(rows):
    def cell(x, h, w, scale, offset):
        return pk.layernorm_gru_cell(x, h, w, scale, offset)

    return cell, [((rows, H), jnp.bfloat16), ((rows, H), jnp.bfloat16), ((2 * H, 3 * H), jnp.bfloat16),
                  ((3 * H,), jnp.float32), ((3 * H,), jnp.float32)]


def _cnn_stage(kind, h, cin, cout):
    """One Dreamer stage as `CNN` / `DeCNN` run it: (transposed) conv k4/s2/SAME
    without bias + affine LayerNorm + SiLU, on [T*B, h, h, cin] in bf16."""
    from sheeprl_tpu.nn.blocks import CNN, DeCNN

    init = CNN.init if kind == "enc" else lambda *a, **kw: DeCNN.init(*a, act_last=True, **kw)
    block = init(jax.random.PRNGKey(0), cin, channels=[cout], kernel_sizes=[4], strides=[2],
                 act="silu", layer_norm=True, use_bias=False, norm_eps=1e-3)

    def stage(x, k, scale, offset):
        return block.replace(layers=(block.layers[0].replace(kernel=k),),
                             norms=(block.norms[0].replace(scale=scale, offset=offset),))(x)

    return stage, [((ROWS, h, h, cin), jnp.bfloat16), ((4, 4, cin, cout), jnp.float32), ((cout,), jnp.float32), ((cout,), jnp.float32)]


def _two_hot():
    def log_prob(x, logits, bins):
        return pk.two_hot_log_prob(x, logits, bins)

    return log_prob, [((ROWS, 1), jnp.float32), ((ROWS, BINS), jnp.float32), ((1, BINS), jnp.float32)]


# (build, the argument differentiated, family, the name compiled outside differentiation, under it)
COMPILED = [
    (lambda: _gru(B), 2, "gru", "gru_fwd", "gru_fwd_res"),
    (lambda: _gru(ROWS), 2, "gru", "gru_fwd", "gru_fwd_res"),
    (_two_hot, 1, "two_hot", "two_hot_fwd", "two_hot_fwd"),
]


@pytest.mark.parametrize("build,grad_of,family,forward,under_grad", COMPILED,
                         ids=["gru_scan_rows", "gru_imagination_rows", "two_hot"])
def test_a_kernels_name_is_its_instructions_name_on_the_chip(one_chip, no_compile_cache, real_kernels,
                                                              build, grad_of, family, forward, under_grad):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    assert kernel_instructions(jax.jit(fn).lower(*args).compile()) == {forward}

    def loss(*a):
        with jax.named_scope("wm/region"):  # as in the train step: every kernel sits in a region's scope
            return jnp.square(fn(*a).astype(jnp.float32)).sum()  # squared: the backward needs the forward's result

    # under differentiation the forward that also writes the residuals runs;
    # every family's backward is plain XLA, so it is the only kernel there
    compiled = jax.jit(jax.grad(loss, argnums=grad_of)).lower(*args).compile()
    assert kernel_instructions(compiled) == {under_grad}
    assert f"jvp(wm/region)/{under_grad}/pallas_call" in compiled.as_text()


# the 32- and 64-channel stages that hold four fifths of the CNNs' activation elements
@pytest.mark.parametrize("kind,h,cin,cout", [("enc", 32, 32, 64), ("dec", 16, 64, 32)],
                         ids=["cnn_encoder_stage", "cnn_decoder_stage"])
def test_a_cnn_stage_is_xlas_own_code_with_the_batch_in_the_lanes(one_chip, no_compile_cache, real_kernels,
                                                                   kind, h, cin, cout):
    """Why the CNN family has no kernel (PERF.md §6, PR 30): the chip's compiler
    lays a stage's NHWC arrays out `{0,3,2,1}`, physically [H, W, C, N] (and the
    decoder's phase views likewise: dimension 0 first in every layout), so with
    T*B = 1024 rows no lane is padded whatever the channel count is, and conv,
    LayerNorm and SiLU need no relayout between them. A kernel's operands are
    pinned row-major, channels in the lanes (32 of 128), behind a transposing
    copy at every boundary."""
    fn, shapes = _cnn_stage(kind, h, cin, cout)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    loss = lambda *a: jnp.square(fn(*a).astype(jnp.float32)).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(*args).compile()
    assert kernel_instructions(compiled) == set()
    minor = re.findall(rf"(?:bf16|f32)\[{ROWS}(?:,\d+){{3,}}\]\{{(\d+),", compiled.as_text())
    assert len(minor) > 100 and set(minor) == {"0"}


def test_with_no_scope_around_it_the_transform_wraps_the_kernels_name(one_chip, no_compile_cache, real_kernels):
    """The instruction takes the innermost component of the name stack, and
    `grad` wraps the outermost: a differentiated kernel keeps its plain name
    only inside a scope, which is why every kernel of the train step sits in
    one. (The benchmark's readers match the plain name.)"""
    fn, shapes = _two_hot()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    bare = jax.jit(jax.grad(lambda *a: jnp.square(fn(*a)).sum(), argnums=1)).lower(*args).compile()
    assert kernel_instructions(bare) == {"jvp_two_hot_fwd_"}


def test_the_table_is_the_names_the_kernels_compile_to():
    names = [n for family in pk.KERNEL_NAMES.values() for n in family]
    assert len(names) == len(set(names))
    assert all(re.fullmatch(r"[a-z0-9_]+", n) for n in names)
    # the families a DreamerV3 cell runs: the table's names are the ones asserted of the compiled programs above
    for family in ("gru", "two_hot"):
        compiled = {name for _, _, f, *pair in COMPILED if f == family for name in pair}
        assert set(pk.KERNEL_NAMES[family]) == compiled
    # and every `name=` literal in the kernels' sources is in the table, every table entry a literal
    import inspect

    below_the_table = inspect.getsource(pk).split("\n}\n", 1)[1]
    literals = set(re.findall(r'"([a-z0-9_]+_fwd(?:_res)?)"', below_the_table))
    assert literals == set(names)


def test_the_looped_lms_policy_step_reads_its_caches_through_the_kernel_on_the_chip(one_chip, no_compile_cache, real_kernels):
    """`ouro_2p6b_pp12`'s policy step at the cell's widths but one layer and two
    loops (32 rows, 16 heads of 128, 1,024 slots), compiled for the described
    v5e: one `decode_attn_fwd` a (loop, layer), inside the step's scope, and
    within the chip's memory."""
    from sheeprl_tpu.algos.ppo_lm import ppo_lm
    from sheeprl_tpu.algos.ppo_lm.args import PPOLMArgs

    args = PPOLMArgs(hidden_size=2048, num_hidden_layers=1, num_attention_heads=16, num_key_value_heads=16, head_dim=128,
                     intermediate_size=5632, total_ut_steps=2, precision="bfloat16")
    model = jax.eval_shape(lambda: ppo_lm.build_policy(jax.random.PRNGKey(0), args, 49152))
    player = jax.eval_shape(lambda m: ppo_lm.player_copy(m, jnp.bfloat16), model)
    state = jax.eval_shape(lambda p: p.init_states(32, 1024, jnp.bfloat16), player)
    placed = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = ppo_lm.make_policy_step(args).lower(placed(player), placed(state), key).compile()
    text = compiled.as_text()
    assert kernel_instructions(compiled) == {"decode_attn_fwd"}
    assert len(re.findall(r"= [^\n]*custom_call_target=\"tpu_custom_call\"", text)) == 2
    assert re.search(r"jit\(lm_policy_step\)/lm/policy_step/lm/loop1/lm/attn/decode_attn_fwd", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


# ------------------------------------------------------------------ scopes
def _tiny_args():
    from sheeprl_tpu.algos.dreamer_v3.args import DreamerV3Args

    args = DreamerV3Args(num_envs=2, env_id="dummy")
    args.cnn_keys, args.mlp_keys = ["rgb"], []
    args.dense_units = args.hidden_size = args.recurrent_state_size = 16
    args.cnn_channels_multiplier = 4
    args.stochastic_size = args.discrete_size = 4
    args.horizon = 4
    args.mlp_layers = 1
    args.per_rank_batch_size, args.per_rank_sequence_length = 3, 5
    return args


def _models(args):
    from sheeprl_tpu.algos.dreamer_v3.agent import build_models

    obs_space = {"rgb": type("S", (), {"shape": (64, 64, 3)})()}
    return build_models(jax.random.PRNGKey(0), [3], False, args, obs_space, ["rgb"], [])


def op_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


def test_every_region_of_the_train_step_carries_its_scope():
    from sheeprl_tpu import ops
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    args = _tiny_args()
    T, B_ = args.per_rank_sequence_length, args.per_rank_batch_size
    world_model, actor, critic, target_critic = _models(args)
    world_opt, actor_opt, critic_opt = dv3.make_optimizers(args)
    state = dv3.DV3TrainState(
        world_model=world_model, actor=actor, critic=critic, target_critic=target_critic,
        world_opt=world_opt.init(world_model), actor_opt=actor_opt.init(actor), critic_opt=critic_opt.init(critic),
        moments=ops.Moments.init(args.moments_decay, args.moment_max),
    )
    data = {
        "rgb": jnp.zeros((T, B_, 64, 64, 3), jnp.uint8),
        "actions": jnp.zeros((T, B_, 3), jnp.float32),
        "rewards": jnp.zeros((T, B_, 1), jnp.float32),
        "dones": jnp.zeros((T, B_, 1), jnp.float32),
        "is_first": jnp.zeros((T, B_, 1), jnp.float32),
    }
    train_step = dv3.make_train_step(args, world_opt, actor_opt, critic_opt, ["rgb"], [], [3], False)
    text = op_names(train_step.lower(state, data, jax.random.PRNGKey(7), jnp.float32(1.0)))
    assert len(dv3.TRAIN_STEP_SCOPES) == len(set(dv3.TRAIN_STEP_SCOPES)) == 12
    for scope in dv3.TRAIN_STEP_SCOPES:
        # bare in the optimizers, `jvp(...)` / `transpose(jvp(...))` where the region is differentiated
        assert re.search(rf'"jit\(train_step\)/(?:\w+\()*{scope}\)*/', text), scope
    # a region's backward keeps the region's name
    assert "transpose(jvp(wm/encoder))" in text and "transpose(jvp(critic/loss))" in text


def test_the_policy_step_and_the_replay_ring_carry_their_scopes():
    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_blob_step
    from sheeprl_tpu.algos.dreamer_v3.utils import make_device_preprocess
    from sheeprl_tpu.data import AsyncReplayBuffer, StepBlobCodec

    args, n_envs = _tiny_args(), 2
    wm, actor, _, _ = _models(args)
    player = PlayerDV3(
        encoder=wm.encoder, rssm=wm.rssm, actor=actor, actions_dim=(3,),
        stochastic_size=args.stochastic_size, discrete_size=args.discrete_size,
        recurrent_state_size=args.recurrent_state_size, is_continuous=False, compute_dtype=args.precision,
    )
    codec = StepBlobCodec(
        {"rgb": (64, 64, 3)}, {"rewards": (1,), "dones": (1,), "is_first": (1,)}, idx_len=2 * n_envs, n_envs=n_envs,
    )
    blob_step = make_blob_step(codec, ("rgb",), make_device_preprocess(("rgb",)), [3], False)
    blob = jnp.zeros((codec.blob_len,), jnp.int32)
    lowered = blob_step.lower(player, player.init_states(n_envs), blob, jax.random.PRNGKey(0), jnp.float32(0.0))
    assert '"jit(_blob_step)/player/step/' in op_names(lowered)

    store = {"rgb": jnp.zeros((8, n_envs, 64, 64, 3), jnp.uint8)}
    row = {"rgb": jnp.zeros((1, n_envs, 64, 64, 3), jnp.uint8), "__idx__": jnp.zeros((2 * n_envs,), jnp.int32)}
    add = AsyncReplayBuffer._store_add_packed.lower(store, row, {}, (), 1)
    assert '"jit(_store_add_packed)/replay/add/' in op_names(add)
    packed_idx = jnp.zeros((4 + 3 * n_envs,), jnp.int32)
    sample = AsyncReplayBuffer._store_sample.lower(
        store, jax.random.PRNGKey(0), packed_idx,
        n_samples=1, seq_len=2, sequential=True, sample_next_obs=False, obs_keys=("rgb",),
    )
    assert '"jit(_store_sample)/replay/sample/' in op_names(sample)


@pytest.mark.parametrize("cell", [0, 1], ids=["ratio1024", "ratio64"])
def test_the_replay_ring_is_touched_in_place_on_the_tpu(one_chip, no_compile_cache, cell):
    """The ring's add and sample at the benchmark cells' shapes, compiled for
    the described v5e: no ring-sized copy, temporaries under 1 % of the
    3.9 GiB ring (the parent's programs held 7.9 GiB of them: PERF.md, PR 27).
    `chip_smoke.py` makes the same check, on the same shapes, with the chip's
    own compile."""
    from chip_smoke import RING_CELLS, RING_ITEMS
    from sheeprl_tpu.data import store_check

    rows, n_envs, n_samples = RING_CELLS[cell]
    rep = store_check.report(rows, n_envs, RING_ITEMS, batch=B, seq_len=64, n_samples=n_samples, sharding=one_chip)
    assert rep["formats"]["rgb"] == "lane_dense"
    assert store_check.faults(rep) == [], rep
    assert rep["add"]["temp_bytes"] < 2**20 and rep["sample"]["temp_bytes"] < 2**21
