"""Config of `ppo_bd`: clipped policy gradient over a block-diffusion language
model's own denoising trajectories (howto/learn_token_tasks.md). The model's
defaults are a small preset a CPU can run; a configuration at published widths
names every key (benchmark/configs/sdar_30b_a3b_ep8.json)."""

from __future__ import annotations

import dataclasses
from typing import Optional

from ...utils.parser import Arg
from ..args import StandardArgs


@dataclasses.dataclass
class PPOBDArgs(StandardArgs):
    env_id: str = Arg(default="TokenTask-v0", help="a token-task environment id (envs/token_task.py)")
    num_envs: int = Arg(default=8, help="sequences generated side by side; a multiple of --group_size")
    num_devices: int = Arg(default=1, help="1: the expert layer runs on one device (parallel/mesh.py has no expert axis yet)")
    total_steps: int = Arg(default=2**14, help="total env steps; an env step is one denoising step of one sequence")
    # ---- the model (keys as in a `sdar_moe` config.json)
    hidden_size: int = Arg(default=128, help="model width")
    num_hidden_layers: int = Arg(default=2, help="transformer layers")
    num_attention_heads: int = Arg(default=4, help="query heads")
    num_key_value_heads: int = Arg(default=2, help="key/value heads (grouped-query attention)")
    head_dim: int = Arg(default=32, help="width of one head")
    rope_theta: float = Arg(default=1e6, help="RoPE base")
    rms_norm_eps: float = Arg(default=1e-6, help="RMSNorm epsilon")
    moe_intermediate_size: int = Arg(default=64, help="width of one routed expert")
    num_experts: int = Arg(default=8, help="routed experts the router chooses among")
    num_experts_per_tok: int = Arg(default=2, help="experts picked per token")
    norm_topk_prob: bool = Arg(default=True, help="normalise the picked experts' weights to sum to 1")
    first_expert: int = Arg(default=0, help="first expert this process holds (its share of an expert-parallel deployment)")
    experts_held: Optional[int] = Arg(default=None, help="experts this process holds; default: all from --first_expert on")
    vocab_size: Optional[int] = Arg(default=None, help="ids held, the mask token the last of them; default: the environment's")
    # ---- generation
    block_length: int = Arg(default=4, help="positions denoised together; bidirectional attention inside a block")
    denoise_steps: int = Arg(default=2, help="denoising steps a block: block_length / denoise_steps ids are committed a step")
    temperature: float = Arg(default=1.0, help="sampling temperature")
    # ---- the update
    group_size: int = Arg(default=4, help="environments that share a prompt; the advantage is the reward normalised over the group")
    update_sequences: int = Arg(default=16, help="finished sequences that start an update")
    per_rank_batch_size: int = Arg(default=8, help="whole sequences a train step")
    lr: float = Arg(default=1e-4, help="learning rate")
    clip_coef: float = Arg(default=0.2, help="surrogate clipping coefficient")
    max_grad_norm: float = Arg(default=1.0, help="global grad-norm clip; 0 disables")
    eps: float = Arg(default=1e-8, help="adam epsilon")
    grace_checkpoint: bool = Arg(default=True, help="save a checkpoint when preempted (16 bytes a parameter: a benchmark run turns it off)")
