"""Operations and bytes `sdar_30b_a3b_ep8`'s programs need, from the
configuration, the trained sequences' lengths and the router's own counts.

Counted as the mathematics requires them (2 per multiply-add; a backward pass
twice its forward): padding, recomputation and masked score pairs do not
count, so a share worked out from these can only be too low. The routed
products are counted by the assignments the program's counter reports
(padding is routed nowhere, so they are the real tokens' alone).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def position(c: dict) -> float:
    """Forward operations of one position outside attention's scores, the experts and the head: q, k, v, o and the router."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2.0 * h * (q + 2 * kv) + 2.0 * q * h + 2.0 * h * c["num_experts"]


def pair(c: dict) -> float:
    """One attended (query, key) pair over all heads: the score and the mix."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def assignment(c: dict) -> float:
    """One token through one expert: gate, up and down."""
    return 3.0 * 2.0 * c["hidden_size"] * c["moe_intermediate_size"]


def head(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def layout_pairs(p: int, r: int, c: dict) -> float:
    """Pairs the update's mask lets through for a prompt of p and a response of r:
    a clean position attends its own and the earlier blocks; a copy's position the
    clean blocks before its own, and its own block of its own copy."""
    bl, steps = c["block_length"], c["denoise_steps"]
    blocks = (p + r) // bl
    clean = bl * bl * blocks * (blocks + 1) / 2.0
    copies = sum(bl * (p + bl * rb + bl) for rb in range(r // bl))
    return clean + steps * copies


def train_step(c: dict, lengths: list, assignments: float) -> float:
    """Forward and backward of one train step on sequences of (prompt, response) `lengths`."""
    layers, steps = c["num_hidden_layers"], c["denoise_steps"]
    positions = sum(p + (1 + steps) * r for p, r in lengths)
    forward = layers * (positions * position(c) + pair(c) * sum(layout_pairs(p, r, c) for p, r in lengths)) + assignments * assignment(c)
    return 3.0 * (forward + head(c) * sum(r for _, r in lengths))


def expert_products(c: dict, assignments: float) -> tuple[float, float]:
    """(operations, bytes over the bus) of a train step's grouped expert
    products, forward and backward, whichever implementation runs: per
    assignment the row read twice and the three results written, per layer
    the held experts' weights read (forward, and again for the two gradients)
    and their gradient written."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    weights = c["num_hidden_layers"] * c["experts_held"] * 3 * h * f * BF16
    rows = assignments * (2 * h * BF16 + 2 * f * BF16 + f * BF16 + h * BF16)
    return 3.0 * assignments * assignment(c), 3.0 * rows + 4.0 * weights


def policy_step(c: dict, num_envs: int, context_tokens: float) -> tuple[float, float]:
    """(operations, bytes over the bus) of one denoising step of `num_envs`
    environments whose caches hold `context_tokens` clean tokens each, in the
    mean: every position of the block through every layer, the head at the
    positions still masked (all of them, then half); the bf16 weights touched
    once, the cache's valid part read once."""
    h, d, layers, bl = c["hidden_size"], c["head_dim"], c["num_hidden_layers"], c["block_length"]
    tokens = num_envs * bl
    routed = tokens * c["num_experts_per_tok"] * c["experts_held"] / c["num_experts"]
    masked = tokens * (1 + 1.0 / c["denoise_steps"]) / 2.0
    ops = layers * (tokens * position(c) + pair(c) * tokens * (context_tokens + bl) + routed * assignment(c)) + masked * head(c)
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    layer = (h * (q + 2 * kv) + q * h) * BF16 + h * c["num_experts"] * F32 + c["experts_held"] * 3 * h * c["moe_intermediate_size"] * BF16
    cache = num_envs * context_tokens * layers * 2 * kv * BF16
    return ops, layers * layer + h * c["vocab_size"] * BF16 + tokens * h * BF16 + cache
