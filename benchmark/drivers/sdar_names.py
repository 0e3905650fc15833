"""Between the plain reference's flat names (`benchmark/reference/sdar_moe.py`:
`embed`, `layers.<i>.<leaf>`, `final_norm`, `lm_head`) and the program's
module tree (`sheeprl_tpu/algos/ppo_bd/agent.py`: `BDPolicy`, a tuple of
`Layer`s). Nothing but names: the values are handed over untouched."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# reference leaf of a layer -> path inside the program's `Layer`
LAYER = {
    "attn_norm": ("attn_norm", "scale"), "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"), "wo": ("attn", "wo"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
    "router": ("experts", "router"), "w_gate": ("experts", "w_gate"), "w_up": ("experts", "w_up"), "w_down": ("experts", "w_down"),
}
TOP = {"embed": ("embed",), "final_norm": ("final_norm", "scale"), "lm_head": ("lm_head",)}


def _get(node, path):
    for name in path:
        node = getattr(node, name)
    return node


def _set(node, path, value):
    if len(path) == 1:
        return node.replace(**{path[0]: value})
    return node.replace(**{path[0]: _set(getattr(node, path[0]), path[1:], value)})


def from_reference(model, leaf_of):
    """The program's model with every leaf taken from `leaf_of(name)`."""
    for name, path in TOP.items():
        model = _set(model, path, leaf_of(name))
    layers = []
    for i, layer in enumerate(model.layers):
        for name, path in LAYER.items():
            layer = _set(layer, path, leaf_of(f"layers.{i}.{name}"))
        layers.append(layer)
    return model.replace(layers=tuple(layers))


def to_reference(model) -> dict[str, jax.Array]:
    """Flat reference names over the program's tree (of a model, its gradient, or Adam's moments of it)."""
    out = {name: _get(model, path) for name, path in TOP.items()}
    for i, layer in enumerate(model.layers):
        out.update({f"layers.{i}.{name}": _get(layer, path) for name, path in LAYER.items()})
    return out


@jax.jit
def leaf_norms(model) -> dict[str, jax.Array]:
    """The norm of every reference leaf, computed where the tree lives."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in to_reference(model).items()}
