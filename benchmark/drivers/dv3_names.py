"""Which leaf of the program's DreamerV3 modules is which parameter of the
plain reference. The one place that knows both namings: the reference knows
only its own, and the weights cross over by this table."""

from __future__ import annotations

import re

import jax

_BLOCK = r"\.(?:layers|norms)\[(\d)\]"
_RULES = [
    (r"^wm\.encoder\.cnn_encoder\.model" + _BLOCK, r"wm.enc.conv\1"),
    (r"^wm\.rssm\.recurrent_model\.mlp" + _BLOCK, "wm.rec.dense"),
    (r"^wm\.rssm\.recurrent_model\.rnn\.(?:proj|norm)", "wm.rec.gru"),
    (r"^wm\.rssm\.representation_model" + _BLOCK, "wm.repr.dense"),
    (r"^wm\.rssm\.representation_model\.head", "wm.repr.head"),
    (r"^wm\.rssm\.transition_model" + _BLOCK, "wm.trans.dense"),
    (r"^wm\.rssm\.transition_model\.head", "wm.trans.head"),
    (r"^wm\.observation_model\.cnn_decoder\.proj", "wm.dec.proj"),
    (r"^wm\.observation_model\.cnn_decoder\.model" + _BLOCK, r"wm.dec.deconv\1"),
    (r"^wm\.reward_model" + _BLOCK, r"wm.reward.l\1"),
    (r"^wm\.reward_model\.head", "wm.reward.head"),
    (r"^wm\.continue_model" + _BLOCK, r"wm.cont.l\1"),
    (r"^wm\.continue_model\.head", "wm.cont.head"),
    (r"^actor\.model" + _BLOCK, r"actor.l\1"),
    (r"^actor\.heads\[0\]", "actor.head"),
    (r"^critic" + _BLOCK, r"critic.l\1"),
    (r"^critic\.head", "critic.head"),
]
_SUFFIX = {"kernel": "w", "weight": "w", "bias": "b", "scale": "ln_s", "offset": "ln_b"}


def reference_name(group: str, path) -> str:
    """`group` is wm, actor or critic; `path` a key path of that module."""
    full = group + jax.tree_util.keystr(path)
    stem, _, leaf = full.rpartition(".")
    for pattern, repl in _RULES:
        new, n = re.subn(pattern + "$", repl, stem)
        if n:
            return f"{new}.{_SUFFIX[leaf]}"
    raise KeyError(f"no reference parameter for the program's leaf {full}")


def to_reference(group: str, module) -> dict:
    """A module of the program (or a tree shaped like it, such as Adam's
    first moment) as the reference's flat dict."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(module)
    return {reference_name(group, path): leaf for path, leaf in leaves}


def from_reference(group: str, module, params: dict):
    """The program's module with every leaf replaced by the reference's
    parameter of that name; a missing name or another shape is an error."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(module)
    new = []
    for path, leaf in leaves:
        value = params[reference_name(group, path)]
        if value.shape != leaf.shape:
            raise ValueError(f"{reference_name(group, path)}: {value.shape} for the program's {leaf.shape}")
        new.append(value.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, new)
