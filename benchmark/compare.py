"""The comparison that decides `correct`: the numbers, each beside its limit.

Every number is a gap between what the timed path produced and what the
plain reference gives, on the same inputs. A number whose limit is null is
printed and not held (PERF.md says which, with their readings)."""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

DEAD_LEAF = 1e-3  # of the median leaf's gradient norm: such a leaf moves by round-off alone


@jax.jit
def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def leaf_norms(tree: dict) -> dict[str, float]:
    return {k: float(v) for k, v in _norms(tree).items()}


NOT_A_NUMBER = 1e30  # what a gap reads where either side is not finite: it fails any limit, and is valid JSON


def _gap(value: float, reference: float, scale: float) -> float:
    gap = abs(value - reference) / max(scale, 1e-30)
    return gap if math.isfinite(gap) else NOT_A_NUMBER


def rel_gap(value: float, reference: float) -> float:
    return _gap(value, reference, abs(reference))


def leaf_gaps(prog: dict[str, float], ref: dict[str, float], skip=()) -> dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    median = statistics.median(ref.values())
    return {name: _gap(prog[name], r, max(r, median)) for name, r in ref.items() if name not in skip}


def dead_leaves(grad_norms: dict[str, float]) -> set[str]:
    median = statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v < DEAD_LEAF * median}


def training_numbers(prog: dict, ref: dict) -> tuple[dict[str, float], dict[str, str]]:
    """`prog` and `ref` each hold, per model (wm, actor, critic): `loss` (one
    per step), `grad` (leaf norms of the first gradient as Adam got it) and
    `delta` (leaf norms of the parameters' change over the steps).
    -> (numbers, the leaf each worst-leaf number was read at). Per model:
    `loss_` the widest of the steps' loss gaps and `loss1_` the first step's;
    `grad_`, `delta_` by the worst leaf and `grad_med_`, `delta_med_` by the
    median leaf (steadier from seed to seed)."""
    numbers, where = {}, {}
    for model in ref["loss"]:
        gaps = [rel_gap(p, r) for p, r in zip(prog["loss"][model], ref["loss"][model])]
        numbers[f"loss_{model}"], numbers[f"loss1_{model}"] = max(gaps), gaps[0]
        for kind, skip in (("grad", ()), ("delta", dead_leaves(ref["grad"][model]))):
            by_leaf = leaf_gaps(prog[kind][model], ref[kind][model], skip)
            where[f"{kind}_{model}"] = max(by_leaf, key=by_leaf.get)
            numbers[f"{kind}_{model}"] = by_leaf[where[f"{kind}_{model}"]]
            numbers[f"{kind}_med_{model}"] = statistics.median(by_leaf.values())
    return numbers, where


def judge(numbers: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}). A number is held where the
    configuration gives it a limit; one that is not a number fails."""
    table, correct = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        table[name] = {"value": value, "limit": limit}
        if limit is not None and not (value <= limit):
            correct = False
    missing = [k for k, v in limits.items() if v is not None and k not in numbers]
    for name in missing:
        table[name] = {"value": None, "limit": limits[name]}
        correct = False
    return correct, table
