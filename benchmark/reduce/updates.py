"""The `update` spans of the block-diffusion main, read once per run: each
holds its train steps' counters (`ppo_bd.py`: `moe_assignments`,
`moe_load_max`, `moe_load_mean`, `lengths`, one entry a train step, and
`pad_positions`, `positions`, `sequences_trained` over the update)."""

from __future__ import annotations

from . import spans

UPDATE = "update"


def in_window(run: dict) -> list[dict]:
    w = spans.window(run)
    return [s for s in w.named(UPDATE) if "moe_assignments" in s] if w else []


def from_window_on(run: dict) -> list[dict]:
    """The window's updates and the traced stretch's after it."""
    t0 = run["t_open"]
    return [e for e in run.get("events", ()) if e.get("event") == "span" and e.get("name") == UPDATE and e.get("p0", t0 - 1) >= t0 and "moe_assignments" in e]


def train_steps(updates: list[dict]) -> list[dict]:
    """One dict a train step: its assignments to held experts, the fullest expert's and the mean count, its sequences' lengths."""
    out = []
    for u in updates:
        for i, n in enumerate(u["moe_assignments"]):
            out.append({"assignments": n, "load_max": u["moe_load_max"][i], "load_mean": u["moe_load_mean"][i], "lengths": u["lengths"][i]})
    return out
