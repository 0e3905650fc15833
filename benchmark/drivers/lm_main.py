"""Drives the autoregressive policy-gradient main (`ppo_lm`), in this process,
for one cell.

As `token_main.py` does for the block-diffusion main, whose window, recorders'
pattern and helpers it takes: the entry is the program's own `main(argv)`,
the traffic the benchmark's token environments taking one id a step
(`envs/token_episodes.py`, `block_length` 1), and environment 0's `step()` is
the clock and the stop switch. Nothing of the program is edited; three of its
names are wrapped while the main runs: `build_models` (the weights are the
benchmark's own, made from the seed by the plain reference's generator),
`make_policy_step` (the first steps' logits and exit loops are kept) and
`make_train_step` (the first steps' batches, losses and the norms of what
they did to the state are kept).

The window opens and closes on the first iteration boundary after an update
ends, so it holds whole collect-and-update cycles. A traced run keeps going
for the traffic's `trace_iterations` boundaries, which the schedule makes
long enough to hold an update (`tests/test_benchmark/test_lm_cell.py`).
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import compare
from ..envs.token_episodes import Traffic, response_of
from ..reduce import spans as reduce_spans
from . import ouro_names
from .token_main import _adam_mu, _compile_note, _flag, _rel_rms, delta_norms, make_window
from .train_main import CONTROL_OPERANDS, RC_PREEMPTED, Compiles

ENV_ID = "SheepBenchTokensAR-v0"
FOLLOWED_STEPS = 3  # train steps followed against the reference
FOLLOWED_POLICY_STEPS = 8  # tokens of every environment: the first reads what the prefill wrote, the rest what the steps before it wrote too
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta",
    "rms_norm_eps", "total_ut_steps", "early_exit_threshold", "vocab_size",
)


class TrainRecorder:
    """The program's train step, with the first steps' batches and losses and
    the norms of what they did to the state kept beside it (norms, not copies:
    a copy of the model does not fit beside it); after those steps one
    comparison per call, and the newest batch kept for the row check."""

    def __init__(self, step, seed_leaf, fault=None):
        self.step = step if fault is None else (lambda *a: fault(step, *a))
        self.seed_leaf = seed_leaf
        self.calls = 0
        self.batches, self.losses = [], []
        self.grad = self.delta = self.last_batch = None

    def __call__(self, state, player, batch):
        self.calls += 1
        if self.calls > FOLLOWED_STEPS:
            self.last_batch = batch
            return self.step(state, player, batch)
        self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        new_state, new_player, metrics = self.step(state, player, batch)
        self.losses.append(metrics["Loss/policy_loss"])
        if self.calls == 1:  # Adam's first moment after one step is (1 - b1) x the gradient it was given
            self.grad = ouro_names.leaf_norms(_adam_mu(new_state.opt_state))
        if self.calls == FOLLOWED_STEPS:
            self.delta = delta_norms(ouro_names.to_reference(new_state.model), self.seed_leaf)
        return new_state, new_player, metrics


class PolicyRecorder:
    """The program's policy step; the first steps' logits and packed actions (the exit loops) are kept."""

    def __init__(self, step):
        self.step, self.calls, self.logits, self.packed = step, 0, [], []

    def __call__(self, player, state, key):
        out = self.step(player, state, key)
        if self.calls < FOLLOWED_POLICY_STEPS:
            self.logits.append(np.asarray(out[2]))  # on the host: warm-up's steps may wait, the window's memory is the program's
            self.packed.append(np.asarray(out[1]))
        self.calls += 1
        return out


def model_config(config: dict, traffic: dict) -> dict:
    """What the reference is built from: the configuration's model keys, and
    the update's and the generation's settings, which are also the program's argv."""
    c = {k: config[k] for k in MODEL_KEYS}
    c.update(traffic["args"])
    c.update(config["args"])
    c.update(adam_eps=config["args"]["eps"])
    return c


def make_traffic(config: dict, traffic: dict, seed: int, **hooks) -> Traffic:
    """The generator of this driver's cells, from a traffic file's parameters: one id a step."""
    params = {**traffic["env"], "vocab_size": config["vocab_size"], "group_size": traffic["args"]["group_size"]}
    return Traffic(params, traffic["num_envs"], 1, seed, **hooks)


def run(cell: dict, seed: int, seconds: float, trace: bool, root: str, control: bool = False, fault=None) -> dict:
    """One run of one cell. `fault`, for the tests, breaks the timed step."""
    config, traffic = cell["config"], cell["traffic"]
    module = importlib.import_module(config["entry"]["module"])
    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    import gymnasium as gym

    c = model_config(config, traffic)
    spec = reference.param_spec(c)
    num_envs = traffic["num_envs"]

    out_dir = os.path.join(root, "benchmark_out", f"{cell['name']}-{seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_cfg = {"dir": os.path.join(out_dir, "trace"), "iterations": traffic["trace_iterations"]} if trace else None
    recorders: dict = {}
    window = make_window(traffic, seconds, lambda: recorders["train"].calls if "train" in recorders else 0, trace_cfg)
    load = make_traffic(config, traffic, seed, on_step=window.on_step, annotate=jax.profiler.TraceAnnotation if trace else None)
    window.counters = lambda: (load.host_seconds, load.resets, load.context_tokens, load.tokens_committed)
    compiles = Compiles()

    real_build, real_policy, real_train = module.build_models, module.make_policy_step, module.make_train_step

    def seed_leaf(name):
        return reference.make_leaf(seed, name, spec[name])

    def build_models(*a, **kw):
        return ouro_names.from_reference(real_build(*a, **kw), seed_leaf)

    def make_policy_step(*a, **kw):
        recorders["policy"] = PolicyRecorder(real_policy(*a, **kw))
        return recorders["policy"]

    def make_train_step(*a, **kw):
        recorders["train"] = TrainRecorder(real_train(*a, **kw), seed_leaf, fault)
        return recorders["train"]

    argv = [
        *config["flags"], *(x for k in MODEL_KEYS for x in _flag(k, config[k])),
        *(x for group in (config["args"], traffic["args"]) for k, v in group.items() for x in _flag(k, v)),
        "--env_id", ENV_ID, "--seed", str(seed % (2**31)), "--num_envs", str(num_envs), "--root_dir", out_dir, "--run_name", "run",
    ]
    if ENV_ID in gym.registry:
        del gym.registry[ENV_ID]
    gym.register(ENV_ID, entry_point=load.make_env)
    jax.monitoring.register_event_duration_secs_listener(compiles)
    module.build_models, module.make_policy_step, module.make_train_step = build_models, make_policy_step, make_train_step
    rc = None
    try:
        getattr(module, config["entry"]["function"])(argv)
    except SystemExit as exit_:
        rc = exit_.code
    finally:
        module.build_models, module.make_policy_step, module.make_train_step = real_build, real_policy, real_train
        jax.monitoring.unregister_event_duration_listener(compiles)
        del gym.registry[ENV_ID]
    if rc != RC_PREEMPTED or window.i_close is None:
        raise RuntimeError(f"the main ended (rc={rc}) before the window closed: raise --total_steps")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
    t_open, t_close = window.stamps[window.i_open], window.stamps[window.i_close]
    iterations = window.i_close - window.i_open
    events_path = os.path.join(out_dir, "run", "telemetry.jsonl")
    events = []
    if os.path.exists(events_path):
        with open(events_path) as f:
            events = [json.loads(line) for line in f if line.strip()]
    result = {
        "t_open": t_open,
        "window_s": t_close - t_open,
        "iterations": iterations,
        "iteration_seconds": window.iteration_seconds,
        "env_steps": iterations * num_envs,
        "resets": window.at_close[1] - window.at_open[1],
        "env_host_seconds": window.at_close[0] - window.at_open[0],
        "tokens_committed": window.at_close[3] - window.at_open[3],
        "update_iteration_seconds": window.update_iteration_seconds,
        "compile_seconds_before_window": compiles.seconds_before(t_open),
        "compiles_in_window": compiles.backend_between(t_open, t_close),
        "events_in_window": [(n, round(s, 4), round(end - t_open, 3)) for n, s, end in compiles.events if t_open < end <= t_close],
        "memory_peak_bytes": peak,
        "events": events,
        "model_config": c,
        "num_envs": num_envs,
        "out_dir": out_dir,
        "trace_dir": trace_cfg["dir"] if trace else None,
        "notes": [f"set-up's last act, freed memory handed back to the system: {window.hand_back_seconds:.3f} s",
                  f"window: {window.updates_seen} updates had ended at its close, {recorders['train'].calls} train steps in the run"],
    }
    result["notes"] += [_compile_note(compiles, t_open), *_counter_notes(result)]
    if trace and window.at_trace_end:
        # the traced stretch: the mean context a policy step reads, from the benchmark's own environments
        result["traced_context_tokens"] = (window.at_trace_end[2] - window.at_close[2]) / (traffic["trace_iterations"] * num_envs)

    # ---- correct: what the timed path produced against the plain reference,
    # once the window has closed, the peak has been read and the program's state freed
    gc.collect()
    t0 = time.perf_counter()
    result["numbers"], result["detail"] = _compare(recorders["train"], recorders["policy"], load, reference, seed, seed_leaf, c, control, config)
    result["reference_seconds"] = time.perf_counter() - t0
    where = result["detail"]["worst_leaf"]
    result["notes"].append(f"logits: {result['detail']['logits_rows']} rows; exit loops of the followed tokens {result['detail']['exit_loops']}")
    result["notes"].append(f"worst leaves: grad_policy {result['numbers']['grad_policy']:.4g} at {where['grad_policy']}, "
                           f"delta_policy {result['numbers']['delta_policy']:.4g} at {where['delta_policy']}")
    result["notes"] += [f"{name[: -len('_verdict')]} in the program's place: {verdict}" for name, verdict in result["detail"].items() if name.endswith("_verdict")]
    return result


def _counter_notes(run: dict) -> list[str]:
    """What the program's own counters say of the window; no metric reads them (PERF.md section 7)."""
    w = reduce_spans.Window(run)
    updates = [s for s in w.named("update") if "positions" in s]
    packs = [s for s in w.named("rollout/pack") if "exit_loop" in s]
    if not updates or not packs:
        return []
    positions = sum(s["positions"] for s in updates)
    exits = np.sum([s["exit_loop"] for s in packs], axis=0).tolist()
    return [f"program counters over the window: {len(updates)} updates, padded positions {100.0 * sum(s['pad_positions'] for s in updates) / positions:.2f} % "
            f"of {positions}; tokens by exit loop {exits}; layer applications a token {packs[0]['loop_applications'] / packs[0]['tokens_committed']:.0f}; "
            f"cache slots reset {sum(s.get('cache_slots_reset', 0) for s in w.named('rollout/prefill'))}; update spans {sum(s['dur_ms'] for s in updates) / 1e3:.3f} s"]


# ------------------------------------------------------------------ correct
def _finished(load: Traffic) -> dict:
    """(prompt bytes, response bytes) -> (episode, advantage) of every finished episode, from the benchmark's own record."""
    group, out = load.params["group_size"], {}
    for env in load.envs:
        for serial, ep in enumerate(env.log):
            if ep.reward is not None:
                members = [load.envs[env.group * group + j].log[serial] for j in range(group)]
                rewards = np.array([m.reward if m.reward is not None else np.nan for m in members])
                adv = (ep.reward - rewards.mean()) / (rewards.std() + 1e-6)
                out[(ep.prompt.tobytes(), response_of(ep, 1)[0].astype(np.int32).tobytes())] = (ep, adv)
    return out


def rows_mismatch(load: Traffic, batches: list[dict]) -> int:
    """Rows of the trained batches that are not, field for field, what the
    environments recorded: prompt, sampled tokens, their loss positions, and
    the advantage its group's rewards give."""
    finished, bad = _finished(load), 0
    for batch in batches:
        host = {k: np.asarray(v) for k, v in batch.items()}
        for b in range(host["ids"].shape[0]):
            n = int(host["loss_mask"][b].sum())
            if n == 0:
                continue  # a padded row: nothing is trained on it
            p = int(host["loss_pos"][b, 0]) + 1
            key = (host["ids"][b, :p].astype(np.int32).tobytes(), host["ids"][b, p : p + n].astype(np.int32).tobytes())
            if key not in finished:
                bad += 1
                continue
            ep, adv = finished[key]
            ids = response_of(ep, 1)[0]
            want_ids = np.zeros_like(host["ids"][b])
            want_ids[:p], want_ids[p : p + n] = ep.prompt, ids
            R = host["loss_pos"].shape[1]
            want = {"ids": want_ids, "loss_pos": np.where(np.arange(R) < n, p - 1 + np.arange(R), 0), "loss_mask": (np.arange(R) < n).astype(np.float32),
                    "targets": np.where(np.arange(R) < n, np.pad(ids, (0, R - n)), 0)}
            same = all(np.array_equal(host[k][b], want[k]) for k in want) and abs(float(host["advantages"][b]) - adv) <= 1e-4 * max(1.0, abs(adv))
            bad += 0 if same else 1
    return bad


def _policy_cases(load: Traffic, steps: int) -> list[tuple]:
    """(ids [prompt ; the first `steps` tokens] padded, prompt length) of every environment's first episode."""
    cases = []
    for env in load.envs:
        ep = env.log[0]
        ids = np.zeros(load.p_max + steps, np.int32)
        p = len(ep.prompt)
        ids[:p], ids[p : p + steps] = ep.prompt, [int(a[0]) for a in ep.actions[:steps]]
        cases.append((ids, p))
    return cases


def _compare(rec: TrainRecorder, pol: PolicyRecorder, load: Traffic, reference, seed: int, seed_leaf, c: dict, control: bool, config: dict):
    K = FOLLOWED_POLICY_STEPS
    if rec.calls < FOLLOWED_STEPS or rec.delta is None or len(pol.logits) < K:
        raise RuntimeError(f"only {rec.calls} train steps and {len(pol.logits)} policy steps ran before the window closed")
    mismatch = rows_mismatch(load, [*rec.batches, *([rec.last_batch] if rec.last_batch is not None else [])])
    rec.last_batch = None
    cases = _policy_cases(load, K)
    prog_logits = np.stack(pol.logits, axis=1)  # [envs, K, vocab]
    prog_exits = np.stack(pol.packed, axis=1)[..., 2]
    batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in rec.batches]
    live = rec.batches[0]["loss_mask"] > 0
    # --control 1: what stands in the program's place, each read against the same reference and judged by the cell's own limits
    stand_ins = {"control": {"quant": CONTROL_OPERANDS}, **{f: {"fault": f} for f in reference.FAULTS}} if control else {}

    with jax.default_matmul_precision("highest"):
        def logits_of(params, **kw):
            """-> the reference's logits [envs, K, vocab] and exit loops [envs, K]."""
            fn = jax.jit(lambda p, ids, n: reference.followed_logits(p, ids, n, K, c, **kw))
            out = [tuple(map(np.asarray, fn(params, jnp.asarray(ids), jnp.int32(n)))) for ids, n in cases]
            return np.stack([o[0] for o in out]), np.stack([o[1] for o in out])

        def logp_of(params, **kw):  # the first batch's sampled tokens, at the seed's weights
            return np.asarray(jax.jit(lambda p, b: reference.batch_loss(p, b, c, **kw)[1])(params, batches[0]))[live]

        def policy_numbers(logits, exits, logp, ref_logits, ref_exits, ref_logp) -> dict:
            return {"logits_policy": _rel_rms(logits, ref_logits), "exit_mismatch": float(np.sum(exits != ref_exits)), "logprob_old": _rel_rms(logp, ref_logp)}

        params = reference.make_params(seed, c)
        ref_logits, ref_exits = logits_of(params)
        ref_logp = logp_of(params)
        sides = {name: policy_numbers(*logits_of(params, **kw), logp_of(params, **kw), ref_logits, ref_exits, ref_logp) for name, kw in stand_ins.items()}

    def followed(params, **kw) -> dict:
        """The reference's side of `compare.training_numbers`; it uses `params` up."""
        seen = {}

        def on_step(i, state, grads):
            if i == 0:  # as Adam got it: after the global-norm clip
                norms = compare.leaf_norms(grads)
                total = float(np.sqrt(sum(v * v for v in norms.values())))
                scale = min(1.0, c["max_grad_norm"] / (total + 1e-6)) if c["max_grad_norm"] > 0 else 1.0
                seen.update({k: scale * v for k, v in norms.items()})

        state, outs = reference.run_steps(params, batches, c, on_step=on_step, **kw)
        return {"loss": {"policy": [float(o["loss"]) for o in outs]}, "grad": {"policy": seen},
                "delta": {"policy": delta_norms(state["params"], seed_leaf)}}

    ref = followed(params)
    del params
    prog = {"loss": {"policy": [float(x) for x in rec.losses]},
            "grad": {"policy": {k: float(v) / (1.0 - reference.ADAM_B1) for k, v in rec.grad.items()}},
            "delta": {"policy": rec.delta}}
    numbers, where = compare.training_numbers(prog, ref)
    numbers.update(policy_numbers(prog_logits, prog_exits, rec.batches[0]["logprob_old"][live], ref_logits, ref_exits, ref_logp))
    numbers["rows_mismatch"] = float(mismatch)
    detail = {"worst_leaf": where, "logits_rows": int(np.prod(prog_exits.shape)), "exit_loops": np.bincount(prog_exits.reshape(-1), minlength=c["total_ut_steps"]).tolist(),
              "reference_loss": ref["loss"], "program_loss": prog["loss"]}
    limits = {k: v for k, v in config["limits"].items() if k != "rows_mismatch"}
    if control:
        side_numbers, detail["control_worst_leaf"] = compare.training_numbers(followed(reference.make_params(seed, c), **stand_ins["control"]), ref)
        sides["control"].update(side_numbers)
    for name, side in sides.items():
        correct, table = compare.judge(side, {k: v for k, v in limits.items() if k in side})
        detail[name] = side
        detail[name + "_verdict"] = {"correct": correct, "failed_by": [k for k, row in table.items() if row["limit"] is not None and not row["value"] <= row["limit"]]}
    return numbers, detail
