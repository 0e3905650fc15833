"""Drives the block-diffusion policy-gradient main (`ppo_bd`), in this process,
for one cell.

As `train_main.py` does for the Dreamer cells: the entry is the program's own
`main(argv)`, the traffic an environment the benchmark registers by id and the
main steps in its own process, so environment 0's `step()` is the clock and the
stop switch. Nothing of the program is edited; three of its names are wrapped
while the main runs: `build_models` (the weights are the benchmark's own, made
from the seed by the plain reference's generator), `make_policy_step` (the
first steps' logits are kept) and `make_train_step` (the first steps' batches,
losses and the norms of what they did to the state are kept).

The window opens and closes on the first iteration boundary after an update
ends, so it holds whole collect-and-update cycles: a train step is half a
second, and one more or less at a window's edge would move the rate by a
cycle in some twenty-five.
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
from ..reduce import by_module
from ..reduce import trace as reduce_trace
from . import sdar_names
from .train_main import CONTROL_OPERANDS, RC_PREEMPTED, Compiles, hand_back_freed_memory, preempt

ENV_ID = "SheepBenchTokens-v0"
FOLLOWED_STEPS = 3  # train steps followed against the reference
FOLLOWED_POLICY_STEPS = 4  # two blocks of two denoising steps: the second block reads what the first's commit pass wrote
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok", "norm_topk_prob", "experts_held", "vocab_size",
)
FAULTS = ("drop_expert", "causal")  # planted in the reference: one held expert left out; the block mask made causal
LEAST_ROWS = 16  # of the followed rows have to pick a held expert for it to be judged: a median over a handful is one flipped pick's


class Window:
    """The measured window, driven from environment 0's `step()`: it opens at
    the first boundary after update number `warmup_updates` (or a later one)
    has ended and `warmup_iterations` boundaries have passed, once freed
    memory is handed back, and closes at the first
    boundary that follows an update's end `seconds` or more later. A traced
    run keeps going: the profiler starts at the closing boundary and stops
    `trace["iterations"]` boundaries later. Then `stop` ends the main."""

    def __init__(self, seconds: float, warmup_updates: int, warmup_iterations: int, train_steps, steps_per_update: int, trace: dict | None = None, stop=None):
        self.seconds, self.warmup_updates, self.warmup_iterations, self.trace = seconds, warmup_updates, warmup_iterations, trace
        self.train_steps, self.steps_per_update = train_steps, steps_per_update
        self.stop = stop or preempt
        self.hand_back_seconds = 0.0
        self.stamps: list[float] = []
        self.steps_at: list[int] = []  # train steps dispatched before each boundary
        self.i_open = self.i_close = None
        self.updates_seen = 0
        self.traced_iterations = 0
        self.counters = lambda: ()
        self.at_open = self.at_close = self.at_trace_end = ()

    def on_step(self) -> None:
        steps = self.train_steps()
        updates = steps // self.steps_per_update
        after_update, self.updates_seen = updates > self.updates_seen, updates
        if self.i_open is None and after_update and updates >= self.warmup_updates and len(self.stamps) >= self.warmup_iterations:
            t = time.perf_counter()
            hand_back_freed_memory()  # before the stamp: set-up's time, not the window's
            self.hand_back_seconds = time.perf_counter() - t
            self.i_open, self.at_open = len(self.stamps), self.counters()
        now = time.perf_counter()
        self.stamps.append(now)
        self.steps_at.append(steps)
        if self.i_open is None or self.i_open == len(self.stamps) - 1:
            return
        if self.i_close is None:
            if after_update and now - self.stamps[self.i_open] >= self.seconds:
                self.i_close, self.at_close = len(self.stamps) - 1, self.counters()
                if self.trace is None:
                    self.stop()
                else:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0  # host spans, not every Python call
                    jax.profiler.start_trace(self.trace["dir"], profiler_options=options)
        elif self.trace is not None and self.traced_iterations < self.trace["iterations"]:
            self.traced_iterations += 1
            if self.traced_iterations == self.trace["iterations"]:
                self.at_trace_end = self.counters()
                jax.profiler.stop_trace()
                self.stop()

    @property
    def iteration_seconds(self) -> list[float]:
        s = self.stamps[self.i_open : self.i_close + 1]
        return [b - a for a, b in zip(s, s[1:])]

    @property
    def update_iteration_seconds(self) -> list[float]:
        """The window's iterations in which train steps were dispatched: an update and the collection step beside it."""
        n = self.steps_at[self.i_open : self.i_close + 1]
        return [dt for dt, a, b in zip(self.iteration_seconds, n, n[1:]) if b > a]


def make_window(traffic: dict, seconds: float, train_steps, trace: dict | None = None) -> Window:
    """The window as a traffic file sets it: `warmup_updates`, and
    `warmup_iterations`, the boundaries that pass before it may open."""
    steps_per_update = traffic["args"]["update_sequences"] // traffic["args"]["per_rank_batch_size"]
    return Window(seconds, traffic["warmup_updates"], traffic["warmup_iterations"], train_steps, steps_per_update, trace)


def make_traffic(config: dict, traffic: dict, seed: int, **hooks) -> Traffic:
    """The generator of this driver's cells, from a traffic file's parameters."""
    params = {**traffic["env"], "vocab_size": config["vocab_size"], "group_size": traffic["args"]["group_size"]}
    return Traffic(params, traffic["num_envs"], traffic["args"]["block_length"], seed, **hooks)


def _adam_mu(opt_state):
    found = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer chain, found {len(found)}")
    return found[0].mu


@jax.jit
def _gap_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


def delta_norms(leaves: dict, seed_leaf) -> dict[str, float]:
    """Norm of each leaf's change from the seed's weights, which `seed_leaf(name)`
    makes again a leaf at a time: no second copy of the model stands by."""
    return {name: float(_gap_norm(leaf, seed_leaf(name))) for name, leaf in leaves.items()}


class TrainRecorder:
    """The program's train step, with the first steps' batches and losses and
    the norms of what they did to the state kept beside it: norms, not copies,
    because a copy of the model does not fit beside it. After those steps it
    is one comparison per call, and keeps the newest batch for the row check."""

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
        new_state, new_player, metrics, counts = self.step(state, player, batch)
        self.losses.append(metrics["Loss/policy_loss"])
        if self.calls == 1:  # Adam's first moment after one step is (1 - b1) x the gradient it was given
            self.grad = sdar_names.leaf_norms(_adam_mu(new_state.opt_state))
        if self.calls == FOLLOWED_STEPS:
            self.delta = delta_norms(sdar_names.to_reference(new_state.model), self.seed_leaf)
        return new_state, new_player, metrics, counts


class PolicyRecorder:
    """The program's policy step; the first steps' logits are kept."""

    def __init__(self, step):
        self.step, self.calls, self.logits = step, 0, []

    def __call__(self, player, state, key):
        out = self.step(player, state, key)
        if self.calls < FOLLOWED_POLICY_STEPS:
            self.logits.append(np.asarray(out[2]))  # on the host: warm-up's steps may wait, the window's memory is the program's
        self.calls += 1
        return out


def model_config(config: dict, traffic: dict) -> dict:
    """What the reference is built from: the configuration's model keys, and
    the update's and the generation's settings, which are also the program's argv."""
    c = {k: config[k] for k in MODEL_KEYS}
    c.update(traffic["args"])
    c.update(config["args"])
    c.update(first_expert=config["args"].get("first_expert", 0), adam_eps=config["args"]["eps"], mask_token_id=config["vocab_size"] - 1)
    return c


def _flag(key: str, value) -> list[str]:
    if isinstance(value, bool):
        return [f"--{key}" if value else f"--no_{key}"]
    return [f"--{key}", str(value)]


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
        return sdar_names.from_reference(real_build(*a, **kw), seed_leaf)

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
        # the traced stretch: the mean context behind a policy step's block, and the expert products by program
        steps = traffic["trace_iterations"] * num_envs
        result["traced_context_tokens"] = (window.at_trace_end[2] - window.at_close[2]) / steps
        if jax.devices()[0].platform == "tpu":
            planes = reduce_trace.load(reduce_trace.newest_trace(trace_cfg["dir"]))
            result["moe_in_train_steps"] = by_module.seconds_inside(planes, "bd_train_step", "ragged-dot")

    # ---- correct: what the timed path produced against the plain reference,
    # once the window has closed, the peak has been read and the program's state freed
    gc.collect()
    t0 = time.perf_counter()
    result["numbers"], result["detail"] = _compare(recorders["train"], recorders["policy"], load, reference, seed, seed_leaf, c, control, config)
    result["reference_seconds"] = time.perf_counter() - t0
    where = result["detail"]["worst_leaf"]
    seen = result["detail"]["logits_rows"]
    result["notes"].append(f"logits: {seen['rows']} rows, a row's gap median {seen['median']:.4g} p90 {seen['p90']:.4g} max {seen['max']:.4g}; rows that pick a held expert, "
                           f"fewest {seen['rows_an_expert'][0]} most {seen['rows_an_expert'][1]}; {seen['experts_judged']} experts judged, the worst held expert {seen['worst_expert']}")
    result["notes"].append(f"worst leaves: grad_policy {result['numbers']['grad_policy']:.4g} at {where['grad_policy']}, "
                           f"delta_policy {result['numbers']['delta_policy']:.4g} at {where['delta_policy']}")
    result["notes"] += [f"{name[: -len('_verdict')]} in the program's place: {verdict}" for name, verdict in result["detail"].items() if name.endswith("_verdict")]
    return result


def _compile_note(compiles: Compiles, t_open: float) -> str:
    """What `compile_s` is made of: the seconds of every timed jax event before the window, by the event's name."""
    kinds: dict[str, list[float]] = {}
    for name, seconds, end in compiles.events:
        if end <= t_open:
            kinds.setdefault(name.rsplit("/", 1)[-1], []).append(seconds)
    return "set-up's jax events (seconds, count, the longest): " + "; ".join(
        f"{k} {sum(v):.1f} x{len(v)} max {max(v):.1f}" for k, v in sorted(kinds.items(), key=lambda kv: -sum(kv[1])))


def _counter_notes(run: dict) -> list[str]:
    """What the program's own counters say of the window's updates; no metric reads them yet (PERF.md section 7)."""
    from ..reduce import updates

    spans = updates.in_window(run)
    steps = [s for s in updates.train_steps(spans) if s["load_mean"] > 0]
    if not steps:
        return []
    ratios = sorted(s["load_max"] / s["load_mean"] for s in steps)
    positions = sum(s["positions"] for s in spans)
    return [f"program counters over the window's {len(spans)} updates ({len(steps)} train steps): padded positions {100.0 * sum(s['pad_positions'] for s in spans) / positions:.2f} % "
            f"of {positions}; fullest held expert over the mean count, median {ratios[len(ratios) // 2]:.3f} max {ratios[-1]:.3f}; "
            f"assignments to held experts a train step, mean {sum(s['assignments'] for s in steps) / len(steps):.0f}; update spans {sum(s['dur_ms'] for s in spans) / 1e3:.3f} s"]


# ------------------------------------------------------------------ correct
def expected_batch_row(ep, c: dict, p_max: int, r_max: int) -> dict[str, np.ndarray]:
    """One trained sequence's row of the update's layout, rebuilt from the
    benchmark's own record of the episode (and from nothing of the program)."""
    bl, steps, mask_id = c["block_length"], c["denoise_steps"], c["mask_token_id"]
    ids, step = response_of(ep, bl)
    p, n, S = len(ep.prompt), ep.response_len, p_max + (1 + steps) * r_max
    row = {"ids": np.zeros(S, np.int64), "positions": np.zeros(S, np.int64), "copy": np.full(S, -1, np.int64),
           "loss_pos": np.zeros(r_max, np.int64), "loss_mask": np.zeros(r_max), "targets": np.zeros(r_max, np.int64)}
    row["ids"][:p], row["positions"][:p], row["copy"][:p] = ep.prompt, np.arange(p), 0
    for k in range(steps + 1):
        at = p_max + k * r_max
        row["ids"][at : at + n] = ids if k == 0 else np.where(step < k, ids, mask_id)
        row["positions"][at : at + n], row["copy"][at : at + n] = p + np.arange(n), k
    row["block"] = row["positions"] // bl
    row["loss_pos"][:n], row["loss_mask"][:n], row["targets"][:n] = p_max + step * r_max + np.arange(n), 1.0, ids
    return row


def rows_mismatch(load: Traffic, batches: list[dict], c: dict) -> int:
    """Rows of the trained batches that are not, field for field, what the
    environments recorded: every sequence, copy and committed position, and
    the advantage its group's rewards give."""
    group = load.params["group_size"]
    finished = {}
    for env in load.envs:
        for serial, ep in enumerate(env.log):
            if ep.reward is not None:
                members = [load.envs[env.group * group + j].log[serial] for j in range(group)]
                rewards = np.array([m.reward if m.reward is not None else np.nan for m in members])
                adv = (ep.reward - rewards.mean()) / (rewards.std() + 1e-6)
                finished[(ep.prompt.tobytes(), response_of(ep, load.block_length)[0].astype(np.int32).tobytes())] = (ep, adv)
    bad = 0
    for batch in batches:
        host = {k: np.asarray(v) for k, v in batch.items()}
        for b in range(host["ids"].shape[0]):
            live = host["copy"][b] >= 0
            if not live.any():
                continue  # a padded row: nothing is trained on it
            p, n = int((host["copy"][b][: load.p_max] == 0).sum()), int(host["loss_mask"][b].sum())
            key = (host["ids"][b, :p].astype(np.int32).tobytes(), host["ids"][b, load.p_max : load.p_max + n].astype(np.int32).tobytes())
            if key not in finished:
                bad += 1
                continue
            ep, adv = finished[key]
            want = expected_batch_row(ep, c, load.p_max, load.r_max)
            same = all(np.array_equal(host[k][b], want[k]) for k in want) and abs(float(host["advantages"][b]) - adv) <= 1e-4 * max(1.0, abs(adv))
            bad += 0 if same else 1
    return bad


def _rel_rms(value, reference) -> float:
    value, reference = np.asarray(value, np.float64), np.asarray(reference, np.float64)
    gap = float(np.sqrt(np.mean((value - reference) ** 2)) / max(np.sqrt(np.mean(reference**2)), 1e-30))
    return gap if np.isfinite(gap) else compare.NOT_A_NUMBER


def _logits_numbers(value, reference, picks) -> tuple[dict[str, float], dict]:
    """The first policy steps' logits against the reference's, a row a position of a block. `logits_policy`: the RMS
    gap over all rows, over the reference's RMS. `logits_expert_policy`: a row's gap the same way, the median over
    the rows that pick a held expert in some layer (by the reference's routing), the worst of the held experts. One
    expert left out or wrong moves its own rows and few others, so the whole's RMS sees it only where many rows pick
    it; a routing pick that flips under bfloat16 moves single rows, which a median passes over. An expert that fewer
    than LEAST_ROWS rows pick is not judged. -> (the two numbers, what the rows looked like)"""
    value, reference = np.asarray(value, np.float64), np.asarray(reference, np.float64)
    rows = np.sqrt(np.mean((value - reference) ** 2, axis=-1) / max(np.mean(reference**2), 1e-60)).reshape(-1)
    counts = picks.sum(axis=0)
    judged = {e: float(np.median(rows[picks[:, e]])) for e in range(picks.shape[1]) if counts[e] >= LEAST_ROWS}
    worst = max(judged, key=judged.get, default=None)
    numbers = {"logits_policy": float(np.sqrt(np.mean(rows**2))), "logits_expert_policy": judged.get(worst, 0.0)}
    seen = {"rows": len(rows), "median": float(np.median(rows)), "p90": float(np.quantile(rows, 0.9)), "max": float(rows.max()),
            "experts_judged": len(judged), "worst_expert": worst, "rows_an_expert": [int(counts.min()), int(counts.max())]}
    return {k: v if np.isfinite(v) else compare.NOT_A_NUMBER for k, v in numbers.items()}, seen


def _policy_cases(pol: PolicyRecorder, load: Traffic, c: dict) -> list[tuple]:
    """(ids padded, valid length, the program's logits) of every followed step and environment."""
    bl, cases = c["block_length"], []
    for t, logits in enumerate(pol.logits):
        for j, env in enumerate(load.envs):
            ep = env.log[0]
            seen = type(ep)(ep.prompt, ep.response_len)
            seen.actions = ep.actions[:t]
            ids, _ = response_of(seen, bl)
            done = int((ids >= 0).sum()) // bl * bl
            block = np.where(ids[done : done + bl] >= 0, ids[done : done + bl], c["mask_token_id"])
            padded = np.zeros(load.p_max + load.r_max, np.int32)
            n = len(ep.prompt) + done + bl
            padded[:n] = np.concatenate([ep.prompt, ids[:done], block])
            cases.append((padded, n, logits[j]))
    return cases


def _compare(rec: TrainRecorder, pol: PolicyRecorder, load: Traffic, reference, seed: int, seed_leaf, c: dict, control: bool, config: dict):
    if rec.calls < FOLLOWED_STEPS or rec.delta is None or len(pol.logits) < FOLLOWED_POLICY_STEPS:
        raise RuntimeError(f"only {rec.calls} train steps and {len(pol.logits)} policy steps ran before the window closed")
    mismatch = rows_mismatch(load, [*rec.batches, *([rec.last_batch] if rec.last_batch is not None else [])], c)
    rec.last_batch = None
    keep = np.arange(c["vocab_size"]) != c["mask_token_id"]  # the program's logit there is -inf by design
    cases = _policy_cases(pol, load, c)
    batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in rec.batches]
    live = rec.batches[0]["loss_mask"] > 0
    # --control 1: what stands in the program's place, each read against the same reference and judged by the cell's own limits
    stand_ins = {"control": {"quant": CONTROL_OPERANDS}, **{f: {"fault": f} for f in FAULTS}} if control else {}

    with jax.default_matmul_precision("highest"):
        def logits_of(params, **kw):
            """-> the reference's logits [cases, block, vocabulary], and the held experts each row picks [rows, held]."""
            fn = jax.jit(lambda p, ids, n: reference.policy_logits(p, ids, n, c, with_picks=True, **kw))
            logits, picks = zip(*(map(np.asarray, fn(params, jnp.asarray(ids), jnp.int32(n))) for ids, n, _ in cases))
            return np.stack(logits)[..., keep], np.concatenate(picks)

        def logp_of(params, **kw):  # the first batch's committed tokens, at the seed's weights
            return np.asarray(jax.jit(lambda p, b: reference.batch_loss(p, b, c, **kw)[1])(params, batches[0]))[live]

        params = reference.make_params(seed, c)
        (ref_logits, picks), ref_logp = logits_of(params), logp_of(params)
        sides = {name: {**_logits_numbers(logits_of(params, **kw)[0], ref_logits, picks)[0], "logprob_old": _rel_rms(logp_of(params, **kw), ref_logp)}
                 for name, kw in stand_ins.items()}

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
    logits_numbers, rows = _logits_numbers(np.stack([l[:, keep] for _, _, l in cases]), ref_logits, picks)
    numbers.update(logits_numbers)
    numbers["logprob_old"] = _rel_rms(rec.batches[0]["logprob_old"][live], ref_logp)
    numbers["rows_mismatch"] = float(mismatch)
    detail = {"worst_leaf": where, "logits_rows": rows, "reference_loss": ref["loss"], "program_loss": prog["loss"]}
    limits = {k: v for k, v in config["limits"].items() if k != "rows_mismatch"}
    if control:
        side_numbers, detail["control_worst_leaf"] = compare.training_numbers(followed(reference.make_params(seed, c), **stand_ins["control"]), ref)
        sides["control"].update(side_numbers)
    for name, side in sides.items():
        correct, table = compare.judge(side, {k: v for k, v in limits.items() if k in side})
        detail[name] = side
        detail[name + "_verdict"] = {"correct": correct, "failed_by": [k for k, row in table.items() if row["limit"] is not None and not row["value"] <= row["limit"]]}
    return numbers, detail
