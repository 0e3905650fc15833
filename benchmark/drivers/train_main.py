"""Drives a training main of the program, in this process, for one cell.

The entry is the program's own `main(argv)`. The traffic is an environment
the benchmark registers by id, which the main steps in its own process
(`--sync_env`): so environment 0's `step()` is the clock, the source of the
host span, and the stop switch — it is called once per iteration, right
after the main has pulled the action indices off the device, which waits for
every train step dispatched before it.

Nothing of the program is edited. Two of its names are wrapped while the
main runs: `build_models`, so that the weights are the benchmark's own (made
from the seed by the plain reference's generator, which is how the reference
gets the same ones without taking any from the program), and
`make_train_step`, so that the first steps' inputs and results are kept —
the same compiled step, with its state, that the window then drives.
"""

from __future__ import annotations

import ctypes
import gc
import importlib
import json
import os
import shutil
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import compare
from ..envs.pixel_episodes import Traffic
from . import dv3_names

ENV_ID = "SheepBenchPixels-v0"
RC_PREEMPTED = 75
COMPILE_EVENTS = "/jax/core/compile/"
FOLLOWED_STEPS = 3
# the control: the reference in the program's place with the operands of every
# product in the nearest precision below the configurations' bfloat16
CONTROL_OPERANDS = jnp.float8_e4m3fn
PROGRAM_LOSS = {"wm": "Loss/reconstruction_loss", "actor": "Loss/policy_loss", "critic": "Loss/value_loss"}


class Compiles:
    """Every trace, lowering and backend compilation, with when it ended."""

    def __init__(self) -> None:
        self.events: list[tuple[str, float, float]] = []

    def __call__(self, name: str, secs: float, **_) -> None:
        self.events.append((name, secs, time.perf_counter()))

    def seconds_before(self, t: float) -> float:
        return sum(s for n, s, end in self.events if n.startswith(COMPILE_EVENTS) and end <= t)

    def backend_between(self, t0: float, t1: float) -> int:
        return sum(1 for n, _, end in self.events if n.endswith("backend_compile_duration") and t0 < end <= t1)


def preempt() -> None:
    """The main's own preemption path: SIGTERM, which its `RunGuard` takes by
    ending the step in flight and leaving. Sent only while a handler stands:
    the default action would kill this process, and whatever runs it."""
    if not callable(signal.getsignal(signal.SIGTERM)):
        raise RuntimeError("the main has no SIGTERM handler installed: it would not stop, it would die")
    os.kill(os.getpid(), signal.SIGTERM)


def hand_back_freed_memory() -> None:
    """The last act of set-up. Compiling and warming up leave gigabytes freed
    and not yet returned to the system; left alone, the allocator returns them
    in one go some hundred iterations later (0.1 to 0.7 s in a process that
    loaded its programs from the cache, 2.3 to 3.1 s in one that compiled
    them): warm-up's work inside the window. Returned here it is inside
    `setup_s`, and a run that compiles reads like one that does not."""
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):  # no glibc: nothing to hand back this way
        pass


class Window:
    """The measured window, driven from environment 0's `step()`.

    It opens at boundary number `open_at`, once freed memory is handed back,
    and closes at the first boundary `seconds` or more later. A traced run then keeps going: the profiler
    starts at the closing boundary (starting it stalls the host for seconds,
    which must not fall inside the window) and stops `trace["iterations"]`
    boundaries later. Then `stop` ends the main."""

    def __init__(self, seconds: float, open_at: int, trace: dict | None = None, stop=None, policy_from: int = 0):
        self.seconds, self.open_at, self.trace, self.policy_from = seconds, open_at, trace, policy_from
        self.stop = stop or preempt
        self.hand_back_seconds = 0.0
        self.stamps: list[float] = []
        self.i_open = self.i_close = None  # indices into stamps
        self.traced_iterations = 0
        self.counters = lambda: ()  # read when the policy takes over, at the opening and at the close
        self.at_policy = self.at_open = self.at_close = ()

    def on_step(self, now: float | None = None) -> None:
        if self.i_open is None and len(self.stamps) + 1 == self.open_at:
            t = time.perf_counter()
            hand_back_freed_memory()  # before the stamp: set-up's time, not the window's
            self.hand_back_seconds = time.perf_counter() - t
        now = time.perf_counter() if now is None else now
        self.stamps.append(now)
        i = len(self.stamps) - 1
        if len(self.stamps) == self.policy_from:
            self.at_policy = self.counters()
        if self.i_open is None:
            if len(self.stamps) >= self.open_at:
                self.i_open, self.at_open = i, self.counters()
        elif self.i_close is None:
            if now - self.stamps[self.i_open] >= self.seconds:
                self.i_close, self.at_close = i, self.counters()
                if self.trace is None:
                    self.stop()
                else:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0  # host spans, not every Python call
                    jax.profiler.start_trace(self.trace["dir"], profiler_options=options)
        elif self.trace is not None and self.traced_iterations < self.trace["iterations"]:
            self.traced_iterations += 1
            if self.traced_iterations == self.trace["iterations"]:
                jax.profiler.stop_trace()
                self.stop()

    @property
    def iteration_seconds(self) -> list[float]:
        s = self.stamps[self.i_open : self.i_close + 1]
        return [b - a for a, b in zip(s, s[1:])]

    @property
    def window_seconds(self) -> float:
        return self.stamps[self.i_close] - self.stamps[self.i_open]


class Recorder:
    """The program's train step, with the first steps' inputs, losses and
    states kept beside it. After those it is one attribute test per call,
    and keeps the newest batch alive for the replay check."""

    def __init__(self, step, fault=None):
        self.step = step if fault is None else (lambda *a: fault(step, *a))
        self.calls = 0
        self.samples, self.keys, self.metrics = [], [], []
        self.mu = self.params_after = self.last_sample = None

    def __call__(self, state, sample, key, tau):
        self.last_sample = sample
        if self.calls >= FOLLOWED_STEPS:
            return self.step(state, sample, key, tau)
        self.calls += 1
        self.samples.append(sample)
        self.keys.append(key)
        new_state, metrics = self.step(state, sample, key, tau)
        self.metrics.append(metrics)
        copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
        if self.calls == 1:  # the next call donates the state: copy what is compared
            self.mu = copy({
                "wm": _adam_mu(new_state.world_opt),
                "actor": _adam_mu(new_state.actor_opt),
                "critic": _adam_mu(new_state.critic_opt),
            })
        if self.calls == FOLLOWED_STEPS:
            self.params_after = copy({"wm": new_state.world_model, "actor": new_state.actor, "critic": new_state.critic})
        return new_state, metrics


def _adam_mu(opt_state):
    found = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer chain, found {len(found)}")
    return found[0].mu


def _model_config(config: dict, traffic: dict) -> dict:
    """What the reference is built from: the configuration's `args`, which
    are also the program's argv, and the traffic's action set."""
    return {**config["args"], "actions": traffic["env"]["actions"], "image_channels": 3}


def run(cell: dict, seed: int, seconds: float, trace: bool, root: str, control: bool = False, fault=None) -> dict:
    """One run of one cell. `fault`, for the tests, breaks the timed step."""
    config, traffic = cell["config"], cell["traffic"]
    module = importlib.import_module(config["entry"]["module"])
    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    import gymnasium as gym

    model_config = _model_config(config, traffic)
    program_seed = seed % (2**31)
    num_envs = traffic["num_envs"]
    learning_start_iteration = traffic["learning_starts"] // num_envs
    # first train step at that iteration, the second compile and the policy
    # step's in the next, then the steady warm-up iterations
    open_at = learning_start_iteration + 2 + traffic["warmup_iterations"]
    policy_from = learning_start_iteration + 1  # random actions until then

    out_dir = os.path.join(root, "benchmark_out", f"{cell['name']}-{seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_cfg = None
    if trace:
        trace_cfg = {"dir": os.path.join(out_dir, "trace"), "iterations": traffic["trace_iterations"]}
    window = Window(seconds, open_at, trace_cfg, policy_from=policy_from)
    load = Traffic(traffic["env"], num_envs, seed, on_step=window.on_step, annotate=jax.profiler.TraceAnnotation if trace else None)
    window.counters = lambda: (load.host_seconds, load.resets)
    compiles = Compiles()
    recorder: list[Recorder] = []

    real_build, real_make_step = module.build_models, module.make_train_step

    def build_models(*a, **kw):
        wm, actor, critic, target = real_build(*a, **kw)
        params = reference.make_params(seed, model_config)
        target = jax.tree_util.tree_map(jnp.copy, dv3_names.from_reference("critic", target, params))
        return (
            dv3_names.from_reference("wm", wm, params),
            dv3_names.from_reference("actor", actor, params),
            dv3_names.from_reference("critic", critic, params),
            target,
        )

    def make_train_step(*a, **kw):
        recorder.append(Recorder(real_make_step(*a, **kw), fault))
        return recorder[-1]

    argv = [
        *config["flags"], *(x for k, v in config["args"].items() for x in (f"--{k}", str(v))),
        "--buffer_size", str(config["replay_capacity"]), *traffic["argv"], "--env_id", ENV_ID, "--seed", str(program_seed),
        "--num_envs", str(num_envs), "--learning_starts", str(traffic["learning_starts"]),
        "--root_dir", out_dir, "--run_name", "run",
    ]
    if ENV_ID in gym.registry:
        del gym.registry[ENV_ID]
    gym.register(ENV_ID, entry_point=load.make_env)
    jax.monitoring.register_event_duration_secs_listener(compiles)
    module.build_models, module.make_train_step = build_models, make_train_step
    rc = None
    try:
        getattr(module, config["entry"]["function"])(argv)
    except SystemExit as exit_:
        rc = exit_.code
    finally:
        module.build_models, module.make_train_step = real_build, real_make_step
        jax.monitoring.unregister_event_duration_listener(compiles)
        del gym.registry[ENV_ID]
    if rc != RC_PREEMPTED or window.i_close is None:
        raise RuntimeError(f"the main ended (rc={rc}) before the window closed: raise --total_steps")
    rec = recorder[0]
    if window.at_open[1] == window.at_policy[1]:
        # the first episode end under the policy compiles the player's reset:
        # the traffic has to bring one before the window, not inside it
        raise RuntimeError("no episode ended between the policy's first step and the window: lengthen the warm-up")

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
        "window_s": window.window_seconds,
        "iterations": iterations,
        "iteration_seconds": window.iteration_seconds,
        "env_steps": iterations * num_envs,
        "resets": window.at_close[1] - window.at_open[1],
        "env_host_seconds": window.at_close[0] - window.at_open[0],
        "compile_seconds_before_window": compiles.seconds_before(t_open),
        "compiles_in_window": compiles.backend_between(t_open, t_close),
        "events_in_window": [(n, round(s, 4), round(end - t_open, 3)) for n, s, end in compiles.events if t_open < end <= t_close],
        "memory_peak_bytes": peak,
        "events": events,
        "model_config": model_config,
        "out_dir": out_dir,
        "trace_dir": trace_cfg["dir"] if trace else None,
        "notes": [f"set-up's last act, freed memory handed back to the system: {window.hand_back_seconds:.3f} s"],
    }

    # ---- correct: the first steps against the plain reference, once the
    # window has closed, the peak has been read and the program's state freed
    gc.collect()
    t0 = time.perf_counter()
    numbers, detail = _compare(rec, load, reference, seed, model_config, control, config)
    result["numbers"], result["detail"] = numbers, detail
    result["reference_seconds"] = time.perf_counter() - t0
    return result


def _program_side(rec: Recorder, params0: dict, adam_b1: float) -> dict:
    side = {"loss": {}, "grad": {}, "delta": {}}
    params0 = jax.device_get(params0)
    for model, metric in PROGRAM_LOSS.items():
        side["loss"][model] = [float(m[metric]) for m in rec.metrics]
        # to the host first: on several chips the program's state spans the
        # mesh, the reference's lives on one device
        mu = jax.device_get(dv3_names.to_reference(model, rec.mu[model]))
        side["grad"][model] = compare.leaf_norms({k: v / (1.0 - adam_b1) for k, v in mu.items()})  # Adam's first moment after one step
        after = jax.device_get(dv3_names.to_reference(model, rec.params_after[model]))
        side["delta"][model] = compare.leaf_norms({k: v - params0[k] for k, v in after.items()})
    return side


def _reference_side(reference, params0, batches, keys, model_config, quant=None, half_batch=False) -> dict:
    if half_batch:
        batches = [{k: v[:, : v.shape[1] // 2] for k, v in b.items()} for b in batches]
    state, outs = reference.run_steps(params0, batches, keys, model_config, quant)
    side = {"loss": {}, "grad": {}, "delta": {}}
    for model in PROGRAM_LOSS:
        side["loss"][model] = [float(o["loss"][model]) for o in outs]
        side["grad"][model] = compare.leaf_norms(outs[0]["grads"][model])
        side["delta"][model] = compare.leaf_norms({k: v - params0[k] for k, v in state["params"][model].items()})
    return side


def _rows_mismatch(load: Traffic, samples: list[dict]) -> tuple[int, list[dict]]:
    """Rows of the sampled batches that are not, field for field, what the
    environments emitted, or do not follow their predecessor in time
    -> (count, the batches rebuilt from the benchmark's own record)."""
    bad, rebuilt = 0, []
    for sample in samples:
        host = {k: np.asarray(v) for k, v in sample.items()}
        rows, valid = load.expected_rows(host["rgb"])
        wrong = ~valid
        for k in ("rgb", "actions", "rewards", "dones", "is_first"):
            # the program stores its straight-through sample, onehot + p - p,
            # which can round to 1 - 2^-24: an action is held to 1e-6, the rest exactly
            diff = np.abs(host[k] - rows[k]) > 1e-6 if k == "actions" else host[k] != rows[k]
            wrong |= diff.reshape(*valid.shape, -1).any(-1)
        follows = (rows["env"][1:] == rows["env"][:-1]) & (rows["serial"][1:] == rows["serial"][:-1] + 1)
        wrong[1:] |= ~follows
        bad += int(wrong.sum())
        rebuilt.append({k: jnp.asarray(rows[k]) for k in ("rgb", "actions", "rewards", "dones", "is_first")})
    return bad, rebuilt


def _compare(rec, load, reference, seed, model_config, control, config):
    if rec.calls < FOLLOWED_STEPS or rec.params_after is None:
        raise RuntimeError(f"only {rec.calls} train steps ran before the window closed")
    mismatch, batches = _rows_mismatch(load, [*rec.samples, rec.last_sample])
    batches = batches[:FOLLOWED_STEPS]
    rec.samples.clear()
    rec.last_sample = None
    params0 = reference.make_params(seed, model_config)
    keys = rec.keys
    ref = _reference_side(reference, params0, batches, keys, model_config)  # plain float32 at `highest`
    prog = _program_side(rec, params0, reference.ADAM_B1)
    numbers, where = compare.training_numbers(prog, ref)
    numbers["rows_mismatch"] = float(mismatch)
    detail = {"worst_leaf": where, "reference_loss": ref["loss"], "program_loss": prog["loss"]}
    if control:
        # the control and the planted fault, in the program's place, read
        # against the same reference and judged by the cell's own limits
        # (never in the driver's runs: PERF.md and the tests use them)
        limits = {k: v for k, v in config["limits"].items() if k != "rows_mismatch"}
        for name, kw in (("control", {"quant": CONTROL_OPERANDS}), ("half_batch", {"half_batch": True})):
            side = _reference_side(reference, params0, batches, keys, model_config, **kw)
            detail[name] = compare.training_numbers(side, ref)[0]
            correct, table = compare.judge(detail[name], limits)
            detail[name + "_verdict"] = {
                "correct": correct,
                "failed_by": [k for k, row in table.items() if row["limit"] is not None and not row["value"] <= row["limit"]],
            }
    return numbers, detail
