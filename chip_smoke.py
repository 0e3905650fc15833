#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # from the root of a checkout, on a TPU host

Drives the main path once through the normal entry points, at the full
default width of the flagship model, and checks the outcome from the
children's own records (`telemetry*.jsonl`), never from its own beliefs:

  phase 0  device report   a child opens the backend and says what it found;
                           anything but a TPU stops the smoke here, non-zero
  phase A  trainer, f32    `python -m sheeprl_tpu dreamer_v3` at every model
                           default (dense 512, recurrent 512, cnn x32, 32x32
                           latent, 255 bins, B=16 x T=64, horizon 15) on
                           64x64x3 pixels: >= 3 train steps after the compile
                           steps, every loss finite
  phase B  trainer, bf16   the same under `--precision bfloat16`
  phase R  replay ring     the ring's add and sample, compiled by the chip's
                           own compiler at the benchmark cells' shapes
                           (3.9 GiB of pixels, nothing allocated): no
                           ring-sized copy, temporaries under 1 % of the ring
  phase E  expert product  the routed expert layer (`nn/moe.py`) at a published
                           width (hidden 2048, 16 of 128 experts of 768 held,
                           top-8, 16,384 tokens in bfloat16): the grouped
                           product as the chip's compiler lowers
                           `jax.lax.ragged_dot` agrees with every held expert
                           multiplied densely, and drops no token
  phase C  server          `python -m sheeprl_tpu serve --algo sac` at the
                           default SAC widths answers sequential, multi-row
                           and concurrent requests from a `ServeClient` here;
                           then again with `--quant int8`. Batched answers
                           must agree with the single-row (rung 1) answer for
                           the same observation, int8 answers with the f32
                           server's within the server's own quality bound

One process uses the chip at a time: this parent never initialises a JAX
backend (no `jax.devices()`, no array), each phase is ONE child, and a child
has exited before the next starts. There is no flag that lets it pass on
another platform. Exit code 0 and a last stdout line
`{"ok": true, "device": {...}}` mean every phase and every assertion held.
"""

from __future__ import annotations

import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
RUNS = os.path.join(ROOT, "logs", "chip_smoke", time.strftime("run_%Y%m%d_%H%M%S"))

DV3_ARGV = [
    "dreamer_v3", "--env_id", "continuous_dummy", "--cnn_keys", "rgb", "--sync_env",
    "--num_devices", "1", "--num_envs", "4",
    # 80 collection iterations (>= 64 rows per env ring for T=64), then one
    # train step per iteration for 9 iterations: 1 compile step + 8 more
    "--learning_starts", "320", "--total_steps", "352", "--buffer_size", "4096",
]
MIN_STEADY_STEPS = 3  # train steps whose interval no longer compiled anything big
DV3_FAMILIES = ("gru", "rssm", "two_hot")

SERVE_ARGV = [
    "serve", "--algo", "sac", "--model_argv", "--env_id Pendulum-v1",
    "--deadline_ms", "5000",
]
N_SEQUENTIAL, MULTI_ROWS, N_THREADS, N_PER_THREAD = 8, (2, 4, 8), 8, 4
N_REQUESTS = N_SEQUENTIAL + len(MULTI_ROWS) + N_THREADS * N_PER_THREAD
BATCH_ATOL = 1e-2  # batched vs rung-1 answer (TPU f32 matmuls run bf16 passes)

failures: list[str] = []
children: list[subprocess.Popen] = []


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> bool:
    if not ok:
        failures.append(what)
        say(f"  FAIL: {what}")
    return ok


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def spawn(argv: list[str], log_name: str) -> subprocess.Popen:
    os.makedirs(RUNS, exist_ok=True)
    log = open(os.path.join(RUNS, log_name), "w")
    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=ROOT, env=child_env(),
        stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
    )
    log.close()  # the child holds its own descriptor
    children.append(proc)
    return proc


def stop_children() -> None:
    for proc in children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def log_tail(log_name: str, n: int = 25) -> str:
    with open(os.path.join(RUNS, log_name), errors="replace") as fh:
        return "".join(fh.readlines()[-n:])


def read_events(path: str) -> list[dict]:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


# --------------------------------------------------------------------------- phase 0


def phase_device() -> dict:
    say("== phase 0: device report")
    code = (
        "import json; from sheeprl_tpu.telemetry.core import device_report; "
        "print('DEVICE_REPORT ' + json.dumps(device_report()))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=child_env(),
        capture_output=True, text=True, timeout=300,
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("DEVICE_REPORT ")]
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-3000:])
        sys.exit(f"chip_smoke: the device-report child failed (rc={proc.returncode})")
    report = json.loads(lines[-1][len("DEVICE_REPORT "):])
    say("  " + " ".join(f"{k}={report[k]}" for k in (
        "platform", "device_kind", "local_devices", "global_devices",
        "jax", "jaxlib", "libtpu", "cache_dir",
    )))
    if report["platform"] != "tpu":
        sys.exit(
            f"chip_smoke: found platform={report['platform']!r} "
            f"(device_kind={report['device_kind']!r}, count={report['global_devices']}), "
            "not a TPU — nothing was run"
        )
    return report


# --------------------------------------------------------------------------- shared record checks


def check_start(events: list[dict], device: dict, what: str) -> None:
    start = next((e for e in events if e["event"] == "start"), None)
    if not check(start is not None, f"{what}: no `start` event"):
        return
    check(
        (start["platform"], start["device_kind"]) == (device["platform"], device["device_kind"]),
        f"{what}: child ran on {start['platform']}/{start['device_kind']}, "
        f"not {device['platform']}/{device['device_kind']}",
    )
    check(start["cache_dir"] == device["cache_dir"],
          f"{what}: compile cache at {start['cache_dir']}, phase 0 said {device['cache_dir']}")
    crashes = [e for e in events if e["event"] == "crash"]
    check(not crashes, f"{what}: crash event {crashes[:1]}")
    check(any(e["event"] == "end" for e in events), f"{what}: no clean `end` event")


def report_cache(events: list[dict], what: str) -> dict:
    end = next((e for e in events if e["event"] == "end"), {})
    cache = {"hits": end.get("cache_hits"), "misses": end.get("cache_misses")}
    say(f"  {what}: persistent compile cache hits={cache['hits']} misses={cache['misses']}")
    return cache


def check_kernels(events: list[dict], families: tuple[str, ...], what: str) -> None:
    """Per Pallas family: was it selected, why (not), and never interpreted
    (a selected kernel lowered through Mosaic: the child compiled and ran)."""
    verdicts: dict[str, dict] = {}
    for e in events:
        if e["event"] != "kernel.select":
            continue
        v = verdicts.setdefault(e["family"], {"selected": 0, "not_selected": 0, "reasons": {}})
        v["selected" if e["selected"] else "not_selected"] += 1
        reason = e["reason"] + (
            f" ({e['bytes'] / 2**20:.1f} of {e['budget'] / 2**20:.0f} MiB)" if "bytes" in e else ""
        )
        v["reasons"][reason] = v["reasons"].get(reason, 0) + 1
        check(e["interpret"] is False,
              f"{what}: Pallas family {e['family']} traced with interpret=True on the chip")
    for fam in families:
        if check(fam in verdicts, f"{what}: no kernel.select event for family {fam}"):
            v = verdicts[fam]
            say(f"  kernel {fam}: selected x{v['selected']}, not selected x{v['not_selected']}, "
                f"reasons {v['reasons']}")


# --------------------------------------------------------------------------- phases A / B


def phase_trainer(name: str, extra: list[str], device: dict) -> dict:
    say(f"== phase {name}: python -m sheeprl_tpu {' '.join(DV3_ARGV + extra)}")
    t0 = time.monotonic()
    proc = spawn(
        ["-m", "sheeprl_tpu", *DV3_ARGV, *extra, "--root_dir", RUNS, "--run_name", name],
        f"{name}.log",
    )
    try:
        rc = proc.wait(timeout=540)
    except subprocess.TimeoutExpired:
        rc = None
    secs = time.monotonic() - t0
    if not check(rc == 0, f"{name}: trainer child rc={rc} after {secs:.0f}s\n{log_tail(name + '.log')}"):
        return {}
    events = read_events(os.path.join(RUNS, name, "telemetry.jsonl"))
    check_start(events, device, name)

    train = [
        e for e in events
        if e["event"] == "log" and any(k.startswith("Loss/") for k in e["metrics"])
    ]
    compiling = [e["step"] for e in train if e["metrics"].get("XLA/compile_seconds", 0.0) > 1.0]
    check(len(train) - len(compiling) >= MIN_STEADY_STEPS,
          f"{name}: {len(train)} logged train steps of which {len(compiling)} still compiled; "
          f"need {MIN_STEADY_STEPS} after the compile steps")
    bad = sorted({
        f"{k}@{e['step']}" for e in train for k, v in e["metrics"].items()
        if k.startswith(("Loss/", "Grads/", "State/")) and not finite(v)
    })
    check(not bad, f"{name}: non-finite train metrics {bad[:8]}")
    nan_events = [e for e in events if e["event"] == "health.nan"]
    check(not nan_events, f"{name}: health.nan events {nan_events[:1]}")
    last = train[-1]["metrics"] if train else {}
    say(f"  {len(train)} train steps logged (iterations {[e['step'] for e in train]}); "
        f"steps that still compiled >1s: {compiling}")
    say("  last interval: " + " ".join(
        f"{k.split('/', 1)[1]}={last[k]:.4g}" for k in sorted(last) if k.startswith("Loss/")))
    peak = last.get("Memory/d0_peak_bytes_in_use")
    if peak is not None:
        say(f"  device 0 peak bytes in use: {peak / 2**20:.0f} MiB")

    check_kernels(events, DV3_FAMILIES, name)
    transport = [e for e in events if e["event"] == "replay.transport"]
    if check(bool(transport), f"{name}: no replay.transport event"):
        t = transport[-1]
        say(f"  replay transport: {t['transport']} ({t['reason']})")
        check(t["transport"] == "blob",
              f"{name}: step-blob bitcast roundtrip failed on the chip: {t['reason']}")
    stores = [e for e in events if e["event"] == "replay.store"]
    if check(len(stores) == 1, f"{name}: {len(stores)} replay.store events, expected one (at allocation)"):
        keys = stores[0]["keys"]
        say("  replay store: " + "; ".join(
            f"{k} {v['dtype']}{v['logical']} as {v['storage']} {v['bytes'] / 2**20:.1f} MiB ({v['format']})"
            for k, v in sorted(keys.items())))
        check(keys.get("rgb", {}).get("format") == "lane_dense",
              f"{name}: the pixel ring is not stored lane-dense: {keys.get('rgb')}")
    broken = [e for e in events if e.get("errors")]
    check(not broken, f"{name}: measured decisions with failed candidates {broken[:1]}")
    cache = report_cache(events, name)
    say(f"  wall {secs:.0f}s")
    return {"cache": cache}


# --------------------------------------------------------------------------- phase R

# the benchmark's two cells: ring rows, environments, n_samples (B=16 x T=64)
RING_CELLS = ((86016, 4, 4), (21504, 16, 1))
RING_ITEMS = {
    "rgb": ((64, 64, 3), "uint8"), "actions": ((18,), "float32"), "rewards": ((1,), "float32"),
    "dones": ((1,), "float32"), "is_first": ((1,), "float32"),
}


def phase_replay_programs() -> None:
    """One child compiles the ring's two programs on the chip, from shapes,
    and reports what `sheeprl_tpu/data/store_check.py` reads off them."""
    say("== phase R: the replay ring's compiled add and sample")
    code = (
        "import json; from sheeprl_tpu.data import store_check\n"
        f"for rows, envs, n in {RING_CELLS!r}:\n"
        f"    rep = store_check.report(rows, envs, {RING_ITEMS!r}, batch=16, seq_len=64, n_samples=n)\n"
        "    rep['faults'] = store_check.faults(rep)\n"
        "    print('REPLAY_PROGRAMS ' + json.dumps({'rows': rows, 'envs': envs, **rep}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=child_env(),
        capture_output=True, text=True, timeout=300,
    )
    reports = [json.loads(l[len("REPLAY_PROGRAMS "):]) for l in proc.stdout.splitlines()
               if l.startswith("REPLAY_PROGRAMS ")]
    if not check(proc.returncode == 0 and len(reports) == len(RING_CELLS),
                 f"replay programs: child rc={proc.returncode}, {len(reports)} reports\n{proc.stderr[-2000:]}"):
        return
    for rep in reports:
        what = f"ring {rep['rows']} x {rep['envs']}"
        say(f"  {what}: {rep['store_bytes'] / 2**30:.2f} GiB, rgb {rep['formats']['rgb']}; "
            f"add temp {rep['add']['temp_bytes']} B, aliased {rep['add']['alias_bytes'] / 2**30:.2f} GiB; "
            f"sample temp {rep['sample']['temp_bytes']} B")
        check(not rep["faults"], f"replay programs, {what}: {rep['faults']}")


# --------------------------------------------------------------------------- phase E


EXPERT_PRODUCT = """
import json, time, jax, jax.numpy as jnp
from sheeprl_tpu.nn.moe import RoutedExperts
layer = RoutedExperts.init(jax.random.PRNGKey(0), 2048, 768, 128, 8, held=16)
x = jax.random.normal(jax.random.PRNGKey(1), (16384, 2048), jnp.bfloat16)
run = jax.jit(lambda m, v: m(v))
y, counts = jax.block_until_ready(run(layer, x))
t0 = time.perf_counter()
for _ in range(10):
    out = run(layer, x)
jax.block_until_ready(out)
ms = 1e3 * (time.perf_counter() - t0) / 10
weights, picks = layer.route(x[:512])
w = jnp.zeros((512, 128)).at[jnp.arange(512)[:, None], picks].set(weights)[:, :16]
up = lambda m: m.astype(jnp.bfloat16).astype(jnp.float32)
with jax.default_matmul_precision("highest"):
    r = x[:512].astype(jnp.float32)
    hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", r, up(layer.w_gate))) * jnp.einsum("th,ehf->etf", r, up(layer.w_up))
    want = jnp.einsum("te,etf,efh->th", w, hidden, up(layer.w_down))
gap = float(jnp.sqrt(jnp.mean((y[:512].astype(jnp.float32) - want) ** 2)) / jnp.sqrt(jnp.mean(want ** 2)))
print("EXPERT_PRODUCT " + json.dumps({"ms": ms, "assignments": int(counts.sum()), "fullest": int(counts.max()), "gap": gap,
                                     "picked_held": int(((picks >= 0) & (picks < 16)).sum()), "counted": int(run(layer, x[:512])[1].sum())}))
"""


def phase_expert_product() -> None:
    """One child runs the routed expert layer's grouped product on the chip."""
    say("== phase E: the routed expert layer's grouped product")
    proc = subprocess.run([sys.executable, "-c", EXPERT_PRODUCT], cwd=ROOT, env=child_env(), capture_output=True, text=True, timeout=600)
    lines = [json.loads(l[len("EXPERT_PRODUCT "):]) for l in proc.stdout.splitlines() if l.startswith("EXPERT_PRODUCT ")]
    if not check(proc.returncode == 0 and len(lines) == 1, f"expert product: child rc={proc.returncode}\n{proc.stderr[-2000:]}"):
        return
    rep = lines[0]
    say(f"  16 of 128 experts held, 16384 tokens x top-8: {rep['assignments']} assignments held (fullest expert {rep['fullest']}), "
        f"{rep['ms']:.3f} ms a call by jax.lax.ragged_dot (no kernel family of this repo's: no kernel.select verdict); "
        f"gap to the dense product {rep['gap']:.4f}")
    check(rep["gap"] < 0.02, f"expert product: gap to the dense product {rep['gap']}")
    check(rep["counted"] == rep["picked_held"], f"expert product: {rep['picked_held']} assignments picked held experts, {rep['counted']} were multiplied")


# --------------------------------------------------------------------------- phase C


def _observations():
    import numpy as np

    rng = np.random.default_rng(0)  # Pendulum-v1 observations: (cos, sin, thetadot)
    return rng.uniform(-1.0, 1.0, size=(max(MULTI_ROWS), 3)).astype(np.float32)


def drive_server(address: str) -> tuple[dict, set, float]:
    """Sequential single-row requests (the rung-1 reference answers), one
    request per multi-row size, then concurrent single-row clients.
    -> (row index -> reference action, rungs seen, worst |batched - reference|)."""
    import numpy as np

    # sockets + numpy; pulls jax in through the package __init__ but opens
    # no backend — asserted below, the chip belongs to the server child
    from sheeprl_tpu.serve.client import ServeClient

    obs = _observations()
    reference: dict[int, np.ndarray] = {}
    rungs: set[int] = set()
    worst = [0.0]
    lock = threading.Lock()

    def note(rows, result, meta):
        actions = np.asarray(result["actions"], np.float32)
        with lock:
            rungs.add(int(meta["rung"]))
            check(actions.shape[0] == len(rows) and np.all(np.isfinite(actions)),
                  f"serve: bad response shape/values {actions.shape} for rows {rows}")
            for i, row in enumerate(rows):
                if row in reference:
                    worst[0] = max(worst[0], float(np.max(np.abs(actions[i] - reference[row]))))
                else:
                    reference[row] = actions[i]

    with ServeClient(address, timeout=120.0) as client:
        for row in range(N_SEQUENTIAL):
            note([row], *client.request({"obs": obs[row:row + 1]}))
        for n in MULTI_ROWS:
            note(list(range(n)), *client.request({"obs": obs[:n]}))

    errors: list[str] = []

    def worker(tid: int) -> None:
        try:
            with ServeClient(address, timeout=120.0) as client:
                for j in range(N_PER_THREAD):
                    row = (tid + j) % N_SEQUENTIAL
                    note([row], *client.request({"obs": obs[row:row + 1]}))
        except Exception as err:  # noqa: BLE001 — reported as a smoke failure below
            errors.append(f"{type(err).__name__}: {err}")

    threads = [threading.Thread(target=worker, args=(t,), name=f"smoke-client-{t}", daemon=True)
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    check(not any(t.is_alive() for t in threads), "serve: a concurrent client never finished")
    check(not errors, f"serve: concurrent client errors {errors[:3]}")

    from jax._src import xla_bridge

    check(not xla_bridge._backends, "chip_smoke's own process initialised a JAX backend")
    return reference, rungs, worst[0]


def phase_server(name: str, extra: list[str], device: dict, f32_reference: dict | None) -> dict:
    say(f"== phase {name}: python -m sheeprl_tpu {' '.join(SERVE_ARGV + extra)}")
    t0 = time.monotonic()
    proc = spawn(
        ["-m", "sheeprl_tpu", *SERVE_ARGV, *extra, "--serve_requests", str(N_REQUESTS),
         "--root_dir", RUNS, "--run_name", name],
        f"{name}.log",
    )
    addr_file = os.path.join(RUNS, name, "serve_address")
    deadline = time.monotonic() + 300.0
    while not os.path.exists(addr_file) and proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.2)
    if not check(os.path.exists(addr_file),
                 f"{name}: server never came up (rc={proc.poll()})\n{log_tail(name + '.log')}"):
        return {}
    with open(addr_file) as fh:
        address = fh.read().strip()
    reference, rungs, worst = drive_server(address)
    try:
        rc = proc.wait(timeout=120)  # exits by itself after --serve_requests
    except subprocess.TimeoutExpired:
        rc = None
    secs = time.monotonic() - t0
    check(rc == 0, f"{name}: server child rc={rc}\n{log_tail(name + '.log')}")

    events = read_events(os.path.join(RUNS, name, "telemetry.serve.jsonl"))
    check_start(events, device, name)
    check(len(reference) == max(MULTI_ROWS), f"{name}: answers for {len(reference)} rows")
    check({1} < rungs, f"{name}: only rungs {sorted(rungs)} dispatched, want 1 and a batched rung")
    # an int8 ladder may be MIXED per rung: there the answers of two rungs
    # agree to the server's own quality bound, not to matmul rounding
    bounds = [e["bound"] for e in events if e["event"] == "serve.quant_rung" and "bound" in e]
    atol = max([BATCH_ATOL, *bounds])
    check(worst <= atol,
          f"{name}: a batched answer differs from the rung-1 answer by {worst:.3g} > {atol}")
    say(f"  {N_REQUESTS} requests answered; rungs dispatched {sorted(rungs)}; "
        f"worst |batched - rung-1 answer| = {worst:.3g}")

    ladder = [e for e in events if e["event"] == "serve.ladder"]
    say("  ladder: " + ", ".join(
        f"b{e['rung']} {'accepted' if e['accepted'] else 'REJECTED'} from {e['source']}"
        for e in ladder))
    check(bool(ladder) and all(e["source"] != "ledger" for e in ladder),
          f"{name}: a CPU-captured ledger sized a rung on the chip "
          f"{[(e['rung'], e['source']) for e in ladder]}")
    stop = next((e for e in events if e["event"] == "serve.stop"), {})
    check(stop.get("completed") == N_REQUESTS,
          f"{name}: server completed {stop.get('completed')} of {N_REQUESTS} requests")
    bad_compiles = [e for e in events if e["event"] == "compile" and e.get("error")]
    check(not bad_compiles, f"{name}: rung compile errors {bad_compiles[:2]}")

    out = {"reference": reference}
    if "--quant" in extra:
        scales = [e for e in events if e["event"] == "serve.quant_scales"]
        check(bool(scales) and all(e["source"] != "error" for e in scales),
              f"{name}: int8 calibration failed: {scales}")
        quant = [e for e in events if e["event"] == "serve.quant_rung"]
        check(bool(quant) and not any(e.get("error") for e in quant),
              f"{name}: int8 rung acceptance errors {quant}")
        say(f"  int8 scales: {', '.join(e['source'] for e in scales)}; rungs: " + ", ".join(
            f"b{e['rung']} {'int8' if e['accepted'] else 'f32'} (divergence {e['divergence']:.3g} "
            f"<= {e['bound']}: {e['within_bound']}, fused={e['fused']}, {e['source']})"
            for e in quant if "divergence" in e))
        check_kernels(events, ("sac_trunk",), name)
        bound = quant[0]["bound"] if quant else 0.0
        import numpy as np

        drift = max(
            float(np.max(np.abs(reference[r] - f32_reference[r]))) for r in reference
        ) if f32_reference else float("inf")
        check(drift <= bound,
              f"{name}: int8 answers differ from the f32 server's by {drift:.3g} > bound {bound}")
        say(f"  worst |int8 answer - f32 server's answer| = {drift:.3g} (bound {bound})")
    out["cache"] = report_cache(events, name)
    say(f"  wall {secs:.0f}s")
    return out


# --------------------------------------------------------------------------- main


def main() -> None:
    t0 = time.monotonic()
    device = phase_device()
    results = {}
    try:
        results["dv3_f32"] = phase_trainer("dv3_f32", [], device)
        results["dv3_bf16"] = phase_trainer("dv3_bf16", ["--precision", "bfloat16"], device)
        phase_replay_programs()
        phase_expert_product()
        results["serve_f32"] = phase_server("serve_f32", [], device, None)
        results["serve_int8"] = phase_server(
            "serve_int8", ["--quant", "int8"], device, results["serve_f32"].get("reference"),
        )
    finally:
        stop_children()
    say("== summary")
    hits = sum((r.get("cache") or {}).get("hits") or 0 for r in results.values())
    misses = sum((r.get("cache") or {}).get("misses") or 0 for r in results.values())
    say(f"  compile cache {device['cache_dir']}: hits={hits} misses={misses} over the four phases")
    say(f"  records under {RUNS}")
    say(f"  wall {time.monotonic() - t0:.0f}s")
    if failures:
        sys.stderr.write("chip_smoke: FAILED\n" + "\n".join(f"- {f}" for f in failures) + "\n")
        sys.exit(1)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["global_devices"],
        },
    }))


if __name__ == "__main__":
    main()
