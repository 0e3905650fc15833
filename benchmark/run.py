"""One run of one cell of BENCHMARK.json.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one driver or
one metric is a file of its own, found by the name BENCHMARK.json gives:
`configs/<config>.json`, `traffic/<traffic>.json`, `drivers/<driver>.py`,
`metrics/<metric>.py`. This file names none of them.

The last line of standard output is the result; the numbers that decided
`correct` are its last key, and the last lines of standard error.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

from . import compare

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_age_seconds() -> float:
    """Seconds since this process was started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str) -> dict:
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    with open(os.path.join(ROOT, files[entry["config"]])) as f:
        config = json.load(f)
    return {
        "name": workload,
        "chips": entry["chips"],
        "config": config,
        "traffic": load_json("traffic", entry["traffic"] + ".json"),
    }


def metrics_for(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group] if workload in m.get("workloads", [workload])]


def read_metric(name: str, run: dict):
    """The metric's own reader; None where it finds nothing to read."""
    return importlib.import_module(f"benchmark.metrics.{name.replace('.', '__')}").read(run)


def run_cell(manifest: dict, cell: dict, seed: int, seconds: float, trace: bool,
             control: bool = False, require_chip: bool = True, fault=None) -> dict:
    """`require_chip=False` and `fault` are for the tests: a rehearsal off the
    chip reports no metric, and a fault breaks the timed step underneath."""
    t_import = time.perf_counter()
    age_at_import = process_age_seconds()
    import jax

    workload = cell["name"]
    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: cell {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    peaks = load_json("peaks.json")
    kind = devices[0].device_kind
    if platform == "tpu" and kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in benchmark/peaks.json")

    driver = importlib.import_module(f"benchmark.drivers.{cell['config']['driver']}")
    run = driver.run(cell, seed, seconds, trace, ROOT, control=control, fault=fault)
    run["setup_s"] = age_at_import + (run["t_open"] - t_import)
    run["peaks"] = peaks.get(kind)
    run["chips"] = cell["chips"]
    if run.get("trace_dir") and platform == "tpu":
        from .reduce import trace as reduce_trace

        run["trace"] = reduce_trace.reduce_dir(run["trace_dir"], cell["chips"])
    shutil.rmtree(run["out_dir"], ignore_errors=True)  # the run dir, its checkpoint, the trace

    group = "per_layer" if trace else "end_to_end"
    values = {}
    for m in metrics_for(manifest, group, workload):
        value = read_metric(m["name"], run)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    for line in run.get("notes", []):
        print(line)
    iters = sorted(run["iteration_seconds"])
    print(f"window: {run['iterations']} iterations, {run['env_steps']} env steps in {run['window_s']:.4f} s; "
          f"iteration ms median {1e3 * iters[len(iters) // 2]:.2f} p95 {1e3 * iters[int(0.95 * (len(iters) - 1))]:.2f} "
          f"max {1e3 * iters[-1]:.2f}; "
          f"resets {run['resets']}; compiles in window {run['compiles_in_window']}; "
          f"reference {run['reference_seconds']:.1f} s")

    slow = sorted(enumerate(run["iteration_seconds"]), key=lambda kv: -kv[1])[:3]
    print("slowest iterations (index, ms):", [(i, round(1e3 * t, 1)) for i, t in slow],
          "; timed jax events inside the window:", run["events_in_window"][:12])

    correct, table = compare.judge(run["numbers"], cell["config"]["limits"])
    result = {
        "correct": correct,
        "attempted": run["env_steps"],
        "failed": run["env_steps"] if run["compiles_in_window"] else 0,
        # a CPU rehearsal's numbers are never written under a device metric's name
        "metrics": values if platform == "tpu" else {},
        "device": {
            "platform": platform,
            "kind": kind,
            "count": cell["chips"] if platform == "tpu" else len(devices),
            "memory_peak_bytes": run["memory_peak_bytes"],
        },
    }
    if platform != "tpu":
        result["cpu_rehearsal"] = values
    if trace and "trace" in run:
        result["device"]["busy_s"] = run["trace"]["busy_s"]
        result["device"]["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    if control:
        result["detail"] = run["detail"]
    result["compared"] = table
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also read the lower-precision control and the planted faults (never the driver's runs)")
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    result = run_cell(manifest, load_cell(manifest, args.workload), args.seed, args.seconds, bool(args.trace), control=bool(args.control))
    sys.stdout.flush()
    for name in ("control", "half_batch"):  # --control 1: each has to come out as not correct
        if name + "_verdict" in result.get("detail", {}):
            print(f"{name} in the program's place: {result['detail'][name + '_verdict']}", file=sys.stderr)
    for name, row in result["compared"].items():
        print(f"compared {name} = {row['value']} limit {row['limit']}", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
