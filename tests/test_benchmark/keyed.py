"""What a cell's metrics are held to, derived from the manifest and from the
source of the main the cell's configuration names, never from a list written
into a test: a later PR that adds a reader and lists a cell under it passes
these checks where the cell's driver feeds the reader.

- A reader of the program's spans or counters (`program_span`,
  `program_counter`) declares the spans it needs as `SPANS`; each has to be a
  literal `telem.mark("<name>"` or `telem.phase("<name>"` of the main, and a
  reader that needs any needs the main's `telem.iteration(` too.
- On the CPU rehearsal of the cell's driver every metric that lists the cell
  and reads the program's spans or counters reads a number >= 0, and one that
  reads the host's clock reads no negative number (`iter_ms_p95` reads none
  under 20 iterations); the device's own readers (`device_trace`, and the
  layer `device`, whose counters a CPU has not) read nothing there, and,
  handed a trace that holds nothing of the program, ask the driver's result
  for no key it lacks.
"""

from __future__ import annotations

import importlib
import inspect
import json
import os
import re
import sys
import types

import pytest

from benchmark import run as bench_run

from .conftest import ROOT

SPAN_SOURCES = ("program_span", "program_counter")
MADE_UP = "made_up_span_ms"
OPENED = re.compile(r'telem\.(?:mark|phase)\("([^"]+)"')
# what reduce/trace.py returns for a trace in which nothing of the program ran
NOTHING_TRACED = {"window_s": 1.0, "busy_s": 1.0, "busy_s_by_device": [1.0], "ops": [], "modules": {}, "idle_by_span_s": {},
                  "idle_outside_s": 0.0, "iterations_traced": 0, "breakdown": {"device_ops": [], "idle_gaps": []}}


def reader(name: str):
    return importlib.import_module(f"benchmark.metrics.{name.replace('.', '__')}")


def config_of(manifest: dict, cell: str) -> dict:
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    path = next(c["file"] for c in manifest["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def cells_of(manifest: dict, driver: str) -> list[str]:
    """The cells whose configuration runs that driver."""
    return [w["name"] for w in manifest["workloads"] if config_of(manifest, w["name"])["driver"] == driver]


def listing(manifest: dict, cell: str) -> list[dict]:
    return bench_run.metrics_for(manifest, "per_layer", cell)


def main_source(config: dict) -> str:
    module = importlib.import_module(config["entry"]["module"])
    return inspect.getsource(getattr(module, config["entry"]["function"]))


def check_spans(manifest: dict, cell: str) -> None:
    source = main_source(config_of(manifest, cell))
    opened = set(OPENED.findall(source))
    for m in listing(manifest, cell):
        if m["source"] not in SPAN_SOURCES:
            continue
        needs = set(reader(m["name"]).SPANS)
        assert needs <= opened, f"{m['name']} lists {cell}, whose main opens none of {sorted(needs - opened)}"
        assert not needs or "telem.iteration(" in source, m["name"]


def check_rehearsal(manifest: dict, cell: str, run: dict) -> None:
    """`run`: the run dict of a CPU rehearsal of the cell's driver, as the readers saw it."""
    for m in listing(manifest, cell):
        read = reader(m["name"]).read
        if m["source"] == "device_trace" or m["layer"] == "device":
            if m["source"] == "device_trace":
                assert read(dict(run)) is None, f"{m['name']} read a number off the chip"
            read({**run, "trace": NOTHING_TRACED})
            continue
        value = read(dict(run))
        if m["source"] in SPAN_SOURCES:
            assert value is not None and value >= 0, f"{m['name']} lists {cell} and reads {value} on its driver's rehearsal"
        else:  # the host's clock: a tail of a short rehearsal may have too few samples to read
            assert value is None or value >= 0, f"{m['name']} reads {value} on the rehearsal of {cell}'s driver"


def rehearse(manifest: dict, cell: dict, driver_of: str, seed: int, trace: bool, **kw) -> tuple[dict, dict]:
    """`run_cell` on a tiny cell of one driver, read by every metric that lists
    a cell of that driver -> (the result line, the run as the readers saw it)."""
    m = json.loads(json.dumps(manifest))
    theirs = set(cells_of(manifest, driver_of))
    for metric in m["end_to_end"] + m["per_layer"]:
        if theirs & set(metric.get("workloads", ())):
            metric["workloads"].append(cell["name"])
    driver = importlib.import_module(f"benchmark.drivers.{driver_of}")
    seen = {}

    def keeping(*a, **k):
        seen["run"] = real(*a, **k)
        return seen["run"]

    with pytest.MonkeyPatch.context() as patch:
        real = driver.run
        patch.setattr(driver, "run", keeping)
        result = bench_run.run_cell(m, cell, seed, 1.0, trace, require_chip=False, **kw)
    return result, seen["run"]


def with_made_up_metric(manifest: dict, patch: pytest.MonkeyPatch) -> dict:
    """A copy of the manifest with one more `program_span` metric that lists
    every cell, and its reader, which needs no span and reads a constant."""
    module = types.ModuleType(f"benchmark.metrics.{MADE_UP}")
    module.SPANS, module.read = (), lambda run: 1.0
    patch.setitem(sys.modules, module.__name__, module)
    m = json.loads(json.dumps(manifest))
    m["per_layer"].append({"name": MADE_UP, "unit": "ms", "better": "lower", "source": "program_span", "layer": "made up",
                           "moves": "env_steps_per_s", "workloads": [w["name"] for w in m["workloads"]]})
    return m
