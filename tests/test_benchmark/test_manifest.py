"""BENCHMARK.json against the contract's letter, and against the files it names."""

import importlib
import os
import re

import pytest

from .conftest import ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def every_name(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            yield entry["name"]
    for w in manifest["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in manifest["configs"]:
        yield from c["reduced"]


def test_names_and_units_use_only_the_allowed_characters(manifest):
    for name in every_name(manifest):
        assert NAME.match(name), name
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert 1 <= manifest["run_seconds"] <= 51


def test_no_name_is_used_twice_and_every_reference_resolves(manifest):
    for group in ("configs", "workloads"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    assert {w["config"] for w in manifest["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_file_the_manifest_names_is_under_paths(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        config = load(os.path.join(ROOT, c["file"]))
        assert config["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert config[key] != config["published"][key]
        assert not set(c["reduced"]) & set(config["args"])  # no width, rate or horizon is cut
        importlib.import_module(f"benchmark.drivers.{config['driver']}")
        importlib.import_module(f"benchmark.reference.{config['reference']}")
    for w in manifest["workloads"]:
        traffic = load(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert traffic["chips"] == w["chips"]


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_of_its_own(manifest, group):
    for m in manifest[group]:
        module = importlib.import_module(f"benchmark.metrics.{m['name'].replace('.', '__')}")
        assert callable(module.read)


def test_run_py_names_no_cell_configuration_or_metric(manifest):
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        text = f.read()
    for name in every_name(manifest):
        if name not in ("setup_s",):  # the one metric the contract itself names
            assert name not in text, name
