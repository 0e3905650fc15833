import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def manifest():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(params=["as_committed", "with_a_made_up_metric"])
def any_manifest(request, manifest, monkeypatch):
    """The manifest, and a copy with a made-up span metric that lists every
    cell: what holds for one holds for the other, so no test counts or lists
    the metrics a cell has."""
    if request.param == "as_committed":
        return manifest
    from .keyed import with_made_up_metric

    return with_made_up_metric(manifest, monkeypatch)


@pytest.fixture()
def tiny_cell():
    return {
        "name": "tiny",
        "chips": 1,
        "config": load(os.path.join(DATA, "dv3_tiny.json")),
        "traffic": load(os.path.join(DATA, "tiny_traffic.json")),
    }
