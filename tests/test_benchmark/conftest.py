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


@pytest.fixture()
def tiny_cell():
    return {
        "name": "tiny",
        "chips": 1,
        "config": load(os.path.join(DATA, "dv3_tiny.json")),
        "traffic": load(os.path.join(DATA, "tiny_traffic.json")),
    }
