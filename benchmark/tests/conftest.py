"""``pytest benchmark/tests``: run by hand, outside tier-1. Everything here
runs on the CPU platform; nothing is a device number."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


@pytest.fixture(scope="session")
def rehearsal_manifest():
    with open(os.path.join(BENCH, "tests", "rehearsal",
                           "BENCHMARK.rehearsal.json")) as f:
        return json.load(f)
