"""The yardstick's own loop under tier-1's floor (ROADMAP D12): the cases of
``benchmark/tests/test_loadgen.py`` (the closed loop against a stub HTTP
server on loopback and the client-side readers by hand; no JAX, no node),
collected here by import. Nothing is copied: a change to the load generator
that breaks its tests now costs the PR its floor."""

import importlib.util
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
# the cases import ``harness`` the way benchmark/tests/conftest.py arranges
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_loadgen",
    os.path.join(BENCH, "tests", "test_loadgen.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)
# tests and the fixtures they name alike
globals().update({name: obj for name, obj in vars(_cases).items()
                  if not name.startswith("_")})
