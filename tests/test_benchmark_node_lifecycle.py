"""The yardstick's process layer under tier-1's floor (ROADMAP D12): the
cases of ``benchmark/tests/test_node_lifecycle.py`` (no process outlives a
run, and a run ends itself; PR 29 was thrown away for a node left running),
collected here by import. Nothing is copied."""

import importlib.util
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the cases say ``from conftest import BENCH, CHECKOUT`` and mean
# benchmark/tests/conftest.py; here ``conftest`` is tier-1's own, so theirs
# stands in for the length of the import
_tier1 = sys.modules.get("conftest")
sys.modules["conftest"] = _load(
    "benchmark_tests_conftest", os.path.join(BENCH, "tests", "conftest.py"))
try:
    _cases = _load("benchmark_tests_test_node_lifecycle",
                   os.path.join(BENCH, "tests", "test_node_lifecycle.py"))
finally:
    if _tier1 is None:
        del sys.modules["conftest"]
    else:
        sys.modules["conftest"] = _tier1
globals().update({name: obj for name, obj in vars(_cases).items()
                  if not name.startswith("_")})
