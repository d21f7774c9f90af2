"""The yardstick's host reduction under tier-1's floor (ROADMAP D12): the
cases of ``benchmark/tests/test_host_spans.py`` on host-plane events
written by hand (edge CPU by subtraction on one thread, the role split,
the collector's union and its overlap with device idle), collected here
by import. Nothing is copied. Its traced CPU rehearsal stays outside
tier-1 with the other rehearsals."""

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


# the cases say ``from conftest import BENCH`` and mean
# benchmark/tests/conftest.py; here ``conftest`` is tier-1's own, so theirs
# stands in for the length of the import
_tier1 = sys.modules.get("conftest")
sys.modules["conftest"] = _load(
    "benchmark_tests_conftest", os.path.join(BENCH, "tests", "conftest.py"))
try:
    _cases = _load("benchmark_tests_test_host_spans",
                   os.path.join(BENCH, "tests", "test_host_spans.py"))
finally:
    if _tier1 is None:
        del sys.modules["conftest"]
    else:
        sys.modules["conftest"] = _tier1
globals().update({name: obj for name, obj in vars(_cases).items()
                  if not name.startswith("_")
                  and name != "test_traced_rehearsal_reports_the_host_metrics"})
