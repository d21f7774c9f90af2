"""The reduction from a profiler trace to numbers: on a small recorded
trace of the kNN cell (``data/knn_trace_planes.json``, one TPU v5 lite,
PR 24) and on hand-made cases."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH
from harness import work, xplane


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "data",
                           "knn_trace_planes.json")) as f:
        return json.load(f)["planes"]


def test_recorded_trace(recorded):
    s = xplane.summarize(recorded)
    dev, = s["devices"]
    assert dev["plane"] == "/device:TPU:0"
    # four executions of the step program, 57 ms each; the per-request
    # programs beside it take a tenth of that together
    assert dev["modules"]["jit_body"]["count"] == 4
    assert dev["modules"]["jit_body"]["seconds"] == pytest.approx(
        0.22830488, rel=1e-9)
    name, count, seconds = xplane.top_module(s)
    assert (name, count) == ("jit_body", 4.0)
    assert xplane.step_ms(s) == pytest.approx(57.07622, rel=1e-6)
    # busy is a union: the ops nest (a while spans its body), so their
    # durations add up to more than the device was busy
    ops = next(ln["events"] for ln in recorded[0]["lines"]
               if ln["name"] == "XLA Ops")
    assert sum(e[2] for e in ops) / 1e9 == pytest.approx(0.418021848)
    assert dev["busy_s"] == pytest.approx(0.254273859, rel=1e-9)
    assert sum(t for _n, t in xplane._self_times(ops).items()) == \
        pytest.approx(dev["busy_s"], rel=1e-6)
    assert dev["busy_s"] <= (dev["last_ns"] - dev["first_ns"]) / 1e9
    top_ops = [n.split(" ")[0] for n, _t in dev["ops"][:3]]
    assert "%sort.12" in top_ops and "%reshape.18" in top_ops


def test_union_and_self_time():
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(7)", 0, 100],
                                           ["jit_step(9)", 200, 100],
                                           ["jit_other(1)", 150, 10]]},
        {"name": "XLA Ops", "events": [["while", 0, 100], ["a", 10, 30],
                                       ["b", 50, 40], ["c", 150, 10],
                                       ["a", 200, 100]]}]}
    dev = xplane.summarize_plane(plane)
    # busy 0..100, 150..160, 200..300
    assert dev["busy_s"] == pytest.approx(210e-9)
    assert (dev["first_ns"], dev["last_ns"]) == (0, 300)
    assert dev["modules"]["jit_step"] == {
        "count": 2, "seconds": pytest.approx(200e-9)}
    own = dict(dev["ops"])
    assert own["while"] == pytest.approx(30e-9)      # 100 - 30 - 40
    assert own["a"] == pytest.approx(130e-9)


def test_read_planes_reads_a_profiler_file(tmp_path):
    """The one step that needs JAX, in a process of its own as run.py
    starts it: a CPU trace has no device plane, and says so."""
    code = (
        "import jax, jax.numpy as jnp\n"
        f"jax.profiler.start_trace({str(tmp_path)!r})\n"
        "jnp.ones((64, 64)).sum().block_until_ready()\n"
        "jax.profiler.stop_trace()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    pb = xplane.find_xplane(str(tmp_path))
    assert pb is not None
    out = tmp_path / "summary.json"
    subprocess.run([sys.executable,
                    os.path.join(BENCH, "harness", "xplane.py"), pb,
                    str(out)], env=env, check=True, timeout=300)
    assert json.load(open(out)) == {"devices": []}


def test_work_and_peaks():
    peaks = work.peaks_for("TPU v5 lite")
    assert peaks == {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0,
                     "int8_top_per_s": 393.0, "hbm_gb": 16.0}
    with pytest.raises(work.UnknownDevice):
        work.peaks_for("cpu")
    w = work.knn_exact(1183514, 100, 4, 64, 100)
    assert w["bytes"] == 1183514 * 400 + 64 * 400 + 64 * 800
    assert w["flops"] == 2.0 * 64 * 1183514 * 100
    t, bound = work.least_seconds(w, peaks)
    assert bound == "memory" and t == pytest.approx(w["bytes"] / 819e9)


def test_roofline_readers_on_the_recorded_trace(recorded):
    from harness.manifest import load_module
    said = []
    ctx = {"trace": xplane.summarize(recorded), "say": said.append,
           "device": {"kind": "TPU v5 lite"},
           "dispatch_groups": [[object()] * 8] * 4,
           "config": {"data": {"params": {"docs": 1183514, "dims": 100}}}}
    share = load_module("readers", "knn_exact_roofline").read(
        ctx, {"stored_bytes": 4, "k": 100})
    least_ms = (1183514 * 400 + 8 * 400 + 8 * 800) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 57.07622, rel=1e-6)
    assert "bound by memory" in said[0]
    # nothing to read: no number, never a 0
    ctx["trace"] = {"devices": []}
    assert load_module("readers", "knn_exact_roofline").read(
        ctx, {"stored_bytes": 4, "k": 100}) is None
    assert load_module("readers", "idle_share").read(ctx, {}) is None
