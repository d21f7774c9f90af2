"""The CPU rehearsal: ``run.py`` end to end on the rehearsal-only cells
(everything but the no-TPU failure), the no-TPU failure itself, and a run
with the timed path broken underneath, which has to come out not correct.
Nothing here is a device number."""

import argparse
import os
import subprocess
import sys

import pytest

from conftest import BENCH, CHECKOUT
import run as bench_run


def _args(workload, seed, trace=0, seconds=2.0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, control=0)


@pytest.mark.parametrize("workload", ["tiny_knn.knn_c4",
                                      "tiny_match.match_c4"])
def test_end_to_end_run(rehearsal_manifest, workload):
    r = bench_run.run(_args(workload, 2147483999), rehearsal=True,
                      manifest=rehearsal_manifest)
    assert list(r)[-1] == "compared"
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 20
    assert set(r["metrics"]) == {"search_qps", "search_p50_ms",
                                 "search_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"      # a rehearsal, no chip


def test_traced_run_reports_per_layer_metrics(rehearsal_manifest):
    r = bench_run.run(_args("tiny_knn.knn_c4", 5, trace=1), rehearsal=True,
                      manifest=rehearsal_manifest)
    assert r["correct"] is True
    # counters read from outside; no device plane on the CPU platform, so
    # the trace readers find nothing and report nothing (never a 0)
    assert {"batcher.mean_batch", "batcher.queue_ms", "planes.prep_ms",
            "planes.dispatch_ms", "planes.compiles_in_window"} \
        <= set(r["metrics"])
    assert "kernels.knn_exact_roofline" not in r["metrics"]
    assert "device.idle_share" not in r["metrics"]


def test_altered_answer_is_not_correct(rehearsal_manifest):
    r = bench_run.run(
        _args("tiny_knn.knn_c4", 6), rehearsal=True,
        manifest=rehearsal_manifest,
        node_launcher=os.path.join(BENCH, "tests", "broken_node.py"))
    assert r["correct"] is False
    c = r["compared"]
    assert c["gap"]["value"] > 100 * c["gap"]["limit"]


def test_no_tpu_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "glove100_knn.exact_c64", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
    assert "no TPU" in p.stderr
