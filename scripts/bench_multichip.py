#!/usr/bin/env python
"""Multi-device serving measurement at ONE VIRTUAL-device count.

A virtual-CPU-mesh tool, not a chip run: the devices are XLA:CPU devices
in one process, so its parity, per-device-bytes and compile-count gates
carry over to a TPU mesh and its timings do not (``python chip_smoke.py
--chips 4`` is the run on four real chips).

One process per device count: the XLA host-platform device count is
fixed per process (``--xla_force_host_platform_device_count`` is read at
backend init), so ``__graft_entry__.dryrun_multichip`` runs this script
once per point of its 1/2/4/8 sweep and compares the JSON docs the runs
print. Everything here runs the SHARDED DEVICE serving path — the
host-native CPU scorers are disabled (``ES_TPU_PLANE_HOST_SERVE=0``)
because they bypass the mesh entirely, and the sweep exists to measure
the mesh.

The corpus is a FIXED 8-segment synthetic build (seeded), identical at
every device count, so per-query results must be bit-identical across
mesh shapes (the kernels partition shards over devices but never change
per-shard scoring or the (score desc, doc asc) merge order) and the
parent asserts exact equality against the 1-device run. Reported
per-device corpus bytes are MEASURED from the live device buffers
(``addressable_shards``), not derived from the mesh shape.

Usage:  python scripts/bench_multichip.py --devices 4 [--replicas 2]
Prints one JSON doc on stdout (last line).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# -- corpus/workload constants: identical at every device count ------------
# Sized so the dispatch is corpus-bandwidth-bound (BM25S's regime — the
# scan streams ~n_pad accumulator + postings bytes per shard): small
# corpora measure XLA's per-device dispatch overhead instead of the
# sharding, and multi-device goes NEGATIVE there. At 32k docs/segment
# the 8-device dispatch is ~1.45x the 1-device rate on this backend.
N_SEGMENTS = 8          # divides every swept device count (1/2/4/8)
DOCS_PER_SEGMENT = 32768
VOCAB = 2048
AVG_DL = 16
KNN_DOCS_PER_SEGMENT = 2048
KNN_DIM = 32
K = 10
EVAL_B = 16             # parity batch (one fixed plane.search call)
N_CLIENTS = 8           # throughput window client threads
PER_CLIENT = 24


def _force_devices(n: int) -> None:
    """Pin the virtual CPU platform BEFORE jax initializes a backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n}"])
    # the whole point is the sharded device path — never the host scorers
    os.environ["ES_TPU_PLANE_HOST_SERVE"] = "0"


def _eval_queries(rng, plane_vocab: int):
    """Fixed bag-of-terms eval batch: mixed run lengths, some repeated
    terms, all within one ladder rung family."""
    qs = []
    for i in range(EVAL_B):
        n_terms = 2 + (i % 3)
        qs.append([f"t{int(rng.randint(8, plane_vocab // 4))}"
                   for _ in range(n_terms)])
    return qs


def _measured_device_bytes(arrays) -> int:
    """Max per-device resident bytes over the given jax arrays, read from
    the live buffers — the ground truth the accessor estimates."""
    per_dev: dict = {}
    for a in arrays:
        if a is None:
            continue
        for s in a.addressable_shards:
            did = int(s.device.id)
            per_dev[did] = per_dev.get(did, 0) + int(s.data.nbytes)
    return max(per_dev.values()) if per_dev else 0


def _compiles_total(tm) -> int:
    doc = tm.DEFAULT.metrics_doc().get("es_xla_compiles_total")
    if not doc:
        return 0
    return int(sum(s["value"] for s in doc["series"]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--replicas", type=int, default=1)
    args = ap.parse_args()
    n_dev = int(args.devices)
    n_repl = max(int(args.replicas), 1)
    if n_dev % n_repl:
        raise SystemExit(f"--replicas {n_repl} must divide --devices {n_dev}")
    _force_devices(n_dev)
    # the serving cache default (mesh_from_env) is what's under test:
    # drive it through the same env knobs production uses
    os.environ["ES_TPU_MESH_REPLICAS"] = str(n_repl)
    os.environ["ES_TPU_MESH_SHARDS"] = str(n_dev // n_repl)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    import jax

    if len(jax.devices()) < n_dev or jax.devices()[0].platform != "cpu":
        raise SystemExit(
            f"needed {n_dev} virtual CPU devices, jax sees "
            f"{len(jax.devices())} {jax.devices()[0].platform}")

    from elasticsearch_tpu.common import telemetry as tm
    from elasticsearch_tpu.parallel import (DistributedKnnPlane,
                                            DistributedSearchPlane,
                                            mesh_from_env)
    from elasticsearch_tpu.parallel.mesh import AXIS_REPLICA, AXIS_SHARD
    from elasticsearch_tpu.search.microbatch import (KnnPlaneMicroBatcher,
                                                     PlaneMicroBatcher)
    from elasticsearch_tpu.utils.synth import synthetic_csr_corpus

    mesh = mesh_from_env()
    s_dev = int(mesh.shape[AXIS_SHARD])
    r_dev = int(mesh.shape[AXIS_REPLICA])

    # -- pack: fixed corpus, device-count-independent -----------------------
    rng = np.random.RandomState(1234)
    shards = []
    for si in range(N_SEGMENTS):
        sh = synthetic_csr_corpus(rng, DOCS_PER_SEGMENT, VOCAB, AVG_DL,
                                  zipf_s=1.2)
        sh["term_ids"] = {f"t{t}": t for t in range(VOCAB)}
        shards.append(sh)
    t0 = time.perf_counter()
    plane = DistributedSearchPlane(mesh, shards, field="body")
    pack_ms = (time.perf_counter() - t0) * 1e3
    assert plane._host_csr is None, \
        "host serve must be off: the sweep measures the device path"

    kvecs = [dict(vectors=rng.randn(KNN_DOCS_PER_SEGMENT,
                                    KNN_DIM).astype(np.float32))
             for _ in range(N_SEGMENTS)]
    knn = DistributedKnnPlane(mesh, kvecs, similarity="dot_product")
    assert knn._host_pack is None

    # -- warm the serving lattice (the batcher's own warmup — what the
    # serving cache runs at plane build) -----------------------------------
    batcher = PlaneMicroBatcher(plane)
    t0 = time.perf_counter()
    batcher.warmup(ks=(K,), max_b=N_CLIENTS, sync=True)
    kbatcher = KnnPlaneMicroBatcher(knn)
    kbatcher.warmup(ks=(K,), max_b=N_CLIENTS, sync=True)
    warm_ms = (time.perf_counter() - t0) * 1e3

    # -- parity payload: one fixed eval dispatch per plane kind -------------
    eval_rng = np.random.RandomState(99)
    equeries = _eval_queries(eval_rng, VOCAB)
    vals, hits, totals = plane.search(equeries, k=K, with_totals=True)
    text_results = {
        "vals": [[float(v) for v in row] for row in np.asarray(vals)],
        "hits": [[[int(s), int(d)] for (s, d) in row] for row in hits],
        "totals": [int(t) for t in totals],
    }
    qv = eval_rng.randn(EVAL_B, KNN_DIM).astype(np.float32)
    kvals, khits = knn.search(qv, k=K)
    knn_results = {
        "vals": [[float(v) for v in row] for row in np.asarray(kvals)],
        "hits": [[[int(s), int(d)] for (s, d) in row] for row in khits],
    }

    # -- throughput window: concurrent clients through the micro-batcher ----
    # (one warm round first so every arrival shape the window produces is
    # already compiled; then assert zero steady-state compiles)
    qpool = [[f"t{int(eval_rng.randint(32, VOCAB // 4))}"
              for _ in range(2)] for _ in range(256)]

    def run_window(per: int):
        lat, errs = [], []
        lock = threading.Lock()

        def client(tid):
            try:
                for j in range(per):
                    q = qpool[(tid * per + j) % len(qpool)]
                    t0 = time.perf_counter()
                    batcher.search(q, K)
                    dt = time.perf_counter() - t0
                    with lock:
                        lat.append(dt)
            except BaseException as e:      # noqa: BLE001
                with lock:
                    errs.append(repr(e))
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise SystemExit(f"serving window errors: {errs[:3]}")
        a = np.asarray(lat)
        return {"qps": round(len(a) / wall, 1),
                "p50_ms": round(float(np.percentile(a, 50) * 1e3), 2),
                "p99_ms": round(float(np.percentile(a, 99) * 1e3), 2),
                "n": int(len(a))}

    run_window(4)                      # warm round (arrival-shape coverage)
    c0 = _compiles_total(tm)
    # best-of-2 steady-state windows: one scheduler hiccup on a shared
    # CPU box must not fail the cross-device-count throughput gate
    w1 = run_window(PER_CLIENT)
    w2 = run_window(PER_CLIENT)
    window = w1 if w1["qps"] >= w2["qps"] else w2
    steady_compiles = _compiles_total(tm) - c0

    # -- PAIRED dispatch-wall ratio vs a 1x1 plane in THIS process ----------
    # Absolute qps drifts +-40% over the minutes a sweep takes (container
    # CPU throttling), swamping any cross-process device-count
    # comparison; a same-process back-to-back measurement of the mesh
    # plane against a fresh 1x1-mesh plane over the SAME corpus and
    # query batch cancels the drift — the ratio is what the sweep's
    # throughput gate judges. Interleaved A/B/A/B reps + median defend
    # against drift WITHIN the paired window too.
    def _dispatch_ms(p, reps=15):
        p.search(equeries, k=K)            # compile + first dispatch
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            p.search(equeries, k=K)
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    # (the ref plane's plain make_search_mesh build below does NOT touch
    # the es_mesh_devices gauge — only serving-mesh owners write it)
    mdoc = tm.DEFAULT.metrics_doc()
    mesh_gauge = {s["labels"]["state"]: int(s["value"])
                  for s in mdoc.get("es_mesh_devices",
                                    {}).get("series", [])}
    from elasticsearch_tpu.parallel import make_search_mesh
    ref_plane = DistributedSearchPlane(
        make_search_mesh(n_shards=1, n_replicas=1,
                         devices=jax.devices()[:1]),
        shards, field="body")
    _dispatch_ms(ref_plane, reps=1)        # compile before interleaving
    mesh_ms, ref_ms = [], []
    for _ in range(4):
        mesh_ms += _dispatch_ms(plane, reps=4)
        ref_ms += _dispatch_ms(ref_plane, reps=4)
    mesh_med = float(np.median(mesh_ms))
    ref_med = float(np.median(ref_ms))
    paired = {"mesh_ms_per_batch": round(mesh_med, 2),
              "ref1x1_ms_per_batch": round(ref_med, 2),
              "ratio": round(mesh_med / max(ref_med, 1e-9), 3)}

    # -- per-device resident corpus bytes: measured from live buffers ------
    text_dev_bytes = _measured_device_bytes(
        [plane.docs_dev, plane.impacts_dev, plane.dense_dev])
    kd = knn._device_arrays()
    knn_dev_bytes = _measured_device_bytes(list(kd))

    out = {
        "devices": n_dev,
        "mesh": f"{r_dev}x{s_dev}",
        "mesh_devices": mesh_gauge,
        "pack_ms": round(pack_ms, 1),
        "warmup_ms": round(warm_ms, 1),
        "steady_compiles": int(steady_compiles),
        "serving": window,
        "paired": paired,
        "text": {"results": text_results,
                 "per_device_corpus_bytes": int(text_dev_bytes),
                 "accessor_per_device_bytes":
                     int(plane.device_corpus_bytes()),
                 "docs": int(plane.n_docs_total)},
        "knn": {"results": knn_results,
                "per_device_corpus_bytes": int(knn_dev_bytes),
                "accessor_per_device_bytes":
                    int(knn.device_corpus_bytes()),
                "docs": int(knn.n_docs_total)},
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
