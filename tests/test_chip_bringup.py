"""Bring-up plumbing: where compiled programs are cached, the rule that no
launcher serves from the CPU by accident, failures that are journaled
instead of passed over, and ``chip_smoke.py`` walked end to end on the CPU
platform at a tiny size."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.common import flightrec, roofline, runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the placeable compile cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("placed", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR", "in-checkout"])
def test_compile_cache_location(monkeypatch, tmp_path, placed):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.enable_compile_cache() == str(tmp_path)
        # jax reads the variable itself: the code sets no directory
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.enable_compile_cache() == want
        assert [v for n, v in updates
                if n.endswith("compilation_cache_dir")] == [want]
        # a fixed path: the same from any working directory or process
        monkeypatch.chdir(tmp_path)
        assert runtime.compile_cache_dir() == want


def test_one_site_sets_the_cache_directory():
    needle = "jax_compilation_" + "cache_dir"
    sites = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "_export")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if needle in fh.read():
                        sites.append(os.path.relpath(path, REPO))
    assert sites == [os.path.join("elasticsearch_tpu", "common",
                                  "runtime.py")]


# ---------------------------------------------------------------------------
# nothing continues on the CPU unasked
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind, self.id = platform, kind, 0


@pytest.mark.parametrize("env,allow,ok", [
    ("", False, False), ("cpu", False, True), ("", True, True),
    ("tpu,cpu", False, True)])
def test_require_accelerator_on_cpu(monkeypatch, env, allow, ok):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("cpu", "cpu")])
    monkeypatch.setenv("JAX_PLATFORMS", env)
    if ok:
        assert runtime.require_accelerator(allow_cpu=allow)
    else:
        with pytest.raises(SystemExit, match="no accelerator"):
            runtime.require_accelerator(allow_cpu=allow)


def test_require_accelerator_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v5 lite")])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert runtime.require_accelerator()[0].platform == "tpu"


def test_bench_starts_no_child_process():
    with open(os.path.join(REPO, "bench.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"subprocess", "multiprocessing"}


# ---------------------------------------------------------------------------
# ceilings: one sourced table, an unknown device is an error
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_peaks():
    roofline._reset_peak_for_tests()
    yield
    roofline._reset_peak_for_tests()


def test_roofline_table_rows_carry_their_source():
    assert roofline.DEVICE_PEAKS["TPU v5 lite"]["hbm"] == 819.0
    assert "nominal" in roofline.DEVICE_PEAKS["cpu"]["source"]
    assert not any("gpu" in kind.lower() for kind in roofline.DEVICE_PEAKS)
    for row in roofline.DEVICE_PEAKS.values():
        assert row["hbm"] > 0 and row["host_link"] > 0 and row["source"]


def test_roofline_unknown_device_kind_raises(monkeypatch, fresh_peaks):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v9 imaginary")])
    monkeypatch.delenv("ES_TPU_ROOFLINE_BW_GBPS", raising=False)
    with pytest.raises(roofline.UnknownDeviceError,
                       match="TPU v9 imaginary"):
        roofline.peak_bandwidth_gbps()
    with pytest.raises(roofline.UnknownDeviceError):
        roofline.audit("bm25_eager", 1_000_000, 1.0)


def test_roofline_override_names_the_ceiling(monkeypatch, fresh_peaks):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v9 imaginary")])
    monkeypatch.setenv("ES_TPU_ROOFLINE_BW_GBPS", "1234.5")
    assert roofline.peak_bandwidth_gbps() == 1234.5


def test_roofline_known_kinds_resolve(monkeypatch, fresh_peaks):
    monkeypatch.delenv("ES_TPU_ROOFLINE_BW_GBPS", raising=False)
    monkeypatch.delenv("ES_TPU_ROOFLINE_STREAM_GBPS", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v5 lite")])
    assert roofline.peak_bandwidth_gbps() == 819.0
    assert roofline.peak_stream_bandwidth_gbps() == 32.0


def test_device_stats_name_the_device_kind():
    from elasticsearch_tpu.common.telemetry import device_stats_doc
    devs = device_stats_doc()["devices"]
    assert devs and all(d["device_kind"] == jax.devices()[0].device_kind
                        and d["platform"] == "cpu" for d in devs)


# ---------------------------------------------------------------------------
# failures are journaled, not passed over
# ---------------------------------------------------------------------------


def _events(type_):
    return list(flightrec.DEFAULT.events(type_=type_))


def test_warmup_failure_is_counted_and_journaled():
    from elasticsearch_tpu.search.microbatch import PlaneMicroBatcher

    class RefusedPlane:
        """A device plane (no host twin) whose every shape fails."""
        _host_csr = None

        def serving_shapes(self, k_buckets, max_b):
            return [(1 << i, (8, 1024, kb, False, True, None))
                    for i in range(max_b.bit_length()) for kb in k_buckets]

        def warm_shape(self, shape):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory in hbm")

    before = len(_events("warmup_failed"))
    b = PlaneMicroBatcher(RefusedPlane())
    b.warmup(sync=True)
    assert b.warmup_failures == 1 and b.warmed_shapes == 0
    assert b.stats_doc()["warmup_failures"] == 1
    ev = _events("warmup_failed")
    assert len(ev) == before + 1
    attrs = ev[-1].get("attrs", ev[-1])
    assert "RESOURCE_EXHAUSTED" in attrs["error"]
    assert attrs["shapes_warmed"] == 0 and attrs["shapes_planned"] == 7


def test_background_repack_failure_is_journaled(monkeypatch):
    from elasticsearch_tpu.search.plane_route import ServingPlaneCache
    cache = ServingPlaneCache()
    cache.repack_mode = "sync"

    def boom(*a, **kw):
        raise MemoryError("pack failed")

    monkeypatch.setattr(cache, "_build_text_generation", boom)
    before = len(_events("plane_repack_failed"))
    cache._schedule_repack("text", "body", [], None, "threshold")
    ev = _events("plane_repack_failed")
    assert len(ev) == before + 1
    attrs = ev[-1].get("attrs", ev[-1])
    assert attrs["field"] == "body" and "pack failed" in attrs["error"]
    # and the slot is free again: the next refresh retries
    assert not cache._repacking
    cache.release()


# ---------------------------------------------------------------------------
# kernel repairs the TPU compiler asked for
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile_slots", [1 << 30, 3 * 64, 64, 1],
                         ids=["plain", "chunk=3", "chunk=1", "floor"])
def test_vmap_queries_sub_batches_change_nothing(tile_slots):
    """8 queries scored at once, 3 + 3 + 2, and one by one: identical."""
    from elasticsearch_tpu.ops.sorted_merge import (bm25_topk_merge_body,
                                                    vmap_queries)
    rng = np.random.RandomState(3)
    n_pad, Qn, L, k = 256, 4, 16, 5
    runs = [np.sort(rng.choice(n_pad, 16, replace=False)) for _ in range(12)]
    docs = np.concatenate(runs + [np.full(L, n_pad)]).astype(np.int32)
    imps = rng.rand(docs.shape[0]).astype(np.float32)
    starts = (rng.randint(0, 12, (8, Qn)) * 16).astype(np.int32)
    lengths = rng.randint(1, 17, (8, Qn)).astype(np.int32)
    idfw = rng.rand(8, Qn).astype(np.float32)

    def per_query(st, ln, iw):
        return bm25_topk_merge_body(jnp.asarray(docs), jnp.asarray(imps),
                                    st, ln, iw, n_pad=n_pad, L=L, k=k,
                                    with_count=True)

    args = (jnp.asarray(starts), jnp.asarray(lengths), jnp.asarray(idfw))
    want = jax.vmap(per_query)(*args)
    got = vmap_queries(per_query, args, slots_per_query=Qn * L,
                       tile_slots=tile_slots)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_knn_profile_reports_its_plane_dispatch(tmp_path):
    """A knn-only request has no lexical dispatch: its kNN plane dispatch
    is reported under ``serving_knn`` (chip_smoke.py reads the device
    verdict there)."""
    from elasticsearch_tpu.node.indices_service import IndicesService
    from elasticsearch_tpu.rest.api import RestAPI
    api = RestAPI(IndicesService(str(tmp_path)))

    def call(method, path, body=None):
        raw = json.dumps(body).encode() if isinstance(body, dict) \
            else (body or b"")
        status, _ct, out = api.handle(
            method, path, "", raw,
            headers={"content-type": "application/json"})
        assert status == 200, out
        return json.loads(out)

    try:
        call("PUT", "/v", {"mappings": {"properties": {
            "vec": {"type": "dense_vector", "dims": 4}}}})
        rng = np.random.RandomState(0)
        lines = []
        for i in range(64):
            lines.append(json.dumps({"index": {"_index": "v",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"vec": rng.randn(4).tolist()}))
        call("POST", "/_bulk", ("\n".join(lines) + "\n").encode())
        call("POST", "/v/_refresh")
        out = call("POST", "/v/_search", {
            "knn": {"field": "vec", "query_vector": [1, 0, 0, 0], "k": 3,
                    "num_candidates": 10}, "profile": True})
        sec = out["profile"]["shards"][0]["serving_knn"]
        assert len(sec) == 1 and sec[0]["compile_cache"] == "host"
        assert sec[0]["batch_size"] == 1 and "dispatch" in sec[0]["stages_ms"]
    finally:
        api.close()


# ---------------------------------------------------------------------------
# chip_smoke.py, rehearsed
# ---------------------------------------------------------------------------


def test_chip_smoke_cpu_rehearsal_walks_every_phase(tmp_path):
    """On the CPU platform at a tiny size the smoke reaches the end of its
    last phase and then fails on the platform alone."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               TMPDIR=str(tmp_path),
               ES_TPU_LEX_PRUNE_MIN_DOCS="2048",
               ES_TPU_KNN_IVF_MIN_DOCS="1024")
    os.makedirs(env["JAX_COMPILATION_CACHE_DIR"])
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--served-docs", "8192", "--served-dim", "16",
         "--full-docs", "16384", "--knn-docs", "20000", "--knn-dim", "16",
         "--knn-k", "10", "--batch", "8", "--waves", "1"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=900)
    lines = p.stdout.strip().splitlines()
    tail = "\n".join(lines[-25:]) + "\n" + p.stderr[-2000:]
    assert p.returncode != 0, tail
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}, tail
    assert any(ln.startswith("== phase served done") for ln in lines), tail
    assert any(ln.startswith("== phase full done") for ln in lines), tail
    assert any("0 host-served" in ln for ln in lines), tail
    assert not any(ln.startswith("FAILED") for ln in lines), tail


def test_chip_smoke_fails_without_the_program(tmp_path):
    """Alone in a directory the script exits non-zero and prints no
    result."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
