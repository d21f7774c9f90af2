#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the served search path still
starts, serves and answers correctly on the TPU.

    python chip_smoke.py             # one chip: served path, then full width
    python chip_smoke.py --chips 4   # the four-chip serving mesh, nothing else

Process model: THIS process never imports JAX. A chip belongs to one
process at a time, so every phase is a child that holds the chip alone and
is gone before the next starts:

1. *served* — ``python -m elasticsearch_tpu.cli.node`` (IndicesService +
   RestAPI behind HttpServer, the way users start a node), driven from here
   over real HTTP: create an index (text + dense_vector), ``_bulk`` a seeded
   Zipf corpus past both tier thresholds, refresh, then match / bool / knn /
   hybrid rrf / terms+percentiles requests, some of them concurrent so the
   micro-batcher co-batches. Every answer is checked from its own
   ``profile`` (a device dispatch, never a host twin; the route the body
   should take), against ``_count``, and against a numpy oracle over the
   docs generated here.
2. *full width* — ``chip_smoke.py --phase full``: the deployment
   BASELINE.json names first (match BM25, one shard, k=10, 2^23 docs) packed
   the way ``ServingPlaneCache`` packs it (dense tier and block-max tier)
   and served through ``PlaneMicroBatcher`` at the serving shapes (B=64,
   Q floor 8, ladder L, k bucket 16) by the tiered and the pruned step,
   checked against ``bench.cpu_bm25_search``; then the GloVe-width kNN plane
   (1.2M x 100, cosine, k=100) through ``KnnPlaneMicroBatcher``, exact and
   IVF, checked against a numpy oracle.

``--chips 4`` runs ``--phase multichip`` and no other phase: the 2^23 corpus
in four shards on ``mesh_from_env()``'s default 1x4 mesh and once as 2x2
(replicas x shards), bit-identical to a 1x1 plane on ``jax.devices()[:1]``
in the same process, per-device resident bytes read from
``addressable_shards``, and 16 client threads through a batcher whose two
dispatchers run 1x4 programs concurrently, under a wall-clock limit (a hang
fails, it does not stall).

The platform is read once, from the first child, and alone decides
``"ok": false`` for a device that is not a TPU; on such a device (a CPU
rehearsal at sizes given on the command line) every phase still runs. Any
failing phase ends the run with ``"ok": false``. Children are told
``ES_TPU_PLANE_HOST_SERVE=0``: nothing on this path may serve from a host
twin, whatever the platform. No background warm-up lattice runs (the node
gets ``ES_TPU_SERVING_WARMUP=0``): each shape the script sends compiles
synchronously in the request or wave that first sends it, and is counted;
a shape that fails to compile fails the smoke.

The last line of stdout is the result and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

VOCAB = 1 << 16
AVG_DL = 32
ZIPF_S = 1.2
K1, B_ = 1.2, 0.75

#: (relative, absolute) score tolerances, the kernels' own tests':
#: bf16 dense-tier impacts (tests/test_dist_search.py), f32 sparse paths,
#: the exact kNN scan (tests/test_knn_blocked.py)
TOL_DENSE = 1e-2
TOL_SPARSE = 1e-4
TOL_KNN = 2e-5
#: IVF recall floor at the serving defaults on a clustered corpus
#: (tests/test_knn_ivf.py asks 0.95 at k=10 on a far smaller corpus)
IVF_RECALL_MIN = 0.9


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# data shared by the phases (numpy only)
# ---------------------------------------------------------------------------


def clustered_vectors(rng, n: int, dim: int, n_centers: int):
    """Clustered synthetic embeddings (bench.py's IVF corpus: a mixture of
    Gaussians — iid rows have no neighbourhoods for any index), on a 1/128
    grid so the JSON text, the engine's f32 and the oracle's f32 are the
    same numbers."""
    centers = rng.randn(n_centers, dim).astype(np.float32)
    out = np.empty((n, dim), np.float32)
    chunk = 1 << 17
    for lo in range(0, n, chunk):
        m = min(chunk, n - lo)
        out[lo: lo + m] = centers[rng.randint(0, n_centers, m)] \
            + 0.35 * rng.randn(m, dim).astype(np.float32)
    return np.round(out * 128.0) / np.float32(128.0)


def query_vectors(rng, corpus: np.ndarray, n: int):
    """Perturbed corpus rows (queries live near the data), same grid."""
    q = corpus[rng.randint(0, corpus.shape[0], n)] \
        + 0.15 * rng.randn(n, corpus.shape[1]).astype(np.float32)
    return np.round(q * 128.0) / np.float32(128.0)


def knn_oracle(unit: np.ndarray, q: np.ndarray, k: int):
    """Exact cosine top-k in numpy: (ids i64[k], cos f32[k]), score
    descending, id ascending among equals."""
    qq = q / max(float(np.linalg.norm(q)), 1e-12)
    cos = unit @ qq.astype(np.float32)
    top = np.argpartition(-cos, k - 1)[:k] if cos.shape[0] > k \
        else np.arange(cos.shape[0])
    top = top[np.lexsort((top, -cos[top]))]
    return top, cos[top], cos


def unit_rows(vecs: np.ndarray) -> np.ndarray:
    return vecs / np.maximum(
        np.linalg.norm(vecs, axis=1, keepdims=True), 1e-12)


def agree(got_ids, got_scores, ref_ids, ref_scores, *, tol: float,
          what: str, true_score=None, slack: int = 1) -> None:
    """Top-k agreement as the kernels' own tests ask it: per-rank scores
    within ``tol`` (relative, floored at 1.0), and the same documents up
    to what that tolerance cannot order. With ``true_score(doc)`` (the
    reference's own score of a returned doc) every returned doc must
    belong in the reference top-k within ``tol`` — at 2^23 docs the k-th
    and (k+1)-th scores sit closer than bf16 impacts resolve; without it,
    all but ``slack`` ids must be common."""
    check(len(got_ids) == len(ref_ids),
          f"{what}: {len(got_ids)} hits, reference has {len(ref_ids)}")
    for r, (a, b) in enumerate(zip(got_scores, ref_scores)):
        check(abs(a - b) <= tol * max(1.0, abs(b)),
              f"{what}: rank {r} score {a} vs reference {b}")
    if true_score is None:
        common = len(set(got_ids) & set(ref_ids))
        check(common >= len(ref_ids) - slack,
              f"{what}: only {common}/{len(ref_ids)} ids in common: "
              f"{list(got_ids)} vs {list(ref_ids)}")
        return
    floor = ref_scores[-1] - tol * max(1.0, abs(ref_scores[-1]))
    for d, s in zip(got_ids, got_scores):
        t = true_score(d)
        check(t >= floor and abs(s - t) <= tol * max(1.0, abs(t)),
              f"{what}: doc {d} served with score {s}; the reference "
              f"scores it {t}, its k-th is {ref_scores[-1]}")


# ---------------------------------------------------------------------------
# phase 1 — the served path (parent side: HTTP client + numpy oracle)
# ---------------------------------------------------------------------------


class ServedCorpus:
    """The docs this script bulks, and the numpy oracle over them."""

    def __init__(self, seed: int, n_docs: int, vocab: int, dim: int):
        rng = np.random.RandomState(seed)
        self.n = n_docs
        self.lens = np.maximum(1, rng.poisson(AVG_DL, n_docs))
        self.tokens = np.minimum(
            rng.zipf(ZIPF_S, int(self.lens.sum())) - 1,
            vocab - 1).astype(np.int64)
        doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), self.lens)
        self.bounds = np.zeros(n_docs + 1, np.int64)
        np.cumsum(self.lens, out=self.bounds[1:])
        order = np.argsort(self.tokens, kind="stable")
        self._by_term_docs = doc_of[order]
        self._term_lo = np.searchsorted(self.tokens[order],
                                        np.arange(vocab + 1))
        self.tok_count = np.diff(self._term_lo)
        self.avgdl = float(self.lens.mean())
        self.vecs = clustered_vectors(rng, n_docs, dim,
                                      max(n_docs // 512, 4))
        self.unit = unit_rows(self.vecs)
        self.tags = np.arange(n_docs) % 7
        self.vals = (np.arange(n_docs) % 100).astype(np.float64)
        self.rng = rng

    def doc_source(self, i: int) -> dict:
        toks = self.tokens[self.bounds[i]: self.bounds[i + 1]]
        return {"body": " ".join(f"t{t}" for t in toks),
                "vec": self.vecs[i].tolist(),
                "tag": f"g{self.tags[i]}", "val": float(self.vals[i])}

    def tf(self, term: int) -> np.ndarray:
        lo, hi = self._term_lo[term], self._term_lo[term + 1]
        return np.bincount(self._by_term_docs[lo:hi],
                           minlength=self.n).astype(np.float64)

    def bm25(self, terms):
        """(scores f64[N], matched bool[N]) of a bag of term ids."""
        scores = np.zeros(self.n)
        matched = np.zeros(self.n, bool)
        for t in set(terms):
            tf = self.tf(t)
            df = int(np.count_nonzero(tf))
            if not df:
                continue
            idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            norm = tf + K1 * (1.0 - B_ + B_ * self.lens / self.avgdl)
            scores += terms.count(t) * idf * (K1 + 1.0) * tf / norm
            matched |= tf > 0
        return scores, matched

    @staticmethod
    def topk(scores, pool_mask, k: int):
        ids = np.flatnonzero(pool_mask)
        ids = ids[np.lexsort((ids, -scores[ids]))][:k]
        return ids, scores[ids]

    def terms_with_df(self, lo: int, hi: int, n: int):
        """``n`` distinct term ids with lo <= token count < hi."""
        pool = np.flatnonzero((self.tok_count >= lo) & (self.tok_count < hi))
        check(pool.size >= n, f"only {pool.size} terms with count in "
                              f"[{lo}, {hi})")
        return [int(t) for t in self.rng.choice(pool, n, replace=False)]


class Http:
    """One keep-alive connection to the node (one per client thread)."""

    def __init__(self, port: int):
        self.port = port
        self.conn = None

    def call(self, method: str, path: str, body=None, timeout: float = 900):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        if isinstance(body, str):
            body = body.encode()
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout)
            elif self.conn.sock is not None:
                self.conn.sock.settimeout(timeout)
            try:
                self.conn.request(
                    method, path, body=body,
                    headers={"content-type": "application/json"})
                resp = self.conn.getresponse()
                raw = resp.read()
                break
            except (OSError, http.client.HTTPException) as e:
                self.conn.close()
                self.conn = None
                if attempt or isinstance(e, TimeoutError):
                    raise
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode(errors="replace")

    def ok(self, method: str, path: str, body=None, **kw):
        status, out = self.call(method, path, body, **kw)
        check(status == 200, f"{method} {path} -> {status}: "
                             f"{str(out)[:600]}")
        return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def prom_values(text: str, family: str) -> dict:
    """{label-string: value} of one family from the text exposition."""
    out = {}
    for m in re.finditer(
            rf"^{re.escape(family)}(\{{[^}}]*\}})?\s+([0-9.eE+-]+)$",
            text, re.M):
        out[m.group(1) or ""] = float(m.group(2))
    return out


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def child_env() -> dict:
    env = dict(os.environ)
    env["ES_TPU_PLANE_HOST_SERVE"] = "0"
    env["ES_TPU_SERVING_WARMUP"] = "0"
    # the first request of each shape compiles for seconds, in the
    # request path: that is set-up here, not an incident. Left at its
    # default the SLO watchdog reads those latencies as burn and the QoS
    # edge sheds the analytics request that follows (bench.py raises the
    # same threshold for the same reason)
    env.setdefault("ES_TPU_SLO_LATENCY_MS", "600000")
    return env


def stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(20)


def phase_served(args, seen: dict) -> None:
    """``seen["device"]`` is set as soon as the node names its device."""
    t_phase = time.perf_counter()
    say(f"== phase served: {args.served_docs} docs, vocab {args.vocab}, "
        f"~{AVG_DL} tokens/doc, zipf {ZIPF_S}, dense_vector dim "
        f"{args.served_dim} ==")
    t0 = time.perf_counter()
    corpus = ServedCorpus(args.seed, args.served_docs, args.vocab,
                          args.served_dim)
    say(f"served: corpus + oracle tables made in "
        f"{time.perf_counter() - t0:.1f}s")
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    port = free_port()
    log_path = os.path.join(tmp, "node.log")
    t0 = time.perf_counter()
    with open(log_path, "w") as log:
        node = subprocess.Popen(
            [sys.executable, "-m", "elasticsearch_tpu.cli.node",
             "--port", str(port), "--data", os.path.join(tmp, "data"),
             "--name", "smoke-node"],
            cwd=HERE, env=child_env(), stdout=log,
            stderr=subprocess.STDOUT)
    try:
        _drive_node(args, corpus, node, port, log_path, t0, seen)
    except BaseException:
        with open(log_path) as f:
            tail = f.read()[-3000:]
        say(f"served: node log tail:\n{tail}")
        raise
    finally:
        stop(node)
    say(f"== phase served done in {time.perf_counter() - t_phase:.1f}s ==")


def _drive_node(args, corpus: ServedCorpus, node, port: int, log_path: str,
                t_start: float, seen: dict) -> None:
    http_ = Http(port)
    deadline = time.monotonic() + 300
    while True:
        check(node.poll() is None,
              f"node exited with code {node.returncode} before serving")
        try:
            status, _ = http_.call("GET", "/", timeout=5)
            if status == 200:
                break
        except OSError:
            pass
        check(time.monotonic() < deadline, "node did not serve in 300 s")
        time.sleep(0.5)
    say(f"served: node up in {time.perf_counter() - t_start:.1f}s")
    with open(log_path) as f:
        line = next(ln.strip() for ln in f if "compile cache " in ln)
    say(f"served: {line}")
    cache_dir = line.split("compile cache ", 1)[1]
    cache_before = cache_entries(cache_dir)

    # the platform, read once: it alone decides ok for a non-TPU device
    dev_doc = http_.ok("GET", "/_nodes/stats/device")
    devs = next(iter(dev_doc["nodes"].values()))["device"]["devices"]
    device = {"platform": devs[0]["platform"],
              "kind": devs[0]["device_kind"], "count": len(devs)}
    seen["device"] = device
    say(f"served: device {json.dumps(device)}")

    http_.ok("PUT", "/smoke", {
        "settings": {"number_of_shards": 1},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "vec": {"type": "dense_vector", "dims": args.served_dim,
                    "similarity": "cosine"},
            "tag": {"type": "keyword"}, "val": {"type": "double"}}}})
    t0 = time.perf_counter()
    step = 2048
    for lo in range(0, corpus.n, step):
        lines = []
        for i in range(lo, min(lo + step, corpus.n)):
            lines.append(json.dumps({"index": {"_index": "smoke",
                                               "_id": str(i)}}))
            lines.append(json.dumps(corpus.doc_source(i)))
        out = http_.ok("POST", "/_bulk", "\n".join(lines) + "\n")
        check(not out["errors"], f"_bulk reported errors: "
                                 f"{str(out)[:400]}")
    bulk_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    http_.ok("POST", "/smoke/_refresh")
    say(f"served: _bulk {corpus.n} docs in {bulk_s:.1f}s "
        f"({corpus.n / bulk_s:.0f} docs/s), refresh "
        f"{time.perf_counter() - t0:.1f}s")
    n_indexed = http_.ok("GET", "/smoke/_count")["count"]
    check(n_indexed == corpus.n, f"_count {n_indexed} != {corpus.n} bulked")

    # -- the requests ---------------------------------------------------
    # dense tier: per-segment df > max(n_pad/256, 4096); one refresh made
    # one segment, so a term's token count bounds its df
    head = corpus.terms_with_df(3 * 4096, 1 << 62, 3)
    tail = corpus.terms_with_df(64, 2048, 40)
    qv = query_vectors(corpus.rng, corpus.vecs, 12)
    k = 10
    n_answers = 0

    def tname(t):
        return f"t{t}"

    def search(body, client=None):
        nonlocal n_answers
        n_answers += 1       # int += under the GIL: wave threads share it
        return (client or http_).ok("POST", "/smoke/_search",
                                    dict(body, profile=True))

    def profile_of(out):
        return out["profile"]["shards"][0]

    def device_dispatch(sec, what):
        """A serving section must say a compiled step ran."""
        cc = (sec or {}).get("compile_cache")
        check(cc in ("hit", "miss"),
              f"{what}: compile_cache is {cc!r}, not a device dispatch "
              f"({json.dumps(sec)[:300]})")
        return sec

    def hit_rows(out):
        return ([int(h["_id"]) for h in out["hits"]["hits"]],
                [float(h["_score"]) for h in out["hits"]["hits"]])

    def total_equals_count(out, query, what):
        cnt = http_.ok("POST", "/smoke/_count", {"query": query})["count"]
        tot = out["hits"]["total"]
        check(tot["relation"] == "eq" and tot["value"] == cnt,
              f"{what}: _search total {tot} != _count {cnt}")
        return cnt

    def note(family, route, sec, extra=""):
        say(f"served: {family:<18s} route={route:<22s} "
            f"compile_cache={sec.get('compile_cache')} "
            f"batch={sec.get('batch_size')} "
            f"dispatch_ms={sec.get('stages_ms', {}).get('dispatch')} "
            f"{extra}")

    def match_check(terms, out, *, tol, what, total=True):
        scores, matched = corpus.bm25(terms)
        ref_ids, ref_sc = corpus.topk(scores, matched, k)
        ids, sc = hit_rows(out)
        agree(ids, sc, ref_ids.tolist(), ref_sc.tolist(), tol=tol,
              what=what, true_score=lambda d: float(scores[d]))
        if total:
            cnt = total_equals_count(
                out, {"match": {"body": " ".join(map(tname, terms))}}, what)
            check(cnt == int(matched.sum()),
                  f"{what}: _count {cnt} != oracle {int(matched.sum())}")

    # match, Zipf-head terms: dense tier -> the tiered step
    terms = [head[0], head[1], tail[0]]
    out = search({"query": {"match": {"body": " ".join(map(tname, terms))}},
                  "size": k})
    prof = profile_of(out)
    check(prof["planner"]["lower_ms"] is None,
          "match: a plain bag is served by the plane route, not lowered")
    sec = device_dispatch(prof.get("serving"), "match/head")
    match_check(terms, out, tol=TOL_DENSE, what="match/head")
    note("match(head terms)", "plane:tiered/dense", sec)

    # match, tail terms, exact totals: the same tiered step (a plane with
    # a dense tier serves every bag through it — one compile shape), the
    # dense weights all zero
    terms = tail[1:4]
    out = search({"query": {"match": {"body": " ".join(map(tname, terms))}},
                  "size": k})
    sec = device_dispatch(profile_of(out).get("serving"), "match/tail")
    match_check(terms, out, tol=TOL_SPARSE, what="match/tail")
    note("match(tail terms)", "plane:tiered/sparse", sec)

    # match, tail terms, totals not tracked: the default route past
    # LEX_PRUNE_MIN_DOCS is the block-max pruned step
    terms = tail[4:7]
    out = search({"query": {"match": {"body": " ".join(map(tname, terms))}},
                  "size": k, "track_total_hits": False})
    sec = device_dispatch(profile_of(out).get("serving"), "match/pruned")
    match_check(terms, out, tol=TOL_SPARSE, what="match/pruned",
                total=False)
    note("match(untracked)", "plane:pruned", sec,
         f"docs_scanned={sec.get('docs_scanned')}")

    # concurrent match wave: the micro-batcher co-batches
    wave = [[tail[7 + 2 * i], tail[8 + 2 * i]] for i in range(8)]
    wave_out: list = [None] * len(wave)
    wave_err: list = []

    def wave_client(i):
        try:
            wave_out[i] = search(
                {"query": {"match": {
                    "body": " ".join(map(tname, wave[i]))}}, "size": k},
                client=Http(port))
        except BaseException as e:   # noqa: BLE001 — re-raised below
            wave_err.append(e)

    threads = [threading.Thread(target=wave_client, args=(i,))
               for i in range(len(wave))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
        check(not t.is_alive(), "concurrent match wave hung")
    if wave_err:
        raise wave_err[0]
    batch_sizes = []
    for i, out in enumerate(wave_out):
        sec = device_dispatch(profile_of(out).get("serving"),
                              f"match/wave[{i}]")
        batch_sizes.append(sec["batch_size"])
        match_check(wave[i], out, tol=TOL_SPARSE, what=f"match/wave[{i}]",
                    total=False)
    check(max(batch_sizes) >= 2,
          f"no co-batching: batch sizes {batch_sizes} — concurrent HTTP "
          f"requests never met in the micro-batcher")
    say(f"served: match wave of {len(wave)} concurrent requests, "
        f"batch sizes {sorted(batch_sizes)}")

    # bool must/should over tail terms: lowered, ONE fused bool dispatch
    must, should = [tail[24]], [tail[25], tail[26]]
    bq = {"bool": {"must": [{"match": {"body": tname(must[0])}}],
                   "should": [{"match": {
                       "body": " ".join(map(tname, should))}}]}}
    out = search({"query": bq, "size": k})
    prof = profile_of(out)
    check(prof["planner"]["outcome"] == "fused",
          f"bool: planner outcome {prof['planner']} for a lowerable body")
    sec = device_dispatch(prof.get("serving"), "bool")
    s_must, m_must = corpus.bm25(must)
    s_should, _ = corpus.bm25(should)
    ref_ids, ref_sc = corpus.topk(s_must + s_should, m_must, k)
    ids, sc = hit_rows(out)
    agree(ids, sc, ref_ids.tolist(), ref_sc.tolist(), tol=TOL_SPARSE,
          what="bool")
    total_equals_count(out, bq, "bool")
    note("bool(must/should)", "fused:bool", sec)

    # knn: past KNN_IVF_MIN_DOCS the default route is the IVF step
    def knn_body(q, **kw):
        return {"field": "vec", "query_vector": q.tolist(), "k": k,
                "num_candidates": 100, **kw}

    recalls = []
    for i in range(3):
        out = search({"knn": knn_body(qv[i]), "size": k})
        secs = profile_of(out).get("serving_knn") or [None]
        sec = device_dispatch(secs[0], "knn/ivf")
        ids, sc = hit_rows(out)
        ref_ids, ref_cos, cos = knn_oracle(corpus.unit, qv[i], k)
        for d, s in zip(ids, sc):
            want = (1.0 + float(cos[d])) / 2.0
            check(abs(s - want) <= TOL_KNN,
                  f"knn/ivf: doc {d} scored {s}, exact re-rank is {want}")
        check(sc == sorted(sc, reverse=True), "knn/ivf: unsorted hits")
        recalls.append(len(set(ids) & set(ref_ids.tolist())) / k)
        note("knn(default)", "knn:ivf", sec,
             f"recall@{k}={recalls[-1]:.2f}")
    check(np.mean(recalls) >= IVF_RECALL_MIN,
          f"knn/ivf: mean recall {np.mean(recalls):.3f} < "
          f"{IVF_RECALL_MIN}")

    # knn, nprobe 0: the exact blocked scan
    out = search({"knn": knn_body(qv[3], nprobe=0), "size": k})
    sec = device_dispatch((profile_of(out).get("serving_knn") or [None])[0],
                          "knn/exact")
    ids, sc = hit_rows(out)
    ref_ids, ref_cos, _ = knn_oracle(corpus.unit, qv[3], k)
    agree(ids, sc, ref_ids.tolist(), ((1.0 + ref_cos) / 2.0).tolist(),
          tol=TOL_KNN, what="knn/exact")
    note("knn(nprobe=0)", "knn:exact", sec)

    # hybrid rank.rrf. With the IVF tier the planner cannot fuse
    # (ROADMAP S5b): per-segment lexical scoring + the knn IVF plane,
    # fused on the host. With nprobe 0 and sparse-tier terms the whole
    # request is ONE fused device program.
    terms = [tail[27], tail[28]]
    mq = {"match": {"body": " ".join(map(tname, terms))}}
    out = search({"query": mq, "knn": knn_body(qv[4]),
                  "rank": {"rrf": {"rank_window_size": k}}, "size": k})
    prof = profile_of(out)
    check(prof["planner"]["outcome"] == "fallback"
          and prof["planner"]["lower_ms"] is not None,
          f"hybrid/ivf: planner said {prof['planner']}")
    sec = device_dispatch((prof.get("serving_knn") or [None])[0],
                          "hybrid/ivf knn stage")
    check(len(out["hits"]["hits"]) == k, "hybrid/ivf: short page")
    note("hybrid(rrf)", "segments+knn:ivf", sec)

    out = search({"query": mq, "knn": knn_body(qv[4], nprobe=0),
                  "rank": {"rrf": {"rank_window_size": k}}, "size": k})
    prof = profile_of(out)
    check(prof["planner"]["outcome"] == "fused",
          f"hybrid/exact: planner outcome {prof['planner']} for a "
          f"lowerable body")
    sec = device_dispatch(prof.get("serving"), "hybrid/exact")
    scores, matched = corpus.bm25(terms)
    t_ids, _ = corpus.topk(scores, matched, k)
    k_ids, _, _ = knn_oracle(corpus.unit, qv[4], k)
    rrf: dict = {}
    for ranking in (t_ids.tolist(), k_ids.tolist()):
        for r, d in enumerate(ranking):
            rrf[d] = rrf.get(d, 0.0) + 1.0 / (60 + r + 1)
    ref = sorted(rrf.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    ids, sc = hit_rows(out)
    agree(ids, sc, [d for d, _ in ref], [s for _, s in ref], tol=1e-6,
          what="hybrid/exact", slack=2)
    note("hybrid(rrf,nprobe=0)", "fused:hybrid", sec)

    # terms + percentiles over a match: agg stages need the host CSR a
    # device plane does not keep (ROADMAP S5c), so the per-segment path
    # serves, through the jitted ops/aggs.py kernels
    pairs0 = sum(prom_values(http_.ok("GET", "/_prometheus/metrics"),
                             "es_agg_device_pairs_total").values())
    terms = [tail[29], tail[30], head[2]]
    mq = {"match": {"body": " ".join(map(tname, terms))}}
    out = search({"query": mq, "size": 0, "aggs": {
        "tags": {"terms": {"field": "tag"}},
        "pct": {"percentiles": {"field": "val"}}}})
    _, matched = corpus.bm25(terms)
    total_equals_count(out, mq, "aggs")
    want = np.bincount(corpus.tags[matched], minlength=7)
    got = {b["key"]: b["doc_count"]
           for b in out["aggregations"]["tags"]["buckets"]}
    check(got == {f"g{i}": int(c) for i, c in enumerate(want) if c},
          f"aggs: terms buckets {got} vs oracle {want.tolist()}")
    pcts = out["aggregations"]["pct"]["values"]
    vals = corpus.vals[matched]
    for p, v in pcts.items():
        ref = float(np.percentile(vals, float(p)))
        check(abs(v - ref) <= 1.0, f"aggs: p{p} = {v}, numpy says {ref}")
    pairs1 = sum(prom_values(http_.ok("GET", "/_prometheus/metrics"),
                             "es_agg_device_pairs_total").values())
    # (a rehearsal corpus under ops/aggs.DEVICE_MIN_PAIRS = 2^16 pairs
    # per segment takes the numpy twin by design; the default size is past)
    check(pairs1 > pairs0 or corpus.n < (1 << 16),
          "aggs: es_agg_device_pairs_total did not rise — the agg kernels "
          "did not run on the device")
    say(f"served: {'aggs(terms+pct)':<18s} route=segments:ops/aggs     "
        f"device_pairs +{int(pairs1 - pairs0)}")

    # -- node-level evidence --------------------------------------------
    prom = http_.ok("GET", "/_prometheus/metrics")
    compiles = prom_values(prom, "es_xla_compiles_total")
    sites = {re.search(r'site="([^"]+)"', lbl).group(1): int(v)
             for lbl, v in compiles.items()}
    for site in ("text_plane", "text_plane_pruned", "text_plane_bool",
                 "knn_plane", "knn_ivf_plane", "fused_plane"):
        check(sites.get(site, 0) >= 1,
              f"es_xla_compiles_total{{site={site}}} never rose: {sites}")
    dev = next(iter(http_.ok("GET", "/_nodes/stats/device")
                    ["nodes"].values()))["device"]
    stats = next(iter(http_.ok("GET", "/_nodes/stats")["nodes"].values()))
    ps = stats["indices"]["plane_serving"]
    check(ps["warmup_failures"] == 0, f"warm-up failures: {ps}")
    journal = http_.ok(
        "GET", "/_flight_recorder?type=warmup_failed,plane_repack_failed")
    check(not journal.get("events"),
          f"journaled failures: {str(journal)[:600]}")
    say(f"served: compiles by site {json.dumps(sites)}")
    say(f"served: compile seconds by site "
        f"{json.dumps({k: round(v / 1e3, 1) for k, v in dev.get('compile_millis', {}).items()})}")
    say(f"served: planner outcomes "
        f"{json.dumps(prom_values(prom, 'es_planner_lowered_total'))}")
    say(f"served: plane_serving dispatches={ps['dispatches']} "
        f"queries={ps['queries']} max_batch={ps['max_batch']} "
        f"rebuilds_sync={ps['rebuilds_sync']}")
    mem = dev["devices"][0].get("memory", {})
    say(f"served: device memory peak_bytes_in_use="
        f"{mem.get('peak_bytes_in_use')} bytes_limit="
        f"{mem.get('bytes_limit')}")
    say(f"served: compile cache {cache_dir}: {cache_before} entries "
        f"before, {cache_entries(cache_dir)} after")
    say(f"served: {n_answers} answers checked, every serving section a "
        f"device dispatch: 0 host-served")


# ---------------------------------------------------------------------------
# children that hold the chip: full width (one chip), multichip (four)
# ---------------------------------------------------------------------------


def _child_start(args):
    """Common start of a chip-holding child: cache, device, listeners."""
    os.environ.update({k: v for k, v in child_env().items()
                       if k.startswith("ES_TPU_")})
    sys.path.insert(0, HERE)
    import jax
    from elasticsearch_tpu.common import runtime
    cache_dir = runtime.enable_compile_cache()
    counts = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    from elasticsearch_tpu import native
    say(f"{args.phase}: device {json.dumps(device)}, compile cache "
        f"{cache_dir} ({cache_entries(cache_dir)} entries), native "
        f"fastpath {'built' if native.AVAILABLE else 'python fallback'}")
    with open(args.report, "w") as f:
        json.dump({"device": device}, f)
    return jax, device, counts


def _compiles_total() -> int:
    from elasticsearch_tpu.common import telemetry as tm
    doc = tm.DEFAULT.metrics_doc().get("es_xla_compiles_total")
    return int(sum(s["value"] for s in doc["series"])) if doc else 0


def _quiet_batcher(cls, plane, **kw):
    """A micro-batcher whose idle dispatcher threads exit quickly, so
    :func:`_one_batch_wave` finds none alive (see there)."""
    batcher = cls(plane, **kw)
    batcher.IDLE_EXIT_S = 0.3
    plane._microbatcher = batcher
    return batcher


def _one_batch_wave(batcher, jobs, timeout: float):
    """Run ``jobs`` (callables) on one client thread each so that they
    land in the batcher as ONE queue. A batch is whatever is queued when
    a dispatcher wakes, so free-running clients split into arbitrary
    batch sizes — each a new compile at full width. The wave waits until
    no dispatcher thread is alive, then holds the batcher's queue lock
    while every client lines up on it: the first client in spawns the
    dispatcher, which parks on the lock behind all the others."""
    results = [None] * len(jobs)
    errors = []
    lined_up = threading.Semaphore(0)
    quiet_by = time.monotonic() + 30
    while any(t.is_alive() for t in batcher._dispatchers):
        check(time.monotonic() < quiet_by, "dispatcher threads never idled")
        time.sleep(0.05)

    def client(i):
        lined_up.release()
        try:
            results[i] = jobs[i]()
        except BaseException as e:   # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(jobs))]
    with batcher._cond:
        for t in threads:
            t.start()
        for _ in threads:
            lined_up.acquire()
        time.sleep(0.5)
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        say(f"FAILED: {sum(t.is_alive() for t in threads)} client "
            f"thread(s) still blocked after {timeout:.0f}s — a hung "
            f"dispatch")
        sys.stdout.flush()
        os._exit(3)       # a hung device call cannot be unwound
    if errors:
        raise errors[0]
    return results


def _wave(args, batcher, what: str, submit, n: int):
    """One wave of ``n`` concurrent clients through ``batcher``
    (``submit(i, stages, info)`` sends client i's query). A shape the
    wave meets for the first time compiles inside it, synchronously —
    the script waits, counts it, and a compile that fails fails the wave.
    Returns (results, shapes compiled)."""
    stages = [dict() for _ in range(n)]
    infos = [dict() for _ in range(n)]
    c0 = _compiles_total()
    t0 = time.perf_counter()
    res = _one_batch_wave(
        batcher, [(lambda i=i: submit(i, stages[i], infos[i]))
                  for i in range(n)], timeout=args.wave_timeout)
    wall = time.perf_counter() - t0
    compiled = _compiles_total() - c0
    for info in infos:
        check(info.get("compile_cache") in ("hit", "miss"),
              f"{what}: not a device dispatch: {info}")
    by_batch = {i["batch_size"]: round(s_["dispatch"], 1)
                for i, s_ in zip(infos, stages)}
    say(f"{args.phase}: {what}: wave of {n} in {wall:.1f}s, "
        f"{compiled} shape(s) compiled in it, dispatch ms by batch size "
        f"{json.dumps(by_batch, sort_keys=True)}")
    return res, compiled


def _text_corpus(args, n_docs: int):
    from elasticsearch_tpu.utils.synth import synthetic_csr_corpus_fast
    t0 = time.perf_counter()
    rng = np.random.RandomState(args.seed)
    corpus = synthetic_csr_corpus_fast(rng, n_docs, args.vocab, AVG_DL,
                                       zipf_s=ZIPF_S)
    corpus["term_ids"] = {f"t{t}": t for t in range(args.vocab)}
    say(f"{args.phase}: corpus {n_docs} docs, "
        f"{corpus['docs'].shape[0]} postings, made in "
        f"{time.perf_counter() - t0:.1f}s")
    return rng, corpus


def _dense_mask(corpus, plane) -> np.ndarray:
    dense = np.zeros(corpus["df"].shape[0], bool)
    for sh in plane.shards:
        dense[list(sh["dense_row_of"])] = True
    return dense


def _tail_queries(rng, corpus, plane, n: int, n_terms: int = 4):
    """Bags of sparse-tier terms only, drawn like bench.sample_queries
    (probability ~ posting mass) — what the pruned step can serve (dense
    terms belong to the matmul tier)."""
    df = corpus["df"].astype(np.float64)
    ok = np.flatnonzero((df >= 2) & ~_dense_mask(corpus, plane))
    draws = rng.choice(ok, size=(n, n_terms), p=df[ok] / df[ok].sum())
    return [[f"t{t}" for t in row] for row in draws]


def _pinned_queries(rng, corpus, plane, n: int):
    """Two sets of ``n`` bags whose compile shapes do not depend on which
    of them share a batch (the multichip waves split into two batches of
    arbitrary membership). *Anchors* are sparse terms whose per-shard run
    is longer than the second-highest L rung: one in a bag pins the top
    rung, four pin the pruned step's pow2 schedule length. The tiered
    bags add three of the twelve heaviest dense terms, so any batch uses
    at most twelve dense rows (one gather width)."""
    df = corpus["df"].astype(np.float64)
    dense = _dense_mask(corpus, plane)
    per_shard = df / len(plane.shards)     # doc-range shards: ~df/S each
    rungs = plane.ladder_rungs()
    floor = rungs[-2] if len(rungs) > 1 else 0
    sparse_max = max(int(sh["sparse_df"].max()) for sh in plane.shards)
    anchors = np.flatnonzero(~dense & (per_shard > 1.1 * floor)
                             & (per_shard <= 0.9 * sparse_max))
    check(anchors.size >= 4, f"only {anchors.size} anchor terms")
    top = np.argsort(-df)[:12]
    check(dense[top].all(), "the twelve heaviest terms are not all dense")
    tiered = [[f"t{t}" for t in rng.choice(top, 3, replace=False)]
              + [f"t{rng.choice(anchors)}"] for _ in range(n)]
    pruned = [[f"t{t}" for t in rng.choice(anchors, 4, replace=False)]
              for _ in range(n)]
    return tiered, pruned


def _check_text_batch(corpus, batch, vals, hits, n_check: int, *, tol,
                      what: str, k: int = 10):
    """The first ``n_check`` queries of a served batch against bench.py's
    numpy BM25 (one shard: hit = (0, doc))."""
    import bench
    _, ref = bench.cpu_bm25_search(corpus, batch[:n_check], k)
    for bi in range(n_check):
        ref_ids = [int(d) for d in ref[bi]]
        ref_sc = [bench._score_one(corpus, batch[bi], d) for d in ref_ids]
        keep = [i for i, s in enumerate(ref_sc) if s > 0]
        ref_ids = [ref_ids[i] for i in keep]
        ref_sc = [ref_sc[i] for i in keep]
        got_ids = [d for (_s, d) in hits[bi][:k]]
        got_sc = [float(v) for v in vals[bi][: len(got_ids)]]
        agree(got_ids, got_sc, ref_ids, ref_sc, tol=tol,
              what=f"{what}[{bi}] {batch[bi]}",
              true_score=lambda d, bi=bi: bench._score_one(
                  corpus, batch[bi], d))


def phase_full(args) -> None:
    t_phase = time.perf_counter()
    jax, _device, cache_counts = _child_start(args)
    import bench
    from elasticsearch_tpu.common import telemetry as tm
    from elasticsearch_tpu.parallel import (DistributedKnnPlane,
                                            DistributedSearchPlane,
                                            mesh_from_env)
    from elasticsearch_tpu.parallel.dist_search import total_value
    from elasticsearch_tpu.search.microbatch import (
        KnnPlaneMicroBatcher, PlaneMicroBatcher, batched_knn_search,
        batched_search)

    mesh = mesh_from_env()
    B, K = args.batch, 10
    say(f"== phase full: match BM25 at {args.full_docs} docs, one shard, "
        f"k={K}, B={B}; kNN {args.knn_docs} x {args.knn_dim} cosine "
        f"k={args.knn_k} ==")

    # -- text: dense tier AND block-max tier, as ServingPlaneCache packs
    rng, corpus = _text_corpus(args, args.full_docs)
    corpus["avgdl"] = float(corpus["doc_len"].mean())
    t0 = time.perf_counter()
    plane = DistributedSearchPlane(mesh, [corpus], "body", blockmax={})
    dev_tier = plane.blockmax.device_arrays(mesh)
    jax.block_until_ready((plane.docs_dev, plane.dense_dev,
                           dev_tier["docs"]))
    say(f"full: text plane packed + resident in "
        f"{time.perf_counter() - t0:.1f}s: n_pad {plane.n_pad}, dense "
        f"rows {plane.n_dense} (T_pad {plane.T_pad}), L_cap "
        f"{plane.L_cap}, rungs {plane.ladder_rungs()}, block-max blocks "
        f"{plane.blockmax.n_blocks}, device bytes "
        f"{plane.device_corpus_bytes()}")
    batcher = _quiet_batcher(PlaneMicroBatcher, plane)
    compiled = 0
    kernels: dict = {}

    def serve_wave(batch, prune, what, tol):
        nonlocal compiled
        res, n_new = _wave(
            args, batcher,
            f"{what} (L rung {plane.ladder_L(plane.max_run_len(batch))})",
            lambda i, st, info: batched_search(
                plane, batch[i], K, stages=st, info=info, prune=prune),
            len(batch))
        compiled += n_new
        _check_text_batch(corpus, batch, [r[0] for r in res],
                          [r[1] for r in res], args.check_queries, tol=tol,
                          what=what)
        say(f"full: {what}: {args.check_queries} queries agree with "
            f"cpu_bm25_search")
        return res

    head_batches = bench.sample_queries(rng, corpus, args.waves, batch=B)
    tail_batches = [_tail_queries(rng, corpus, plane, B)
                    for _ in range(args.waves)]
    seq0 = max((r["seq"] for r in _profile_records()), default=0)
    for i, batch in enumerate(head_batches):
        # frequency-weighted bags hit the dense tier: the default route
        # hands them to the tiered step (pruning is the sparse tier's)
        serve_wave(batch, None, f"default route, head bags #{i}", TOL_DENSE)
    for i, batch in enumerate(tail_batches):
        res = serve_wave(batch, None, f"default route, tail bags #{i}",
                         TOL_SPARSE)
        lower = sum(isinstance(r[2], tuple) for r in res)
        say(f"full: tail bags #{i}: {lower}/{len(res)} totals are lower "
            f"bounds (pruned early), first total "
            f"{total_value(res[0][2])}")
    for i, batch in enumerate(head_batches):
        serve_wave(batch, False, f"prune=False, head bags #{i}", TOL_DENSE)
    for rec in _profile_records():
        if rec["seq"] > seq0:
            kernels[rec["kernel"]] = kernels.get(rec["kernel"], 0) + 1
    check(kernels.get("bm25_pruned", 0) >= 1 and
          kernels.get("bm25_eager", 0) >= 1,
          f"expected pruned and tiered dispatches, saw {kernels}")
    lex = {k_: int(sum(s["value"] for s in fam["series"]))
           for k_, fam in tm.DEFAULT.metrics_doc().items()
           if k_.startswith("es_lex_blocks")}
    say(f"full: text kernels served {json.dumps(kernels)}, "
        f"block-max counters {json.dumps(lex)}")
    say(f"full: text: {compiled} shapes compiled, "
        f"{batcher.stats_doc()['dispatches']} dispatches, max batch "
        f"{batcher.stats_doc()['max_batch']}")
    _memory_line(jax, "full", "after text")
    del plane, batcher, dev_tier
    import gc
    gc.collect()

    # -- kNN at GloVe width: exact and IVF ----------------------------------
    t0 = time.perf_counter()
    vecs = clustered_vectors(rng, args.knn_docs, args.knn_dim,
                             max(args.knn_docs // 512, 4))
    unit = unit_rows(vecs)
    say(f"full: kNN corpus made in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    kplane = DistributedKnnPlane(mesh, [dict(vectors=vecs)],
                                 similarity="cosine", ivf={})
    kplane._device_arrays()
    kplane.ivf.device_arrays(mesh, kplane.n_pad)
    say(f"full: kNN plane packed + resident in "
        f"{time.perf_counter() - t0:.1f}s: n_pad {kplane.n_pad}, IVF "
        f"nlist {kplane.ivf.nlist}, blocks {kplane.ivf.n_blocks}, "
        f"default nprobe {kplane.ivf.default_nprobe}, device bytes "
        f"{kplane.device_corpus_bytes()}")
    kb = _quiet_batcher(KnnPlaneMicroBatcher, kplane)
    kk = args.knn_k

    def knn_wave(q, nprobe, what):
        nonlocal compiled
        res, n_new = _wave(
            args, kb, what,
            lambda i, st, info: batched_knn_search(
                kplane, q[i], kk, stages=st, info=info, nprobe=nprobe),
            len(q))
        compiled += n_new
        return res

    for w in range(args.waves):
        q = query_vectors(rng, vecs, B)
        res = knn_wave(q, 0, f"kNN exact #{w}")
        for bi in range(args.check_queries):
            ref_ids, ref_cos, _ = knn_oracle(unit, q[bi], kk)
            vals, hits = res[bi]
            agree([d for (_s, d) in hits], [float(v) for v in vals],
                  ref_ids.tolist(), ref_cos.tolist(), tol=TOL_KNN,
                  what=f"kNN exact #{w}[{bi}]")
        res = knn_wave(q, None, f"kNN IVF #{w}")
        recalls = []
        for bi in range(args.check_queries):
            ref_ids, _, cos = knn_oracle(unit, q[bi], kk)
            vals, hits = res[bi]
            ids = [d for (_s, d) in hits]
            for d, v in zip(ids, vals):
                check(abs(float(v) - float(cos[d])) <= TOL_KNN,
                      f"kNN IVF #{w}[{bi}]: doc {d} scored {v}, exact "
                      f"re-rank is {cos[d]}")
            recalls.append(len(set(ids) & set(ref_ids.tolist())) / kk)
        check(np.mean(recalls) >= IVF_RECALL_MIN,
              f"kNN IVF #{w}: recall@{kk} {np.mean(recalls):.3f} < "
              f"{IVF_RECALL_MIN}")
        say(f"full: kNN #{w}: exact agrees with numpy on "
            f"{args.check_queries} queries; IVF recall@{kk} "
            f"{np.mean(recalls):.3f}, re-ranked scores exact")
    say(f"full: kNN: {kb.stats_doc()['dispatches']} dispatches, max batch "
        f"{kb.stats_doc()['max_batch']}")
    say(f"full: {compiled} shapes compiled in all, each synchronously in "
        f"the wave that first sent it; persistent cache hits "
        f"{cache_counts['hits']} misses {cache_counts['misses']}")
    comp_ms = {s["labels"]["site"]: round(s["value"] / 1e3, 1)
               for s in tm.DEFAULT.metrics_doc()[
                   "es_xla_compile_millis_total"]["series"]}
    say(f"full: compile seconds by site {json.dumps(comp_ms)}")
    _memory_line(jax, "full", "after kNN")
    say(f"== phase full done in {time.perf_counter() - t_phase:.1f}s ==")


def _profile_records() -> list:
    from elasticsearch_tpu.search import dispatch_profile as dp
    return dp.RING.records(limit=0)


def _memory_line(jax, phase: str, when: str) -> None:
    ms = jax.devices()[0].memory_stats() or {}
    say(f"{phase}: device 0 memory {when}: peak_bytes_in_use="
        f"{ms.get('peak_bytes_in_use')} bytes_in_use="
        f"{ms.get('bytes_in_use')} bytes_limit={ms.get('bytes_limit')}")


def _measured_device_bytes(arrays) -> dict:
    """Resident bytes per device id, read from the live buffers
    (scripts/bench_multichip.py's reader)."""
    per_dev: dict = {}
    for a in arrays:
        if a is None:
            continue
        for s in a.addressable_shards:
            did = int(s.device.id)
            per_dev[did] = per_dev.get(did, 0) + int(s.data.nbytes)
    return per_dev


def phase_multichip(args) -> None:
    t_phase = time.perf_counter()
    jax, device, cache_counts = _child_start(args)
    check(device["count"] >= 4, f"--chips 4 needs four devices, jax sees "
                                f"{device['count']}")
    from elasticsearch_tpu.parallel import (DistributedSearchPlane,
                                            make_search_mesh, mesh_from_env)
    from elasticsearch_tpu.parallel.mesh import AXIS_REPLICA, AXIS_SHARD
    from elasticsearch_tpu.search.microbatch import (PlaneMicroBatcher,
                                                     batched_search)
    from elasticsearch_tpu.utils.synth import split_csr_shards

    K, KB = 10, 16
    say(f"== phase multichip: {args.full_docs} docs in 4 shards; 1x4 "
        f"(mesh_from_env default), 2x2, and 1x1 on devices[:1] ==")
    rng, corpus = _text_corpus(args, args.full_docs)
    t0 = time.perf_counter()
    avgdl = float(corpus["doc_len"].mean())
    shards = split_csr_shards(corpus, 4)
    for s in shards:
        s["term_ids"] = corpus["term_ids"]
        s["avgdl"] = avgdl
    say(f"multichip: split into 4 doc-range shards in "
        f"{time.perf_counter() - t0:.1f}s")
    mesh14 = mesh_from_env(jax.devices()[:4])
    check((mesh14.shape[AXIS_REPLICA], mesh14.shape[AXIS_SHARD]) == (1, 4),
          f"mesh_from_env default is {dict(mesh14.shape)}, expected 1x4")
    t0 = time.perf_counter()
    p14 = DistributedSearchPlane(mesh14, shards, "body", blockmax={})
    tier14 = p14.blockmax.device_arrays(mesh14)
    jax.block_until_ready((p14.docs_dev, p14.dense_dev, tier14["docs"]))
    say(f"multichip: 1x4 plane packed + resident in "
        f"{time.perf_counter() - t0:.1f}s: n_pad {p14.n_pad}/shard, dense "
        f"rows {p14.n_dense}, L_cap {p14.L_cap}, rungs "
        f"{p14.ladder_rungs()}")
    del shards

    def resident(plane, tier):
        return _measured_device_bytes(
            [plane.docs_dev, plane.impacts_dev, plane.dense_dev,
             tier["docs"], tier["codes"], tier["scale"], tier["off"]])

    by_dev = resident(p14, tier14)
    total = sum(by_dev.values())
    say(f"multichip: 1x4 resident bytes per device "
        f"{json.dumps(by_dev)} (total {total})")
    check(len(by_dev) == 4, f"corpus on {len(by_dev)} devices, not 4")
    for did, b in by_dev.items():
        check(abs(b - total / 4) <= 0.02 * total,
              f"device {did} holds {b} bytes, not ~1/4 of {total}")
    check(abs(p14.device_corpus_bytes() - total / 4) <= 0.05 * total / 4,
          f"device_corpus_bytes() {p14.device_corpus_bytes()} vs measured "
          f"{total / 4:.0f}")

    n_q = 16
    head, anchored = _pinned_queries(rng, corpus, p14, n_q)
    routes = (("tiered", head, False), ("pruned", anchored, None))

    def serve_all(plane, what):
        """Both routes, in two dispatches of 8 each: the one B=8 compile
        shape per (mesh, route) the concurrent waves below reuse."""
        out = {}
        for name, qs, prune in routes:
            c0 = _compiles_total()
            t0 = time.perf_counter()
            halves = [plane.serve(qs[lo: lo + 8], k=KB, with_totals=True,
                                  prune=prune) for lo in (0, 8)]
            say(f"multichip: {what} {name}: {n_q} queries in "
                f"{time.perf_counter() - t0:.1f}s "
                f"({_compiles_total() - c0} compiles)")
            out[name] = (np.concatenate([np.asarray(h[0]) for h in halves]),
                         halves[0][1] + halves[1][1],
                         halves[0][2] + halves[1][2])
        return out

    ref14 = serve_all(p14, "1x4")
    _check_text_sharded(corpus, head, ref14["tiered"], args.check_queries,
                        TOL_DENSE, "1x4 tiered")
    _check_text_sharded(corpus, anchored, ref14["pruned"],
                        args.check_queries, TOL_SPARSE, "1x4 pruned")

    # >= 8 client threads, two dispatchers in flight: max_batch 8 and a
    # wave of 16 make two B=8 multi-device dispatches run concurrently
    # (dist_search._CPU_COLLECTIVE_LOCK serializes them on XLA:CPU only)
    def concurrent(plane, what):
        b = _quiet_batcher(PlaneMicroBatcher, plane, max_batch=8)
        c0 = _compiles_total()
        for name, qs, prune in routes:
            for rnd in range(args.waves):
                t0 = time.perf_counter()
                res = _one_batch_wave(b, [
                    (lambda i=i: batched_search(plane, qs[i], K,
                                                prune=prune))
                    for i in range(n_q)], timeout=args.wave_timeout)
                for i, (vals, hits, _tot) in enumerate(res):
                    want_v, want_h, _ = ref14[name]
                    check(np.array_equal(np.asarray(vals),
                                         want_v[i][: len(vals)])
                          and list(hits) == list(want_h[i][:K]),
                          f"{what} {name} concurrent round {rnd} query "
                          f"{i} differs from the direct dispatch")
                say(f"multichip: {what} {name}: 16 clients, round {rnd} "
                    f"in {(time.perf_counter() - t0) * 1e3:.0f} ms")
        st = b.stats_doc()
        check(st["max_batch"] == 8 and st["dispatches"] >= 2,
              f"{what}: batcher stats {st}")
        say(f"multichip: {what}: {st['dispatches']} dispatches of "
            f"{st['max_batch']}, two dispatchers, no hang, "
            f"{_compiles_total() - c0} compiled in traffic")

    concurrent(p14, "1x4")
    packed = p14.export_packed()
    _memory_line(jax, "multichip", "1x4 resident")
    del p14, tier14
    import gc
    gc.collect()

    # every comparison runs before any verdict: one four-chip call
    # brings back all of the evidence
    differing = []

    def same(a, b, what):
        for name in a:
            if np.array_equal(a[name][0], b[name][0]) \
                    and a[name][1] == b[name][1] \
                    and a[name][2] == b[name][2]:
                say(f"multichip: {what} {name}: bit-identical to the 1x4 "
                    f"plane (values, hits, totals)")
                continue
            dv = np.abs(np.where(np.isfinite(a[name][0]), a[name][0], 0)
                        - np.where(np.isfinite(b[name][0]), b[name][0], 0))
            n_hits = sum(x != y for x, y in zip(a[name][1], b[name][1]))
            n_tot = sum(x != y for x, y in zip(a[name][2], b[name][2]))
            differing.append(
                f"{what} {name}: max |dv| {float(dv.max()):.3g}, "
                f"{n_hits} hit rows and {n_tot} totals differ")
            say(f"multichip: {what} {name}: NOT bit-identical — "
                f"{differing[-1]}")

    # 2x2: replicas x shards over the same four shards
    mesh22 = make_search_mesh(n_shards=2, n_replicas=2,
                              devices=jax.devices()[:4])
    t0 = time.perf_counter()
    p22 = DistributedSearchPlane.from_packed(mesh22, packed)
    tier22 = p22.blockmax.device_arrays(mesh22)
    jax.block_until_ready((p22.docs_dev, p22.dense_dev, tier22["docs"]))
    by_dev = resident(p22, tier22)
    say(f"multichip: 2x2 plane resident in {time.perf_counter() - t0:.1f}s"
        f", bytes per device {json.dumps(by_dev)}")
    check(len(by_dev) == 4, f"2x2 corpus on {len(by_dev)} devices")
    for did, b in by_dev.items():
        check(abs(b - total / 2) <= 0.02 * total,
              f"2x2 device {did} holds {b} bytes, not ~1/2 of {total}")
    same(serve_all(p22, "2x2"), ref14, "2x2")
    del p22, tier22
    gc.collect()

    # the comparison: a 1x1 plane on devices[:1], same process
    mesh11 = make_search_mesh(n_shards=1, n_replicas=1,
                              devices=jax.devices()[:1])
    t0 = time.perf_counter()
    p11 = DistributedSearchPlane.from_packed(mesh11, packed)
    tier11 = p11.blockmax.device_arrays(mesh11)
    jax.block_until_ready((p11.docs_dev, p11.dense_dev, tier11["docs"]))
    say(f"multichip: 1x1 plane resident in {time.perf_counter() - t0:.1f}s"
        f", bytes {json.dumps(resident(p11, tier11))}")
    same(serve_all(p11, "1x1"), ref14, "1x1")
    check(not differing, "results differ across meshes: "
                         + "; ".join(differing))
    say("multichip: 1x4 and 2x2 results are bit-identical to the 1x1 "
        "plane (values, hits, totals; tiered and pruned routes)")
    say(f"multichip: persistent cache hits {cache_counts['hits']} misses "
        f"{cache_counts['misses']}")
    _memory_line(jax, "multichip", "end")
    say(f"== phase multichip done in "
        f"{time.perf_counter() - t_phase:.1f}s ==")


def _check_text_sharded(corpus, queries, served, n_check: int, tol: float,
                        what: str, k: int = 10) -> None:
    """Doc-range shards: global doc = shard * ceil(N/4) + local."""
    per = -(-corpus["doc_len"].shape[0] // 4)
    vals, hits, _ = served
    flat = [[(0, s * per + d) for (s, d) in row] for row in hits]
    _check_text_batch(corpus, queries, vals, flat, n_check, tol=tol,
                      what=what, k=k)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def run_child(args, phase: str, timeout: float, seen: dict) -> dict:
    """Run one chip-holding phase as a child of its own; its lines pass
    through, its device comes back in a report file."""
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    report = os.path.join(tmp, "report.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--report", report]
    for name in ("seed", "vocab", "full_docs", "knn_docs", "knn_dim",
                 "knn_k", "batch", "waves", "check_queries",
                 "wave_timeout"):
        cmd += [f"--{name.replace('_', '-')}", str(getattr(args, name))]
    proc = subprocess.Popen(cmd, cwd=HERE, env=child_env())
    device = None
    try:
        rc = proc.wait(timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {phase} did not finish in {timeout:.0f}s")
    finally:
        stop(proc)
        if os.path.exists(report):     # written as the child starts
            with open(report) as f:
                device = json.load(f)["device"]
            seen.setdefault("device", device)
    check(rc == 0, f"phase {phase} exited with code {rc}")
    return device


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--phase", choices=("full", "multichip"), default=None,
                    help="(internal) run one chip-holding phase in this "
                         "process")
    ap.add_argument("--report", default=None, help="(internal)")
    # sizes: the defaults are the only ones a chip run uses; a CPU
    # rehearsal passes small ones
    ap.add_argument("--served-docs", type=int, default=(1 << 17) + 4096)
    ap.add_argument("--served-dim", type=int, default=100)
    ap.add_argument("--vocab", type=int, default=VOCAB)
    ap.add_argument("--full-docs", type=int, default=1 << 23)
    ap.add_argument("--knn-docs", type=int, default=1_200_000)
    ap.add_argument("--knn-dim", type=int, default=100)
    ap.add_argument("--knn-k", type=int, default=100)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--waves", type=int, default=1,
                    help="waves per route; one B=64 tiered dispatch at "
                         "2^23 docs took 44 s on a v5e (PR 21), and the "
                         "whole run has 1200 s")
    ap.add_argument("--check-queries", type=int, default=4)
    ap.add_argument("--wave-timeout", type=float, default=600.0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "elasticsearch_tpu")):
        print("chip_smoke.py: the elasticsearch_tpu package is not next to "
              "this script", file=sys.stderr)
        return 2
    if args.phase:
        fn = {"full": phase_full, "multichip": phase_multichip}[args.phase]
        try:
            fn(args)
        except SmokeFailure as e:
            say(f"FAILED ({args.phase}): {e}")
            return 1
        return 0

    t0 = time.perf_counter()
    seen: dict = {}
    failed = None
    try:
        if args.chips == 4:
            run_child(args, "multichip", 3000, seen)
        else:
            phase_served(args, seen)
            second = run_child(args, "full", 1500, seen)
            check(second == seen["device"],
                  f"phases saw different devices: {seen['device']} vs "
                  f"{second}")
    except SmokeFailure as e:
        failed = str(e)
        say(f"FAILED: {failed}")
    except Exception as e:   # noqa: BLE001 — reported, then the verdict
        import traceback
        traceback.print_exc()
        failed = repr(e)
        say(f"FAILED: {failed}")
    device = seen.get("device")
    ok = failed is None and device is not None \
        and device["platform"] == "tpu" and device["count"] == args.chips
    if failed is None and not ok:
        say(f"every phase passed, but the device is {json.dumps(device)}: "
            f"not {args.chips} TPU chip(s)")
    say(f"chip_smoke: {time.perf_counter() - t0:.1f}s in all")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
