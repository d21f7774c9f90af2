"""Both references against hand-computed tiny cases, and each control
against its reference (the control has to come out as not correct)."""

import json
import math
import os

import numpy as np

from conftest import BENCH
from harness.manifest import load_module


def _knn(vecs, k):
    cfg = {"reference": {"params": {"field": "v", "k": k}}}
    data = {"vector_fields": {"v": np.asarray(vecs, np.float32)}}
    return load_module("references", "knn_cosine").Reference(cfg, data)


def test_knn_reference_hand_case():
    ref = _knn([[1, 0], [0, 1], [1, 1], [-1, 0]], 2)
    q = {"vector": np.asarray([1.0, 0.0], np.float32)}
    # cosines: 1, 0, 1/sqrt2, -1 -> scores (1+cos)/2
    exact = {"ids": [0, 2], "scores": [1.0, (1 + 1 / math.sqrt(2)) / 2],
             "total": {"value": 2, "relation": "eq"}}
    got = ref.compare([q], [exact])
    assert got["malformed"] == 0 and got["total_mismatch"] == 0
    assert got["gap"] < 1e-12
    wrong_doc = dict(exact, ids=[0, 1], scores=[1.0, 0.5])
    got = ref.compare([q], [wrong_doc])
    assert abs(got["gap"] - (1 / math.sqrt(2)) / 2) < 1e-12
    assert ref.parts["score_gap"] < 1e-12
    wrong_score = dict(exact, scores=[1.0, 0.8])
    assert ref.compare([q], [wrong_score])["gap"] > 0.05
    assert ref.parts["rank_deficit"] < 1e-12
    assert ref.compare([q], [dict(exact, ids=[0, 0])])["malformed"] == 1
    assert ref.compare([q], [dict(exact, total={"value": 3,
                       "relation": "eq"})])["total_mismatch"] == 1


def test_bm25_reference_hand_case():
    # three docs; term a in docs 0 (tf 1) and 2 (tf 2); term b in doc 1
    field = dict(terms=["a", "b"], df=np.asarray([2, 1], np.int32),
                 offsets=np.asarray([0, 2, 3], np.int64),
                 docs=np.asarray([0, 2, 1], np.int32),
                 tf=np.asarray([1, 2, 1], np.float32),
                 doc_len=np.asarray([2, 1, 3], np.float32))
    cfg = {"reference": {"params": {"field": "body", "k": 10}}}
    ref = load_module("references", "bm25").Reference(
        cfg, {"text_fields": {"body": field}})
    n, avgdl, k1, b = 3, 2.0, 1.2, 0.75

    def score(tf, dl, df):
        idf = math.log(1 + (n - df + 0.5) / (df + 0.5))
        return idf * (k1 + 1) * tf / (tf + k1 * (1 - b + b * dl / avgdl))

    s0, s2 = score(1, 2, 2), score(2, 3, 2)
    order = [2, 0] if s2 > s0 else [0, 2]
    exact = {"ids": order, "scores": sorted([s0, s2], reverse=True),
             "total": {"value": 2, "relation": "eq"}}
    got = ref.compare([{"terms": ["a"]}], [exact])
    assert got == {"score_gap": got["score_gap"], "rank_deficit": 0.0,
                   "total_mismatch": 0, "malformed": 0}
    assert got["score_gap"] < 1e-12
    both = ref.compare([{"terms": ["a", "b"]}], [
        {"ids": [0], "scores": [s0], "total": {"value": 3,
                                               "relation": "eq"}}])
    assert both["malformed"] == 1        # three documents match, one served
    not_matching = dict(exact, ids=[order[0], 1])
    assert ref.compare([{"terms": ["a"]}], [not_matching])["malformed"] == 1


def _cell_parts(name):
    cfg = json.load(open(os.path.join(BENCH, "tests", "rehearsal",
                                      name + ".json")))
    data = load_module("generators", cfg["data"]["generator"]).make(
        cfg["data"]["params"], 11)
    ref = load_module("references", cfg["reference"]["name"]).Reference(
        cfg, data)
    return cfg, data, ref


def test_knn_control_fails_the_limit():
    cfg, data, ref = _cell_parts("tiny_knn")
    q = load_module("generators", "perturbed_rows").Queries(
        {"field": "vec", "noise": 0.15, "body": {"knn": {"k": 10}}},
        data, 11, 1)
    qrecs = [r[1] for r in q.more(0)[:48]]
    got = ref.compare(qrecs, ref.control(qrecs))
    lim = cfg["reference"]["limits"]
    assert any(got[k] > lim[k] for k in got), got


def test_bm25_control_fails_the_limit():
    cfg, data, ref = _cell_parts("tiny_match")
    q = load_module("generators", "mass_bags").Queries(
        {"field": "body", "bag_terms": [3, 5],
         "body": {"query": {"match": {}}}}, data, 11, 1)
    qrecs = [r[1] for r in q.more(0)[:48]]
    got = ref.compare(qrecs, ref.control(qrecs))
    lim = cfg["reference"]["limits"]
    assert any(got[k] > lim[k] for k in got), got


def test_bags_follow_the_seed_and_not_the_clients_order():
    _cfg, data, _ref = _cell_parts("tiny_match")
    params = {"field": "body", "bag_terms": [3, 5],
              "body": {"query": {"match": {}}}}
    mod = load_module("generators", "mass_bags")

    def bodies(seed, order):
        q = mod.Queries(params, data, seed, 4)
        got = {c: q.more(c) for c in order}
        return [r[0] for c in range(4) for r in got[c]]

    a = bodies(2147483999, (0, 1, 2, 3))
    assert len(set(a)) == len(a) == 4 * 64
    assert a == bodies(2147483999, (3, 1, 0, 2))
    assert a != bodies(1, (0, 1, 2, 3))
