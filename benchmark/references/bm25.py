"""Reference ``bm25``: Elasticsearch's BM25 (k1 1.2, b 0.75, idf
``ln(1 + (N - df + 0.5) / (df + 0.5))``) of a bag of terms over one text
field, term at a time in float64 numpy over the seeded postings, independent
of the engine. Built on ``bench.cpu_bm25_search`` / ``_score_one`` and
``chip_smoke.agree`` (PR 21).

An answer is what a ``_search`` response held: document ids, ``_score`` and
``hits.total``.

Numbers compared (``compare``), each the worst over the sampled requests;
gaps are relative to the reference score, floored at 1:

- ``score_gap``: |served score - reference score of the served document|.
- ``rank_deficit``: how far the reference score of the document served at
  rank r lies below the reference's own r-th best score (ids are not
  compared directly: at millions of documents neighbours in the ranking sit
  closer than the engine's stored impacts resolve).
- ``total_mismatch``: responses whose ``hits.total`` is not the exact count
  of documents holding any term of the bag, with relation ``eq``.
- ``malformed``: responses with the wrong number of hits, a repeated id, an
  id outside the corpus, a document that holds no term of the bag, or
  scores not in descending order.

``control`` is this reference put in the engine's place with every
posting's impact (the tf-normalization factor) rounded to float8 e4m3, the
precision step below the bfloat16 in which the engine stores its dense-tier
impacts; accumulation stays float32.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

K1, B = 1.2, 0.75


class Reference:
    def __init__(self, config: dict, data: dict):
        p = config["reference"]["params"]
        self.k = int(p["k"])
        f = data["text_fields"][p["field"]]
        self.f = f
        self.n = int(f["doc_len"].shape[0])
        self.dl = f["doc_len"].astype(np.float64)
        self.avgdl = float(self.dl.sum() / max((self.dl > 0).sum(), 1))
        self.tid = {t: i for i, t in enumerate(f["terms"])}

    def _scores(self, terms, impact_dtype=None):
        """(scores[n], matched bool[n]) of one bag."""
        f = self.f
        acc = np.float64 if impact_dtype is None else np.float32
        scores = np.zeros(self.n, acc)
        matched = np.zeros(self.n, bool)
        for t in set(terms):
            tid = self.tid.get(t)
            if tid is None:
                continue
            st, en = int(f["offsets"][tid]), int(f["offsets"][tid + 1])
            if en == st:
                continue
            docs = f["docs"][st:en]
            tf = f["tf"][st:en].astype(np.float64)
            df = en - st
            idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            impact = (K1 + 1.0) * tf / (
                tf + K1 * (1.0 - B + B * self.dl[docs] / self.avgdl))
            if impact_dtype is not None:
                impact = impact.astype(impact_dtype).astype(np.float32)
            scores[docs] += (terms.count(t) * idf * impact).astype(acc)
            matched[docs] = True
        return scores, matched

    @staticmethod
    def _topk(scores, matched, k: int):
        ids = np.flatnonzero(matched)
        if ids.size > k:
            part = np.argpartition(-scores[ids], k - 1)[:k]
            ids = ids[part]
        ids = ids[np.lexsort((ids, -scores[ids]))]
        return ids, scores[ids]

    def compare(self, qrecs, served) -> dict:
        out = {"score_gap": 0.0, "rank_deficit": 0.0, "total_mismatch": 0,
               "malformed": 0}
        for rec, ans in zip(qrecs, served):
            scores, matched = self._scores(rec["terms"])
            ref_ids, ref_vals = self._topk(scores, matched, self.k)
            ids = np.asarray(ans["ids"], np.int64)
            sc = np.asarray(ans["scores"], np.float64)
            if ids.size != ref_ids.size or np.unique(ids).size != ids.size \
                    or ids.min(initial=0) < 0 \
                    or ids.max(initial=0) >= self.n \
                    or not matched[ids].all() or (np.diff(sc) > 0).any():
                out["malformed"] += 1
                continue
            if ans["total"] != {"value": int(matched.sum()),
                                "relation": "eq"}:
                out["total_mismatch"] += 1
            if not ids.size:
                continue
            true = scores[ids]
            floor = np.maximum(1.0, np.abs(true))
            out["score_gap"] = max(out["score_gap"],
                                   float((np.abs(sc - true) / floor).max()))
            out["rank_deficit"] = max(
                out["rank_deficit"],
                float(((ref_vals - true)
                       / np.maximum(1.0, np.abs(ref_vals))).max()))
        return out

    def control(self, qrecs) -> list:
        """The answers of this reference with float8 e4m3 impacts."""
        out = []
        for rec in qrecs:
            scores, matched = self._scores(
                rec["terms"], impact_dtype=ml_dtypes.float8_e4m3fn)
            ids, vals = self._topk(scores, matched, self.k)
            out.append({"ids": ids.tolist(),
                        "scores": [float(v) for v in vals],
                        "total": {"value": int(matched.sum()),
                                  "relation": "eq"}})
        return out
