"""Reference ``knn_cosine``: exact cosine top-k by a plain numpy scan, in
float64 from the float32 data, independent of the engine. Built on
``chip_smoke.knn_oracle`` / ``unit_rows`` / ``agree`` (PR 21).

An answer is what a ``_search`` response held: document ids, ``_score``
(Elasticsearch's cosine score, ``(1 + cos) / 2``), and ``hits.total``.

Numbers compared (``compare``), each the worst over the sampled requests:

- ``gap``: how far a served hit lies from the exact answer, in score: the
  larger of |served score - reference score of the served document| and of
  how far the reference score of the document served at rank r lies below
  the reference's own r-th best (0 for an exact top-k; ids are not compared
  directly, because documents closer than float32 resolves may swap). The
  two are one number because rounding bounds the second by twice the first:
  apart, the second does not separate float32 from the control on every
  seed (PERF.md). ``compare`` leaves both parts in ``self.parts``.
- ``total_mismatch``: responses whose ``hits.total`` is not ``{k, "eq"}``.
- ``malformed``: responses with the wrong number of hits, a repeated id, an
  id outside the corpus, or scores not in descending order.

``control`` is this reference put in the engine's place, computed one
precision step below float32 at ``highest``: ``high``, three bfloat16
passes (hi*hi + hi*lo + lo*hi, float32 accumulation).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BLOCK = 64


def _unit64(vecs: np.ndarray) -> np.ndarray:
    v = vecs.astype(np.float64)
    return v / np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-12)


def _topk_rows(scores: np.ndarray, k: int):
    """Per row of scores[q, n]: (ids[q, k], vals[q, k]), score descending,
    id ascending among equals."""
    n = scores.shape[1]
    kk = min(k, n)
    ids, vals = [], []
    for row in scores:
        top = np.argpartition(row, n - kk)[n - kk:] if n > kk \
            else np.arange(n)
        top = top[np.lexsort((top, -row[top]))]
        ids.append(top)
        vals.append(row[top])
    return np.stack(ids), np.stack(vals)


class Reference:
    def __init__(self, config: dict, data: dict):
        p = config["reference"]["params"]
        self.k = int(p["k"])
        self.vecs = data["vector_fields"][p["field"]]
        self.n = self.vecs.shape[0]
        self._unit = None

    @property
    def unit(self) -> np.ndarray:
        if self._unit is None:
            self._unit = _unit64(self.vecs)
        return self._unit

    def _cos(self, qrecs) -> np.ndarray:
        """float64 cosines[len(qrecs), n]."""
        q = _unit64(np.stack([r["vector"] for r in qrecs]))
        return q @ self.unit.T

    def compare(self, qrecs, served) -> dict:
        out = {"gap": 0.0, "total_mismatch": 0, "malformed": 0}
        parts = self.parts = {"score_gap": 0.0, "rank_deficit": 0.0}
        for lo in range(0, len(qrecs), BLOCK):
            score = self._cos(qrecs[lo: lo + BLOCK])
            score += 1.0
            score /= 2.0
            _, ref_vals = _topk_rows(score, self.k)
            for j, ans in enumerate(served[lo: lo + BLOCK]):
                ids = np.asarray(ans["ids"], np.int64)
                sc = np.asarray(ans["scores"], np.float64)
                want = min(self.k, self.n)
                if ids.size != want or np.unique(ids).size != ids.size \
                        or ids.min(initial=0) < 0 \
                        or ids.max(initial=0) >= self.n \
                        or (np.diff(sc) > 0).any():
                    out["malformed"] += 1
                    continue
                if ans["total"] != {"value": want, "relation": "eq"}:
                    out["total_mismatch"] += 1
                true = score[j, ids]
                parts["score_gap"] = max(parts["score_gap"],
                                         float(np.abs(sc - true).max()))
                parts["rank_deficit"] = max(
                    parts["rank_deficit"],
                    float((ref_vals[j] - true).max()))
        out["gap"] = max(parts.values())
        return out

    def control(self, qrecs) -> list:
        """The answers of this scan computed in ``high`` precision."""
        bf = ml_dtypes.bfloat16

        def split(x32):
            hi = x32.astype(bf).astype(np.float32)
            lo = (x32 - hi).astype(bf).astype(np.float32)
            return hi, lo

        unit32 = (self.vecs / np.maximum(np.linalg.norm(
            self.vecs, axis=1, keepdims=True), 1e-12)).astype(np.float32)
        a_hi, a_lo = split(unit32)
        out = []
        for lo_ in range(0, len(qrecs), BLOCK):
            q = np.stack([r["vector"] for r in qrecs[lo_: lo_ + BLOCK]])
            q32 = (q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True),
                                  1e-12)).astype(np.float32)
            q_hi, q_lo = split(q32)
            cos = q_hi @ a_hi.T + (q_lo @ a_hi.T + q_hi @ a_lo.T)
            score = (np.float32(1.0) + cos) / np.float32(2.0)
            ids, vals = _topk_rows(score, self.k)
            for i, v in zip(ids, vals):
                out.append({"ids": i.tolist(),
                            "scores": [float(x) for x in v],
                            "total": {"value": int(i.size),
                                      "relation": "eq"}})
        return out
