"""BM25 scoring kernels: whole-segment eager term scoring on TPU.

This replaces Lucene's per-doc postings-iterator hot loop
(reference: ``search/internal/ContextIndexSearcher.java:210-224`` driving
``BM25Similarity``/``BulkScorer``; Elasticsearch selects
``LegacyBM25Similarity`` in ``index/similarity/SimilarityService.java:59``)
with a dense, fixed-shape XLA program:

1. gather each query term's postings slice (doc ids + term freqs) out of the
   segment's flat CSR arrays with a static padded length ``L``;
2. compute every posting's BM25 contribution on the VPU in one shot::

       idf * (k1 + 1) * tf / (tf + k1 * (1 - b + b * dl / avgdl))

   (the ``(k1 + 1)`` factor matches LegacyBM25Similarity's legacy scaling);
3. scatter-add contributions into a dense per-doc score array (out-of-bounds
   sentinel indices are dropped), plus a matched-unique-terms counter used for
   ``operator=and`` / ``minimum_should_match`` semantics.

Exactness notes vs Lucene: Lucene lossily encodes doc length into one byte
(``SmallFloat``); we keep exact lengths, so absolute scores differ slightly
but ranking semantics are equivalent, and score ties break by ascending doc id
in both (``lax.top_k`` returns the lowest index first).

All shapes are static per (padded segment size, padded slice length) bucket —
callers bucket via ``utils/shapes.py`` so the compile cache stays small.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import in_named_scope

# Elasticsearch defaults (SimilarityService: BM25 with k1=1.2, b=0.75).
DEFAULT_K1 = 1.2
DEFAULT_B = 0.75


@in_named_scope("bm25_score_body")
def bm25_score_body(postings_docs, postings_tf, doc_len, starts, lengths, idf,
                    weights, avgdl, k1, b, *, segment_pad: int, L: int):
    """Score one segment for a bag of query terms into *dense* per-doc
    arrays (pure traced body; ``get_bm25_kernel`` jits it). This is the
    general-query-DSL path — compound queries need dense (scores, mask)
    algebra; the pure top-k hot path uses the scatter-free kernel in
    ``ops/sorted_merge.py`` instead.

    postings_docs: int32[P] flat CSR doc ids (runs sorted by doc id).
    postings_tf:   float32[P] term frequency per posting.
    doc_len:       float32[N] tokens per doc in this field (padding: 0).
    starts:        int32[Q] start offset of each term's postings run;
                   terms absent from the segment use start=P (→ no-op).
    lengths:       int32[Q] postings run length (0 if absent).
    idf:           float32[Q] per-term idf from *shard-level* stats (idf
                   is cross-segment in Lucene, so it cannot be baked into
                   the segment at build time).
    weights:       float32[Q] boost × duplicate-count per unique term.
    avgdl, k1, b:  float32 scalars.

    Returns (scores float32[N], matched int32[N]) where ``matched`` counts
    distinct query term slots hitting each doc.
    """
    P = postings_docs.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]             # [1, L]
    valid = pos < lengths[:, None]                            # [Q, L]
    idx = jnp.where(valid, starts[:, None] + pos, P)
    docs = jnp.take(postings_docs, idx, mode="fill", fill_value=segment_pad)
    tfs = jnp.take(postings_tf, idx, mode="fill", fill_value=0.0)
    dl = jnp.take(doc_len, docs, mode="fill", fill_value=0.0)
    norm = tfs + k1 * (1.0 - b + b * dl / avgdl)
    contrib = (idf * weights)[:, None] * (k1 + 1.0) * tfs / jnp.maximum(norm, 1e-9)
    contrib = jnp.where(valid, contrib, 0.0)
    flat_docs = docs.reshape(-1)
    scores = jnp.zeros(segment_pad, jnp.float32).at[flat_docs].add(
        contrib.reshape(-1), mode="drop")
    matched = jnp.zeros(segment_pad, jnp.int32).at[flat_docs].add(
        valid.reshape(-1).astype(jnp.int32), mode="drop")
    return scores, matched


def _bm25_kernel(segment_pad: int, L: int):
    def bm25_kernel(postings_docs, postings_tf, doc_len, starts, lengths,
                    idf, weights, avgdl, k1, b):
        return bm25_score_body(postings_docs, postings_tf, doc_len, starts,
                               lengths, idf, weights, avgdl, k1, b,
                               segment_pad=segment_pad, L=L)

    return jax.jit(bm25_kernel)


_KERNEL_CACHE: dict = {}


def get_bm25_kernel(segment_pad: int, L: int):
    """Jitted BM25 kernel for a (padded segment size, padded postings slice
    length) bucket; cached so repeated searches reuse the compiled program."""
    key = (segment_pad, L)
    fn = _KERNEL_CACHE.get(key)
    if fn is None:
        fn = _KERNEL_CACHE[key] = _bm25_kernel(segment_pad, L)
    return fn


def idf_weight(n_docs: int, doc_freq) -> np.ndarray:
    """Lucene BM25 idf: ln(1 + (N - df + 0.5) / (df + 0.5))."""
    df = np.asarray(doc_freq, dtype=np.float64)
    return np.log(1.0 + (np.float64(n_docs) - df + 0.5) / (df + 0.5)).astype(np.float32)
