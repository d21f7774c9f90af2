"""Data generator ``zipf_text``: a Zipf-distributed postings table for one
text field, built directly in term-major CSR order. After
``elasticsearch_tpu/utils/synth.synthetic_csr_corpus_fast`` (it is the data,
and a later PR must not be able to change it); terms are named ``t<rank>``.

Per-term document frequencies follow the Zipf pmf analytically and are
exactly the same for every seed: a run's documents are drawn distinct (a
sorted sample with repeats from ``n - df + 1`` slots, spread by rank) at the
document frequency the original reaches in expectation, where the original
drops duplicates at random and so moves every df, and with them the postings
count and the shape of every compiled program, with the seed. The
seed moves which documents hold a term, the term frequencies and the
document lengths.

Parameters (``data`` in the configuration file): ``docs``, ``vocab``,
``mean_doc_tokens``, ``zipf_s``, ``field``.
"""

from __future__ import annotations

import numpy as np


def make(params: dict, seed: int) -> dict:
    n_docs, vocab = int(params["docs"]), int(params["vocab"])
    avg_dl, zipf_s = int(params["mean_doc_tokens"]), float(params["zipf_s"])
    rng = np.random.default_rng([int(seed), 1])
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    pmf = ranks ** (-zipf_s)
    pmf /= pmf.sum()
    drawn = np.minimum(n_docs, np.maximum(1, np.round(pmf * n_docs * avg_dl)))
    # the original draws that many documents with repeats and drops the
    # repeats; its expected document frequency, taken here exactly
    df = np.maximum(1, np.round(
        n_docs * -np.expm1(-drawn / n_docs))).astype(np.int64)
    p_total = int(df.sum())
    # sorted uniform doc ids per run: normalized cumulative sums of
    # exponential gaps are order statistics of uniforms; computed in place
    gaps = rng.standard_exponential(p_total + vocab)
    run_ends = np.cumsum(df + 1)
    run_starts = run_ends - (df + 1)
    first_gap = gaps[run_starts].copy()
    g = np.cumsum(gaps, out=gaps)
    seg_base = g[run_starts] - first_gap
    g -= np.repeat(seg_base, df + 1)
    seg_total = g[run_ends - 1].copy()
    g /= np.repeat(seg_total, df + 1)
    keep = np.ones(p_total + vocab, bool)
    keep[run_ends - 1] = False
    u = g[keep]
    del gaps, g, keep
    # distinct, ascending doc ids per run: a sorted sample with repeats
    # from n - df + 1 slots, plus the rank within the run
    starts0 = np.cumsum(df) - df
    rank = np.arange(p_total, dtype=np.int64) - np.repeat(starts0, df)
    span = np.repeat(n_docs - df + 1, df)
    docs = (np.minimum((u * span).astype(np.int64), span - 1)
            + rank).astype(np.int32)
    del u, rank, span
    new_df = df.astype(np.int32)
    offsets = np.zeros(vocab + 1, np.int64)
    np.cumsum(new_df, out=offsets[1:])
    tf = (1.0 + rng.poisson(0.35, docs.shape[0])).astype(np.float32)
    doc_len = np.maximum(1, rng.poisson(avg_dl, n_docs)).astype(np.float32)
    field = dict(terms=[f"t{t}" for t in range(vocab)], df=new_df,
                 offsets=offsets, docs=docs, tf=tf, doc_len=doc_len)
    return {"n_docs": n_docs, "text_fields": {params["field"]: field},
            "vector_fields": {}}
