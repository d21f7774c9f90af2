"""Tiered BM25 top-k: dense Zipf-head scoring + sorted-merge tail, exact.

The sorted-merge kernel (``ops/sorted_merge.py``) slices each query term's
postings run into a fixed [Q, L] tile. On a Zipfian corpus the head terms
have df ≈ N, so L — and with it the per-query sort — explodes (round-1
verdict: the bench dodged this with a df cap; Lucene handles it with
block-max WAND pruning inside ``BulkScorer`` —
``search/internal/ContextIndexSearcher.java:210-224``).

TPU-native answer: split the vocabulary by document frequency.

- **Dense tier** (df > threshold — the few hundred Zipf-head terms that own
  most postings): per-term *dense* impact rows, bf16[n_pad], stored
  block-major [n_blk, T, C]. A query batch scores them as a streaming
  matmul ``W[B, T] @ block[T, C]`` with a running top-k carried through a
  ``lax.scan`` — pure MXU + top_k, no scatter, no sort, O(T·N) HBM traffic
  amortized over the whole batch.
- **Sparse tier** (df ≤ threshold): the existing sorted-merge candidate
  stage, whose L is now *bounded by the threshold* regardless of corpus
  size.

**Exact combination.** Every doc matching any sparse term appears as a
merge candidate (runs are complete), so its full score = sparse group sum +
its dense-tier contributions, added by *gathering* the candidate's dense
row values (Qd small gathers, no scatter). Docs matching only dense terms
are covered by the dense-only streaming top-k. For a non-candidate doc x in
the true top-k, any doc beating x's dense-only score either is a
non-candidate that also beats x globally or a candidate whose true score is
at least its dense score — so fewer than k docs can push x out of the
dense-only top-k without pushing it out of the true top-k. Union + dedup +
re-top-k of the two k-lists is therefore exact.

Tie-break: the final merge sorts (score desc, global candidate order asc),
where both lists carry doc-ascending order — Lucene's tie order.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import in_named_scope
from .topk import batched_blockwise_topk
from .sorted_merge import bm25_merge_candidates, vmap_queries

NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# host-side tier construction
# ---------------------------------------------------------------------------


def split_tiers(shard: dict, *, dense_threshold: int,
                max_dense_terms: int = 512) -> dict:
    """Split one shard's CSR postings into sparse CSR + dense-term list.

    Returns a dict with the sparse-tier CSR (``docs``/``tf``/``offsets``/
    ``df`` shrunk to tail terms only — head postings leave the table
    entirely) plus ``dense_tids`` (original term ids of the dense tier,
    df-descending) for row building.
    """
    df = shard["df"]
    dense_mask = df > dense_threshold
    dense_tids = np.nonzero(dense_mask)[0]
    if dense_tids.size > max_dense_terms:
        # keep the heaviest; overflow terms fall back to the sparse tier
        order = np.argsort(-df[dense_tids], kind="stable")
        keep = dense_tids[order[:max_dense_terms]]
        dense_mask = np.zeros_like(dense_mask)
        dense_mask[keep] = True
        dense_tids = np.sort(keep)
    else:
        dense_tids = np.sort(dense_tids)

    offsets = shard["offsets"]
    keep_posting = np.ones(shard["docs"].shape[0], bool)
    for t in dense_tids:
        keep_posting[offsets[t]: offsets[t + 1]] = False
    new_df = df.copy()
    new_df[dense_mask] = 0
    new_offsets = np.zeros_like(offsets)
    np.cumsum(new_df, out=new_offsets[1:])
    return dict(
        docs=shard["docs"][keep_posting],
        tf=shard["tf"][keep_posting],
        offsets=new_offsets, df=new_df,
        dense_tids=dense_tids.astype(np.int64),
        sparse_max_df=int(new_df.max()) if new_df.size else 0)


def build_dense_rows(shard: dict, dense_tids: np.ndarray, impacts: np.ndarray,
                     *, n_pad: int, block: int,
                     t_pad: int) -> np.ndarray:
    """bf16 impact rows for the dense tier, block-major [n_blk, t_pad, C].

    ``impacts`` are the per-posting query-independent BM25 impacts for the
    ORIGINAL (unsplit) postings table, aligned with ``shard['docs']``.
    Fills the bf16 array directly (no f32 [T, N] transient — that would be
    gigabytes at realistic corpus sizes).
    """
    n_blk = -(-n_pad // block)
    out = np.zeros((n_blk, t_pad, block), dtype=jnp.bfloat16)
    offsets = shard["offsets"]
    docs_all = shard["docs"]
    for r, t in enumerate(dense_tids):
        st, en = int(offsets[t]), int(offsets[t + 1])
        d = docs_all[st:en]
        out[d // block, r, d % block] = \
            impacts[st:en].astype(jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# device kernel pieces
# ---------------------------------------------------------------------------


@in_named_scope("dense_stream_topk")
def dense_stream_topk(W, dense_blocks, *, k: int,
                      min_should_match: int = 1, u_ids=None):
    """Batched streaming top-k over the dense tier.

    W:            f32[B, T] per-query idf·boost weights over dense rows
                  (f32[B, U] over the used-row slots with ``u_ids``).
    dense_blocks: bf16[n_blk, T, C] block-major impact rows.
    u_ids:        optional i32[U] used-row ids: each streamed block is
                  narrowed to those rows as it is read, so only U of the
                  T rows move and no [n_blk, U, C] working set is ever
                  materialized.
    Returns (vals f32[B, k], docs i32[B, k], n_matched i32[B]) of docs
    scored by dense terms alone (unmatched docs masked to -inf);
    ``n_matched`` counts ALL dense-tier-matched docs, not just the top-k.
    """
    B = W.shape[0]
    C = dense_blocks.shape[2]
    need_count = min_should_match > 1
    Wpos = (W > 0).astype(jnp.float32)

    def step(carry, xs):
        best_v, best_i, n_matched = carry
        blk_idx, blk = xs
        if u_ids is not None:
            blk = jnp.take(blk, u_ids, axis=0)
        s = lax.dot_general(W, blk.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if need_count:
            cnt = lax.dot_general(Wpos, (blk > 0).astype(jnp.float32),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            s = jnp.where(cnt >= min_should_match, s, NEG_INF)
        # a matched doc always scores > 0 (impacts > 0, idf > 0)
        s = jnp.where(s > 0, s, NEG_INF)
        n_matched = n_matched + jnp.sum((s > NEG_INF).astype(jnp.int32),
                                        axis=1)
        v, i = batched_blockwise_topk(s, min(k, C))
        gi = (i + blk_idx * C).astype(jnp.int32)
        if v.shape[1] < k:
            v = jnp.pad(v, ((0, 0), (0, k - v.shape[1])),
                        constant_values=NEG_INF)
            gi = jnp.pad(gi, ((0, 0), (0, k - gi.shape[1])))
        cat_v = jnp.concatenate([best_v, v], axis=1)
        cat_i = jnp.concatenate([best_i, gi], axis=1)
        # earlier blocks sit first, so top_k's lowest-index tie preference
        # keeps doc-ascending tie order
        nv, sel = lax.top_k(cat_v, k)
        ni = jnp.take_along_axis(cat_i, sel, axis=1)
        return (nv, ni, n_matched), None

    n_blk = dense_blocks.shape[0]
    init = (jnp.full((B, k), NEG_INF, jnp.float32),
            jnp.zeros((B, k), jnp.int32),
            jnp.zeros((B,), jnp.int32))
    (vals, docs, n_matched), _ = lax.scan(
        step, init, (jnp.arange(n_blk, dtype=jnp.int32), dense_blocks))
    return vals, docs, n_matched


@in_named_scope("gather_dense_for_candidates")
def gather_dense_for_candidates(dense_blocks, cand_docs, dense_rid, dense_w,
                                *, n_pad: int, u_ids=None):
    """Per-candidate dense-tier contributions for ONE query.

    dense_blocks: bf16[n_blk, T, C]; cand_docs: i32[M] (n_pad = absent);
    dense_rid/dense_w: i32[Qd] / f32[Qd] (w = 0 on padding slots);
    with ``u_ids`` i32[U], ``dense_rid`` holds slots into it.
    Returns (add f32[M], match_cnt f32[M]).
    """
    C = dense_blocks.shape[2]
    safe = jnp.minimum(cand_docs, n_pad - 1)
    blk_i = safe // C
    off = safe % C
    add = jnp.zeros(cand_docs.shape, jnp.float32)
    cnt = jnp.zeros(cand_docs.shape, jnp.float32)
    Qd = dense_rid.shape[0]
    for j in range(Qd):
        rid = dense_rid[j] if u_ids is None else u_ids[dense_rid[j]]
        row_vals = dense_blocks[blk_i, rid, off].astype(jnp.float32)
        w = dense_w[j]
        hit = (row_vals > 0) & (w > 0) & (cand_docs < n_pad)
        add = add + jnp.where(hit, w * row_vals, 0.0)
        cnt = cnt + jnp.where(hit, 1.0, 0.0)
    return add, cnt


@in_named_scope("merge_topk_lists")
def merge_topk_lists(vals_a, docs_a, vals_b, docs_b, *, k: int,
                     n_pad: int):
    """Exact union of two per-query top-k lists that may share docs (the
    candidate list's score dominates on overlap). Returns (vals, docs)."""
    docs = jnp.concatenate([docs_a, docs_b], axis=-1)
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    docs = jnp.where(vals > NEG_INF, docs, n_pad)
    # group duplicates: sort by (doc asc, score desc) then drop non-first
    sd, sv = lax.sort((docs, -vals), num_keys=2)
    sv = -sv
    prev = jnp.concatenate(
        [jnp.full(sd.shape[:-1] + (1,), -1, sd.dtype), sd[..., :-1]],
        axis=-1)
    dup = sd == prev
    sv = jnp.where(dup | (sd >= n_pad), NEG_INF, sv)
    # final order: score desc, doc asc
    fv, fd = lax.sort((-sv, sd), num_keys=2)
    return -fv[..., :k], fd[..., :k]


@in_named_scope("tiered_bm25_topk")
def tiered_bm25_topk(postings_docs, postings_impact, dense_blocks,
                     starts, lengths, idfw, dense_rid, dense_w, W,
                     *, n_pad: int, L: int, k: int,
                     min_should_match: int = 1, with_count: bool = False,
                     u_ids=None):
    """Full tiered scoring of a query batch against ONE shard partition.

    Shapes: starts/lengths i32[B, Q], idfw f32[B, Q], dense_rid i32[B, Qd],
    dense_w f32[B, Qd], W f32[B, T]. With ``u_ids`` i32[U] (the batch's
    used dense rows) ``W`` is f32[B, U] and ``dense_rid`` holds slots
    into ``u_ids``. Returns (vals f32[B, k],
    docs i32[B, k]) — plus i32[B] exact match counts when ``with_count``
    (total = sparse candidates + dense-matched − overlap, each tier counted
    in its own full pass; requires min_should_match == 1, where a doc's
    tier membership alone decides matching)."""
    if with_count and min_should_match != 1:
        raise ValueError("with_count requires min_should_match == 1")

    def per_query(st_q, ln_q, iw_q, rid_q, dw_q):
        sdocs, gscore, gcount, is_last = bm25_merge_candidates(
            postings_docs, postings_impact, st_q, ln_q, iw_q,
            n_pad=n_pad, L=L)
        add, cnt = gather_dense_for_candidates(
            dense_blocks, sdocs, rid_q, dw_q, n_pad=n_pad, u_ids=u_ids)
        gscore = gscore + add
        gcount = gcount + cnt
        matched = is_last & (sdocs < n_pad) & (gcount >= min_should_match)
        score = jnp.where(matched, gscore, NEG_INF)
        n = sdocs.shape[0]
        vals, sel = lax.top_k(score, min(k, n))
        out_docs = jnp.take(sdocs, sel, mode="clip")
        out_docs = jnp.where(vals > NEG_INF, out_docs, n_pad)
        if n < k:
            vals = jnp.pad(vals, (0, k - n), constant_values=NEG_INF)
            out_docs = jnp.pad(out_docs, (0, k - n), constant_values=n_pad)
        # candidates double-counted by the dense tier's own pass
        overlap = jnp.sum((matched & (cnt > 0)).astype(jnp.int32))
        return vals, out_docs.astype(jnp.int32), \
            jnp.sum(matched.astype(jnp.int32)) - overlap

    cand_vals, cand_docs, cand_net = vmap_queries(
        per_query, (starts, lengths, idfw, dense_rid, dense_w),
        slots_per_query=starts.shape[1] * L)
    dense_vals, dense_docs, dense_n = dense_stream_topk(
        W, dense_blocks, k=k, min_should_match=min_should_match,
        u_ids=u_ids)
    vals, docs = merge_topk_lists(cand_vals, cand_docs, dense_vals,
                                  dense_docs, k=k, n_pad=n_pad)
    if with_count:
        return vals, docs, cand_net + dense_n
    return vals, docs
