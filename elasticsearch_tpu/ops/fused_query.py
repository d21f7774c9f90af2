"""Fused-query kernel bodies: bool-tree scoring, exact bisect re-score,
and in-device rank fusion — the stages the one-dispatch query planner
(``search/query_planner.py``) composes into a single jitted program.

The reference engine executes a hybrid request as several passes (query
phase per clause, a separate kNN section, host-side RRF, a rescore
phase re-running a second query over the top window). Here every stage
is a fixed-shape traced body over the serving planes' resident tensors,
so ``parallel/dist_search.build_fused_step`` can lower a request's
whole retrieval pipeline into ONE XLA program:

- :func:`bool_bm25_topk_body` — the sorted-merge BM25 kernel
  (``ops/sorted_merge.py``) generalized to a lowered bool tree: each
  term slot is tagged with its owning clause's bit, the merge
  OR-reduces per-doc clause membership alongside the score sum, and
  eligibility (must/filter all present, must_not absent, ≥ msm should
  clauses) is a bitmask test per candidate group. Scoring clauses
  (must/should) contribute to the sum; filter/must_not slots carry
  zero weight and only set bits — Lucene's BooleanWeight semantics as
  one data-parallel pass.
- :func:`bisect_exact_scores` — exact per-candidate scoring from the
  f32 sparse CSR (binary search per (candidate, term), f32 summation
  in the sorted-merge kernel's highest-slot-first order). Shared by the
  block-max pruned step's re-score and the fused rescore stage, so the
  two paths can never drift.
- :func:`rrf_fuse_body` / :func:`sum_fuse_body` — reciprocal-rank /
  linear rank fusion over two ranked candidate lists in unified global
  id space, with the engine-wide (score desc, id asc) tie order and
  first-list-first accumulation order (parity with the host fusion
  loop in ``search/shard_search.py``).
- :func:`knn_raw_to_score` — the plane's raw similarity → ES ``_score``
  transform (the traced twin of ``ShardSearcher._knn_score_from_raw``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import in_named_scope
from .sorted_merge import bm25_merge_candidates

NEG_INF = float("-inf")

#: clause-count ceiling for lowered bool trees: membership rides an
#: int32 bitmask through the merge and the popcount unrolls statically
MAX_BOOL_CLAUSES = 8


@in_named_scope("bool_bm25_topk_body")
def bool_bm25_topk_body(postings_docs, postings_impact, starts, lengths,
                        idfw, slot_bits, req_mask, neg_mask, should_mask,
                        msm, *, n_pad: int, L: int, k: int,
                        with_count: bool = False, nc: int = MAX_BOOL_CLAUSES):
    """Score one lowered bool tree against one shard partition.

    Per-slot inputs (int32[Q]/f32[Q]): ``starts``/``lengths`` postings
    runs, ``idfw`` idf·boost·dup-weight — ZERO for filter/must_not
    slots so they never contribute score — and ``slot_bits`` the owning
    clause's bit (1 << clause_idx). Per-query scalars: ``req_mask``
    bits of clauses that MUST match (must + filter), ``neg_mask`` bits
    that must NOT (must_not), ``should_mask`` + ``msm`` the
    minimum-should-match count over should clauses.

    Returns (values f32[k], local_doc i32[k][, matched i32]). A doc
    whose only matches are filter clauses is a legitimate hit with
    score 0.0 (the reference's constant-score bool), so emptiness is
    signalled by -inf values, never by score."""
    sdocs, gscore, _gcount, is_last, gbits = bm25_merge_candidates(
        postings_docs, postings_impact, starts, lengths, idfw,
        n_pad=n_pad, L=L, slot_bits=slot_bits)
    n = sdocs.shape[0]
    should_hits = jnp.zeros_like(gbits)
    sb = gbits & should_mask
    for ci in range(nc):
        should_hits = should_hits + ((sb >> ci) & 1)
    eligible = ((gbits & req_mask) == req_mask) \
        & ((gbits & neg_mask) == 0) \
        & (should_hits >= msm)
    matched = is_last & (sdocs < n_pad) & eligible
    score = jnp.where(matched, gscore, NEG_INF)
    vals, sel = lax.top_k(score, min(k, n))
    out_docs = jnp.take(sdocs, sel, mode="clip")
    out_docs = jnp.where(vals > NEG_INF, out_docs, n_pad)
    if n < k:
        vals = jnp.pad(vals, (0, k - n), constant_values=NEG_INF)
        out_docs = jnp.pad(out_docs, (0, k - n), constant_values=n_pad)
    if with_count:
        return vals, out_docs.astype(jnp.int32), \
            jnp.sum(matched.astype(jnp.int32))
    return vals, out_docs.astype(jnp.int32)


@in_named_scope("bisect_exact_scores")
def bisect_exact_scores(postings_docs, postings_impact, starts, lengths,
                        idfw, cand_docs, *, n_pad: int):
    """Exact f32 scores of ``cand_docs`` i32[R] (``n_pad`` = empty slot)
    against a bag of term runs: binary search per (candidate, term) over
    the doc-sorted sparse table, then f32 summation in the sorted-merge
    kernel's highest-slot-first order (bit-parity with the eager step's
    shifted-add group reduction — the contract the block-max pruned
    step's re-score already relies on).

    Returns (scores f32[R], found_any bool[R]); ``found_any`` is True
    when ANY term's postings hold the candidate — the rescore stage's
    "rescore query matched" predicate."""
    Q = starts.shape[0]
    R = cand_docs.shape[0]
    p_table = postings_docs.shape[-1]
    bisect_iters = max(int(np.ceil(np.log2(p_table + 1))) + 1, 1)
    doc = cand_docs[:, None]                                 # [R, 1]
    lo = jnp.broadcast_to(starts[None, :], (R, Q))
    hi = lo + lengths[None, :]
    for _ in range(bisect_iters):
        cont = lo < hi
        mid = (lo + hi) // 2
        dv = jnp.take(postings_docs, mid, mode="clip")
        go = dv < doc
        lo = jnp.where(cont & go, mid + 1, lo)
        hi = jnp.where(cont & ~go, mid, hi)
    found = (lo < starts[None, :] + lengths[None, :]) & \
        (jnp.take(postings_docs, lo, mode="clip") == doc)
    c = jnp.where(found,
                  idfw[None, :] * jnp.take(postings_impact, lo,
                                           mode="clip"),
                  0.0)
    score = c[:, Q - 1]
    for qslot in range(Q - 2, -1, -1):
        score = score + c[:, qslot]
    live = cand_docs < n_pad
    return (jnp.where(live, score, 0.0),
            jnp.any(found, axis=1) & live)


def knn_raw_to_score(similarity: str, raw):
    """Plane raw similarity → ES ``_score`` (traced; the scalar host
    twin is ``ShardSearcher._knn_score_from_raw``). The plane's l2 raw
    is ``-‖q-v‖²``, clamped at 0 for float cancellation."""
    if similarity in ("cosine", "cos", "dot_product"):
        return (1.0 + raw) / 2.0
    if similarity == "max_inner_product":
        return jnp.where(raw < 0, 1.0 / (1.0 - raw), raw + 1.0)
    return 1.0 / (1.0 + jnp.maximum(0.0, -raw))              # l2_norm


def _dedupe_first(ids, pad_id: int):
    """True for entries that are a LATER duplicate of an earlier id
    (first occurrence wins — the host fusion dict's insertion order)."""
    n = ids.shape[0]
    eq = ids[None, :] == ids[:, None]                        # [n, n]
    earlier = jnp.tril(jnp.ones((n, n), bool), k=-1)
    return jnp.any(eq & earlier, axis=1) & (ids != pad_id)


def _rank_contrib(ids, list_ids, list_valid, rc):
    """Per-``ids`` RRF contribution of one ranked list: 1/(rc+rank+1)
    where the id sits in the list, else 0 (an id appears at most once
    per list)."""
    w = 1.0 / (rc + jnp.arange(list_ids.shape[0], dtype=jnp.float32)
               + 1.0)
    hit = (ids[:, None] == list_ids[None, :]) & list_valid[None, :]
    return jnp.sum(jnp.where(hit, w[None, :], 0.0), axis=1)


@in_named_scope("rrf_fuse_body")
def rrf_fuse_body(ids_a, ids_b, rc, *, k: int, pad_id: int):
    """Reciprocal-rank fusion of two ranked id lists (unified global id
    space; ``pad_id`` marks empty slots). Contribution order is list a
    then list b (two-term f32 sum — the host fusion loop's order), tie
    order (score desc, id asc). Returns (vals f32[k], ids i32[k],
    sel i32[k]) where ``sel`` indexes into concat(a, b) so callers can
    gather per-candidate payload (e.g. rescore secondaries) along."""
    valid_a = ids_a != pad_id
    valid_b = ids_b != pad_id
    cat = jnp.concatenate([ids_a, ids_b])
    score = _rank_contrib(cat, ids_a, valid_a, rc) + \
        _rank_contrib(cat, ids_b, valid_b, rc)
    dup = _dedupe_first(cat, pad_id)
    live = (cat != pad_id) & ~dup
    score = jnp.where(live, score, NEG_INF)
    return _fused_topk(score, cat, k, pad_id)


@in_named_scope("sum_fuse_body")
def sum_fuse_body(ids_a, vals_a, ids_b, vals_b, *, k: int, pad_id: int):
    """Hybrid linear fusion: docs in both lists sum text + knn scores
    (text first — the host combine dict's accumulation order); docs in
    one list keep that list's score. Same return convention as
    :func:`rrf_fuse_body`."""
    valid_a = ids_a != pad_id
    valid_b = ids_b != pad_id
    cat = jnp.concatenate([ids_a, ids_b])

    def lookup(ids, list_ids, list_valid, list_vals):
        hit = (ids[:, None] == list_ids[None, :]) & list_valid[None, :]
        present = jnp.any(hit, axis=1)
        val = jnp.sum(jnp.where(hit, list_vals[None, :], 0.0), axis=1)
        return present, val

    in_a, va = lookup(cat, ids_a, valid_a, vals_a)
    in_b, vb = lookup(cat, ids_b, valid_b, vals_b)
    score = jnp.where(in_a, va, 0.0) + jnp.where(in_b, vb, 0.0)
    dup = _dedupe_first(cat, pad_id)
    live = (cat != pad_id) & ~dup
    score = jnp.where(live, score, NEG_INF)
    return _fused_topk(score, cat, k, pad_id)


def _fused_topk(score, ids, k: int, pad_id: int):
    """(score desc, id asc) selection over a small fused candidate set;
    -inf slots trail with ``pad_id`` ids. Returns (vals, ids, sel)."""
    n = score.shape[0]
    sel0 = jnp.arange(n, dtype=jnp.int32)
    neg, sids, ssel = lax.sort((-score, ids, sel0), num_keys=2)
    kk = min(k, n)
    vals = -neg[:kk]
    out_ids = jnp.where(vals > NEG_INF, sids[:kk], pad_id)
    out_sel = ssel[:kk]
    if kk < k:
        vals = jnp.pad(vals, (0, k - kk), constant_values=NEG_INF)
        out_ids = jnp.pad(out_ids, (0, k - kk), constant_values=pad_id)
        out_sel = jnp.pad(out_sel, (0, k - kk))
    return vals, out_ids, out_sel


def rescore_combine(mode: str, primary, secondary, matched, in_window,
                    qw, rw):
    """The rescore window's combine (``QueryRescorer`` semantics, all
    five validated ``score_mode`` values): in-window docs the rescore
    query matched combine per ``mode``; everything else — in-window
    misses AND the tail below the window — keeps ``qw·primary``."""
    ps = qw * primary
    rs = rw * secondary
    if mode == "total":
        ns = ps + rs
    elif mode == "multiply":
        ns = ps * rs
    elif mode == "avg":
        ns = (ps + rs) / 2.0
    elif mode == "max":
        ns = jnp.maximum(ps, rs)
    elif mode == "min":
        ns = jnp.minimum(ps, rs)
    else:
        raise ValueError(f"illegal rescore score_mode [{mode}]")
    return jnp.where(in_window & matched, ns, ps)


@in_named_scope("rescore_reorder_body")
def rescore_reorder_body(vals, ids, secondary, matched, qw, rw, window,
                         *, mode: str, k: int, pad_id: int):
    """Fused rescore stage: reorder the top ``window`` (a traced scalar
    — per-request window sizes share one compile) of an already ranked
    candidate list by the combined score; ranks below the window keep
    their original order (with the primary weight still applied).
    ``vals``/``ids`` are the fused ranking (score desc, -inf padded);
    ``secondary``/``matched`` per-candidate rescore-query results.
    Returns (vals f32[k], ids i32[k])."""
    n = vals.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    live = vals > NEG_INF
    in_window = live & (pos < window)
    ns = rescore_combine(mode, vals, secondary, matched, in_window,
                         qw, rw)
    ns = jnp.where(live, ns, NEG_INF)
    # window entries re-sort by (ns desc, id asc) but always PRECEDE the
    # tail, which keeps its original rank order (QueryRescorer appends
    # the tail after the rescored window regardless of score)
    region = jnp.where(live, jnp.where(in_window, 0, 1), 2)
    k2 = jnp.where(in_window, -ns, pos.astype(jnp.float32))
    k3 = jnp.where(in_window, ids, 0)
    _r, _k2, _k3, svals, sids = lax.sort(
        (region, k2, k3, ns, ids), num_keys=3)
    kk = min(k, n)
    out_v = svals[:kk]
    out_i = jnp.where(out_v > NEG_INF, sids[:kk], pad_id)
    if kk < k:
        out_v = jnp.pad(out_v, (0, k - kk), constant_values=NEG_INF)
        out_i = jnp.pad(out_i, (0, k - kk), constant_values=pad_id)
    return out_v, out_i
