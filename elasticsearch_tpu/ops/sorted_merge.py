"""Sorted-merge BM25 top-k: scatter-free, gather-free candidate scoring.

The dense kernel in ``ops/bm25.py`` scatter-adds every posting into a
[N_docs] score array and top-ks the whole corpus — fine for feeding
aggregations a dense mask, but wrong for the pure top-k hot path: TPU
scatters serialize, arbitrary-index gathers from HBM-resident postings
tables are slow, and ``lax.top_k`` over the corpus costs O(N log N).

This kernel is the document-at-a-time analogue, mapped to what the TPU does
well (Lucene's equivalent is the postings-cursor heap inside ``BulkScorer`` —
``search/internal/ContextIndexSearcher.java:210-224``):

1. **dynamic_slice** (a DMA copy, not a gather) pulls each query term's
   postings run — doc ids + *precomputed impact scores* — into a [Q, L]
   tile. Impacts are the query-independent part of BM25,
   ``(k1+1)·tf / (tf + k1·(1-b+b·dl/avgdl))``, materialized per posting at
   segment-build time (the BM25S eager-scoring idea), so query time does no
   doc-length lookups at all; only ``idf·boost`` scales at query time.
2. flatten to [Q*L] and sort by doc id (``lax.sort`` — bitonic, fully
   vectorized);
3. segment-reduce duplicate docs with cumsum + group-boundary bookkeeping:
   a doc matched by multiple terms sums its contributions;
4. ``lax.top_k`` over the Q*L candidates (≪ corpus size). Any doc with a
   non-zero score appears in some run, so this is exact.

Tie-break: group totals are emitted at each group's last slot and tail slots
stay doc-ascending, so equal scores resolve to the lower doc id — Lucene's
order.

Table padding contract: ``postings_docs``/``postings_impact`` must be padded
with sentinel ``doc = n_pad`` entries to at least ``max(starts) + L`` so a
``dynamic_slice`` never clamps into another term's run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from . import in_named_scope

NEG_INF = float("-inf")


#: candidate slots (queries x Q x L) one sorted-merge pass holds at once.
#: The merge keeps ~30 slot-wide i32/f32 arrays live (measured from the
#: TPU compiler's memory analysis: 7.8 GB of temporaries at B=64, Q=8,
#: L=2^17), so 2^23 slots bound a dispatch's transient HBM near 1 GB
#: next to a resident multi-GB plane; wider batches score in sub-batches.
MERGE_TILE_SLOTS = 1 << 23


def vmap_queries(per_query, args, *, slots_per_query: int,
                 tile_slots: int = MERGE_TILE_SLOTS):
    """``jax.vmap(per_query)(*args)`` over the leading (query) axis, in
    sequential sub-batches once the batch's working set passes
    ``tile_slots``. Queries are independent, so results are identical
    either way; batches under the bound trace exactly the plain vmap."""
    n = args[0].shape[0]
    chunk = max(1, tile_slots // max(int(slots_per_query), 1))
    if chunk >= n:
        return jax.vmap(per_query)(*args)
    return lax.map(lambda a: per_query(*a), tuple(args), batch_size=chunk)


def make_impacts(tf: np.ndarray, docs: np.ndarray, doc_len: np.ndarray,
                 avgdl: float, k1: float, b: float) -> np.ndarray:
    """Per-posting query-independent BM25 impact (host-side, at build)."""
    dl = doc_len[docs]
    return ((k1 + 1.0) * tf / (tf + k1 * (1.0 - b + b * dl / avgdl))
            ).astype(np.float32)


@in_named_scope("bm25_merge_candidates")
def bm25_merge_candidates(postings_docs, postings_impact, starts, lengths,
                          idfw, *, n_pad: int, L: int, slot_bits=None):
    """Sorted-merge candidate stage shared by the plain top-k kernel and the
    tiered kernel (``ops/tiered_bm25.py``).

    Returns ``(sdocs i32[Q*L], gscore f32[Q*L], gcount f32[Q*L],
    is_last bool[Q*L])``: candidates sorted by doc id with each doc group's
    summed score/match-count materialized at its *last* slot (other slots
    hold partial prefixes — mask with ``is_last``).

    ``slot_bits`` (optional int32[Q]): a per-slot tag bitmask carried
    through the merge and OR-reduced per doc group — the bool-tree fused
    kernel (``ops/fused_query.py``) tags each term slot with its owning
    clause's bit so per-doc clause membership falls out of the same
    merge that sums scores. When given, a fifth output ``gbits
    int32[Q*L]`` is appended (group OR at the group's last slot, like
    ``gscore``).
    """
    Q = starts.shape[0]

    def slice_run(s):
        return (lax.dynamic_slice(postings_docs, (s,), (L,)),
                lax.dynamic_slice(postings_impact, (s,), (L,)))

    docs, imps = jax.vmap(slice_run)(starts)                  # [Q, L]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    valid = pos < lengths[:, None]
    docs = jnp.where(valid, docs, n_pad)
    contrib = jnp.where(valid, imps * idfw[:, None], 0.0)
    bits = None
    if slot_bits is not None:
        bits = jnp.where(valid, slot_bits[:, None],
                         jnp.int32(0))                       # [Q, L]

    # Combine the Q runs into one doc-ascending sequence. Each run is
    # ALREADY sorted (postings are doc-ordered; masked tails hold the
    # n_pad sentinel), so a log2(Q)-level pairwise merge — positions via
    # binary search, placement via a sorted-unique-index scatter — does
    # the job in O(Q·L·log L) instead of lax.sort's full bitonic
    # network over Q·L elements (hundreds of passes at realistic L;
    # this was the dominant cost of the whole tiered dispatch on TPU).
    # The merge is DETERMINISTIC and stable (left runs' copies precede
    # right runs' for equal doc ids at every level), which pins is_last
    # flags, FP summation order, and tie-break order — a guarantee the
    # replaced lax.sort (is_stable defaulting False) never made.
    # The valid flag needs no channel of its own: real doc ids are
    # strictly below the n_pad sentinel, so validity is recomputed from
    # the merged doc ids (saves one scatter in three).
    items = [(docs[q], contrib[q]) + ((bits[q],) if bits is not None
                                      else ()) for q in range(Q)]
    while len(items) > 1:
        merged = []
        for i in range(0, len(items) - 1, 2):
            da, va = items[i][0], items[i][1]
            db, vb = items[i + 1][0], items[i + 1][1]
            n, m = da.shape[0], db.shape[0]
            pa = jnp.arange(n, dtype=jnp.int32) + \
                jnp.searchsorted(db, da, side="left").astype(jnp.int32)
            pb = jnp.arange(m, dtype=jnp.int32) + \
                jnp.searchsorted(da, db, side="right").astype(jnp.int32)
            out = []
            pairs = [(da, db), (va, vb)]
            if bits is not None:
                pairs.append((items[i][2], items[i + 1][2]))
            for xa, xb in pairs:
                o = jnp.zeros((n + m,), xa.dtype)
                o = o.at[pa].set(xa, unique_indices=True,
                                 indices_are_sorted=True)
                o = o.at[pb].set(xb, unique_indices=True,
                                 indices_are_sorted=True)
                out.append(o)
            merged.append(tuple(out))
        if len(items) % 2:
            merged.append(items[-1])
        items = merged
    sdocs, scontrib = items[0][0], items[0][1]
    sbits = items[0][2] if bits is not None else None
    svalid = (sdocs < n_pad).astype(jnp.float32)

    # Segment-reduce groups of equal doc id (contiguous after the sort).
    # A doc appears in at most Q runs, so every group has <= Q elements:
    # sum them with Q-1 shifted adds instead of a cumsum difference — the
    # cumsum trick reconstructs each group's sum with prefix-dependent
    # rounding, which breaks exact score ties (Lucene tie-break parity
    # needs identical docs to score bitwise-identically).
    nxt = jnp.concatenate([sdocs[1:], jnp.full((1,), -2, sdocs.dtype)])
    is_last = sdocs != nxt
    gscore = scontrib
    gcount = svalid
    gbits = sbits
    for j in range(1, Q):
        shifted_docs = jnp.concatenate(
            [jnp.full((j,), -1, sdocs.dtype), sdocs[:-j]])
        same = shifted_docs == sdocs
        gscore = gscore + jnp.where(
            same, jnp.concatenate([jnp.zeros((j,), scontrib.dtype),
                                   scontrib[:-j]]), 0.0)
        gcount = gcount + jnp.where(
            same, jnp.concatenate([jnp.zeros((j,), svalid.dtype),
                                   svalid[:-j]]), 0.0)
        if gbits is not None:
            gbits = gbits | jnp.where(
                same, jnp.concatenate([jnp.zeros((j,), sbits.dtype),
                                       sbits[:-j]]), jnp.int32(0))
    if sbits is not None:
        return sdocs, gscore, gcount, is_last, gbits
    return sdocs, gscore, gcount, is_last


@in_named_scope("bm25_topk_merge_body")
def bm25_topk_merge_body(postings_docs, postings_impact, starts, lengths,
                         idfw, *, n_pad: int, L: int, k: int,
                         min_should_match: int = 1, with_count: bool = False):
    """Score one query against one shard partition, returning (values f32[k],
    local_doc i32[k]); empty slots carry -inf / n_pad. With ``with_count``
    also returns the scalar i32 number of matching docs (every match is a
    candidate since runs are complete) — the device-side equivalent of
    Lucene's ``TotalHitCountCollector`` without a second pass.

    postings_docs:   int32[P'] flat CSR doc ids (padding: n_pad sentinel).
    postings_impact: float32[P'] precomputed impacts (see make_impacts).
    starts:          int32[Q] run start offsets (absent terms: any valid
                     offset with length 0).
    lengths:         int32[Q] run lengths, clamped to L by the caller.
    idfw:            float32[Q] idf × boost × duplicate-count per term.
    min_should_match: minimum distinct matching term slots per doc.
    """
    sdocs, gscore, gcount, is_last = bm25_merge_candidates(
        postings_docs, postings_impact, starts, lengths, idfw,
        n_pad=n_pad, L=L)
    n = sdocs.shape[0]
    matched = is_last & (sdocs < n_pad) & (gcount >= min_should_match)
    score = jnp.where(matched, gscore, NEG_INF)
    vals, sel = lax.top_k(score, min(k, n))
    out_docs = jnp.take(sdocs, sel, mode="clip")
    out_docs = jnp.where(vals > NEG_INF, out_docs, n_pad)
    if n < k:                       # fewer candidates than requested hits
        vals = jnp.pad(vals, (0, k - n), constant_values=NEG_INF)
        out_docs = jnp.pad(out_docs, (0, k - n), constant_values=n_pad)
    if with_count:
        return vals, out_docs.astype(jnp.int32), \
            jnp.sum(matched.astype(jnp.int32))
    return vals, out_docs.astype(jnp.int32)
