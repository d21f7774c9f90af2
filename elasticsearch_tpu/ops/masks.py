"""Match-mask kernels: postings runs → dense per-doc boolean masks.

Used by filter-context queries (term/terms/exists/range as filters —
reference: Lucene's ConstantScoreQuery under
``index/query/TermQueryBuilder.java`` etc.) where no BM25 score is needed,
only set membership. Same CSR gather + OOB-drop scatter pattern as
``ops/bm25.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import in_named_scope


def _postings_match_kernel(segment_pad: int, L: int):
    @in_named_scope("postings_match")
    def postings_match_kernel(postings_docs, starts, lengths):
        """Count, per doc, how many of the Q postings runs contain it.

        Returns int32[N]; callers derive masks (>0 → any, ==Q → all).
        """
        P = postings_docs.shape[0]
        pos = jnp.arange(L, dtype=jnp.int32)[None, :]
        valid = pos < lengths[:, None]
        idx = jnp.where(valid, starts[:, None] + pos, P)
        docs = jnp.take(postings_docs, idx, mode="fill", fill_value=segment_pad)
        matched = jnp.zeros(segment_pad, jnp.int32).at[docs.reshape(-1)].add(
            valid.reshape(-1).astype(jnp.int32), mode="drop")
        return matched

    return jax.jit(postings_match_kernel)


def _range_mask_kernel(segment_pad: int):
    @in_named_scope("range_mask")
    def range_mask_kernel(vals_off, docs, lo, hi):
        """Mask of docs having any (value - base) within [lo, hi].

        Bounds are float32 offsets relative to the field's per-segment base;
        the host adjusts open bounds via nextafter and handles exactness
        (see ``NumericFieldData``). Padded pairs carry doc=N (dropped).
        """
        in_range = (vals_off >= lo) & (vals_off <= hi)
        mask = jnp.zeros(segment_pad, jnp.bool_).at[docs].max(
            in_range, mode="drop")
        return mask

    return jax.jit(range_mask_kernel)


_MATCH_CACHE: dict = {}
_RANGE_CACHE: dict = {}


def get_postings_match_kernel(segment_pad: int, L: int):
    key = (segment_pad, L)
    fn = _MATCH_CACHE.get(key)
    if fn is None:
        fn = _MATCH_CACHE[key] = _postings_match_kernel(segment_pad, L)
    return fn


def get_range_mask_kernel(segment_pad: int):
    fn = _RANGE_CACHE.get(segment_pad)
    if fn is None:
        fn = _RANGE_CACHE[segment_pad] = _range_mask_kernel(segment_pad)
    return fn
