"""Top-k hit selection on device.

Replaces Lucene's ``TopScoreDocCollector`` heap
(reference: ``search/query/TopDocsCollectorContext.java:215``) with
``jax.lax.top_k`` over the dense per-segment score array. On the TPU
``lax.top_k`` is a full sort of its row, so wide rows go through an exact
*selection* first (:func:`batched_blockwise_topk`): the maxima of
contiguous groups of columns choose the k groups that can hold a winner,
and only those groups are sorted. Tie-break matches Lucene's
ascending-doc-id order because ``lax.top_k`` selects the lowest position
among equal values and both the groups and the candidates gathered from
them stay in doc-id order.

Callers of the selection: the exact kNN scan's blocks
(``parallel/dist_search._knn_shard_scan``, under ``build_knn_step`` and
the fused hybrid step), the IVF step's re-rank window over its whole probed
union at once (``build_ivf_knn_step``, PR 36), the dense text tier
(``ops/tiered_bm25``) and the 1-D kernel below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import in_named_scope

NEG_INF = float("-inf")

#: rows narrower than this are sorted whole: on the chip (PERF.md §6, PR 31)
#: the selection saves most of a 16,384-wide sort and loses on a 4,096-wide
_SELECT_MIN = 1 << 14


def _topk_kernel(n: int, k: int):
    @in_named_scope("topk")
    def topk_kernel(scores, mask):
        """scores float32[n]; mask bool[n] (False → excluded). Returns
        (values float32[k], indices int32[k]); excluded slots carry -inf."""
        masked = jnp.where(mask, scores, NEG_INF)
        # one algorithm, one implementation: the 1-D case is the batched
        # selection's B=1 slice
        vals, idx = batched_blockwise_topk(masked[None], k)
        return vals[0], idx[0]

    return jax.jit(topk_kernel)


def _group_width(n: int, k: int) -> int:
    """Columns per group for a row of ``n`` scores of which ``k`` are
    kept, or 0 where the row is sorted whole. The smallest power of two
    at or above sqrt(n / k), which makes the two sorts (n/g maxima, k·g
    candidates) about equally wide; it has to divide the row, and the
    candidates have to be at most a quarter of it. A function of the
    shape alone, so it is decided while tracing."""
    if n < _SELECT_MIN:
        return 0
    g = 1
    while g * g * k < n:
        g *= 2
    return g if n % g == 0 and k * g <= n // 4 else 0


@in_named_scope("batched_blockwise_topk")
def batched_blockwise_topk(scores, k: int):
    """Exact top-k over the last axis of ``scores`` [B, n], without
    sorting the row: (1) the maximum of each contiguous group of g
    columns (:func:`_group_width`), (2) ``top_k`` over the n/g maxima
    picks k groups, taken in ascending order, (3) those groups are
    gathered, [B, k·g] candidates in column order, (4) ``top_k`` over the
    candidates.

    Exact, ties and -inf padding included. Order elements by (value
    descending, column ascending). If x of the true top-k sat in a group
    that was not picked, each of the k picked groups holds a maximum that
    is larger than x, or equal to x in a group of lower index (groups are
    contiguous and ``top_k`` prefers the lowest position): k elements
    come before x, a contradiction. The candidates are in column order,
    so the last ``top_k``'s lowest-position preference is the lowest
    column: values AND indices equal ``lax.top_k(scores, k)``."""
    B, n = scores.shape
    g = _group_width(n, k)
    if not g:
        vals, idx = jax.lax.top_k(scores, min(k, n))
        return vals, idx.astype(jnp.int32)
    groups = scores.reshape(B, n // g, g)
    with jax.named_scope("group_max"):
        gmax = jnp.max(groups, axis=-1)
    with jax.named_scope("select"):
        _, gid = jax.lax.top_k(gmax, k)
        gid = jnp.sort(gid, axis=-1)
    with jax.named_scope("gather"):
        cand = jnp.take_along_axis(groups, gid[:, :, None], axis=1)
    with jax.named_scope("final"):
        vals, sel = jax.lax.top_k(cand.reshape(B, k * g), k)
        idx = jnp.take_along_axis(gid, sel // g, axis=1) * g + sel % g
    return vals, idx


_CACHE: dict = {}


def get_topk_kernel(n: int, k: int):
    key = (n, k)
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = _topk_kernel(n, k)
    return fn
