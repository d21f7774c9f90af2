"""Top-k hit selection on device.

Replaces Lucene's ``TopScoreDocCollector`` heap
(reference: ``search/query/TopDocsCollectorContext.java:215``) with
``jax.lax.top_k`` over the dense per-segment score array. For large segments a
two-stage blockwise top-k cuts the sort cost: per-block top-k on the VPU, then
a final top-k over the small candidate set. Tie-break matches Lucene's
ascending-doc-id order because ``lax.top_k`` selects the lowest index among
equal values and block candidates are laid out in doc-id order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import in_named_scope

NEG_INF = float("-inf")

_BLOCK = 16384          # scores per block in the two-stage path
_BLOCKWISE_MIN = 1 << 17  # use the two-stage path above this many docs


def _topk_kernel(n: int, k: int):
    use_blocks = n >= _BLOCKWISE_MIN and n % _BLOCK == 0 and k <= _BLOCK

    @in_named_scope("topk")
    def topk_kernel(scores, mask):
        """scores float32[n]; mask bool[n] (False → excluded). Returns
        (values float32[k], indices int32[k]); excluded slots carry -inf."""
        masked = jnp.where(mask, scores, NEG_INF)
        if use_blocks:
            # one algorithm, one implementation: the batched helper's
            # tie-break argument (block-major candidates + top_k's
            # lowest-index preference) covers the 1-D case as its B=1
            # slice
            vals, idx = batched_blockwise_topk(masked[None], k,
                                               block=_BLOCK)
            return vals[0], idx[0]
        vals, idx = jax.lax.top_k(masked, k)
        return vals, idx.astype(jnp.int32)

    return jax.jit(topk_kernel)


@in_named_scope("batched_blockwise_topk")
def batched_blockwise_topk(scores, k: int, block: int = _BLOCK):
    """Exact top-k over the last axis of ``scores`` [B, n] via the
    two-stage blockwise path: per-block ``top_k`` then a final ``top_k``
    over the B × (n/block)·k candidate set.  ``lax.top_k`` cost grows
    with the sorted width, so two narrow selections beat one over n
    (the same trade ops/topk.py's 1-D kernel makes; this is the batched
    form the kNN einsum and the dense-tier scan need).

    Exact: any global top-k element is inside its own block's top-k
    (k ≤ block).  Tie-break stays ascending-index: candidates are laid
    out block-major, within a block ``top_k`` puts the lowest index
    first among equals, and the final ``top_k`` picks the lowest
    candidate position among equals — which is the earlier block.
    Falls back to plain ``top_k`` when the shape doesn't block."""
    n = scores.shape[-1]
    if n % block or n < 2 * block or k > block:
        vals, idx = jax.lax.top_k(scores, min(k, n))
        return vals, idx.astype(jnp.int32)
    nb = n // block
    blocks = scores.reshape(scores.shape[0], nb, block)
    bv, bi = jax.lax.top_k(blocks, k)                # [B, nb, k]
    base = (jnp.arange(nb, dtype=jnp.int32) * block)[None, :, None]
    cand_idx = (bi.astype(jnp.int32) + base).reshape(
        scores.shape[0], nb * k)
    cand_vals = bv.reshape(scores.shape[0], nb * k)
    vals, sel = jax.lax.top_k(cand_vals, k)
    idx = jnp.take_along_axis(cand_idx, sel, axis=1)
    return vals, idx


_CACHE: dict = {}


def get_topk_kernel(n: int, k: int):
    key = (n, k)
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = _topk_kernel(n, k)
    return fn
