"""Per-stage profile of the tiered BM25 dispatch.

Times each kernel stage in isolation at the headline bench shapes
(B=64, Q=4, L=131072, n_pad=2^23, T=256, C=2^19) to find where a
dispatch's time goes: dispatch overhead, H2D transfer, sparse merge,
candidate gather, dense scan, or the final merges. It runs in one
process on whatever backend jax brings up, from any checkout
(``--cpu`` asks for the CPU platform, ``--small`` for toy shapes):

    python scripts/profile_tpu_kernel.py [--small] [--cpu]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
from jax import lax                                      # noqa: E402


def timeit(label, fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        ts.append(time.perf_counter() - t0)
    med = float(np.median(ts)) * 1e3
    print(f"{label:<42s} {med:9.1f} ms  (min {min(ts)*1e3:.1f})")
    return med


def main():
    small = "--small" in sys.argv
    print(f"devices: {jax.devices()}")
    B, Q, K = 64, 4, 10
    if small:
        n_pad, L, T, C = 1 << 18, 1 << 12, 64, 1 << 15
    else:
        n_pad, L, T, C = 1 << 23, 1 << 17, 256, 1 << 19
    n_blk = n_pad // C
    n_postings = 10 * n_pad
    rng = np.random.RandomState(0)

    # -- 0. dispatch overhead -------------------------------------------
    one = jnp.ones((8,), jnp.float32)
    f_null = jax.jit(lambda x: x + 1)
    timeit("null jit dispatch (dispatch floor)", f_null, one)

    for size, lbl in ((1 << 10, "1KB"), (1 << 20, "1MB"),
                      (1 << 24, "16MB")):
        host = np.zeros(size // 4, np.float32)
        timeit(f"device_put {lbl}", jax.device_put, host)

    # -- stage inputs ---------------------------------------------------
    postings_docs = jnp.asarray(
        np.sort(rng.randint(0, n_pad, n_postings)).astype(np.int32))
    postings_imp = jnp.asarray(
        rng.rand(n_postings).astype(np.float32))
    starts = jnp.asarray(rng.randint(
        0, n_postings - L, (B, Q)).astype(np.int32))
    lengths = jnp.asarray(np.full((B, Q), L, np.int32))
    idfw = jnp.asarray(rng.rand(B, Q).astype(np.float32))
    W = jnp.asarray(rng.rand(B, T).astype(np.float32))
    blocks_host = np.zeros((n_blk, T, C), dtype=np.float32)
    for b in range(n_blk):
        blk = rng.rand(T, C).astype(np.float32)
        blk *= (rng.rand(T, C) < 0.02)
        blocks_host[b] = blk
    dense_blocks = jnp.asarray(blocks_host).astype(jnp.bfloat16)
    del blocks_host
    print(f"shapes: n_pad={n_pad} L={L} T={T} C={C} n_blk={n_blk} "
          f"dense={dense_blocks.nbytes/2**30:.2f}GiB")

    from elasticsearch_tpu.ops.sorted_merge import bm25_merge_candidates
    from elasticsearch_tpu.ops.tiered_bm25 import (
        dense_stream_topk, gather_dense_for_candidates,
        merge_topk_lists, tiered_bm25_topk)

    # -- 1. sparse sorted-merge alone -----------------------------------
    @jax.jit
    def sparse_only(pd, pi, st, ln, iw):
        def per_q(s, l, w):
            return bm25_merge_candidates(pd, pi, s, l, w,
                                         n_pad=n_pad, L=L)
        return jax.vmap(per_q)(st, ln, iw)

    timeit(f"sparse merge (sort {B}x{Q}x{L})", sparse_only,
           postings_docs, postings_imp, starts, lengths, idfw)

    # -- 2. dense scan alone --------------------------------------------
    @jax.jit
    def dense_only(w, blocks):
        return dense_stream_topk(w, blocks, k=K)

    timeit(f"dense scan ({n_blk} blk matmul+top_k)", dense_only,
           W, dense_blocks)

    # -- 2b. dense as ONE matmul + ONE topk (alternative) ---------------
    flat = dense_blocks.transpose(1, 0, 2).reshape(T, n_pad)

    @jax.jit
    def dense_flat(w, fb):
        s = lax.dot_general(w, fb.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(s > 0, s, -jnp.inf)
        return lax.top_k(s, K)

    try:
        timeit("dense ONE matmul+topk (2.1GiB scores)", dense_flat,
               W, flat)
    except Exception as e:
        print(f"dense flat variant failed: {e}")

    # -- 3. candidate dense-gather alone --------------------------------
    cand = jnp.asarray(rng.randint(
        0, n_pad, (B, Q * L)).astype(np.int32))
    rid = jnp.asarray(rng.randint(0, T, (B, Q)).astype(np.int32))
    dw = jnp.asarray(rng.rand(B, Q).astype(np.float32))

    @jax.jit
    def gather_only(blocks, cd, r, w):
        def per_q(c, rr, ww):
            return gather_dense_for_candidates(blocks, c, rr, ww,
                                               n_pad=n_pad)
        return jax.vmap(per_q)(cd, rid, dw)

    timeit(f"candidate dense gather ({B}x{Q*L})", gather_only,
           dense_blocks, cand, rid, dw)

    # -- 4. full tiered kernel ------------------------------------------
    dense_rid = rid
    dense_w = dw

    @jax.jit
    def full(pd, pi, blocks, st, ln, iw, r, w2, w3):
        return tiered_bm25_topk(pd, pi, blocks, st, ln, iw, r, w2, w3,
                                n_pad=n_pad, L=L, k=K)

    timeit("FULL tiered kernel", full, postings_docs, postings_imp,
           dense_blocks, starts, lengths, idfw, dense_rid, dense_w, W)

    # same kernel, per-dispatch args passed as HOST numpy (what the
    # serving path does each request) — the delta is transfer overhead
    h_starts = np.asarray(starts)
    h_lengths = np.asarray(lengths)
    h_idfw = np.asarray(idfw)
    h_rid = np.asarray(dense_rid)
    h_dw = np.asarray(dense_w)
    h_W = np.asarray(W)
    timeit("FULL kernel, host-numpy query args", full,
           postings_docs, postings_imp, dense_blocks,
           h_starts, h_lengths, h_idfw, h_rid, h_dw, h_W)

    # -- 5. L sensitivity ------------------------------------------------
    for L2 in (1 << 12, 1 << 14, 1 << 15):
        st2 = jnp.asarray(rng.randint(
            0, n_postings - L2, (B, Q)).astype(np.int32))
        ln2 = jnp.asarray(np.full((B, Q), L2, np.int32))

        @jax.jit
        def sparse_L2(pd, pi, st, ln, iw, L2=L2):
            def per_q(s, l, w):
                return bm25_merge_candidates(pd, pi, s, l, w,
                                             n_pad=n_pad, L=L2)
            return jax.vmap(per_q)(st, ln, iw)

        timeit(f"sparse merge at L={L2}", sparse_L2,
               postings_docs, postings_imp, st2, ln2, idfw)


if __name__ == "__main__":
    main()
