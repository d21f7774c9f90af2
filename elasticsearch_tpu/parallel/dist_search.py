"""Distributed query execution: shard-parallel scoring + ICI top-k reduce.

Re-design of the reference's scatter-gather search coordination
(``action/search/AbstractSearchAsyncAction.java:70`` fans a query out to every
shard over TCP; ``SearchPhaseController.java:155-219`` merges per-shard
``TopDocs`` on the coordinating node) as a *single jitted SPMD program* over a
``(replica, shard)`` mesh:

- corpus arrays (CSR postings / doc lengths / vector matrices) live
  device-resident, partitioned over the ``shard`` axis;
- a batch of queries is partitioned over the ``replica`` axis (each replica
  group owns a full corpus copy — ES's replica read scaling);
- inside ``shard_map`` every device scores its shard partition locally
  (the BM25 eager-scoring kernel / an einsum for kNN), takes a local top-k,
  then the global top-k is reduced with ``all_gather`` + ``lax.top_k`` over
  the ``shard`` axis — the ICI equivalent of the coordinator's
  ``TopDocs.merge`` heap (no host round-trip, no TCP).

Tie-break parity: candidates are concatenated in shard order and
``lax.top_k`` prefers the lowest index among equal values, so ties resolve by
(shard id, local doc id) ascending — the same global order as the
reference's ``ScoreDoc`` shard-index tie-break.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import tracing as _tracing
from ..ops import in_named_scope
from ..ops.bm25 import DEFAULT_B, DEFAULT_K1, idf_weight
from ..ops.fused_query import (bisect_exact_scores, bool_bm25_topk_body,
                               knn_raw_to_score, rescore_reorder_body,
                               rrf_fuse_body, sum_fuse_body)
from ..ops.sorted_merge import (bm25_topk_merge_body, make_impacts,
                                vmap_queries)
from ..ops.tiered_bm25 import (build_dense_rows, split_tiers,
                               tiered_bm25_topk)
from ..ops.topk import batched_blockwise_topk
from ..utils.shapes import round_up_multiple, round_up_pow2
from .mesh import AXIS_REPLICA, AXIS_SHARD

NEG_INF = float("-inf")


#: XLA:CPU runs the in-process collective rendezvous (all_gather/psum
#: over the virtual-device mesh) without a hardware stream order:
#: two threads executing multi-device programs concurrently — the
#: micro-batcher's PIPELINE_DEPTH=2 dispatchers, or a text and a kNN
#: dispatcher in different batchers — can interleave participants
#: across programs and deadlock (both threads park inside execute;
#: seen on the 2-device serving bench). Serialize multi-device
#: executions process-wide on CPU, holding the lock THROUGH completion
#: so the collective epoch finishes before the next program starts.
#: Real accelerator backends order collectives on device streams, and
#: single-device programs have no collectives — both skip the lock, so
#: production TPU serving keeps concurrent dispatch. Host prep still
#: pipelines with device execution (the lock covers only the XLA call).
_CPU_COLLECTIVE_LOCK = threading.Lock()


def _run_step(serial: bool, step, *args):
    """Execute a jitted step; under ``serial`` (multi-device mesh on a
    CPU backend) the dispatch is serialized process-wide and synced
    before the lock releases — see ``_CPU_COLLECTIVE_LOCK``."""
    if serial:
        with _CPU_COLLECTIVE_LOCK:
            out = step(*args)
            jax.block_until_ready(out)
        return out
    return step(*args)


def _serial_dispatch_required(mesh: Mesh) -> bool:
    return (int(mesh.devices.size) > 1
            and jax.devices()[0].platform == "cpu")


def host_serve_enabled() -> bool:
    """CPU backends keep a host-native serving path (eager CSR scorer /
    BLAS blocked scan) by default — it beats XLA:CPU outright.
    ``ES_TPU_PLANE_HOST_SERVE=0`` disables that fallback so serving runs
    the jitted SPMD path even on a CPU backend: the MULTICHIP bench (and
    the mesh-parity tests) measure the sharded device path itself, which
    the host scorers would otherwise bypass."""
    import os
    return os.environ.get("ES_TPU_PLANE_HOST_SERVE", "1").lower() \
        not in ("0", "false")


# ---------------------------------------------------------------------------
# SPMD step builders
# ---------------------------------------------------------------------------


def _jit_step(step, family: str):
    """The jitted SPMD step under its kernel family's name: the XLA module
    is ``jit_<family>`` (``jit_knn_exact``, not ``jit_body``), a name a
    profiler trace can be reduced by whatever the batch shape. Each
    builder's ``body`` opens the scope ``<family>``; its parts open
    ``score`` (kNN: ``scores`` and ``block_topk``) and ``merge`` under
    it."""
    step.__name__ = step.__qualname__ = family
    return jax.jit(step)


@in_named_scope("merge")
def _global_topk_reduce(vals, idx, *, s_loc: int, kk: int, n_pad: int,
                        out_k: Optional[int] = None, payload=()):
    """Shared ICI reduce: globalize local doc ids, merge the device's own
    shards, then all_gather + top_k over the shard axis. vals/idx are
    [B_loc, S_loc, kk]; returns ([B_loc, out_k], [B_loc, out_k]).

    ``out_k`` (default ``kk``) is the GLOBAL result width: per-shard lists
    cap at that shard's pad (kk ≤ n_pad) but the union across shards can
    satisfy a larger k, so intermediate merges keep min(out_k, available)
    candidates instead of collapsing to the per-shard cap.

    ``payload``: optional tuple of [B_loc, S_loc, kk] per-candidate
    channels (e.g. the fused step's rescore secondaries) gathered along
    the same selections; when non-empty the return grows a third
    element, a tuple of [B_loc, out_k] arrays."""
    out_k = kk if out_k is None else out_k
    b_loc = vals.shape[0]
    shard0 = lax.axis_index(AXIS_SHARD) * s_loc
    sid = shard0 + jnp.arange(s_loc, dtype=jnp.int32)
    gidx = idx + sid[None, :, None] * n_pad
    vals = vals.reshape(b_loc, s_loc * kk)
    gidx = gidx.reshape(b_loc, s_loc * kk)
    pls = [p.reshape(b_loc, s_loc * kk) for p in payload]
    if s_loc > 1 and s_loc * kk > out_k:
        vals, sel = lax.top_k(vals, out_k)
        gidx = jnp.take_along_axis(gidx, sel, axis=1)
        pls = [jnp.take_along_axis(p, sel, axis=1) for p in pls]
    av_all = lax.all_gather(vals, AXIS_SHARD, axis=1, tiled=True)
    ai_all = lax.all_gather(gidx, AXIS_SHARD, axis=1, tiled=True)
    pl_all = [lax.all_gather(p, AXIS_SHARD, axis=1, tiled=True)
              for p in pls]
    gvals, gsel = lax.top_k(av_all, min(out_k, av_all.shape[1]))
    gdocs = jnp.take_along_axis(ai_all, gsel, axis=1)
    if payload:
        gpl = tuple(jnp.take_along_axis(p, gsel, axis=1) for p in pl_all)
        return gvals, gdocs, gpl
    return gvals, gdocs


def build_bm25_topk_step(mesh: Mesh, *, n_pad: int, Q: int, L: int, k: int,
                         n_shards: int, min_should_match: int = 1,
                         with_count: bool = False):
    """Jitted distributed step: batched BM25 scoring + global top-k.

    Global input shapes (S = n_shards, B = query batch):
      postings_docs   int32[S, P'] sharded over ``shard`` (P' padded with
                      sentinel doc = n_pad entries; see sorted_merge.py)
      postings_impact f32[S, P']   sharded over ``shard`` (precomputed
                      query-independent BM25 impacts)
      starts          i32[B, S, Q] sharded over (``replica``, ``shard``)
      lengths         i32[B, S, Q] sharded over (``replica``, ``shard``)
      idfw            f32[B, Q]    sharded over ``replica``
                      (global idf × boost per term)

    Returns (values f32[B, k], global_doc i32[B, k]) where
    ``global_doc = shard_idx * n_pad + local_doc``.
    """
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)

    @in_named_scope("bm25_topk")
    def body(pd, pi, st, ln, idfw):
        assert st.shape[-1] == Q, (
            f"starts last dim {st.shape[-1]} != step Q={Q}")

        @in_named_scope("score")
        def per_shard(pd_s, pi_s, st_s, ln_s):
            def per_query(st_q, ln_q, iw_q):
                # scatter-free sorted-merge scoring: top-k over the Q*L
                # candidate postings, not the whole shard partition
                return bm25_topk_merge_body(
                    pd_s, pi_s, st_q, ln_q, iw_q, n_pad=n_pad, L=L, k=kk,
                    min_should_match=min_should_match,
                    with_count=with_count)

            return vmap_queries(per_query, (st_s, ln_s, idfw),
                                slots_per_query=Q * L)       # [B_loc, kk]

        out = jax.vmap(per_shard, in_axes=(0, 0, 1, 1),
                       out_axes=1)(pd, pi, st, ln)
        # vals/idx: [B_loc, S_loc, kk]
        gvals, gdocs = _global_topk_reduce(out[0], out[1], s_loc=s_loc,
                                           kk=kk, n_pad=n_pad, out_k=out_k)
        if with_count:
            counts = lax.psum(jnp.sum(out[2], axis=1), AXIS_SHARD)
            return gvals, gdocs, counts
        return gvals, gdocs

    shard_corpus = P(AXIS_SHARD, None)
    out_specs = (P(AXIS_REPLICA, None), P(AXIS_REPLICA, None)) \
        + ((P(AXIS_REPLICA),) if with_count else ())
    step = shard_map(
        body, mesh=mesh,
        in_specs=(shard_corpus, shard_corpus,
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, None)),
        out_specs=out_specs,
        check_vma=False)
    return _jit_step(step, "bm25_topk")


def build_tiered_bm25_step(mesh: Mesh, *, n_pad: int, Q: int, L: int, k: int,
                           T_pad: int, C: int, n_shards: int,
                           min_should_match: int = 1,
                           with_count: bool = False,
                           U: Optional[int] = None):
    """Jitted distributed tiered step (``ops/tiered_bm25.py``): sparse
    sorted-merge + dense Zipf-head streaming matmul per shard, then the ICI
    all_gather/top_k reduce.

    Additional global shapes vs :func:`build_bm25_topk_step`:
      dense_blocks bf16[S, n_blk, T_pad, C]  sharded over ``shard``
      dense_rid    i32[B, S, Q]              (row ids into the shard's dense
                                              tier; weight-0 slots inert)
      dense_w      f32[B, S, Q]
      W            f32[B, S, T_pad]          (per-query dense row weights)

    ``U``: used-row gather width. A query batch touches only the dense
    rows its terms map to — usually a small subset of T_pad — so when
    ``U < T_pad`` each streamed block is narrowed to the batch's used
    rows (``u_ids i32[S, U]``) as it is read: HBM traffic and MXU work
    drop from T_pad·n_pad to U·n_pad per dispatch, with no gathered
    copy of the tier (a [n_blk, U, C] working set cost 3.3 GB of
    temporaries at n_pad=2^23, U=64). ``W`` / ``dense_rid`` are then
    slot-indexed ([B, S, U] / slot ids). Exact: unused rows have zero
    weight everywhere.
    """
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)
    gathered = U is not None and U < T_pad

    @in_named_scope("bm25_tiered")
    def body(pd, pi, dense, st, ln, idfw, rid, dw, W, u_ids):
        @in_named_scope("score")
        def per_shard(pd_s, pi_s, dense_s, st_s, ln_s, rid_s, dw_s, W_s,
                      u_s):
            return tiered_bm25_topk(
                pd_s, pi_s, dense_s, st_s, ln_s, idfw, rid_s, dw_s, W_s,
                n_pad=n_pad, L=L, k=kk, min_should_match=min_should_match,
                with_count=with_count, u_ids=u_s if gathered else None)

        out = jax.vmap(per_shard,
                       in_axes=(0, 0, 0, 1, 1, 1, 1, 1, 0),
                       out_axes=1)(pd, pi, dense, st, ln, rid, dw, W, u_ids)
        gvals, gdocs = _global_topk_reduce(out[0], out[1], s_loc=s_loc,
                                           kk=kk, n_pad=n_pad, out_k=out_k)
        if with_count:
            counts = lax.psum(jnp.sum(out[2], axis=1), AXIS_SHARD)
            return gvals, gdocs, counts
        return gvals, gdocs

    shard_corpus = P(AXIS_SHARD, None)
    out_specs = (P(AXIS_REPLICA, None), P(AXIS_REPLICA, None)) \
        + ((P(AXIS_REPLICA),) if with_count else ())
    step = shard_map(
        body, mesh=mesh,
        in_specs=(shard_corpus, shard_corpus,
                  P(AXIS_SHARD, None, None, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_SHARD, None)),
        out_specs=out_specs,
        check_vma=False)
    return _jit_step(step, "bm25_tiered")


#: docs per streamed kNN block (the dense-tier DENSE_BLOCK pattern): the
#: corpus is scanned through the MXU block by block with a carried running
#: top-k, so per-device transient memory is O(B·(block + k)) instead of
#: the full O(B·n_pad) score matrix
KNN_BLOCK = 1 << 16

KNN_SIMILARITIES = ("dot_product", "cosine", "l2_norm")


def prepare_knn_corpus(vecs: np.ndarray, similarity: str):
    """Pack-time corpus invariants for the kNN step (host-side, ONCE).

    ``cosine`` unit-normalizes every row up front; ``l2_norm`` caches the
    ``‖v‖²`` rows so the step can expand ``-‖q-v‖²`` as
    ``2q·v - ‖v‖² - ‖q‖²`` without touching the corpus twice. The jitted
    step then does only the [B,D]×[N,D]ᵀ einsum plus masking — no
    corpus-side div/rsqrt ever appears in the per-query trace (the ratchet
    test in ``tests/test_knn_blocked.py`` asserts this on the jaxpr).

    ``vecs``: f32[..., dim] (any leading shard/doc shape). Returns
    (vecs', vnorm2) with vnorm2 f32[...] (zeros unless ``l2_norm``).
    """
    if similarity not in KNN_SIMILARITIES:
        raise ValueError(f"unknown similarity [{similarity}]")
    vecs = np.asarray(vecs, np.float32)
    if similarity == "cosine":
        norms = np.linalg.norm(vecs, axis=-1, keepdims=True)
        vecs = vecs / np.maximum(norms, 1e-12)
    if similarity == "l2_norm":
        vnorm2 = np.sum(vecs.astype(np.float64) ** 2,
                        axis=-1).astype(np.float32)
    else:
        vnorm2 = np.zeros(vecs.shape[:-1], np.float32)
    return vecs, vnorm2


def _knn_shard_scan(vecs_s, vn_s, exists_s, qq, qn, *, similarity: str,
                    n_pad: int, dim: int, kk: int, blk: int,
                    use_blocks: bool):
    """One shard partition's blocked kNN top-k — the traced scoring
    STAGE shared by :func:`build_knn_step` and the fused one-dispatch
    program (``build_fused_hybrid_step``): [B,D]×[block,D]ᵀ matmuls
    streamed over the corpus with a ``lax.scan``-carried running top-k.
    ``qq`` is the packed-convention query batch (unit rows for cosine),
    ``qn`` the cached ``Σq²`` rows (l2 only). Returns
    (vals f32[B, kk], local idx i32[B, kk])."""

    @in_named_scope("scores")
    def score_block(vecs_b, vn_b, exists_b):
        # HIGHEST: the TPU's default matmul precision rounds f32 operands
        # to bf16 (~1e-3 on a cosine) — this is the EXACT scan, held to
        # f32 by its tests. It also keeps XLA from hoisting a bf16
        # convert of the whole corpus out of the block loop.
        dots = jnp.einsum("bd,nd->bn", qq, vecs_b,
                          preferred_element_type=jnp.float32,
                          precision=lax.Precision.HIGHEST)
        if similarity == "l2_norm":
            # -||q - v||² expanded to ride the MXU; ||v||² is the
            # cached pack-time column, never recomputed per query
            scores = 2.0 * dots - vn_b[None, :] - qn[:, None]
        else:
            scores = dots
        return jnp.where(exists_b[None, :], scores, NEG_INF)

    # the running top-k, under its own scope beside ``scores``
    block_topk = in_named_scope("block_topk")(batched_blockwise_topk)

    @in_named_scope("block_topk")
    def merge_block(acc_v, acc_i, bv, gi):
        cat_v = jnp.concatenate([acc_v, bv], axis=1)
        cat_i = jnp.concatenate([acc_i, gi], axis=1)
        # earlier blocks sit first: top_k's lowest-position tie
        # preference keeps doc-ascending tie order
        nv, sel = lax.top_k(cat_v, kk)
        return nv, jnp.take_along_axis(cat_i, sel, axis=1)

    if not use_blocks:
        vals, idx = block_topk(score_block(vecs_s, vn_s, exists_s), kk)
        return vals, idx.astype(jnp.int32)
    nb = n_pad // blk
    with jax.named_scope("scores"):
        vecs_blk = vecs_s.reshape(nb, blk, dim)
        vn_blk = vn_s.reshape(nb, blk)
        exists_blk = exists_s.reshape(nb, blk)

    def score_block_at(b_idx):
        # blocks are read in place by index: handing the scan
        # ``vecs_blk[1:]`` as xs materializes a second copy of the whole
        # corpus per dispatch (2x the corpus in HBM; refused by the TPU
        # compiler at 2^22 x 768)
        with jax.named_scope("scores"):
            block = [lax.dynamic_index_in_dim(a, b_idx, keepdims=False)
                     for a in (vecs_blk, vn_blk, exists_blk)]
        return score_block(*block)

    # seed the accumulator from block 0 so every carried entry is
    # a real (value, global index) pair: merges then keep the
    # lowest global index among equal values — identical tie
    # order (and identical -inf padding indices) to the one-shot
    # full-matrix top_k
    with jax.named_scope("scores"):
        first = (vecs_blk[0], vn_blk[0], exists_blk[0])
    v0, i0 = block_topk(score_block(*first), kk)

    def step_blk(carry, b_idx):
        acc_v, acc_i = carry
        bv, bi = block_topk(score_block_at(b_idx), kk)
        gi = bi.astype(jnp.int32) + b_idx * blk
        return merge_block(acc_v, acc_i, bv, gi), None

    (vals, idx), _ = lax.scan(
        step_blk, (v0, i0.astype(jnp.int32)),
        jnp.arange(1, nb, dtype=jnp.int32))
    return vals, idx


def _knn_blocking(block: Optional[int], n_pad: int, kk: int):
    """(blk, use_blocks) under the shared engagement guard: blocking
    only when it divides the corpus cleanly and the per-block top-k can
    hold kk candidates."""
    use_blocks = (block is not None and block > 0 and n_pad % block == 0
                  and n_pad // block >= 2 and kk <= block)
    return (block if use_blocks else n_pad), use_blocks


def build_knn_step(mesh: Mesh, *, n_pad: int, dim: int, k: int,
                   n_shards: int, similarity: str = "dot_product",
                   block: Optional[int] = KNN_BLOCK):
    """Jitted distributed brute-force kNN: blocked einsum on the MXU per
    shard partition with a streaming running top-k + the same ICI reduce.

    Replaces the reference's script_score brute-force loop
    (``x-pack/plugin/vectors/.../query/ScoreScriptUtils.java:112-136``) —
    there a per-doc Java loop, here [B,D]x[block,D]ᵀ matmuls streamed over
    the corpus with a ``lax.scan``-carried top-k accumulator, so scores
    are never fully materialized (per-device memory O(B·(block + k))).

    Corpus invariants are NOT computed here: callers pack vectors through
    :func:`prepare_knn_corpus` once (unit rows for cosine, cached ``‖v‖²``
    for l2) and pass both; the trace contains no corpus-side
    normalization.

    Global shapes: vectors f32[S, n_pad, dim] sharded over ``shard``;
    vnorm2 f32[S, n_pad] (``‖v‖²`` rows — ignored/DCE'd unless l2_norm);
    exists bool[S, n_pad]; queries f32[B, dim] sharded over ``replica``.

    ``block=None`` disables blocking (one-shot full-matrix scoring) — the
    parity reference for tests.
    """
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)
    if similarity not in KNN_SIMILARITIES:
        raise ValueError(f"unknown similarity [{similarity}]")
    # blocking engages only when it divides the corpus cleanly and the
    # per-block top-k can hold kk candidates (same guard style as
    # ops/topk.py); n_pad is pow2 so any pow2 block ≤ n_pad divides it
    blk, use_blocks = _knn_blocking(block, n_pad, kk)

    @in_named_scope("knn_exact")
    def body(vecs, vnorm2, exists, q):
        with jax.named_scope("scores"):
            if similarity == "cosine":
                qq = q / jnp.maximum(
                    jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
            else:
                qq = q
            qn = jnp.sum(q * q, axis=-1)

        def per_shard(vecs_s, vn_s, exists_s):
            return _knn_shard_scan(vecs_s, vn_s, exists_s, qq, qn,
                                   similarity=similarity, n_pad=n_pad,
                                   dim=dim, kk=kk, blk=blk,
                                   use_blocks=use_blocks)

        vals, idx = jax.vmap(per_shard, out_axes=1)(vecs, vnorm2, exists)
        return _global_topk_reduce(vals, idx, s_loc=s_loc, kk=kk, n_pad=n_pad,
                                   out_k=out_k)

    step = shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_SHARD, None, None), P(AXIS_SHARD, None),
                  P(AXIS_SHARD, None), P(AXIS_REPLICA, None)),
        out_specs=(P(AXIS_REPLICA, None), P(AXIS_REPLICA, None)),
        check_vma=False)
    return _jit_step(step, "knn_exact")


# ---------------------------------------------------------------------------
# IVF tier: cluster-pruned ANN over an int8 quantized corpus
# ---------------------------------------------------------------------------
#
# Brute-force kNN is exact O(N) and, per ROOFLINE.md, bandwidth-bound —
# bytes moved per query is the lever. HNSW-style graphs (the Lucene/
# Anserini answer) don't batch on device: pointer-chasing serializes on
# the scalar unit. The TPU-shaped answer is cluster-pruned dense scans:
#
# - PACK time: k-means (batched-matmul Lloyd iterations; on an
#   accelerator the assignment matmuls run through jnp, on the CPU
#   backend through BLAS) assigns every corpus vector to one of nlist
#   centroids; rows are REORDERED cluster-contiguous (stable within a
#   cluster, so tie order inside a cluster stays doc-ascending) with a
#   cluster-offset table, and each vector is scalar-quantized to int8
#   with per-vector (scale, offset) rows: v ≈ scale·q + off, so
#   dot(u, v) ≈ scale·dot(u, q) + off·Σu — one fused multiply-add per
#   candidate after the int8 matmul. ``quant="bf16"`` keeps a bf16 tier
#   instead (2 bytes/dim, no scale/off error).
# - QUERY time: one [B, nlist] centroid matmul picks nprobe clusters per
#   query; only those clusters' blocks stream through the running-top-k
#   scan over the QUANTIZED tier (bytes moved drop by
#   ~(nprobe/nlist)·(1/4) vs the exact f32 scan); the top
#   ``rerank·k`` survivors are re-scored EXACTLY from the f32 tier and
#   the final top-k keeps the plane's (score desc, global id asc) tie
#   order.
#
# nprobe == nlist disables pruning: every row is scanned quantized, and
# the exact re-rank restores f32 scores/order for everything that
# reaches the rerank window (the property-test contract).

#: rows per IVF device-scan block: the quantized tier is tiled into
#: fixed blocks (block-major [NB, IVF_BLOCK, d]) so the probed-cluster
#: union becomes a static-shape gather + lax.scan; boundary blocks are
#: masked per row by cluster id, so blocks need no cluster alignment
IVF_BLOCK = 256

#: serving defaults (the knn_ivf_recall bench measures THESE — the
#: plane_serving health indicator flags dispatches below the benched
#: nprobe as recall-config drift)
IVF_DEFAULT_NPROBE = 8
IVF_DEFAULT_RERANK = 4

#: k-means training defaults: Lloyd on a bounded sample (assignment of
#: the FULL corpus happens once, chunked, after training)
IVF_TRAIN_SAMPLE = 1 << 15
IVF_KMEANS_ITERS = 6


def _device_linalg() -> bool:
    """True when the default jax backend is an accelerator — k-means
    assignment matmuls then run through jnp (MXU); the CPU backend uses
    BLAS directly (XLA:CPU runs well under numpy's sgemm here, same
    verdict as search_host vs the jitted step)."""
    return jax.devices()[0].platform != "cpu"


def _assign_clusters(x: np.ndarray, centroids: np.ndarray, l2: bool,
                     chunk: Optional[int] = None) -> np.ndarray:
    """argmax_c metric(x, c) per row, chunked so the [chunk, nlist]
    score matrix stays ≤ ~64 MB at ANY nlist (the chunk scales
    inversely with the centroid count). Metric matches query-time probe
    selection exactly: dot for cosine/dot_product (rows/centroids in
    the plane's packed convention), ``2x·c - ‖c‖²`` for l2."""
    if chunk is None:
        chunk = max(1024, (64 << 20) // (4 * max(centroids.shape[0], 1)))
    c2 = np.sum(centroids.astype(np.float64) ** 2,
                axis=1).astype(np.float32)
    on_dev = _device_linalg()
    out = np.empty(x.shape[0], np.int32)
    for lo in range(0, x.shape[0], chunk):
        xb = x[lo: lo + chunk]
        if on_dev:
            s = jnp.einsum("nd,cd->nc", jnp.asarray(xb),
                           jnp.asarray(centroids),
                           preferred_element_type=jnp.float32,
                           precision=lax.Precision.HIGHEST)
            if l2:
                s = 2.0 * s - jnp.asarray(c2)[None, :]
            out[lo: lo + chunk] = np.asarray(jnp.argmax(s, axis=1),
                                             np.int32)
        else:
            s = xb @ centroids.T
            if l2:
                s = 2.0 * s - c2[None, :]
            out[lo: lo + chunk] = np.argmax(s, axis=1).astype(np.int32)
    return out


def kmeans_fit(x: np.ndarray, nlist: int, *, l2: bool = False,
               spherical: bool = False, iters: int = IVF_KMEANS_ITERS,
               sample: int = IVF_TRAIN_SAMPLE, seed: int = 0) -> np.ndarray:
    """Batched-matmul Lloyd: train nlist centroids on (a sample of) x.

    Each iteration is one assignment matmul (device when an accelerator
    backend is up) + one scatter-add update; empty clusters re-seed from
    random rows so nlist stays fully used. ``spherical`` renormalizes
    centroids each round (cosine corpora are packed unit — spherical
    k-means keeps the probe metric consistent with row scoring)."""
    rng = np.random.RandomState(seed)
    n = x.shape[0]
    if n == 0 or nlist <= 0:
        raise ValueError("kmeans_fit needs rows and nlist >= 1")
    train = x if n <= sample else x[rng.choice(n, sample, replace=False)]
    # centroids are seeded (and re-seeded on empties) from the TRAIN
    # sample, so nlist is capped by it, not by the full corpus
    nlist = min(nlist, train.shape[0])
    cent = train[rng.choice(train.shape[0], nlist, replace=False)].copy()
    for _ in range(max(iters, 1)):
        assign = _assign_clusters(train, cent, l2)
        sums = np.zeros_like(cent, dtype=np.float64)
        np.add.at(sums, assign, train.astype(np.float64))
        counts = np.bincount(assign, minlength=nlist)
        empty = counts == 0
        nz = ~empty
        cent[nz] = (sums[nz] / counts[nz, None]).astype(np.float32)
        if empty.any():
            cent[empty] = train[rng.choice(train.shape[0],
                                           int(empty.sum()))]
        if spherical:
            cent /= np.maximum(
                np.linalg.norm(cent, axis=1, keepdims=True), 1e-12)
    return cent


def quantize_int8_rows(vecs: np.ndarray):
    """Per-vector asymmetric int8 scalar quantization.

    Row i maps [min_i, max_i] onto [-127, 127]:
    ``v ≈ scale·q + off`` with ``scale = (max-min)/254`` and
    ``off = min + 127·scale`` — so a dequantized dot product is one
    fused multiply-add on the int8 matmul result:
    ``dot(u, v̂) = scale·dot(u, q) + off·Σu``. Returns
    (codes int8[N, d], scale f32[N], off f32[N])."""
    vecs = np.asarray(vecs, np.float32)
    lo = vecs.min(axis=-1)
    hi = vecs.max(axis=-1)
    scale = np.maximum((hi - lo) / 254.0, 1e-12).astype(np.float32)
    codes = np.clip(np.rint((vecs - lo[:, None]) / scale[:, None]) - 127.0,
                    -127, 127).astype(np.int8)
    off = (lo + 127.0 * scale).astype(np.float32)
    return codes, scale, off


class IvfKnnTier:
    """Pack-time IVF index over one :class:`DistributedKnnPlane`'s packed
    corpus: shared centroids + per-shard cluster-contiguous quantized
    rows. The f32 tier (the plane's own packed vectors) stays in original
    row order and serves the exact re-rank; only the QUANTIZED tier is
    reordered."""

    def __init__(self, similarity: str, quant: str = "int8",
                 block: int = IVF_BLOCK):
        if quant not in ("int8", "bf16"):
            raise ValueError(f"unknown ivf quant [{quant}]")
        self.similarity = similarity
        self.quant = quant
        self.block = block
        self.nlist = 0
        self.centroids: Optional[np.ndarray] = None
        #: per shard: offsets i64[nlist+1] (cluster → row range in the
        #: reordered space), rows i32[n_exist] (reordered → original
        #: local row), codes, scale f32, off f32
        self.shards: List[dict] = []
        self.default_nprobe = IVF_DEFAULT_NPROBE
        #: blocks per shard in the device tier (max over shards of
        #: ceil(rows/block)) — the ONE source of the sentinel pad-block
        #: index both device_arrays and union_blocks key off
        self.n_blocks = 1
        #: rows per cluster summed over shards (docs-scanned attribution
        #: of a pruned dispatch reads this instead of re-diffing offsets)
        self.cluster_sizes: Optional[np.ndarray] = None
        self._dev = None
        self._dev_lock = threading.Lock()

    # -- pack ----------------------------------------------------------------

    @classmethod
    def build(cls, vecs: np.ndarray, exists: np.ndarray, similarity: str,
              *, nlist: Optional[int] = None, quant: str = "int8",
              iters: int = IVF_KMEANS_ITERS,
              train_sample: int = IVF_TRAIN_SAMPLE, seed: int = 0,
              block: int = IVF_BLOCK) -> "IvfKnnTier":
        """``vecs`` f32[S, n_pad, d] / ``exists`` bool[S, n_pad]: the
        plane's PACKED arrays (cosine rows already unit — centroid and
        row scoring then share one metric). ``nlist`` defaults to
        ~sqrt(N) rounded to a power of two (bounded so the average
        cluster keeps ≥ 8 rows)."""
        tier = cls(similarity, quant=quant, block=block)
        S = vecs.shape[0]
        d = vecs.shape[2]
        flat = np.concatenate([vecs[s][exists[s]] for s in range(S)]) \
            if S else np.zeros((0, d), np.float32)
        n_exist = flat.shape[0]
        if n_exist == 0:
            raise ValueError("IVF tier needs at least one vector")
        if nlist is None:
            nlist = round_up_pow2(max(int(np.sqrt(n_exist)), 1))
        nlist = max(1, min(int(nlist), max(n_exist // 8, 1)))
        l2 = similarity == "l2_norm"
        tier.centroids = kmeans_fit(
            flat, nlist, l2=l2, spherical=(similarity == "cosine"),
            iters=iters, sample=train_sample, seed=seed)
        tier.nlist = tier.centroids.shape[0]
        tier.default_nprobe = min(IVF_DEFAULT_NPROBE, tier.nlist)
        for s in range(S):
            rows0 = np.flatnonzero(exists[s]).astype(np.int32)
            v = vecs[s][rows0]
            assign = _assign_clusters(v, tier.centroids, l2) \
                if rows0.size else np.zeros(0, np.int32)
            # stable sort: rows within a cluster stay doc-ascending, so
            # equal re-ranked scores tie-break exactly like the exact scan
            order = np.argsort(assign, kind="stable")
            rows = rows0[order]
            offsets = np.zeros(tier.nlist + 1, np.int64)
            np.cumsum(np.bincount(assign, minlength=tier.nlist),
                      out=offsets[1:])
            if quant == "int8":
                codes, scale, off = quantize_int8_rows(v[order])
            else:
                # bf16 tier: 2 B/dim, no quantization error rows. Host
                # math uses f16 (numpy has no bf16); the device tier is
                # cast to bf16 at upload.
                codes = v[order].astype(np.float16)
                scale = np.ones(rows.size, np.float32)
                off = np.zeros(rows.size, np.float32)
            tier.shards.append(dict(offsets=offsets, rows=rows,
                                    codes=codes, scale=scale, off=off))
        tier.n_blocks = max(max((-(-sh["rows"].size // tier.block)
                                 for sh in tier.shards), default=1), 1)
        sizes = np.zeros(tier.nlist, np.int64)
        for sh in tier.shards:
            sizes += np.diff(sh["offsets"]).astype(np.int64)
        tier.cluster_sizes = sizes
        return tier

    def quant_bytes_per_dim(self) -> int:
        return 1 if self.quant == "int8" else 2

    def nbytes(self) -> int:
        return sum(sh["codes"].nbytes + sh["scale"].nbytes
                   + sh["off"].nbytes + sh["rows"].nbytes
                   for sh in self.shards) \
            + (self.centroids.nbytes if self.centroids is not None else 0)

    # -- query-time probe selection ------------------------------------------

    def probe(self, qq: np.ndarray, nprobe: int) -> np.ndarray:
        """Top-``nprobe`` cluster ids per query from ONE [B, nlist]
        centroid matmul (host BLAS — the matrix is tiny and the probed
        set must be host-visible anyway to size the static gather
        shapes, the same reason the text plane's U-gather picks rows on
        the host). ``qq``: queries in the plane's packed convention
        (unit rows for cosine)."""
        s = qq @ self.centroids.T
        if self.similarity == "l2_norm":
            c2 = np.sum(self.centroids.astype(np.float64) ** 2,
                        axis=1).astype(np.float32)
            s = 2.0 * s - c2[None, :]
        nprobe = min(nprobe, self.nlist)
        if nprobe >= self.nlist:
            return np.broadcast_to(
                np.arange(self.nlist, dtype=np.int32),
                (qq.shape[0], self.nlist)).copy()
        part = np.argpartition(-s, nprobe - 1, axis=1)[:, :nprobe]
        return part.astype(np.int32)

    # -- device tier ---------------------------------------------------------

    def device_arrays(self, mesh: Mesh, n_pad: int):
        """Block-major device tier (built lazily, once): codes
        [S, NB+1, blk, d], scale/off/vn-row metadata [S, NB+1, blk],
        rowid i32 (original local row; n_pad = sentinel), rcl i32
        (cluster id per row; -1 = padding). Block NB is an all-sentinel
        pad target for the probed-union gather."""
        with self._dev_lock:
            if self._dev is not None:
                return self._dev
            S = len(self.shards)
            blk = self.block
            d = self.shards[0]["codes"].shape[1] if S else 1
            nb = self.n_blocks
            cdt = np.int8 if self.quant == "int8" else np.float16
            codes = np.zeros((S, nb + 1, blk, d), cdt)
            scale = np.zeros((S, nb + 1, blk), np.float32)
            off = np.zeros((S, nb + 1, blk), np.float32)
            rowid = np.full((S, nb + 1, blk), n_pad, np.int32)
            rcl = np.full((S, nb + 1, blk), -1, np.int32)
            for s, sh in enumerate(self.shards):
                n = sh["rows"].size
                if not n:
                    continue
                flat_cl = np.repeat(
                    np.arange(self.nlist, dtype=np.int32),
                    np.diff(sh["offsets"]).astype(np.int64))
                codes[s].reshape(-1, d)[:n] = sh["codes"]
                scale[s].reshape(-1)[:n] = sh["scale"]
                off[s].reshape(-1)[:n] = sh["off"]
                rowid[s].reshape(-1)[:n] = sh["rows"]
                rcl[s].reshape(-1)[:n] = flat_cl
            spec4 = NamedSharding(mesh, P(AXIS_SHARD, None, None, None))
            spec3 = NamedSharding(mesh, P(AXIS_SHARD, None, None))
            dev_codes = jax.device_put(
                codes if self.quant == "int8"
                else codes.astype(jnp.bfloat16), spec4)
            self._dev = dict(
                nb=nb,
                codes=dev_codes,
                scale=jax.device_put(scale, spec3),
                off=jax.device_put(off, spec3),
                rowid=jax.device_put(rowid, spec3),
                rcl=jax.device_put(rcl, spec3))
            return self._dev

    def union_blocks(self, probed: np.ndarray, n_shards: int):
        """Per-shard union of the blocks the batch's probed clusters
        touch, padded (with the sentinel block NB) to a shared pow2
        width P — the static gather shape of the device step."""
        blk = self.block
        nb = self.n_blocks
        uniq = np.unique(probed)
        per_shard: List[np.ndarray] = []
        for sh in self.shards[:n_shards]:
            offs = sh["offsets"]
            blocks: set = set()
            for c in uniq:
                lo, hi = int(offs[c]), int(offs[c + 1])
                if hi > lo:
                    blocks.update(range(lo // blk, (hi - 1) // blk + 1))
            per_shard.append(np.fromiter(sorted(blocks), np.int32,
                                         len(blocks)))
        width = max(max((b.size for b in per_shard), default=1), 1)
        Pw = min(round_up_pow2(width), nb)
        Pw = max(Pw, 1)
        out = np.full((n_shards, Pw), nb, np.int32)    # sentinel block
        for s, b in enumerate(per_shard):
            out[s, :min(b.size, Pw)] = b[:Pw]
        return out, Pw


def build_ivf_knn_step(mesh: Mesh, *, n_pad: int, dim: int, k: int,
                       n_shards: int, similarity: str, nprobe: int,
                       r_cand: int, p_blocks: int, blk: int,
                       quant: str = "int8"):
    """Jitted IVF dispatch: gather the probed-union blocks of the
    quantized tier, stream them through a ``lax.scan`` running top-k of
    width ``r_cand`` (the rerank window), re-score the survivors exactly
    from the f32 tier, then the usual ICI all_gather/top_k reduce.

    Global shapes: codes [S, NB+1, blk, dim] int8/bf16; scale/off/rowid/
    rcl [S, NB+1, blk]; vecs f32[S, n_pad, dim] + vnorm2 f32[S, n_pad]
    (the EXACT tier, original row order); queries f32[B, dim]; probed
    i32[B, nprobe] (global cluster ids); u_blocks i32[S, p_blocks]
    (per-shard union, sentinel NB padding). Bytes moved from HBM per
    dispatch are ~p_blocks·blk·(dim·qbytes + 12) + r_cand·dim·4 per
    shard — the pruning win the knn_ivf_recall bench measures."""
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)
    l2 = similarity == "l2_norm"

    @in_named_scope("knn_ivf")
    def body(codes, scale, off, rowid, rcl, vecs, vnorm2, q, probed,
             u_blocks):
        if similarity == "cosine":
            qq = q / jnp.maximum(
                jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        else:
            qq = q
        qsum = jnp.sum(qq, axis=-1)                       # [B]
        qn = jnp.sum(q * q, axis=-1)                      # [B]

        @in_named_scope("score")
        def per_shard(codes_s, scale_s, off_s, rowid_s, rcl_s, vecs_s,
                      vn_s, u_s):
            # gather ONLY the probed-union blocks: HBM reads scale with
            # the union, not the corpus
            g_codes = jnp.take(codes_s, u_s, axis=0)      # [P, blk, d]
            g_scale = jnp.take(scale_s, u_s, axis=0)      # [P, blk]
            g_off = jnp.take(off_s, u_s, axis=0)
            g_rowid = jnp.take(rowid_s, u_s, axis=0)
            g_rcl = jnp.take(rcl_s, u_s, axis=0)

            def score_block(c_b, sc_b, of_b, rid_b, rc_b):
                dots = jnp.einsum(
                    "bd,nd->bn", qq, c_b.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
                s = sc_b[None, :] * dots \
                    + of_b[None, :] * qsum[:, None]
                if l2:
                    vn_b = jnp.take(vn_s, jnp.clip(rid_b, 0, n_pad - 1))
                    s = 2.0 * s - vn_b[None, :] - qn[:, None]
                # per-query membership: the row's cluster must be in
                # THIS query's probed set (co-batched queries share the
                # gathered union but not the mask)
                member = jnp.any(
                    rc_b[None, :, None] == probed[:, None, :], axis=-1)
                live = (rid_b < n_pad)[None, :]
                return jnp.where(member & live, s, NEG_INF)

            v0 = score_block(g_codes[0], g_scale[0], g_off[0],
                             g_rowid[0], g_rcl[0])
            rr = min(r_cand, blk)
            v0, i0 = batched_blockwise_topk(v0, rr)
            i0 = i0.astype(jnp.int32)
            if rr < r_cand:
                # the scan carry is the FIXED-width rerank window: pad
                # the seed so every merge keeps exactly r_cand entries
                padw = r_cand - rr
                v0 = jnp.pad(v0, ((0, 0), (0, padw)),
                             constant_values=NEG_INF)
                i0 = jnp.pad(i0, ((0, 0), (0, padw)))

            def step_blk(carry, xs):
                acc_v, acc_i = carry
                p_idx, c_b, sc_b, of_b, rid_b, rc_b = xs
                bv, bi = batched_blockwise_topk(
                    score_block(c_b, sc_b, of_b, rid_b, rc_b), rr)
                gi = bi.astype(jnp.int32) + p_idx * blk
                cat_v = jnp.concatenate([acc_v, bv], axis=1)
                cat_i = jnp.concatenate([acc_i, gi], axis=1)
                nv, sel = lax.top_k(cat_v, min(r_cand, cat_v.shape[1]))
                ni = jnp.take_along_axis(cat_i, sel, axis=1)
                return (nv, ni), None

            if p_blocks > 1:
                (vals_q, pos_q), _ = lax.scan(
                    step_blk, (v0, i0),
                    (jnp.arange(1, p_blocks, dtype=jnp.int32),
                     g_codes[1:], g_scale[1:], g_off[1:], g_rowid[1:],
                     g_rcl[1:]))
            else:
                vals_q, pos_q = v0, i0
            # positions in the gathered space → original local rows
            rid_flat = g_rowid.reshape(-1)
            cand_rows = jnp.take(rid_flat, pos_q)          # [B, R]
            # EXACT re-rank from the f32 tier: gather survivor rows,
            # re-score, and sort candidates by row id FIRST so the final
            # top_k's lowest-position tie preference restores the exact
            # scan's (score desc, doc asc) order
            order = jnp.argsort(cand_rows, axis=1)
            cand_rows = jnp.take_along_axis(cand_rows, order, axis=1)
            qvals = jnp.take_along_axis(vals_q, order, axis=1)
            safe_rows = jnp.clip(cand_rows, 0, n_pad - 1)
            cvecs = jnp.take(vecs_s, safe_rows, axis=0)    # [B, R, d]
            ex = jnp.einsum("bd,brd->br", qq, cvecs,
                            preferred_element_type=jnp.float32,
                            precision=lax.Precision.HIGHEST)
            if l2:
                cvn = jnp.take(vn_s, safe_rows)
                ex = 2.0 * ex - cvn - qn[:, None]
            ex = jnp.where(qvals == NEG_INF, NEG_INF, ex)
            vals, sel = lax.top_k(ex, min(kk, ex.shape[1]))
            idx = jnp.take_along_axis(cand_rows, sel, axis=1)
            if vals.shape[1] < kk:
                padw = kk - vals.shape[1]
                vals = jnp.pad(vals, ((0, 0), (0, padw)),
                               constant_values=NEG_INF)
                idx = jnp.pad(idx, ((0, 0), (0, padw)))
            return vals, idx

        vals, idx = jax.vmap(per_shard, in_axes=(0, 0, 0, 0, 0, 0, 0, 0),
                             out_axes=1)(codes, scale, off, rowid, rcl,
                                         vecs, vnorm2, u_blocks)
        return _global_topk_reduce(vals, idx, s_loc=s_loc, kk=kk,
                                   n_pad=n_pad, out_k=out_k)

    step = shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_SHARD, None, None, None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None),
                  P(AXIS_REPLICA, None),
                  P(AXIS_REPLICA, None),
                  P(AXIS_SHARD, None)),
        out_specs=(P(AXIS_REPLICA, None), P(AXIS_REPLICA, None)),
        check_vma=False)
    return _jit_step(step, "knn_ivf")


# ---------------------------------------------------------------------------
# Block-max lexical pruning tier: rank-safe WAND-as-a-scan for BM25
# ---------------------------------------------------------------------------
#
# The CSR planes eager-score every posting of every query term (the BM25S
# bet) — unbeatable while corpora are small, but at 2-10M docs a Zipf
# head term drags millions of postings through every dispatch while the
# top-10 is decided by a few thousand. Lucene's answer is WAND/block-max
# skipping (doc-at-a-time cursors + per-block score upper bounds); the
# TPU-shaped recast is the same shape PR 6's IVF tier proved for kNN:
#
# - PACK time: each term's postings are reordered impact-descending and
#   chunked into fixed LEX_BLOCK-wide blocks, so blocks are born sorted
#   by descending per-block BM25 upper bound (the bound = the block's
#   first impact, computed at the generation's FROZEN avgdl — the PR 4
#   invariant that keeps bounds stable under delta serving). Impacts in
#   the tier are int8-quantized per block (impact-ordered blocks are
#   value-coherent, so the per-block scale is tight); the bound table,
#   block offsets and per-term quantization error ride along as dense
#   arrays.
# - QUERY time: the blocks the query's terms own are merged into ONE
#   descending-bound schedule; blocks stream through a scan that
#   accumulates quantized partial scores and carries a running top-k
#   window whose k·Q-th value lower-bounds the final k-th score (a doc
#   holds at most one posting per term, so at least k DISTINCT docs sit
#   above it). The scan early-exits once the remaining per-term bound
#   mass ρ falls below that threshold θ: an unseen doc's whole score is
#   ≤ ρ < θ ≤ the final k-th, so it can neither enter the top-k nor tie
#   into it. Survivors (partial score + quantization slack + ρ still ≥
#   θ) are re-scored EXACTLY from the f32 CSR in the eager path's
#   arithmetic order — quantized scores only choose the window, never
#   the final ranking — so results are bit-identical to the eager scan
#   including the (score desc, doc asc) tie order.
# - On the jitted device path the trip count is FIXED (the schedule
#   length) and pruning is a per-query mask over scan steps, plus a
#   per-query SAFETY verdict (window overflow / bound margin): an unsafe
#   query re-dispatches through the eager kernel, so the pruned path is
#   rank-safe by construction on every input. The CPU host path
#   (``search_pruned_eager``) takes a true break and widens its survivor
#   set dynamically, so it is always safe in one pass.

#: postings per block-max block: small enough that per-block int8 scales
#: stay tight on impact-ordered runs, large enough that the per-block
#: bound/scale metadata (12 B) amortizes to <0.1 B/posting
LEX_BLOCK = 128

#: cap on the carried θ-window width; dispatches whose k·Q exceeds it
#: serve with pruning inert (θ = -inf) and fall back to eager via the
#: safety verdict — huge result windows shouldn't prune anyway
LEX_THETA_WINDOW = 1024

#: survivor (exact re-score) window factor: the device step keeps
#: ``LEX_RERANK × k`` accumulator survivors for the exact re-score
LEX_RERANK = 8


class BlockMaxTier:
    """Pack-time impact-ordered block-max tier over one
    :class:`DistributedSearchPlane`'s full per-shard CSR (sparse AND
    dense-tier terms — the host pruned path covers every query; the
    device path prunes the sparse tier and leaves Zipf-head terms to the
    streaming-matmul dense tier it already rides)."""

    def __init__(self, block: int = LEX_BLOCK):
        self.block = block
        self.n_pad = 0
        #: per shard: docs i32[NB, BS] (sentinel n_pad pad), codes
        #: int8[NB, BS], scale/off/bound f32[NB], blk_offsets i64[V+1]
        #: (term → block range), qerr f32[V] (max quantization half-step
        #: over the term's blocks — the slack term of the rank-safety
        #: margin), n_postings
        self.shards: List[dict] = []
        self.n_blocks = 1
        self._dev = None
        self._dev_lock = threading.Lock()

    @classmethod
    def build(cls, shards: Sequence[dict], impacts_full: Sequence[np.ndarray],
              *, n_pad: int, block: int = LEX_BLOCK) -> "BlockMaxTier":
        """``shards``: the plane constructor's shard dicts (original CSR
        ``offsets``/``docs``); ``impacts_full``: per-shard f32 impacts at
        the generation's frozen avgdl (``make_impacts`` output)."""
        tier = cls(block=block)
        tier.n_pad = n_pad
        BS = block
        for s, imp in zip(shards, impacts_full):
            offsets = np.asarray(s["offsets"], np.int64)
            docs = np.asarray(s["docs"], np.int32)
            imp = np.asarray(imp, np.float32)
            V = offsets.shape[0] - 1
            Pn = docs.shape[0]
            lens = np.diff(offsets)
            # ONE stable global sort puts every term's postings
            # impact-descending in place (stable: equal impacts keep the
            # CSR's doc-ascending order, so block contents are
            # deterministic)
            tids = np.repeat(np.arange(V, dtype=np.int64), lens)
            order = np.lexsort((-imp, tids))
            nblk = -(-lens // BS)
            blk_offsets = np.zeros(V + 1, np.int64)
            np.cumsum(nblk, out=blk_offsets[1:])
            NB = int(blk_offsets[-1])
            bdocs = np.full((NB, BS), n_pad, np.int32)
            bimp = np.zeros((NB, BS), np.float32)
            if Pn:
                rank = np.arange(Pn, dtype=np.int64) - \
                    np.repeat(offsets[:-1], lens)
                dst = np.repeat(blk_offsets[:-1], lens) * BS + rank
                bdocs.reshape(-1)[dst] = docs[order]
                bimp.reshape(-1)[dst] = imp[order]
            real = bdocs < n_pad
            # impact-descending within the term → slot 0 is the block max
            # = the block's score upper bound (per unit idf weight)
            bound = bimp[:, 0].copy()
            lo_v = np.where(real, bimp, np.float32(np.inf)).min(axis=1) \
                if NB else np.zeros(0, np.float32)
            lo_v = np.minimum(lo_v, bound)
            scale = np.maximum((bound - lo_v) / 254.0,
                               1e-12).astype(np.float32)
            codes = np.clip(
                np.rint((bimp - lo_v[:, None]) / scale[:, None]) - 127.0,
                -127, 127).astype(np.int8)
            off = (lo_v + 127.0 * scale).astype(np.float32)
            qerr = np.zeros(max(V, 1), np.float32)
            if NB:
                blk_tid = np.repeat(np.arange(V), nblk)
                np.maximum.at(qerr, blk_tid,
                              (scale * 0.5).astype(np.float32))
            tier.shards.append(dict(
                docs=bdocs, codes=codes, scale=scale, off=off,
                bound=bound.astype(np.float32), blk_offsets=blk_offsets,
                qerr=qerr, n_blocks=NB, n_postings=int(Pn)))
        tier.n_blocks = max(max((sh["n_blocks"] for sh in tier.shards),
                                default=1), 1)
        return tier

    # -- byte accounting (the bench's before/after quantization row) --------

    def impact_bytes_f32(self) -> int:
        """Bytes the eager plane holds per posting for impact values
        (the f32 column quantization replaces in the scan tier)."""
        return sum(sh["n_postings"] * 4 for sh in self.shards)

    def impact_bytes_int8(self) -> int:
        """Resident bytes of the quantized impact payload: int8 codes
        (incl. block padding) + per-block scale/off/bound."""
        return sum(sh["codes"].nbytes + sh["scale"].nbytes
                   + sh["off"].nbytes + sh["bound"].nbytes
                   for sh in self.shards)

    def nbytes(self) -> int:
        return sum(sh["docs"].nbytes + sh["codes"].nbytes
                   + sh["scale"].nbytes + sh["off"].nbytes
                   + sh["bound"].nbytes + sh["blk_offsets"].nbytes
                   + sh["qerr"].nbytes for sh in self.shards)

    # -- query-time schedule -------------------------------------------------

    def schedule(self, si: int, term_rows: Sequence[Tuple[int, float]]):
        """Descending-bound block schedule of one (query, shard):
        ``term_rows`` = [(tid, idf·weight)]. Returns (blk i32[n],
        w f32[n], rho f32[n], tpos i32[n], slack) where ``rho[i]`` is
        the remaining per-term bound mass BEFORE scoring position i (the
        WAND upper bound on any not-yet-seen doc's whole score),
        ``tpos`` the owning term's index in ``term_rows`` (the host
        chunk scatter groups by it — postings are unique only WITHIN a
        term) and ``slack`` upper-bounds the accumulated quantization +
        fp error of any doc's partial score."""
        tsh = self.shards[si]
        offs, bound, qerr = tsh["blk_offsets"], tsh["bound"], tsh["qerr"]
        bl: List[np.ndarray] = []
        sb: List[np.ndarray] = []
        wl: List[np.ndarray] = []
        nx: List[np.ndarray] = []
        tp: List[np.ndarray] = []
        slack = 0.0
        rho0 = 0.0
        for ti, (tid, w) in enumerate(term_rows):
            b0, b1 = int(offs[tid]), int(offs[tid + 1])
            if b1 <= b0:
                continue
            s = bound[b0:b1] * np.float32(w)
            bl.append(np.arange(b0, b1, dtype=np.int32))
            sb.append(s)
            wl.append(np.full(b1 - b0, w, np.float32))
            nx.append(np.concatenate([s[1:], np.zeros(1, np.float32)]))
            tp.append(np.full(b1 - b0, ti, np.int32))
            slack += float(qerr[tid]) * float(w)
            rho0 += float(s[0])
        if not bl:
            return (np.zeros(0, np.int32), np.zeros(0, np.float32),
                    np.zeros(0, np.float32), np.zeros(0, np.int32), 0.0)
        blk = np.concatenate(bl)
        sball = np.concatenate(sb)
        wall = np.concatenate(wl)
        nxall = np.concatenate(nx)
        tpall = np.concatenate(tp)
        order = np.argsort(-sball, kind="stable")
        # consuming block j of term t shrinks t's remaining bound from
        # bound[j] to bound[j+1] — rho is the exclusive cumsum of those
        # drops off the total starting mass
        delta = (sball - nxall)[order]
        rho = np.float64(rho0) - (np.cumsum(delta, dtype=np.float64)
                                  - delta)
        # fp-margin: the partial accumulator runs in different precision/
        # order than the eager scorer; a tiny relative pad keeps the
        # rank-safety margin sound without costing measurable pruning
        slack += 1e-5 * rho0
        return (blk[order], wall[order], rho.astype(np.float32),
                tpall[order], float(slack))

    # -- device tier ---------------------------------------------------------

    def device_arrays(self, mesh: Mesh):
        """Block-major device tier (lazy, once): docs i32[S, NB+1, BS]
        (row NB = all-sentinel pad block the masked scan steps read),
        codes int8[S, NB+1, BS], scale/off f32[S, NB+1]."""
        with self._dev_lock:
            if self._dev is not None:
                return self._dev
            S = len(self.shards)
            BS = self.block
            nb = self.n_blocks
            docs = np.full((S, nb + 1, BS), self.n_pad, np.int32)
            codes = np.zeros((S, nb + 1, BS), np.int8)
            scale = np.zeros((S, nb + 1), np.float32)
            off = np.zeros((S, nb + 1), np.float32)
            for s, sh in enumerate(self.shards):
                n = sh["n_blocks"]
                if not n:
                    continue
                docs[s, :n] = sh["docs"]
                codes[s, :n] = sh["codes"]
                scale[s, :n] = sh["scale"]
                off[s, :n] = sh["off"]
            spec3 = NamedSharding(mesh, P(AXIS_SHARD, None, None))
            spec2 = NamedSharding(mesh, P(AXIS_SHARD, None))
            self._dev = dict(
                docs=jax.device_put(docs, spec3),
                codes=jax.device_put(codes, spec3),
                scale=jax.device_put(scale, spec2),
                off=jax.device_put(off, spec2))
            return self._dev


def tie_stable_topk_docs(scores: np.ndarray, kk: int) -> np.ndarray:
    """Doc ids of the top-``kk`` positive scores in (score desc, doc
    asc) order, with the k-th-boundary TIE resolved doc-ascending —
    introselect alone keeps an arbitrary tie member, which breaks the
    kernel paths' tie contract. Bounded: the boundary tie set is
    reduced with a linear partition before any sort, so a corpus where
    millions of docs share the k-th score costs O(N), not
    O(N log N)."""
    n = scores.shape[0]
    if n > kk:
        kth = -np.partition(-scores, kk - 1)[kk - 1]
        if kth <= 0:
            sel = np.flatnonzero(scores > 0)
        else:
            sel = np.flatnonzero(scores > kth)
            need = kk - sel.size
            if need > 0:
                ties = np.flatnonzero(scores == kth)
                if ties.size > need:
                    # smallest `need` doc ids among the boundary ties
                    ties = np.partition(ties, need - 1)[:need]
                sel = np.concatenate([sel, ties])
    else:
        sel = np.flatnonzero(scores > 0)
    order = np.lexsort((sel, -scores[sel]))[:kk]
    return sel[order]


def tie_stable_topk_masked(scores: np.ndarray, pool: np.ndarray,
                           kk: int) -> np.ndarray:
    """Doc ids of the top-``kk`` of an ELIGIBLE pool in (score desc, doc
    asc) order with the k-th-boundary tie resolved doc-ascending — the
    bool-tree twin of :func:`tie_stable_topk_docs`, where eligibility is
    a clause-mask verdict rather than ``score > 0`` (a doc matching only
    filter clauses is a legitimate 0.0-score hit)."""
    if pool.size > kk:
        sub = scores[pool]
        kth = -np.partition(-sub, kk - 1)[kk - 1]
        strict = pool[sub > kth]
        need = kk - strict.size
        ties = pool[sub == kth]
        if need > 0 and ties.size > need:
            ties = np.partition(ties, need - 1)[:need]
        sel = np.concatenate([strict, ties[:max(need, 0)]])
    else:
        sel = pool
    order = np.lexsort((sel, -scores[sel]))[:kk]
    return sel[order]


#: popcount LUT for the bool clause bitmask (≤ 8 clauses fit one byte)
_POPCNT8 = np.array([bin(i).count("1") for i in range(256)], np.uint8)


def bool_role_masks(clauses) -> Tuple[int, int, int]:
    """(required, prohibited, should) clause bitmasks of a lowered bool
    tree — clause ci owns bit ``1 << ci``; must/filter are required,
    must_not prohibited, should optional (counted against msm)."""
    req = neg = shd = 0
    for ci, (role, _terms) in enumerate(clauses):
        bit = 1 << ci
        if role in ("must", "filter"):
            req |= bit
        elif role == "must_not":
            neg |= bit
        else:
            shd |= bit
    return req, neg, shd


def bool_clause_rows(clauses, idf_of):
    """Per-clause ``[(term, idf·weight)]`` in first-appearance order
    under ``idf_of`` stats. Scoring clauses (must/should) drop zero-idf
    terms (they contribute nothing, matching the bag paths'
    ``idfw_of``); filter/must_not clauses keep every term with weight
    0.0 (membership needs the posting run, never the weight). ONE copy
    for the base plane, the delta tier and the device assembly — clause
    semantics can never drift between tiers."""
    out = []
    for role, terms in clauses:
        weights: Dict[str, float] = {}
        for t in terms:
            weights[t] = weights.get(t, 0.0) + 1.0
        if role in ("must", "should"):
            rows = [(t, idf_of(t) * w) for t, w in weights.items()
                    if idf_of(t) > 0.0]
        else:
            rows = [(t, 0.0) for t in weights]
        out.append((role, rows))
    return out


def _bool_csr_shard_pool(term_ids, csr, per_clause, req: int, neg: int,
                         shd: int, msm: int):
    """Score ONE CSR shard for a lowered bool tree: scatter-add the
    scoring clauses' impacts, OR clause bits per doc, then the bitmask
    eligibility verdict (must/filter all present, must_not absent,
    ≥ msm should clauses). Returns (scores f32[n_docs], eligible doc
    pool) or None when no clause term touched the shard. THE shared
    core of ``DistributedSearchPlane.search_bool_eager`` and
    ``EagerDeltaScorer.score_bool`` — base and delta tiers score bool
    trees through this one function."""
    n_docs = csr["n_docs"]
    scores = np.zeros(n_docs, np.float32)
    bits = np.zeros(n_docs, np.uint8)
    touched = False
    for ci, (role, rows) in enumerate(per_clause):
        scoring = role in ("must", "should")
        bit = np.uint8(1 << ci)
        for t, idfw in rows:
            tid = term_ids.get(t)
            if tid is None:
                continue
            st = int(csr["offsets"][tid])
            en = int(csr["offsets"][tid + 1])
            if en > st:
                run = csr["docs"][st:en]
                if scoring:
                    scores[run] += idfw * csr["impacts"][st:en]
                bits[run] |= bit
                touched = True
    if not touched:
        return None
    ok = (bits & req) == req
    if neg:
        ok &= (bits & neg) == 0
    if msm > 0:
        ok &= _POPCNT8[bits & shd] >= msm
    return scores, np.flatnonzero(ok & (bits != 0))


def bool_csr_doc_mask(term_ids, csr, per_clause, req: int, neg: int,
                      shd: int, msm: int, n_slots: int) -> np.ndarray:
    """Eligible-doc mask of one CSR shard for a lowered bool tree —
    the fused planner's aggregation stages pool their per-segment doc
    masks through this (``search/agg_planner.py``), so agg matching is
    the SAME scatter/bitmask verdict as scoring, on both the base tier
    and the eager delta twin. ``n_slots`` sizes the returned mask (the
    segment's padded slot count); docs past ``csr["n_docs"]`` stay
    False. Returns bool[n_slots]."""
    mask = np.zeros(n_slots, bool)
    pooled = _bool_csr_shard_pool(term_ids, csr, per_clause, req, neg,
                                  shd, msm)
    if pooled is not None:
        mask[pooled[1]] = True
    return mask


def total_value(t) -> int:
    """Value of a per-query totals entry — plain int (exact count) or a
    ``(value, "gte")`` tuple from a pruned dispatch (the count is a
    lower bound: pruning skipped blocks whose docs were never seen,
    Lucene's track_total_hits-under-WAND semantics)."""
    return int(t[0]) if isinstance(t, tuple) else int(t or 0)


def total_is_lower_bound(t) -> bool:
    return isinstance(t, tuple)


def build_pruned_bm25_step(mesh: Mesh, *, n_pad: int, Q: int, k: int,
                           P_sched: int, W: int, R: int, BS: int,
                           NB: int, n_shards: int):
    """Jitted block-max pruned BM25 dispatch: stream the query batch's
    descending-bound block schedule through a ``lax.scan`` that
    scatter-adds dequantized impacts into a dense accumulator and
    carries a running top-W window; steps whose remaining bound mass ρ
    falls below the window's rank-safety threshold θ are MASKED OUT
    (fixed trip count on device — the host path takes a true break).
    The top-R accumulator survivors are re-scored EXACTLY from the f32
    sparse postings table (binary search per (candidate, term), f32
    summation in the sorted-merge kernel's order) and reduced over the
    ICI like every other step.

    Global shapes: postings_docs i32[S, P'] / postings_impact f32[S, P']
    (the plane's sparse table, re-score tier); t_docs i32[S, NB+1, BS] /
    t_codes i8[S, NB+1, BS] / t_scale, t_off f32[S, NB+1] (quantized
    block tier; row NB = sentinel pad block); sched i32[B, S, P_sched]
    (block ids, sentinel NB), w f32[B, S, P_sched] (idf·weight of the
    block's term), rho f32[B, S, P_sched] (remaining bound mass before
    each position), slack f32[B, S]; starts/lengths i32[B, S, Q] (FULL
    sparse run lengths — never L-clamped; the re-score bisects whole
    runs), idfw f32[B, Q].

    Returns (vals f32[B, k], gdocs i32[B, k], matched i32[B],
    unsafe i32[B], pruned i32[B], blocks_scored i32[B]): ``unsafe > 0``
    means the survivor window could not certify rank-safety for that
    query (caller re-dispatches it through the eager kernel);
    ``matched`` is exact when ``pruned == 0``, else a lower bound."""
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)
    kq = k * Q
    prune_active = kq <= W
    kq_idx = min(kq, W) - 1

    @in_named_scope("bm25_pruned")
    def body(pd, pi, td, tc, ts, to, sched, w, rho, slack, st, ln, idfw):
        @in_named_scope("score")
        def per_shard(pd_s, pi_s, td_s, tc_s, ts_s, to_s, sched_s, w_s,
                      rho_s, slack_s, st_s, ln_s):
            def per_query(sched_q, w_q, rho_q, slack_q, st_q, ln_q, iw_q):
                acc0 = jnp.zeros(n_pad, jnp.float32)
                win0 = jnp.full(W, NEG_INF, jnp.float32)

                def step(carry, xs):
                    acc, win, pruned, rho_stop, n_sc = carry
                    b_id, w_b, rho_b = xs
                    theta = win[kq_idx] - slack_q if prune_active \
                        else jnp.float32(NEG_INF)
                    real = b_id != NB
                    live = real & (rho_b >= theta)
                    newly = real & ~live
                    pruned = pruned | newly
                    rho_stop = jnp.maximum(
                        rho_stop, jnp.where(newly, rho_b, NEG_INF))
                    safe_b = jnp.where(live, b_id, NB)
                    d_b = jnp.take(td_s, safe_b, axis=0)      # [BS]
                    q_b = jnp.take(tc_s, safe_b,
                                   axis=0).astype(jnp.float32)
                    # dequantized impact, clamped strictly positive so
                    # acc > 0 is exactly "this doc was seen"
                    vhat = jnp.maximum(
                        ts_s[safe_b] * q_b + to_s[safe_b], 1e-9)
                    contrib = jnp.where(live & (d_b < n_pad),
                                        w_b * vhat, 0.0)
                    acc = acc.at[d_b].add(contrib, mode="drop")
                    av = jnp.take(acc, d_b, mode="fill",
                                  fill_value=NEG_INF)
                    av = jnp.where(live & (d_b < n_pad), av, NEG_INF)
                    win, _ = lax.top_k(jnp.concatenate([win, av]), W)
                    n_sc = n_sc + live.astype(jnp.int32)
                    return (acc, win, pruned, rho_stop, n_sc), None

                (acc, win, pruned, rho_stop, n_sc), _ = lax.scan(
                    step,
                    (acc0, win0, jnp.bool_(False),
                     jnp.float32(NEG_INF), jnp.int32(0)),
                    (sched_q, w_q, rho_q))
                theta_end = win[kq_idx] - slack_q if prune_active \
                    else jnp.float32(NEG_INF)
                seen = acc > 0
                matched = jnp.sum(seen.astype(jnp.int32))
                rr = min(R, n_pad)
                cv, ci = lax.top_k(jnp.where(seen, acc, NEG_INF), rr)
                # safety verdict: docs outside the survivor window have
                # partial ≤ cv[-1]; with the quantization slack and (if
                # pruned) the remaining bound mass they must sit
                # strictly below θ or the window may have cut a true
                # top-k member — the caller then re-serves eagerly
                rho_eff = jnp.maximum(rho_stop, 0.0)
                overflow = matched > rr
                unsafe = (overflow & (cv[-1] + slack_q >= theta_end)) \
                    | (pruned & (cv[-1] + slack_q + rho_eff
                                 >= theta_end))
                # exact re-score: candidates sorted doc-ascending so the
                # final top_k's lowest-position tie preference restores
                # the eager kernel's (score desc, doc asc) order. The
                # bisect + highest-slot-first f32 summation live in the
                # shared stage (``ops/fused_query.bisect_exact_scores``)
                # the fused rescore kernel also composes.
                ci = jnp.where(cv == NEG_INF, n_pad, ci)
                order = jnp.argsort(ci)
                ci = jnp.take(ci, order)
                cvs = jnp.take(cv, order)
                score, _found = bisect_exact_scores(
                    pd_s, pi_s, st_q, ln_q, iw_q, ci, n_pad=n_pad)
                score = jnp.where(cvs == NEG_INF, NEG_INF, score)
                vals, sel = lax.top_k(score, kk)
                docs = jnp.take(ci, sel)
                docs = jnp.where(vals > NEG_INF, docs, n_pad)
                return (vals, docs.astype(jnp.int32), matched,
                        unsafe.astype(jnp.int32),
                        pruned.astype(jnp.int32), n_sc)

            # one n_pad-wide f32 accumulator per query rides the scan
            # carry: the same slot bound as the sorted merge keeps a
            # B=64 dispatch at n_pad=2^23 from holding 8 GB of them
            return vmap_queries(
                per_query,
                (sched_s, w_s, rho_s, slack_s, st_s, ln_s, idfw),
                slots_per_query=n_pad)

        out = jax.vmap(per_shard,
                       in_axes=(0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1),
                       out_axes=1)(pd, pi, td, tc, ts, to, sched, w,
                                   rho, slack, st, ln)
        vals, idx, matched, unsafe, pruned, n_sc = out
        gvals, gdocs = _global_topk_reduce(vals, idx, s_loc=s_loc,
                                           kk=kk, n_pad=n_pad,
                                           out_k=out_k)
        matched = lax.psum(jnp.sum(matched, axis=1), AXIS_SHARD)
        unsafe = lax.psum(jnp.sum(unsafe, axis=1), AXIS_SHARD)
        pruned = lax.psum(jnp.sum(pruned, axis=1), AXIS_SHARD)
        n_sc = lax.psum(jnp.sum(n_sc, axis=1), AXIS_SHARD)
        return gvals, gdocs, matched, unsafe, pruned, n_sc

    shard_corpus = P(AXIS_SHARD, None)
    step = shard_map(
        body, mesh=mesh,
        in_specs=(shard_corpus, shard_corpus,
                  P(AXIS_SHARD, None, None), P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None), P(AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, AXIS_SHARD, None),
                  P(AXIS_REPLICA, None)),
        out_specs=(P(AXIS_REPLICA, None), P(AXIS_REPLICA, None),
                   P(AXIS_REPLICA), P(AXIS_REPLICA), P(AXIS_REPLICA),
                   P(AXIS_REPLICA)),
        check_vma=False)
    return _jit_step(step, "bm25_pruned")


# ---------------------------------------------------------------------------
# One-dispatch fused query steps (the planner's device programs)
# ---------------------------------------------------------------------------
#
# A hybrid request historically cost two serving dispatches (text plane,
# knn plane) plus host-side fusion, and bool trees never reached the
# plane at all. These builders lower a PLANNED request
# (``search/query_planner.py``) into one jitted SPMD program over both
# planes' resident tensors: per-clause partial scores combined in-device
# (the bool merge body's clause-bit channel), the lexical sorted-merge
# and the kNN blocked scan sharing one program (XLA overlaps the two
# pipelines; two dispatches serialize them), RRF/linear rank fusion and
# the rescore-window reorder as final fused stages, and ONE result
# fetch. Shapes are bucketed into the same (B, k, L, params) lattice as
# every other serving step, so the fused path compiles per request
# SHAPE, never per query.


def build_bool_bm25_step(mesh: Mesh, *, n_pad: int, Q: int, L: int,
                         k: int, nc: int, n_shards: int,
                         with_count: bool = False, Q2: int = 0,
                         rescore_mode: str = "total"):
    """Jitted bool-tree BM25 dispatch (+ optional fused rescore stage).

    Global shapes beyond :func:`build_bm25_topk_step`'s: ``cbits``
    i32[B, Q] per-slot owning-clause bit, ``req``/``neg``/``shd``/
    ``msm`` i32[B] per-query clause-role masks. With ``Q2 > 0`` the
    rescore query rides along (``st2``/``ln2`` i32[B, S, Q2], ``iw2``
    f32[B, Q2], ``qw``/``rw`` f32[B], ``rwin`` i32[B]): per-shard
    candidates carry exact bisect secondaries through the reduce and
    the window reorders in-device."""
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk = min(k, n_pad)
    out_k = min(k, n_shards * n_pad)
    pad_id = n_shards * n_pad
    rescore = Q2 > 0

    @in_named_scope("bm25_bool")
    def body(pd, pi, st, ln, idfw, cbits, req, neg, shd, msm, *rest):
        if rescore:
            st2, ln2, iw2, qw, rw, rwin = rest
        else:
            st2 = ln2 = iw2 = qw = rw = rwin = None

        @in_named_scope("score")
        def per_shard(pd_s, pi_s, st_s, ln_s, st2_s, ln2_s):
            def per_query(st_q, ln_q, iw_q, cb_q, req_q, neg_q, shd_q,
                          msm_q, st2_q, ln2_q, iw2_q):
                vals, docs, cnt = bool_bm25_topk_body(
                    pd_s, pi_s, st_q, ln_q, iw_q, cb_q, req_q, neg_q,
                    shd_q, msm_q, n_pad=n_pad, L=L, k=kk,
                    with_count=True, nc=nc)
                if rescore:
                    sec, fnd = bisect_exact_scores(
                        pd_s, pi_s, st2_q, ln2_q, iw2_q, docs,
                        n_pad=n_pad)
                    return (vals, docs, cnt, sec,
                            fnd.astype(jnp.float32))
                return vals, docs, cnt

            if rescore:
                return vmap_queries(
                    per_query,
                    (st_s, ln_s, idfw, cbits, req, neg, shd, msm,
                     st2_s, ln2_s, iw2), slots_per_query=Q * L)
            z2 = jnp.zeros((1,), jnp.int32)
            zf = jnp.zeros((1,), jnp.float32)
            return vmap_queries(
                lambda a, b, c, d, e, f, g, h: per_query(
                    a, b, c, d, e, f, g, h, z2, z2, zf),
                (st_s, ln_s, idfw, cbits, req, neg, shd, msm),
                slots_per_query=Q * L)

        if rescore:
            out = jax.vmap(per_shard, in_axes=(0, 0, 1, 1, 1, 1),
                           out_axes=1)(pd, pi, st, ln, st2, ln2)
            vals, idx, cnt, sec, fnd = out
            gvals, gdocs, (gsec, gfnd) = _global_topk_reduce(
                vals, idx, s_loc=s_loc, kk=kk, n_pad=n_pad,
                out_k=out_k, payload=(sec, fnd))
        else:
            z = jnp.zeros((st.shape[0], s_loc, st.shape[-1]), jnp.int32)
            out = jax.vmap(per_shard, in_axes=(0, 0, 1, 1, 1, 1),
                           out_axes=1)(pd, pi, st, ln, z, z)
            vals, idx, cnt = out
            gvals, gdocs = _global_topk_reduce(
                vals, idx, s_loc=s_loc, kk=kk, n_pad=n_pad, out_k=out_k)
        counts = lax.psum(jnp.sum(cnt, axis=1), AXIS_SHARD)
        if rescore:
            @in_named_scope("rescore")
            def finish(v_q, g_q, sec_q, fnd_q, qw_q, rw_q, rwin_q):
                g_q = jnp.where(v_q > NEG_INF, g_q, pad_id)
                return rescore_reorder_body(
                    v_q, g_q, sec_q, fnd_q > 0.0, qw_q, rw_q, rwin_q,
                    mode=rescore_mode, k=out_k, pad_id=pad_id)

            gvals, gdocs = jax.vmap(finish)(gvals, gdocs, gsec, gfnd,
                                            qw, rw, rwin)
        if with_count:
            return gvals, gdocs, counts
        return gvals, gdocs

    shard_corpus = P(AXIS_SHARD, None)
    repl3 = P(AXIS_REPLICA, AXIS_SHARD, None)
    repl2 = P(AXIS_REPLICA, None)
    repl1 = P(AXIS_REPLICA)
    in_specs = [shard_corpus, shard_corpus, repl3, repl3, repl2, repl2,
                repl1, repl1, repl1, repl1]
    if rescore:
        in_specs += [repl3, repl3, repl2, repl1, repl1, repl1]
    out_specs = (repl2, repl2) + ((repl1,) if with_count else ())
    step = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=out_specs, check_vma=False)
    return _jit_step(step, "bm25_bool")


def build_fused_hybrid_step(mesh: Mesh, *, n_pad_t: int, Q: int, L: int,
                            W_text: int, nc: int, n_pad_k: int, dim: int,
                            similarity: str, W_knn: int, k: int,
                            fusion: str, n_shards: int, Q2: int = 0,
                            rescore_mode: str = "total",
                            block: Optional[int] = KNN_BLOCK):
    """THE one-dispatch hybrid program: lexical bool-tree scoring +
    blocked kNN scan + in-device rank fusion (+ optional fused rescore)
    over both planes' resident tensors, with one ICI reduce per
    retriever and the fusion/rescore stages running in replica space.

    The two candidate streams share one program, so XLA schedules the
    MXU kNN blocks against the VPU sorted-merge instead of serializing
    two dispatches through the host. Unified candidate ids are
    ``shard * UP + doc`` with ``UP = max(n_pad_t, n_pad_k)`` (both
    planes serve one segment per shard, so shard indices agree);
    ``pad = n_shards * UP``.

    Runtime (non-compile) per-query knobs: ``rc`` f32[B] RRF rank
    constant, ``wt``/``wk`` i32[B] per-list rank windows, ``kboost``
    f32[B], and the rescore ``qw``/``rw``/``rwin``. Returns
    (fused_vals f32[B, k], fused_ids i32[B, k], text_counts i32[B],
    text_vals f32[B, W_text], text_ids i32[B, W_text],
    knn_vals f32[B, W_knn], knn_ids i32[B, W_knn]) — the raw rankings
    ride along so generation-level serving can re-merge a live delta
    tier without a second dispatch."""
    if fusion not in ("rrf", "sum"):
        raise ValueError(f"unknown fusion [{fusion}]")
    s_dev = mesh.shape[AXIS_SHARD]
    if n_shards % s_dev:
        raise ValueError(f"{n_shards} shards not divisible over {s_dev} devices")
    s_loc = n_shards // s_dev
    kk_t = min(W_text, n_pad_t)
    out_t = min(W_text, n_shards * n_pad_t)
    kk_k = min(W_knn, n_pad_k)
    out_kn = min(W_knn, n_shards * n_pad_k)
    UP = max(n_pad_t, n_pad_k)
    pad_id = n_shards * UP
    blk, use_blocks = _knn_blocking(block, n_pad_k, kk_k)
    rescore = Q2 > 0

    @in_named_scope("fused_hybrid")
    def body(pd, pi, kvecs, kvn, kex, st, ln, idfw, cbits, req, neg,
             shd, msm, qv, kboost, rc, wt, wk, *rest):
        if rescore:
            st2, ln2, iw2, qw, rw, rwin = rest
        else:
            st2 = ln2 = iw2 = qw = rw = rwin = None
        if similarity == "cosine":
            qq = qv / jnp.maximum(
                jnp.linalg.norm(qv, axis=-1, keepdims=True), 1e-12)
        else:
            qq = qv
        qn = jnp.sum(qv * qv, axis=-1)

        @in_named_scope("score")
        def per_shard(pd_s, pi_s, kv_s, kn_s, ke_s, st_s, ln_s,
                      st2_s, ln2_s):
            def per_query(st_q, ln_q, iw_q, cb_q, req_q, neg_q, shd_q,
                          msm_q, st2_q, ln2_q, iw2_q):
                return bool_bm25_topk_body(
                    pd_s, pi_s, st_q, ln_q, iw_q, cb_q, req_q, neg_q,
                    shd_q, msm_q, n_pad=n_pad_t, L=L, k=kk_t,
                    with_count=True, nc=nc)

            if rescore:
                tv, td, cnt = vmap_queries(
                    per_query,
                    (st_s, ln_s, idfw, cbits, req, neg, shd, msm,
                     st2_s, ln2_s, iw2), slots_per_query=Q * L)
            else:
                z2 = jnp.zeros((1,), jnp.int32)
                zf = jnp.zeros((1,), jnp.float32)
                tv, td, cnt = vmap_queries(
                    lambda a, b, c, d, e, f, g, h: per_query(
                        a, b, c, d, e, f, g, h, z2, z2, zf),
                    (st_s, ln_s, idfw, cbits, req, neg, shd, msm),
                    slots_per_query=Q * L)
            kv, kd = _knn_shard_scan(kv_s, kn_s, ke_s, qq, qn,
                                     similarity=similarity,
                                     n_pad=n_pad_k, dim=dim, kk=kk_k,
                                     blk=blk, use_blocks=use_blocks)
            if rescore:
                def sec_of(st2_q, ln2_q, iw2_q, docs):
                    s, f = bisect_exact_scores(
                        pd_s, pi_s, st2_q, ln2_q, iw2_q, docs,
                        n_pad=n_pad_t)
                    return s, f.astype(jnp.float32)

                sec_t, fnd_t = jax.vmap(sec_of)(st2_s, ln2_s, iw2, td)
                # kNN candidates live in the kNN pad space; their doc
                # ids are valid text-CSR doc ids (same segment), only
                # the pad sentinel differs — clamp cross-space
                kd_t = jnp.where((kv > NEG_INF) & (kd < n_pad_t),
                                 kd, n_pad_t)
                sec_k, fnd_k = jax.vmap(sec_of)(st2_s, ln2_s, iw2, kd_t)
                return (tv, td, cnt, kv, kd, sec_t, fnd_t, sec_k,
                        fnd_k)
            return tv, td, cnt, kv, kd

        zT = jnp.zeros((st.shape[0], s_loc, 1), jnp.int32)
        if rescore:
            out = jax.vmap(per_shard, in_axes=(0, 0, 0, 0, 0, 1, 1,
                                               1, 1),
                           out_axes=1)(pd, pi, kvecs, kvn, kex, st, ln,
                                       st2, ln2)
            (tv, td, cnt, kv, kd, sec_t, fnd_t, sec_k, fnd_k) = out
            tvals, tids, (tsec, tfnd) = _global_topk_reduce(
                tv, td, s_loc=s_loc, kk=kk_t, n_pad=n_pad_t,
                out_k=out_t, payload=(sec_t, fnd_t))
            kvals, kids, (ksec, kfnd) = _global_topk_reduce(
                kv, kd, s_loc=s_loc, kk=kk_k, n_pad=n_pad_k,
                out_k=out_kn, payload=(sec_k, fnd_k))
        else:
            out = jax.vmap(per_shard, in_axes=(0, 0, 0, 0, 0, 1, 1,
                                               1, 1),
                           out_axes=1)(pd, pi, kvecs, kvn, kex, st, ln,
                                       zT, zT)
            tv, td, cnt, kv, kd = out
            tvals, tids = _global_topk_reduce(
                tv, td, s_loc=s_loc, kk=kk_t, n_pad=n_pad_t, out_k=out_t)
            kvals, kids = _global_topk_reduce(
                kv, kd, s_loc=s_loc, kk=kk_k, n_pad=n_pad_k,
                out_k=out_kn)
            tsec = tfnd = ksec = kfnd = None
        counts = lax.psum(jnp.sum(cnt, axis=1), AXIS_SHARD)

        n_f = out_t + out_kn

        @in_named_scope("fuse")
        def finish(tv_q, tg_q, kv_q, kg_q, kb_q, rc_q, wt_q, wk_q,
                   tsec_q, tfnd_q, ksec_q, kfnd_q, qw_q, rw_q, rwin_q):
            pos_t = jnp.arange(out_t, dtype=jnp.int32)
            pos_k = jnp.arange(out_kn, dtype=jnp.int32)
            # unify ids into the shared (shard, doc) space and apply the
            # per-request rank windows (entries past the window leave
            # the fusion, exactly like the host truncating its lists)
            t_ok = (tv_q > NEG_INF) & (pos_t < wt_q)
            k_ok = (kv_q > NEG_INF) & (pos_k < wk_q)
            tug = jnp.where(t_ok, (tg_q // n_pad_t) * UP
                            + tg_q % n_pad_t, pad_id)
            kug = jnp.where(k_ok, (kg_q // n_pad_k) * UP
                            + kg_q % n_pad_k, pad_id)
            if fusion == "rrf":
                fv, fi, sel = rrf_fuse_body(tug, kug, rc_q, k=n_f,
                                            pad_id=pad_id)
            else:
                ks = jnp.where(k_ok,
                               knn_raw_to_score(similarity, kv_q)
                               * kb_q, NEG_INF)
                ts = jnp.where(t_ok, tv_q, NEG_INF)
                fv, fi, sel = sum_fuse_body(tug, ts, kug, ks, k=n_f,
                                            pad_id=pad_id)
            if rescore:
                sec_cat = jnp.concatenate([tsec_q, ksec_q])
                fnd_cat = jnp.concatenate([tfnd_q, kfnd_q])
                sec_f = jnp.take(sec_cat, sel, mode="clip")
                fnd_f = jnp.take(fnd_cat, sel, mode="clip") > 0.0
                fv, fi = rescore_reorder_body(
                    fv, fi, sec_f, fnd_f, qw_q, rw_q, rwin_q,
                    mode=rescore_mode, k=k, pad_id=pad_id)
            else:
                fv, fi = fv[:k], fi[:k]
                if fv.shape[0] < k:
                    fv = jnp.pad(fv, (0, k - fv.shape[0]),
                                 constant_values=NEG_INF)
                    fi = jnp.pad(fi, (0, k - fi.shape[0]),
                                 constant_values=pad_id)
            return fv, fi

        zB = jnp.zeros(tvals.shape[:2], jnp.float32)
        zB1 = jnp.zeros((tvals.shape[0],), jnp.float32)
        zBk = jnp.zeros(kvals.shape[:2], jnp.float32)
        zBi = jnp.zeros((tvals.shape[0],), jnp.int32)
        fvals, fids = jax.vmap(finish)(
            tvals, tids, kvals, kids, kboost, rc, wt, wk,
            tsec if rescore else zB, tfnd if rescore else zB,
            ksec if rescore else zBk, kfnd if rescore else zBk,
            qw if rescore else zB1, rw if rescore else zB1,
            rwin if rescore else zBi)
        return fvals, fids, counts, tvals, tids, kvals, kids

    shard_corpus = P(AXIS_SHARD, None)
    shard3 = P(AXIS_SHARD, None, None)
    repl3 = P(AXIS_REPLICA, AXIS_SHARD, None)
    repl2 = P(AXIS_REPLICA, None)
    repl1 = P(AXIS_REPLICA)
    in_specs = [shard_corpus, shard_corpus, shard3,
                P(AXIS_SHARD, None), P(AXIS_SHARD, None),
                repl3, repl3, repl2, repl2, repl1, repl1, repl1, repl1,
                repl2, repl1, repl1, repl1, repl1]
    if rescore:
        in_specs += [repl3, repl3, repl2, repl1, repl1, repl1]
    out_specs = (repl2, repl2, repl1, repl2, repl2, repl2, repl2)
    step = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=out_specs, check_vma=False)
    return _jit_step(step, "fused_hybrid")


# ---------------------------------------------------------------------------
# Host-side plane: shard packing + query dispatch
# ---------------------------------------------------------------------------


def _plane_cached_step(self, key: Tuple, builder, site: str):
    """Get-or-build a jitted step in a plane's per-shape cache: read
    under the lock, build + instrument OUTSIDE it (ESTP-L02 —
    telemetry never under a serving lock, concurrent distinct-shape
    builds never serialize), then ``setdefault`` so the first copy wins
    a race. ONE copy of the dance for every step family on BOTH planes
    (eager/tiered/pruned/bool/fused/knn/ivf) — bound as
    ``cached_step`` on each plane class."""
    with self._steps_lock:
        fn = self._steps.get(key)
    if fn is None:
        fn = builder()
        from ..common.telemetry import instrument_step
        fn = instrument_step(fn, site=site)
        with self._steps_lock:
            fn = self._steps.setdefault(key, fn)
    return fn


def _padded_batches(mesh: Mesh, max_b: int) -> List[int]:
    """The padded batches the micro-batcher dispatches: powers of two up
    to ``max_b``, each rounded up to a multiple of the mesh's replica
    axis (which partitions the batch)."""
    n_repl = mesh.shape[AXIS_REPLICA]
    return sorted({-(-(1 << i) // n_repl) * n_repl
                   for i in range(max(max_b, 1).bit_length())})


class DistributedSearchPlane:
    """Packs per-shard postings into mesh-sharded device arrays and runs
    batched distributed searches.

    The host side plays the coordinating-node role
    (``TransportSearchAction``): term-dictionary lookups per shard, global
    document-frequency stats (the DFS phase — ``search/dfs/DfsPhase.java`` —
    is *always on* here since global df is a cheap host-side sum), and query
    batch assembly; everything per-document runs on device.
    """

    #: dense-tier block width (docs per streamed matmul block)
    DENSE_BLOCK = 1 << 19
    #: dense-tier row budget per shard (memory cap: T × n_pad × 2B each)
    MAX_DENSE_TERMS = 256
    #: largest padded batch whose bag dispatch still picks a short L rung
    #: (see :meth:`serving_shape`, which has the figures)
    SHORT_RUNG_MAX_B = 2

    def __init__(self, mesh: Mesh, shards: Sequence[dict], field: str,
                 *, k1: float = DEFAULT_K1, b: float = DEFAULT_B,
                 dense_threshold: Optional[int] = None,
                 blockmax: Optional[dict] = None):
        """``shards``: one dict per shard with keys
        ``term_ids`` (term→tid), ``df`` i32[V], ``offsets`` i64[V+1],
        ``docs`` i32[P], ``tf`` f32[P], ``doc_len`` f32[N], ``doc_uids``
        (optional list), as produced by
        :meth:`from_segments` / index builders.

        ``dense_threshold``: terms with per-shard df above this go to the
        dense tier (default ``max(n_pad // 64, 4096)``) — see
        ``ops/tiered_bm25.py``. The sorted-merge L is then bounded by the
        largest *sparse* df instead of the corpus-wide max df.

        ``blockmax``: kwargs dict for :meth:`BlockMaxTier.build` (may be
        empty) — builds the impact-ordered block-max pruning tier at
        pack time so :meth:`serve` can run the rank-safe WAND-as-a-scan
        path (``prune``); None = eager-only plane (the default — the
        serving route enables the tier past its corpus threshold).

        A shard dict may carry an ``avgdl`` override: the serving path
        (``search/plane_route.py``) feeds one SEGMENT per plane shard but
        needs impacts normalized by the cross-segment shard-level avgdl
        (Lucene computes avgdl at the IndexSearcher level) so plane scores
        equal the per-segment path's bit-for-tie.
        """
        self.mesh = mesh
        self.field = field
        self.k1, self.b = k1, b
        # the mesh partitions the leading corpus dim over the shard axis:
        # absorb non-dividing shard counts with EMPTY pad shards (no
        # postings, no docs) — they can never match a term, so results
        # and hit coordinates are bit-identical to the same shard list on
        # any other mesh shape. Real shard indices are unchanged (pads
        # append), so callers decoding gdoc // n_pad are unaffected.
        shards = list(shards)
        for _ in range((-len(shards)) % mesh.shape[AXIS_SHARD]):
            shards.append(self.empty_pad_shard())
        self.n_shards = len(shards)
        #: dispatches through a compiled step (tests assert the plane ran)
        self.n_dispatches = 0

        self.n_pad = round_up_pow2(max(max(s["doc_len"].shape[0] for s in shards), 1))
        if dense_threshold is None:
            # ROOFLINE.md: the sparse tier's bitonic sort (VPU) is the
            # dominant per-dispatch cost at n_pad/64, while the dense
            # tier's streaming matmul (MXU + HBM) is far under its
            # ceiling — so push the boundary down: more head terms dense
            # (bounded by MAX_DENSE_TERMS), 4x smaller sort tiles
            dense_threshold = max(self.n_pad // 256, 4096)
        self.dense_threshold = dense_threshold

        # full-table impacts first (dense rows reference original postings),
        # then split each shard's vocab into tiers
        S = self.n_shards
        self.n_docs_total = 0
        impacts_full: List[np.ndarray] = []
        tiers: List[dict] = []
        for s in shards:
            if s.get("avgdl") is not None:
                avgdl = max(float(s["avgdl"]), 1e-9)
            else:
                fdc = max(int((s["doc_len"] > 0).sum()), 1)
                avgdl = max(float(s["doc_len"].sum()) / fdc, 1e-9)
            impacts_full.append(make_impacts(
                s["tf"], s["docs"], s["doc_len"], avgdl, k1, b))
            tiers.append(split_tiers(
                s, dense_threshold=dense_threshold,
                max_dense_terms=self.MAX_DENSE_TERMS))
            self.n_docs_total += int(s["doc_len"].shape[0])

        # block-max pruning tier: impact-ordered int8 blocks + bound
        # table over the FULL CSR, at the same frozen avgdl the impacts
        # above baked — bounds stay valid for the generation's lifetime
        self.blockmax: Optional[BlockMaxTier] = None
        if blockmax is not None:
            self.blockmax = BlockMaxTier.build(
                shards, impacts_full, n_pad=self.n_pad, **blockmax)

        # retain what query assembly needs: term dicts, ORIGINAL df (global
        # idf stats), sparse-tier offsets/df, dense row maps
        self.shards = []
        for s, t in zip(shards, tiers):
            dense_row_of = {int(tid): r
                            for r, tid in enumerate(t["dense_tids"])}
            self.shards.append(dict(
                term_ids=s["term_ids"], df=s["df"],
                sparse_offsets=t["offsets"], sparse_df=t["df"],
                dense_row_of=dense_row_of, doc_uids=s.get("doc_uids")))

        self.max_sparse_df = max(
            max((t["sparse_max_df"] for t in tiers), default=1), 1)
        self.L_cap = round_up_pow2(self.max_sparse_df)
        self.n_dense = max(t["dense_tids"].size for t in tiers)
        # multiple-of-16 (not pow2): the dense tier is T_pad × n_pad bf16 of
        # HBM, and the MXU only needs lane alignment, not a power of two
        self.T_pad = round_up_multiple(max(self.n_dense, 1), 16) \
            if self.n_dense else 0

        # sparse postings table with L_cap sentinel slack after the last run
        # so dynamic_slice(start, L) never clamps into foreign data
        p_need = max(t["docs"].shape[0] for t in tiers) + self.L_cap
        p_pad = -(-p_need // 1024) * 1024
        self.p_pad = p_pad
        docs = np.full((S, p_pad), self.n_pad, np.int32)
        impacts = np.zeros((S, p_pad), np.float32)
        for i, (s, t, imp) in enumerate(zip(shards, tiers, impacts_full)):
            pn = t["docs"].shape[0]
            docs[i, :pn] = t["docs"]
            keep = np.ones(s["docs"].shape[0], bool)
            for tid in t["dense_tids"]:
                keep[s["offsets"][tid]: s["offsets"][tid + 1]] = False
            impacts[i, :pn] = imp[keep]

        corpus_spec = NamedSharding(mesh, P(AXIS_SHARD, None))
        self.docs_dev = jax.device_put(docs, corpus_spec)
        self.impacts_dev = jax.device_put(impacts, corpus_spec)

        # CPU fallback: the streaming-matmul dense tier exists to ride the
        # MXU; on a CPU backend it does ~25x the arithmetic of term-at-a-
        # time scoring, so the plane keeps the ORIGINAL per-shard CSR (with
        # precomputed impacts) host-side and serves via
        # :meth:`search_eager` instead. Only retained on CPU — on TPU this
        # would duplicate the corpus in host RAM for nothing.
        self._host_csr = None
        if jax.devices()[0].platform == "cpu" and host_serve_enabled():
            self._host_csr = [
                dict(offsets=s["offsets"], docs=s["docs"], impacts=imp,
                     n_docs=int(s["doc_len"].shape[0]))
                for s, imp in zip(shards, impacts_full)]

        self.dense_dev = None
        if self.T_pad:
            C = min(self.DENSE_BLOCK, self.n_pad)
            self.dense_block = C
            dense = np.stack([
                build_dense_rows(s, t["dense_tids"], imp,
                                 n_pad=self.n_pad, block=C,
                                 t_pad=self.T_pad)
                for s, t, imp in zip(shards, tiers, impacts_full)])
            self.dense_dev = jax.device_put(
                dense, NamedSharding(mesh, P(AXIS_SHARD, None, None, None)))
        self._steps: Dict[Tuple, callable] = {}
        # dispatcher threads + the warmup thread build steps concurrently
        self._steps_lock = threading.Lock()
        self._serial_dispatch = _serial_dispatch_required(mesh)
        #: storage tier: "hot" = device-resident corpus arrays (today's
        #: path); "warm" = corpus pulled to host (``_warm_host``) and
        #: streamed to device per dispatch (the ``bm25_streamed``
        #: roofline family). Transitions run through
        #: :meth:`demote_to_warm` / :meth:`promote_to_hot` (the serving
        #: cache's tier manager drives them on access pressure).
        self.storage_tier = "hot"
        self._warm_host: Optional[dict] = None

    @staticmethod
    def empty_pad_shard(avgdl: Optional[float] = None) -> dict:
        """Inert mesh-pad shard (no postings, no docs): absorbs shard
        counts that don't divide the mesh's shard axis — it can never
        match a term or emit a hit. The ONE definition of the pad-shard
        schema, appended by both this constructor and the serving
        cache's pack paths (which pass the generation's frozen
        ``avgdl``, a no-op for a shard with no postings but kept
        uniform with its real shard dicts)."""
        sh = dict(term_ids={}, df=np.zeros(0, np.int32),
                  offsets=np.zeros(1, np.int64),
                  docs=np.zeros(0, np.int32), tf=np.zeros(0, np.float32),
                  doc_len=np.zeros(0, np.float32))
        if avgdl is not None:
            sh["avgdl"] = avgdl
        return sh

    def device_corpus_bytes(self) -> int:
        """Packed-corpus bytes RESIDENT PER DEVICE: the corpus arrays are
        sharded over the ``shard`` axis (each device holds 1/s_dev of the
        rows; replica groups hold full copies), so this is the per-chip
        HBM cost the MULTICHIP bench asserts scales ~1/n_shards.

        A demoted (warm/cold) generation holds NO resident device corpus
        — reporting 0 here is what makes the ``es_plane_hbm_bytes``
        gauge decrement on demotion."""
        if self.storage_tier != "hot":
            return 0
        s_dev = self.mesh.shape[AXIS_SHARD]
        total = int(self.docs_dev.nbytes) + int(self.impacts_dev.nbytes)
        if self.dense_dev is not None:
            total += int(self.dense_dev.nbytes)
        if self.blockmax is not None:
            # the block-major device tier incl. its sentinel pad block:
            # docs i32 + codes i8 per posting slot, scale/off per block
            bmx = self.blockmax
            nb1 = bmx.n_blocks + 1
            total += len(bmx.shards) * nb1 * (bmx.block * 5 + 8)
        return total // max(s_dev, 1)

    # -- storage tiers (hot / warm) ------------------------------------------

    def host_tier_bytes(self) -> int:
        """Host bytes the warm tier holds (the host-memory breaker's
        unit of account): the pulled corpus arrays only — the CPU
        host-CSR serving copy exists on every tier and is accounted at
        build time, not here."""
        warm = self._warm_host
        if warm is None:
            return 0
        total = int(warm["docs"].nbytes) + int(warm["impacts"].nbytes)
        if warm.get("dense") is not None:
            total += int(warm["dense"].nbytes)
        return total

    def demote_to_warm(self) -> int:
        """Hot → warm: pull the corpus arrays to host, drop every device
        reference (the HBM frees once in-flight dispatches release their
        captured refs). Serving keeps working — :meth:`search` streams
        the host copies to device per dispatch (``bm25_streamed``).
        Returns the host bytes now held (the warm-tier breaker
        estimate); 0 if the plane was not hot."""
        if self.storage_tier != "hot":
            return 0
        # pull OUTSIDE the steps lock (a device→host sync must not stall
        # concurrent step-cache readers), then swap refs under it
        warm = dict(
            docs=np.asarray(self.docs_dev),
            impacts=np.asarray(self.impacts_dev),
            dense=(np.asarray(self.dense_dev)
                   if self.dense_dev is not None else None))
        with self._steps_lock:
            self._warm_host = warm
            self.docs_dev = None
            self.impacts_dev = None
            self.dense_dev = None
            self.storage_tier = "warm"
        if self.blockmax is not None:
            with self.blockmax._dev_lock:
                self.blockmax._dev = None
        return self.host_tier_bytes()

    def promote_to_hot(self) -> int:
        """Warm → hot: re-upload the host copies as resident sharded
        device arrays and release the warm host tier. Returns the host
        bytes released (the warm breaker estimate to free); 0 if the
        plane was not warm."""
        if self.storage_tier != "warm":
            return 0
        warm = self._warm_host
        freed = self.host_tier_bytes()
        corpus_spec = NamedSharding(self.mesh, P(AXIS_SHARD, None))
        docs_dev = jax.device_put(warm["docs"], corpus_spec)
        impacts_dev = jax.device_put(warm["impacts"], corpus_spec)
        dense_dev = None
        if warm.get("dense") is not None and self.T_pad:
            dense_dev = jax.device_put(
                np.asarray(warm["dense"]).astype(jnp.bfloat16),
                NamedSharding(self.mesh, P(AXIS_SHARD, None, None, None)))
        with self._steps_lock:
            self.docs_dev = docs_dev
            self.impacts_dev = impacts_dev
            self.dense_dev = dense_dev
            self._warm_host = None
            self.storage_tier = "hot"
        return freed

    def _corpus_refs(self):
        """``(docs, impacts, dense, stream_bytes)`` for one dispatch:
        the resident device arrays (stream 0) when hot; fresh
        per-dispatch uploads of the warm host tiers when warm — the
        streamed bytes feed the ``bm25_streamed`` roofline model and
        ``es_plane_tier_stream_bytes_total``."""
        if self.storage_tier == "hot":
            return self.docs_dev, self.impacts_dev, self.dense_dev, 0
        warm = self._warm_host
        corpus_spec = NamedSharding(self.mesh, P(AXIS_SHARD, None))
        docs = jax.device_put(warm["docs"], corpus_spec)
        impacts = jax.device_put(warm["impacts"], corpus_spec)
        stream = int(warm["docs"].nbytes) + int(warm["impacts"].nbytes)
        dense = None
        if warm.get("dense") is not None and self.T_pad:
            dense = jax.device_put(
                np.asarray(warm["dense"]).astype(jnp.bfloat16),
                NamedSharding(self.mesh, P(AXIS_SHARD, None, None, None)))
            stream += int(warm["dense"].nbytes)
        return docs, impacts, dense, stream

    # -- warm-handoff packed state (the recovery artifact) -------------------

    def export_packed(self) -> dict:
        """Every post-pack tensor + invariant this plane computed, as a
        host dict the data-only wire codec can ship: the sorted-merge
        postings/impacts tables, the dense bf16 tier (shipped as exact
        f32 — bf16→f32→bf16 round-trips bit-identically), the block-max
        tier, the CPU host-CSR serving tier, and the per-shard lookup
        state. :meth:`from_packed` reconstructs a serving-identical
        plane WITHOUT re-running the pack (impacts, tier split,
        impact-ordering lexsort, dense fill) — the packed plane IS the
        recovery artifact (BM25S's eagerly-scored form). Works from any
        storage tier: a warm generation reads its host copies instead
        of the (released) device arrays."""
        warm = self._warm_host
        if warm is not None:
            docs_np = np.asarray(warm["docs"])
            impacts_np = np.asarray(warm["impacts"])
            dense_np = (np.asarray(warm["dense"]).astype(np.float32)
                        if warm.get("dense") is not None else None)
        else:
            docs_np = np.asarray(self.docs_dev)
            impacts_np = np.asarray(self.impacts_dev)
            dense_np = (np.asarray(self.dense_dev).astype(np.float32)
                        if self.dense_dev is not None else None)
        out = dict(
            field=self.field, k1=float(self.k1), b=float(self.b),
            n_shards=int(self.n_shards), n_pad=int(self.n_pad),
            p_pad=int(self.p_pad),
            dense_threshold=int(self.dense_threshold),
            n_docs_total=int(self.n_docs_total),
            max_sparse_df=int(self.max_sparse_df),
            L_cap=int(self.L_cap), n_dense=int(self.n_dense),
            T_pad=int(self.T_pad),
            dense_block=int(getattr(self, "dense_block", 0)),
            docs=docs_np,
            impacts=impacts_np,
            dense=dense_np,
            shards=[dict(term_ids=dict(sh["term_ids"]), df=sh["df"],
                         sparse_offsets=sh["sparse_offsets"],
                         sparse_df=sh["sparse_df"],
                         dense_row_of=dict(sh["dense_row_of"]),
                         doc_uids=(list(sh["doc_uids"])
                                   if sh.get("doc_uids") is not None
                                   else None))
                    for sh in self.shards],
            host_csr=self._host_csr, blockmax=None)
        if self.blockmax is not None:
            t = self.blockmax
            out["blockmax"] = dict(block=int(t.block),
                                   n_pad=int(t.n_pad),
                                   n_blocks=int(t.n_blocks),
                                   shards=t.shards)
        return out

    @classmethod
    def from_packed(cls, mesh: Mesh, packed: dict
                    ) -> "DistributedSearchPlane":
        """Reconstruct a plane from :meth:`export_packed` state: only
        the device uploads run — no pack work. Raises when the donor's
        (padded) shard count does not divide THIS mesh's shard axis
        (heterogeneous slices; the caller falls back to a local pack)."""
        self = cls.__new__(cls)
        self.mesh = mesh
        self.field = str(packed["field"])
        self.k1, self.b = float(packed["k1"]), float(packed["b"])
        self.n_shards = int(packed["n_shards"])
        if self.n_shards % mesh.shape[AXIS_SHARD]:
            raise ValueError(
                f"packed plane has {self.n_shards} shards; mesh shard "
                f"axis {mesh.shape[AXIS_SHARD]} does not divide it")
        self.n_pad = int(packed["n_pad"])
        self.p_pad = int(packed["p_pad"])
        self.dense_threshold = int(packed["dense_threshold"])
        self.n_docs_total = int(packed["n_docs_total"])
        self.max_sparse_df = int(packed["max_sparse_df"])
        self.L_cap = int(packed["L_cap"])
        self.n_dense = int(packed["n_dense"])
        self.T_pad = int(packed["T_pad"])
        self.n_dispatches = 0
        self.shards = [dict(term_ids=sh["term_ids"], df=sh["df"],
                            sparse_offsets=sh["sparse_offsets"],
                            sparse_df=sh["sparse_df"],
                            dense_row_of={int(k): int(v) for k, v in
                                          sh["dense_row_of"].items()},
                            doc_uids=sh.get("doc_uids"))
                       for sh in packed["shards"]]
        corpus_spec = NamedSharding(mesh, P(AXIS_SHARD, None))
        self.docs_dev = jax.device_put(
            np.asarray(packed["docs"], np.int32), corpus_spec)
        self.impacts_dev = jax.device_put(
            np.asarray(packed["impacts"], np.float32), corpus_spec)
        self.dense_dev = None
        if packed.get("dense") is not None and self.T_pad:
            self.dense_block = int(packed["dense_block"])
            self.dense_dev = jax.device_put(
                np.asarray(packed["dense"]).astype(jnp.bfloat16),
                NamedSharding(mesh, P(AXIS_SHARD, None, None, None)))
        self.blockmax = None
        bmx = packed.get("blockmax")
        if bmx is not None:
            t = BlockMaxTier(block=int(bmx["block"]))
            t.n_pad = int(bmx["n_pad"])
            t.n_blocks = int(bmx["n_blocks"])
            t.shards = [dict(sh) for sh in bmx["shards"]]
            self.blockmax = t
        self._host_csr = None
        if jax.devices()[0].platform == "cpu" and host_serve_enabled():
            self._host_csr = packed.get("host_csr")
        self._steps = {}
        self._steps_lock = threading.Lock()
        self._serial_dispatch = _serial_dispatch_required(mesh)
        self.storage_tier = "hot"
        self._warm_host = None
        return self

    @classmethod
    def from_segments(cls, mesh: Mesh, segments: Sequence, field: str, **kw):
        """Build from one :class:`~elasticsearch_tpu.index.segment.Segment`
        per shard (each shard collapsed to a single segment)."""
        shards = []
        for seg in segments:
            f = seg.text_fields[field]
            shards.append(dict(
                term_ids=f.term_ids, df=f.df, offsets=f.offsets,
                docs=f.docs_host, tf=f.tf_host, doc_len=f.doc_len_host,
                doc_uids=seg.doc_uids))
        return cls(mesh, shards, field, **kw)

    # -- query assembly ------------------------------------------------------

    def global_df(self, term: str) -> int:
        """Document frequency of ``term`` summed over every plane shard —
        the plane's contribution to global idf stats (the delta tier adds
        its own df on top via the ``extra_df`` dispatch kwarg)."""
        out = 0
        for sh in self.shards:
            tid = sh["term_ids"].get(term)
            if tid is not None:
                out += int(sh["df"][tid])
        return out

    def _lookup(self, queries: Sequence[Sequence[str]], Q: int,
                extra_docs: int = 0,
                extra_df: Optional[Dict[str, int]] = None):
        """Per-shard run/row lookup for a query batch. A term is scored by
        the sparse tier or the dense tier *per shard* (membership can differ
        across shards); global idf always uses the original df stats.

        ``extra_docs``/``extra_df``: corpus mass living OUTSIDE this plane
        (the serving delta tier — segments appended since the base pack).
        They only shift the host-side idf weights, so base and delta docs
        are scored under ONE shared set of global statistics; compile
        shapes are untouched."""
        B, S = len(queries), self.n_shards
        starts = np.zeros((B, S, Q), np.int32)
        lengths = np.zeros((B, S, Q), np.int32)
        dense_rid = np.zeros((B, S, Q), np.int32)
        dense_hit = np.zeros((B, S, Q), bool)
        weights = np.zeros((B, Q), np.float32)
        gdf = np.zeros((B, Q), np.int64)
        max_len = 1
        any_dense = False
        for bi, terms in enumerate(queries):
            uniq: Dict[str, int] = {}
            for t in terms:
                if t in uniq:
                    weights[bi, uniq[t]] += 1.0
                    continue
                qi = len(uniq)
                if qi >= Q:
                    continue
                uniq[t] = qi
                weights[bi, qi] = 1.0
                if extra_df:
                    gdf[bi, qi] += int(extra_df.get(t, 0))
                for si, sh in enumerate(self.shards):
                    tid = sh["term_ids"].get(t)
                    if tid is None:
                        continue
                    gdf[bi, qi] += int(sh["df"][tid])
                    row = sh["dense_row_of"].get(int(tid)) \
                        if sh["dense_row_of"] else None
                    if row is not None:
                        dense_rid[bi, si, qi] = row
                        dense_hit[bi, si, qi] = True
                        any_dense = True
                        continue
                    st = int(sh["sparse_offsets"][tid])
                    ln = int(sh["sparse_offsets"][tid + 1]) - st
                    starts[bi, si, qi] = st
                    lengths[bi, si, qi] = ln
                    max_len = max(max_len, ln)
        idf = idf_weight(self.n_docs_total + extra_docs,
                         gdf).astype(np.float32)
        idf[gdf == 0] = 0.0
        idfw = idf * weights
        return (starts, lengths, idfw, dense_rid, dense_hit, max_len,
                any_dense)

    def max_run_len(self, queries: Sequence[Sequence[str]]) -> int:
        """Longest sparse-tier posting run any of these queries touches
        — the minimal safe L.  Cheap (dict probes + offset diffs only;
        none of _lookup's array assembly), for callers sizing a shared
        compile shape across a workload; the bag route walks it only
        for a padded batch of at most :attr:`SHORT_RUNG_MAX_B`."""
        out = 1
        for terms in queries:
            for t in set(terms):
                for sh in self.shards:
                    tid = sh["term_ids"].get(t)
                    if tid is None:
                        continue
                    if sh["dense_row_of"] and \
                            int(tid) in sh["dense_row_of"]:
                        continue
                    ln = int(sh["sparse_offsets"][tid + 1]) - \
                        int(sh["sparse_offsets"][tid])
                    out = max(out, ln)
        return out

    def ladder_rungs(self) -> List[int]:
        """The fixed 4-step geometric L ladder (L_cap, L_cap/8, L_cap/64,
        L_cap/512 floored at 1024): the L axis of the bool and fused
        routes, and of the bag route's smallest padded batches
        (:meth:`serving_shape`); :meth:`ladder_L` picks from these."""
        return sorted({max(1024, self.L_cap >> s) for s in (9, 6, 3, 0)})

    def ladder_L(self, needed: int) -> int:
        """Smallest ladder rung ≥ needed: at most 4 sparse-merge compile
        shapes per (B, Q, k) family instead of ~log2(L_cap), while
        short-run batches still skip the worst-case merge cost."""
        for r in self.ladder_rungs():
            if needed <= r:
                return r
        return self.L_cap

    def _dense_rows(self, dense_rid, dense_hit) -> List[np.ndarray]:
        """The distinct dense-tier rows a batch touches, per shard."""
        return [np.unique(dense_rid[:, si, :][dense_hit[:, si, :]])
                for si in range(self.n_shards)]

    def _dense_inputs(self, idfw, dense_rid, dense_hit, u_lists, U: int):
        """Slot-space dense-tier inputs for one batch at the caller's
        gather width ``U``: ``u_ids`` i32[S, U]
        (the batch's used rows per shard, ``u_lists``; unused slots carry
        zero weight everywhere), the slot-indexed per-candidate (rid, w)
        pairs, and the slot-space weight matrix W f32[B, S, U]. At
        ``U == T_pad`` u_ids is a dummy (the step streams the full block
        array, no gather)."""
        B, S = dense_hit.shape[0], self.n_shards
        T = self.T_pad
        if U < T:
            u_ids = np.zeros((S, U), np.int32)
            rid_out = np.zeros_like(dense_rid)
            for si, rows in enumerate(u_lists):
                u_ids[si, :rows.size] = rows
                bi_ix, qi_ix = np.nonzero(dense_hit[:, si, :])
                if bi_ix.size:
                    rid_out[bi_ix, si, qi_ix] = np.searchsorted(
                        rows, dense_rid[bi_ix, si, qi_ix]).astype(np.int32)
        else:
            u_ids = np.zeros((S, 1), np.int32)
            rid_out = dense_rid
        dense_w = np.where(dense_hit, idfw[:, None, :], 0.0) \
            .astype(np.float32)
        W = np.zeros((B, S, max(U, 1)), np.float32)
        bi_ix, si_ix, qi_ix = np.nonzero(dense_hit)
        if bi_ix.size:
            np.add.at(W, (bi_ix, si_ix, rid_out[bi_ix, si_ix, qi_ix]),
                      idfw[bi_ix, qi_ix])
        return u_ids, rid_out, dense_w, W

    # -- the serving list: which programs a served bag batch runs ----------

    #: serving Q floor: dispatches through :meth:`serve` never trace a Q
    #: below this, collapsing the Q shape axis (1..8-unique-term queries
    #: all share one compile) at negligible host-assembly cost
    SERVING_Q_MIN = 8

    def serving_q(self, queries: Sequence[Sequence[str]]) -> int:
        """Q of a served bag batch: :attr:`SERVING_Q_MIN`, or the next
        power of two at or above the most distinct terms any query
        holds — the one axis of the serving list a batch's bags can
        still open (a query of more than 8 distinct terms)."""
        return max(self.SERVING_Q_MIN, round_up_pow2(max(
            max((len(set(q)) for q in queries), default=1), 1)))

    def serving_shape(self, b_pad: int, k_bucket: int,
                      Q: Optional[int] = None,
                      run_len: Optional[int] = None) -> Tuple:
        """THE decision "which program does a served bag batch run": the
        ``_get_step`` key ``(Q, L, k, tiered, with_count, U)`` of a batch
        padded to ``b_pad`` (the jitted step retraces on the batch
        dimension, so a program is the pair). A function of its
        arguments and of constants fixed at pack time (``L_cap``,
        ``T_pad``, the mesh), not of the batch's bags. Figures: one
        v5e chip, 2^21 docs of a Zipf corpus at MS MARCO's parameters
        (T_pad 256, L_cap 32768), ``jit_bm25_tiered``'s device ms a step
        (PERF.md §6, PR 32).

        - **L** is ``L_cap``: from five requests a batch on, 98 % of
          batches hold a long sparse run, and the ladder's rungs opened
          three programs a (B, k) for the rest. Only a padded batch of
          at most :attr:`SHORT_RUNG_MAX_B` keeps the ladder, by
          ``run_len`` (its longest sparse run): at B_pad 1 a step reads
          10.5 / 22.8 / 152.5 ms at L 1024 / 4096 / 32768, and 46 % of
          single requests (20 % of pairs) fit a short rung.
        - **U** is ``T_pad``: the whole dense tier streams, no gather.
          The width never showed in the step: at B_pad 8, 1238.4 /
          1239.1 / 1239.9 / 1241.9 ms gathered at U 16 / 32 / 64 / 128
          and 1233.1 streamed (B_pad 1: 152.5-156.7 against 148.3; 16
          and 32 alike), while a width read from the bags sat on a
          boundary between two programs at every batch size.
        - **Q** is :attr:`SERVING_Q_MIN` unless given (:meth:`serving_q`).
        - ``with_count`` is True: the batcher always asks for totals.

        :meth:`serve` runs only what this returns, and the batcher's
        warm-up compiles :meth:`serving_shapes`, which calls it."""
        Q = self.SERVING_Q_MIN if Q is None else Q
        L = self.L_cap
        if run_len is not None and b_pad <= self.SHORT_RUNG_MAX_B:
            L = min(self.ladder_L(run_len), self.L_cap)
        if self.T_pad:
            return Q, L, k_bucket, True, True, self.T_pad
        return Q, L, k_bucket, False, True, None

    def serving_shapes(self, k_buckets: Sequence[int],
                       max_b: int) -> List[Tuple]:
        """Every program the bag route serves at Q =
        :attr:`SERVING_Q_MIN`, as ``(b_pad, key)``: the micro-batcher's
        padded batches up to ``max_b`` x ``k_buckets``. The same list
        from a plane rebuilt by ``from_packed(export_packed())``."""
        out: List[Tuple] = []
        for b_pad in _padded_batches(self.mesh, max_b):
            runs = self.ladder_rungs() \
                if b_pad <= self.SHORT_RUNG_MAX_B else [None]
            for kb in sorted(set(k_buckets)):
                for run_len in runs:
                    shape = (b_pad, self.serving_shape(b_pad, kb,
                                                       run_len=run_len))
                    if shape not in out:
                        out.append(shape)
        return out

    def warm_shape(self, shape: Tuple) -> None:
        """Compile (or load from the cache) and run one member of
        :meth:`serving_shapes` on inert queries."""
        b_pad, (Q, L, k, tiered, with_count, U) = shape
        self.search([[]] * b_pad, k=k, Q=Q, L=L, U=U, tiered=tiered,
                    with_totals=with_count)

    def _serve_listed(self, queries: Sequence[Sequence[str]], k: int,
                      **kw):
        """The jitted bag dispatch at its :meth:`serving_shape`."""
        n_repl = self.mesh.shape[AXIS_REPLICA]
        b_pad = -(-len(queries) // n_repl) * n_repl
        run_len = self.max_run_len(queries) \
            if b_pad <= self.SHORT_RUNG_MAX_B else None
        Q, L, _k, tiered, _wc, U = self.serving_shape(
            b_pad, k, self.serving_q(queries), run_len)
        return self.search(queries, k=k, Q=Q, L=L, U=U, tiered=tiered,
                           **kw)

    def serve(self, queries: Sequence[Sequence[str]], k: int = 10,
              *, with_totals: bool = False,
              stages: Optional[dict] = None, extra_docs: int = 0,
              extra_df: Optional[Dict[str, int]] = None,
              prune: Optional[bool] = None):
        """Serving entry (the micro-batcher's dispatch hook): the
        CPU-native eager scorer when this plane was built on a CPU
        backend — term-at-a-time over precomputed impacts compiles
        nothing and beats XLA:CPU — else the jitted step at the shape
        :meth:`serving_shape` states for the padded batch, a member of
        the list the batcher's warm-up compiles
        (:meth:`serving_shapes`). ``extra_docs``/``extra_df`` fold a
        delta tier's corpus mass into the idf weights (see
        :meth:`_lookup`).

        ``prune``: block-max pruned scan (rank-safe — results are
        bit-identical to the eager scan; under an early exit the totals
        become ``(value, "gte")`` lower bounds, Lucene's WAND
        track-total-hits semantics). None = tier default (on when the
        plane packed a :class:`BlockMaxTier`); False forces eager.
        Result windows past the θ-window cap (k·Q > LEX_THETA_WINDOW —
        deep pagination / wide rescore windows) route straight to the
        eager scan: pruning is provably inert there, and the pruned
        machinery would only add candidate bookkeeping on top of a full
        scan."""
        # a warm plane serving the jitted path streams the f32 corpus
        # per dispatch anyway — the block-max device tier would pin HBM
        # back (its device cache is exactly what demotion dropped), so
        # warm routes to the plain streamed scan (rank-safe: pruning is
        # an optimization, never a result change). The host pruned path
        # stays available — it touches no device memory.
        warm_stream = self.storage_tier != "hot" and self._host_csr is None
        if self.blockmax is not None and prune is not False \
                and not warm_stream \
                and k * self.serving_q(queries) <= LEX_THETA_WINDOW:
            if self._host_csr is not None:
                return self.search_pruned_eager(
                    queries, k=k, with_totals=with_totals,
                    stages=stages, extra_docs=extra_docs,
                    extra_df=extra_df)
            return self.search_pruned(
                queries, k=k, with_totals=with_totals, stages=stages,
                extra_docs=extra_docs, extra_df=extra_df)
        if self._host_csr is not None:
            return self.search_eager(queries, k=k,
                                     with_totals=with_totals, stages=stages,
                                     extra_docs=extra_docs,
                                     extra_df=extra_df)
        return self._serve_listed(queries, k, with_totals=with_totals,
                                  stages=stages, extra_docs=extra_docs,
                                  extra_df=extra_df)

    def search(self, queries: Sequence[Sequence[str]], k: int = 10,
               *, Q: Optional[int] = None, L: Optional[int] = None,
               U: Optional[int] = None,
               tiered: Optional[bool] = None, with_totals: bool = False,
               stages: Optional[dict] = None, extra_docs: int = 0,
               extra_df: Optional[Dict[str, int]] = None):
        """Run a batch of bag-of-terms queries. Returns
        (scores f32[B, k], hits list[list[(shard, local_doc)]]) — plus
        exact per-query match counts (list[int], the device-side
        TotalHitCountCollector) when ``with_totals``.

        ``Q`` / ``L`` / ``U`` (slots a query, merge tile, dense-tier
        width): None sizes each to the smallest shape this batch's bags
        need — what the kernel tests and the plain references call, and
        the reference :meth:`serve`'s stated shapes are compared with;
        a given value too small for the batch raises.

        ``tiered``: None (default) picks the tiered kernel iff the batch
        touches a dense-tier term; True forces the tiered kernel whenever a
        dense tier exists (stable compile shapes for latency benchmarking —
        an all-sparse batch then just scores an empty dense weight matrix).

        ``stages``: optional dict receiving per-stage ms timings
        (``prep_ms`` host assembly + upload, ``dispatch_ms`` device step
        incl. any compile, ``fetch_ms`` result sync + decode).
        """
        with _tracing.Phases() as phases:
            phases.enter("plane[h2d]")
            t0 = time.perf_counter()
            B = len(queries)
            # pad the batch to a replica-axis multiple (the mesh partitions the
            # batch dim over replicas); padded slots run a no-op query
            n_repl = self.mesh.shape[AXIS_REPLICA]
            B_pad = -(-B // n_repl) * n_repl
            queries = list(queries) + [[] for _ in range(B_pad - B)]
            needed_q = max(max((len(set(q)) for q in queries), default=1), 1)
            if Q is None:
                Q = round_up_pow2(needed_q)
            elif Q < needed_q:
                raise ValueError(
                    f"Q={Q} would drop terms from a {needed_q}-term query; "
                    f"pass Q=None to size automatically")
            (starts, lengths, idfw, dense_rid, dense_hit, max_len,
             any_dense) = self._lookup(queries, Q, extra_docs=extra_docs,
                                       extra_df=extra_df)
            if L is None:
                L = round_up_pow2(max_len)
            elif L < max_len:
                raise ValueError(
                    f"L={L} would truncate a postings run of length "
                    f"{max_len}; pass L=None to size automatically")
            # L may never exceed the table's sentinel slack (slices would clamp
            # into foreign runs); L_cap >= max_sparse_df, so no real sparse run
            # is truncated
            L = min(L, self.L_cap)
            np.minimum(lengths, L, out=lengths)
            repl = NamedSharding(self.mesh, P(AXIS_REPLICA, None))
            repl3 = NamedSharding(self.mesh, P(AXIS_REPLICA, AXIS_SHARD, None))
            use_tiered = any_dense if tiered is None \
                else (tiered and self.T_pad > 0)
            if tiered is False and any_dense:
                raise ValueError(
                    "tiered=False but the batch hits dense-tier terms")
            docs_dev, impacts_dev, dense_dev, stream_b = self._corpus_refs()
            if use_tiered:
                u_lists = self._dense_rows(dense_rid, dense_hit)
                max_used = max((r.size for r in u_lists), default=0)
                if U is None:
                    U = min(self.T_pad,
                            max(16, round_up_pow2(max(max_used, 1))))
                elif U < min(max_used, self.T_pad):
                    raise ValueError(
                        f"U={U} would drop dense rows from a batch that "
                        f"touches {max_used}; pass U=None to size "
                        f"automatically")
                u_ids, rid_slots, dense_w, W = self._dense_inputs(
                    idfw, dense_rid, dense_hit, u_lists, U)
                step = self._get_step(Q, L, k, tiered=True,
                                      with_count=with_totals, U=U)
                shard2 = NamedSharding(self.mesh, P(AXIS_SHARD, None))
                step_args = (
                    docs_dev, impacts_dev, dense_dev,
                    jax.device_put(starts, repl3),
                    jax.device_put(lengths, repl3),
                    jax.device_put(idfw, repl),
                    jax.device_put(rid_slots, repl3),
                    jax.device_put(dense_w, repl3),
                    jax.device_put(W, repl3),
                    jax.device_put(u_ids, shard2))
            else:
                step = self._get_step(Q, L, k, with_count=with_totals)
                step_args = (
                    docs_dev, impacts_dev,
                    jax.device_put(starts, repl3),
                    jax.device_put(lengths, repl3),
                    jax.device_put(idfw, repl))
            phases.enter("plane[launch]")
            t1 = time.perf_counter()
            out = _run_step(self._serial_dispatch, step, *step_args)
            if stages is not None:
                # sync here so device time lands in dispatch_ms, not in the
                # first np.asarray of the fetch below
                phases.enter("plane[sync]")
                jax.block_until_ready(out)
            phases.enter("plane[d2h]")
            t2 = time.perf_counter()
            self.n_dispatches += 1
            from ..common import telemetry as _tm
            _tm.record_mesh_dispatch(self.mesh.shape[AXIS_SHARD],
                                     self.mesh.shape[AXIS_REPLICA])
            if stages is not None:
                # per-dispatch compile-cache verdict: profile's serving
                # section distinguishes a first-shape compile from steady state
                stages["compile_cache"] = (
                    "miss" if _tm.last_call_compiled() else "hit")
            vals, gdocs = out[0], out[1]
            vals = np.asarray(vals)[:B]          # drop replica-padding slots
            gdocs = np.asarray(gdocs)[:B]
            # device-transfer accounting: the per-dispatch uploads (resident
            # hot corpus arrays excluded; a warm plane's per-dispatch corpus
            # stream counted) + the fetched result rows
            h2d = starts.nbytes + lengths.nbytes + idfw.nbytes + stream_b + \
                (rid_slots.nbytes + dense_w.nbytes + W.nbytes + u_ids.nbytes
                 if use_tiered else 0)
            d2h = vals.nbytes + gdocs.nbytes
            _tm.record_transfer(h2d_bytes=h2d, d2h_bytes=d2h)
            if stream_b:
                _tm.record_tier_stream_bytes(stream_b)
            if stages is not None:
                # per-dispatch bytes for task resource attribution (the
                # micro-batcher shares them across the batch's slots)
                stages["h2d_bytes"] = h2d
                stages["d2h_bytes"] = d2h
            phases.enter("plane[decode]")
            hits = []
            for bi in range(B):
                row = []
                for v, g in zip(vals[bi], gdocs[bi]):
                    if v == NEG_INF:
                        break
                    row.append((int(g) // self.n_pad, int(g) % self.n_pad))
                hits.append(row)
        if stages is not None:
            stages["prep_ms"] = (t1 - t0) * 1e3
            stages["dispatch_ms"] = (t2 - t1) * 1e3
            stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
            # roofline audit inputs (common/roofline.py): the dense-tier
            # stream (U-gather working set when the batch gathered used
            # rows) + the sparse sorted-merge tile — the ROOFLINE.md
            # per-dispatch cost model for this exact dispatch's shapes.
            # A warm plane's dispatch is dominated by the host→device
            # corpus re-upload instead: the streamed-tier model, audited
            # against the host-link ceiling.
            from ..common import roofline as _rl
            if stream_b:
                stages["kernel"] = "bm25_streamed"
                stages["tier"] = "warm"
                stages["stream_bytes"] = stream_b
                stages["model_bytes"] = _rl.model_bytes_streamed(
                    stream_b, B_pad, k)
            else:
                stages["kernel"] = "bm25_eager"
                stages["model_bytes"] = _rl.model_bytes_bm25_dense(
                    B_pad, Q, L, U if use_tiered else 0, self.n_pad)
        if with_totals:
            totals = [int(c) for c in np.asarray(out[2])[:B]]
            return vals, hits, totals
        return vals, hits

    def search_eager(self, queries: Sequence[Sequence[str]], k: int = 10,
                     *, with_totals: bool = False,
                     stages: Optional[dict] = None, extra_docs: int = 0,
                     extra_df: Optional[Dict[str, int]] = None):
        """CPU-native serving path: term-at-a-time scatter-add over the
        original CSR with precomputed impacts, per shard, exact top-k with
        the kernel path's tie order (score desc, (shard, doc) asc).

        This is the same eager-scoring algorithm as Lucene's ``BulkScorer``
        loop (``search/internal/ContextIndexSearcher.java:210-224``) but
        each posting costs one multiply-add instead of the full BM25 norm
        (impacts are precomputed at build time — the plane's representation
        pays off on every backend). Only available when the plane was built
        on a CPU backend (``_host_csr`` retained).

        ``with_totals`` adds exact per-query match counts (docs with a
        positive score — impacts and idf weights are strictly positive,
        so a doc is matched iff some query term's posting touched it),
        matching the kernel path's ``with_count`` semantics."""
        if self._host_csr is None:
            raise RuntimeError("search_eager requires a CPU-backend plane")
        t0 = time.perf_counter()
        vals_out = np.full((len(queries), k), NEG_INF, np.float32)
        hits_out: List[List[Tuple[int, int]]] = []
        totals: List[int] = []
        postings_touched = 0
        for bi, terms in enumerate(queries):
            weights: Dict[str, float] = {}
            for t in terms:
                weights[t] = weights.get(t, 0.0) + 1.0
            # global idf over the original df stats (same as _lookup),
            # plus any delta-tier mass living outside this plane
            idfw_of: Dict[str, float] = {}
            for t, w in weights.items():
                gdf = sum(int(s2["df"][s2["term_ids"][t]])
                          for s2 in self.shards if t in s2["term_ids"])
                if extra_df:
                    gdf += int(extra_df.get(t, 0))
                if gdf:
                    idfw_of[t] = float(idf_weight(
                        self.n_docs_total + extra_docs, np.int64(gdf))) * w
            cand_v: List[np.ndarray] = []
            cand_g: List[np.ndarray] = []
            total = 0
            for si, (sh, csr) in enumerate(zip(self.shards,
                                               self._host_csr)):
                scores = np.zeros(csr["n_docs"], np.float32)
                matched = False
                for t, idfw in idfw_of.items():
                    tid = sh["term_ids"].get(t)
                    if tid is None:
                        continue
                    st = int(csr["offsets"][tid])
                    en = int(csr["offsets"][tid + 1])
                    if en > st:
                        # docs within one postings run are unique, so the
                        # fancy-index += is a safe (buffered) scatter-add
                        scores[csr["docs"][st:en]] += \
                            idfw * csr["impacts"][st:en]
                        matched = True
                        postings_touched += en - st
                if not matched:
                    continue
                if with_totals:
                    total += int(np.count_nonzero(scores > 0))
                kk = min(k, csr["n_docs"])
                # tie-stable bounded cut: the k-th-boundary tie resolves
                # doc-ascending (the kernel paths' tie contract)
                sel = tie_stable_topk_docs(scores, kk)
                cand_v.append(scores[sel])
                cand_g.append(sel.astype(np.int64) + si * self.n_pad)
            row: List[Tuple[int, int]] = []
            if cand_v:
                v = np.concatenate(cand_v)
                g = np.concatenate(cand_g)
                order = np.lexsort((g, -v))[:k]
                vals_out[bi, :order.size] = v[order]
                row = [(int(g[j]) // self.n_pad, int(g[j]) % self.n_pad)
                       for j in order]
            hits_out.append(row)
            totals.append(total)
        self.n_dispatches += 1
        if stages is not None:
            # host path: scoring IS the dispatch (no separate upload or
            # device sync to attribute); nothing compiles here
            stages["prep_ms"] = 0.0
            stages["dispatch_ms"] = (time.perf_counter() - t0) * 1e3
            stages["fetch_ms"] = 0.0
            stages["compile_cache"] = "host"
            # roofline audit inputs: postings read + per-query N-wide
            # score array (ROOFLINE.md block-max table, eager column)
            from ..common import roofline as _rl
            stages["kernel"] = "bm25_eager"
            stages["postings_touched"] = postings_touched
            stages["model_bytes"] = _rl.model_bytes_bm25_eager(
                len(queries), postings_touched, self.n_docs_total)
        if with_totals:
            return vals_out, hits_out, totals
        return vals_out, hits_out

    # -- block-max pruned serving -------------------------------------------

    def _query_idfw(self, terms: Sequence[str], extra_docs: int,
                    extra_df: Optional[Dict[str, int]]):
        """(term → idf·weight) in first-appearance order — the SAME dict
        :meth:`search_eager` iterates, so the pruned path's exact
        re-score accumulates f32 contributions in the identical order
        (bit-parity of every survivor's score)."""
        weights: Dict[str, float] = {}
        for t in terms:
            weights[t] = weights.get(t, 0.0) + 1.0
        idfw_of: Dict[str, float] = {}
        for t, w in weights.items():
            gdf = sum(int(s2["df"][s2["term_ids"][t]])
                      for s2 in self.shards if t in s2["term_ids"])
            if extra_df:
                gdf += int(extra_df.get(t, 0))
            if gdf:
                idfw_of[t] = float(idf_weight(
                    self.n_docs_total + extra_docs, np.int64(gdf))) * w
        return idfw_of

    def _prune_buffers(self, n_docs: int):
        """Per-(thread, corpus-size) reusable accumulators for the host
        pruned scan — callers reset the entries they touched (O(seen)),
        never the whole buffer. Thread-local: the micro-batcher runs
        PIPELINE_DEPTH dispatcher threads concurrently."""
        tls = self.__dict__.get("_prune_tls")
        if tls is None:
            with self._steps_lock:
                tls = self.__dict__.setdefault("_prune_tls",
                                               threading.local())
        bufs = getattr(tls, "bufs", None)
        if bufs is None:
            bufs = tls.bufs = {}
        pair = bufs.get(n_docs)
        if pair is None:
            pair = bufs[n_docs] = (np.zeros(n_docs, np.float32),
                                   np.zeros(n_docs, np.uint16))
        return pair

    def search_pruned_eager(self, queries: Sequence[Sequence[str]],
                            k: int = 10, *, with_totals: bool = False,
                            stages: Optional[dict] = None,
                            extra_docs: int = 0,
                            extra_df: Optional[Dict[str, int]] = None):
        """CPU-native rank-safe pruned serving: blocks stream in
        descending-bound order through a chunked scatter-add with a TRUE
        break once the remaining bound mass ρ drops below the running
        rank-safety threshold θ; survivors re-score exactly from the
        original CSR. Results (values, hits, tie order) are
        bit-identical to :meth:`search_eager`; totals become
        ``(value, "gte")`` lower bounds for queries that early-exited
        (the skipped blocks' docs were never counted)."""
        if self._host_csr is None or self.blockmax is None:
            raise RuntimeError("search_pruned_eager requires a CPU-backend "
                               "plane with a block-max tier")
        t0 = time.perf_counter()
        tier = self.blockmax
        BS = tier.block
        B = len(queries)
        vals_out = np.full((B, k), NEG_INF, np.float32)
        hits_out: List[List[Tuple[int, int]]] = []
        totals: List = []
        blocks_scored = blocks_total = surv_total = 0
        scanned_docs = 0
        for bi, terms in enumerate(queries):
            idfw_of = self._query_idfw(terms, extra_docs, extra_df)
            cand_v: List[np.ndarray] = []
            cand_g: List[np.ndarray] = []
            theta_seed = NEG_INF       # exact k-th best across shards
            pruned_any = False
            seen_total = 0
            for si, (sh, csr) in enumerate(zip(self.shards,
                                               self._host_csr)):
                term_rows = [(int(sh["term_ids"][t]), w)
                             for t, w in idfw_of.items()
                             if t in sh["term_ids"]]
                if not term_rows:
                    continue
                blk, wblk, rho, tpos, slack = tier.schedule(si, term_rows)
                n_sched = blk.shape[0]
                blocks_total += n_sched
                if not n_sched:
                    continue
                tsh = tier.shards[si]
                n_docs = csr["n_docs"]
                nterms = len(term_rows)
                # reusable per-(thread, corpus-size) accumulators: acc
                # holds quantized partials, tmask the per-doc seen-term
                # bitmask (a doc seen in term t's scanned blocks holds
                # its ONLY posting of t — postings are unique within a
                # term — so the doc's remaining mass is the UNSEEN
                # terms' remaining bounds, far tighter than the global
                # ρ). Reset is O(seen), not O(corpus): fresh 2×O(N)
                # allocations would cost more page faults per query
                # than the whole scan
                acc, tmask = self._prune_buffers(n_docs)
                fine_mask = nterms <= 16
                # θ candidates: DISTINCT doc ids whose live partial the
                # dense acc serves — the true k-th distinct partial is a
                # far tighter threshold than a value ring with up to Q
                # duplicate entries per doc
                wdocs = np.zeros(0, np.int64)
                wcap = max(4 * k, 64)
                theta = theta_seed
                pos = 0
                rho_end = 0.0
                chunk = 128
                # scan past the bare ρ < θ point by this factor: extra
                # blocks are cheap (~128 postings each) while every unit
                # of leftover per-term bound mass inflates the phase-2
                # candidate set — stop only once ρ < θ·tighten
                tighten = self.prune_tighten
                uniq = None
                seen_parts: List[np.ndarray] = []
                try:
                    while pos < n_sched:
                        theta_stop = theta * tighten if theta > 0 \
                            else theta
                        if theta > NEG_INF and rho[pos] < theta_stop:
                            rho_end = float(rho[pos])
                            pruned_any = True
                            break
                        take = min(chunk, n_sched - pos)
                        chunk = min(chunk * 4, 1024)
                        if theta > NEG_INF:
                            # ρ is nonincreasing: score only up to the
                            # first position the current θ already prunes
                            cut = int(np.searchsorted(
                                -rho[pos: pos + take], -theta_stop,
                                side="left"))
                            if cut < take:
                                take = cut
                                if take == 0:
                                    rho_end = float(rho[pos])
                                    pruned_any = True
                                    break
                        cb = blk[pos: pos + take]
                        cw = wblk[pos: pos + take]
                        ct = tpos[pos: pos + take]
                        d = tsh["docs"][cb]                  # [take, BS]
                        vhat = np.maximum(
                            tsh["scale"][cb][:, None]
                            * tsh["codes"][cb].astype(np.float32)
                            + tsh["off"][cb][:, None], 1e-9)
                        contrib = cw[:, None] * vhat
                        # duplicate docs inside one chunk only occur
                        # ACROSS terms (postings are unique within a
                        # term), so grouping the scatter by term keeps
                        # the fast buffered fancy-index add safe
                        for ti in np.unique(ct):
                            rows = ct == ti
                            dd = d[rows].ravel()
                            cc = contrib[rows].ravel()
                            m = dd < n_docs
                            if not m.all():
                                dd = dd[m]
                                cc = cc[m]
                            acc[dd] += cc
                            tmask[dd] |= np.uint16(
                                1 << int(ti)) if fine_mask \
                                else np.uint16(1)
                        # chunk's θ candidates by ACCUMULATED partial —
                        # multi-term docs concentrate here, and θ from
                        # true partials converges fastest
                        dr = d.ravel()
                        msk = dr < n_docs
                        dr = dr[msk]
                        seen_parts.append(dr)
                        av = acc[dr]
                        if av.size > wcap:
                            top = np.argpartition(-av, wcap - 1)[:wcap]
                            cdocs = dr[top]
                        else:
                            cdocs = dr
                        wdocs = np.unique(
                            np.concatenate([wdocs, cdocs]))
                        wvals = acc[wdocs]
                        if wdocs.size > wcap:
                            keepw = np.argpartition(-wvals,
                                                    wcap - 1)[:wcap]
                            wdocs, wvals = wdocs[keepw], wvals[keepw]
                        if wvals.size >= k:
                            theta = max(theta, float(
                                -np.partition(-wvals, k - 1)[k - 1])
                                - slack)
                        pos += take
                    scored = min(pos, n_sched)
                    blocks_scored += scored
                    scanned_docs += scored * BS
                    uniq = np.unique(np.concatenate(seen_parts)) \
                        if seen_parts else np.zeros(0, np.int64)
                    if with_totals:
                        seen_total += int(uniq.size)
                    if not uniq.size:
                        continue
                    sv = acc[uniq]
                    if theta > NEG_INF:
                        # per-term remaining bound at the stop point →
                        # per-doc remaining mass via a bitmask LUT (a
                        # completed schedule has no remaining mass and
                        # skips the 2^nterms table outright)
                        r_t = np.zeros(nterms, np.float64)
                        if pruned_any and pos < n_sched:
                            tail_t = tpos[pos:]
                            tail_b = tsh["bound"][blk[pos:]] \
                                * wblk[pos:]
                            for ti in range(nterms):
                                m = tail_t == ti
                                if m.any():
                                    r_t[ti] = float(tail_b[m].max())
                        if fine_mask and r_t.any():
                            lut = np.zeros(1 << nterms, np.float32)
                            idx = np.arange(1 << nterms)
                            for ti in range(nterms):
                                lut += np.where(idx & (1 << ti) == 0,
                                                np.float32(r_t[ti]), 0.0)
                            ub = sv + (slack + lut[tmask[uniq]])
                        elif r_t.any():
                            ub = sv + np.float32(slack + rho_end)
                        else:
                            ub = sv + np.float32(slack)
                        keep = ub >= theta
                        cand = uniq[keep]
                        cub = ub[keep]
                    else:
                        cand = uniq
                        cub = np.full(uniq.size, np.float64(np.inf))
                finally:
                    # O(seen) buffer reset — the scanned doc lists mark
                    # exactly the entries any scatter touched
                    if uniq is not None:
                        dirty = uniq
                    elif seen_parts:
                        dirty = np.unique(np.concatenate(seen_parts))
                    else:
                        dirty = np.zeros(0, np.int64)
                    acc[dirty] = 0.0
                    tmask[dirty] = 0
                if not cand.size:
                    continue
                # phase 2 — WAND's own evaluation loop, vectorized:
                # exact-score candidates in DESCENDING upper-bound order
                # and stop once the next upper bound falls strictly
                # below the running exact k-th (ties keep evaluating).
                # True top docs carry the largest bounds, so this
                # usually touches a few hundred docs, not the seen set.
                kk = min(k, n_docs)
                theta_x = theta_seed
                ev_docs: List[np.ndarray] = []
                ev_vals: List[np.ndarray] = []
                n_ev = 0
                i = 0
                CH = max(4 * kk, 512)
                # order only the head of the candidate list (argsort of
                # the full set costs more than the evaluations it
                # schedules); widen on the rare non-converged tail
                M = min(max(8 * kk, 8 * CH), cand.size)
                if cand.size > M:
                    head = np.argpartition(-cub, M - 1)[:M]
                    order = head[np.argsort(-cub[head], kind="stable")]
                else:
                    order = np.argsort(-cub, kind="stable")
                cub_sorted = cub[order]
                while i < cand.size:
                    if i >= order.size:
                        # the pre-sorted head ran out before θ_x closed
                        # the loop: widen to the full candidate order
                        rest = np.setdiff1d(np.arange(cand.size), order,
                                            assume_unique=False)
                        rest = rest[np.argsort(-cub[rest],
                                               kind="stable")]
                        order = np.concatenate([order, rest])
                        cub_sorted = cub[order]
                    if theta_x > NEG_INF:
                        stop = int(np.searchsorted(-cub_sorted[i:],
                                                   -theta_x,
                                                   side="right"))
                        if stop == 0:
                            break
                        take = min(CH, stop)
                    else:
                        take = CH
                    sel_i = order[i: i + take]
                    chunk_d = cand[sel_i]
                    chunk_d.sort()
                    # exact re-score from the f32 CSR, in the eager
                    # path's term order and arithmetic (quantized
                    # partials only chose the window, never the ranking)
                    scores = np.zeros(chunk_d.size, np.float32)
                    for t, idfw in idfw_of.items():
                        tid = sh["term_ids"].get(t)
                        if tid is None:
                            continue
                        st = int(csr["offsets"][tid])
                        en = int(csr["offsets"][tid + 1])
                        if en <= st:
                            continue
                        run = csr["docs"][st:en]
                        p = np.searchsorted(run, chunk_d)
                        hit = p < (en - st)
                        hit[hit] = run[p[hit]] == chunk_d[hit]
                        scores[hit] += idfw * csr["impacts"][st + p[hit]]
                    ev_docs.append(chunk_d)
                    ev_vals.append(scores)
                    n_ev += chunk_d.size
                    if n_ev >= kk:
                        allv = np.concatenate(ev_vals) if len(ev_vals) > 1 \
                            else ev_vals[0]
                        theta_x = max(theta_x, float(
                            -np.partition(-allv, kk - 1)[kk - 1]))
                    i += take
                surv_total += n_ev
                if not ev_docs:
                    continue
                sel = np.concatenate(ev_docs)
                svv = np.concatenate(ev_vals)
                posv = svv > 0
                sel, svv = sel[posv], svv[posv]
                # tie-stable cut, matching search_eager's boundary order
                if sel.size > kk:
                    kth = -np.partition(-svv, kk - 1)[kk - 1]
                    keepv = svv >= kth
                    sel, svv = sel[keepv], svv[keepv]
                order = np.lexsort((sel, -svv))[:kk]
                sel, sv = sel[order], svv[order]
                cand_v.append(sv)
                cand_g.append(sel.astype(np.int64) + si * self.n_pad)
                # exact k-th best so far floors the next shard's θ —
                # a later shard prunes against the global threshold
                allv = np.concatenate(cand_v)
                if allv.size >= k:
                    theta_seed = max(
                        theta_seed,
                        float(-np.partition(-allv, k - 1)[k - 1]))
            row: List[Tuple[int, int]] = []
            if cand_v:
                v = np.concatenate(cand_v)
                g = np.concatenate(cand_g)
                order = np.lexsort((g, -v))[:k]
                vals_out[bi, :order.size] = v[order]
                row = [(int(g[j]) // self.n_pad, int(g[j]) % self.n_pad)
                       for j in order]
            hits_out.append(row)
            totals.append((seen_total, "gte") if pruned_any
                          else seen_total)
        self.n_dispatches += 1
        from ..common import telemetry as _tm
        q_bytes = blocks_scored * BS * 5 + blocks_total * 4
        x_bytes = surv_total * 8 * max(
            max((len(set(q)) for q in queries), default=1), 1)
        _tm.record_lex(blocks_scored=blocks_scored,
                       blocks_skipped=blocks_total - blocks_scored,
                       quantized_bytes=q_bytes, exact_bytes=x_bytes)
        if stages is not None:
            stages["prep_ms"] = 0.0
            stages["dispatch_ms"] = (time.perf_counter() - t0) * 1e3
            stages["fetch_ms"] = 0.0
            stages["compile_cache"] = "host"
            stages["docs_scanned"] = scanned_docs // max(B, 1)
            stages["lex_blocks_scored"] = blocks_scored
            stages["lex_blocks_total"] = blocks_total
            stages["lex_survivors"] = surv_total
            from ..common import roofline as _rl
            stages["kernel"] = "bm25_pruned"
            stages["model_bytes"] = _rl.model_bytes_bm25_pruned(
                q_bytes, x_bytes)
        if with_totals:
            return vals_out, hits_out, totals
        return vals_out, hits_out

    #: pruned-step compile knob: survivor window = LEX_RERANK × k
    #: (pow2-rounded); tests shrink it to force the unsafe→eager
    #: fallback
    prune_rerank = LEX_RERANK

    #: host-scan stop factor: keep scanning until ρ < θ·prune_tighten —
    #: values < 1 trade a few extra (cheap) blocks for a much smaller
    #: phase-2 candidate set (the per-term remaining bounds shrink).
    #: 0.7 measured best on the lexical_10m_prune bench shape
    prune_tighten = 0.7

    def search_pruned(self, queries: Sequence[Sequence[str]],
                      k: int = 10, *, with_totals: bool = False,
                      stages: Optional[dict] = None, extra_docs: int = 0,
                      extra_df: Optional[Dict[str, int]] = None):
        """Jitted block-max pruned dispatch
        (:func:`build_pruned_bm25_step`): host assembles the batch's
        descending-bound block schedule (pow2-bucketed length — the
        compile-shape lattice's P axis), the device scan masks out steps
        past each query's rank-safety threshold, survivors re-score
        exactly, and any query whose safety verdict fails — or any batch
        touching dense-tier terms, which the streaming-matmul tier
        already serves — re-dispatches through the eager kernel. Exact
        on every input by construction."""
        if self.blockmax is None:
            raise RuntimeError("plane has no block-max tier")
        if self.storage_tier != "hot":
            # warm plane: the block-max device tier was dropped on
            # demotion and the corpus streams per dispatch anyway —
            # serve through the (rank-identical) streamed eager scan
            return self._serve_listed(
                queries, k, with_totals=with_totals, stages=stages,
                extra_docs=extra_docs, extra_df=extra_df)
        with _tracing.Phases() as phases:
            phases.enter("plane[h2d]")
            t0 = time.perf_counter()
            tier = self.blockmax
            BS = tier.block
            B = len(queries)
            n_repl = self.mesh.shape[AXIS_REPLICA]
            B_pad = -(-B // n_repl) * n_repl
            queries = list(queries) + [[] for _ in range(B_pad - B)]
            Q = self.serving_q(queries)
            (starts, lengths, idfw, _rid, dense_hit, _ml,
             any_dense) = self._lookup(queries, Q, extra_docs=extra_docs,
                                       extra_df=extra_df)
            if any_dense:
                # Zipf-head terms live in the dense streaming-matmul tier —
                # already the device's fast path for exactly those postings:
                # the bag route's own dispatch, at its stated shape
                return self._serve_listed(
                    queries[:B], k, with_totals=with_totals,
                    stages=stages, extra_docs=extra_docs,
                    extra_df=extra_df)
            S = self.n_shards
            NB = tier.n_blocks
            P_need = 1
            per_qs: List[List[tuple]] = []
            for bi, terms in enumerate(queries):
                idfw_of = self._query_idfw(terms, extra_docs, extra_df)
                rows = []
                for si, sh in enumerate(self.shards):
                    term_rows = [(int(sh["term_ids"][t]), w)
                                 for t, w in idfw_of.items()
                                 if t in sh["term_ids"]]
                    blk, wblk, rho, _tpos, slack = tier.schedule(
                        si, term_rows)
                    rows.append((blk, wblk, rho, slack))
                    P_need = max(P_need, blk.shape[0])
                per_qs.append(rows)
            P_sched = round_up_pow2(P_need)
            sched = np.full((B_pad, S, P_sched), NB, np.int32)
            w_arr = np.zeros((B_pad, S, P_sched), np.float32)
            rho_arr = np.zeros((B_pad, S, P_sched), np.float32)
            slack_arr = np.zeros((B_pad, S), np.float32)
            sched_lens = np.zeros((B_pad, S), np.int64)
            for bi, rows in enumerate(per_qs):
                for si, (blk, wblk, rho, slack) in enumerate(rows):
                    n = blk.shape[0]
                    sched[bi, si, :n] = blk
                    w_arr[bi, si, :n] = wblk
                    rho_arr[bi, si, :n] = rho
                    slack_arr[bi, si] = slack
                    sched_lens[bi, si] = n
            kk = min(k, self.n_pad)
            W = min(round_up_pow2(max(k * Q, 1)), LEX_THETA_WINDOW)
            R = min(round_up_pow2(max(self.prune_rerank * kk, 64)),
                    self.n_pad)
            step = self._get_pruned_step(Q, k, P_sched, W, R)
            dev = tier.device_arrays(self.mesh)
            repl = NamedSharding(self.mesh, P(AXIS_REPLICA, None))
            repl2 = NamedSharding(self.mesh, P(AXIS_REPLICA, AXIS_SHARD))
            repl3 = NamedSharding(self.mesh, P(AXIS_REPLICA, AXIS_SHARD, None))
            phases.enter("plane[launch]")
            t1 = time.perf_counter()
            out = _run_step(
                self._serial_dispatch, step,
                self.docs_dev, self.impacts_dev,
                dev["docs"], dev["codes"], dev["scale"], dev["off"],
                jax.device_put(sched, repl3),
                jax.device_put(w_arr, repl3),
                jax.device_put(rho_arr, repl3),
                jax.device_put(slack_arr, repl2),
                jax.device_put(starts, repl3),
                jax.device_put(lengths, repl3),
                jax.device_put(idfw, repl))
            if stages is not None:
                phases.enter("plane[sync]")
                jax.block_until_ready(out)
            phases.enter("plane[d2h]")
            t2 = time.perf_counter()
            self.n_dispatches += 1
            from ..common import telemetry as _tm
            _tm.record_mesh_dispatch(self.mesh.shape[AXIS_SHARD],
                                     self.mesh.shape[AXIS_REPLICA])
            compiled = _tm.last_call_compiled()
            gvals = np.asarray(out[0])[:B]
            gdocs = np.asarray(out[1])[:B]
            matched = np.asarray(out[2])[:B]
            unsafe = np.asarray(out[3])[:B] > 0
            pruned = np.asarray(out[4])[:B] > 0
            n_sc = np.asarray(out[5])[:B]
            h2d = sched.nbytes + w_arr.nbytes + rho_arr.nbytes + \
                slack_arr.nbytes + starts.nbytes + lengths.nbytes + idfw.nbytes
            d2h = gvals.nbytes + gdocs.nbytes + matched.nbytes * 4
            _tm.record_transfer(h2d_bytes=h2d, d2h_bytes=d2h)
            phases.enter("plane[decode]")
            vals_out = np.full((B, k), NEG_INF, np.float32)
            wk = min(k, gvals.shape[1])
            vals_out[:, :wk] = gvals[:, :wk]
            hits_out: List[List[Tuple[int, int]]] = []
            totals: List = []
            for bi in range(B):
                row = []
                for v, g in zip(vals_out[bi], gdocs[bi]):
                    if v == NEG_INF:
                        break
                    row.append((int(g) // self.n_pad, int(g) % self.n_pad))
                hits_out.append(row)
                totals.append((int(matched[bi]), "gte") if pruned[bi]
                              else int(matched[bi]))
            # rank-safety fallback: queries whose survivor window could not
            # certify the top-k re-serve through the eager kernel (pruned
            # results are bit-exact BY CONSTRUCTION, not by luck)
            bad = np.flatnonzero(unsafe)
            if bad.size:
                bad_q = [queries[i] for i in bad]
                # pad to a power of two like the micro-batcher does: a raw
                # count of unsafe queries would compile one eager program per
                # distinct count, off the serving list
                bad_q += [[] for _ in range(
                    round_up_pow2(len(bad_q), 1) - len(bad_q))]
                ev = self._serve_listed(bad_q, k, with_totals=True,
                                        extra_docs=extra_docs,
                                        extra_df=extra_df)
                for j, i in enumerate(bad):
                    src = np.asarray(ev[0][j], np.float32)[:k]
                    vals_out[i] = NEG_INF
                    vals_out[i, :src.shape[0]] = src
                    hits_out[i] = list(ev[1][j])[:k]
                    totals[i] = int(ev[2][j])
            blocks_scored = int(n_sc.sum())
            blocks_total = int(sched_lens[:B].sum())
            q_bytes = blocks_scored * BS * 5 + blocks_total * 4
            x_bytes = B * R * Q * 8 * S
            _tm.record_lex(blocks_scored=blocks_scored,
                           blocks_skipped=blocks_total - blocks_scored,
                           quantized_bytes=q_bytes, exact_bytes=x_bytes)
        if stages is not None:
            stages["prep_ms"] = (t1 - t0) * 1e3
            stages["dispatch_ms"] = (t2 - t1) * 1e3
            stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
            stages["compile_cache"] = "miss" if compiled else "hit"
            stages["h2d_bytes"] = h2d
            stages["d2h_bytes"] = d2h
            stages["docs_scanned"] = blocks_scored * BS // max(B, 1)
            stages["lex_blocks_scored"] = blocks_scored
            stages["lex_blocks_total"] = blocks_total
            from ..common import roofline as _rl
            stages["kernel"] = "bm25_pruned"
            stages["model_bytes"] = _rl.model_bytes_bm25_pruned(
                q_bytes, x_bytes)
        if with_totals:
            return vals_out, hits_out, totals
        return vals_out, hits_out

    def _get_pruned_step(self, Q: int, k: int, P_sched: int, W: int,
                         R: int):
        return self.cached_step(
            ("bmx", Q, k, P_sched, W, R),
            lambda: build_pruned_bm25_step(
                self.mesh, n_pad=self.n_pad, Q=Q, k=k,
                P_sched=P_sched, W=W, R=R, BS=self.blockmax.block,
                NB=self.blockmax.n_blocks, n_shards=self.n_shards),
            "text_plane_pruned")

    # -- bool-tree serving stages (the fused planner's lexical stage) --------

    def _bool_clause_idfw(self, clauses, extra_docs: int,
                          extra_df: Optional[Dict[str, int]]):
        """Per-clause ``[(term, idf·weight)]`` under this plane's global
        stats (+ any delta-tier mass) — :func:`bool_clause_rows` with
        the same cached idf closure :meth:`_query_idfw` uses."""
        idf_cache: Dict[str, float] = {}

        def idf_of(t: str) -> float:
            v = idf_cache.get(t)
            if v is None:
                gdf = sum(int(s2["df"][s2["term_ids"][t]])
                          for s2 in self.shards if t in s2["term_ids"])
                if extra_df:
                    gdf += int(extra_df.get(t, 0))
                v = float(idf_weight(self.n_docs_total + extra_docs,
                                     np.int64(gdf))) if gdf else 0.0
                idf_cache[t] = v
            return v

        return bool_clause_rows(clauses, idf_of)

    def search_bool_eager(self, bool_queries, k: int = 10, *,
                          with_totals: bool = False,
                          stages: Optional[dict] = None,
                          extra_docs: int = 0,
                          extra_df: Optional[Dict[str, int]] = None):
        """CPU-native bool-tree serving: one scatter-add pass per
        scoring clause plus a clause-bit pass per matching clause, then
        a bitmask eligibility verdict (must/filter all present, must_not
        absent, ≥ msm should clauses) — Lucene's BooleanWeight as a
        data-parallel pass over the plane's precomputed impacts. Each
        query is ``{"clauses": [(role, [terms...])...], "msm": int}``
        (msm already resolved by the planner). Degenerates bit-exactly
        to :meth:`search_eager` for a single should clause."""
        if self._host_csr is None:
            raise RuntimeError(
                "search_bool_eager requires a CPU-backend plane")
        t0 = time.perf_counter()
        B = len(bool_queries)
        vals_out = np.full((B, k), NEG_INF, np.float32)
        hits_out: List[List[Tuple[int, int]]] = []
        totals: List[int] = []
        for bi, bq in enumerate(bool_queries):
            clauses = bq.get("clauses") or []
            msm = int(bq.get("msm", 0))
            req, neg, shd = bool_role_masks(clauses)
            per_clause = self._bool_clause_idfw(clauses, extra_docs,
                                                extra_df)
            cand_v: List[np.ndarray] = []
            cand_g: List[np.ndarray] = []
            total = 0
            for si, (sh, csr) in enumerate(zip(self.shards,
                                               self._host_csr)):
                got = _bool_csr_shard_pool(sh["term_ids"], csr,
                                           per_clause, req, neg, shd,
                                           msm)
                if got is None:
                    continue
                scores, pool = got
                if with_totals:
                    total += int(pool.size)
                if not pool.size:
                    continue
                kk = min(k, csr["n_docs"])
                sel = tie_stable_topk_masked(scores, pool, kk)
                cand_v.append(scores[sel])
                cand_g.append(sel.astype(np.int64) + si * self.n_pad)
            row: List[Tuple[int, int]] = []
            if cand_v:
                v = np.concatenate(cand_v)
                g = np.concatenate(cand_g)
                order = np.lexsort((g, -v))[:k]
                vals_out[bi, :order.size] = v[order]
                row = [(int(g[j]) // self.n_pad, int(g[j]) % self.n_pad)
                       for j in order]
            hits_out.append(row)
            totals.append(total)
        self.n_dispatches += 1
        if stages is not None:
            stages["prep_ms"] = 0.0
            stages["dispatch_ms"] = (time.perf_counter() - t0) * 1e3
            stages["fetch_ms"] = 0.0
            stages["compile_cache"] = "host"
        if with_totals:
            return vals_out, hits_out, totals
        return vals_out, hits_out

    def has_dense_terms(self, terms) -> bool:
        """True when any term lives in some shard's dense matmul tier —
        the jitted bool/fused steps slice only the SPARSE table, so such
        batches must fall back (the host paths carry the full CSR)."""
        for t in set(terms):
            for sh in self.shards:
                tid = sh["term_ids"].get(t)
                if tid is not None and sh["dense_row_of"] and \
                        int(tid) in sh["dense_row_of"]:
                    return True
        return False

    def bool_inputs(self, bool_queries, Q: int, *, extra_docs: int = 0,
                    extra_df: Optional[Dict[str, int]] = None):
        """Device-input assembly for a bool-query batch: slot-per-
        (clause, unique term) runs over the SPARSE table plus the
        per-query clause-role masks. Returns (starts, lengths, idfw,
        cbits, req, neg, shd, msm, max_len, any_dense)."""
        B, S = len(bool_queries), self.n_shards
        starts = np.zeros((B, S, Q), np.int32)
        lengths = np.zeros((B, S, Q), np.int32)
        idfw = np.zeros((B, Q), np.float32)
        cbits = np.zeros((B, Q), np.int32)
        req = np.zeros(B, np.int32)
        neg = np.zeros(B, np.int32)
        shd = np.zeros(B, np.int32)
        msm = np.zeros(B, np.int32)
        max_len = 1
        any_dense = False
        for bi, bq in enumerate(bool_queries):
            clauses = bq.get("clauses") or []
            msm[bi] = int(bq.get("msm", 0))
            r, n, s = bool_role_masks(clauses)
            req[bi], neg[bi], shd[bi] = r, n, s
            per_clause = self._bool_clause_idfw(clauses, extra_docs,
                                                extra_df)
            qi = 0
            for ci, (role, rows) in enumerate(per_clause):
                for t, w in rows:
                    if qi >= Q:
                        continue
                    idfw[bi, qi] = w
                    cbits[bi, qi] = 1 << ci
                    for si, sh in enumerate(self.shards):
                        tid = sh["term_ids"].get(t)
                        if tid is None:
                            continue
                        if sh["dense_row_of"] and \
                                int(tid) in sh["dense_row_of"]:
                            any_dense = True
                            continue
                        st = int(sh["sparse_offsets"][tid])
                        ln = int(sh["sparse_offsets"][tid + 1]) - st
                        starts[bi, si, qi] = st
                        lengths[bi, si, qi] = ln
                        max_len = max(max_len, ln)
                    qi += 1
        return (starts, lengths, idfw, cbits, req, neg, shd, msm,
                max_len, any_dense)

    @staticmethod
    def bool_slot_count(bool_queries) -> int:
        """Slots a bool-query batch needs (one per (clause, unique
        term)) — the Q shape axis of the bool/fused steps."""
        out = 1
        for bq in bool_queries:
            n = 0
            for _role, terms in (bq.get("clauses") or []):
                n += len(set(terms))
            out = max(out, n)
        return out

    def search_bool(self, bool_queries, k: int = 10, *,
                    with_totals: bool = False,
                    stages: Optional[dict] = None, extra_docs: int = 0,
                    extra_df: Optional[Dict[str, int]] = None):
        """Jitted bool-tree dispatch at the serving shapes (Q floor,
        ladder L, fixed NC unroll). Dense-tier terms cannot ride the
        sparse slice — callers check :meth:`has_dense_terms` first."""
        from ..ops.fused_query import MAX_BOOL_CLAUSES
        with _tracing.Phases() as phases:
            phases.enter("plane[h2d]")
            t0 = time.perf_counter()
            B = len(bool_queries)
            n_repl = self.mesh.shape[AXIS_REPLICA]
            B_pad = -(-B // n_repl) * n_repl
            bool_queries = list(bool_queries) + [
                {"clauses": [], "msm": 0} for _ in range(B_pad - B)]
            Q = max(self.SERVING_Q_MIN,
                    round_up_pow2(self.bool_slot_count(bool_queries)))
            (starts, lengths, idfw, cbits, req, neg, shd, msm, max_len,
             any_dense) = self.bool_inputs(bool_queries, Q,
                                           extra_docs=extra_docs,
                                           extra_df=extra_df)
            if any_dense:
                raise ValueError(
                    "bool batch touches dense-tier terms; the sparse-slice "
                    "bool step cannot serve it (fall back)")
            L = min(self.ladder_L(max_len), self.L_cap)
            np.minimum(lengths, L, out=lengths)
            step = self._get_bool_step(Q, L, k, with_count=True,
                                       nc=MAX_BOOL_CLAUSES)
            # warm plane: stream the sparse tables per dispatch (the bool
            # step never reads the dense tier, so only docs/impacts ship)
            if self.storage_tier == "hot":
                docs_dev, impacts_dev, stream_b = \
                    self.docs_dev, self.impacts_dev, 0
            else:
                _warm = self._warm_host
                _cs = NamedSharding(self.mesh, P(AXIS_SHARD, None))
                docs_dev = jax.device_put(_warm["docs"], _cs)
                impacts_dev = jax.device_put(_warm["impacts"], _cs)
                stream_b = int(_warm["docs"].nbytes) + \
                    int(_warm["impacts"].nbytes)
            repl = NamedSharding(self.mesh, P(AXIS_REPLICA, None))
            repl1 = NamedSharding(self.mesh, P(AXIS_REPLICA))
            repl3 = NamedSharding(self.mesh, P(AXIS_REPLICA, AXIS_SHARD,
                                               None))
            phases.enter("plane[launch]")
            t1 = time.perf_counter()
            out = _run_step(
                self._serial_dispatch, step, docs_dev,
                impacts_dev,
                jax.device_put(starts, repl3),
                jax.device_put(lengths, repl3),
                jax.device_put(idfw, repl), jax.device_put(cbits, repl),
                jax.device_put(req, repl1), jax.device_put(neg, repl1),
                jax.device_put(shd, repl1), jax.device_put(msm, repl1))
            if stages is not None:
                phases.enter("plane[sync]")
                jax.block_until_ready(out)
            phases.enter("plane[d2h]")
            t2 = time.perf_counter()
            self.n_dispatches += 1
            from ..common import telemetry as _tm
            _tm.record_mesh_dispatch(self.mesh.shape[AXIS_SHARD],
                                     self.mesh.shape[AXIS_REPLICA])
            compiled = _tm.last_call_compiled()
            vals = np.asarray(out[0])[:B]
            gdocs = np.asarray(out[1])[:B]
            counts = np.asarray(out[2])[:B]
            h2d = starts.nbytes + lengths.nbytes + idfw.nbytes + \
                cbits.nbytes + 16 * B_pad + stream_b
            d2h = vals.nbytes + gdocs.nbytes + counts.nbytes
            _tm.record_transfer(h2d_bytes=h2d, d2h_bytes=d2h)
            if stream_b:
                _tm.record_tier_stream_bytes(stream_b)
            phases.enter("plane[decode]")
            hits = []
            for bi in range(B):
                row = []
                for v, g in zip(vals[bi], gdocs[bi]):
                    if v == NEG_INF:
                        break
                    row.append((int(g) // self.n_pad, int(g) % self.n_pad))
                hits.append(row)
        if stages is not None:
            stages["prep_ms"] = (t1 - t0) * 1e3
            stages["dispatch_ms"] = (t2 - t1) * 1e3
            stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
            stages["compile_cache"] = "miss" if compiled else "hit"
            stages["h2d_bytes"] = h2d
            stages["d2h_bytes"] = d2h
            if stream_b:
                from ..common import roofline as _rl
                stages["kernel"] = "bm25_streamed"
                stages["tier"] = "warm"
                stages["stream_bytes"] = stream_b
                stages["model_bytes"] = _rl.model_bytes_streamed(
                    stream_b, B_pad, k)
        if with_totals:
            return vals, hits, [int(c) for c in counts]
        return vals, hits

    def serve_bool(self, bool_queries, k: int = 10, *,
                   with_totals: bool = False,
                   stages: Optional[dict] = None, extra_docs: int = 0,
                   extra_df: Optional[Dict[str, int]] = None):
        """Serving entry for lowered bool trees: CPU-native eager pass
        on a CPU-backend plane, else the jitted bool step."""
        if self._host_csr is not None:
            return self.search_bool_eager(
                bool_queries, k=k, with_totals=with_totals,
                stages=stages, extra_docs=extra_docs, extra_df=extra_df)
        return self.search_bool(bool_queries, k=k,
                                with_totals=with_totals, stages=stages,
                                extra_docs=extra_docs, extra_df=extra_df)

    cached_step = _plane_cached_step

    def _get_bool_step(self, Q: int, L: int, k: int, *,
                       with_count: bool, nc: int):
        return self.cached_step(
            ("bool", Q, L, k, with_count, nc),
            lambda: build_bool_bm25_step(
                self.mesh, n_pad=self.n_pad, Q=Q, L=L, k=k, nc=nc,
                n_shards=self.n_shards, with_count=with_count),
            "text_plane_bool")

    def _get_step(self, Q: int, L: int, k: int, *, tiered: bool = False,
                  with_count: bool = False, U: Optional[int] = None):
        def build():
            if tiered:
                return build_tiered_bm25_step(
                    self.mesh, n_pad=self.n_pad, Q=Q, L=L, k=k,
                    T_pad=self.T_pad, C=self.dense_block,
                    n_shards=self.n_shards, with_count=with_count, U=U)
            return build_bm25_topk_step(
                self.mesh, n_pad=self.n_pad, Q=Q, L=L, k=k,
                n_shards=self.n_shards, with_count=with_count)

        # each new input-shape signature through the jitted step is one
        # XLA compile — counted per (site, shape) by the instrumentation
        # cached_step wraps on, so compile churn stays attributable
        return self.cached_step((Q, L, k, tiered, with_count, U), build,
                                "text_plane")


class DistributedKnnPlane:
    """Device-resident brute-force kNN plane: per-shard vector matrices
    packed ONCE with their corpus invariants (unit rows for cosine, cached
    ``‖v‖²`` rows for l2) and served through the blocked running-top-k
    step — the vector analogue of :class:`DistributedSearchPlane`.

    ``shards``: one dict per shard with ``vectors`` f32[N, dim] and
    optional ``exists`` bool[N] (default: all rows present). The serving
    path (``search/plane_route.py``) feeds one SEGMENT per plane shard so
    the plane's (shard, doc)-ascending tie order equals the per-segment
    path's (segment, doc) order.
    """

    def __init__(self, mesh: Mesh, shards: Sequence[dict], *,
                 similarity: str = "cosine",
                 block: Optional[int] = KNN_BLOCK,
                 ivf: Optional[dict] = None):
        if similarity not in KNN_SIMILARITIES:
            raise ValueError(f"unknown similarity [{similarity}]")
        self.mesh = mesh
        self.similarity = similarity
        self.block = block
        # same padding rule as DistributedSearchPlane: empty pad shards
        # (zero rows, exists all-False) absorb shard counts that don't
        # divide the mesh's shard axis; their rows score NEG_INF exactly
        # like within-shard pad rows, so results are mesh-shape-invariant
        shards = list(shards)
        _dim0 = next((int(s["vectors"].shape[1]) for s in shards
                      if s["vectors"].size), 1)
        for _ in range((-len(shards)) % mesh.shape[AXIS_SHARD]):
            shards.append(self.empty_pad_shard(_dim0))
        self.n_shards = len(shards)
        self.n_dispatches = 0
        dims = {int(s["vectors"].shape[1]) for s in shards
                if s["vectors"].size}
        if len(dims) > 1:
            raise ValueError(f"mixed vector dims across shards: {dims}")
        self.dim = dims.pop() if dims else 0
        #: real (unpadded) corpus rows — task docs-scanned attribution
        self.n_docs_total = sum(int(s["vectors"].shape[0])
                                for s in shards)
        self.n_pad = round_up_pow2(
            max(max(int(s["vectors"].shape[0]) for s in shards), 1))
        S = self.n_shards
        vecs = np.zeros((S, self.n_pad, max(self.dim, 1)), np.float32)
        exists = np.zeros((S, self.n_pad), bool)
        for i, s in enumerate(shards):
            v = np.asarray(s["vectors"], np.float32)
            n = v.shape[0]
            if n:
                vecs[i, :n, :] = v
            ex = s.get("exists")
            exists[i, :n] = np.ones(n, bool) if ex is None else ex
        # pack-time invariants: computed once here, never in the step trace
        vecs, vnorm2 = prepare_knn_corpus(vecs, similarity)
        vecs[~exists] = 0.0
        vnorm2[~exists] = 0.0
        self.nbytes = vecs.nbytes + vnorm2.nbytes + exists.nbytes
        self._packed = (vecs, vnorm2, exists)
        # IVF tier (cluster-pruned ANN): built at pack time from the
        # packed rows, BEFORE the accelerator path releases the host
        # copy. ``ivf`` is a kwargs dict for IvfKnnTier.build (nlist,
        # quant, seed, iters, train_sample); None = exact-only plane
        # (the brute-force fallback the existing bench config measures).
        self.ivf: Optional[IvfKnnTier] = None
        if ivf is not None and exists.any() and self.dim:
            self.ivf = IvfKnnTier.build(vecs, exists, similarity, **ivf)
            self.nbytes += self.ivf.nbytes()
        self._dev = None          # device arrays, uploaded on first search()
        self._steps: Dict[int, callable] = {}
        # dispatcher threads + the warmup thread hit the lazy upload and
        # step cache concurrently — guard both (a double device_put would
        # transiently hold 2x the corpus in HBM, and the _packed release
        # below must not race a concurrent reader)
        self._steps_lock = threading.Lock()
        self._serial_dispatch = _serial_dispatch_required(mesh)
        # CPU fallback (same pattern as DistributedSearchPlane._host_csr):
        # XLA:CPU's dot/top_k run far below BLAS+introselect, so a CPU
        # backend serves through :meth:`search_host` — the same blocked
        # streaming running-top-k over the same packed invariants, in
        # numpy. Only set on CPU; serving never uploads a second (device)
        # corpus copy there, keeping the breaker estimate one-copy honest.
        self._host_pack = self._packed \
            if (jax.devices()[0].platform == "cpu"
                and host_serve_enabled()) else None
        #: storage tier (mirror of the text plane's): "hot" =
        #: device-resident (lazily uploaded) corpus; "warm" = host-only
        #: ``_packed``, streamed to device per dispatch (``knn_streamed``)
        self.storage_tier = "hot"

    @staticmethod
    def empty_pad_shard(dim: int) -> dict:
        """Inert mesh-pad shard (zero rows, ``exists`` all-False): its
        rows score NEG_INF exactly like within-shard pad rows, so
        results are mesh-shape-invariant. The one pad-shard schema for
        both this constructor and the serving cache's kNN pack."""
        return dict(vectors=np.zeros((0, max(int(dim), 1)), np.float32),
                    exists=np.zeros(0, bool))

    def _device_arrays(self):
        with self._steps_lock:
            if self._dev is None:
                vecs, vnorm2, exists = self._packed
                corpus3 = NamedSharding(self.mesh, P(AXIS_SHARD, None, None))
                corpus2 = NamedSharding(self.mesh, P(AXIS_SHARD, None))
                self._dev = (jax.device_put(vecs, corpus3),
                             jax.device_put(vnorm2, corpus2),
                             jax.device_put(exists, corpus2))
                if self._host_pack is None:
                    # accelerator: the corpus now lives in HBM; don't hold
                    # a second copy in host RAM for the plane's lifetime
                    self._packed = None
            return self._dev

    def device_corpus_bytes(self) -> int:
        """Packed-corpus bytes RESIDENT PER DEVICE (vectors + invariants
        + the IVF quantized tier when present), shard-axis-sharded — the
        vector mirror of the text plane's accessor; the MULTICHIP bench
        asserts it scales ~1/n_shards. A demoted (warm/cold) generation
        reports 0: nothing is resident, so ``es_plane_hbm_bytes``
        decrements on demotion."""
        if self.storage_tier != "hot":
            return 0
        s_dev = self.mesh.shape[AXIS_SHARD]
        dim = max(self.dim, 1)
        # vecs f32 + vnorm2 f32 + exists bool per padded row
        total = self.n_shards * self.n_pad * (dim * 4 + 4 + 1)
        if self.ivf is not None:
            # block-major quantized tier incl. the sentinel pad block:
            # codes + scale/off/rowid/rcl rows per slot
            nb1 = self.ivf.n_blocks + 1
            total += self.n_shards * nb1 * self.ivf.block * \
                (dim * self.ivf.quant_bytes_per_dim() + 16)
        return total // max(s_dev, 1)

    # -- storage tiers (hot / warm) ------------------------------------------

    def host_tier_bytes(self) -> int:
        """Host bytes the warm tier holds — the packed invariants kept
        host-side for per-dispatch streaming."""
        if self.storage_tier != "warm":
            return 0
        with self._steps_lock:
            packed = self._packed
        if packed is None:
            return 0
        return sum(int(a.nbytes) for a in packed)

    def demote_to_warm(self) -> int:
        """Hot → warm: ensure a host copy of the packed invariants
        exists (accelerators released it after the lazy upload — read
        the device arrays back once), then drop every device reference
        (corpus + IVF tier caches). Returns the host bytes now held."""
        if self.storage_tier != "hot":
            return 0
        with self._steps_lock:
            if self._packed is None and self._dev is not None:
                self._packed = tuple(np.asarray(a) for a in self._dev)
            self._dev = None
            self.storage_tier = "warm"
        if self.ivf is not None:
            with self.ivf._dev_lock:
                self.ivf._dev = None
        return self.host_tier_bytes()

    def promote_to_hot(self) -> int:
        """Warm → hot: flip the tier back — the resident upload stays
        lazy (:meth:`_device_arrays` on the next dispatch, exactly like
        a fresh plane). Returns the host breaker bytes to release."""
        if self.storage_tier != "warm":
            return 0
        freed = self.host_tier_bytes()
        with self._steps_lock:
            self.storage_tier = "hot"
        return freed

    def _corpus_refs(self):
        """``(vecs, vnorm2, exists, stream_bytes)``: the cached resident
        arrays when hot; fresh per-dispatch uploads of the host pack
        when warm (``knn_streamed`` — no device caching, or demotion
        would silently re-pin the HBM it just freed)."""
        if self.storage_tier == "hot":
            return self._device_arrays() + (0,)
        with self._steps_lock:
            vecs, vnorm2, exists = self._packed
        corpus3 = NamedSharding(self.mesh, P(AXIS_SHARD, None, None))
        corpus2 = NamedSharding(self.mesh, P(AXIS_SHARD, None))
        stream = int(vecs.nbytes) + int(vnorm2.nbytes) + \
            int(exists.nbytes)
        return (jax.device_put(vecs, corpus3),
                jax.device_put(vnorm2, corpus2),
                jax.device_put(exists, corpus2), stream)

    # -- warm-handoff packed state (the recovery artifact) -------------------

    def export_packed(self) -> dict:
        """Packed invariants (unit/norm² rows already computed) + the
        IVF tier's centroids/codes, as a host dict for the wire codec —
        :meth:`from_packed` restores a serving-identical plane without
        re-running ``prepare_knn_corpus`` or the k-means pack."""
        with self._steps_lock:
            packed = self._packed or self._host_pack
            dev = self._dev
        if packed is None and dev is not None:
            # accelerator path released the host copy after upload:
            # read the (fully addressable) device arrays back once
            packed = tuple(np.asarray(a) for a in dev)
        vecs, vnorm2, exists = packed
        out = dict(similarity=self.similarity, block=self.block,
                   dim=int(self.dim), n_shards=int(self.n_shards),
                   n_docs_total=int(self.n_docs_total),
                   n_pad=int(self.n_pad), nbytes=int(self.nbytes),
                   vecs=vecs, vnorm2=vnorm2, exists=exists, ivf=None)
        if self.ivf is not None:
            t = self.ivf
            out["ivf"] = dict(
                similarity=t.similarity, quant=t.quant,
                block=int(t.block), nlist=int(t.nlist),
                centroids=t.centroids,
                default_nprobe=int(t.default_nprobe),
                n_blocks=int(t.n_blocks),
                cluster_sizes=t.cluster_sizes,
                shards=t.shards)
        return out

    @classmethod
    def from_packed(cls, mesh: Mesh, packed: dict
                    ) -> "DistributedKnnPlane":
        """Reconstruct from :meth:`export_packed` state — device upload
        stays lazy exactly like the normal constructor. Raises on a
        mesh whose shard axis does not divide the donor's padded shard
        count (the caller falls back to a local pack)."""
        self = cls.__new__(cls)
        self.mesh = mesh
        self.similarity = str(packed["similarity"])
        self.block = packed["block"]
        self.n_shards = int(packed["n_shards"])
        if self.n_shards % mesh.shape[AXIS_SHARD]:
            raise ValueError(
                f"packed knn plane has {self.n_shards} shards; mesh "
                f"shard axis {mesh.shape[AXIS_SHARD]} does not divide")
        self.n_dispatches = 0
        self.dim = int(packed["dim"])
        self.n_docs_total = int(packed["n_docs_total"])
        self.n_pad = int(packed["n_pad"])
        self.nbytes = int(packed["nbytes"])
        vecs = np.asarray(packed["vecs"], np.float32)
        vnorm2 = np.asarray(packed["vnorm2"], np.float32)
        exists = np.asarray(packed["exists"], bool)
        self._packed = (vecs, vnorm2, exists)
        self.ivf = None
        ivf = packed.get("ivf")
        if ivf is not None:
            t = IvfKnnTier(str(ivf["similarity"]),
                           quant=str(ivf["quant"]),
                           block=int(ivf["block"]))
            t.nlist = int(ivf["nlist"])
            t.centroids = np.asarray(ivf["centroids"], np.float32)
            t.default_nprobe = int(ivf["default_nprobe"])
            t.n_blocks = int(ivf["n_blocks"])
            t.cluster_sizes = np.asarray(ivf["cluster_sizes"])
            t.shards = [dict(sh) for sh in ivf["shards"]]
            self.ivf = t
        self._dev = None
        self._steps = {}
        self._steps_lock = threading.Lock()
        self._serial_dispatch = _serial_dispatch_required(mesh)
        self._host_pack = self._packed \
            if (jax.devices()[0].platform == "cpu"
                and host_serve_enabled()) else None
        self.storage_tier = "hot"
        return self

    def resolve_ann(self, nprobe: Optional[int],
                    rerank: Optional[int]):
        """Effective (nprobe, rerank) for a dispatch, or None for the
        exact path: nprobe=0 forces exact; None picks the tier's benched
        default; values clip into [1, nlist] / [1, …]."""
        if self.ivf is None or nprobe == 0:
            return None
        if nprobe is None:
            nprobe = self.ivf.default_nprobe
        nprobe = max(1, min(int(nprobe), self.ivf.nlist))
        rerank = max(1, int(rerank)) if rerank else IVF_DEFAULT_RERANK
        return nprobe, rerank

    def serve(self, query_vectors, k: int = 10,
              stages: Optional[dict] = None,
              nprobe: Optional[int] = None,
              rerank: Optional[int] = None):
        """Serving entry: the CPU-native scorer when this plane was
        built on a CPU backend, the jitted device step otherwise. When
        an IVF tier exists the dispatch is cluster-pruned (quantized
        scan + exact re-rank) at the resolved ``nprobe``/``rerank``;
        ``nprobe=0`` forces the exact brute-force scan."""
        ann = self.resolve_ann(nprobe, rerank)
        if ann is not None:
            if self._host_pack is not None:
                return self.search_ivf_host(query_vectors, k=k,
                                            nprobe=ann[0], rerank=ann[1],
                                            stages=stages)
            if self.storage_tier == "hot":
                return self.search_ivf(query_vectors, k=k, nprobe=ann[0],
                                       rerank=ann[1], stages=stages)
            # warm device plane: the IVF device tier was dropped on
            # demotion, and cluster-pruning buys nothing when the whole
            # corpus streams anyway — fall through to the (rank-safe
            # superset) streamed exact scan
        if self._host_pack is not None:
            return self.search_host(query_vectors, k=k, stages=stages)
        return self.search(query_vectors, k=k, stages=stages)

    cached_step = _plane_cached_step

    def serving_shapes(self, k_buckets: Sequence[int],
                       max_b: int) -> List[Tuple]:
        """The exact scan's programs, ``(b_pad, key)`` with ``key`` the
        ``_get_step`` key ``(k,)``: the micro-batcher's padded batches
        up to ``max_b`` x ``k_buckets``. The IVF step's shapes follow
        the probe (its union width) and are not stated."""
        return [(b_pad, (kb,))
                for b_pad in _padded_batches(self.mesh, max_b)
                for kb in sorted(set(k_buckets))]

    def warm_shape(self, shape: Tuple) -> None:
        """Compile (or load from the cache) and run one member of
        :meth:`serving_shapes` on zero vectors; with an IVF tier, its
        default dispatch at the same batch too (the program a zero
        probe asks for, which live traffic may or may not share)."""
        b_pad, (k,) = shape
        q = np.zeros((b_pad, max(self.dim, 1)), np.float32)
        self.search(q, k=k)
        if self.ivf is not None:
            self.serve(q, k=k)

    def _get_step(self, k: int):
        return self.cached_step(
            (k,),
            lambda: build_knn_step(
                self.mesh, n_pad=self.n_pad, dim=max(self.dim, 1), k=k,
                n_shards=self.n_shards, similarity=self.similarity,
                block=self.block),
            "knn_plane")

    def search(self, query_vectors, k: int = 10,
               stages: Optional[dict] = None):
        """Top-k over the packed corpus for a batch of query vectors.

        Returns (raw_scores f32[B, k'], hits list[list[(shard, local)]])
        where raw scores are the step's similarity values (cosine/dot: the
        dot product; l2_norm: ``-‖q-v‖²``) — callers apply their own
        monotone _score transform."""
        with _tracing.Phases() as phases:
            phases.enter("plane[h2d]")
            t0 = time.perf_counter()
            q = np.asarray(query_vectors, np.float32)
            if q.ndim != 2 or (self.dim and q.shape[1] != self.dim):
                raise ValueError(
                    f"query_vectors must be [B, {self.dim}], got {q.shape}")
            B = q.shape[0]
            n_repl = self.mesh.shape[AXIS_REPLICA]
            B_pad = -(-B // n_repl) * n_repl
            if B_pad != B:
                q = np.concatenate(
                    [q, np.zeros((B_pad - B, q.shape[1]), np.float32)])
            step = self._get_step(k)
            vecs_dev, vnorm2_dev, exists_dev, stream_b = self._corpus_refs()
            q_dev = jax.device_put(q, NamedSharding(self.mesh,
                                                    P(AXIS_REPLICA, None)))
            phases.enter("plane[launch]", bytes=q.nbytes + stream_b)
            t1 = time.perf_counter()
            out = _run_step(self._serial_dispatch, step,
                            vecs_dev, vnorm2_dev, exists_dev, q_dev)
            if stages is not None:
                phases.enter("plane[sync]")
                jax.block_until_ready(out)
            d2h_span = phases.enter("plane[d2h]")
            t2 = time.perf_counter()
            vals, gdocs = out
            self.n_dispatches += 1
            from ..common import telemetry as _tm
            _tm.record_mesh_dispatch(self.mesh.shape[AXIS_SHARD],
                                     self.mesh.shape[AXIS_REPLICA])
            compiled = _tm.last_call_compiled()
            vals = np.asarray(vals)[:B]
            gdocs = np.asarray(gdocs)[:B]
            if d2h_span is not None:
                d2h_span.attrs["bytes"] = vals.nbytes + gdocs.nbytes
            phases.enter("plane[decode]")
            _tm.record_transfer(h2d_bytes=q.nbytes + stream_b,
                                d2h_bytes=vals.nbytes + gdocs.nbytes)
            if stream_b:
                _tm.record_tier_stream_bytes(stream_b)
            hits = self._decode_hits(vals, gdocs)
        if stages is not None:
            stages["prep_ms"] = (t1 - t0) * 1e3
            stages["dispatch_ms"] = (t2 - t1) * 1e3
            stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
            stages["compile_cache"] = "miss" if compiled else "hit"
            stages["h2d_bytes"] = q.nbytes + stream_b
            stages["d2h_bytes"] = vals.nbytes + gdocs.nbytes
            # roofline audit inputs: the f32 corpus streams once per
            # batch (ROOFLINE.md kNN bytes-moved model); a warm plane's
            # dispatch is the host→device re-upload instead — the
            # streamed-tier model against the host-link ceiling
            from ..common import roofline as _rl
            if stream_b:
                stages["kernel"] = "knn_streamed"
                stages["tier"] = "warm"
                stages["stream_bytes"] = stream_b
                stages["model_bytes"] = _rl.model_bytes_streamed(
                    stream_b, B_pad, k)
            else:
                stages["kernel"] = "knn_exact"
                stages["model_bytes"] = _rl.model_bytes_knn_exact(
                    self.n_shards * self.n_pad, max(self.dim, 1),
                    l2=self.similarity == "l2_norm")
        return vals, hits

    def _decode_hits(self, vals, gdocs):
        hits = []
        for bi in range(vals.shape[0]):
            row = []
            for v, g in zip(vals[bi], gdocs[bi]):
                if v == NEG_INF:
                    break
                row.append((int(g) // self.n_pad, int(g) % self.n_pad))
            hits.append(row)
        return hits

    def search_host(self, query_vectors, k: int = 10,
                    stages: Optional[dict] = None):
        """CPU-native serving path: the SAME blocked streaming design as
        the device step — corpus read block by block, carried running
        top-k, O(B·block) transient memory — but in numpy, where the
        matmul is BLAS and block selection is a vectorized threshold scan
        (each block only sorts entries beating the current per-query k-th
        best, the CPU shape of 'scores are never fully materialized').
        Exact, with the kernel path's tie order (score desc, (shard, doc)
        asc). Only available when the plane was built on a CPU backend."""
        if self._host_pack is None:
            raise RuntimeError("search_host requires a CPU-backend plane")
        t0 = time.perf_counter()
        hvecs, hvn, hexists = self._host_pack
        q = np.asarray(query_vectors, np.float32)
        B = q.shape[0]
        l2 = self.similarity == "l2_norm"
        if self.similarity == "cosine":
            qq = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        else:
            qq = q
        qn = np.sum(q * q, axis=1) if l2 else None
        kk = min(k, self.n_shards * self.n_pad)
        best_v = np.full((B, kk), NEG_INF, np.float32)
        best_g = np.zeros((B, kk), np.int64)
        theta = np.full(B, NEG_INF, np.float32)     # per-query k-th best
        blk = min(self.block or self.n_pad, self.n_pad)
        # a small SEED block establishes θ cheaply, so the big blocks'
        # selection is a vectorized compare (candidates ≈ k) instead of a
        # full-width introselect per query per block
        seed = min(max(4 * kk, 1024), blk)
        sbufs: Dict[int, np.ndarray] = {}   # per-width reused score
        # buffers (np.dot out= needs C-contiguity; the naive path
        # allocates a fresh [B, n] matrix every batch)
        for si in range(self.n_shards):
            b0 = 0
            while b0 < self.n_pad:
                step_b = seed if (si == 0 and b0 == 0) else blk
                ex = hexists[si, b0: b0 + step_b]
                if not ex.any():
                    b0 += step_b
                    continue
                sub = hvecs[si, b0: b0 + step_b]
                s = sbufs.get(sub.shape[0])
                if s is None:
                    s = sbufs[sub.shape[0]] = np.empty(
                        (B, sub.shape[0]), np.float32)
                np.dot(qq, sub.T, out=s)              # [B, blk] BLAS
                if l2:
                    s *= 2.0
                    s -= hvn[si, b0: b0 + step_b][None, :]
                    s -= qn[:, None]
                if not ex.all():
                    s[:, ~ex] = NEG_INF
                base = si * self.n_pad + b0
                # ONE vectorized pass extracts every query's candidates
                # (strict > θ: equal scores at later addresses lose the
                # tie anyway — earlier blocks already hold them); after
                # the seed block θ makes this a near-empty set
                bi_ix, c_ix = np.nonzero(s > theta[:, None])
                if bi_ix.size == 0:
                    b0 += step_b
                    continue
                bounds = np.searchsorted(bi_ix, np.arange(B + 1))
                for bi in range(B):
                    lo, hi = bounds[bi], bounds[bi + 1]
                    if lo == hi:
                        continue
                    cand = c_ix[lo:hi]
                    sv = s[bi][cand]
                    if cand.size > kk:
                        # introselect to the k-th value, then keep every
                        # tied-or-better candidate so boundary ties still
                        # resolve by ascending address in the merge
                        kth = -np.partition(-sv, kk - 1)[kk - 1]
                        keep = sv >= kth
                        cand, sv = cand[keep], sv[keep]
                    cv = np.concatenate([best_v[bi], sv])
                    cg = np.concatenate(
                        [best_g[bi], cand.astype(np.int64) + base])
                    order = np.lexsort((cg, -cv))[:kk]
                    best_v[bi] = cv[order]
                    best_g[bi] = cg[order]
                    theta[bi] = best_v[bi, -1]
                b0 += step_b
        self.n_dispatches += 1
        if stages is not None:
            stages["prep_ms"] = 0.0
            stages["dispatch_ms"] = (time.perf_counter() - t0) * 1e3
            stages["fetch_ms"] = 0.0
            stages["compile_cache"] = "host"
            from ..common import roofline as _rl
            stages["kernel"] = "knn_exact"
            stages["model_bytes"] = _rl.model_bytes_knn_exact(
                self.n_shards * self.n_pad, max(self.dim, 1), l2=l2)
        return best_v, self._decode_hits(best_v, best_g)

    # -- IVF: cluster-pruned quantized scan + exact re-rank ------------------

    def _probe_queries(self, q: np.ndarray):
        """Queries in the packed convention (unit rows for cosine) plus
        the per-query Σq the dequantized dot needs."""
        if self.similarity == "cosine":
            qq = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        else:
            qq = q
        return qq, np.sum(qq, axis=1)

    def _ivf_probed_docs(self, probed: np.ndarray) -> int:
        """Mean rows per query the probed clusters cover (summed over
        shards) — the docs-scanned attribution of a pruned dispatch."""
        sizes = self.ivf.cluster_sizes
        return int(sizes[probed].sum(axis=1).mean()) if probed.size else 0

    def _record_ann(self, B: int, nprobe: int, cand: int,
                    q_bytes: int, x_bytes: int,
                    stages: Optional[dict]) -> None:
        from ..common import telemetry as _tm
        _tm.record_ann(
            clusters_probed=B * nprobe, candidates_reranked=cand,
            quantized_bytes=q_bytes, exact_bytes=x_bytes,
            below_default=nprobe < self.ivf.default_nprobe)
        if stages is not None:
            stages["ann_quantized_bytes"] = q_bytes
            stages["ann_exact_bytes"] = x_bytes
            from ..common import roofline as _rl
            stages["kernel"] = "knn_ivf"
            stages["model_bytes"] = _rl.model_bytes_knn_ivf(
                q_bytes, x_bytes)

    def search_ivf(self, query_vectors, k: int = 10, *, nprobe: int,
                   rerank: int, stages: Optional[dict] = None):
        """Device IVF dispatch: host centroid matmul picks the probed
        clusters and sizes the static gather (pow2 union width), then
        the jitted step streams ONLY those blocks of the quantized tier
        through the running-top-k and re-ranks exactly from the f32
        tier. Same return convention as :meth:`search`."""
        if self.ivf is None:
            raise RuntimeError("plane has no IVF tier")
        if self.storage_tier != "hot":
            # warm plane: the IVF device tier was dropped on demotion —
            # serve the streamed exact scan instead (rank-safe superset)
            return self.search(query_vectors, k=k, stages=stages)
        with _tracing.Phases() as phases:
            phases.enter("plane[h2d]")
            t0 = time.perf_counter()
            tier = self.ivf
            q = np.asarray(query_vectors, np.float32)
            if q.ndim != 2 or (self.dim and q.shape[1] != self.dim):
                raise ValueError(
                    f"query_vectors must be [B, {self.dim}], got {q.shape}")
            B = q.shape[0]
            n_repl = self.mesh.shape[AXIS_REPLICA]
            B_pad = -(-B // n_repl) * n_repl
            if B_pad != B:
                q = np.concatenate(
                    [q, np.zeros((B_pad - B, q.shape[1]), np.float32)])
            qq, _ = self._probe_queries(q)
            probed = tier.probe(qq, nprobe)
            u_blocks, Pw = tier.union_blocks(probed, self.n_shards)
            kk = min(k, self.n_pad)
            r_cand = max(kk, min(rerank * kk, Pw * tier.block))
            step = self._get_ivf_step(k, nprobe, r_cand, Pw)
            dev = tier.device_arrays(self.mesh, self.n_pad)
            vecs_dev, vnorm2_dev, _exists_dev = self._device_arrays()
            repl = NamedSharding(self.mesh, P(AXIS_REPLICA, None))
            shard2 = NamedSharding(self.mesh, P(AXIS_SHARD, None))
            q_dev = jax.device_put(q, repl)
            probed_dev = jax.device_put(probed, repl)
            u_dev = jax.device_put(u_blocks, shard2)
            phases.enter("plane[launch]")
            t1 = time.perf_counter()
            out = _run_step(
                self._serial_dispatch, step,
                dev["codes"], dev["scale"], dev["off"], dev["rowid"],
                dev["rcl"], vecs_dev, vnorm2_dev, q_dev, probed_dev,
                u_dev)
            if stages is not None:
                phases.enter("plane[sync]")
                jax.block_until_ready(out)
            phases.enter("plane[d2h]")
            t2 = time.perf_counter()
            vals, gdocs = out
            self.n_dispatches += 1
            from ..common import telemetry as _tm
            _tm.record_mesh_dispatch(self.mesh.shape[AXIS_SHARD],
                                     self.mesh.shape[AXIS_REPLICA])
            compiled = _tm.last_call_compiled()
            vals = np.asarray(vals)[:B]
            gdocs = np.asarray(gdocs)[:B]
            h2d = q.nbytes + probed.nbytes + u_blocks.nbytes
            d2h = vals.nbytes + gdocs.nbytes
            _tm.record_transfer(h2d_bytes=h2d, d2h_bytes=d2h)
            # bytes the pruned scan actually reads from HBM vs the exact
            # re-rank gather (the ROOFLINE IVF model's two terms)
            meta_b = 12 + (4 if self.similarity == "l2_norm" else 0)
            q_bytes = self.n_shards * Pw * tier.block * \
                (self.dim * tier.quant_bytes_per_dim() + meta_b)
            x_bytes = self.n_shards * B_pad * r_cand * self.dim * 4
            self._record_ann(B, nprobe, B_pad * r_cand * self.n_shards,
                             q_bytes, x_bytes, stages)
            phases.enter("plane[decode]")
            hits = self._decode_hits(vals, gdocs)
        if stages is not None:
            stages["prep_ms"] = (t1 - t0) * 1e3
            stages["dispatch_ms"] = (t2 - t1) * 1e3
            stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
            stages["compile_cache"] = "miss" if compiled else "hit"
            stages["h2d_bytes"] = h2d
            stages["d2h_bytes"] = d2h
            stages["docs_scanned"] = self._ivf_probed_docs(probed[:B])
        return vals, hits

    def _get_ivf_step(self, k: int, nprobe: int, r_cand: int, Pw: int):
        return self.cached_step(
            ("ivf", k, nprobe, r_cand, Pw),
            lambda: build_ivf_knn_step(
                self.mesh, n_pad=self.n_pad, dim=max(self.dim, 1),
                k=k, n_shards=self.n_shards,
                similarity=self.similarity, nprobe=nprobe,
                r_cand=r_cand, p_blocks=Pw, blk=self.ivf.block,
                quant=self.ivf.quant),
            "knn_ivf_plane")

    def search_ivf_host(self, query_vectors, k: int = 10, *, nprobe: int,
                        rerank: int, stages: Optional[dict] = None):
        """CPU-native IVF serving: centroid matmul picks each query's
        clusters, every DISTINCT probed cluster is dequantized once per
        batch and scored for its probing queries with one gemm over the
        cluster's contiguous slice, the per-shard top-``rerank·k``
        survivors re-rank exactly from the f32 tier, and the final
        top-k keeps the kernel path's tie order (score desc,
        (shard, doc) asc)."""
        if self.ivf is None:
            raise RuntimeError("plane has no IVF tier")
        if self._host_pack is None:
            raise RuntimeError("search_ivf_host requires a CPU-backend "
                               "plane")
        t0 = time.perf_counter()
        tier = self.ivf
        hvecs, hvn, _hex = self._host_pack
        q = np.asarray(query_vectors, np.float32)
        if q.ndim != 2 or (self.dim and q.shape[1] != self.dim):
            raise ValueError(
                f"query_vectors must be [B, {self.dim}], got {q.shape}")
        B = q.shape[0]
        qq, qsum = self._probe_queries(q)
        l2 = self.similarity == "l2_norm"
        qn = np.sum(q * q, axis=1) if l2 else None
        probed = tier.probe(qq, nprobe)
        kk = min(k, self.n_shards * self.n_pad)
        R = max(kk, rerank * kk)
        vals_out = np.full((B, kk), NEG_INF, np.float32)
        hits_out: List[List[Tuple[int, int]]] = []
        q_bytes = 0
        qbpd = tier.quant_bytes_per_dim()
        # batch × cluster inversion: each DISTINCT probed cluster is
        # dequantized (astype) once per batch and scored for every query
        # probing it with one [rows, d]×[d, nq] gemm over a CONTIGUOUS
        # slice (the reorder made clusters contiguous — no gather) —
        # co-batched queries sharing hot clusters share the decode
        by_cluster: Dict[int, List[int]] = {}
        for bi in range(B):
            for c in probed[bi]:
                by_cluster.setdefault(int(c), []).append(bi)
        cand_v: List[List[np.ndarray]] = [[] for _ in range(B)]
        cand_g: List[List[np.ndarray]] = [[] for _ in range(B)]
        for si, sh in enumerate(tier.shards):
            offs = sh["offsets"]
            for c, bis in by_cluster.items():
                lo, hi = int(offs[c]), int(offs[c + 1])
                if hi <= lo:
                    continue
                sub = sh["codes"][lo:hi].astype(np.float32)
                dots = sub @ qq[bis].T                 # [rows, nq]
                s = sh["scale"][lo:hi, None] * dots \
                    + sh["off"][lo:hi, None] * qsum[bis][None, :]
                rows = sh["rows"][lo:hi]
                if l2:
                    s = 2.0 * s - hvn[si, rows][:, None] \
                        - qn[bis][None, :]
                q_bytes += (hi - lo) * (self.dim * qbpd + 8)
                grows = rows.astype(np.int64) + si * self.n_pad
                if s.shape[0] > R:
                    # per-(query, cluster) pre-prune to R in ONE 2-D
                    # introselect: the per-shard top-R of the union
                    # equals the top-R over per-cluster top-Rs
                    top = np.argpartition(-s, R - 1, axis=0)[:R]
                    vs = s[top, np.arange(s.shape[1])[None, :]]
                    for j, bi in enumerate(bis):
                        cand_v[bi].append(vs[:, j])
                        cand_g[bi].append(grows[top[:, j]])
                else:
                    for j, bi in enumerate(bis):
                        cand_v[bi].append(s[:, j])
                        cand_g[bi].append(grows)
        for bi in range(B):
            row: List[Tuple[int, int]] = []
            if cand_v[bi]:
                cv0 = np.concatenate(cand_v[bi])
                cg = np.concatenate(cand_g[bi])
                # per-shard window: keep R candidates per shard (the
                # device step's semantics) before the exact re-rank
                keep: List[np.ndarray] = []
                sis_all = cg // self.n_pad
                for si in np.unique(sis_all):
                    m = np.flatnonzero(sis_all == si)
                    if m.size > R:
                        m = m[np.argpartition(-cv0[m], R - 1)[:R]]
                    keep.append(m)
                sel = np.concatenate(keep)
                cg = cg[sel]
                # exact re-rank: every surviving candidate re-scored
                # from the f32 tier; quantized scores only chose the
                # window, never the final order
                sis = cg // self.n_pad
                ds = cg % self.n_pad
                cv = hvecs[sis, ds] @ qq[bi]
                if l2:
                    cv = 2.0 * cv - hvn[sis, ds] - qn[bi]
                order = np.lexsort((cg, -cv))[:kk]
                vals_out[bi, :order.size] = cv[order]
                row = [(int(cg[j]) // self.n_pad,
                        int(cg[j]) % self.n_pad) for j in order]
            hits_out.append(row)
        self.n_dispatches += 1
        # nominal per-shard window accounting, matching the device
        # path's convention (R candidates PER SHARD re-ranked) so
        # es_ann_* totals agree across backends
        x_bytes = B * R * self.n_shards * self.dim * 4
        self._record_ann(B, nprobe, B * R * self.n_shards, q_bytes,
                         x_bytes, stages)
        if stages is not None:
            stages["prep_ms"] = 0.0
            stages["dispatch_ms"] = (time.perf_counter() - t0) * 1e3
            stages["fetch_ms"] = 0.0
            stages["compile_cache"] = "host"
            stages["docs_scanned"] = self._ivf_probed_docs(probed)
        return vals_out, hits_out


# ---------------------------------------------------------------------------
# One-dispatch fused serving entry (device): both planes, one program
# ---------------------------------------------------------------------------


def fused_search_device(text_plane: "DistributedSearchPlane",
                        knn_plane: "DistributedKnnPlane", fqs, *,
                        fusion: str, rescore_mode: Optional[str] = None,
                        stages: Optional[dict] = None,
                        extra_docs: int = 0,
                        extra_df: Optional[Dict[str, int]] = None):
    """Serve a batch of planned hybrid queries through ONE jitted
    program over both planes' tensors (:func:`build_fused_hybrid_step`).

    ``fqs``: one dict per query — ``clauses``/``msm`` (the lowered bool
    tree), ``qv`` (query vector), ``kboost``, ``rc`` (RRF constant),
    ``wt``/``wk`` (text/knn rank windows), ``k`` (final size) and an
    optional ``rescore`` dict (``terms``/``qw``/``rw``/``window``).
    Every query in the batch shares ``fusion`` and ``rescore_mode``
    (the micro-batcher co-batches only within one plan shape).

    Returns (rows, totals, text_rows, knn_rows): ``rows[bi]`` is the
    fused [(score, shard, doc)] ranking trimmed to that query's ``k``;
    the raw per-retriever rankings ride along for delta-merge and
    parity callers."""
    if text_plane.mesh is not knn_plane.mesh:
        raise ValueError("fused dispatch needs both planes on one mesh")
    if text_plane.n_shards != knn_plane.n_shards:
        raise ValueError("fused dispatch needs aligned shard counts")
    with _tracing.Phases() as phases:
        phases.enter("plane[h2d]")
        t0 = time.perf_counter()
        mesh = text_plane.mesh
        B = len(fqs)
        n_repl = mesh.shape[AXIS_REPLICA]
        B_pad = -(-B // n_repl) * n_repl
        dim = max(knn_plane.dim, 1)
        pad_fq = {"clauses": [], "msm": 0,
                  "qv": np.zeros(dim, np.float32), "kboost": 1.0,
                  "rc": 60.0, "wt": 0, "wk": 0, "k": 0,
                  "rescore": {"terms": [], "qw": 1.0, "rw": 1.0,
                              "window": 0} if rescore_mode else None}
        fqs = list(fqs) + [pad_fq] * (B_pad - B)
        bool_queries = [{"clauses": fq["clauses"], "msm": fq["msm"]}
                        for fq in fqs]
        Q = max(text_plane.SERVING_Q_MIN, round_up_pow2(
            text_plane.bool_slot_count(bool_queries)))
        (starts, lengths, idfw, cbits, req, neg, shd, msm, max_len,
         any_dense) = text_plane.bool_inputs(bool_queries, Q,
                                             extra_docs=extra_docs,
                                             extra_df=extra_df)
        if any_dense:
            raise ValueError("fused batch touches dense-tier terms; the "
                             "sparse-slice fused step cannot serve it")
        L = min(text_plane.ladder_L(max_len), text_plane.L_cap)
        np.minimum(lengths, L, out=lengths)
        qv = np.stack([np.asarray(fq["qv"], np.float32) for fq in fqs])
        kboost = np.asarray([fq.get("kboost", 1.0) for fq in fqs],
                            np.float32)
        rc = np.asarray([fq.get("rc", 60.0) for fq in fqs], np.float32)
        wt = np.asarray([fq.get("wt", 0) for fq in fqs], np.int32)
        wk = np.asarray([fq.get("wk", 0) for fq in fqs], np.int32)
        W_text = round_up_pow2(max(int(wt.max()), 1))
        W_knn = round_up_pow2(max(int(wk.max()), 1))
        from ..ops.fused_query import MAX_BOOL_CLAUSES
        Q2 = 0
        rescore_args = ()
        if rescore_mode is not None:
            bags2 = [list(fq["rescore"]["terms"]) for fq in fqs]
            Q2 = max(8, round_up_pow2(max(
                max((len(set(b)) for b in bags2), default=1), 1)))
            (st2, ln2, iw2, _dr, _dh, _ml2, dense2) = text_plane._lookup(
                bags2, Q2, extra_docs=extra_docs, extra_df=extra_df)
            if dense2:
                raise ValueError("fused rescore touches dense-tier terms")
            qw = np.asarray([fq["rescore"]["qw"] for fq in fqs], np.float32)
            rw = np.asarray([fq["rescore"]["rw"] for fq in fqs], np.float32)
            rwin = np.asarray([fq["rescore"]["window"] for fq in fqs],
                              np.int32)
        step = text_plane.cached_step(
            ("fused", Q, L, W_text, W_knn, fusion, Q2, rescore_mode,
             knn_plane.n_pad, dim, knn_plane.similarity),
            lambda: build_fused_hybrid_step(
                mesh, n_pad_t=text_plane.n_pad, Q=Q, L=L, W_text=W_text,
                nc=MAX_BOOL_CLAUSES, n_pad_k=knn_plane.n_pad, dim=dim,
                similarity=knn_plane.similarity, W_knn=W_knn,
                k=W_text + W_knn, fusion=fusion,
                n_shards=text_plane.n_shards, Q2=Q2,
                rescore_mode=rescore_mode or "total",
                block=knn_plane.block),
            "fused_plane")
        kvecs_dev, kvn_dev, kex_dev, k_stream = knn_plane._corpus_refs()
        tdocs_dev, timpacts_dev, _tdense, t_stream = \
            text_plane._corpus_refs()
        stream_b = k_stream + t_stream
        repl = NamedSharding(mesh, P(AXIS_REPLICA, None))
        repl1 = NamedSharding(mesh, P(AXIS_REPLICA))
        repl3 = NamedSharding(mesh, P(AXIS_REPLICA, AXIS_SHARD, None))
        args = [tdocs_dev, timpacts_dev,
                kvecs_dev, kvn_dev, kex_dev,
                jax.device_put(starts, repl3),
                jax.device_put(lengths, repl3),
                jax.device_put(idfw, repl), jax.device_put(cbits, repl),
                jax.device_put(req, repl1), jax.device_put(neg, repl1),
                jax.device_put(shd, repl1), jax.device_put(msm, repl1),
                jax.device_put(qv, repl), jax.device_put(kboost, repl1),
                jax.device_put(rc, repl1), jax.device_put(wt, repl1),
                jax.device_put(wk, repl1)]
        h2d = starts.nbytes + lengths.nbytes + idfw.nbytes + cbits.nbytes \
            + qv.nbytes + 24 * B_pad + stream_b
        if Q2:
            args += [jax.device_put(st2, repl3), jax.device_put(ln2, repl3),
                     jax.device_put(iw2, repl), jax.device_put(qw, repl1),
                     jax.device_put(rw, repl1), jax.device_put(rwin, repl1)]
            h2d += st2.nbytes + ln2.nbytes + iw2.nbytes + 12 * B_pad
        phases.enter("plane[launch]")
        t1 = time.perf_counter()
        out = _run_step(text_plane._serial_dispatch, step, *args)
        if stages is not None:
            phases.enter("plane[sync]")
            jax.block_until_ready(out)
        phases.enter("plane[d2h]")
        t2 = time.perf_counter()
        text_plane.n_dispatches += 1
        knn_plane.n_dispatches += 1
        from ..common import telemetry as _tm
        _tm.record_mesh_dispatch(mesh.shape[AXIS_SHARD],
                                 mesh.shape[AXIS_REPLICA])
        compiled = _tm.last_call_compiled()
        fvals = np.asarray(out[0])[:B]
        fids = np.asarray(out[1])[:B]
        counts = np.asarray(out[2])[:B]
        tvals = np.asarray(out[3])[:B]
        tids = np.asarray(out[4])[:B]
        kvals = np.asarray(out[5])[:B]
        kids = np.asarray(out[6])[:B]
        d2h = fvals.nbytes + fids.nbytes + counts.nbytes + tvals.nbytes \
            + tids.nbytes + kvals.nbytes + kids.nbytes
        _tm.record_transfer(h2d_bytes=h2d, d2h_bytes=d2h)
        if stream_b:
            _tm.record_tier_stream_bytes(stream_b)
        phases.enter("plane[decode]")
        UP = max(text_plane.n_pad, knn_plane.n_pad)

        def decode(vrow, grow, npad, kq):
            rows = []
            for v, g in zip(vrow, grow):
                if v == NEG_INF or len(rows) >= kq:
                    break
                rows.append((float(v), int(g) // npad, int(g) % npad))
            return rows

        rows = [decode(fvals[bi], fids[bi], UP, fqs[bi].get("k") or
                       (W_text + W_knn)) for bi in range(B)]
        text_rows = [decode(tvals[bi], tids[bi], text_plane.n_pad,
                            int(wt[bi])) for bi in range(B)]
        knn_rows = [decode(kvals[bi], kids[bi], knn_plane.n_pad,
                           int(wk[bi])) for bi in range(B)]
        totals = [int(c) for c in counts]
    if stages is not None:
        stages["prep_ms"] = (t1 - t0) * 1e3
        stages["dispatch_ms"] = (t2 - t1) * 1e3
        stages["fetch_ms"] = (time.perf_counter() - t2) * 1e3
        stages["compile_cache"] = "miss" if compiled else "hit"
        stages["h2d_bytes"] = h2d
        stages["d2h_bytes"] = d2h
        stages["docs_scanned"] = text_plane.n_docs_total \
            + knn_plane.n_docs_total
        if stream_b:
            stages["tier"] = "warm"
            stages["stream_bytes"] = stream_b
    return rows, totals, text_rows, knn_rows


# ---------------------------------------------------------------------------
# Delta tier: eager scoring of segments appended since the last base pack
# ---------------------------------------------------------------------------
#
# A refresh under live indexing appends small segments far faster than a
# full plane repack (CSR pack + dense tier + device upload + warmup
# lattice) can absorb them. The serving layer therefore splits each plane
# into the packed BASE generation plus an append-only DELTA tier: delta
# segments are scored eagerly per query — CSR scatter-add for BM25 (the
# BM25S observation: eager sparse scoring is cheap at small corpus
# sizes), a BLAS matmul for kNN — and merged into the base dispatch's
# top-k. Both scorers keep the kernel path's exact tie order
# (score desc, global segment asc, doc asc), so the merged ranking equals
# a full repack's.


def merge_topk_rows(base_rows, delta_rows, k: int):
    """Merge two per-query candidate lists of ``(value, seg, doc)`` rows
    into the global top-k with the plane's tie order (value desc, seg
    asc, doc asc). Each side covers its own partition's top-k, so the
    union's top-k is the exact global top-k."""
    if not delta_rows:
        return base_rows[:k]
    if not base_rows:
        return delta_rows[:k]
    cat = list(base_rows) + list(delta_rows)
    cat.sort(key=lambda r: (-r[0], r[1], r[2]))
    return cat[:k]


class EagerDeltaScorer:
    """Append-only lexical delta tier: term-at-a-time scatter-add over
    each delta segment's CSR with impacts precomputed ONCE at
    construction (the same eager algorithm as
    :meth:`DistributedSearchPlane.search_eager`).

    ``shards``: one dict per delta segment with ``term_ids``, ``df``,
    ``offsets``, ``docs``, ``tf``, ``doc_len`` (a field-less segment
    passes empty postings but still contributes its doc count).
    ``seg_positions``: each delta segment's index in the CURRENT
    serving segment list — hits are emitted in that global space so the
    merge with base hits preserves (segment, doc) tie order.
    ``avgdl``: the owning generation's FROZEN length norm — the base
    plane's impacts baked it at pack time, so the delta must score under
    the same value or base and delta scores would live on different
    scales (it refreshes at the next repack).

    No breaker reservation: the only allocation is the impacts column,
    O(delta postings) — the arrays otherwise alias the segments' own
    host columns."""

    def __init__(self, shards: Sequence[dict], seg_positions: Sequence[int],
                 *, avgdl: float, k1: float = DEFAULT_K1,
                 b: float = DEFAULT_B):
        self.seg_positions = list(seg_positions)
        self.avgdl = max(float(avgdl), 1e-9)
        self.n_docs = 0
        self._csr: List[dict] = []
        for s in shards:
            n = int(s["doc_len"].shape[0])
            self.n_docs += n
            self._csr.append(dict(
                term_ids=s["term_ids"], df=s["df"], offsets=s["offsets"],
                docs=s["docs"],
                impacts=make_impacts(s["tf"], s["docs"], s["doc_len"],
                                     self.avgdl, k1, b),
                n_docs=n))

    def df(self, term: str) -> int:
        """Delta-tier document frequency of ``term`` — fed back into the
        base dispatch as ``extra_df`` so both tiers share one idf."""
        out = 0
        for csr in self._csr:
            tid = csr["term_ids"].get(term)
            if tid is not None:
                out += int(csr["df"][tid])
        return out

    def score(self, queries: Sequence[Sequence[str]], k: int, idf_of,
              with_totals: bool = False):
        """Score a query batch against the delta tier. ``idf_of(term)``
        returns the COMBINED-stats idf (base + delta df over base + delta
        docs) — the same value the base dispatch uses via ``extra_df``.
        Returns (rows per query [(val, global_seg, doc)] sorted by the
        merge order, totals per query)."""
        rows_out: List[List[Tuple[float, int, int]]] = []
        totals: List[int] = []
        for terms in queries:
            weights: Dict[str, float] = {}
            for t in terms:
                weights[t] = weights.get(t, 0.0) + 1.0
            idfw_of = {t: idf_of(t) * w for t, w in weights.items()
                       if idf_of(t) > 0.0}
            rows: List[Tuple[float, int, int]] = []
            total = 0
            for gseg, csr in zip(self.seg_positions, self._csr):
                scores = np.zeros(csr["n_docs"], np.float32)
                matched = False
                for t, idfw in idfw_of.items():
                    tid = csr["term_ids"].get(t)
                    if tid is None:
                        continue
                    st = int(csr["offsets"][tid])
                    en = int(csr["offsets"][tid + 1])
                    if en > st:
                        scores[csr["docs"][st:en]] += \
                            idfw * csr["impacts"][st:en]
                        matched = True
                if not matched:
                    continue
                if with_totals:
                    total += int(np.count_nonzero(scores > 0))
                kk = min(k, csr["n_docs"])
                # tie-stable bounded cut (see search_eager): the k-th-
                # boundary tie must resolve doc-ascending for delta-merge
                # parity
                sel = tie_stable_topk_docs(scores, kk)
                rows.extend((float(scores[d]), gseg, int(d)) for d in sel)
            rows.sort(key=lambda r: (-r[0], r[1], r[2]))
            rows_out.append(rows[:k])
            totals.append(total)
        return rows_out, totals


    def score_bool(self, bool_queries, k: int, idf_of,
                   with_totals: bool = False):
        """Bool-tree twin of :meth:`score` for the fused planner: the
        same clause-bit eligibility pass as
        :meth:`DistributedSearchPlane.search_bool_eager`, over the delta
        segments' CSR, under the COMBINED-stats idf (``idf_of``)."""
        rows_out: List[List[Tuple[float, int, int]]] = []
        totals: List[int] = []
        for bq in bool_queries:
            clauses = bq.get("clauses") or []
            msm = int(bq.get("msm", 0))
            req, neg, shd = bool_role_masks(clauses)
            per_clause = bool_clause_rows(clauses, idf_of)
            rows: List[Tuple[float, int, int]] = []
            total = 0
            for gseg, csr in zip(self.seg_positions, self._csr):
                got = _bool_csr_shard_pool(csr["term_ids"], csr,
                                           per_clause, req, neg, shd,
                                           msm)
                if got is None:
                    continue
                scores, pool = got
                if with_totals:
                    total += int(pool.size)
                if not pool.size:
                    continue
                sel = tie_stable_topk_masked(scores, pool,
                                             min(k, csr["n_docs"]))
                rows.extend((float(scores[d]), gseg, int(d))
                            for d in sel)
            rows.sort(key=lambda r: (-r[0], r[1], r[2]))
            rows_out.append(rows[:k])
            totals.append(total)
        return rows_out, totals


class KnnDeltaScorer:
    """Append-only vector delta tier: one BLAS matmul per delta segment
    with the SAME pack-time corpus invariants as the device plane
    (:func:`prepare_knn_corpus` — unit rows for cosine, cached ``‖v‖²``
    for l2), producing raw similarities in the plane's convention so
    merged scores are directly comparable. kNN has no corpus-wide
    statistics, so the delta tier is exactly exact — no frozen-stat
    window.

    ``shards``: dicts with ``vectors`` f32[N, dim] and ``exists``
    bool[N], one per delta segment; ``seg_positions`` as in
    :class:`EagerDeltaScorer`."""

    def __init__(self, shards: Sequence[dict], seg_positions: Sequence[int],
                 *, similarity: str):
        if similarity not in KNN_SIMILARITIES:
            raise ValueError(f"unknown similarity [{similarity}]")
        self.similarity = similarity
        self.seg_positions = list(seg_positions)
        self.n_docs = 0
        self._packed: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        for s in shards:
            v = np.asarray(s["vectors"], np.float32)
            n = v.shape[0]
            self.n_docs += n
            ex = np.asarray(s.get("exists")) if s.get("exists") is not None \
                else np.ones(n, bool)
            vecs, vnorm2 = prepare_knn_corpus(v, similarity)
            vecs = vecs.copy()
            vecs[~ex] = 0.0
            vnorm2 = vnorm2.copy()
            vnorm2[~ex] = 0.0
            self._packed.append((vecs, vnorm2, ex))

    def score(self, query_vectors, k: int):
        """Raw-similarity top-k of the delta tier for a query batch —
        rows per query [(raw, global_seg, doc)] in merge order."""
        q = np.asarray(query_vectors, np.float32)
        B = q.shape[0]
        l2 = self.similarity == "l2_norm"
        if self.similarity == "cosine":
            qq = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        else:
            qq = q
        qn = np.sum(q * q, axis=1) if l2 else None
        rows_out: List[List[Tuple[float, int, int]]] = [[]
                                                        for _ in range(B)]
        for gseg, (vecs, vnorm2, ex) in zip(self.seg_positions,
                                            self._packed):
            if not ex.any() or vecs.shape[1] != q.shape[1]:
                continue
            s = qq @ vecs.T                              # [B, N] BLAS
            if l2:
                s = 2.0 * s - vnorm2[None, :] - qn[:, None]
            if not ex.all():
                s[:, ~ex] = NEG_INF
            kk = min(k, s.shape[1])
            for bi in range(B):
                top = np.argpartition(-s[bi], kk - 1)[:kk]
                sel = top[s[bi][top] > NEG_INF]
                rows_out[bi].extend(
                    (float(s[bi][d]), gseg, int(d)) for d in sel)
        for bi in range(B):
            rows_out[bi].sort(key=lambda r: (-r[0], r[1], r[2]))
            rows_out[bi] = rows_out[bi][:k]
        return rows_out
