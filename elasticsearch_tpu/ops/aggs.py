"""Device kernels for aggregations: scatter-free masked ordinal reductions.

The reference collects aggregations doc-at-a-time into BigArrays buckets
(``search/aggregations/AggregatorBase.java``; the hot loop is
``LeafBucketCollector.collect(doc, bucket)`` — SURVEY §3.2 hot loop 2).
A TPU scatter-add over bucket ords would serialize, so these kernels use two
scatter-free shapes instead:

- **ordinal-CSR cumsum-diff** for high-cardinality keyword ordinals: with
  doc-values pairs sorted by (ordinal, doc) and a CSR ``offsets[V+1]``, the
  per-ordinal masked count is ``cumsum(mask_pairs)`` gathered at run
  boundaries — one gather + one cumsum + one small gather, all vectorized.
  Counts accumulate in int32, so they are **exact** (no float summation
  order issues) and bitwise-match the host numpy path.
- **one-hot matmul** for low-cardinality buckets (histograms): a
  ``[M, nb]`` equality one-hot reduced over pairs — XLA fuses the compare +
  sum; for f32 sums this rides the MXU.

Masks arrive as the query's dense ``bool[n_pad]`` doc mask (the query tree
output); pair docs are padded with the ``n_pad`` sentinel which gathers a
``False``/0 via OOB-fill, so padding is inert.

Precision contract: counts are int32-exact; value sums use f32 cumsum and
are only used on the device path when the caller accepts f32 (the exact
float64 reduction stays host-side, see ``search/aggregations.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import in_named_scope

#: below this many doc-values pairs the host numpy path wins (dispatch
#: overhead dominates); aggregations consult this before shipping to device
DEVICE_MIN_PAIRS = 1 << 16

#: one-hot histogram kernel cap: above this bucket count the [M, nb]
#: one-hot is wasteful and the host path wins
MAX_DEVICE_BUCKETS = 4096


@jax.jit
@in_named_scope("masked_ordinal_counts")
def masked_ordinal_counts(offsets, pair_docs, mask):
    """Exact per-ordinal masked pair counts.

    offsets:   int32[Vp+1] ordinal-CSR run boundaries (padded ordinals are
               zero-length runs — ``offsets`` repeats its last value).
    pair_docs: int32[Mp] owning doc per pair, sorted by (ordinal, doc),
               padded with an out-of-range sentinel.
    mask:      bool[n_pad] dense query doc mask.
    Returns int32[Vp] counts.
    """
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                         jnp.cumsum(m.astype(jnp.int32))])
    return jnp.take(c, offsets[1:]) - jnp.take(c, offsets[:-1])


@jax.jit
@in_named_scope("masked_ordinal_sums")
def masked_ordinal_sums(offsets, pair_docs, pair_vals, mask):
    """Per-ordinal masked f32 value sums (same layout as
    :func:`masked_ordinal_counts`; f32 cumsum — see precision contract)."""
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    mv = jnp.where(m, pair_vals, 0.0)
    s = jnp.concatenate([jnp.zeros(1, jnp.float32), jnp.cumsum(mv)])
    return jnp.take(s, offsets[1:]) - jnp.take(s, offsets[:-1])


@functools.partial(jax.jit, static_argnames=("n_buckets",))
@in_named_scope("masked_bucket_counts")
def masked_bucket_counts(bucket_ids, pair_docs, mask, *, n_buckets: int):
    """Low-cardinality masked bucket counts via one-hot reduction.

    bucket_ids: int32[Mp] precomputed bucket per pair (host computes these
                exactly in f64 once per (field, interval) and caches the
                device array); out-of-range ids fall outside [0, n_buckets).
    Returns int32[n_buckets].
    """
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    onehot = (bucket_ids[:, None] == jnp.arange(n_buckets, dtype=jnp.int32)
              [None, :]) & m[:, None]
    return jnp.sum(onehot.astype(jnp.int32), axis=0)


@functools.partial(jax.jit, static_argnames=("n_buckets",))
@in_named_scope("masked_bucket_sums")
def masked_bucket_sums(bucket_ids, pair_docs, pair_vals, mask,
                       *, n_buckets: int):
    """One-hot masked f32 value sums per bucket (MXU-friendly matmul)."""
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    onehot = ((bucket_ids[:, None] ==
               jnp.arange(n_buckets, dtype=jnp.int32)[None, :]) &
              m[:, None]).astype(jnp.float32)
    mv = jnp.where(m, pair_vals, 0.0)
    return mv @ onehot


@jax.jit
@in_named_scope("masked_metrics")
def masked_metrics(pair_docs, pair_vals, mask):
    """One-pass masked (count, sum, min, max) over a pair column.
    Returns (f32 count, f32 sum, f32 min, f32 max) — min/max are +inf/-inf
    when nothing matches."""
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    cnt = jnp.sum(m.astype(jnp.float32))
    s = jnp.sum(jnp.where(m, pair_vals, 0.0))
    mn = jnp.min(jnp.where(m, pair_vals, jnp.inf))
    mx = jnp.max(jnp.where(m, pair_vals, -jnp.inf))
    return cnt, s, mn, mx


@jax.jit
@in_named_scope("masked_rank_prefix")
def masked_rank_prefix(offsets, pair_docs, mask):
    """Masked-count prefix over a **(ordinal, value)**-sorted pair layout —
    the exact-percentile primitive.

    With pairs sorted by (ordinal, value) so each ordinal's run holds its
    values ascending, the masked prefix ``C = cumsum(mask[pair_docs])`` is
    monotone; the r-th smallest *masked* value of ordinal ``o`` (run
    ``[st, en)``) sits at the first index ``i`` with
    ``C[i+1] - C[st] == r + 1`` — found by ``searchsorted`` on ``C``
    (:func:`_rank_pick`). One bandwidth pass + O(log M) per
    (bucket, rank): exact percentiles where the reference approximates
    with TDigest (``search/aggregations/metrics/TDigestState.java``) and
    collects doc-at-a-time.

    Returns (counts int32[V], prefix int32[M+1]) — the prefix stays a
    device array for :func:`_rank_pick`.
    """
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                         jnp.cumsum(m.astype(jnp.int32))])
    counts = jnp.take(c, offsets[1:]) - jnp.take(c, offsets[:-1])
    return counts, c


@jax.jit
def _rank_pick(c, offsets, pair_vals_sorted, ordinals, lo, hi, frac):
    """Device rank→value gather: searchsorted on the monotone masked-count
    prefix ``c`` finds the pair index of each wanted masked rank; linear
    interpolation between the lo/hi ranks happens in the same kernel.
    ordinals int32[B]; lo/hi int32[B, R]; frac f32[B, R]."""
    st = jnp.take(offsets, ordinals)                        # [B]
    base = jnp.take(c, st)                                  # [B]

    def pick(rank):                                         # [B, R]
        tgt = base[:, None] + rank + 1
        idx = jnp.searchsorted(c, tgt, side="left") - 1
        idx = jnp.clip(idx, 0, pair_vals_sorted.shape[0] - 1)
        return jnp.take(pair_vals_sorted, idx)

    return (1.0 - frac) * pick(lo) + frac * pick(hi)


def masked_ordinal_percentiles(offsets, pair_docs, pair_vals_sorted, mask,
                               ordinals, qs):
    """Exact masked percentiles per ordinal (Hazen interpolation, matching
    ``search/aggregations.py``'s host path). ``ordinals`` int32[B] selects
    which buckets; ``qs`` float[R] in [0, 100]. Returns f64[B, R] (NaN for
    empty buckets). Only the V-sized counts and the [B, R] result cross
    the host boundary; the M-sized prefix stays on device.

    Callers: the terms+percentiles benchmark (``bench.py`` config #3,
    BASELINE.md). Product integration is staged: the REST percentiles agg
    (``search/aggregations.py`` PercentilesAgg) reduces exactly across
    multiple segments, which needs a cross-segment rank merge on top of
    this single-run kernel."""
    counts, c = masked_rank_prefix(offsets, pair_docs, mask)
    counts_h = np.asarray(counts)
    ordinals = np.asarray(ordinals, np.int64)
    qs = np.asarray(qs, np.float64)
    n = counts_h[ordinals].astype(np.float64)              # [B]
    # Hazen position q·n − ½ clamped to [0, n−1]; lo/hi adjacent ranks
    pos = np.clip(qs[None, :] / 100.0 * n[:, None] - 0.5, 0.0,
                  np.maximum(n[:, None] - 1.0, 0.0))
    lo = np.floor(pos).astype(np.int32)
    hi = np.minimum(lo + 1,
                    np.maximum(n[:, None].astype(np.int32) - 1, 0))
    frac = (pos - lo).astype(np.float32)
    picked = _rank_pick(c, jnp.asarray(offsets),
                        pair_vals_sorted, jnp.asarray(ordinals, jnp.int32),
                        jnp.asarray(lo), jnp.asarray(hi),
                        jnp.asarray(frac))
    out = np.asarray(picked, np.float64)
    out[n == 0] = np.nan
    return out


def top_ordinals(counts, k: int):
    """(counts desc, ordinal asc) top-k over a device counts vector.
    Ties resolve to the lower ordinal (term-dictionary order — the
    reference's ``BytesRef`` compare)."""
    kk = min(k, counts.shape[0])
    vals, ords = jax.lax.top_k(counts, kk)
    return np.asarray(vals), np.asarray(ords)


# ---------------------------------------------------------------------------
# per-segment device caches (ordinal CSR, histogram bucket ids)
# ---------------------------------------------------------------------------


def _pad_pow2(arr: np.ndarray, fill) -> np.ndarray:
    from ..utils.shapes import round_up_pow2
    size = round_up_pow2(max(arr.shape[0], 1))
    if arr.shape[0] == size:
        return arr
    out = np.full(size, fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _seg_cache(seg) -> dict:
    # lives on the segment so it dies with it (no id()-keyed global map
    # that could collide after GC)
    c = getattr(seg, "_agg_dev_cache", None)
    if c is None:
        c = seg._agg_dev_cache = {}
    return c


def ordinal_csr(seg, field: str):
    """Lazy per-(segment, field) ordinal-CSR device arrays for keyword
    doc-values: pairs re-sorted by (ordinal, doc) + padded offsets.
    Returns (offsets_dev i32[Vp+1], pair_docs_dev i32[Mp], V)."""
    cache = _seg_cache(seg)
    key = ("ord_csr", field)
    hit = cache.get(key)
    if hit is not None:
        return hit
    f = seg.keyword_fields[field]
    order = np.lexsort((f.dv_docs_host, f.dv_ords_host))
    sdocs = f.dv_docs_host[order]
    sords = f.dv_ords_host[order]
    v = len(f.ord_terms)
    offsets = np.zeros(v + 1, np.int32)
    np.cumsum(np.bincount(sords, minlength=v).astype(np.int32),
              out=offsets[1:])
    off_pad = _pad_pow2(offsets, offsets[-1])
    docs_pad = _pad_pow2(sdocs, seg.n_pad)
    hit = (jnp.asarray(off_pad), jnp.asarray(docs_pad), v)
    cache[key] = hit
    return hit


HLL_P = 14  #: register precision: m = 2^p registers, ~1.04/sqrt(m) error

_U64 = np.uint64
_MIX_1 = _U64(0xFF51AFD7ED558CCD)
_MIX_2 = _U64(0xC4CEB9FE1A85EC53)


def _mix64_u64(z: np.ndarray) -> np.ndarray:
    """Stafford mix13 finalizer over uint64 (vectorized, wrap-around)."""
    with np.errstate(over="ignore"):
        z = (z ^ (z >> _U64(33))) * _MIX_1
        z = (z ^ (z >> _U64(33))) * _MIX_2
        return z ^ (z >> _U64(33))


def _clz64(x: np.ndarray) -> np.ndarray:
    """Leading-zero count of uint64 (vectorized; returns 63 for 0 —
    callers special-case zero words)."""
    x = x.astype(np.uint64, copy=True)
    n = np.zeros(x.shape, np.int32)
    for s in (32, 16, 8, 4, 2, 1):
        small = x < (_U64(1) << _U64(64 - s))
        n[small] += s
        with np.errstate(over="ignore"):
            x[small] = x[small] << _U64(s)
    return n


def _fnv64_bytes(data: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in data:
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def value_hash_u64(value):
    """Deterministic 64-bit hash of a doc value (str via mix13-finalized
    FNV-1a — FNV alone leaves the top bits poorly mixed on short strings
    and the register index is the top ``p`` bits; numeric via mix13 of
    the f64 bit pattern). The scalar twin of the pair-cache hashing —
    CardinalityAgg folds exact sets into sketches with it."""
    if isinstance(value, str):
        bits = np.array(_fnv64_bytes(value.encode("utf-8")), np.uint64)
    else:
        bits = np.array(float(value), np.float64).view(np.uint64)
    return int(_mix64_u64(bits.reshape(1))[0])


def _hll_reg_rho(h: np.ndarray, p: int):
    """Split hashes into (register id, rho): top ``p`` bits pick the
    register, rho = leading-zero count of the remaining bits + 1
    (``64 - p + 1`` when they are all zero)."""
    reg = (h >> _U64(64 - p)).astype(np.int32)
    with np.errstate(over="ignore"):
        w = h << _U64(p)
    rho = np.where(w == 0, np.int32(64 - p + 1),
                   _clz64(w) + 1).astype(np.int32)
    return reg, rho


def hll_sketch_pairs(seg, field: str, p: int = HLL_P):
    """Lazy per-(segment, field, p) hashed doc-values pairs for the HLL++
    cardinality sketch: pairs sorted by (register, rho) so the masked
    per-register max is the LAST masked element of each ascending-rho run
    (same cumsum+searchsorted shape as the percentile kernel).

    Returns a dict with device arrays (``off_dev``, ``docs_dev``,
    ``rhos_dev``) and their host twins (``reg``, ``rho``, ``docs``) plus
    ``m`` (register count) and ``n_pairs``.
    """
    cache = _seg_cache(seg)
    key = ("hll", field, p)
    hit = cache.get(key)
    if hit is not None:
        return hit
    if field in getattr(seg, "keyword_fields", {}):
        f = seg.keyword_fields[field]
        term_h = _mix64_u64(np.fromiter(
            (_fnv64_bytes(str(t).encode("utf-8")) for t in f.ord_terms),
            np.uint64, count=len(f.ord_terms)))
        h = term_h[f.dv_ords_host]
        docs = f.dv_docs_host
    else:
        f = seg.numeric_fields[field]
        h = _mix64_u64(f.vals_host.astype(np.float64).view(np.uint64))
        docs = f.docs_host
    reg, rho = _hll_reg_rho(h, p)
    order = np.lexsort((rho, reg))
    reg_s, rho_s, docs_s = reg[order], rho[order], docs[order]
    m = 1 << p
    offsets = np.zeros(m + 1, np.int32)
    np.cumsum(np.bincount(reg_s, minlength=m).astype(np.int32),
              out=offsets[1:])
    hit = {
        "off_dev": jnp.asarray(_pad_pow2(offsets, offsets[-1])),
        "docs_dev": jnp.asarray(_pad_pow2(docs_s.astype(np.int32),
                                          np.int32(seg.n_pad))),
        "rhos_dev": jnp.asarray(_pad_pow2(rho_s, np.int32(0))),
        "reg": reg_s, "rho": rho_s, "docs": docs_s.astype(np.int32),
        "m": m, "n_pairs": int(docs_s.shape[0]),
    }
    cache[key] = hit
    return hit


def distinct_count(seg, field: str) -> int:
    """Cached per-(segment, field) distinct value count — the regime
    trigger for exact-set vs HLL cardinality (route-independent: both the
    fused and the legacy path consult the same cached number)."""
    cache = _seg_cache(seg)
    key = ("distinct", field)
    hit = cache.get(key)
    if hit is None:
        if field in getattr(seg, "keyword_fields", {}):
            hit = len(seg.keyword_fields[field].ord_terms)
        else:
            hit = int(np.unique(
                seg.numeric_fields[field].vals_host).size)
        cache[key] = hit
    return hit


@jax.jit
@in_named_scope("masked_register_max")
def masked_register_max(offsets, pair_docs, pair_rhos, mask):
    """Masked per-register rho max over (register, rho)-sorted pairs.

    Within each register's run rhos ascend, so the last *masked* pair of
    the run carries the max masked rho; its index is recovered from the
    monotone masked-count prefix by one searchsorted (no scatter-max).
    Returns int32[len(offsets) - 1] registers (0 where nothing matched).
    Segment/shard merge of two register arrays is one elementwise
    ``maximum`` — ICI-friendly like the top-k payload reduce.
    """
    m = jnp.take(mask, pair_docs, mode="fill", fill_value=False)
    c = jnp.concatenate([jnp.zeros(1, jnp.int32),
                         jnp.cumsum(m.astype(jnp.int32))])
    st = jnp.take(c, offsets[:-1])
    cnt = jnp.take(c, offsets[1:]) - st
    idx = jnp.searchsorted(c, st + cnt, side="left") - 1
    idx = jnp.clip(idx, 0, pair_rhos.shape[0] - 1)
    return jnp.where(cnt > 0, jnp.take(pair_rhos, idx), 0)


def host_register_max(pairs: dict, mask: np.ndarray) -> np.ndarray:
    """Host numpy twin of :func:`masked_register_max` — integer max is
    order-independent, so this is bitwise-identical to the device kernel
    over the same cached pairs."""
    regs = np.zeros(pairs["m"], np.int32)
    pm = mask[pairs["docs"]]
    np.maximum.at(regs, pairs["reg"][pm], pairs["rho"][pm])
    return regs


def hll_merge(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sketch merge = elementwise register maximum."""
    return np.maximum(a, b)


def hll_add_values(regs: np.ndarray, values, p: int) -> np.ndarray:
    """Fold raw values (an exact-set partial) into a register array —
    used when a reduce mixes exact and sketch partials across segments."""
    for v in values:
        h = value_hash_u64(v)
        reg = h >> (64 - p)
        w = (h << p) & 0xFFFFFFFFFFFFFFFF
        rho = (64 - p + 1) if w == 0 else (64 - w.bit_length()) + 1
        if rho > regs[reg]:
            regs[reg] = rho
    return regs


def hll_estimate(regs: np.ndarray) -> int:
    """Deterministic HLL estimate with linear-counting small-range
    correction (reference: ``metrics/HyperLogLogPlusPlus.java``; this
    repro uses the classic bias-corrected form — deterministic and
    identical across the fused and legacy routes, which share this code)."""
    regs = np.asarray(regs, np.int64)
    m = regs.size
    alpha = 0.7213 / (1.0 + 1.079 / m)
    est = alpha * m * m / float(np.sum(np.exp2(-regs.astype(np.float64))))
    if est <= 2.5 * m:
        zeros = int(np.count_nonzero(regs == 0))
        if zeros:
            est = m * float(np.log(m / zeros))
    return int(est + 0.5)


def histogram_bucket_ids(seg, field: str, interval: float, offset: float):
    """Lazy per-(segment, field, interval, offset) device bucket-id arrays
    for numeric histograms. Bucket ids are computed host-side in exact f64
    once, then reused across queries with different masks.
    Returns (ids_dev i32[Mp], pair_docs_dev i32[Mp], n_buckets, base)."""
    cache = _seg_cache(seg)
    key = ("hist", field, interval, offset)
    hit = cache.get(key)
    if hit is not None:
        return hit
    f = seg.numeric_fields[field]
    keys = np.floor((f.vals_host - offset) / interval)
    base = float(keys.min()) if keys.size else 0.0
    # bucket span in exact f64 BEFORE any int32 cast: a wide value range
    # must report its true n_buckets so the caller's cardinality guard
    # falls back to the host path instead of silently wrapping
    span = float(keys.max() - base) if keys.size else -1.0
    n_buckets = int(span) + 1 if keys.size else 0
    if n_buckets > MAX_DEVICE_BUCKETS:
        # too many buckets for the one-hot kernel (and beyond 2^31 the
        # int32 cast would wrap) — callers take the host path
        hit = (None, None, n_buckets, base)
        cache[key] = hit
        return hit
    ids = (keys - base).astype(np.int32)
    ids_pad = _pad_pow2(ids, np.int32(-1))
    docs_pad = _pad_pow2(f.docs_host, seg.n_pad)
    hit = (jnp.asarray(ids_pad), jnp.asarray(docs_pad), n_buckets, base)
    cache[key] = hit
    return hit
