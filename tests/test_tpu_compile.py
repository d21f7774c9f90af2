"""The served path's step builders, compiled for a described TPU v5e (2x2)
at real widths — no chip attached, nothing runs. The TPU compiler refuses
what XLA:CPU never sees (a program past HBM, a kernel it cannot lay out),
so these guard every later change at no chip time: Q floor 8, ladder L,
k buckets for 10 and 100, dims 100 and 768, T_pad 256, C 2^19, B 64.

Only the process that runs this file loads libtpu (inside the ``topo``
fixture, never at import); keep every such test in this one file."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.ops.fused_query import MAX_BOOL_CLAUSES
from elasticsearch_tpu.parallel import dist_search as ds
from elasticsearch_tpu.parallel.mesh import AXIS_REPLICA as R
from elasticsearch_tpu.parallel.mesh import AXIS_SHARD as S

GIB = 1 << 30
B, Q = 64, 8
K10, K100 = 16, 128          # the micro-batcher's k buckets for 10 / 100


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 (skips when it cannot be described), with the
    persistent compilation cache off around this module's compiles: an
    entry compiled for a described device cannot be read back without a
    chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / locked: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mesh(topo, replicas: int, shards: int) -> Mesh:
    grid = np.asarray(topo.devices[: replicas * shards]).reshape(
        replicas, shards)
    return Mesh(grid, (R, S))


def _sds(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P(*spec)))


def _text_args(mesh, n_shards, p_pad):
    return (_sds(mesh, (n_shards, p_pad), jnp.int32, S, None),
            _sds(mesh, (n_shards, p_pad), jnp.float32, S, None),
            _sds(mesh, (B, n_shards, Q), jnp.int32, R, S, None),
            _sds(mesh, (B, n_shards, Q), jnp.int32, R, S, None),
            _sds(mesh, (B, Q), jnp.float32, R, None))


def _bool_tail(mesh):
    r1 = _sds(mesh, (B,), jnp.int32, R)
    return (_sds(mesh, (B, Q), jnp.int32, R, None), r1, r1, r1, r1)


def _tiered(mesh, n_pad, p_pad, L, U=None, T_pad=256, C=1 << 19):
    step = ds.build_tiered_bm25_step(
        mesh, n_pad=n_pad, Q=Q, L=L, k=K10, T_pad=T_pad, C=C, n_shards=1,
        with_count=True, U=U)
    pd, pi, st, ln, iw = _text_args(mesh, 1, p_pad)
    gathered = U is not None and U < T_pad
    return step, (
        pd, pi,
        _sds(mesh, (1, n_pad // C, T_pad, C), jnp.bfloat16,
             S, None, None, None),
        st, ln, iw,
        _sds(mesh, (B, 1, Q), jnp.int32, R, S, None),
        _sds(mesh, (B, 1, Q), jnp.float32, R, S, None),
        _sds(mesh, (B, 1, U if gathered else T_pad), jnp.float32,
             R, S, None),
        _sds(mesh, (1, U if gathered else 1), jnp.int32, S, None))


def _pruned(mesh, n_pad, p_pad, n_blocks, P_sched=1024, BS=128):
    step = ds.build_pruned_bm25_step(
        mesh, n_pad=n_pad, Q=Q, k=K10, P_sched=P_sched, W=128, R=128,
        BS=BS, NB=n_blocks, n_shards=1)
    pd, pi, st, ln, iw = _text_args(mesh, 1, p_pad)
    sched = _sds(mesh, (B, 1, P_sched), jnp.float32, R, S, None)
    return step, (
        pd, pi,
        _sds(mesh, (1, n_blocks + 1, BS), jnp.int32, S, None, None),
        _sds(mesh, (1, n_blocks + 1, BS), jnp.int8, S, None, None),
        _sds(mesh, (1, n_blocks + 1), jnp.float32, S, None),
        _sds(mesh, (1, n_blocks + 1), jnp.float32, S, None),
        _sds(mesh, (B, 1, P_sched), jnp.int32, R, S, None),
        sched, sched, _sds(mesh, (B, 1), jnp.float32, R, S),
        st, ln, iw)


def _bool(mesh, n_pad, p_pad, L):
    step = ds.build_bool_bm25_step(
        mesh, n_pad=n_pad, Q=Q, L=L, k=K10, nc=MAX_BOOL_CLAUSES,
        n_shards=1, with_count=True)
    return step, _text_args(mesh, 1, p_pad) + _bool_tail(mesh)


def _knn(mesh, n_shards, n_pad, dim, k):
    step = ds.build_knn_step(mesh, n_pad=n_pad, dim=dim, k=k,
                             n_shards=n_shards, similarity="cosine")
    return step, (
        _sds(mesh, (n_shards, n_pad, dim), jnp.float32, S, None, None),
        _sds(mesh, (n_shards, n_pad), jnp.float32, S, None),
        _sds(mesh, (n_shards, n_pad), jnp.bool_, S, None),
        _sds(mesh, (B, dim), jnp.float32, R, None))


def _ivf(mesh, n_pad, dim, k, nprobe=8, p_blocks=512, blk=256, nlist=512):
    n_blocks = n_pad // blk
    step = ds.build_ivf_knn_step(
        mesh, n_pad=n_pad, dim=dim, k=k, n_shards=1, similarity="cosine",
        nprobe=nprobe, r_cand=4 * k, blk=blk, width=p_blocks)

    def meta(dt):
        return _sds(mesh, (1, n_blocks + 1, blk), dt, S, None, None)

    span = _sds(mesh, (1, nlist), jnp.int32, S, None)
    return step, (
        _sds(mesh, (1, n_blocks + 1, blk, dim), jnp.int8,
             S, None, None, None),
        meta(jnp.float32), meta(jnp.float32), meta(jnp.int32),
        meta(jnp.int32),
        _sds(mesh, (1, n_pad, dim), jnp.float32, S, None, None),
        _sds(mesh, (1, n_pad), jnp.float32, S, None),
        _sds(mesh, (nlist, dim), jnp.float32),
        _sds(mesh, (nlist,), jnp.float32),
        span, span, span,
        _sds(mesh, (B, dim), jnp.float32, R, None))


def _fused(mesh, n_pad, p_pad, L, dim):
    step = ds.build_fused_hybrid_step(
        mesh, n_pad_t=n_pad, Q=Q, L=L, W_text=K10, nc=MAX_BOOL_CLAUSES,
        n_pad_k=n_pad, dim=dim, similarity="cosine", W_knn=K10,
        k=2 * K10, fusion="rrf", n_shards=1)
    r1i = _sds(mesh, (B,), jnp.int32, R)
    r1f = _sds(mesh, (B,), jnp.float32, R)
    return step, _text_args(mesh, 1, p_pad)[:2] + (
        _sds(mesh, (1, n_pad, dim), jnp.float32, S, None, None),
        _sds(mesh, (1, n_pad), jnp.float32, S, None),
        _sds(mesh, (1, n_pad), jnp.bool_, S, None)) \
        + _text_args(mesh, 1, p_pad)[2:] + _bool_tail(mesh) + (
        _sds(mesh, (B, dim), jnp.float32, R, None), r1f, r1f, r1i, r1i)


def _compile(step, args):
    compiled = step.lower(*args).compile()
    return compiled, compiled.memory_analysis()


def test_tiered_step_full_width(topo):
    """The headline deployment's serving shape: n_pad 2^23 with the 2^23
    pack's own p_pad and top L rung, on the used-row variant (U=64).
    Two bounds on its temporaries, next to 4.4 GB of resident plane:
    scoring the batch in sub-batches (7.8 GB when the merge held all 64
    queries' candidate tiles at once) and narrowing each streamed block
    to the used rows as it is read (a gathered [n_blk, U, C] copy of the
    tier cost another 3.3 GB)."""
    step, args = _tiered(_mesh(topo, 1, 1), 1 << 23, 58_827_776, 1 << 17,
                         U=64)
    _, mem = _compile(step, args)
    assert 4 * GIB < mem.argument_size_in_bytes < 5 * GIB
    assert mem.temp_size_in_bytes < 2 * GIB


def test_knn_step_glove_full_width(topo):
    """GloVe width at full n_pad (1.2M rows pad to 2^21), k bucket 128."""
    _, mem = _compile(*_knn(_mesh(topo, 1, 1), 1, 1 << 21, 100, K100))
    assert mem.argument_size_in_bytes < GIB


def test_knn_step_on_1x4_mesh(topo):
    """dim 768 over a 1x4 mesh (n_pad 2^18 per shard): the global top-k
    rides one all-gather per channel over the shard axis, each device
    holds a quarter, and the blocked scan makes no copy of its corpus
    (the [63, 1, 65536, 768] bf16 copy the compiler refused at 2^22)."""
    compiled, mem = _compile(*_knn(_mesh(topo, 1, 4), 4, 1 << 18, 768, K10))
    text = compiled.as_text()
    assert "all-gather" in text
    assert "all-to-all" not in text and "all-reduce" not in text
    shard_bytes = (1 << 18) * 768 * 4
    assert shard_bytes <= mem.argument_size_in_bytes < 1.1 * shard_bytes
    assert mem.temp_size_in_bytes < shard_bytes // 2


@pytest.mark.parametrize("name", [
    "pruned[n_pad=2^18]", "bool[n_pad=2^18]",
    "ivf[n_pad=2^18,dim=100,k=100]", "fused[n_pad=2^18,dim=768]"])
def test_step_compiles_at_reduced_n_pad(topo, name):
    mesh = _mesh(topo, 1, 1)
    n_pad, p_pad, L = 1 << 18, 4 << 20, 4096
    step, args = {
        "pruned": lambda: _pruned(mesh, n_pad, p_pad, 40_000),
        "bool": lambda: _bool(mesh, n_pad, p_pad, L),
        "ivf": lambda: _ivf(mesh, n_pad, 100, K100),
        "fused": lambda: _fused(mesh, n_pad, p_pad, L, 768),
    }[name.split("[")[0]]()
    _, mem = _compile(step, args)
    assert mem.temp_size_in_bytes < GIB


def test_segment_path_kernels(topo):
    """ops/topk.py and ops/aggs.py: what the per-segment route (hybrid
    fallback, analytics) runs on the device, at a 2^18-slot segment."""
    from jax.sharding import SingleDeviceSharding

    from elasticsearch_tpu.ops import aggs, topk
    one = SingleDeviceSharding(topo.devices[0])
    n = 1 << 18

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    topk._topk_kernel(n, K10).lower(
        sds((n,), jnp.float32), sds((n,), jnp.bool_)).compile()
    aggs.masked_ordinal_counts.lower(
        sds((1025,), jnp.int32), sds((n,), jnp.int32),
        sds((n,), jnp.bool_)).compile()
