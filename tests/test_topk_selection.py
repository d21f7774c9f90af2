"""``ops.topk.batched_blockwise_topk`` is EXACT: values and indices equal
plain ``lax.top_k`` bit for bit, on both sides of its engagement guard.

The selection sorts only the groups whose maxima can hold a winner; what
can go wrong is the tie order (equal values must come out column
ascending, as ``merge_block`` and ``_global_topk_reduce`` rely on) and the
-inf padding (a row with fewer than k finite scores returns -inf slots
at the lowest free columns, as ``lax.top_k`` does).
"""

import zlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax

from elasticsearch_tpu.ops import topk
from elasticsearch_tpu.ops.topk import batched_blockwise_topk

KINDS = ("random", "ties3", "neginf50", "neginf99", "all_neginf")


def _scores(rng, kind, B, n):
    if kind == "ties3":
        return rng.randint(0, 3, (B, n)).astype(np.float32)
    s = rng.randn(B, n).astype(np.float32)
    if kind == "neginf50":
        s[rng.rand(B, n) < 0.5] = -np.inf
    elif kind == "neginf99":
        s[rng.rand(B, n) < 0.99] = -np.inf
    elif kind == "all_neginf":
        s[:] = -np.inf
    return s


# (B, n, k, selects): the kNN scan's block at the served k (128) and the
# body's (100), the narrowest row that selects, and shapes the guard turns
# away (a row under the floor, no group dividing n, k groups passing a
# quarter of the row, k >= the number of groups, k > n)
SHAPES = [
    (1, 65536, 1, True), (2, 65536, 10, True), (32, 65536, 100, True),
    (2, 65536, 128, True), (1, 1 << 17, 1000, True),
    (1, 16384, 10, True), (32, 16384, 100, True),
    (2, 8192, 1, False), (32, 4096, 10, False),
    (2, 16400, 10, False), (1, 16384, 2000, False),
    (1, 16384, 5000, False), (32, 512, 100, False), (1, 64, 100, False),
]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B,n,k,selects", SHAPES)
def test_selection_equals_lax_top_k(B, n, k, selects, kind):
    assert bool(topk._group_width(n, k)) == selects, \
        "the case no longer sits on the side of the guard it was written for"
    rng = np.random.RandomState(zlib.crc32(repr((B, n, k, kind)).encode()))
    scores = jnp.asarray(_scores(rng, kind, B, n))
    want_v, want_i = lax.top_k(scores, min(k, n))
    got_v, got_i = jax.jit(batched_blockwise_topk, static_argnums=1)(
        scores, k)
    assert got_v.dtype == want_v.dtype and got_i.dtype == jnp.int32
    # bit for bit: the int32 view tells -0.0 from 0.0
    np.testing.assert_array_equal(np.asarray(got_v).view(np.int32),
                                  np.asarray(want_v).view(np.int32))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_group_width_is_a_function_of_the_shape():
    """Engagement is decided at trace time from (n, k) alone: a group
    divides the row, k groups are at most a quarter of it, and the
    maxima and the candidates are about equally many."""
    for n in (64, 1000, 4096, 16384, 65536, 1 << 21):
        for k in (1, 10, 100, 128, 1000, 10000):
            g = topk._group_width(n, k)
            if g:
                assert n % g == 0 and k * g <= n // 4, (n, k, g)
                # the two sorts are balanced within a factor of four
                assert n / g <= k * g < 4 * n / g, (n, k, g)
