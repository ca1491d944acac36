"""Exact top-k of every row of a score matrix AS A MASK, without a sort.

Learned sparse attention (a DeepSeek-Sparse-Attention indexer:
``models/keye_vl2.py``) keeps, for each query, the k keys its index scores
rank highest, and the flash kernels read that choice as a [queries, keys]
mask (``ops/flash_attention.py``, "selected tiles"). ``lax.top_k`` at k in
the thousands lowers to a full row sort on the TPU and hands back INDICES,
which a scatter would have to turn into the mask. This finds the k-th
largest score of a row instead — a bisection over the 32 bits of the float,
one compare-and-count pass over the row a bit — and the mask is a compare
with it:

    key(x)   the float's bits as an unsigned integer in the floats' order
             (the total order ``lax.top_k`` sorts by: -0.0 below +0.0; keys
             not ``valid`` take 0, below every number)
    tau      the largest u with  #{s: key(s) >= u} >= k        (32 passes)
    selected key(s) > tau, and of the keys EQUAL to tau the first
             k - #{key > tau} by position                       (ties to the
             lower index, as ``lax.top_k`` resolves them: a second
             bisection, over the position, run only where a row has more
             equal keys than it may take)

so the result is ``lax.top_k``'s set exactly, ties included
(tests/test_index_select.py), and a row with fewer than k valid keys keeps
them all. No gradient: a selection is discrete.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _ordered_keys(scores):
    """float32 -> uint32 in the floats' TOTAL order (-0.0 below +0.0, as
    ``lax.top_k`` compares)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    top = jnp.uint32(1 << 31)
    return jnp.where(bits >= top, ~bits, bits | top)


def _bisect(bits: int, accept, start):
    """The largest u of ``bits`` bits, built from the top bit down, with
    ``accept(u)`` (monotone: true for every smaller u), per row."""
    def step(i, found):
        candidate = found | (jnp.uint32(1) << (jnp.uint32(bits - 1) - i))
        return jnp.where(accept(candidate), candidate, found)

    return jax.lax.fori_loop(
        jnp.uint32(0), jnp.uint32(bits), step, start
    )


def top_k_mask(scores, valid, k: int):
    """[R, S] bool: for each row of ``scores`` [R, S] float32, the ``k``
    ``valid`` [R, S] entries with the largest score — all of them where a
    row has no more than k —, ties to the lower position."""
    rows, width = scores.shape
    keys = jnp.where(valid, _ordered_keys(scores), jnp.uint32(0))

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    zero = jnp.zeros((rows,), jnp.uint32)
    tau = _bisect(32, lambda u: count(keys >= u[:, None]) >= k, zero)
    above = keys > tau[:, None]
    equal = valid & (keys == tau[:, None])
    need = k - count(above)  # of the equal ones: >= 1 where a row has k
    position = jax.lax.broadcasted_iota(jnp.uint32, keys.shape, 1)

    def first_needed():
        # the largest p with fewer than ``need`` equal keys BEFORE it: the
        # key at p is the last one taken
        return _bisect(
            max(width - 1, 1).bit_length(),
            lambda p: count(equal & (position < p[:, None])) < need, zero,
        )

    last = jax.lax.cond(
        jnp.any(count(equal) > need), first_needed,
        lambda: jnp.full((rows,), width, jnp.uint32),
    )
    return jax.lax.stop_gradient(
        above | (equal & (position <= last[:, None]))
    )
