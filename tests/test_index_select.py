"""``ops/index_select.top_k_mask``: the k largest valid scores of every row
as a mask, without a sort — ``lax.top_k``'s set exactly, ties (and signed
zeros) included, rows with fewer than k valid keys keep them all — and
``ops/index_select.index_select``, a causal layer's index scores and their
exact top-k as ONE Pallas kernel (here under ``interpret=True``), held to
``lax.top_k`` and to ``top_k_mask(index_scores(...))``, its oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models.keye_vl2 import KeyeVL2Config, index_scores
from dedloc_tpu.ops.index_select import (
    _ordered_keys,
    _signed_keys,
    index_select,
    top_k_mask,
    top_k_mask_and_ties,
)


def _by_top_k(scores, valid, k):
    masked = np.where(valid, scores, -np.inf)
    _, chosen = jax.lax.top_k(jnp.asarray(masked), min(k, scores.shape[-1]))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(chosen), True, -1)
    return want & valid


@pytest.mark.parametrize(
    "seq,k,levels", [(64, 8, None), (64, 8, 5), (32, 64, None),
                     (128, 16, 3), (96, 1, 2)],
    ids=["distinct", "many_ties", "k_over_the_row", "ties_at_128",
         "top_1_of_ties"],
)
def test_the_set_lax_top_k_gives(seq, k, levels):
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(seq, seq)).astype(np.float32)
    if levels:  # a few distinct values: the k-th is shared
        scores = (np.round(scores * levels / 3) / levels * 3).astype(
            np.float32
        )
        scores[3, :5], scores[3, 5:9] = -0.0, 0.0  # -0.0 sorts below +0.0
    valid = np.tril(np.ones((seq, seq), bool))
    got = np.asarray(jax.jit(top_k_mask, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(valid), k
    ))
    np.testing.assert_array_equal(got, _by_top_k(scores, valid, k))
    np.testing.assert_array_equal(
        got.sum(-1), np.minimum(np.arange(seq) + 1, k)
    )


def test_infinities_and_no_gradient():
    scores = jnp.asarray(
        [[np.inf, 1.0, -np.inf, 1.0, 0.5, -np.inf]], jnp.float32
    )
    valid = jnp.ones((1, 6), bool)
    np.testing.assert_array_equal(
        top_k_mask(scores, valid, 2), [[True, True, False, False, False,
                                        False]],
    )
    np.testing.assert_array_equal(
        top_k_mask(scores, valid, 5),
        [[True, True, True, True, True, False]],  # the FIRST -inf
    )
    grad = jax.grad(
        lambda x: jnp.sum(jnp.where(top_k_mask(x, valid, 2), x, 0.0))
    )(jnp.asarray([[3.0, 1.0, 2.0, 0.0, -1.0, 5.0]]))
    # the gradient of the gathered values alone: none through the choice
    np.testing.assert_array_equal(grad, [[1.0, 0, 0, 0, 0, 1.0]])


# ------------------------------------------------- the selection as a kernel


def _operands(batch, seq, heads, width, levels, seed=0, dtype=jnp.float32):
    """q_index, k_index, weights: small INTEGERS (``levels``: every product
    and sum is exact in float32 whatever the order, so the kernel's scores
    are XLA's bit for bit, and equal scores are many) or normal draws."""
    rng = np.random.default_rng(seed)
    shapes = ((batch, seq, heads, width), (batch, seq, width),
              (batch, seq, heads))
    if levels:
        q, k, w = (
            rng.integers(-top, top + 1, shape).astype(np.float32)
            for top, shape in zip((levels, levels, 2), shapes)
        )
    else:
        q, k, w = (rng.normal(size=shape).astype(np.float32)
                   for shape in shapes)
    return jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(w)


def _oracle(q, k, w, top_k, block_rows):
    """(scores [B, S, S] float32, ``top_k_mask`` of them under the causal
    mask, the blocks of ``block_rows`` queries whose rows tied) in XLA."""
    cfg = KeyeVL2Config.tiny(index_n_heads=q.shape[2],
                             index_head_dim=q.shape[3])
    seq = q.shape[1]
    valid = jnp.tril(jnp.ones((seq, seq), bool))
    scores, masks, tied = [], [], []
    for b in range(q.shape[0]):
        scores.append(index_scores(cfg, q[b], k[b], w[b]))
        masks.append(top_k_mask(scores[-1], valid, top_k))
        tied.append([
            bool(top_k_mask_and_ties(
                scores[-1][t:t + block_rows], valid[t:t + block_rows], top_k
            )[1]) for t in range(0, seq, block_rows)
        ])
    return np.asarray(jnp.stack(scores)), np.asarray(jnp.stack(masks)), tied


def _select(q, k, w, top_k, block_rows):
    selection, tied = jax.jit(
        lambda *x: index_select(*x, top_k, block_rows=block_rows,
                                interpret=True)
    )(q, k, w)
    return np.asarray(selection), np.asarray(tied)


@pytest.mark.parametrize(
    "seq,heads,width,k,levels,rows",
    [(128, 4, 16, 16, 40, 64), (128, 4, 16, 16, 1, 64),
     (128, 2, 8, 100, 2, 64), (256, 2, 8, 130, 1, 256),
     (128, 2, 8, 1, 1, 32)],
    ids=["distinct", "many_ties", "k_over_the_row", "ties_at_128",
         "top_1_of_ties"],
)
def test_the_kernel_gives_the_set_lax_top_k_gives(seq, heads, width, k,
                                                  levels, rows):
    """On integer-valued operands the kernel's scores are XLA's bit for bit,
    so its mask is ``lax.top_k``'s set — and ``top_k_mask``'s, and the
    blocks that resolved ties by position are the oracle's."""
    q, kk, w = _operands(1, seq, heads, width, levels)
    scores, want, tied = _oracle(q, kk, w, k, rows)
    got, got_tied = _select(q, kk, w, k, rows)
    valid = np.tril(np.ones((seq, seq), bool))
    np.testing.assert_array_equal(got[0] != 0, _by_top_k(scores[0], valid, k))
    np.testing.assert_array_equal(got != 0, want)
    np.testing.assert_array_equal(got_tied != 0, tied)
    if levels == 1:  # a handful of distinct scores: rows tie at their k-th
        assert got_tied.any()
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}


def test_distinct_scores_take_no_tie_pass_and_blocks_under_k_keep_every_key():
    """Two batch rows of four blocks of 64 queries, top-128: the first two
    blocks of a row keep every valid key without a score (t < k), the others
    bisect; positive operands leave the relu idle and no two scores of a row
    equal, so no block takes the pass over the position."""
    rng = np.random.default_rng(5)
    q, kk, w = (
        jnp.asarray(rng.uniform(0.5, 1.5, shape).astype(np.float32))
        for shape in ((2, 256, 2, 8), (2, 256, 8), (2, 256, 2))
    )
    scores, want, tied = _oracle(q, kk, w, 128, 64)
    got, got_tied = _select(q, kk, w, 128, 64)
    np.testing.assert_array_equal(got != 0, want)
    np.testing.assert_array_equal(
        got[:, :128], np.broadcast_to(np.tril(np.ones((256, 256)))[:128],
                                      (2, 128, 256)),
    )
    assert not got_tied.any() and not np.any(tied)
    assert got_tied.shape == (2, 4)


@pytest.mark.parametrize("seq,rows", [(512, 256), (384, 128)],
                         ids=["two_blocks_of_256", "three_blocks_of_128"])
def test_the_kernel_on_bf16_operands_at_the_published_widths(seq, rows):
    """16 index heads of 64 (two a lane window), bf16 dots with float32
    sums: a row's sixteen terms are summed in another order than XLA's, so a
    score may differ in its last bits — the two masks differ, if at all, only
    at keys within a few ulps of the row's threshold; every row holds
    min(t + 1, k) ones and nothing above the diagonal."""
    k = 96
    q, kk, w = _operands(1, seq, 16, 64, 0, seed=1, dtype=jnp.bfloat16)
    scores, want, _tied = _oracle(q, kk, w, k, rows)
    got, _ = _select(q, kk, w, k, rows)
    np.testing.assert_array_equal(
        got[0].sum(-1), np.minimum(np.arange(seq) + 1, k)
    )
    assert not np.triu(got[0], 1).any()
    for t, s in zip(*np.nonzero((got[0] != 0) != want[0])):
        threshold = np.sort(scores[0, t, :t + 1])[-k]
        assert abs(scores[0, t, s] - threshold) <= 8 * np.spacing(
            np.abs(threshold)
        ), (t, s)


def test_the_kernels_signed_keys_are_the_ordered_keys():
    """The kernel compares SIGNED integers: the unsigned key with its top
    bit flipped — the same total order (-0.0 under +0.0, the infinities at
    the ends), and 0, "below every number", is INT_MIN."""
    x = jnp.asarray([-np.inf, -3.5, -1e-45, -0.0, 0.0, 1e-45, 2.0, np.inf],
                    jnp.float32)
    signed = np.asarray(_signed_keys(x))
    assert (np.diff(signed.astype(np.int64)) > 0).all()
    np.testing.assert_array_equal(
        signed.view(np.uint32) ^ np.uint32(2**31), np.asarray(_ordered_keys(x))
    )
