"""``ops/index_select.top_k_mask``: the k largest valid scores of every row
as a mask, without a sort — ``lax.top_k``'s set exactly, ties (and signed
zeros) included, rows with fewer than k valid keys keep them all."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.index_select import top_k_mask


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
