"""Kimi Delta Attention's kernel pair (ops/kda.py) under ``interpret=True``
against the float32 token-by-token recurrence: the output and all five
gradients at two chunk counts and a batch x head grid, in float32 (the
algebra, tight) and in bf16 (the rounding, loose); decays of e^-30 a step
(``e^{-G}`` alone would overflow); beta = 0 leaves pure decay; alpha = beta =
1 under a repeated unit key returns the LAST value written; the state
crosses a chunk's edge; the state that leaves the row; more heads than a
grid step takes; a ragged row."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops import kda as kda_ops
from dedloc_tpu.ops.kda import CHUNK, kda, kda_recurrence


def _operands(batch, seq, heads, dim, dtype=jnp.float32, seed=0, decay=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    shape = (batch, seq, heads, dim)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(keys[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    # a rate per head from e^-3 to e^1 a step, times ``decay``
    rate = jnp.exp(jax.random.uniform(keys[3], (heads, 1), minval=-3, maxval=1))
    g = -decay * rate * jax.nn.softplus(jax.random.normal(keys[4], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3]))
    weight = jax.random.normal(keys[6], shape)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), weight


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _value_and_grads(fn, operands, weight):
    def loss(*xs):
        out = fn(*xs)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True
    )(*operands)
    return out, grads


@pytest.mark.parametrize(
    "batch,seq,heads,dim,dtype,tolerance",
    [(2, 2 * CHUNK, 3, 16, jnp.float32, 2e-5),
     (1, 4 * CHUNK, 2, 32, jnp.float32, 2e-5),
     (2, 2 * CHUNK, 2, 32, jnp.bfloat16, 2e-2)],
    ids=["float32_two_chunks_2x3", "float32_four_chunks", "bf16_two_chunks"],
)
def test_kernels_match_the_recurrence(batch, seq, heads, dim, dtype,
                                      tolerance):
    operands, weight = _operands(batch, seq, heads, dim, dtype)
    out, grads = _value_and_grads(kda, operands, weight)
    rounded = tuple(x.astype(jnp.float32) for x in operands)
    ref_out, ref_grads = _value_and_grads(kda_recurrence, rounded, weight)
    assert out.dtype == dtype and _rel(out, ref_out) < tolerance
    for name, grad, ref in zip("q k v g beta".split(), grads, ref_grads):
        assert grad.shape == ref.shape, name
        assert _rel(grad, ref) < 3 * tolerance, name


def test_decays_of_e_minus_30_a_step_stay_finite_and_exact():
    """g down to -30 x e a step: 64 steps of it are e^-5000 and ``e^{-G}``
    is infinite in any float; only differences are exponentiated."""
    operands, weight = _operands(1, 2 * CHUNK, 2, 16, decay=30.0)
    assert float(jnp.min(operands[3])) < -30.0
    out, grads = _value_and_grads(kda, operands, weight)
    ref_out, ref_grads = _value_and_grads(kda_recurrence, operands, weight)
    for got, ref in zip((out,) + grads, (ref_out,) + ref_grads):
        assert bool(jnp.all(jnp.isfinite(got)))
        assert _rel(got, ref) < 1e-4


def test_beta_zero_leaves_pure_decay():
    """Nothing is ever written: the state stays 0 and so does the output,
    whatever q, k, v and g."""
    (q, k, v, g, beta), _w = _operands(1, 2 * CHUNK, 2, 16)
    out, state = kda(q, k, v, g, jnp.zeros_like(beta), return_state=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0
    assert float(jnp.max(jnp.abs(state))) == 0.0


def test_a_repeated_unit_key_returns_the_last_value_written():
    """alpha = 1, beta = 1, k the same unit vector at every step: each write
    REPLACES what the key held (the delta rule's defining property), so
    reading with q = k gives v_t at every t, across the chunk's edge too."""
    seq, dim = 2 * CHUNK, 16
    key = jnp.zeros((dim,)).at[3].set(1.0)
    k = jnp.broadcast_to(key, (1, seq, 1, dim))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, seq, 1, dim))
    out = kda(k, k, v, jnp.zeros((1, seq, 1, dim)), jnp.ones((1, seq, 1)))
    np.testing.assert_allclose(out, v, atol=1e-5)


def test_the_state_crosses_a_chunk_edge():
    """A value written in the first chunk is read in the second, decayed by
    every step between, and the state that leaves the row is the
    recurrence's."""
    seq, dim = 2 * CHUNK, 16
    (q, k, v, g, beta), _w = _operands(1, seq, 1, dim, seed=1)
    live = jnp.arange(seq) == 5  # ONE write, at t = 5
    beta = jnp.where(live[None, :, None], 1.0, 0.0)
    out, state = kda(q, k, v, g, beta, return_state=True)
    t = CHUNK + 7
    decay = jnp.exp(jnp.sum(g[0, 6:t + 1, 0], axis=0))  # [dk]
    expected = jnp.sum(q[0, t, 0] * decay * k[0, 5, 0]) * v[0, 5, 0]
    np.testing.assert_allclose(out[0, t, 0], expected, rtol=1e-4, atol=1e-7)
    assert float(jnp.max(jnp.abs(out[0, :5]))) == 0.0
    _ref, ref_state = kda_recurrence(q, k, v, g, beta, return_state=True)
    np.testing.assert_allclose(state, ref_state, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("per", [1, 3])
def test_a_grid_step_takes_its_share_of_the_heads(monkeypatch, per):
    """Four heads through steps of one head, and of two (the largest
    divisor under three): the same function."""
    monkeypatch.setattr(kda_ops, "HEADS_PER_STEP", per)
    operands, weight = _operands(1, 2 * CHUNK, 4, 16, seed=2)
    out, grads = _value_and_grads(kda, operands, weight)
    ref_out, ref_grads = _value_and_grads(kda_recurrence, operands, weight)
    for got, ref in zip((out,) + grads, (ref_out,) + ref_grads):
        assert _rel(got, ref) < 5e-5


def test_a_ragged_row_is_padded_with_tokens_that_write_nothing():
    operands, weight = _operands(1, CHUNK + 8, 2, 16, seed=3)
    out, grads = _value_and_grads(kda, operands, weight)
    ref_out, ref_grads = _value_and_grads(kda_recurrence, operands, weight)
    for got, ref in zip((out,) + grads, (ref_out,) + ref_grads):
        assert got.shape == ref.shape and _rel(got, ref) < 5e-5
    _out, state = kda(*operands, return_state=True)
    _ref, ref_state = kda_recurrence(*operands, return_state=True)
    np.testing.assert_allclose(state, ref_state, rtol=1e-4, atol=1e-7)
