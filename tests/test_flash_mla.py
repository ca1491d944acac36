"""The two-width flash kernels (latent attention: q and k wider than v and
the result) in interpret mode against dense float32 causal attention:
forward and the three gradients, one tile and several, and equal widths
staying on the programs they had."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import flash_attention


def _dense(q, k, v, causal=True):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _operands(rng, b, s, h, d, dv):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, dv)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, s, h, dv)), jnp.float32)
    return q, k, v, w


# (S, heads, q/k width, v width, block): one tile; several tiles with the
# whole width as the column block; several tiles at the published widths
# (two heads a column block: 384 and 256 lanes)
SHAPES = [
    pytest.param(64, 4, 24, 16, 64, id="one_tile"),
    pytest.param(128, 4, 24, 16, 32, id="tiles"),
    pytest.param(256, 2, 192, 128, 128, id="tiles_192_128"),
]


@pytest.mark.parametrize("s,h,d,dv,block", SHAPES)
def test_forward_matches_dense(rng, s, h, d, dv, block):
    q, k, v, _w = _operands(rng, 2, s, h, d, dv)
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, _dense(q, k, v), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,h,d,dv,block", SHAPES)
def test_gradients_match_dense(rng, s, h, d, dv, block):
    q, k, v, w = _operands(rng, 1, s, h, d, dv)

    def flash_loss(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block
        ))

    def dense_loss(q, k, v):
        return jnp.sum(w * _dense(q, k, v))

    got = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4, err_msg=name)


def test_not_causal_two_widths(rng):
    q, k, v, _w = _operands(rng, 1, 64, 2, 24, 16)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(
        out, _dense(q, k, v, causal=False), atol=2e-5, rtol=2e-5
    )


def _kernel_names(d, dv, s, block):
    q = jnp.zeros((1, s, 2, d), jnp.float32)
    v = jnp.zeros((1, s, 2, dv), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=False,
        ))

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, v))
    return {
        name for name in (
            "flash_causal_fwd", "flash_causal_bwd_dq", "flash_causal_bwd_dkv",
            "flash_causal_bwd_fused", "flash_mla_fwd", "flash_mla_bwd_dq",
            "flash_mla_bwd_dkv", "flash_mla_bwd_fused",
        ) if name in text
    }


def test_kernel_names_follow_the_widths():
    """Equal widths stay the programs they were (``flash_causal_*``: what
    the accepted roofline metrics read); two widths get names of their own
    (``flash_mla_*``)."""
    assert _kernel_names(16, 16, 64, 32) == {
        "flash_causal_fwd", "flash_causal_bwd_dq", "flash_causal_bwd_dkv"
    }
    assert _kernel_names(16, 16, 64, 64) == {
        "flash_causal_fwd", "flash_causal_bwd_fused"
    }
    assert _kernel_names(24, 16, 64, 32) == {
        "flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv"
    }
