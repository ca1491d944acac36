"""The causal mode of the flash kernels against dense causal attention, on
the CPU interpreter: forward and all three gradients, at D=128 (one head per
column block) and D=64 (two), a sequence of one tile (the one-tile forward,
whose ``lse`` the fused backward reads) and of 2-4 tiles with unequal
``block_q`` / ``block_k``, with a KV bias on top. Small shapes: the
interpreter is slow."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import flash_attention


def _dense(q, k, v, bias, causal):
    d, s = q.shape[-1], q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        scores = scores + bias[:, None, None, :]
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def _inputs(b, s, h, d, with_bias):
    keys = jax.random.split(jax.random.PRNGKey(s + d), 5)
    q, k, v, w = (
        jax.random.normal(x, (b, s, h, d), jnp.float32) for x in keys[:4]
    )
    bias = None
    if with_bias:
        # position 0 stays visible: a causal row must keep one key
        bias = jnp.where(
            jax.random.uniform(keys[4], (b, s)) < 0.15, -1e30, 0.0
        ).at[:, 0].set(0.0)
    return q, k, v, w, bias


SHAPES = [
    # (S, H, D, block_q, block_k): tiles
    (64, 2, 128, 64, 64),  # one tile: the fused backward, masked
    (64, 2, 64, 64, 64),
    (64, 3, 64, 64, 64),  # one tile, odd head count: the whole-width block
    (128, 2, 128, 64, 32),  # 2 x 4 tiles, two key tiles to a query tile
    (128, 2, 64, 32, 64),  # 4 x 2 tiles, two query tiles to a key tile
    (96, 1, 64, 32, 32),  # 3 x 3, one head: the whole-width block
]


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("s,h,d,bq,bk", SHAPES)
def test_causal_forward_matches_dense(s, h, d, bq, bk, with_bias):
    q, k, v, _w, bias = _inputs(1, s, h, d, with_bias)
    out = flash_attention(q, k, v, bias, block_q=bq, block_k=bk, causal=True)
    np.testing.assert_allclose(
        out, _dense(q, k, v, bias, True), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("s,h,d,bq,bk", SHAPES)
def test_causal_gradients_match_dense(s, h, d, bq, bk, with_bias):
    q, k, v, w, bias = _inputs(2, s, h, d, with_bias)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, bias, block_q=bq, block_k=bk, causal=True
        ) * w)

    def dense_loss(q, k, v):
        return jnp.sum(_dense(q, k, v, bias, True) * w)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


def test_causal_is_a_mode_with_kernel_names_of_its_own():
    """``causal`` changes the kernels' names in a lowering (a device trace
    tells them apart) and nothing about the non-causal call: its lowering
    names no causal kernel, and a future key cannot reach a past query."""
    q, k, v, _w, _bias = _inputs(1, 64, 2, 64, False)

    def traced(causal):  # the pallas_call equations carry the names
        return str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, block_q=32, block_k=32, causal=causal, interpret=False
        ))))(q))

    plain, causal = traced(False), traced(True)
    for kernel in ("fwd", "bwd_tiled"):
        assert f"flash_causal_{kernel}" in causal
        assert f"flash_causal_{kernel}" not in plain
        assert f"flash_{kernel}" in plain
    # changing the LAST key and value moves no earlier output
    out = flash_attention(q, k, v, block_q=32, block_k=32, causal=True)
    k2, v2 = k.at[:, -1].add(3.0), v.at[:, -1].add(3.0)
    out2 = flash_attention(q, k2, v2, block_q=32, block_k=32, causal=True)
    np.testing.assert_array_equal(out[:, :-1], out2[:, :-1])
    assert not np.allclose(out[:, -1], out2[:, -1])


def test_causal_tile_bookkeeping():
    """Which tiles a causal grid visits, by hand: with 4 query tiles of 32
    and 2 key tiles of 64, query tile j needs key tiles 0..(32j+31)//64 =
    0, 0, 1, 1; a step of the sweep past a query tile's last key tile names
    that tile again (the index maps' answer, and the rows of dk / dv the
    one-sweep backward would add to: the step runs no body)."""
    from dedloc_tpu.ops.flash_attention import _k_tile, _last_k_tile, _Mask

    assert [_last_k_tile(j, 32, 64) for j in range(4)] == [0, 0, 1, 1]
    assert [[int(_k_tile(_Mask(True), j, step, 32, 64)) for step in range(2)]
            for j in range(4)] == [[0, 0], [0, 0], [0, 1], [0, 1]]
    assert [_last_k_tile(j, 64, 32) for j in range(2)] == [1, 3]
    assert [int(_k_tile(_Mask(True), 0, step, 64, 32))
            for step in range(4)] == [0, 1, 1, 1]
    # equal tiles: the diagonal
    assert [_last_k_tile(j, 512, 512) for j in range(8)] == list(range(8))
