"""Long-context attention tests: blockwise and ring match dense attention,
gradients flow, and masking works."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dedloc_tpu.parallel.ring_attention import (
    blockwise_attention,
    dense_attention,
    ring_attention,
)


def _qkv(rng, b=2, s=64, h=2, d=8, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return mk(), mk(), mk()


def test_blockwise_matches_dense(rng):
    q, k, v = _qkv(rng)
    out = blockwise_attention(q, k, v, block_size=16)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blockwise_with_mask_matches_dense(rng):
    q, k, v = _qkv(rng)
    mask = jnp.asarray(rng.random((2, 64)) > 0.3)
    bias = jnp.where(mask, 0.0, -1e9).astype(jnp.float32)
    out = blockwise_attention(q, k, v, bias, block_size=16)
    ref = dense_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blockwise_gradients_match_dense(rng):
    q, k, v = _qkv(rng, s=32)

    def loss_block(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, block_size=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    g_block = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gb, gd in zip(g_block, g_dense):
        np.testing.assert_allclose(np.asarray(gb), np.asarray(gd), atol=1e-4)


@pytest.fixture
def seq_mesh():
    return Mesh(np.array(jax.devices()[:8]), ("seq",))


def test_ring_matches_dense(rng, seq_mesh):
    q, k, v = _qkv(rng, s=64)
    shard = NamedSharding(seq_mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, mesh=seq_mesh)
    )(qs, ks, vs)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_with_mask_matches_dense(rng, seq_mesh):
    q, k, v = _qkv(rng, s=64)
    mask = jnp.asarray(rng.random((2, 64)) > 0.3)
    bias = jnp.where(mask, 0.0, -1e9).astype(jnp.float32)
    out = jax.jit(
        lambda a, b, c, bi: ring_attention(a, b, c, bi, mesh=seq_mesh)
    )(q, k, v, bias)
    ref = dense_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_gradients_flow(rng, seq_mesh):
    q, k, v = _qkv(rng, s=32)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=seq_mesh) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    g_ring = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), atol=1e-4)


def test_blockwise_bf16_stable(rng):
    q, k, v = _qkv(rng, dtype=jnp.bfloat16)
    out = blockwise_attention(q, k, v, block_size=16)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_albert_ring_impl_matches_dense_model_level():
    """attention_impl='ring' is a drop-in workload option: same params, same
    logits as the dense model (sequence sharded over a 2-device seq axis)."""
    import numpy as np
    from jax.sharding import Mesh

    from dedloc_tpu.models.albert import AlbertConfig, AlbertForPreTraining

    devices = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh = Mesh(devices, ("data", "seq"))
    dense_cfg = AlbertConfig.tiny(attention_impl="dense")
    ring_cfg = AlbertConfig.tiny(attention_impl="ring", mesh=mesh)
    dense_model = AlbertForPreTraining(dense_cfg)
    ring_model = AlbertForPreTraining(ring_cfg)

    B, S = 2, dense_cfg.max_position_embeddings
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, dense_cfg.vocab_size, (B, S)),
        jnp.int32,
    )
    params = dense_model.init(jax.random.PRNGKey(0), ids)["params"]
    mlm_d, sop_d = dense_model.apply({"params": params}, ids)
    mlm_r, sop_r = ring_model.apply({"params": params}, ids)
    np.testing.assert_allclose(
        np.asarray(mlm_d, np.float32), np.asarray(mlm_r, np.float32),
        atol=5e-2, rtol=5e-2,
    )
    np.testing.assert_allclose(
        np.asarray(sop_d, np.float32), np.asarray(sop_r, np.float32),
        atol=5e-2, rtol=5e-2,
    )
