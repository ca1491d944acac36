"""Fused add+LayerNorm Pallas kernel vs the jnp reference (interpret mode
on CPU — identical kernel code to the compiled TPU path), plus model-level
equivalence of the fused_ln recipe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.fused_ln import ln_residual, ln_residual_reference


def _inputs(rng, n=64, h=256, dtype=jnp.float32):
    x = jnp.asarray(rng.standard_normal((n, h)), dtype)
    r = jnp.asarray(rng.standard_normal((n, h)), dtype)
    gamma = jnp.asarray(1.0 + 0.1 * rng.standard_normal(h), jnp.float32)
    beta = jnp.asarray(0.1 * rng.standard_normal(h), jnp.float32)
    return x, r, gamma, beta


def test_forward_matches_reference(rng):
    x, r, gamma, beta = _inputs(rng)
    out = ln_residual(x, r, gamma, beta, block_n=16)
    ref = ln_residual_reference(x, r, gamma, beta)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_leading_dims_and_bf16(rng):
    x, r, gamma, beta = _inputs(rng, n=48, h=128)
    x3 = x.reshape(4, 12, 128).astype(jnp.bfloat16)
    r3 = r.reshape(4, 12, 128).astype(jnp.bfloat16)
    out = ln_residual(x3, r3, gamma, beta, block_n=16)
    assert out.shape == (4, 12, 128) and out.dtype == jnp.bfloat16
    ref = ln_residual_reference(x3, r3, gamma, beta)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=3e-2
    )


def test_gradients_match_reference(rng):
    x, r, gamma, beta = _inputs(rng, n=32, h=64)
    w = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)

    def loss_fused(x, r, g, b):
        return jnp.sum(ln_residual(x, r, g, b, block_n=8) * w)

    def loss_ref(x, r, g, b):
        return jnp.sum(ln_residual_reference(x, r, g, b) * w)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    gd = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    for a, b, name in zip(gf, gd, ["dx", "dr", "dgamma", "dbeta"]):
        np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4, err_msg=name
        )


def test_residual_branches_get_identical_cotangent(rng):
    x, r, gamma, beta = _inputs(rng, n=16, h=32)

    def loss(x, r):
        return jnp.sum(ln_residual(x, r, gamma, beta, block_n=8) ** 2)

    dx, dr = jax.grad(loss, argnums=(0, 1))(x, r)
    np.testing.assert_allclose(dx, dr, atol=1e-6)


@pytest.mark.parametrize("policy", ["fused_ln", "fused_ln_gelu"])
def test_model_fused_ln_matches_unfused(rng, policy):
    """AlbertForPreTraining with fused_ln=True + a fused_ln* remat policy
    (fused_ln_gelu additionally saves the gelu output, skipping its backward
    replay) produces the same loss and gradients as the unfused path."""
    from dedloc_tpu.models.albert import (
        AlbertConfig,
        AlbertForPreTraining,
        albert_pretraining_loss,
        fused_ln_for_policy,
    )

    ids = jnp.asarray(rng.integers(0, 512, (2, 64)), jnp.int32)
    labels = jnp.where(
        jnp.asarray(rng.random((2, 64)) < 0.15), ids, -100
    )
    sop = jnp.asarray(rng.integers(0, 2, (2,)), jnp.int32)

    def build(remat_policy):
        cfg = AlbertConfig.tiny(
            dtype=jnp.float32,
            attention_impl="flash",
            remat_policy=remat_policy,
            fused_ln=fused_ln_for_policy(remat_policy),
        )
        return cfg, AlbertForPreTraining(cfg)

    cfg0, model0 = build("dots_no_batch_attn")
    params = model0.init(jax.random.PRNGKey(0), ids)["params"]

    def loss_fn(model):
        def f(params):
            mlm, sop_logits = model.apply({"params": params}, ids)
            loss, _ = albert_pretraining_loss(mlm, sop_logits, labels, sop)
            return loss

        return f

    cfg1, model1 = build(policy)
    assert cfg1.fused_ln
    l0, g0 = jax.value_and_grad(loss_fn(model0))(params)
    l1, g1 = jax.value_and_grad(loss_fn(model1))(params)
    np.testing.assert_allclose(l1, l0, atol=1e-5, rtol=1e-5)
    flat0 = jax.tree_util.tree_leaves_with_path(g0)
    flat1 = dict(jax.tree_util.tree_flatten_with_path(g1)[0])
    for path, leaf in flat0:
        np.testing.assert_allclose(
            flat1[path], leaf, atol=5e-4, rtol=5e-3,
            err_msg=jax.tree_util.keystr(path),
        )


def test_param_tree_unchanged_by_fused_ln(rng):
    """AddLayerNorm keeps nn.LayerNorm's parameter tree (scale/bias under
    'layernorm'), so checkpoints from earlier rounds stay loadable."""
    from dedloc_tpu.models.albert import AlbertConfig, AlbertForPreTraining

    ids = jnp.zeros((1, 16), jnp.int32)
    cfg = AlbertConfig.tiny(fused_ln=True)
    params = AlbertForPreTraining(cfg).init(jax.random.PRNGKey(0), ids)[
        "params"
    ]
    block = params["albert"]["encoder"]["layer"]["block"]
    assert set(block["layernorm"]) == {"scale", "bias"}
    assert set(block["attention"]["layernorm"]) == {"scale", "bias"}


def test_under_a_mesh_matches_one_device(rng):
    """On a multi-device mesh the op runs per row shard under shard_map
    with γ/β replicated: y and da must equal the unsharded call, and
    dγ/dβ — per-shard partial sums — must come back summed over the mesh
    (the shard_map transpose's psum)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    x, r, gamma, beta = _inputs(rng, n=64, h=128)
    x3, r3 = x.reshape(4, 16, 128), r.reshape(4, 16, 128)
    w = jnp.asarray(rng.standard_normal(x3.shape), jnp.float32)

    def loss(mesh):
        return lambda x, r, g, b: jnp.sum(
            ln_residual(x, r, g, b, block_n=8, mesh=mesh) * w
        )

    rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    sharded = jax.jit(
        jax.value_and_grad(loss(mesh), argnums=(0, 1, 2, 3)),
        in_shardings=(rows, rows, repl, repl),
    )(x3, r3, gamma, beta)
    local = jax.value_and_grad(loss(None), argnums=(0, 1, 2, 3))(
        x3, r3, gamma, beta
    )
    np.testing.assert_allclose(sharded[0], local[0], rtol=1e-5)
    for a, b, name in zip(sharded[1], local[1],
                          ["dx", "dr", "dgamma", "dbeta"]):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)
