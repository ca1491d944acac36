"""``kanana2_tiny`` through the model against the plain reference
(``benchmark/reference/deepseek_v3.py``: float32, matmul precision
'highest', dense attention, a loop over experts, whole logits) on seeded
random weights: loss, every gradient leaf, the choices exactly, the load
statistic on the bias leaf; with dense and with the two-width flash kernels;
a reference without the bias in the choice, or without the 2.448, failing;
and THE SHARE TEST: the 16 shares' routed parts plus the shared experts
counted once add up to the uncut reference's layer output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v3 as reference
from dedloc_tpu.models.decoder import BIAS, RoutedFFN, sign_step_mask
from dedloc_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    deepseek_v3_loss,
    deepseek_v3_train_tflops_per_sample,
    deepseek_v3_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 2e-6, 2e-4


def _setup(impl="dense", **overrides):
    cfg = DeepseekV3Config.tiny(
        dtype=jnp.float32, attention_impl=impl, attention_block_size=32,
        **overrides,
    )
    model = DeepseekV3ForCausalLM(cfg)
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65)
    ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(rows[:, :-1]),
             "labels": jnp.asarray(rows[:, 1:])}
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    # away from the initialiser's symmetry: norms off 1, the bias off 0 by
    # more than neighbouring scores differ
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)
    ])
    return cfg, model, params, batch


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, rank=cfg.kv_lora_rank,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


def _model_grads(model, params, batch):
    return jax.value_and_grad(
        lambda p: deepseek_v3_loss(model, p, batch), has_aux=True
    )(params)


def _reference_grads(cfg, params, batch, **changes):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: (lambda out: (out["loss"], out))(
                reference.forward(p, batch, **_reference_kwargs(cfg, **changes))
            ), has_aux=True,
        )(params)


def _without_bias(tree):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if path[-1].key == BIAS else x, tree
    )


def _worst_leaf(got, want):
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        norm = float(jnp.linalg.norm(b))
        if norm > 0:
            worst = max(worst, float(jnp.linalg.norm(a - b)) / norm)
    return worst


@pytest.mark.parametrize(
    "impl,shard", [("dense", (0, 1)), ("flash", (0, 1)), ("dense", (1, 4))],
    ids=["dense", "flash", "share_1_of_4"],
)
def test_model_matches_reference(impl, shard):
    cfg, model, params, batch = _setup(impl, expert_shard=shard)
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(cfg, params, batch)
    # float32 on both sides: the choices agree exactly, nothing is forced
    np.testing.assert_array_equal(metrics["moe.choice"], ref["choice"])
    assert abs(float(loss) - float(ref_loss)) <= LOSS_TOL * float(ref_loss)
    assert _worst_leaf(
        _without_bias(grads), _without_bias(ref_grads)
    ) <= LEAF_TOL
    # the bias leaf carries the load statistic, not a gradient — exactly
    # what the reference counts from the same choices
    np.testing.assert_allclose(
        grads["layers"]["block"]["mlp"][BIAS], ref["load_excess"], atol=1e-7
    )
    assert float(jnp.max(jnp.abs(ref_grads["layers"]["block"]["mlp"][BIAS]))) == 0
    assert float(metrics["moe.dropped_slots"]) == 0.0
    assert abs(
        float(metrics["moe.local_slot_share"]) - 1.0 / shard[1]
    ) < (0.0 if shard[1] == 1 else 0.15) + 1e-6


@pytest.mark.parametrize(
    "changes", [dict(bias_in_choice=False), dict(scale=1.0)],
    ids=["no_bias_in_choice", "no_scaling_factor"],
)
def test_a_different_function_fails(changes):
    cfg, model, params, batch = _setup()
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(cfg, params, batch, **changes)
    off = _worst_leaf(_without_bias(grads), _without_bias(ref_grads))
    assert off > 100 * LEAF_TOL, off
    if "bias_in_choice" in changes:
        assert np.mean(
            np.asarray(metrics["moe.choice"]) != np.asarray(ref["choice"])
        ) > 0.05


def test_reference_routed_by_given_choices():
    """Routed by the program's choices the reference reproduces its own
    result (the chip check routes it so)."""
    cfg, _model, params, batch = _setup()
    (loss, own), _ = _reference_grads(cfg, params, batch)
    (again, _), _ = _reference_grads(
        cfg, params, batch, choices=own["choice"]
    )
    assert float(loss) == pytest.approx(float(again), rel=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """One expert layer's FFN: the routed parts that the 16 shares compute
    (each told its share, holding 1 of the 16 experts), plus what every chip
    computes alike — the shared experts — counted once, are the uncut
    reference's layer output."""
    cfg, _model, params, _batch = _setup()
    layer = jax.tree.map(lambda x: x[0], params["layers"]["block"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), layer, held=(0, 16),
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        )
    total, shared, local = 0.0, None, 0.0
    for index in range(16):
        share = DeepseekV3Config.tiny(
            dtype=jnp.float32, expert_shard=(index, 16)
        )
        first, held = share.held_experts
        mine = dict(layer, **{
            name: layer[name][first:first + held]
            for name in ("experts_gate", "experts_up", "experts_down")
        })
        y, routing = RoutedFFN(
            share, shared_width=(
                share.n_shared_experts * share.moe_intermediate_size
            ),
        ).apply({"params": mine}, x)
        with jax.default_matmul_precision("highest"):
            shared = reference._swiglu(x, layer["shared_experts"])
        total = total + (y - shared)
        local += float(routing["local_slot_share"])
        np.testing.assert_array_equal(routing["choice"], whole["choice"])
        assert float(routing["dropped_slots"]) == 0.0
    assert local == pytest.approx(1.0, abs=1e-6)
    want = (whole["routed"] + whole["shared"]).reshape(x.shape)
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=2e-5)
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(y - want))) > 1e-3


def test_masks_and_flops():
    cfg, _model, params, _batch = _setup()
    decay = deepseek_v3_weight_decay_mask(params)
    signed = sign_step_mask(params)
    mlp = "mlp"
    assert signed["layers"]["block"][mlp][BIAS] is True
    assert decay["layers"]["block"][mlp][BIAS] is False
    assert decay["norm"]["weight"] is False and decay["lm_head"] is True
    assert sum(jax.tree.leaves(signed)) == 1
    # the cell's cut: 425 M parameters, and routed work counted for the held
    # experts only
    cut = dict(num_hidden_layers=5, vocab_size=16032)
    held = DeepseekV3Config(expert_shard=(0, 16), **cut)
    whole = DeepseekV3Config(**cut)
    routed = 3 * 4096 * 4 * 6 * 2 * 3 * 2048 * 768 / 1e12
    assert deepseek_v3_train_tflops_per_sample(whole, 4096) - (
        deepseek_v3_train_tflops_per_sample(held, 4096)
    ) == pytest.approx(routed * 15 / 16, rel=1e-9)
    shapes = jax.eval_shape(
        lambda: DeepseekV3ForCausalLM(held).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 424_961_024
