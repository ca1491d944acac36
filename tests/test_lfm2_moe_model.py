"""``lfm2_tiny`` through the model against the plain reference
(``benchmark/reference/lfm2_moe.py``: float32, matmul precision 'highest', a
shifted-sum convolution, dense attention with k / v repeated per group, a
loop over experts, whole logits) on seeded random weights: loss, every
gradient leaf, the choices exactly, the load statistic on the bias leaves;
a reference WITHOUT the bias in the choice, without the q / k norm, with
the gates swapped or with a non-causal convolution failing; the depth rule;
and THE SHARE TEST: the 8 shares' routed parts add up to the uncut
reference's layer output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import lfm2_moe as reference
from dedloc_tpu.models.decoder import BIAS, RoutedFFN, sign_step_mask
from dedloc_tpu.models.lfm2_moe import (
    ATTENTION,
    CONV,
    Lfm2MoeConfig,
    Lfm2MoeForCausalLM,
    lfm2_moe_loss,
    lfm2_moe_train_tflops_per_sample,
    lfm2_moe_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 1e-5, 1e-4


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        route_eps=cfg.route_eps, held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


LFM2 = cases.Family(
    tiny=Lfm2MoeConfig.tiny, module=Lfm2MoeForCausalLM, loss=lfm2_moe_loss,
    reference=reference, reference_kwargs=_reference_kwargs,
    loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL, comparable=cases.without_bias,
)


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=5)],
    ids=["whole", "share_1_of_4", "cut_to_5_layers"],
)
def test_model_matches_reference(overrides):
    cfg, metrics, grads, ref, ref_grads = (
        cases.check_model_matches_reference(LFM2, **overrides)
    )
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    # the bias leaves carry the load statistic, not a gradient — exactly
    # what the reference counts from the same choices, layer by layer
    np.testing.assert_allclose(
        np.concatenate([x.reshape(-1, cfg.num_experts)
                        for x in cases.bias_leaves(grads)]),
        ref["load_excess"], atol=1e-7,
    )
    assert all(np.abs(x).max() == 0 for x in cases.bias_leaves(ref_grads))


@pytest.mark.parametrize(
    "changes", [dict(bias_in_choice=False), dict(qk_norm=False),
                dict(gates_swapped=True), dict(causal_conv=False)],
    ids=["no_bias_in_choice", "no_qk_norm", "gates_swapped",
         "non_causal_conv"],
)
def test_a_different_function_fails(changes):
    metrics, ref = cases.check_a_different_function_fails(
        LFM2, changes, given_choices=False
    )
    if "bias_in_choice" in changes:
        cases.check_the_choices_differ(metrics, ref)


def test_reference_routed_by_given_choices():
    cases.check_reference_routed_by_given_choices(LFM2)


def test_the_depth_rule():
    """The published stack whole; cut, ONE leading dense layer and the
    published pattern from the first expert layer on."""
    whole = Lfm2MoeConfig.lfm2_24b_a2b().layer_plan
    assert len(whole) == 40
    assert [i for i, kind, _s in whole if kind == ATTENTION] == list(
        range(2, 40, 4)
    )
    assert [sparse for _i, _k, sparse in whole] == [False] * 2 + [True] * 38
    cut = Lfm2MoeConfig.lfm2_24b_a2b(num_hidden_layers=5).layer_plan
    assert cut == [(0, CONV, False), (2, ATTENTION, True), (3, CONV, True),
                   (4, CONV, True), (5, CONV, True)]
    with pytest.raises(ValueError, match="pattern"):
        Lfm2MoeConfig.lfm2_24b_a2b(num_hidden_layers=41)
    # whole periods are scanned, what is left over is unrolled: 38 expert
    # layers = 9 periods of (attention, conv, conv, conv) + 2
    shapes = jax.eval_shape(
        lambda: Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
            num_hidden_layers=40, layer_types=Lfm2MoeConfig().layer_types,
        )).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    )
    assert sorted(shapes["layers"]) == [f"layer_{i}" for i in range(4)]
    assert shapes["layers"]["layer_1"]["conv"]["conv"].shape == (9, 32, 3)
    assert "self_attn" in shapes["tail_layer_0"] and "conv" in shapes[
        "tail_layer_1"
    ] and "tail_layer_2" not in shapes and "dense_layer_1" in shapes


def test_the_shares_add_up_to_the_uncut_layer():
    """One expert layer's FFN: the routed parts that the 8 shares compute
    (each told its share, holding 2 of the 16 experts) are the uncut
    reference's layer output — this model has no shared expert, so nothing
    is computed alike on every chip but the router, whose choices agree."""
    cfg, _model, params, _batch = cases.case(LFM2)
    layer = jax.tree.map(
        lambda x: x[0], params["layers"]["layer_1"]["feed_forward"]
    )
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), layer, held=(0, 16),
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            route_eps=cfg.route_eps,
        )
    cases.check_the_routed_shares_add_up(
        LFM2, layer, RoutedFFN, (x,), whole,
        ("experts_gate", "experts_up", "experts_down"),
    )


def test_masks_and_flops():
    params = cases.case(LFM2).params
    decay = lfm2_moe_weight_decay_mask(params)
    signed = sign_step_mask(params)
    ffn = "feed_forward"
    assert signed["layers"]["layer_0"][ffn][BIAS] is True
    assert signed["tail_layer_0"][ffn][BIAS] is True
    assert decay["layers"]["layer_0"][ffn][BIAS] is False
    assert decay["norm"]["weight"] is False and decay["embed_tokens"] is True
    assert decay["dense_layer_0"]["conv"]["conv"] is True
    assert decay["layers"]["layer_0"]["self_attn"]["q_layernorm"][
        "weight"
    ] is False
    assert sum(jax.tree.leaves(signed)) == 5  # one leaf a position + tail
    # the cell's cut: 469 M parameters, and routed work counted for the held
    # experts only
    cut = dict(num_hidden_layers=5, vocab_size=8192)
    held = Lfm2MoeConfig(expert_shard=(0, 8), **cut)
    whole = Lfm2MoeConfig(**cut)
    routed = 3 * 4096 * 4 * 4 * 2 * 3 * 2048 * 1536 / 1e12
    assert lfm2_moe_train_tflops_per_sample(whole, 4096) - (
        lfm2_moe_train_tflops_per_sample(held, 4096)
    ) == pytest.approx(routed * 7 / 8, rel=1e-9)
    shapes = jax.eval_shape(
        lambda: Lfm2MoeForCausalLM(held).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 469_285_248
