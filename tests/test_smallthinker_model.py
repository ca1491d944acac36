"""``smallthinker_tiny`` through the model against the plain reference
(``benchmark/reference/smallthinker.py``: float32, matmul precision
'highest', dense attention with k / v repeated per group and an explicit
[S, S] mask, a loop over experts, whole logits) on seeded random weights:
loss, every gradient leaf, the router's logits, the choices exactly; a
reference with RoPE on the global layers, with the router fed AFTER
attention, with SiLU for ReLU or with the band off failing; the same model
through the band kernels (interpreter mode); the depth rule; and THE SHARE
TEST: the 8 shares' routed parts add up to the uncut reference's layer
output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import smallthinker as reference
from dedloc_tpu.models.decoder import RoutedGLU
from dedloc_tpu.models.smallthinker import (
    BAND_ROPE,
    GLOBAL_NOPE,
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
    band_tile_share,
    smallthinker_layer_flops_per_token,
    smallthinker_loss,
    smallthinker_train_tflops_per_sample,
    smallthinker_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 1e-5, 1e-4


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, window=cfg.sliding_window_size,
        rope_layout=cfg.rope_layout[:cfg.num_hidden_layers],
        window_layout=cfg.sliding_window_layout[:cfg.num_hidden_layers],
        held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


SMALLTHINKER = cases.Family(
    tiny=SmallThinkerConfig.tiny, module=SmallThinkerForCausalLM,
    loss=smallthinker_loss, reference=reference,
    reference_kwargs=_reference_kwargs, loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL,
)


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=6)],
    ids=["whole", "share_1_of_4", "cut_to_6_layers"],
)
def test_model_matches_reference(overrides):
    cfg, metrics, _grads, ref, _ref_grads = (
        cases.check_model_matches_reference(SMALLTHINKER, **overrides)
    )
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0  # none handed
    assert metrics["moe.load_max_over_mean"].shape == (cfg.num_hidden_layers,)
    assert float(metrics["attn.band_tile_share"]) == 1.0  # one tile of 64


@pytest.mark.parametrize(
    "changes", [dict(rope_on_global=True), dict(router_after_attention=True),
                dict(activation="silu"), dict(band=False)],
    ids=["rope_on_the_global_layer", "router_fed_after_attention",
         "silu_for_relu", "band_off"],
)
def test_a_different_function_fails(changes):
    # the same routing, so that what differs is the function alone — but
    # for the router's own input, where the CHOICES are what differs
    rerouted = "router_after_attention" in changes
    metrics, ref = cases.check_a_different_function_fails(
        SMALLTHINKER, changes, given_choices=not rerouted
    )
    if rerouted:
        cases.check_the_choices_differ(metrics, ref)


def test_reference_routed_by_given_choices():
    cases.check_reference_routed_by_given_choices(SMALLTHINKER)


def test_the_band_kernels_inside_the_model():
    """``attention_impl="flash"``: the grouped kernels (a group of seven
    wants heads of 128: 7 / 1 x 128 here), causal for the global layers and
    banded for the others, in interpreter mode, against the reference."""
    metrics = cases.check_the_model_under_overrides(
        SMALLTHINKER, head_dim=128, num_hidden_layers=4,
        attention_impl="flash", attention_block_size=16,
        sliding_window_size=24,
    )
    # 4 query tiles of 16 under a band of 24: 1 + 2 + 3 + 3 of 1 + 2 + 3 + 4
    assert float(metrics["attn.band_tile_share"]) == pytest.approx(0.9)
    assert band_tile_share(SmallThinkerConfig(), 16384) == 252 / 528


def test_the_depth_rule():
    """The first layers of the published layouts; whole periods scanned,
    what is left over unrolled."""
    whole = SmallThinkerConfig.smallthinker_21b_a3b().layer_plan
    assert len(whole) == 52
    assert [i for i, (rope, band) in enumerate(whole) if not rope] == list(
        range(0, 52, 4)
    )
    assert all(rope == band for rope, band in whole)
    cut = SmallThinkerConfig.smallthinker_21b_a3b(num_hidden_layers=4)
    assert cut.layer_plan == [(False, False)] + [(True, True)] * 3
    with pytest.raises(ValueError, match="layouts"):
        SmallThinkerConfig.smallthinker_21b_a3b(num_hidden_layers=53)
    shapes = jax.eval_shape(
        lambda: SmallThinkerForCausalLM(SmallThinkerConfig.tiny(
            num_hidden_layers=6
        )).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    )
    assert sorted(shapes["layers"]) == [f"layer_{i}" for i in range(4)]
    assert shapes["layers"]["layer_1"]["self_attn"]["q_proj"][
        "kernel"
    ].shape == (1, 32, 56)
    assert "tail_layer_1" in shapes and "tail_layer_2" not in shapes


def test_the_shares_add_up_to_the_uncut_layer():
    """One layer's FFN: the routed parts that the 8 shares compute (each
    told its share, holding 1 of the 8 experts) are the uncut reference's
    layer output — there is no shared expert, so nothing is computed alike
    on every chip but the router, whose choices agree."""
    cfg, _model, params, _batch = cases.case(SMALLTHINKER)
    layer = jax.tree.map(
        lambda x: x[0], params["layers"]["layer_1"]["block_sparse_moe"]
    )
    x, n = jax.random.normal(
        jax.random.PRNGKey(3), (2, 2, 64, cfg.hidden_size)
    )
    with jax.default_matmul_precision("highest"):
        whole = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), n.reshape(-1, cfg.hidden_size),
            layer, held=(0, 8), top_k=cfg.num_experts_per_tok,
        )
    cases.check_the_routed_shares_add_up(
        SMALLTHINKER, layer, RoutedGLU, (x, n), whole, reference.EXPERTS
    )


def test_masks_and_flops():
    params = cases.case(SMALLTHINKER).params
    decay = smallthinker_weight_decay_mask(params)
    assert decay["norm"]["weight"] is False and decay["lm_head"] is True
    assert decay["layers"]["layer_0"]["input_layernorm"]["weight"] is False
    assert decay["layers"]["layer_0"]["block_sparse_moe"]["router"] is True
    # the cell's cut: 370.5 M parameters; a band layer counted at its band
    cut = dict(num_hidden_layers=4, vocab_size=18992)
    held = SmallThinkerConfig(expert_shard=(0, 8), **cut)
    shapes = jax.eval_shape(
        lambda: SmallThinkerForCausalLM(held).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 370_547_200
    part = smallthinker_layer_flops_per_token(held, 16384)
    pair = 2 * 2 * 28 * 128
    assert part[GLOBAL_NOPE] - part[BAND_ROPE] == pytest.approx(
        pair * (134_225_920 - 58_722_304) / 16384, rel=1e-9
    )
    assert part["head"] == 2 * 2560 * 18992
    assert smallthinker_train_tflops_per_sample(
        held, 16384
    ) == pytest.approx(28.18, abs=0.01)
    # at one window's length a band layer is a global one
    short = smallthinker_layer_flops_per_token(held, 4096)
    assert short[GLOBAL_NOPE] == short[BAND_ROPE]
    routed = 3 * 16384 * 4 * 6 * 2 * 3 * 2560 * 768 / 1e12
    assert smallthinker_train_tflops_per_sample(
        SmallThinkerConfig(**cut), 16384
    ) - smallthinker_train_tflops_per_sample(held, 16384) == pytest.approx(
        routed * 7 / 8, rel=1e-9
    )
