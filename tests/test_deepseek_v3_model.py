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

import decoder_cases as cases
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


def _tiny(**overrides):
    return DeepseekV3Config.tiny(attention_block_size=32, **overrides)


DEEPSEEK = cases.Family(
    tiny=_tiny, module=DeepseekV3ForCausalLM, loss=deepseek_v3_loss,
    reference=reference, reference_kwargs=_reference_kwargs,
    loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL, comparable=cases.without_bias,
)


@pytest.mark.parametrize(
    "overrides", [dict(), dict(attention_impl="flash"),
                  dict(expert_shard=(1, 4))],
    ids=["dense", "flash", "share_1_of_4"],
)
def test_model_matches_reference(overrides):
    _cfg, _metrics, grads, ref, ref_grads = (
        cases.check_model_matches_reference(DEEPSEEK, **overrides)
    )
    # the bias leaf carries the load statistic, not a gradient — exactly
    # what the reference counts from the same choices
    np.testing.assert_allclose(
        grads["layers"]["block"]["mlp"][BIAS], ref["load_excess"], atol=1e-7
    )
    assert float(jnp.max(jnp.abs(ref_grads["layers"]["block"]["mlp"][BIAS]))) == 0


@pytest.mark.parametrize(
    "changes", [dict(bias_in_choice=False), dict(scale=1.0)],
    ids=["no_bias_in_choice", "no_scaling_factor"],
)
def test_a_different_function_fails(changes):
    metrics, ref = cases.check_a_different_function_fails(
        DEEPSEEK, changes, given_choices=False
    )
    if "bias_in_choice" in changes:
        cases.check_the_choices_differ(metrics, ref)


def test_reference_routed_by_given_choices():
    cases.check_reference_routed_by_given_choices(DEEPSEEK)


def test_the_shares_add_up_to_the_uncut_layer():
    """One expert layer's FFN: the routed parts that the 16 shares compute
    (each told its share, holding 1 of the 16 experts), plus what every chip
    computes alike — the shared experts — counted once, are the uncut
    reference's layer output."""
    cfg, _model, params, _batch = cases.case(DEEPSEEK)
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
    params = cases.case(DEEPSEEK).params
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
