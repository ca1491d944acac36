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


def _setup(**overrides):
    cfg = Lfm2MoeConfig.tiny(dtype=jnp.float32, **overrides)
    model = Lfm2MoeForCausalLM(cfg)
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65)
    ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(rows[:, :-1]),
             "labels": jnp.asarray(rows[:, 1:])}
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    # away from the initialiser's symmetry: norms off 1, the bias off 0 by
    # more than neighbouring scores differ, taps of the size of a weight
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)
    ])
    return cfg, model, params, batch


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        route_eps=cfg.route_eps, held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


def _model_grads(model, params, batch):
    return jax.value_and_grad(
        lambda p: lfm2_moe_loss(model, p, batch), has_aux=True
    )(params)


def _reference_grads(cfg, params, batch, **changes):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: (lambda out: (out["loss"], out))(
                reference.forward(p, batch, **_reference_kwargs(cfg, **changes))
            ), has_aux=True,
        )(params)


def _bias_apart(tree):
    taken = []

    def split(path, x):
        if path[-1].key != BIAS:
            return x
        taken.append(x)
        return jnp.zeros_like(x)

    return jax.tree_util.tree_map_with_path(split, tree), taken


def _worst_leaf(got, want):
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        norm = float(jnp.linalg.norm(b))
        if norm > 0:
            worst = max(worst, float(jnp.linalg.norm(a - b)) / norm)
    return worst


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=5)],
    ids=["whole", "share_1_of_4", "cut_to_5_layers"],
)
def test_model_matches_reference(overrides):
    cfg, model, params, batch = _setup(**overrides)
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(cfg, params, batch)
    # float32 on both sides: the choices agree exactly, nothing is forced
    np.testing.assert_array_equal(metrics["moe.choice"], ref["choice"])
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_TOL * float(ref_loss)
    grads, load = _bias_apart(grads)
    ref_grads, ref_bias_grads = _bias_apart(ref_grads)
    assert _worst_leaf(grads, ref_grads) <= LEAF_TOL
    # the bias leaves carry the load statistic, not a gradient — exactly
    # what the reference counts from the same choices, layer by layer
    np.testing.assert_allclose(
        np.concatenate([np.asarray(x).reshape(-1, cfg.num_experts)
                        for x in load]),
        ref["load_excess"], atol=1e-7,
    )
    assert all(float(jnp.max(jnp.abs(x))) == 0 for x in ref_bias_grads)
    assert float(metrics["moe.dropped_slots"]) == 0.0
    shards = cfg.expert_shard[1]
    assert abs(
        float(metrics["moe.local_slot_share"]) - 1.0 / shards
    ) < (0.0 if shards == 1 else 0.15) + 1e-6


@pytest.mark.parametrize(
    "changes", [dict(bias_in_choice=False), dict(qk_norm=False),
                dict(gates_swapped=True), dict(causal_conv=False)],
    ids=["no_bias_in_choice", "no_qk_norm", "gates_swapped",
         "non_causal_conv"],
)
def test_a_different_function_fails(changes):
    cfg, model, params, batch = _setup()
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(cfg, params, batch, **changes)
    off = _worst_leaf(_bias_apart(grads)[0], _bias_apart(ref_grads)[0])
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
    cfg, _model, params, _batch = _setup()
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
    total, local = 0.0, 0.0
    for index in range(8):
        share = Lfm2MoeConfig.tiny(dtype=jnp.float32, expert_shard=(index, 8))
        first, held = share.held_experts
        mine = dict(layer, **{
            name: layer[name][first:first + held]
            for name in ("experts_gate", "experts_up", "experts_down")
        })
        y, routing = RoutedFFN(share).apply({"params": mine}, x)
        total = total + y
        local += float(routing["local_slot_share"])
        np.testing.assert_array_equal(routing["choice"], whole["choice"])
        assert float(routing["dropped_slots"]) == 0.0
    assert local == pytest.approx(1.0, abs=1e-6)
    want = whole["routed"].reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(y - want))) > 1e-3


def test_masks_and_flops():
    cfg, _model, params, _batch = _setup()
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
