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


def _setup(seq=64, **overrides):
    cfg = SmallThinkerConfig.tiny(dtype=jnp.float32, **overrides)
    model = SmallThinkerForCausalLM(cfg)
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, seq + 1)
    ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(rows[:, :-1]),
             "labels": jnp.asarray(rows[:, 1:])}
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    # away from the initialiser's symmetry: norms off 1, every matrix of
    # the size at which a different function shows
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
        top_k=cfg.num_experts_per_tok, window=cfg.sliding_window_size,
        rope_layout=cfg.rope_layout[:cfg.num_hidden_layers],
        window_layout=cfg.sliding_window_layout[:cfg.num_hidden_layers],
        held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


def _model_grads(model, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: smallthinker_loss(model, p, batch), has_aux=True
    ))(params)


def _reference_grads(cfg, params, batch, choices=None, **changes):
    def loss(p, choices):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(
                p, batch, choices=choices, **_reference_kwargs(cfg, **changes)
            )
        return out["loss"], out

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params, choices)


def _worst_leaf(got, want):
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        norm = float(jnp.linalg.norm(b))
        if norm > 0:
            worst = max(worst, float(jnp.linalg.norm(a - b)) / norm)
    return worst


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=6)],
    ids=["whole", "share_1_of_4", "cut_to_6_layers"],
)
def test_model_matches_reference(overrides):
    cfg, model, params, batch = _setup(**overrides)
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(cfg, params, batch)
    # float32 on both sides: the choices agree exactly, nothing is forced
    np.testing.assert_array_equal(metrics["moe.choice"], ref["choice"])
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_TOL * float(ref_loss)
    assert _worst_leaf(grads, ref_grads) <= LEAF_TOL
    assert float(metrics["moe.dropped_slots"]) == 0.0
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0  # none handed
    assert metrics["moe.load_max_over_mean"].shape == (cfg.num_hidden_layers,)
    assert float(metrics["attn.band_tile_share"]) == 1.0  # one tile of 64
    shards = cfg.expert_shard[1]
    assert abs(
        float(metrics["moe.local_slot_share"]) - 1.0 / shards
    ) < (0.0 if shards == 1 else 0.15) + 1e-6


@pytest.mark.parametrize(
    "changes", [dict(rope_on_global=True), dict(router_after_attention=True),
                dict(activation="silu"), dict(band=False)],
    ids=["rope_on_the_global_layer", "router_fed_after_attention",
         "silu_for_relu", "band_off"],
)
def test_a_different_function_fails(changes):
    cfg, model, params, batch = _setup()
    (_loss, metrics), grads = _model_grads(model, params, batch)
    kwargs = dict(changes)
    if "router_after_attention" not in changes:
        # the same routing, so that what differs is the function alone
        kwargs["choices"] = metrics["moe.choice"]
    (_ref_loss, ref), ref_grads = _reference_grads(
        cfg, params, batch, **kwargs
    )
    off = _worst_leaf(grads, ref_grads)
    assert off > 100 * LEAF_TOL, off
    if "router_after_attention" in changes:
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


def test_the_band_kernels_inside_the_model():
    """``attention_impl="flash"``: the grouped kernels (a group of seven
    wants heads of 128: 7 / 1 x 128 here), causal for the global layers and
    banded for the others, in interpreter mode, against the reference."""
    cfg, model, params, batch = _setup(
        seq=64, head_dim=128, num_hidden_layers=4, attention_impl="flash",
        attention_block_size=16, sliding_window_size=24,
    )
    (loss, metrics), grads = _model_grads(model, params, batch)
    (ref_loss, ref), ref_grads = _reference_grads(
        cfg, params, batch, choices=metrics["moe.choice"]
    )
    assert abs(float(loss) - float(ref_loss)) <= LOSS_TOL * float(ref_loss)
    assert _worst_leaf(grads, ref_grads) <= LEAF_TOL
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
    cfg, _model, params, _batch = _setup()
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
    total, local = 0.0, 0.0
    for index in range(8):
        share = SmallThinkerConfig.tiny(
            dtype=jnp.float32, expert_shard=(index, 8)
        )
        first, held = share.held_experts
        mine = dict(layer, **{
            name: layer[name][first:first + held]
            for name in reference.EXPERTS
        })
        y, routing = RoutedGLU(share).apply({"params": mine}, x, n)
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
