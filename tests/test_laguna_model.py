"""``laguna_tiny`` through the model against the plain reference
(``benchmark/reference/laguna.py``: float32, matmul precision 'highest',
dense attention under an explicit [S, S] mask per kind, a loop over experts,
whole logits) on seeded random weights: loss, every gradient leaf, the
router's scores, the choices exactly — with BOTH attention kinds, both head
counts, a dense and a sparse layer, a band shorter than, equal to and longer
than the tile; a reference under another reading of what the config leaves
open failing; the same model through the flash kernels (interpreter mode);
partial rotary, YaRN's table, the gate's gradient; the depth rule; the
parameter count of the cell's cut; and THE SHARE TEST: the shares' routed
parts and the shared expert counted once add up to the uncut reference's
layer output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import laguna as reference
from benchmark.roles.trainer_laguna_lm import (
    reference_kwargs as role_reference_kwargs,
)
from dedloc_tpu.models.decoder import (
    RoutedFFN,
    apply_rope,
    rope_tables,
    yarn_inv_freq,
)
from dedloc_tpu.models.laguna import (
    DENSE,
    DecoderLayer,
    FULL,
    SLIDING,
    SPARSE,
    LagunaConfig,
    LagunaForCausalLM,
    band_tile_share,
    band_visible_share,
    laguna_layer_flops_per_token,
    laguna_loss,
    laguna_rope_tables,
    laguna_train_tflops_per_sample,
    laguna_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 1e-5, 1e-4


def reference_kwargs(cfg, **changes):
    """``reference.forward``'s arguments from the config's own keys (the
    benchmark role's), with a test's departures."""
    return dict(role_reference_kwargs(cfg), **changes)


LAGUNA = cases.Family(
    tiny=LagunaConfig.tiny, module=LagunaForCausalLM, loss=laguna_loss,
    reference=reference, reference_kwargs=reference_kwargs, seq=32,
    loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL,
)


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=2)],
    ids=["whole", "share_1_of_4", "cut_to_2_layers"],
)
def test_model_matches_reference(overrides):
    cfg, metrics, _grads, ref, _ref_grads = (
        cases.check_model_matches_reference(LAGUNA, **overrides)
    )
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0  # none handed
    sparse = sum(ffn == SPARSE for ffn in cfg.mlp_layer_types[
        :cfg.num_hidden_layers
    ])
    assert metrics["moe.load_max_over_mean"].shape == (sparse,)
    # a mean gate a kind, as the reference's layers read it
    kinds = np.asarray(cfg.layer_types[:cfg.num_hidden_layers])
    for kind in set(kinds):
        want = float(np.mean(np.asarray(ref["gate_mean"])[kinds == kind]))
        got = float(metrics[f"attn.gate_mean.{kind}"])
        assert got == pytest.approx(want, abs=1e-6)
        assert 0.3 < got < 0.7  # a gate at 0 or 1 is a dead mechanism


@pytest.mark.parametrize(
    "changes", [dict(gate="none"), dict(gate="softplus"),
                dict(rotary="last"), dict(yarn=False),
                dict(router="softmax"), dict(band=False)],
    ids=["no_gate", "softplus_gate", "last_lanes_rotated",
         "plain_frequencies", "softmax_router", "window_off"],
)
def test_a_different_function_fails(changes):
    cases.check_a_different_function_fails(LAGUNA, changes)


def test_the_models_own_gradients_are_computed_once():
    """What holds the cases above to a reference's compile each: the
    default case's gradients come from the cache however often asked."""
    assert cases.own(LAGUNA) is cases.own(LAGUNA)
    assert cases.OWN_COMPUTED and set(cases.OWN_COMPUTED.values()) == {1}


def test_all_lanes_rotated_fails():
    """A reference that rotates the WHOLE head of a full layer (partial
    rotary factor 1 where the config says 0.5) is far off."""
    cfg, _model, params, batch = cases.case(LAGUNA)
    (_loss, metrics), grads = cases.own(LAGUNA)
    kwargs = reference_kwargs(cfg)
    kwargs["rope"][FULL]["partial_rotary_factor"] = 1

    def loss(p):
        with jax.default_matmul_precision("highest"):
            return reference.forward(
                p, batch, choices=metrics["moe.choice"], **kwargs
            )["loss"]

    assert cases.worst_leaf(
        grads, jax.jit(jax.grad(loss))(params)
    ) > 100 * LEAF_TOL


def test_the_reference_routed_by_given_choices_and_in_bf16():
    """Routed by the program's choices the reference reproduces its own
    result (the chip check routes it so); in bf16-everything, routed alike,
    it is off by more than the float32 tolerances (the chip check's limits
    are set between the role's reading and this one's). A dense and a
    sparse layer are enough to show both."""
    cfg, _model, params, batch = cases.case(LAGUNA, num_hidden_layers=2)
    (loss, own), grads = cases.reference_own(LAGUNA, num_hidden_layers=2)
    (again, _), _ = cases.reference_grads(
        reference, reference_kwargs(cfg), params, batch, choices=own["choice"]
    )
    assert float(loss) == pytest.approx(float(again), rel=1e-6)
    (low_loss, _), low_grads = cases.reference_grads(
        reference, reference_kwargs(cfg, dtype=jnp.bfloat16), params, batch,
        choices=own["choice"],
    )
    assert abs(float(low_loss) - float(loss)) > LOSS_TOL * float(loss)
    assert cases.worst_leaf(low_grads, grads) > LEAF_TOL


@pytest.mark.parametrize("window", [8, 16, 24], ids=[
    "band_shorter_than_the_tile", "band_equal_to_the_tile",
    "band_longer_than_the_tile",
])
def test_the_flash_kernels_inside_the_model(window):
    """``attention_impl="flash"``: the grouped kernels at heads of 128 — a
    whole group of THREE a program in the full layers (6 / 2), a group of
    four under ``band=`` in the others (8 / 2) — in interpreter mode against
    the reference, at 16 x 16 tiles."""
    metrics = cases.check_the_model_under_overrides(
        LAGUNA, 64, head_dim=128, num_hidden_layers=2,
        attention_impl="flash", attention_block_size=16,
        sliding_window=window,
    )
    # 4 query tiles of 16: 1 + 2 + 2 + 2 tiles at a band of 8 or 16 (the
    # second crossed tile holds ONE visible pair at 16, none of whose pairs
    # at 8 but the tile before the diagonal's last column), 1 + 2 + 3 + 3 at 24
    tiles = {8: 7, 16: 7, 24: 9}[window]
    assert float(metrics["attn.band_tile_share"]) == pytest.approx(tiles / 10)
    pairs = window * (window + 1) // 2 + (64 - window) * window
    assert float(metrics["attn.band_visible_share"]) == pytest.approx(
        pairs / (tiles * 256)
    )


def test_the_cells_tile_shares():
    cfg = LagunaConfig()
    assert band_tile_share(cfg, 8192) == 31 / 136
    assert band_visible_share(cfg, 8192) == 4_063_488 / (31 * 512 * 512)
    assert band_visible_share(cfg, 8192) == pytest.approx(0.50, abs=0.001)
    # SmallThinker's band of 4,096 at 16,384: the number M2(c) compares with
    wide = LagunaConfig(sliding_window=4096)
    assert band_visible_share(wide, 16384) == pytest.approx(0.889, abs=0.001)


def test_partial_rotary_leaves_the_last_lanes_bit_equal():
    cfg = LagunaConfig()
    cos, sin = laguna_rope_tables(cfg, 16)[FULL]
    assert cos.shape == (16, 64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 6, 128))
    y = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    # position 0 turns nothing: the rotated lanes are scaled by the factor
    np.testing.assert_allclose(
        y[:, 0, :, :64], x[:, 0, :, :64] * cfg.full_yarn_attention_factor,
        rtol=1e-6,
    )
    assert float(jnp.max(jnp.abs(y[:, 1:, :, :64] - x[:, 1:, :, :64]))) > 0.1
    # the sliding layers' table covers the head
    assert laguna_rope_tables(cfg, 16)[SLIDING][0].shape == (16, 128)
    # and against the reference's own rotation
    ref = reference._rope(x, *reference.rope_tables(
        16, FULL, reference_kwargs(cfg)["rope"]
    ))
    np.testing.assert_allclose(y, ref, rtol=1e-6, atol=1e-6)


def test_yarn_table_against_a_hand_written_one():
    """Laguna-XS.2's 32 frequencies: c(64) = 7.9 → low 7, c(1) = 18.1 → high
    19: pairs 0..7 keep f_i, pairs 19..31 are f_i / 64, a ramp between; the
    table at three positions, cos and sin x 1.4158883083359672."""
    import math

    theta, dim, factor, original = 500000.0, 64, 64.0, 4096
    inv = yarn_inv_freq(dim, theta, factor, original, 64.0, 1.0)

    def c(beta):
        return dim * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta)
        )

    assert (math.floor(c(64.0)), math.ceil(c(1.0))) == (5, 16)
    want = []
    for i in range(32):
        f = theta ** (-2.0 * i / dim)
        r = min(max((i - 5) / (16 - 5), 0.0), 1.0)
        want.append(f / factor * r + f * (1.0 - r))
    np.testing.assert_allclose(inv, np.asarray(want, np.float32), rtol=1e-6)
    assert inv[0] == 1.0 and inv[5] == np.float32(theta ** (-10 / 64))
    assert inv[16] == np.float32(theta ** (-32 / 64) / 64)
    np.testing.assert_allclose(
        inv, reference.yarn_frequencies(dim, theta, factor, original, 64, 1),
        rtol=1e-6,
    )
    scale = 1.4158883083359672
    cos, sin = rope_tables(8192, dim, theta, inv_freq=jnp.asarray(inv),
                           scale=scale)
    assert cos.shape == (8192, 64)
    for position in (1, 4097, 8191):
        angles = np.float32(position) * inv
        np.testing.assert_allclose(
            cos[position], np.tile(np.cos(angles), 2) * scale, atol=2e-6
        )
        np.testing.assert_allclose(
            sin[position], np.tile(np.sin(angles), 2) * scale, atol=2e-6
        )
    # the plain call is what it was: no scale, theta's own frequencies
    plain_cos, _ = rope_tables(4, 8, 10000.0)
    np.testing.assert_allclose(
        plain_cos[1, :4], np.cos(10000.0 ** (-np.arange(4) / 4)), rtol=1e-6
    )


def test_the_gates_gradient():
    """d loss / d W_g of every layer against the reference's (inside
    ``test_model_matches_reference``: every leaf), and here the gate alone:
    one dense layer (no router: a smooth function) against a central
    difference along one direction."""
    cfg, _model, params, _batch = cases.case(LAGUNA)
    _, grads = cases.own(LAGUNA)
    for tree in ("dense_layer_0", "tail_layer_0"):
        assert float(jnp.linalg.norm(grads[tree]["g_proj"]["kernel"])) > 1e-4
    layer = DecoderLayer(cfg, FULL, 6, False)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.hidden_size))
    rope = laguna_rope_tables(cfg, 32)
    weights = params["dense_layer_0"]
    readout = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def f(kernel):
        with jax.default_matmul_precision("highest"):
            y, report = layer.apply(
                {"params": dict(weights, g_proj={"kernel": kernel})}, x, rope
            )
        return jnp.sum(y * readout), report["gate_mean"]

    kernel = weights["g_proj"]["kernel"]
    direction = jax.random.normal(jax.random.PRNGKey(5), kernel.shape)
    (_, gate_mean), grad = jax.value_and_grad(f, has_aux=True)(kernel)
    assert 0.3 < float(gate_mean) < 0.7
    slope = (f(kernel + 1e-2 * direction)[0]
             - f(kernel - 1e-2 * direction)[0]) / 2e-2
    assert float(jnp.sum(grad * direction)) == pytest.approx(
        float(slope), rel=2e-3
    )
    assert abs(float(slope)) > 1e-2


def test_the_depth_rule():
    """The first layers of the published lists; the dense layer unrolled,
    whole periods scanned, what is left over unrolled; a width per kind."""
    whole = LagunaConfig.laguna_xs2_33b_a3b().layer_plan
    assert len(whole) == 40
    assert [i for i, k in enumerate(whole) if k[0] == FULL] == list(
        range(0, 40, 4)
    )
    assert all(heads == (48 if kind == FULL else 64)
               for kind, heads, _sparse in whole)
    assert [sparse for _k, _h, sparse in whole] == [False] + [True] * 39
    cut = LagunaConfig.laguna_xs2_33b_a3b(num_hidden_layers=5)
    assert cut.layer_plan == [
        (FULL, 48, False), (SLIDING, 64, True), (SLIDING, 64, True),
        (SLIDING, 64, True), (FULL, 48, True),
    ]
    with pytest.raises(ValueError, match="lists"):
        LagunaConfig.laguna_xs2_33b_a3b(num_hidden_layers=41)
    with pytest.raises(ValueError, match="dense layers lead"):
        LagunaConfig.tiny(mlp_layer_types=(SPARSE, DENSE) + (SPARSE,) * 4)
    shapes = jax.eval_shape(
        lambda: LagunaForCausalLM(LagunaConfig.tiny()).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    assert sorted(shapes["layers"]) == [f"layer_{i}" for i in range(4)]
    widths = {
        name: shapes["layers"][name]["self_attn"]["q_proj"]["kernel"].shape
        for name in ("layer_0", "layer_3")
    }
    assert widths == {"layer_0": (1, 32, 128), "layer_3": (1, 32, 96)}
    assert shapes["layers"]["layer_3"]["g_proj"]["kernel"].shape == (1, 32, 6)
    assert shapes["dense_layer_0"]["self_attn"]["o_proj"]["kernel"].shape == (
        96, 32
    )
    assert "tail_layer_0" in shapes and "tail_layer_1" not in shapes
    mlp = shapes["tail_layer_0"]["mlp"]
    assert "e_score_correction_bias" not in mlp  # no bias leaf
    assert mlp["shared_experts"]["gate_proj"]["kernel"].shape == (32, 24)


def test_the_shares_add_up_to_the_uncut_layer():
    """One layer's FFN: the routed parts that the 8 shares compute (each
    told its share, holding 2 of the 16 experts) and the shared expert
    COUNTED ONCE — every chip computes it alike — are the uncut reference's
    layer output."""
    cfg, _model, params, _batch = cases.case(LAGUNA)
    layer = jax.tree.map(lambda x: x[0], params["layers"]["layer_1"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), layer, held=(0, 16),
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        )
    shared = whole["shared"].reshape(x.shape)
    total, local = 0.0, 0.0
    for index in range(8):
        share = LagunaConfig.tiny(dtype=jnp.float32, expert_shard=(index, 8))
        first, held = share.held_experts
        mine = dict(layer, **{
            name: layer[name][first:first + held]
            for name in reference.EXPERTS
        })
        y, routing = RoutedFFN(
            share, shared_width=share.shared_expert_intermediate_size,
            biased=False,
        ).apply({"params": mine}, x)
        # what this chip computes that every chip computes: the shared expert
        np.testing.assert_allclose(
            y - shared, reference.moe_ffn(
                x.reshape(-1, cfg.hidden_size), mine, held=(first, held),
                top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor,
            )["routed"].reshape(x.shape), atol=2e-5, rtol=2e-5,
        )
        total = total + (y - shared)
        local += float(routing["local_slot_share"])
        np.testing.assert_array_equal(routing["choice"], whole["choice"])
        assert float(routing["dropped_slots"]) == 0.0
    assert local == pytest.approx(1.0, abs=1e-6)
    want = (whole["routed"] + whole["shared"]).reshape(x.shape)
    np.testing.assert_allclose(total + shared, want, atol=5e-5, rtol=5e-5)
    # the weights of a token's chosen eight sum to the scaling factor
    _, weights = reference.route(
        whole["scores"], cfg.num_experts_per_tok, cfg.routed_scaling_factor
    )
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.5, rtol=1e-6)
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(y - want))) > 1e-3


def test_masks_parameters_and_flops():
    params = cases.case(LAGUNA).params
    decay = laguna_weight_decay_mask(params)
    assert decay["norm"]["weight"] is False and decay["lm_head"] is True
    assert decay["layers"]["layer_0"]["input_layernorm"]["weight"] is False
    assert decay["layers"]["layer_0"]["mlp"]["router"] is True
    assert decay["layers"]["layer_0"]["g_proj"]["kernel"] is True
    # the cell's cut: 389,634,048 parameters
    cut = dict(num_hidden_layers=5, vocab_size=12544)
    held = LagunaConfig(expert_shard=(0, 32), **cut)
    shapes = jax.eval_shape(
        lambda: LagunaForCausalLM(held).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == 389_634_048
    mixer = lambda layer: count(layer["self_attn"]) + count(layer["g_proj"])  # noqa: E731
    assert mixer(shapes["dense_layer_0"]) == 29_458_432  # full, with its gate
    assert mixer(shapes["layers"]["layer_0"]) == 37_879_808  # sliding
    assert count(shapes["dense_layer_0"]["mlp"]) == 50_331_648
    assert count(shapes["layers"]["layer_0"]["mlp"]) == (
        3_145_728 * (8 + 1) + 524_288
    )
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == (
        51_380_224
    )
    part = laguna_layer_flops_per_token(held, 8192)
    assert part[FULL] - 2 * 2 * 48 * 128 * 8193 / 2 == pytest.approx(
        2 * 2048 * (48 + 16) * 128 + 2 * 48 * 128 * 2048 + 2 * 2048 * 48
    )
    assert part[SLIDING] - (
        2 * 2048 * (64 + 16) * 128 + 2 * 64 * 128 * 2048 + 2 * 2048 * 64
    ) == pytest.approx(2 * 2 * 64 * 128 * 4_063_488 / 8192)
    assert part["head"] == 2 * 2048 * 12544
    assert part[DENSE] == 2 * 3 * 2048 * 8192
    assert laguna_train_tflops_per_sample(held, 8192) == pytest.approx(
        19.24, abs=0.01
    )
    # at one window's length a sliding layer sees the triangle
    short = laguna_layer_flops_per_token(held, 512)
    assert short[SLIDING] - short[FULL] == pytest.approx(
        (64 - 48) * (2 * 2 * 2048 * 128 + 2 * 2048 + 2 * 2 * 128 * 513 / 2)
    )
    routed = 3 * 8192 * 4 * 8 * 2 * 3 * 2048 * 512 / 1e12
    assert laguna_train_tflops_per_sample(
        LagunaConfig(**cut), 8192
    ) - laguna_train_tflops_per_sample(held, 8192) == pytest.approx(
        routed * 31 / 32, rel=1e-9
    )
