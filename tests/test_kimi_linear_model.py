"""Kimi Linear (models/kimi_linear.py) against the plain reference
(benchmark/reference/kimi_linear.py: the token-by-token recurrence) at tiny
sizes, float32, seeded weights away from the initialiser: loss, whole
gradient, every leaf, router scores and choices, behind the recurrence
("dense") and behind the kernels (interpreted); mutations of the reference
that must be far off; a bf16 reference fails; THE SHARE TESTS (head shards'
and expert shards' outputs add up to the uncut layer's); the cut's parameter
count; the leaf masks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import kimi_linear as reference
from dedloc_tpu.models.decoder import (
    BIAS,
    LatentAttention,
    RoutedFFN,
    SwiGLU,
    sign_step_mask,
)
from dedloc_tpu.models.kimi_linear import (
    KDA,
    KDA_GAUGES,
    MLA,
    KimiDeltaAttention,
    KimiLinearConfig,
    KimiLinearForCausalLM,
    kimi_linear_loss,
    kimi_linear_train_tflops_per_sample,
    kimi_linear_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL, SCORE_TOL = 1e-5, 3e-4, 1e-5
SEQ = 128  # two chunks of the kernels


@pytest.fixture(scope="module")
def setup():
    cfg = KimiLinearConfig.tiny(dtype=jnp.float32, num_hidden_layers=5)
    model = KimiLinearForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, SEQ + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = cases.perturbed(
        model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    )
    (loss, metrics), grads = cases.model_grads(
        kimi_linear_loss, model, params, batch
    )
    return cfg, model, params, batch, loss, metrics, grads


def _reference_kwargs(cfg):
    return dict(
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        eps=cfg.rms_norm_eps, top_k=cfg.num_experts_per_token,
        scale=cfg.routed_scaling_factor, held=cfg.held_experts,
    )


def _reference(cfg, params, batch, choices, **mutations):
    (value, out), grads = cases.reference_grads(
        reference, dict(_reference_kwargs(cfg), **mutations), params, batch,
        choices=choices,
    )
    return value, out, grads


def _leaf_errors(grads, ref_grads):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = jax.tree.leaves(ref_grads)
    return {
        jax.tree_util.keystr(path): float(
            jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)
        )
        for (path, a), b in zip(flat, ref) if path[-1].key != BIAS
    }


def test_the_model_is_the_reference(setup):
    cfg, _model, params, batch, loss, metrics, grads = setup
    assert [m for _n, m, _s in cfg.layer_plan] == [KDA, KDA, KDA, MLA, KDA]
    ref_loss, out, ref_grads = _reference(
        cfg, params, batch, metrics["moe.choice"]
    )
    assert abs(float(loss) - float(ref_loss)) < LOSS_TOL * float(ref_loss)
    np.testing.assert_allclose(metrics["moe.scores"], out["scores"],
                               atol=SCORE_TOL)
    # the reference's OWN top-k of its scores: the same choices, as sets
    _l, own, _g = _reference(cfg, params, batch, None)
    assert np.array_equal(
        np.sort(np.asarray(own["choice"]), -1),
        np.sort(np.asarray(metrics["moe.choice"]), -1),
    )
    errors = _leaf_errors(grads, ref_grads)
    assert len(errors) > 100 and max(errors.values()) < LEAF_TOL, max(
        errors.items(), key=lambda kv: kv[1]
    )
    for name in KDA_GAUGES:
        assert metrics[name].shape == (4,)  # one entry a KDA layer
    assert float(jnp.max(metrics["kda.chunk_log_decay_min"])) < 0.0
    assert 0.3 < float(jnp.mean(metrics["kda.beta_mean"])) < 0.7
    assert float(jnp.min(metrics["kda.state_abs_max"])) > 0.0


def test_behind_the_kernels_it_is_the_same_model(setup):
    cfg, _model, params, batch, loss, _metrics, grads = setup
    flash = KimiLinearForCausalLM(KimiLinearConfig.tiny(
        dtype=jnp.float32, num_hidden_layers=5, attention_impl="flash",
        attention_block_size=64,
    ))
    value, flash_grads = jax.jit(jax.value_and_grad(
        lambda p: kimi_linear_loss(flash, p, batch)[0]
    ))(params)
    assert abs(float(value) - float(loss)) < LOSS_TOL * float(loss)
    errors = _leaf_errors(flash_grads, grads)
    assert max(errors.values()) < LEAF_TOL, max(
        errors.items(), key=lambda kv: kv[1]
    )


@pytest.mark.parametrize("mutation", [
    {"decay": "none"}, {"decay": "head"}, {"beta_sigmoid": False},
    {"causal_conv": False}, {"gate_sigmoid": False}, {"k_norm": False},
    {"mla_rope_theta": 10000.0}, {"dtype": jnp.bfloat16},
], ids=lambda m: "_".join(f"{k}_{getattr(v, '__name__', v)}"
                          for k, v in m.items()))
def test_a_different_function_is_far_off(setup, mutation):
    """No decay, a decay per head instead of per channel, beta without its
    sigmoid, a non-causal convolution, the gate's sigmoid dropped, k not
    normalised, RoPE applied in latent attention, bf16 everywhere: each
    moves a router score — continuous, downstream of every mixer, equal to
    1e-5 between model and reference — by over 100x that (forward only: a
    mutation a compile)."""
    cfg, _model, params, batch, _loss, metrics, _grads = setup
    with jax.default_matmul_precision("highest"):
        mutated = jax.jit(lambda p: reference.forward(
            p, batch, **_reference_kwargs(cfg),
            choices=metrics["moe.choice"], **mutation,
        )["scores"])(params)
    assert float(jnp.max(jnp.abs(
        mutated.astype(jnp.float32) - metrics["moe.scores"]
    ))) > 100 * SCORE_TOL


def _columns(x, index, count, axis):
    width = x.shape[axis] // count
    return jax.lax.slice_in_dim(x, index * width, (index + 1) * width, axis=axis)


def _kda_share(p, index, count):
    """The leaves of a KDA mixer that the chip ``index`` of ``count`` holds:
    the projections of its heads by columns, ``o_proj`` by rows, the two
    gates' first factors and the norm's weight whole."""
    by_columns = ("q_proj", "k_proj", "v_proj", "f_b_proj", "b_proj",
                  "g_b_proj")
    by_rows = ("q_conv", "k_conv", "v_conv", "A_log", "dt_bias", "g_b_bias")
    out = dict(p)
    for name in by_columns:
        out[name] = {"kernel": _columns(p[name]["kernel"], index, count, 1)}
    for name in by_rows:
        out[name] = _columns(p[name], index, count, 0)
    out["o_proj"] = {"kernel": _columns(p["o_proj"]["kernel"], index, count, 0)}
    return out


def _mla_share(p, index, count):
    out = dict(p)
    for name in ("q_proj", "kv_b_proj"):
        out[name] = {"kernel": _columns(p[name]["kernel"], index, count, 1)}
    out["o_proj"] = {"kernel": _columns(p["o_proj"]["kernel"], index, count, 0)}
    return out


def test_the_head_shards_add_up_to_the_uncut_mixer(count=4):
    """What every shard computes alike — W_fa, W_ga, W_kva and its norm —
    counted once; each shard's W_o gives its heads' PARTIAL sum."""
    cfg = KimiLinearConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, cfg.hidden_size))
    for whole, shard, share in (
        (KimiDeltaAttention(cfg),
         lambda c: KimiDeltaAttention(c), _kda_share),
        (LatentAttention(cfg, rotated=False),
         lambda c: LatentAttention(
             c, heads=c.held_attention_heads, rotated=False
         ), _mla_share),
    ):
        is_kda = isinstance(whole, KimiDeltaAttention)
        args = (x,) if is_kda else (x, None)
        params = cases.perturbed(whole.init(jax.random.PRNGKey(1), *args)["params"])
        full = whole.apply({"params": params}, *args)
        parts = []
        for index in range(count):
            held = KimiLinearConfig.tiny(
                dtype=jnp.float32, head_shard=(index, count)
            )
            parts.append(shard(held).apply(
                {"params": share(params, index, count)}, *args
            ))
        if is_kda:
            full, parts = full[0], [part[0] for part in parts]
        np.testing.assert_allclose(sum(parts), full, rtol=2e-4, atol=2e-5)


def test_the_expert_shards_add_up_to_the_uncut_layer():
    """Each shard adds its held experts' part of every token's top-k and
    the shared expert; the shared expert counted once, they are the layer."""
    cfg = KimiLinearConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    layer = RoutedFFN(cfg, shared_width=cfg.moe_intermediate_size)
    params = cases.perturbed(layer.init(jax.random.PRNGKey(1), x)["params"])
    full, _routing = layer.apply({"params": params}, x)
    shared = SwiGLU(cfg, cfg.moe_intermediate_size).apply(
        {"params": params["shared_experts"]}, x
    )
    count, total = 4, 0.0
    for index in range(count):
        held = KimiLinearConfig.tiny(
            dtype=jnp.float32, expert_shard=(index, count)
        )
        own = dict(params, **{
            name: _columns(params[name], index, count, 0)
            for name in ("experts_gate", "experts_up", "experts_down")
        })
        part, _r = RoutedFFN(
            held, shared_width=cfg.moe_intermediate_size
        ).apply({"params": own}, x)
        total = total + part - shared
    np.testing.assert_allclose(total + shared, full, rtol=2e-4, atol=2e-5)


def test_the_cut_holds_464_825_120_parameters():
    cfg = KimiLinearConfig.kimi_linear_48b_a3b(
        num_hidden_layers=5, vocab_size=20480, expert_shard=(0, 32),
        head_shard=(0, 4),
    )
    assert [m for _n, m, _s in cfg.layer_plan] == [KDA, KDA, KDA, MLA, KDA]
    assert [s for _n, _m, s in cfg.layer_plan] == [False] + [True] * 4
    shapes = jax.eval_shape(
        lambda r: KimiLinearForCausalLM(cfg).init(
            r, jnp.zeros((1, 64), jnp.int32)
        )["params"], jax.random.PRNGKey(0),
    )
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa
    assert count(shapes) == 464_825_120
    assert count(shapes["dense_layer_0"]["self_attn"]) == 10_322_056
    period = shapes["layers"]
    assert count(period["layer_2"]["self_attn"]) == 8_274_432  # MLA
    assert count(period["layer_0"]) == 74_617_736
    assert count(period["layer_2"]) == 72_570_112
    assert kimi_linear_train_tflops_per_sample(cfg, 8192) == pytest.approx(
        10.398, abs=1e-3
    )
    published = KimiLinearConfig.kimi_linear_48b_a3b()
    assert len(published.layer_plan) == 27
    assert sum(m == MLA for _n, m, _s in published.layer_plan) == 7
    with pytest.raises(ValueError, match="head_shard 0/3"):
        KimiLinearConfig.tiny(head_shard=(0, 3))
    with pytest.raises(ValueError, match="num_hidden_layers 28"):
        KimiLinearConfig.kimi_linear_48b_a3b(num_hidden_layers=28)


def test_the_leaf_masks(setup):
    _cfg, _model, params, *_rest = setup
    decayed = kimi_linear_weight_decay_mask(params)
    mixer = decayed["dense_layer_0"]["self_attn"]
    assert mixer["q_proj"]["kernel"] and mixer["f_a_proj"]["kernel"]
    for name in ("A_log", "dt_bias", "q_conv", "k_conv", "v_conv",
                 "g_b_bias"):
        assert not mixer[name], name
    assert not mixer["o_norm"]["weight"]
    assert not decayed["layers"]["layer_0"]["mlp"][BIAS]
    signed = sign_step_mask(params)
    assert signed["layers"]["layer_2"]["mlp"][BIAS]
    assert sum(jax.tree.leaves(signed)) == 4  # the sparse layers' biases
