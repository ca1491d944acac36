"""Nemotron-H (models/nemotron_h.py) against the plain reference
(benchmark/reference/nemotron_h.py: the token-by-token recurrence) at tiny
sizes, float32, seeded weights away from the initialiser: loss, whole
gradient, every leaf, router scores and choices, behind the recurrence
("dense") and behind the kernels (interpreted); mutations of the reference
that must be far off; a bf16 reference fails; THE SHARE TESTS (head shards'
and expert shards' outputs add up to the uncut layer's); the cut's parameter
count and the whole model's; the leaf masks; the initialisers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import nemotron_h as reference
from dedloc_tpu.models.decoder import (
    BIAS,
    GroupedQueryAttention,
    PlainMLP,
    RoutedFFN,
    Visibility,
    routed_grad_sink_mask,
    sign_step_mask,
)
from dedloc_tpu.models.nemotron_h import (
    SSD_GAUGES,
    Mamba2Mixer,
    NemotronHConfig,
    NemotronHForCausalLM,
    nemotron_h_loss,
    nemotron_h_train_tflops_per_sample,
    nemotron_h_weight_decay_mask,
)

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL, SCORE_TOL = 1e-5, 3e-4, 1e-5
SEQ = 64  # two chunks of the tiny model's kernels


@pytest.fixture(scope="module")
def setup():
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    model = NemotronHForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, SEQ + 1), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = cases.perturbed(
        model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    )
    (loss, metrics), grads = cases.model_grads(
        nemotron_h_loss, model, params, batch
    )
    return cfg, model, params, batch, loss, metrics, grads


def _reference_kwargs(cfg):
    return dict(
        state=cfg.ssm_state_size, head_dim=cfg.head_dim, eps=cfg.rms_norm_eps,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        held=cfg.held_experts,
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
    assert cfg.layer_kinds == "MEMEM*E"
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
    assert len(errors) > 45 and max(errors.values()) < LEAF_TOL, max(
        errors.items(), key=lambda kv: kv[1]
    )
    for name in SSD_GAUGES:
        assert metrics[name].shape == (3,)  # one entry a Mamba layer
    assert float(jnp.min(metrics["ssd.dt_mean"])) > 0.0
    assert float(jnp.max(metrics["ssd.chunk_log_decay_min"])) < 0.0
    assert float(jnp.min(metrics["ssd.state_abs_max"])) > 0.0
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0


def test_behind_the_kernels_it_is_the_same_model(setup):
    """The scan's kernels (interpreted) in place of the recurrence; the
    flash kernels want a head of 64 or 128, so attention stays dense: the
    mixer is asked for its kernels by a config of its own."""
    cfg, _model, params, batch, loss, _metrics, grads = setup
    import dataclasses

    from dedloc_tpu.models import nemotron_h

    flash = dataclasses.replace(cfg, attention_impl="flash")

    def loss_fn(p):
        # the Mamba layers under "flash", the attention layer under "dense"
        hidden = jnp.take(p["embed_tokens"], batch["input_ids"], axis=0)
        for i, kind in enumerate(cfg.layer_kinds):
            layer_cfg = flash if kind == nemotron_h.MAMBA else cfg
            hidden, _report = nemotron_h.NemotronLayer(layer_cfg, kind).apply(
                {"params": p[f"layer_{i}"]}, hidden
            )
        from dedloc_tpu.models.decoder import RMSNorm, chunked_cross_entropy

        hidden = RMSNorm(cfg).apply({"params": p["norm"]}, hidden)
        return jnp.mean(chunked_cross_entropy(
            hidden.reshape(1, -1, cfg.hidden_size), p["lm_head"],
            batch["labels"].reshape(-1), cfg.loss_chunk_tokens,
        ))

    value, flash_grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert abs(float(value) - float(loss)) < LOSS_TOL * float(loss)
    errors = _leaf_errors(flash_grads, grads)
    assert max(errors.values()) < LEAF_TOL, max(
        errors.items(), key=lambda kv: kv[1]
    )


@pytest.mark.parametrize("mutation", [
    {"decay": False}, {"skip": False}, {"gate_before_norm": False},
    {"norm_groups": 1}, {"causal_conv": False}, {"conv_bias": False},
    {"activation": "relu"}, {"expert_gate": True}, {"rope_theta": 10000.0},
    {"kv_interleaved": True}, {"dtype": jnp.bfloat16},
], ids=lambda m: "_".join(f"{k}_{getattr(v, '__name__', v)}"
                          for k, v in m.items()))
def test_a_different_function_is_far_off(setup, mutation):
    """No decay, the ``D`` skip dropped, the gate after the norm, one norm
    over all lanes instead of a group's, a non-causal convolution, its bias
    dropped, relu in place of relu², a gate added to the experts, RoPE
    applied in attention, another grouping of the query heads, bf16
    everywhere: each moves the last layer's router scores — continuous,
    downstream of every layer before it, equal to 1e-5 between model and
    reference — by over 100x that (forward only: a mutation a compile)."""
    cfg, _model, params, batch, _loss, metrics, _grads = setup
    with jax.default_matmul_precision("highest"):
        mutated = jax.jit(lambda p: reference.forward(
            p, batch, **_reference_kwargs(cfg),
            choices=metrics["moe.choice"], **mutation,
        )["scores"])(params)
    assert float(jnp.max(jnp.abs(
        mutated.astype(jnp.float32)[-1] - metrics["moe.scores"][-1]
    ))) > 100 * SCORE_TOL


def _columns(x, index, count, axis):
    width = x.shape[axis] // count
    return jax.lax.slice_in_dim(x, index * width, (index + 1) * width, axis=axis)


def _mamba_share(cfg, p, index, count):
    """The leaves of a Mamba mixer that the chip ``index`` of ``count``
    holds: whole GROUPS — their z | x | B | C | dt columns of ``W_in``, taps
    and bias, ``A_log``, ``D``, ``dt_bias``, the norm's weight, the rows of
    ``W_out``."""
    inner = cfg.mamba_num_heads * cfg.mamba_head_dim
    keys = cfg.n_groups * cfg.ssm_state_size

    def parts(x, axis, widths):
        """Each run of ``widths`` along ``axis`` cut to the chip's part."""
        edges = np.cumsum([0, *widths])
        return jnp.concatenate([
            _columns(jax.lax.slice_in_dim(x, lo, hi, axis=axis), index, count,
                     axis)
            for lo, hi in zip(edges[:-1], edges[1:])
        ], axis=axis)

    out = dict(p)
    out["in_proj"] = {"kernel": parts(  # z | x | B | C | dt
        p["in_proj"]["kernel"], 1,
        (inner, inner, keys, keys, cfg.mamba_num_heads),
    )}
    for name in ("conv", "conv_bias"):  # x | B | C
        out[name] = parts(p[name], 0, (inner, keys, keys))
    for name in ("A_log", "D", "dt_bias"):
        out[name] = _columns(p[name], index, count, 0)
    out["norm"] = {"weight": _columns(p["norm"]["weight"], index, count, 0)}
    out["out_proj"] = {
        "kernel": _columns(p["out_proj"]["kernel"], index, count, 0)
    }
    return out


def _attention_share(p, index, count):
    out = dict(p)
    for name in ("q_proj", "k_proj", "v_proj"):
        out[name] = {"kernel": _columns(p[name]["kernel"], index, count, 1)}
    out["o_proj"] = {"kernel": _columns(p["o_proj"]["kernel"], index, count, 0)}
    return out


def test_the_head_shards_add_up_to_the_uncut_mixer(count=2):
    """Mamba by groups (a group's B and C go with its heads), attention by
    key head: each shard's out-projection gives its heads' PARTIAL sum."""
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, cfg.hidden_size))

    def attention(c):
        return GroupedQueryAttention(
            c, Visibility(causal=True), rotated=False, heads=c.held_heads,
            kv_heads=c.held_kv_heads,
        )

    for whole, shard, share, args in (
        (Mamba2Mixer(cfg), Mamba2Mixer,
         lambda p, i: _mamba_share(cfg, p, i, count), (x,)),
        (attention(cfg), attention,
         lambda p, i: _attention_share(p, i, count), (x, None)),
    ):
        params = cases.perturbed(
            whole.init(jax.random.PRNGKey(1), *args)["params"]
        )
        full = whole.apply({"params": params}, *args)
        parts = []
        for index in range(count):
            held = NemotronHConfig.tiny(
                dtype=jnp.float32, head_shard=(index, count)
            )
            assert (held.held_groups, held.held_mamba_heads,
                    held.held_heads, held.held_kv_heads) == (1, 4, 2, 1)
            parts.append(shard(held).apply(
                {"params": share(params, index)}, *args
            ))
        if isinstance(whole, Mamba2Mixer):
            full, parts = full[0], [part[0] for part in parts]
        np.testing.assert_allclose(sum(parts), full, rtol=2e-4, atol=2e-5)


def test_the_expert_shards_add_up_to_the_uncut_layer():
    """Each shard adds its held experts' part of every token's top-k and
    the shared expert; the shared expert counted once, they are the layer."""
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.hidden_size))
    width = cfg.moe_shared_expert_intermediate_size
    layer = RoutedFFN(cfg, shared_width=width, activation="relu2")
    params = cases.perturbed(layer.init(jax.random.PRNGKey(1), x)["params"])
    assert "experts_gate" not in params and set(
        params["shared_experts"]
    ) == {"up_proj", "down_proj"}
    full, _routing = layer.apply({"params": params}, x)
    shared = PlainMLP(cfg, width).apply(
        {"params": params["shared_experts"]}, x
    )
    count, total = 16, 0.0
    for index in range(count):
        held = NemotronHConfig.tiny(
            dtype=jnp.float32, expert_shard=(index, count)
        )
        own = dict(params, **{
            name: _columns(params[name], index, count, 0)
            for name in ("experts_up", "experts_down")
        })
        part, _r = RoutedFFN(
            held, shared_width=width, activation="relu2"
        ).apply({"params": own}, x)
        total = total + part - shared
    np.testing.assert_allclose(total + shared, full, rtol=2e-4, atol=2e-5)


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def _shapes(cfg):
    return jax.eval_shape(
        lambda r: NemotronHForCausalLM(cfg).init(
            r, jnp.zeros((1, 64), jnp.int32)
        )["params"], jax.random.PRNGKey(0),
    )


def test_the_cut_holds_458_281_632_parameters():
    cfg = NemotronHConfig.nemotron3_nano_30b_a3b(
        num_hidden_layers=7, vocab_size=16384, expert_shard=(0, 16),
        head_shard=(0, 2),
    )
    assert cfg.layer_kinds == "MEMEM*E"
    assert (cfg.held_mamba_heads, cfg.held_groups, cfg.held_heads,
            cfg.held_kv_heads, cfg.held_experts) == (32, 4, 16, 1, (0, 8))
    shapes = _shapes(cfg)
    assert _count(shapes) == 458_281_632
    assert _count(shapes["layer_0"]["mixer"]) == 19_371_104  # Mamba
    assert _count(shapes["layer_5"]["mixer"]) == 11_698_176  # attention
    assert _count(shapes["layer_1"]["mixer"]) == 100_122_752  # experts
    assert _count(shapes["layer_0"]) == 19_373_792
    assert _count(jax.tree.map(
        lambda x, held: x if held else jnp.zeros((0,)), shapes,
        routed_grad_sink_mask(shapes),
    )) == 239_468_544
    assert nemotron_h_train_tflops_per_sample(cfg, 8192) == pytest.approx(
        10.089, abs=1e-3
    )
    # the lever: a quarter of the heads, one key head shared by two chips
    lever = NemotronHConfig.nemotron3_nano_30b_a3b(
        num_hidden_layers=7, vocab_size=16384, expert_shard=(0, 16),
        head_shard=(0, 4),
    )
    assert (lever.held_mamba_heads, lever.held_heads,
            lever.held_kv_heads) == (16, 8, 1)
    assert _count(_shapes(lever)) == 423_719_952


def test_the_whole_published_model_holds_31_577_940_288_parameters():
    published = NemotronHConfig.nemotron3_nano_30b_a3b()
    kinds = published.layer_kinds
    assert (len(kinds), kinds.count("M"), kinds.count("E"),
            kinds.count("*")) == (52, 23, 23, 6)
    assert [i for i, k in enumerate(kinds) if k == "*"] == [
        5, 12, 19, 26, 33, 42
    ]
    assert _count(_shapes(published)) == 31_577_940_288
    with pytest.raises(ValueError, match="head_shard 0/3"):
        NemotronHConfig.tiny(head_shard=(0, 3))
    with pytest.raises(ValueError, match="num_hidden_layers 53"):
        NemotronHConfig.nemotron3_nano_30b_a3b(num_hidden_layers=53)
    with pytest.raises(ValueError, match="no expert layer"):
        NemotronHConfig.nemotron3_nano_30b_a3b(num_hidden_layers=1)


def test_the_leaf_masks_and_the_initialisers(setup):
    cfg, model, params, batch, *_rest = setup
    decayed = nemotron_h_weight_decay_mask(params)
    mixer = decayed["layer_0"]["mixer"]
    assert mixer["in_proj"]["kernel"] and mixer["out_proj"]["kernel"]
    for name in ("A_log", "D", "dt_bias", "conv", "conv_bias"):
        assert not mixer[name], name
    assert not mixer["norm"]["weight"] and not decayed["layer_0"]["norm"][
        "weight"
    ]
    assert not decayed["layer_1"]["mixer"][BIAS]
    assert decayed["layer_1"]["mixer"]["experts_up"]
    signed = sign_step_mask(params)
    assert signed["layer_1"]["mixer"][BIAS]
    assert sum(jax.tree.leaves(signed)) == 3  # the expert layers' biases
    sinks = routed_grad_sink_mask(params)
    assert sum(jax.tree.leaves(sinks)) == 2 * 3  # up and down, no gate
    # the initialiser: A_log = log(1 .. heads) BY HEAD (a share starts at
    # its first head), D = 1, steps inside the published range
    second = NemotronHForCausalLM(NemotronHConfig.tiny(head_shard=(1, 2)))
    fresh = second.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    m = fresh["layer_0"]["mixer"]
    np.testing.assert_allclose(jnp.exp(m["A_log"]), [5, 6, 7, 8], rtol=1e-6)
    np.testing.assert_array_equal(m["D"], 1.0)
    steps = jax.nn.softplus(m["dt_bias"])
    assert float(steps.min()) >= 1e-3 * 0.999 and float(steps.max()) <= 0.1001
    assert float(jnp.max(jnp.abs(m["conv"]))) <= 0.5
    # the out-projections' deviation over sqrt(52)
    own = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    for leaf in (own["layer_0"]["mixer"]["out_proj"]["kernel"],
                 own["layer_5"]["mixer"]["o_proj"]["kernel"],
                 own["layer_1"]["mixer"]["experts_down"],
                 own["layer_1"]["mixer"]["shared_experts"]["down_proj"][
                     "kernel"]):
        assert float(jnp.std(leaf)) == pytest.approx(
            0.02 / 52 ** 0.5, rel=0.2
        )
    assert float(jnp.std(
        own["layer_0"]["mixer"]["in_proj"]["kernel"]
    )) == pytest.approx(0.02, rel=0.1)
