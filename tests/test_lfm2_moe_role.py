"""The conv-hybrid expert decoder through the trainer role:
``--training.model_size lfm2_tiny`` makes global steps solo on the CPU
through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as every
other model; every correction-bias entry — a leaf per position of the
scanned period and one in the tail — moves by exactly ±gamma or 0 a global
step; the step records carry the routing gauges and the counter that must
read 0."""
import jax
import numpy as np
import pytest

import decoder_cases as cases
from dedloc_tpu.models.lfm2_moe import Lfm2MoeConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    LFM2_MOE,
    build_model,
    model_family,
)


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "5")],
    ids=["whole", "share_1_of_4_cut_to_5"],
)
def test_lfm2_tiny_trainer_steps_the_bias_by_gamma(tmp_path, shard, layers):
    state, stepped, _records = cases.run_tiny_trainer(tmp_path, "lfm2_tiny", [
        "--training.expert_shard", shard,
        "--training.num_hidden_layers", layers,
    ])
    steps = int(state.step)
    gamma = Lfm2MoeConfig.bias_update_speed
    biases = cases.bias_leaves(state.params)
    # the period's four positions (+ the whole model's tail layer)
    expert_layers = 5 if layers == "0" else 4
    assert len(biases) == expert_layers
    assert all(b.shape[-1] == 16 for b in biases)
    bias = np.concatenate([b.reshape(-1) for b in biases])
    # it started at 0: after n steps every entry is a whole number of gammas
    in_gammas = bias / gamma
    np.testing.assert_allclose(in_gammas, np.round(in_gammas), atol=1e-3)
    assert np.abs(in_gammas).max() <= steps + 1e-3 and np.abs(bias).max() > 0
    # the sign rule keeps no moments for the leaves
    from dedloc_tpu.optim.lamb import ScaleByLambState
    from dedloc_tpu.parallel.train_step import _find_opt_state

    lamb_state = _find_opt_state(state.opt_state, ScaleByLambState)
    for moments in (lamb_state.mu, lamb_state.nu):
        assert all(
            float(np.abs(m).max()) == 0.0 for m in cases.bias_leaves(moments)
        )

    cases.check_routing_records(stepped, shard, expert_layers, slack=0.2)
    for n, rec in enumerate(stepped, start=1):
        # the held matrices in bf16, cast ONCE a set of weights: three a
        # routed layer ride beside the sinks, one run of the cast program a
        # global step (``parallel/train_step._StepWithComputeCopies``)
        assert rec["moe.compute_copy_leaves"] == 3.0 * expert_layers
        assert rec["moe.compute_copy_builds"] == 1.0
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        # read with the loss BEFORE this step's apply: n − 1 steps so far
        assert rec["moe.bias_abs_max"] <= (n - 1) * gamma + 1e-9
    assert stepped[-1]["moe.bias_abs_max"] > 0


def test_the_table_builds_the_conv_hybrid_decoder():
    for size in ("lfm2_tiny", "lfm2_24b_a2b"):
        assert model_family(size) is LFM2_MOE
    cfg, model = build_model(
        "lfm2_tiny", num_hidden_layers=5, vocab_size=128, expert_shard="2/8",
    )
    assert model_family(model) is LFM2_MOE
    assert [kind for _i, kind, _s in cfg.layer_plan] == [
        "conv", "full_attention", "conv", "conv", "conv"
    ]
    assert cfg.held_experts == (4, 2) and cfg.vocab_size == 128
    batch = next(LFM2_MOE.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert LFM2_MOE.tflops_per_sample(cfg, 16) > 0
    # the same source, gauges, counter and sign step as the other expert
    # decoder; its own module, loss, FLOPs and masks
    assert LFM2_MOE.step_gauges == DEEPSEEK_V3.step_gauges
    assert LFM2_MOE.step_counters == ("moe.dropped_slots",)
    assert LFM2_MOE.sign_step == 0.001 and LFM2_MOE.loss is not DEEPSEEK_V3.loss
    published = Lfm2MoeConfig.lfm2_24b_a2b()
    assert (published.hidden_size, published.num_attention_heads,
            published.num_key_value_heads, published.head_dim,
            published.intermediate_size, published.moe_intermediate_size,
            published.num_experts, published.num_experts_per_tok,
            published.routed_scaling_factor, published.conv_L_cache,
            published.rope_theta, published.rms_norm_eps) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, 1.0, 3, 1e6, 1e-5)
    with pytest.raises(ValueError, match="must divide"):
        build_model("lfm2_tiny", expert_shard="0/3")


def test_ouro_takes_grouped_heads():
    """The looped decoder's attention no longer refuses fewer kv heads
    than heads (the kernels take groups): k and v are projected at their
    own width."""
    import jax.numpy as jnp

    from dedloc_tpu.models.ouro import OuroConfig, OuroForCausalLM

    cfg = OuroConfig.tiny(num_key_value_heads=1, dtype=jnp.float32)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = OuroForCausalLM(cfg).init(jax.random.PRNGKey(0), ids)["params"]
    attn = params["model"]["layers"]["block"]["self_attn"]
    assert attn["k_proj"]["kernel"].shape[-1] == cfg.head_dim
    assert attn["q_proj"]["kernel"].shape[-1] == 2 * cfg.head_dim
    hiddens, _gates = OuroForCausalLM(cfg).apply({"params": params}, ids)
    assert bool(jnp.isfinite(hiddens).all())


def test_accumulate_step_leaves_expert_gradients_in_the_accumulator():
    """The scanned period's expert leaves (a layer each) AND the tail
    layer's: five layers, each with leaves of its own."""
    _model, params, batches, loss_fn = cases.sink_case("lfm2_tiny")
    cases.check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
        params, batches, loss_fn, sink_leaves=15.0, expert_leaves=15
    )


def test_accumulate_step_under_a_mesh_keeps_the_plain_path():
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )

    _model, params, batches, loss_fn = cases.sink_case("lfm2_tiny")
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    operands = (
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32),
        batches[0], jax.random.PRNGKey(0),
    )
    meshed = make_accumulate_step(loss_fn, mesh=mesh).lower(*operands)
    assert meshed.as_text() == make_accumulate_step(
        loss_fn.loss, mesh=mesh
    ).lower(*operands).as_text()
    assert meshed.as_text() != make_accumulate_step(loss_fn).lower(
        *operands
    ).as_text()


@pytest.mark.parametrize("size", ["tiny", "ouro_tiny"])
def test_a_model_without_sink_leaves_lowers_to_the_step_it_was(size):
    """No family but the expert decoders marks sink leaves: their loss is a
    plain function and ``accumulate_step`` the module it was before sinks —
    value_and_grad, then ``a + g`` a leaf."""
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn, drop_collator_keys

    cfg, model = build_model(size)
    loss_fn = build_loss_fn(model)
    assert not hasattr(loss_fn, "sink_mask")
    batch = drop_collator_keys(
        next(model_family(size).synthetic_batches(cfg, 2, 32, 0))
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32)
    )["params"]

    def accumulate_step(params, grad_acc, n_acc, batch, rng):
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng
        )
        grad_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
        )
        return grad_acc, n_acc + 1, metrics

    operands = (
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32), batch,
        jax.random.PRNGKey(0),
    )
    assert make_accumulate_step(loss_fn).lower(*operands).as_text() == (
        jax.jit(accumulate_step, donate_argnums=(1, 2))
        .lower(*operands).as_text()
    )
