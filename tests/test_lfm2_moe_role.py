"""The conv-hybrid expert decoder through the trainer role:
``--training.model_size lfm2_tiny`` makes global steps solo on the CPU
through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as every
other model; every correction-bias entry — a leaf per position of the
scanned period and one in the tail — moves by exactly ±gamma or 0 a global
step; the step records carry the routing gauges and the counter that must
read 0."""
import json

import jax
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.deepseek_v3 import BIAS
from dedloc_tpu.models.lfm2_moe import Lfm2MoeConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    LFM2_MOE,
    build_model,
    model_family,
)
from dedloc_tpu.roles.trainer import run_trainer


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "lfm2_tiny",
        "--training.seq_length", "32",
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--training.output_dir", str(tmp_path / "out"),
        "--averager.averaging_expiration", "1.0",
        "--averager.min_refresh_period", "0.1",
        "--averager.default_refresh_period", "0.3",
    ]
    return parse_config(CollaborationArguments, base + list(argv))


def _bias_leaves(tree):
    return [
        np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if path[-1].key == BIAS
    ]


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "5")],
    ids=["whole", "share_1_of_4_cut_to_5"],
)
def test_lfm2_tiny_trainer_steps_the_bias_by_gamma(tmp_path, shard, layers):
    events = tmp_path / "events.jsonl"
    args = _args(tmp_path, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "9",
        "--training.expert_shard", shard,
        "--training.num_hidden_layers", layers,
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
    ])
    state = run_trainer(args)
    steps = int(state.step)
    assert steps >= 2
    gamma = Lfm2MoeConfig.bias_update_speed
    biases = _bias_leaves(state.params)
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
            float(np.abs(m).max()) == 0.0 for m in _bias_leaves(moments)
        )

    log = [json.loads(line) for line in events.read_text().splitlines()]
    stepped = [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]
    assert len(stepped) >= 2
    count = int(shard.split("/")[1])
    for n, rec in enumerate(stepped, start=1):
        assert rec["moe.dropped_slots"] == 0.0
        assert all(
            rec[f"moe.load_max_over_mean.{i}"] >= 1.0
            for i in range(1, expert_layers + 1)
        )
        assert rec["moe.local_slot_share"] == pytest.approx(
            1.0 / count, abs=0.0 if count == 1 else 0.2
        )
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
