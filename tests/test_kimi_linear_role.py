"""Kimi Linear through the trainer role: ``--training.model_size
kimi_linear_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as every other model, whole
and at a share of heads and experts; the step records carry the routing
gauges and the three KDA gauges a KDA layer; the held experts' gradients land
in the accumulator; a head share that does not divide is refused."""
import pytest

import decoder_cases as cases
from dedloc_tpu.models.kimi_linear import KDA_GAUGES, KimiLinearConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    KIMI_LINEAR,
    build_model,
    model_family,
)


def test_kimi_tiny_trainer_makes_global_steps_at_a_share(tmp_path):
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "kimi_linear_tiny", [
            "--training.expert_shard", "1/4", "--training.head_shard", "1/2",
            "--training.num_hidden_layers", "5",
        ]
    )
    for rec in stepped:
        assert rec["moe.dropped_slots"] == 0.0
        assert rec["moe.grad_sink_leaves"] == 3.0 * 4  # the sparse layers
        assert f"moe.load_max_over_mean.{4}" in rec
        assert "moe.load_max_over_mean.5" not in rec
        for layer in range(1, 5):  # the cut's four KDA layers
            assert rec[f"kda.chunk_log_decay_min.{layer}"] < 0.0
            assert 0.3 < rec[f"kda.beta_mean.{layer}"] < 0.7
            assert rec[f"kda.state_abs_max.{layer}"] > 0.0
        assert "kda.beta_mean.5" not in rec
    cfg = cases.check_kept_bytes_is_the_shapes(  # the model's default policy
        stepped, KIMI_LINEAR, "whole_mixer", state.params, "kimi_linear_tiny",
        num_hidden_layers=5, expert_shard="1/4", head_shard="1/2",
    )
    assert (cfg.held_kda_heads, cfg.held_attention_heads) == (2, 2)
    # the decay's own leaves moved: the loss reaches them
    mixer = state.params["dense_layer_0"]["self_attn"]
    assert mixer["A_log"].shape == (2,) and mixer["q_conv"].shape == (16, 4)


def test_the_table_builds_kimi():
    for size in ("kimi_linear_tiny", "kimi_linear_48b_a3b"):
        assert model_family(size) is KIMI_LINEAR
    cfg, model = build_model(
        "kimi_linear_tiny", num_hidden_layers=5, vocab_size=128,
        expert_shard="2/8", head_shard="3/4",
    )
    assert model_family(model) is KIMI_LINEAR
    assert cfg.held_experts == (4, 2) and cfg.vocab_size == 128
    assert cfg.head_shard == (3, 4) and cfg.held_kda_heads == 1
    batch = next(KIMI_LINEAR.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert KIMI_LINEAR.tflops_per_sample(cfg, 16) > 0
    assert KIMI_LINEAR.step_counters == ("moe.dropped_slots",)
    assert KIMI_LINEAR.sign_step_mask is DEEPSEEK_V3.sign_step_mask
    assert KIMI_LINEAR.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert set(KDA_GAUGES) <= set(KIMI_LINEAR.step_gauges)
    published = KimiLinearConfig.kimi_linear_48b_a3b()
    assert (published.hidden_size, published.kda_num_heads,
            published.kda_head_dim, published.short_conv_kernel_size,
            published.num_attention_heads, published.qk_nope_head_dim,
            published.qk_rope_head_dim, published.v_head_dim,
            published.kv_lora_rank, published.intermediate_size,
            published.moe_intermediate_size, published.num_experts,
            published.num_experts_per_token, published.num_shared_experts,
            published.routed_scaling_factor, published.vocab_size,
            published.num_hidden_layers, published.rms_norm_eps) == (
        2304, 32, 128, 4, 32, 128, 64, 128, 512, 9216, 1024, 256, 8, 1,
        2.446, 163840, 27, 1e-5)


@pytest.mark.parametrize("flag,model_size,message", [
    ("0/3", "kimi_linear_tiny", "must divide"),
    ("4/4", "kimi_linear_tiny", "0 <= index < count"),
    ("0/2", "kanana2_tiny", "no mixer that holds a share"),
], ids=["count_does_not_divide", "index_out_of_range", "another_family"])
def test_a_head_share_that_cannot_be_held_is_refused(flag, model_size,
                                                     message):
    with pytest.raises(ValueError, match=message):
        build_model(model_size, head_shard=flag)


def test_the_flag_reaches_the_model(tmp_path):
    args = cases.trainer_args(
        tmp_path, "kimi_linear_tiny", ["--training.head_shard", "1/4"]
    )
    assert args.training.head_shard == "1/4"
    assert cases.trainer_args(
        tmp_path, "kimi_linear_tiny"
    ).training.head_shard == "0/1"  # today's programs
