"""Nemotron-H through the trainer role: ``--training.model_size
nemotron_h_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as every other model, at a
share of heads and experts; the step records carry the routing gauges and
the three state-space gauges a Mamba layer; the held experts' gradients land
in the accumulator (TWO leaves a layer: no gate); a head share that does not
divide the groups is refused."""
import pytest

import decoder_cases as cases
from dedloc_tpu.models.nemotron_h import SSD_GAUGES, NemotronHConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    NEMOTRON_H,
    build_model,
    model_family,
)


def test_nemotron_tiny_trainer_makes_global_steps_at_a_share(tmp_path):
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "nemotron_h_tiny", [
            "--training.expert_shard", "1/4", "--training.head_shard", "1/2",
        ]
    )
    for rec in stepped:
        assert rec["moe.dropped_slots"] == 0.0
        assert rec["moe.grad_sink_leaves"] == 2.0 * 3  # up and down, E layers
        assert rec["moe.compute_copy_leaves"] == 2.0 * 3
        assert "moe.load_max_over_mean.3" in rec
        assert "moe.load_max_over_mean.4" not in rec
        for layer in range(1, 4):  # the cut's three Mamba layers
            assert rec[f"ssd.dt_mean.{layer}"] > 0.0
            assert rec[f"ssd.chunk_log_decay_min.{layer}"] < 0.0
            assert rec[f"ssd.state_abs_max.{layer}"] > 0.0
        assert "ssd.dt_mean.4" not in rec
    cfg = cases.check_kept_bytes_is_the_shapes(  # the model's default policy
        stepped, NEMOTRON_H, "whole_mixer", state.params, "nemotron_h_tiny",
        expert_shard="1/4", head_shard="1/2",
    )
    assert (cfg.held_mamba_heads, cfg.held_groups, cfg.held_heads,
            cfg.held_kv_heads) == (4, 1, 2, 1)
    mixer = state.params["layer_0"]["mixer"]
    assert mixer["A_log"].shape == (4,) and mixer["conv"].shape == (64, 4)
    assert "experts_gate" not in state.params["layer_1"]["mixer"]


def test_the_accumulate_step_leaves_two_leaves_a_layer_in_the_accumulator():
    _model, params, batches, loss_fn = cases.sink_case("nemotron_h_tiny")
    cases.check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
        params, batches, loss_fn, sink_leaves=2.0 * 3, expert_leaves=2 * 3
    )


def test_the_table_builds_nemotron():
    for size in ("nemotron_h_tiny", "nemotron3_nano_30b_a3b"):
        assert model_family(size) is NEMOTRON_H
    cfg, model = build_model(
        "nemotron_h_tiny", num_hidden_layers=10, vocab_size=128,
        expert_shard="2/8", head_shard="1/2",
    )
    assert model_family(model) is NEMOTRON_H
    assert cfg.held_experts == (4, 2) and cfg.vocab_size == 128
    assert cfg.head_shard == (1, 2) and cfg.layer_kinds == "MEMEM*EM*E"
    batch = next(NEMOTRON_H.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert NEMOTRON_H.tflops_per_sample(cfg, 16) > 0
    assert NEMOTRON_H.step_counters == ("moe.dropped_slots",)
    assert NEMOTRON_H.sign_step_mask is DEEPSEEK_V3.sign_step_mask
    assert NEMOTRON_H.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert set(SSD_GAUGES) <= set(NEMOTRON_H.step_gauges)
    published = NemotronHConfig.nemotron3_nano_30b_a3b()
    assert (published.hidden_size, published.mamba_num_heads,
            published.mamba_head_dim, published.n_groups,
            published.ssm_state_size, published.conv_kernel,
            published.chunk_size, published.num_attention_heads,
            published.num_key_value_heads, published.head_dim,
            published.n_routed_experts, published.num_experts_per_tok,
            published.moe_intermediate_size,
            published.moe_shared_expert_intermediate_size,
            published.routed_scaling_factor, published.vocab_size,
            published.num_hidden_layers, published.rms_norm_eps) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 128, 6, 1856, 3712, 2.5,
        131072, 52, 1e-5)


@pytest.mark.parametrize("flag,message", [
    ("0/4", "must divide"),  # four chips: the tiny model has two groups
    ("0/3", "must divide"),
    ("2/2", "0 <= index < count"),
], ids=["more_chips_than_groups", "count_does_not_divide",
        "index_out_of_range"])
def test_a_head_share_that_cannot_be_held_is_refused(flag, message):
    with pytest.raises(ValueError, match=message):
        build_model("nemotron_h_tiny", head_shard=flag)


def test_key_heads_are_split_while_the_count_allows_and_shared_beyond():
    cut = NemotronHConfig.nemotron3_nano_30b_a3b
    assert [
        (c.held_groups, c.held_mamba_heads, c.held_heads, c.held_kv_heads)
        for c in (cut(head_shard=(0, n)) for n in (1, 2, 4, 8))
    ] == [(8, 64, 32, 2), (4, 32, 16, 1), (2, 16, 8, 1), (1, 8, 4, 1)]
    with pytest.raises(ValueError, match="must divide"):
        cut(head_shard=(0, 16))  # sixteen chips: eight groups
