"""Laguna through the trainer role: ``--training.model_size laguna_tiny``
makes global steps solo on the CPU through the same ``run_trainer`` /
``CollaborativeOptimizer`` path as every other model; no leaf is stepped by
a sign (the routed layer has no bias); the step records carry the routing
gauges, the sliding layers' two tile shares, a mean gate per attention kind
and the counter that must read 0; the held experts' gradients land in the
accumulator (gradient sinks)."""
import pytest

import decoder_cases as cases
from dedloc_tpu.models.laguna import FULL, SLIDING, LagunaConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    LAGUNA,
    build_model,
    model_family,
)


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "5")],
    ids=["whole", "share_1_of_4_cut_to_5"],
)
def test_laguna_tiny_trainer_makes_global_steps(tmp_path, shard, layers):
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "laguna_tiny", [
            "--training.expert_shard", shard,
            "--training.num_hidden_layers", layers,
        ]
    )
    sparse = (int(layers) or 6) - 1  # one leading dense layer
    cases.check_routing_records(stepped, shard, sparse)
    for rec in stepped:
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        assert rec["attn.band_tile_share"] == 1.0  # S=32: one tile
        # a band of 8 inside one 32 x 32 tile: 228 visible pairs of 1,024
        assert rec["attn.band_visible_share"] == pytest.approx(228 / 1024)
        for kind in (FULL, SLIDING):  # at the initialiser a gate is a half
            assert 0.4 < rec[f"attn.gate_mean.{kind}"] < 0.6
        assert "moe.bias_abs_max" not in rec  # no bias leaf, no sign step
    cases.check_kept_bytes_is_the_shapes(
        stepped, LAGUNA, "whole_mixer", state.params, "laguna_tiny",
        num_hidden_layers=int(layers), expert_shard=shard,
    )


def test_the_table_builds_laguna():
    for size in ("laguna_tiny", "laguna_xs2_33b_a3b"):
        assert model_family(size) is LAGUNA
    cfg, model = build_model(
        "laguna_tiny", num_hidden_layers=5, vocab_size=128,
        expert_shard="2/8",
    )
    assert model_family(model) is LAGUNA
    assert cfg.layer_plan == [(FULL, 6, False)] + [(SLIDING, 8, True)] * 3 + [
        (FULL, 6, True)
    ]
    assert cfg.held_experts == (4, 2) and cfg.vocab_size == 128
    batch = next(LAGUNA.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert LAGUNA.tflops_per_sample(cfg, 16) > 0
    # the same source, counter and sinks as the other expert decoders; no
    # bias, so no leaf stepped by a sign; its own gauges
    assert LAGUNA.step_counters == ("moe.dropped_slots",)
    assert LAGUNA.sign_step_mask is None
    assert LAGUNA.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert {"attn.band_tile_share", "attn.band_visible_share",
            f"attn.gate_mean.{FULL}", f"attn.gate_mean.{SLIDING}"} <= set(
        LAGUNA.step_gauges
    )
    assert "moe.bias_abs_max" not in LAGUNA.step_gauges
    published = LagunaConfig.laguna_xs2_33b_a3b()
    assert (published.hidden_size, published.intermediate_size,
            published.num_key_value_heads, published.head_dim,
            published.moe_intermediate_size,
            published.shared_expert_intermediate_size, published.num_experts,
            published.num_experts_per_tok, published.sliding_window,
            published.routed_scaling_factor, published.rms_norm_eps,
            published.vocab_size, published.num_hidden_layers,
            published.max_position_embeddings) == (
        2048, 8192, 8, 128, 512, 512, 256, 8, 512, 2.5, 1e-6, 100352, 40,
        262144)
    assert (published.full_rope_theta, published.full_partial_rotary_factor,
            published.full_yarn_factor,
            published.full_yarn_original_max_position_embeddings,
            published.full_yarn_beta_fast, published.full_yarn_beta_slow,
            published.full_yarn_attention_factor,
            published.sliding_rope_theta) == (
        500000.0, 0.5, 64.0, 4096, 64.0, 1.0, 1.4158883083359672, 10000.0)
    assert set(published.num_attention_heads_per_layer) == {48, 64}
    with pytest.raises(ValueError, match="must divide"):
        build_model("laguna_tiny", expert_shard="0/3")
    with pytest.raises(ValueError, match="unknown model_size"):
        model_family("laguna_xs2")
