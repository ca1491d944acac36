"""The band-and-global expert decoder through the trainer role:
``--training.model_size smallthinker_tiny`` makes global steps solo on the
CPU through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as
every other model; no leaf is stepped by a sign; the step records carry the
routing gauges, ``attn.band_tile_share`` and the counter that must read 0;
the held experts' gradients land in the accumulator (gradient sinks) for
ReLU-gated experts as for the SiLU ones."""
import pytest

import decoder_cases as cases
from dedloc_tpu.models.smallthinker import SmallThinkerConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    SMALLTHINKER,
    build_model,
    model_family,
)


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "6")],
    ids=["whole", "share_1_of_4_cut_to_6"],
)
def test_smallthinker_tiny_trainer_makes_global_steps(tmp_path, shard, layers):
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "smallthinker_tiny", [
            "--training.expert_shard", shard,
            "--training.num_hidden_layers", layers,
        ]
    )
    cases.check_routing_records(stepped, shard, int(layers) or 8)
    for rec in stepped:
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        assert rec["attn.band_tile_share"] == 1.0  # S=32: one tile
        assert "moe.bias_abs_max" not in rec  # no bias leaf, no sign step
    cases.check_kept_bytes_is_the_shapes(
        stepped, SMALLTHINKER, "whole_mixer", state.params,
        "smallthinker_tiny", num_hidden_layers=int(layers),
        expert_shard=shard,
    )


def test_the_table_builds_the_band_and_global_decoder():
    for size in ("smallthinker_tiny", "smallthinker_21b_a3b"):
        assert model_family(size) is SMALLTHINKER
    cfg, model = build_model(
        "smallthinker_tiny", num_hidden_layers=5, vocab_size=128,
        expert_shard="2/8",
    )
    assert model_family(model) is SMALLTHINKER
    assert cfg.layer_plan == [(False, False)] + [(True, True)] * 3 + [
        (False, False)
    ]
    assert cfg.held_experts == (2, 1) and cfg.vocab_size == 128
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (7, 1)
    batch = next(SMALLTHINKER.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert SMALLTHINKER.tflops_per_sample(cfg, 16) > 0
    # the same source, counter and sinks as the other expert decoders; no
    # bias, so no leaf stepped by a sign; its own gauge
    assert SMALLTHINKER.step_counters == ("moe.dropped_slots",)
    assert SMALLTHINKER.sign_step_mask is None
    assert SMALLTHINKER.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert "attn.band_tile_share" in SMALLTHINKER.step_gauges
    assert "moe.bias_abs_max" not in SMALLTHINKER.step_gauges
    published = SmallThinkerConfig.smallthinker_21b_a3b()
    assert (published.hidden_size, published.num_attention_heads,
            published.num_key_value_heads, published.head_dim,
            published.moe_intermediate_size, published.num_experts,
            published.num_experts_per_tok, published.sliding_window_size,
            published.rope_theta, published.rms_norm_eps,
            published.vocab_size, published.num_hidden_layers) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 1.5e6, 1e-6, 151936, 52)
    with pytest.raises(ValueError, match="must divide"):
        build_model("smallthinker_tiny", expert_shard="0/3")
    with pytest.raises(ValueError, match="unknown model_size"):
        model_family("smallthinker_21b")


def test_accumulate_step_leaves_expert_gradients_in_the_accumulator():
    """Five layers, each with leaves of its own: ReLU-gated experts' as the
    SiLU ones'."""
    _model, params, batches, loss_fn = cases.sink_case(
        "smallthinker_tiny", num_hidden_layers=5
    )
    cases.check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
        params, batches, loss_fn, sink_leaves=15.0, expert_leaves=15
    )
