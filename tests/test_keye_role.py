"""Keye-VL-2.0's language model through the trainer role:
``--training.model_size keye_vl2_tiny`` makes global steps solo on the CPU
through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as every
other model, on rows with image spans (three position streams a token, no
loss on an image label); the step records carry the routing gauges, the
selection's two shares, an index peak a layer, the indexer's own loss term
and the image share; the held experts' gradients land in the accumulator
(gradient sinks); the indexer's leaves move (its loss reaches them)."""
import numpy as np
import pytest

import decoder_cases as cases
from dedloc_tpu.data import causal_lm
from dedloc_tpu.models.keye_vl2 import KeyeVL2Config, selected_pairs
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    KEYE_VL2,
    build_model,
    model_family,
)
from dedloc_tpu.roles.trainer import _make_batches


@pytest.mark.parametrize(
    "shard,layers,share", [("0/1", "0", "0.25"), ("1/4", "5", "0")],
    ids=["whole_with_image_spans", "share_1_of_4_cut_to_5_text_rows"],
)
def test_keye_tiny_trainer_makes_global_steps(tmp_path, monkeypatch, shard,
                                              layers, share):
    # image spans that fit a quarter of a row of 32
    monkeypatch.setattr(causal_lm, "IMAGE_GRIDS", ((2, 2), (2, 3)))
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "keye_vl2_tiny", [
            "--training.expert_shard", shard,
            "--training.num_hidden_layers", layers,
            "--training.image_token_share", share,
        ]
    )
    depth = int(layers) or 2
    cases.check_routing_records(stepped, shard, depth)
    for rec in stepped:
        # top-8 of up to 32 keys: 36 + 24 x 8 selected pairs of 528
        assert rec["attn.select_kept_share"] == pytest.approx(228 / 528)
        assert rec["attn.select_tile_share"] == 1.0  # S=32: one tile
        # the tiny preset's attention is dense: the loss's block loop takes
        # whole rows (the kernels' causal sweep: 528 / 1,024 at 16,384)
        assert rec["attn.index_loss_tile_share"] == 1.0
        for i in range(1, depth + 1):  # near the initialiser: nearly flat
            assert 1.0 <= rec[f"attn.index_peak.{i}"] < 1.5
            # the share of a layer's blocks of query rows (one a batch
            # row, at 32 positions) whose rows tied at their threshold,
            # averaged over the global step's micro-batches: a gauge a layer
            assert 0.0 <= rec[f"attn.select_tie_block_share.{i}"] <= 1.0
        assert f"attn.select_tie_block_share.{depth + 1}" not in rec
        assert 0.0 <= rec["loss.index_kl"] < 0.5
        if float(share):
            assert 0.1 < rec["data.image_token_share"] <= 0.3
        else:
            assert rec["data.image_token_share"] == 0.0
    cases.check_kept_bytes_is_the_shapes(  # the model's default policy
        stepped, KEYE_VL2, "kernel_operands", state.params, "keye_vl2_tiny",
        num_hidden_layers=int(layers), expert_shard=shard,
    )
    # the indexer's loss reached the indexer: its leaves left the
    # initialiser (a LayerNorm's weight starts at exactly 1)
    first = state.params["layers"]["layer_0"]["indexer"]
    assert float(np.max(np.abs(np.asarray(first["k_norm_weight"]) - 1))) > 0
    assert float(np.max(np.abs(np.asarray(first["k_norm_bias"])))) > 0


def test_the_table_builds_keye():
    for size in ("keye_vl2_tiny", "keye_vl2_30b_a3b"):
        assert model_family(size) is KEYE_VL2
    cfg, model = build_model(
        "keye_vl2_tiny", num_hidden_layers=5, vocab_size=128,
        expert_shard="2/8",
    )
    assert model_family(model) is KEYE_VL2
    assert cfg.held_experts == (2, 1) and cfg.vocab_size == 128
    batch = next(KEYE_VL2.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert batch["position_ids"].shape == (3, 2, 16)
    assert batch["loss_weights"].shape == (2, 16)
    assert KEYE_VL2.tflops_per_sample(cfg, 16) > 0
    assert selected_pairs(cfg, 32) == 228
    assert KEYE_VL2.step_counters == ("moe.dropped_slots",)
    assert KEYE_VL2.sign_step_mask is None
    assert KEYE_VL2.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert {"attn.select_kept_share", "attn.select_tile_share",
            "attn.index_loss_tile_share", "attn.index_peak",
            "attn.select_tie_block_share", "loss.index_kl",
            "data.image_token_share"} <= set(KEYE_VL2.step_gauges)
    published = KeyeVL2Config.keye_vl2_30b_a3b()
    assert (published.hidden_size, published.num_attention_heads,
            published.num_key_value_heads, published.head_dim,
            published.moe_intermediate_size, published.num_experts,
            published.num_experts_per_tok, published.vocab_size,
            published.num_hidden_layers, published.rms_norm_eps,
            published.rope_theta, published.mrope_section,
            published.index_n_heads, published.index_head_dim,
            published.index_topk) == (
        2048, 32, 4, 128, 768, 128, 8, 151936, 48, 1e-6, 1e7, (16, 24, 24),
        16, 64, 2048)
    with pytest.raises(ValueError, match="must divide"):
        build_model("keye_vl2_tiny", expert_shard="0/3")
    with pytest.raises(ValueError, match="unknown model_size"):
        model_family("keye_vl2")


def test_the_share_of_image_spans_is_the_runs(tmp_path, monkeypatch):
    """``--training.image_token_share`` reaches the rows of a family that
    reads its positions from the batch — 0, the default: text rows that
    still carry three equal streams — and a family that reads none refuses
    it and builds no ``position_ids``."""
    assert KEYE_VL2.batch_positions and not DEEPSEEK_V3.batch_positions
    cfg, _model = build_model("keye_vl2_tiny")

    def _args(argv=()):
        return cases.trainer_args(tmp_path, "keye_vl2_tiny", argv)

    text = next(_make_batches(_args(), cfg, b"peer"))
    assert (text["loss_weights"] == 1).all()
    np.testing.assert_array_equal(
        text["position_ids"], np.broadcast_to(np.arange(32), (3, 2, 32))
    )
    monkeypatch.setattr(causal_lm, "IMAGE_GRIDS", ((2, 2), (2, 3)))
    spans = next(_make_batches(
        _args(["--training.image_token_share", "0.25"]), cfg, b"peer",
    ))
    assert 0.1 < 1 - spans["loss_weights"].mean() <= 0.3
    assert (spans["position_ids"][0] != spans["position_ids"][2]).any()
    sdar = [
        "--training.model_size", "sdar_tiny", "--training.seq_length", "16",
    ]
    cfg, _model = build_model("sdar_tiny")
    assert "position_ids" not in next(
        _make_batches(_args(sdar), cfg, b"peer")
    )
    with pytest.raises(ValueError, match="reads no positions"):
        _make_batches(
            _args(sdar + ["--training.image_token_share", "0.25"]), cfg,
            b"peer",
        )
