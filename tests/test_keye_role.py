"""Keye-VL-2.0's language model through the trainer role:
``--training.model_size keye_vl2_tiny`` makes global steps solo on the CPU
through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as every
other model, on rows with image spans (three position streams a token, no
loss on an image label); the step records carry the routing gauges, the
selection's two shares, an index peak a layer, the indexer's own loss term
and the image share; the held experts' gradients land in the accumulator
(gradient sinks); the indexer's leaves move (its loss reaches them)."""
import json

import jax
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.data import causal_lm
from dedloc_tpu.models.keye_vl2 import KeyeVL2Config, selected_pairs
from dedloc_tpu.parallel.train_step import stash_bytes
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    KEYE_VL2,
    build_loss_fn,
    build_model,
    model_family,
)
from dedloc_tpu.roles.trainer import _make_batches, run_trainer


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "keye_vl2_tiny",
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


@pytest.mark.parametrize(
    "shard,layers,share", [("0/1", "0", "0.25"), ("1/4", "5", "0")],
    ids=["whole_with_image_spans", "share_1_of_4_cut_to_5_text_rows"],
)
def test_keye_tiny_trainer_makes_global_steps(tmp_path, monkeypatch, shard,
                                              layers, share):
    # image spans that fit a quarter of a row of 32
    monkeypatch.setattr(causal_lm, "IMAGE_GRIDS", ((2, 2), (2, 3)))
    events = tmp_path / "events.jsonl"
    args = _args(tmp_path, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "9",
        "--training.expert_shard", shard,
        "--training.num_hidden_layers", layers,
        "--training.image_token_share", share,
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
    ])
    state = run_trainer(args)
    assert int(state.step) >= 2
    depth = int(layers) or 2
    log = [json.loads(line) for line in events.read_text().splitlines()]
    stepped = [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]
    assert len(stepped) >= 2
    count = int(shard.split("/")[1])
    for rec in stepped:
        assert rec["moe.dropped_slots"] == 0.0
        assert all(
            rec[f"moe.load_max_over_mean.{i}"] >= 1.0
            for i in range(1, depth + 1)
        )
        assert f"moe.load_max_over_mean.{depth + 1}" not in rec
        assert rec["moe.local_slot_share"] == pytest.approx(
            1.0 / count, abs=0.0 if count == 1 else 0.25
        )
        assert rec["moe.grad_sink_leaves"] == 3.0 * depth
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
    losses = [rec["loss"] for rec in stepped if "loss" in rec]
    assert all(np.isfinite(losses))
    cfg, model = build_model(
        "keye_vl2_tiny", num_hidden_layers=int(layers), expert_shard=shard,
    )
    assert cfg.remat_policy == "kernel_operands"  # the model's default
    kept = stash_bytes(  # the same number, from the shapes alone
        build_loss_fn(model), state.params,
        next(KEYE_VL2.synthetic_batches(cfg, 2, 32, 0)),
        jax.random.PRNGKey(0),
    )
    assert {rec["remat.kept_bytes"] for rec in stepped} == {float(kept)}
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
    text = next(_make_batches(_args(tmp_path), cfg, b"peer"))
    assert (text["loss_weights"] == 1).all()
    np.testing.assert_array_equal(
        text["position_ids"], np.broadcast_to(np.arange(32), (3, 2, 32))
    )
    monkeypatch.setattr(causal_lm, "IMAGE_GRIDS", ((2, 2), (2, 3)))
    spans = next(_make_batches(
        _args(tmp_path, ["--training.image_token_share", "0.25"]), cfg,
        b"peer",
    ))
    assert 0.1 < 1 - spans["loss_weights"].mean() <= 0.3
    assert (spans["position_ids"][0] != spans["position_ids"][2]).any()
    sdar = [
        "--training.model_size", "sdar_tiny", "--training.seq_length", "16",
    ]
    cfg, _model = build_model("sdar_tiny")
    assert "position_ids" not in next(
        _make_batches(_args(tmp_path, sdar), cfg, b"peer")
    )
    with pytest.raises(ValueError, match="reads no positions"):
        _make_batches(
            _args(tmp_path, sdar + ["--training.image_token_share", "0.25"]),
            cfg, b"peer",
        )
