"""Kimi Linear through the trainer role: ``--training.model_size
kimi_linear_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as every other model, whole
and at a share of heads and experts; the step records carry the routing
gauges and the three KDA gauges a KDA layer; the held experts' gradients land
in the accumulator; a head share that does not divide is refused."""
import json

import jax
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.kimi_linear import KDA_GAUGES, KimiLinearConfig
from dedloc_tpu.parallel.train_step import stash_bytes
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    KIMI_LINEAR,
    build_loss_fn,
    build_model,
    model_family,
)
from dedloc_tpu.roles.trainer import run_trainer


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "kimi_linear_tiny",
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


def test_kimi_tiny_trainer_makes_global_steps_at_a_share(tmp_path):
    events = tmp_path / "events.jsonl"
    args = _args(tmp_path, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "9",
        "--training.expert_shard", "1/4", "--training.head_shard", "1/2",
        "--training.num_hidden_layers", "5",
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
    ])
    state = run_trainer(args)
    assert int(state.step) >= 2
    log = [json.loads(line) for line in events.read_text().splitlines()]
    stepped = [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]
    assert len(stepped) >= 2
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
    assert all(np.isfinite([rec["loss"] for rec in stepped if "loss" in rec]))
    cfg, model = build_model(
        "kimi_linear_tiny", num_hidden_layers=5, expert_shard="1/4",
        head_shard="1/2",
    )
    assert cfg.remat_policy == "whole_mixer"  # the model's default
    assert (cfg.held_kda_heads, cfg.held_attention_heads) == (2, 2)
    kept = stash_bytes(  # the same number, from the shapes alone
        build_loss_fn(model), state.params,
        next(KIMI_LINEAR.synthetic_batches(cfg, 2, 32, 0)),
        jax.random.PRNGKey(0),
    )
    assert {rec["remat.kept_bytes"] for rec in stepped} == {float(kept)}
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
    args = _args(tmp_path, ["--training.head_shard", "1/4"])
    assert args.training.head_shard == "1/4"
    assert _args(tmp_path).training.head_shard == "0/1"  # today's programs
