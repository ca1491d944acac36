"""The block-diffusion expert decoder through the trainer role:
``--training.model_size sdar_tiny`` makes global steps solo on the CPU
through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as every
other model, its batch three arrays a row; a finite, falling loss; the step
records carry the routing gauges, ``diffusion.masked_share``,
``attn.bd_tile_share`` and the two counters (``moe.dropped_slots`` must
read 0); the held experts' gradients land in the accumulator (gradient
sinks)."""
import json

import jax
import numpy as np
import pytest

import decoder_cases as cases
from dedloc_tpu.models.sdar_moe import SdarMoeConfig
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    SDAR_MOE,
    build_loss_fn,
    build_model,
    drop_collator_keys,
    model_family,
)
from dedloc_tpu.roles.trainer import run_trainer


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "2")],
    ids=["whole", "share_1_of_4_cut_to_2"],
)
def test_sdar_tiny_trainer_makes_global_steps(tmp_path, shard, layers):
    _state, stepped, _records = cases.run_tiny_trainer(tmp_path, "sdar_tiny", [
        "--training.expert_shard", shard,
        "--training.num_hidden_layers", layers,
    ])
    cases.check_routing_records(stepped, shard, int(layers) or 3)
    for rec in stepped:
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        assert 0.0 < rec["diffusion.masked_share"] < 1.0
        assert rec["diffusion.masked_tokens"] > 0  # the step's total
        assert rec["attn.bd_tile_share"] == 1.0  # L=32: one tile a stream
        assert "moe.bias_abs_max" not in rec  # no bias leaf, no sign step


def test_the_loss_falls(tmp_path, monkeypatch):
    """Global steps of the tiny preset over FOUR fixed micro-batches (rows,
    noise and weights), cycled: the weighted masked-position loss of fresh
    noise is heavy-tailed by construction (1 / t weights: a step's loss
    says more of its draw of t than of the model), so the test reads what
    the model makes of batches it sees again; early and late steps are
    compared by their means."""
    import itertools

    from dedloc_tpu.data.block_diffusion import block_diffusion_batches
    from dedloc_tpu.roles import trainer as role

    def four_batches(args, cfg, public_key, slice_batch=None):
        rng = np.random.default_rng(0)
        rows = (rng.integers(1, 9, (2, 32), dtype=np.int32) for _ in range(4))
        return itertools.cycle(list(block_diffusion_batches(
            rows, cfg.block_length, cfg.mask_token_id, seed=1
        )))

    monkeypatch.setattr(role, "_make_batches", four_batches)
    log = tmp_path / "train.jsonl"
    run_trainer(cases.trainer_args(tmp_path, "sdar_tiny", [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "91",
        "--training.learning_rate", "0.02",
        "--training.total_steps", "100",
        "--training.train_log_path", str(log),
    ]))
    losses = [json.loads(line)["loss"] for line in log.read_text().splitlines()]
    assert len(losses) >= 30 and all(np.isfinite(losses))
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.5, losses


def test_the_table_builds_the_block_diffusion_decoder():
    for size in ("sdar_tiny", "sdar_30b_a3b"):
        assert model_family(size) is SDAR_MOE
    cfg, model = build_model(
        "sdar_tiny", num_hidden_layers=2, vocab_size=128, expert_shard="2/8",
    )
    assert model_family(model) is SDAR_MOE
    assert cfg.held_experts == (4, 2) and cfg.vocab_size == 128
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (8, 1)
    batch = next(SDAR_MOE.synthetic_batches(cfg, 2, 16, 0))
    assert batch["labels"].max() < 127  # ids over the held slice, no mask
    assert batch["input_ids"].max() == 127 == cfg.mask_token_id
    assert sorted(drop_collator_keys(batch)) == [
        "input_ids", "labels", "loss_weights"
    ]
    assert SDAR_MOE.tflops_per_sample(cfg, 16) > 0
    assert SDAR_MOE.step_counters == (
        "moe.dropped_slots", "diffusion.masked_tokens"
    )
    assert SDAR_MOE.sign_step_mask is None
    assert SDAR_MOE.grad_sink_mask is DEEPSEEK_V3.grad_sink_mask
    assert {"attn.bd_tile_share", "diffusion.masked_share"} <= set(
        SDAR_MOE.step_gauges
    )
    published = SdarMoeConfig.sdar_30b_a3b()
    assert (published.hidden_size, published.num_attention_heads,
            published.num_key_value_heads, published.head_dim,
            published.moe_intermediate_size, published.num_experts,
            published.num_experts_per_tok, published.rope_theta,
            published.rms_norm_eps, published.vocab_size,
            published.num_hidden_layers, published.max_position_embeddings,
            published.block_length) == (
        2048, 32, 4, 128, 768, 128, 8, 1e6, 1e-6, 151936, 48, 32768, 4)
    with pytest.raises(ValueError, match="must divide"):
        build_model("sdar_tiny", expert_shard="0/3")
    with pytest.raises(ValueError, match="unknown model_size"):
        model_family("sdar_30b")


def test_accumulate_step_leaves_expert_gradients_in_the_accumulator():
    """Three layers, each with leaves of its own, on the family's own
    batches (three arrays a row)."""
    import jax.numpy as jnp

    cfg, model = build_model("sdar_tiny")
    source = SDAR_MOE.synthetic_batches(cfg, 2, 32, 1)
    batches = [
        jax.tree.map(jnp.asarray, drop_collator_keys(next(source)))
        for _ in range(2)
    ]
    params = model.init(jax.random.PRNGKey(0), batches[0]["input_ids"])[
        "params"
    ]
    cases.check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
        params, batches, build_loss_fn(model), sink_leaves=9.0,
        expert_leaves=9,
    )
