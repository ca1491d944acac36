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

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.decoder import EXPERT_LEAVES
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


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "sdar_tiny",
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


def _stepped(events):
    log = [json.loads(line) for line in events.read_text().splitlines()]
    return [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]


@pytest.mark.parametrize(
    "shard,layers", [("0/1", "0"), ("1/4", "2")],
    ids=["whole", "share_1_of_4_cut_to_2"],
)
def test_sdar_tiny_trainer_makes_global_steps(tmp_path, shard, layers):
    events = tmp_path / "events.jsonl"
    state = run_trainer(_args(tmp_path, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "9",
        "--training.expert_shard", shard,
        "--training.num_hidden_layers", layers,
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
    ]))
    assert int(state.step) >= 2
    n_layers = int(layers) or 3
    stepped = _stepped(events)
    assert len(stepped) >= 2
    count = int(shard.split("/")[1])
    for rec in stepped:
        assert rec["moe.dropped_slots"] == 0.0
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        assert all(
            rec[f"moe.load_max_over_mean.{i}"] >= 1.0
            for i in range(1, n_layers + 1)
        )
        assert rec["moe.local_slot_share"] == pytest.approx(
            1.0 / count, abs=0.0 if count == 1 else 0.25
        )
        assert rec["moe.grad_sink_leaves"] == 3.0 * n_layers
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
    run_trainer(_args(tmp_path, [
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
    """Every layer's expert leaves are sinks of
    ``make_accumulate_step(build_loss_fn(model))``: float32 sums where the
    plain step adds bf16-rounded gradients, every other leaf exactly the
    plain step's."""
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )

    cfg, model = build_model("sdar_tiny")
    source = SDAR_MOE.synthetic_batches(cfg, 2, 32, 1)
    batches = [
        jax.tree.map(jnp.asarray, drop_collator_keys(next(source)))
        for _ in range(2)
    ]
    params = model.init(jax.random.PRNGKey(0), batches[0]["input_ids"])[
        "params"
    ]
    loss_fn = build_loss_fn(model)

    def two(step):
        acc, n = zeros_like_grads(params), jnp.zeros([], jnp.int32)
        for i, batch in enumerate(batches):
            acc, n, metrics = step(params, acc, n, batch, jax.random.PRNGKey(i))
        return acc, metrics

    sunk, metrics = two(make_accumulate_step(loss_fn))
    plain, plain_metrics = two(make_accumulate_step(loss_fn.loss))
    assert float(metrics["moe.grad_sink_leaves"]) == 9.0  # 3 layers x 3
    assert float(plain_metrics["moe.grad_sink_leaves"]) == 0.0
    assert float(metrics["loss"]) == float(plain_metrics["loss"])
    seen = 0
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(sunk), jax.tree.leaves(plain)
    ):
        if path[-1].key in EXPERT_LEAVES:
            seen += 1
            apart = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            assert 0.0 < apart < 2.0 ** -8, (path, apart)
        else:
            np.testing.assert_array_equal(got, want, err_msg=str(path))
    assert seen == 9  # three layers, each with leaves of its own
