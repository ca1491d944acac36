"""The band-and-global expert decoder through the trainer role:
``--training.model_size smallthinker_tiny`` makes global steps solo on the
CPU through the same ``run_trainer`` / ``CollaborativeOptimizer`` path as
every other model; no leaf is stepped by a sign; the step records carry the
routing gauges, ``attn.band_tile_share`` and the counter that must read 0;
the held experts' gradients land in the accumulator (gradient sinks) for
ReLU-gated experts as for the SiLU ones."""
import json

import jax
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.decoder import EXPERT_LEAVES
from dedloc_tpu.models.smallthinker import SmallThinkerConfig
from dedloc_tpu.parallel.train_step import stash_bytes
from dedloc_tpu.roles.common import (
    DEEPSEEK_V3,
    SMALLTHINKER,
    build_loss_fn,
    build_model,
    model_family,
)
from dedloc_tpu.roles.trainer import run_trainer


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "smallthinker_tiny",
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
    "shard,layers", [("0/1", "0"), ("1/4", "6")],
    ids=["whole", "share_1_of_4_cut_to_6"],
)
def test_smallthinker_tiny_trainer_makes_global_steps(tmp_path, shard, layers):
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
    assert int(state.step) >= 2
    n_layers = int(layers) or 8
    log = [json.loads(line) for line in events.read_text().splitlines()]
    stepped = [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]
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
        assert rec["attn.band_tile_share"] == 1.0  # S=32: one tile
        assert "moe.bias_abs_max" not in rec  # no bias leaf, no sign step
    losses = [rec["loss"] for rec in stepped if "loss" in rec]
    assert all(np.isfinite(losses))
    # the remat policy's counter: what the builder read from the shapes
    cfg, model = build_model(
        "smallthinker_tiny", num_hidden_layers=int(layers), expert_shard=shard
    )
    assert cfg.remat_policy == "whole_mixer"
    kept = stash_bytes(  # the same number, from the shapes alone
        build_loss_fn(model), state.params,
        next(SMALLTHINKER.synthetic_batches(cfg, 2, 32, 0)),
        jax.random.PRNGKey(0),
    )
    assert {rec["remat.kept_bytes"] for rec in stepped} == {float(kept)}


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
    """Every layer's expert leaves are sinks of
    ``make_accumulate_step(build_loss_fn(model))``: float32 sums where the
    plain step adds bf16-rounded gradients, every other leaf exactly the
    plain step's."""
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )

    cfg, model = build_model("smallthinker_tiny", num_hidden_layers=5)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 2, 32), 0, cfg.vocab_size
    )
    params = model.init(jax.random.PRNGKey(0), ids[0])["params"]
    batches = [{"input_ids": x, "labels": jnp.roll(x, -1, 1)} for x in ids]
    loss_fn = build_loss_fn(model)

    def two(step):
        acc, n = zeros_like_grads(params), jnp.zeros([], jnp.int32)
        for i, batch in enumerate(batches):
            acc, n, metrics = step(params, acc, n, batch, jax.random.PRNGKey(i))
        return acc, metrics

    sunk, metrics = two(make_accumulate_step(loss_fn))
    plain, plain_metrics = two(make_accumulate_step(loss_fn.loss))
    assert float(metrics["moe.grad_sink_leaves"]) == 15.0  # 5 layers x 3
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
    assert seen == 15
