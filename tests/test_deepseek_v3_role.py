"""The expert decoder through the trainer role: ``--training.model_size
kanana2_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as ALBERT and Ouro; every
correction-bias entry moves by exactly ±gamma or 0 a global step; the step
records carry the routing gauges and the counter that must read 0."""
import json

import jax
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.decoder import BIAS, EXPERT_LEAVES
from dedloc_tpu.models.deepseek_v3 import DeepseekV3Config
from dedloc_tpu.roles.common import DEEPSEEK_V3, build_model, model_family
from dedloc_tpu.roles.trainer import run_trainer


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "kanana2_tiny",
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


def _two_micro_batches(accumulate, params, batches):
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import zeros_like_grads

    acc, n = zeros_like_grads(params), jnp.zeros([], jnp.int32)
    for i, batch in enumerate(batches):
        acc, n, metrics = accumulate(
            params, acc, n, batch, jax.random.PRNGKey(i)
        )
    return acc, metrics


def _sink_case(size, **overrides):
    """(model, params, two batches, the table's loss) of a tiny decoder."""
    import jax.numpy as jnp

    from dedloc_tpu.roles.common import build_loss_fn

    cfg, model = build_model(size, **overrides)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 32), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids[0])["params"]
    batches = [{"input_ids": x, "labels": jnp.roll(x, -1, 1)} for x in ids]
    return model, params, batches, build_loss_fn(model)


@pytest.mark.parametrize("shard", ["0/1", "1/4"])
def test_kanana2_tiny_trainer_steps_the_bias_by_gamma(tmp_path, shard):
    events = tmp_path / "events.jsonl"
    args = _args(tmp_path, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", "9",
        "--training.expert_shard", shard,
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
    ])
    state = run_trainer(args)
    steps = int(state.step)
    assert steps >= 2
    gamma = DeepseekV3Config.bias_update_speed
    bias = np.asarray(state.params["layers"]["block"]["mlp"][BIAS])
    assert bias.shape == (2, 16)
    # it started at 0: after n steps every entry is a whole number of gammas,
    # of the parity n allows only if no step left it where it was
    in_gammas = bias / gamma
    np.testing.assert_allclose(in_gammas, np.round(in_gammas), atol=1e-3)
    assert np.abs(in_gammas).max() <= steps + 1e-3 and np.abs(bias).max() > 0
    # the sign rule keeps no moments for the leaf
    from dedloc_tpu.optim.lamb import ScaleByLambState
    from dedloc_tpu.parallel.train_step import _find_opt_state

    lamb_state = _find_opt_state(state.opt_state, ScaleByLambState)
    moments = [
        np.asarray(m["layers"]["block"]["mlp"][BIAS])
        for m in (lamb_state.mu, lamb_state.nu)
    ]
    assert all(float(np.abs(m).max()) == 0.0 for m in moments)

    log = [json.loads(line) for line in events.read_text().splitlines()]
    stepped = [
        e for e in log if e.get("event") == "step.record" and e.get("stepped")
    ]
    assert len(stepped) >= 2
    count = int(shard.split("/")[1])
    for n, rec in enumerate(stepped, start=1):
        assert rec["moe.dropped_slots"] == 0.0
        # the scanned stack's three held leaves in bf16 beside the sinks,
        # cast once a global step (``test_compute_copies.py``)
        assert rec["moe.compute_copy_leaves"] == 6.0
        assert rec["moe.compute_copy_builds"] == 1.0
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
        assert all(rec[f"moe.load_max_over_mean.{i}"] >= 1.0 for i in (1, 2))
        assert rec["moe.local_slot_share"] == pytest.approx(
            1.0 / count, abs=0.0 if count == 1 else 0.2
        )
        # read with the loss BEFORE this step's apply: n − 1 steps so far
        assert rec["moe.bias_abs_max"] <= (n - 1) * gamma + 1e-9
    assert stepped[-1]["moe.bias_abs_max"] > 0


def test_the_table_builds_the_expert_decoder():
    for size in ("kanana2_tiny", "kanana2_30b_a3b"):
        assert model_family(size) is DEEPSEEK_V3
    cfg, model = build_model(
        "kanana2_tiny", num_hidden_layers=4, vocab_size=128,
        expert_shard="2/8",
    )
    assert model_family(model) is DEEPSEEK_V3
    assert cfg.num_expert_layers == 3 and cfg.held_experts == (4, 2)
    assert cfg.vocab_size == 128
    batch = next(DEEPSEEK_V3.synthetic_batches(cfg, 2, 16, 0))
    assert batch["input_ids"].max() < 128  # ids over the held slice
    assert DEEPSEEK_V3.tflops_per_sample(cfg, 16) > 0
    published = DeepseekV3Config.kanana2_30b_a3b()
    assert (published.hidden_size, published.n_routed_experts,
            published.num_experts_per_tok, published.kv_lora_rank) == (
        2048, 128, 6, 512)
    with pytest.raises(ValueError, match="no routed expert layer"):
        build_model("ouro_tiny", expert_shard="0/2")
    with pytest.raises(ValueError, match="must divide"):
        build_model("kanana2_tiny", expert_shard="0/3")


def test_accumulate_step_leaves_expert_gradients_in_the_accumulator():
    """``make_accumulate_step(build_loss_fn(model))`` — the call the role and
    the benchmark make — hands the accumulator's expert leaves to the tile
    loop: over two micro-batches they hold the float32 sums the plain step
    rounds to bf16 first, every other leaf is the plain step's exactly."""
    from dedloc_tpu.parallel.train_step import (
        GradSinkLoss,
        make_accumulate_step,
    )

    _model, params, batches, loss_fn = _sink_case("kanana2_tiny")
    assert isinstance(loss_fn, GradSinkLoss)
    sunk, metrics = _two_micro_batches(
        make_accumulate_step(loss_fn), params, batches
    )
    plain, plain_metrics = _two_micro_batches(
        make_accumulate_step(loss_fn.loss), params, batches
    )
    # three stacked leaves, two expert layers each
    assert float(metrics["moe.grad_sink_leaves"]) == 6.0
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
    assert seen == 3


def test_accumulate_step_under_a_mesh_keeps_the_plain_path():
    """Under a data mesh the gradient is a mean over devices and the
    accumulator is not: the step lowers to the program it was."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )

    _model, params, batches, loss_fn = _sink_case("kanana2_tiny")
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    operands = (
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32),
        batches[0], jax.random.PRNGKey(0),
    )
    meshed = make_accumulate_step(loss_fn, mesh=mesh).lower(*operands)
    assert meshed.as_text() == make_accumulate_step(
        loss_fn.loss, mesh=mesh
    ).lower(*operands).as_text()
    assert meshed.as_text() != make_accumulate_step(loss_fn).lower(
        *operands
    ).as_text()
    _acc, _n, metrics = meshed.compile()(*operands)
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0


@pytest.mark.parametrize("stray", ["router", "lm_head"])
def test_a_marked_leaf_that_no_module_reads_stops_the_trace(stray):
    """A sink nobody sums into comes back zero, and the step would write
    that zero over the accumulated gradient: tracing it raises instead."""
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        GradSinkLoss,
        make_accumulate_step,
        zeros_like_grads,
    )

    _model, params, batches, loss_fn = _sink_case("kanana2_tiny")
    too_wide = GradSinkLoss(loss_fn.loss, lambda tree: (
        jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key in EXPERT_LEAVES + (stray,), tree
        )
    ))
    with pytest.raises(ValueError, match=f"read by no module.*{stray}"):
        make_accumulate_step(too_wide).lower(
            params, zeros_like_grads(params), jnp.zeros([], jnp.int32),
            batches[0], jax.random.PRNGKey(0),
        )
