"""The expert decoder through the trainer role: ``--training.model_size
kanana2_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as ALBERT and Ouro; every
correction-bias entry moves by exactly ±gamma or 0 a global step; the step
records carry the routing gauges and the counter that must read 0; and the
model's row of ``tools/tpu_aot.py`` (its kernels and accumulate_step compiled
for a TPU v5e WITHOUT a chip: ``tests/tpu_aot_rows.py``)."""
import jax
import numpy as np
import pytest

import decoder_cases as cases
from dedloc_tpu.models.decoder import BIAS, EXPERT_LEAVES
from dedloc_tpu.models.deepseek_v3 import DeepseekV3Config
from dedloc_tpu.roles.common import DEEPSEEK_V3, build_model, model_family
from tpu_aot_rows import tpu_aot


@pytest.mark.parametrize("shard", ["0/1", "1/4"])
def test_kanana2_tiny_trainer_steps_the_bias_by_gamma(tmp_path, shard):
    state, stepped, _records = cases.run_tiny_trainer(
        tmp_path, "kanana2_tiny", ["--training.expert_shard", shard]
    )
    steps = int(state.step)
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

    cases.check_routing_records(stepped, shard, 2, slack=0.2)
    for n, rec in enumerate(stepped, start=1):
        # the scanned stack's three held leaves in bf16 beside the sinks,
        # cast once a global step (``test_compute_copies.py``)
        assert rec["moe.compute_copy_leaves"] == 6.0
        assert rec["moe.compute_copy_builds"] == 1.0
        # the walk's counter (``parallel/moe.py``): a share of the held rows
        assert 0.0 <= rec["moe.bulk_row_share"] <= 1.0
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
    """Three stacked leaves, two expert layers each."""
    _model, params, batches, loss_fn = cases.sink_case("kanana2_tiny")
    cases.check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
        params, batches, loss_fn, sink_leaves=6.0, expert_leaves=3
    )


def test_accumulate_step_under_a_mesh_keeps_the_plain_path():
    """Under a data mesh the gradient is a mean over devices and the
    accumulator is not: the step lowers to the program it was."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )

    _model, params, batches, loss_fn = cases.sink_case("kanana2_tiny")
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

    _model, params, batches, loss_fn = cases.sink_case("kanana2_tiny")
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


def test_two_width_kernels_contract_over_a_heads_own_lane_tiles():
    """Latent attention (q/k 192 wide, v and out 128; two heads a column
    block of 384 / 256 lanes), compiled for a v5e alone and inside
    kanana-2's accumulate_step at the cell's cut: every per-head product
    of the three kernels contracts over, and lands in, the head's own lane
    window — 256 of the 384 q/k lanes, the head's own 128-lane tile of v,
    dO and out — as each call's metadata says (``flash_windows``)."""
    rows = tpu_aot("mla_kernels", "kanana_accumulate_step")
    windowed = {
        "qk_window": 256, "qk_block": 384, "v_window": 128, "v_block": 256,
    }
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_mla_fwd": windowed, "flash_mla_bwd_tiled": windowed,
        }
        # dk / dv of a program's OWN kv heads are resident in the backward
        assert row["flash_heads"] == {
            "flash_mla_fwd": 8, "flash_mla_bwd_tiled": 4,
        }
    # the dense layer and the scanned expert layers: a forward site each
    assert rows["kanana_accumulate_step"]["flash_fwd_forms"] == {
        "one_tile": 0, "tiles": 2
    }
    # the routed loop's backward sums into the accumulator's expert leaves
    # (gradient sinks): no add pass of its own over one (3 before PR 33)
    passes = rows["kanana_accumulate_step"]["expert_grad_passes"]
    assert passes["adds"] == 0
    # the routed walk (PR 42): a bulk and a tail loop a direction in the
    # scanned layer's body (2 loops with the single-size walk), the three
    # ``old + term`` adds of each backward loop riding their dots' fusions
    assert (passes["tile_loops"], passes["fused_adds"],
            passes["loose_adds"]) == (4, 6, 0)
    # the held matrices arrive in bf16 (PR 50: the step's compute-dtype
    # copies, stacked like the leaves): no whole-matrix float32 -> bf16 pass
    # inside the program (3 before: XLA hoisted the stack's casts out of the
    # scan), and the bf16 stack's 0.30 GB of scratch gone (3,557,284,864)
    assert passes["held_casts"] == 0
    assert rows["kanana_accumulate_step"]["memory"]["temp_bytes"] <= 3.3e9
    # 0.05 GB under the line: the layers keep the kernels' OUTPUTS alone
    assert rows["kanana_accumulate_step"]["remat_policy"] == "kernel_outputs"
