"""Kimi Linear's FLOP and byte model (benchmark/flops_kimi.py): the numbers
the issue reckoned, by part; the program's own FLOP model is the same
arithmetic; the parameter count is the tree's; the kernels' costs count the
heads the call HAS; the reducers read a trace that has the kernels and give
nothing from one that has not."""
import json
import os

import pytest

from benchmark import flops, flops_kimi, flops_moe, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes():
    with open(os.path.join(
        HERE, "configs", "kimi_linear_48b_a3b_s8192.json"
    )) as f:
        return json.load(f)["sizes"]


def test_the_parts_are_the_issues():
    sizes = _sizes()
    part = flops_kimi.kimi_parts_flops_per_token(sizes, 8192)
    total = sum(part.values())
    assert total / 1e6 == pytest.approx(423.1, abs=0.05)
    share = {name: 100 * value / total for name, value in part.items()}
    assert share["kda_projections"] == pytest.approx(19.5, abs=0.05)
    assert share["kda_rule"] == pytest.approx(1.4, abs=0.05)
    assert share["mla"] == pytest.approx(8.9, abs=0.05)
    assert share["dense_ffn"] == pytest.approx(30.1, abs=0.05)
    assert share["routed_ffn"] == pytest.approx(17.8, abs=0.05)
    assert share["head"] == pytest.approx(22.3, abs=0.05)
    assert flops_kimi.kimi_train_flops_per_sample(sizes, 8192) / 1e12 == (
        pytest.approx(10.40, abs=0.005)
    )
    # with every head held the mechanism would be 43.5 % of the step
    whole = flops_kimi.kimi_parts_flops_per_token(
        dict(sizes, held_heads=32), 8192
    )
    assert 100 * (whole["kda_projections"] + whole["kda_rule"]) / sum(
        whole.values()
    ) == pytest.approx(43.5, abs=0.1)
    # half of latent attention is the triangle
    triangle = 2 * 8 * (192 + 128) * 8193 / 2
    assert triangle / part["mla"] == pytest.approx(0.56, abs=0.01)


def test_the_programs_own_flop_model_is_the_same_arithmetic():
    from dedloc_tpu.models.kimi_linear import (
        KimiLinearConfig,
        kimi_linear_train_tflops_per_sample,
    )

    cfg = KimiLinearConfig(
        num_hidden_layers=5, vocab_size=20480, expert_shard=(0, 32),
        head_shard=(0, 4),
    )
    assert kimi_linear_train_tflops_per_sample(cfg, 8192) * 1e12 == (
        pytest.approx(flops_kimi.kimi_train_flops_per_sample(_sizes(), 8192))
    )


def test_the_parameters_are_the_issues():
    count = flops_kimi.kimi_parameters(_sizes())
    assert count == dict(
        kda_mixer=10_322_056, mla_mixer=8_274_432, dense_ffn=63_700_992,
        routed_ffn=64_291_072, held_experts=226_492_416, norms_a_layer=4_608,
        ends=94_374_144, total=464_825_120,
    )


def test_a_chunk_of_the_rule_is_11_7_mflop():
    chunk = flops_kimi.kda_chunk_flops(64, 128, 128)
    assert chunk["kda_fwd"] / 1e6 == pytest.approx(11.71, abs=0.005)
    assert chunk["kda_bwd"] / 1e6 == pytest.approx(31.63, abs=0.005)
    # 2 x 1.049 + 0.175 + 2 x 1.049 + 3 x 2.097 + 1.049, by hand
    assert chunk["kda_fwd"] == 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128 + (
        2 * 64 ** 3 / 3
    )


def test_the_kernels_costs_count_the_heads_the_call_has():
    sizes = _sizes()
    v5e = peaks.chip_peaks("TPU v5 lite")
    fwd = flops_kimi.kda_kernel_cost("kda_fwd", 1, 8, 8192, 128, 128, 64)
    bwd = flops_kimi.kda_kernel_cost("kda_bwd", 1, 8, 8192, 128, 128, 64)
    tokens = 8 * 8192
    states = 8 * 128 * 128 * 128 * 4  # 128 chunks a head
    assert fwd[1] == tokens * (4 * 128 * 2 + 129 * 4) + states
    assert bwd[1] == tokens * (7 * 128 * 2 + 2 * 129 * 4) + states
    assert fwd[0] == 1024 * flops_kimi.kda_chunk_flops(64, 128, 128)["kda_fwd"]
    # the bytes bind: 0.205 ms and 0.308 ms a call at 819 GB/s
    for cost, ms in ((fwd, 0.2052), (bwd, 0.3079)):
        least, which = flops.roofline_seconds(*cost, v5e)
        assert which == "memory" and least * 1e3 == pytest.approx(ms, abs=5e-4)
    with pytest.raises(KeyError):
        flops_kimi.kda_kernel_cost("kda_other", 1, 8, 8192, 128, 128, 64)
    # the two-width kernels at the 8 HELD heads: a quarter of the
    # published 32's work, which the accepted reducer would have counted
    for kernel in ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv"):
        held = flops_kimi.held_mla_kernel_cost(kernel, 1, sizes, 8192)
        whole = flops_moe.mla_kernel_cost(
            kernel, 1, 32, 8192, 192, 128, 512, 512
        )
        assert held[0] * 4 == pytest.approx(whole[0])


class _Run:
    """What a reducer reads of a run, over a hand-made trace."""

    def __init__(self, ops, records=()):
        from benchmark import trace as T

        self.trace = {"device0": {
            T.OPS: [(name, 0.0, seconds * 1e9) for name, seconds in ops],
            T.MODULES: [("jit_accumulate_step(1)", 0.0, 0.15e9)] * 2,
        }} if ops is not None else None
        self.config = {"sizes": _sizes()}
        self.device_kind = "TPU v5 lite"
        self.step_records = list(records)
        self.args = None

        class role:
            @staticmethod
            def microbatch_rows_per_device(_args):
                return 1

        self.role = role

    def seq_length(self):
        return 8192

    def program(self, logical):
        return "accumulate_step"


def test_the_reducers_read_what_is_there_and_nothing_else():
    from benchmark.reducers import (
        kimi_kda_gauge,
        kimi_kda_time,
        kimi_kernel_roofline,
        kimi_mfu,
    )

    ops = [("kda_fwd", 0.5e-3)] * 8 + [("kda_bwd", 1.0e-3)] * 8 + [
        ("flash_mla_fwd", 2.0e-3)] * 2 + [("fusion.1", 0.1)]
    run = _Run(ops)
    assert kimi_kernel_roofline.reduce(run, {"kernel": "kda_fwd"}) == (
        pytest.approx(100 * 0.2052 / 0.5, rel=2e-3)
    )
    assert kimi_kernel_roofline.reduce(run, {"kernel": "kda_bwd"}) == (
        pytest.approx(100 * 0.3079 / 1.0, rel=2e-3)
    )
    assert 0 < kimi_kernel_roofline.reduce(
        run, {"kernel": "flash_mla_fwd"}
    ) < 100
    assert kimi_kernel_roofline.reduce(
        run, {"kernel": "flash_mla_bwd_dq"}
    ) is None
    # four layers forward + backward, two executions traced
    assert kimi_kda_time.reduce(run, {}) == pytest.approx(6.0)
    assert kimi_mfu.reduce(run, {}) == pytest.approx(
        100 * 10.398e12 / 0.15 / 197e12, rel=1e-3
    )
    # a program without the kernels (the parent): nothing, no error
    older = _Run([("fusion.1", 0.1)])
    assert kimi_kernel_roofline.reduce(older, {"kernel": "kda_fwd"}) is None
    assert kimi_kda_time.reduce(older, {}) is None
    untraced = _Run(None)
    assert kimi_kda_time.reduce(untraced, {}) is None
    assert kimi_mfu.reduce(untraced, {}) is None
    records = [
        {"kda.beta_mean.1": 0.4, "kda.beta_mean.2": 0.6,
         "kda.chunk_log_decay_min.1": -90.0, "kda.chunk_log_decay_min.2": -50.0},
        {"kda.beta_mean.1": 0.5, "kda.beta_mean.2": 0.5,
         "kda.chunk_log_decay_min.1": -70.0, "kda.chunk_log_decay_min.2": -80.0},
        {"loss": 1.0},
    ]
    gauged = _Run(None, records)
    assert kimi_kda_gauge.reduce(
        gauged, {"gauge": "kda.beta_mean", "over": "mean"}
    ) == pytest.approx(0.5)
    assert kimi_kda_gauge.reduce(
        gauged, {"gauge": "kda.chunk_log_decay_min", "over": "min"}
    ) == pytest.approx(-85.0)
    assert kimi_kda_gauge.reduce(
        gauged, {"gauge": "kda.state_abs_max", "over": "max"}
    ) is None
