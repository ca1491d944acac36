"""Nemotron-H's FLOP and byte model (benchmark/flops_nemotron.py): the
numbers the issue reckoned, by part; the program's own FLOP model is the same
arithmetic; the parameter count is the tree's; the kernels' costs count the
heads the call HAS; the reducers read a trace that has the kernels and give
nothing from one that has not."""
import json
import os

import pytest

from benchmark import flops, flops_lfm2, flops_nemotron, peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes():
    with open(os.path.join(
        HERE, "configs", "nemotron3_nano_30b_a3b_s8192.json"
    )) as f:
        return json.load(f)["sizes"]


def test_the_parts_are_the_issues():
    sizes = _sizes()
    part = flops_nemotron.nemotron_parts_flops_per_token(sizes, 8192)
    total = sum(part.values())
    assert total / 1e6 == pytest.approx(410.5, abs=0.05)
    share = {name: 100 * value / total for name, value in part.items()}
    assert share["mamba_projections"] == pytest.approx(28.3, abs=0.05)
    assert share["mamba_scan"] == pytest.approx(1.25, abs=0.05)
    assert share["attention_projections"] + share["attention_triangle"] == (
        pytest.approx(13.9, abs=0.05)
    )
    assert share["attention_triangle"] > share["attention_projections"]
    assert share["shared_expert"] == pytest.approx(29.2, abs=0.05)
    assert share["routed_experts"] == pytest.approx(5.5, abs=0.05)
    assert share["router"] + share["shared_expert"] + share[
        "routed_experts"
    ] == pytest.approx(35.1, abs=0.1)
    assert share["head"] == pytest.approx(21.5, abs=0.05)
    assert flops_nemotron.nemotron_train_flops_per_sample(
        sizes, 8192
    ) / 1e12 == pytest.approx(10.09, abs=0.005)


def test_the_programs_own_flop_model_is_the_same_arithmetic():
    from dedloc_tpu.models.nemotron_h import (
        NemotronHConfig,
        nemotron_h_train_tflops_per_sample,
    )

    cfg = NemotronHConfig(
        num_hidden_layers=7, vocab_size=16384, expert_shard=(0, 16),
        head_shard=(0, 2),
    )
    assert nemotron_h_train_tflops_per_sample(cfg, 8192) * 1e12 == (
        pytest.approx(
            flops_nemotron.nemotron_train_flops_per_sample(_sizes(), 8192)
        )
    )


def test_the_parameters_are_the_issues():
    count = flops_nemotron.nemotron_parameters(_sizes())
    assert count == dict(
        mamba_mixer=19_371_104, attention_mixer=11_698_176,
        routed_ffn=100_122_752, held_experts=239_468_544, norm_a_layer=2_688,
        ends=88_083_072, total=458_281_632,
    )
    # the lever: a quarter of the heads
    lever = dict(_sizes(), held_mamba_heads=16, held_groups=2, held_heads=8)
    assert flops_nemotron.nemotron_parameters(lever)["total"] == 423_719_952


def test_a_groups_chunk_of_the_scan_is_54_5_mflop():
    chunk = flops_nemotron.ssd_chunk_flops(128, 8, 64, 128)
    assert chunk["ssd_fwd"] / 1e6 == pytest.approx(54.5, abs=0.05)
    # C Bᵀ 4.2, the intra product 16.8, the state's read and update 16.8 each
    assert chunk["ssd_fwd"] == 2 * 128 * 128 * 128 + 8 * 2 * 128 * 128 * 64 + (
        2 * 8 * 2 * 128 * 128 * 64
    )
    assert chunk["ssd_bwd"] / 1e6 == pytest.approx(146.8, abs=0.05)


def test_the_kernels_costs_count_the_heads_the_call_has():
    sizes = _sizes()
    v5e = peaks.chip_peaks("TPU v5 lite")
    fwd = flops_nemotron.ssd_kernel_cost("ssd_fwd", 1, 32, 4, 8192, 64, 128, 128)
    bwd = flops_nemotron.ssd_kernel_cost("ssd_bwd", 1, 32, 4, 8192, 64, 128, 128)
    wide, keys, scalars = 8192 * 2048 * 2, 8192 * 512 * 2, 8192 * 32 * 4
    states = 64 * 32 * 128 * 64 * 4  # 64 chunks, 32 KB a head: 67 MB
    assert states == 67_108_864
    assert fwd[1] == 2 * wide + 2 * keys + 2 * scalars + states
    assert bwd[1] == 3 * wide + 4 * keys + 4 * scalars + states
    assert fwd[0] == pytest.approx(13.96e9, rel=1e-3)
    # the bytes bind: 0.187 ms and 0.251 ms a call at 819 GB/s
    for cost, ms in ((fwd, 0.1869), (bwd, 0.2509)):
        least, which = flops.roofline_seconds(*cost, v5e)
        assert which == "memory" and least * 1e3 == pytest.approx(ms, abs=5e-4)
    with pytest.raises(KeyError):
        flops_nemotron.ssd_kernel_cost("ssd_other", 1, 32, 4, 8192, 64, 128, 128)
    # the grouped kernels at the 16 / 1 HELD heads: half of the published
    # 32 / 2's work, which the accepted reducer would have counted
    held = flops_nemotron.held_gqa_kernel_cost("flash_gqa_fwd", 1, sizes, 8192)
    whole = flops_lfm2.gqa_kernel_cost(
        "flash_gqa_fwd", 1, 32, 2, 8192, 128, 512, 512
    )
    assert held[0] * 2 == pytest.approx(whole[0])
    tiled = flops_nemotron.held_gqa_kernel_cost(
        "flash_gqa_bwd_tiled", 1, sizes, 8192
    )
    assert tiled[0] == pytest.approx(2.5 * held[0])  # 5 matmuls against 2
    assert tiled[1] == 8192 * 128 * 2 * (4 * 16 + 4 * 1) + 17 * 8192 * 4


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
        nemotron_kernel_roofline,
        nemotron_mfu,
        nemotron_ssd_gauge,
        nemotron_ssd_time,
    )

    ops = [("ssd_fwd", 0.4e-3)] * 6 + [("ssd_bwd", 0.6e-3)] * 6 + [
        ("flash_gqa_fwd", 2.0e-3)] * 2 + [("flash_gqa_bwd_tiled", 5.0e-3)] * 2 + [
        ("fusion.1", 0.1)]
    run = _Run(ops)
    assert nemotron_kernel_roofline.reduce(run, {"kernel": "ssd_fwd"}) == (
        pytest.approx(100 * 0.1869 / 0.4, rel=2e-3)
    )
    assert nemotron_kernel_roofline.reduce(run, {"kernel": "ssd_bwd"}) == (
        pytest.approx(100 * 0.2509 / 0.6, rel=2e-3)
    )
    for kernel in ("flash_gqa_fwd", "flash_gqa_bwd_tiled"):
        assert 50 < nemotron_kernel_roofline.reduce(
            run, {"kernel": kernel}
        ) < 100
    assert nemotron_kernel_roofline.reduce(
        run, {"kernel": "flash_gqa_bwd_dq"}
    ) is None
    # three layers forward + backward, two executions traced
    assert nemotron_ssd_time.reduce(run, {}) == pytest.approx(3.0)
    assert nemotron_mfu.reduce(run, {}) == pytest.approx(
        100 * 10.089e12 / 0.15 / 197e12, rel=1e-3
    )
    # a program without the kernels (the parent): nothing, no error
    older = _Run([("fusion.1", 0.1)])
    assert nemotron_kernel_roofline.reduce(older, {"kernel": "ssd_fwd"}) is None
    assert nemotron_ssd_time.reduce(older, {}) is None
    untraced = _Run(None)
    assert nemotron_ssd_time.reduce(untraced, {}) is None
    assert nemotron_mfu.reduce(untraced, {}) is None
    records = [
        {"ssd.dt_mean.1": 0.01, "ssd.dt_mean.2": 0.03,
         "ssd.chunk_log_decay_min.1": -90.0, "ssd.chunk_log_decay_min.2": -50.0},
        {"ssd.dt_mean.1": 0.02, "ssd.dt_mean.2": 0.02,
         "ssd.chunk_log_decay_min.1": -70.0, "ssd.chunk_log_decay_min.2": -80.0},
        {"loss": 1.0},
    ]
    gauged = _Run(None, records)
    assert nemotron_ssd_gauge.reduce(
        gauged, {"gauge": "ssd.dt_mean", "over": "mean"}
    ) == pytest.approx(0.02)
    assert nemotron_ssd_gauge.reduce(
        gauged, {"gauge": "ssd.chunk_log_decay_min", "over": "min"}
    ) == pytest.approx(-85.0)
    assert nemotron_ssd_gauge.reduce(
        gauged, {"gauge": "ssd.state_abs_max", "over": "max"}
    ) is None
