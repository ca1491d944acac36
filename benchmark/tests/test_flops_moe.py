"""``flops_moe.py`` against hand counts at the cell's shapes, and the
reducers that read it: no roofline or peak share can pass 100 % unless a
call runs faster than the chip's peaks allow."""
import json
import os
import types

import pytest

from benchmark import flops_moe, peaks
from benchmark.flops import roofline_seconds
from benchmark.reducers import mla_kernel_roofline, moe_lm_mfu, moe_routed_time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    with open(os.path.join(HERE, "configs", "kanana2_30b_a3b_s4096.json")) as f:
        return json.load(f)


def test_mla_kernel_costs_by_hand():
    # one row, 32 heads, q/k 192 and v 128 wide, S=4,096, tiles of 512: 36
    # tiles a head on and under the diagonal
    tile = 2 * 512 * 512  # one matmul of one tile, per unit of width
    qk, v = 32 * 4096 * 192 * 2, 32 * 4096 * 128 * 2  # one bf16 operand
    rows = (32 + 1) * 4096 * 4
    by_hand = {
        # QK^T (192) + PV (128); q k | v o
        "flash_mla_fwd": (192 + 128, 2 * qk + 2 * v),
        # QK^T, dQ (192 each) + dP (128); q k dq | v dO O
        "flash_mla_bwd_dq": (2 * 192 + 128, 3 * qk + 3 * v),
        # QK^T, dK (192 each) + dP, dV (128 each); q k dk | v dO O dv
        "flash_mla_bwd_dkv": (2 * 192 + 2 * 128, 3 * qk + 4 * v),
    }
    for kernel, (width, tensors) in by_hand.items():
        flops, bytes_ = flops_moe.mla_kernel_cost(
            kernel, 1, 32, 4096, 192, 128, 512, 512
        )
        assert flops == tile * width * 36 * 32
        assert bytes_ == tensors + rows
    # equal widths: the one-width cost functions of flops_lm.py
    from benchmark import flops_lm

    for mla, causal in (("flash_mla_fwd", "flash_causal_fwd"),
                        ("flash_mla_bwd_dq", "flash_causal_bwd_dq"),
                        ("flash_mla_bwd_dkv", "flash_causal_bwd_dkv")):
        assert flops_moe.mla_kernel_cost(
            mla, 1, 16, 4096, 128, 128, 512, 512
        ) == flops_lm.causal_kernel_cost(causal, 1, 16, 4096, 128, 512, 512)
    # compute binds on a v5e: 193 GFLOP against 168 MB -> 0.98 ms
    least, which = roofline_seconds(
        *flops_moe.mla_kernel_cost(
            "flash_mla_fwd", 1, 32, 4096, 192, 128, 512, 512
        ), peaks.chip_peaks("TPU v5 lite"),
    )
    assert which == "compute" and least == pytest.approx(9.81e-4, rel=0.01)
    with pytest.raises(KeyError):
        flops_moe.mla_kernel_cost("flash_causal_fwd", 1, 32, 4096, 192, 128,
                                  512, 512)


def test_model_flops_by_hand_and_equal_to_the_programs():
    sizes = _config()["sizes"]
    attention = (
        2 * 2048 * 32 * 192 + 2 * 2048 * 576 + 2 * 512 * 32 * 256
        + 2 * 32 * 128 * 2048 + 2 * 32 * 320 * 2048.5
    )
    sparse = (
        2 * 2048 * 128 + 2 * 3 * 2048 * 768 * 2
        + 2 * 3 * 2048 * 768 * 6 * 8 / 128
    )
    want = 3 * 4096 * (
        5 * attention + 2 * 3 * 2048 * 6144 + 4 * sparse + 2 * 2048 * 16032
    )
    got = flops_moe.moe_lm_train_flops_per_sample(sizes, 4096)
    assert got == pytest.approx(want)
    # the recorder's own FLOP model (its MFU gauge) is the same arithmetic
    from dedloc_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        deepseek_v3_train_tflops_per_sample,
    )

    cfg = DeepseekV3Config(
        num_hidden_layers=5, vocab_size=16032, expert_shard=(0, 16)
    )
    assert deepseek_v3_train_tflops_per_sample(cfg, 4096) * 1e12 == (
        pytest.approx(got)
    )
    # what the cut distorts: the head ~9 % of model FLOPs at 5 layers (~1 %
    # at 48), the routed experts' held share 2 %
    head = 3 * 4096 * 2 * 2048 * 16032
    assert head / got == pytest.approx(0.093, abs=0.005)
    deep = flops_moe.moe_lm_train_flops_per_sample(
        dict(sizes, num_hidden_layers=48), 4096
    )
    assert head / deep == pytest.approx(0.011, abs=0.002)
    routed = 3 * 4096 * 4 * 2 * 3 * 2048 * 768 * 6 * 8 / 128
    assert routed / got == pytest.approx(0.020, abs=0.002)


def _run(trace):
    config = _config()
    args = types.SimpleNamespace(training=types.SimpleNamespace(
        seq_length=4096, per_device_batch_size=1
    ))
    role = types.SimpleNamespace(
        microbatch_rows_per_device=lambda a: a.training.per_device_batch_size,
        PROGRAMS={"accumulate": "accumulate_step"},
    )
    run = types.SimpleNamespace(
        config=config, args=args, role=role, device_kind="TPU v5 lite",
        trace=trace,
        seq_length=lambda: 4096, program=lambda name: role.PROGRAMS[name],
    )
    return run


def test_reducers_read_a_trace_and_give_nothing_without_the_ops():
    held = "bf16[8,2048,768]{2,1,0:T(8,128)(2,1)}"
    ops = [
        # two executions' worth: per layer a top-k sort, a slot sort, the
        # forward and the backward tile loop; the scan over layers carries
        # the experts stacked once more and is NOT one of them
        ("%sort = (f32[4096,128]{0,1}, s32[4096,128]) sort(", 0.0, 50e3),
        ("%sort.1 = (s32[24576]{0}, s32[24576]) sort(", 60e3, 20e3),
        (f"%while.4 = (s32[], f32[4096,2048], {held}, {held}) while(", 1e5, 4e5),
        (f"%while.10 = (s32[], f32[8,2048,768], {held}) while(", 6e5, 1e6),
        ("%while.2 = (s32[], f32[4,8,2048,768]{3,2,1,0}) while(", 0.0, 5e6),
        ("%flash_mla_fwd.3 = (bf16[1,4096,4096]) custom-call(", 2e6, 3.0e6),
        ("%flash_mla_bwd_dq.3 = bf16[1,4096,6144] custom-call(", 2e6, 3.9e6),
        ("%flash_mla_bwd_dkv.3 = (bf16[1,4096,6144]) custom-call(", 2e6, 4.9e6),
    ]
    modules = [("jit_accumulate_step(123)", 0.0, 150e6),
               ("jit_accumulate_step(123)", 2e8, 150e6),
               ("jit_guarded_apply_step(9)", 4e8, 40e6)]
    run = _run({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}})
    assert moe_routed_time.reduce(run, {}) == pytest.approx(
        (50e3 + 20e3 + 4e5 + 1e6) / 2 / 1e6
    )
    # 0.98 / 1.57 / 1.96 ms least against 3.0 / 3.9 / 4.9 measured
    for kernel, share in (("flash_mla_fwd", 32.7), ("flash_mla_bwd_dq", 40.2),
                          ("flash_mla_bwd_dkv", 40.0)):
        assert mla_kernel_roofline.reduce(run, {"kernel": kernel}) == (
            pytest.approx(share, abs=0.2)
        )
    # 8.68 TFLOP a row over 150 ms over 197 TFLOP/s
    assert moe_lm_mfu.reduce(run, {}) == pytest.approx(29.4, abs=0.2)
    # a call cannot be read above 100 % unless it beats the peaks
    assert mla_kernel_roofline.bound(run, "flash_mla_fwd")[0] == (
        pytest.approx(9.81e-4, rel=0.01)
    )
    # a program older than the routed layer and the two-width kernels: no
    # such op in its trace, nothing reported, nothing raised
    old = _run({"/device:TPU:0": {
        "XLA Ops": [("%flash_causal_fwd.3 = (bf16[1,4096,2048]) custom-call(",
                     0.0, 1e6),
                    ("%while.2 = (s32[], f32[3,2048,5632]) while(", 0.0, 5e6)],
        "XLA Modules": modules,
    }})
    assert moe_routed_time.reduce(old, {}) is None
    assert mla_kernel_roofline.reduce(old, {"kernel": "flash_mla_fwd"}) is None
    assert moe_routed_time.reduce(_run(None), {}) is None
    assert moe_lm_mfu.reduce(_run(None), {}) is None
