"""``flops_lfm2.py`` against hand counts at the cell's shapes, and the
reducers that read it: no roofline or peak share can pass 100 % unless a
call runs faster than the chip's peaks allow."""
import json
import os
import types

import pytest

from benchmark import flops_lfm2, flops_lm, peaks
from benchmark.flops import roofline_seconds
from benchmark.reducers import (
    conv_kernel_roofline,
    gqa_kernel_roofline,
    lfm2_mfu,
    moe_routed_time,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    with open(os.path.join(HERE, "configs", "lfm2_24b_a2b_s4096.json")) as f:
        return json.load(f)


def test_gqa_kernel_costs_by_hand():
    # one row, 32 query heads over 8 kv heads of 64, S=4,096, tiles of 512:
    # 36 tiles a head on and under the diagonal
    tile = 2 * 512 * 512 * 64  # one matmul of one tile
    q, kv = 32 * 4096 * 64 * 2, 8 * 4096 * 64 * 2  # one bf16 tensor
    rows = (32 + 1) * 4096 * 4
    by_hand = {
        "flash_gqa_fwd": (2, 2 * q + 2 * kv),  # q o | k v
        "flash_gqa_bwd_dq": (3, 4 * q + 2 * kv),  # q dO O dq | k v
        "flash_gqa_bwd_dkv": (4, 3 * q + 4 * kv),  # q dO O | k v dk dv
    }
    for kernel, (matmuls, tensors) in by_hand.items():
        flops, bytes_ = flops_lfm2.gqa_kernel_cost(
            kernel, 1, 32, 8, 4096, 64, 512, 512
        )
        assert flops == tile * matmuls * 36 * 32
        assert bytes_ == tensors + rows
    # as many kv heads as heads: the one-count cost functions of flops_lm.py
    for gqa, causal in (("flash_gqa_fwd", "flash_causal_fwd"),
                        ("flash_gqa_bwd_dq", "flash_causal_bwd_dq"),
                        ("flash_gqa_bwd_dkv", "flash_causal_bwd_dkv")):
        assert flops_lfm2.gqa_kernel_cost(
            gqa, 1, 16, 16, 4096, 128, 512, 512
        ) == flops_lm.causal_kernel_cost(causal, 1, 16, 4096, 128, 512, 512)
    # compute binds on a v5e: 77 GFLOP against 42 MB -> 0.39 ms
    least, which = roofline_seconds(
        *flops_lfm2.gqa_kernel_cost("flash_gqa_fwd", 1, 32, 8, 4096, 64,
                                    512, 512),
        peaks.chip_peaks("TPU v5 lite"),
    )
    assert which == "compute" and least == pytest.approx(3.92e-4, rel=0.01)
    with pytest.raises(KeyError):
        flops_lfm2.gqa_kernel_cost("flash_causal_fwd", 1, 32, 8, 4096, 64,
                                   512, 512)


def test_conv_kernel_costs_by_hand():
    tensor = 4096 * 2048 * 2  # one H-wide bf16 tensor of the row
    flops, bytes_ = flops_lfm2.conv_kernel_cost("short_conv_fwd", 1, 4096, 2048)
    assert bytes_ == 4 * tensor  # B C u | y
    _f, bytes_bwd = flops_lfm2.conv_kernel_cost("short_conv_bwd", 1, 4096, 2048)
    assert bytes_bwd == 7 * tensor  # B C u dy | dB dC du
    # a tensor held in on-chip memory crosses no HBM
    assert flops_lfm2.conv_kernel_cost(
        "short_conv_fwd", 1, 4096, 2048, on_chip={"y"}
    )[1] == 3 * tensor
    assert flops_lfm2.conv_kernel_cost(
        "short_conv_bwd", 1, 4096, 2048, on_chip={"dy", "d_bcu"}
    )[1] == 3 * tensor
    # memory binds by two orders of magnitude: 67 MB -> 82 us
    least, which = roofline_seconds(
        flops, bytes_, peaks.chip_peaks("TPU v5 lite")
    )
    assert which == "memory" and least == pytest.approx(81.9e-6, rel=0.01)
    assert flops / peaks.chip_peaks("TPU v5 lite")["flops_per_s"] < least / 50
    with pytest.raises(KeyError):
        flops_lfm2.conv_kernel_cost("flash_gqa_fwd", 1, 4096, 2048)


def test_model_flops_by_hand_and_against_the_program():
    sizes = _config()["sizes"]
    seq = 4096
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048
    attention = (
        2 * 2048 * (2048 + 512 + 512) + 2 * 2048 * 2048
        + 2 * 2 * 2048 * (seq + 1) / 2
    )
    dense = 2 * 3 * 2048 * 11776
    routed = 2 * 2048 * 64 + 2 * 3 * 2048 * 1536 * 4 * 8 / 64
    head = 2 * 2048 * 8192
    per_token = 4 * conv + attention + dense + 4 * routed + head
    assert flops_lfm2.lfm2_train_flops_per_sample(sizes, seq) == (
        pytest.approx(3 * per_token * seq, rel=1e-12)
    )
    # ~4.8 TFLOP a row; what the cut distorts (the configuration's
    # ``deployment`` says so): the dense FFN ~39 %, the head ~9 %
    total = per_token
    assert 3 * total * seq == pytest.approx(4.78e12, rel=0.01)
    assert dense / total == pytest.approx(0.372, abs=0.01)
    assert head / total == pytest.approx(0.086, abs=0.01)
    # the program's own model says the same
    from dedloc_tpu.models.lfm2_moe import (
        Lfm2MoeConfig,
        lfm2_moe_train_tflops_per_sample,
    )

    cfg = Lfm2MoeConfig(
        num_hidden_layers=5, vocab_size=8192, expert_shard=(0, 8)
    )
    assert lfm2_moe_train_tflops_per_sample(cfg, seq) * 1e12 == (
        pytest.approx(flops_lfm2.lfm2_train_flops_per_sample(sizes, seq),
                      rel=1e-12)
    )


class _Role:
    PROGRAMS = {"accumulate": "accumulate_step"}

    @staticmethod
    def microbatch_rows_per_device(args):
        return 1


def _run(trace):
    run = types.SimpleNamespace(
        trace=trace, config=_config(), role=_Role, args=None,
        device_kind="TPU v5 lite",
    )
    run.seq_length = lambda: 4096
    run.program = lambda logical: _Role.PROGRAMS[logical]
    return run


def test_reducers_read_the_trace_and_stay_under_the_peaks():
    from benchmark import trace as T

    held = "f32[8,2048,1536]"
    ops = [
        ("%flash_gqa_fwd.1 = bf16[1,4096,2048]", 0, 1.9e6),
        ("%flash_gqa_bwd_dq.1 = bf16[1,4096,2048]", 0, 1.6e6),
        ("%flash_gqa_bwd_dkv.1 = (bf16[1,4096,512]", 0, 2.2e6),
        ("%short_conv_fwd.2 = bf16[1,4096,2048]", 0, 0.09e6),
        ("%short_conv_bwd.2 = (bf16[1,4096,6144]", 0, 0.34e6),
        # the same kernels as a trace names them, y and dy held on the chip
        ("%short_conv_fwd.3 = bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(bf16[1,4096,6144]{2,1,0:T(8,128)(2,1)} %x, "
         "bf16[1,4096,6144]{2,1,0:T(8,128)(2,1)} %x, f32[8,2048]{1,0:T(8,128)"
         "S(1)} %w), custom_call_target=\"tpu_custom_call\"", 0, 0.07e6),
        ("%short_conv_bwd.3 = (bf16[1,4096,6144]{2,1,0:T(8,128)(2,1)}, "
         "f32[8,2048]{1,0:T(8,128)S(1)}) custom-call(bf16[1,4096,6144]{2,1,0"
         ":T(8,128)(2,1)} %x, bf16[1,4096,6144]{2,1,0} %x, bf16[1,4096,6144]"
         "{2,1,0} %x, bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)S(1)} %dy, "
         "bf16[1,4096,6144]{2,1,0} %x), custom_call_target=\"x\"", 0, 0.3e6),
        (f"%while.3 = (s32[], {held}, bf16[4096,2048]) while(...)", 0, 3.0e6),
        ("%sort.9 = (f32[4096,64]) sort(...)", 0, 0.2e6),
        ("%while.4 = (s32[], f32[4,8,2048,1536]) while(...)", 0, 50e6),
    ]
    trace = {"dev0": {
        T.OPS: ops, T.MODULES: [("jit_accumulate_step(1)", 0, 70e6)],
    }}
    run = _run(trace)
    for kernel in ("flash_gqa_fwd", "flash_gqa_bwd_dq", "flash_gqa_bwd_dkv"):
        share = gqa_kernel_roofline.reduce(run, {"kernel": kernel})
        assert 15 < share < 50, (kernel, share)  # D=64: half the MXU's work
    # an event without its HLO text is held to every byte; one that says
    # where its tensors live, to those that cross HBM: y (1 part of 4) and
    # dy (1 of 7) left out
    fwd, bwd = (
        sorted(share for share, _which in conv_kernel_roofline.shares(
            run, kernel
        )) for kernel in ("short_conv_fwd", "short_conv_bwd")
    )
    assert fwd == pytest.approx([81.9 * 3 / 4 / 70, 81.9 / 90], rel=0.01)
    assert bwd == pytest.approx([143.4 * 6 / 7 / 300, 143.4 / 340], rel=0.01)
    assert conv_kernel_roofline.on_chip_tensors(
        "short_conv_fwd", ops[5][0]
    ) == {"y"}
    assert conv_kernel_roofline.on_chip_tensors(
        "short_conv_bwd", ops[6][0]
    ) == {"dy"}
    assert conv_kernel_roofline.reduce(
        run, {"kernel": "short_conv_fwd"}
    ) == pytest.approx(100 * (fwd[0] + fwd[1]) / 2)
    # a call with every tensor on the chip has no HBM bound: left out of
    # the median while another call crosses HBM, the whole of it otherwise
    on_chip = (
        "%short_conv_fwd.4 = bf16[1,4096,2048]{2,1,0:S(1)} custom-call("
        "bf16[1,4096,6144]{2,1,0:S(1)} %x), custom_call_target=\"x\""
    )
    trace["dev0"][T.OPS] = ops + [(on_chip, 0, 0.068e6)] * 5
    assert conv_kernel_roofline.reduce(
        run, {"kernel": "short_conv_fwd"}
    ) == pytest.approx(100 * (fwd[0] + fwd[1]) / 2)
    trace["dev0"][T.OPS] = [(on_chip, 0, 0.068e6)]
    assert 0 < conv_kernel_roofline.reduce(
        run, {"kernel": "short_conv_fwd"}
    ) < 1
    trace["dev0"][T.OPS] = ops
    assert lfm2_mfu.reduce(run, {}) == pytest.approx(
        100 * 4.78e12 / 0.070 / 197e12, rel=0.01
    )
    # the routed path: the sort and the loop that holds ONE layer's held
    # experts, not the scan that holds them stacked
    assert moe_routed_time.reduce(run, {}) == pytest.approx(3.2)
    # a program without these ops (an older one) reports nothing
    empty = _run({"dev0": {T.OPS: [], T.MODULES: []}})
    for reducer, params in (
        (gqa_kernel_roofline, {"kernel": "flash_gqa_fwd"}),
        (conv_kernel_roofline, {"kernel": "short_conv_fwd"}),
        (lfm2_mfu, {}), (moe_routed_time, {}),
    ):
        assert reducer.reduce(empty, params) is None
        assert reducer.reduce(_run(None), params) is None
