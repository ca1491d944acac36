"""``flops_smallthinker.py`` against hand counts at the cell's shapes, and
the reducers that read it: no roofline or peak share can pass 100 % unless a
call runs faster than the chip's peaks allow."""
import json
import os
import types

import pytest

from benchmark import flops_lfm2, flops_smallthinker as fs, peaks
from benchmark.flops import roofline_seconds
from benchmark.flops_lm import causal_tiles
from benchmark.reducers import (
    band_kernel_roofline,
    gqa_kernel_roofline,
    moe_routed_time,
    smallthinker_mfu,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    path = os.path.join(HERE, "configs", "smallthinker_21b_a3b_s16384.json")
    with open(path) as f:
        return json.load(f)


def test_band_tiles_and_pairs():
    assert fs.band_tiles(16384, 512, 512, 4096) == 252
    assert fs.band_tiles(16384, 512, 512, 16384) == 528
    assert fs.band_tiles(16384, 512, 512, 1 << 30) == causal_tiles(
        16384, 512, 512
    )
    assert fs.band_tiles(4096, 512, 512, 4096) == 36  # the triangle
    assert fs.band_tiles(128, 32, 32, 8) == 7
    assert fs.band_tiles(128, 32, 64, 40) == 1 + 1 + 2 + 2
    assert fs.band_pairs(16384, 4096) == 58_722_304
    assert fs.band_pairs(16384, 16384) == 134_225_920
    # the kernels' own count of what they visit (the mask description in
    # ops/flash_attention.py) agrees with the arithmetic re-stated here
    from dedloc_tpu.ops.flash_attention import visited_tiles

    for seq, bq, bk, band in ((16384, 512, 512, 4096), (128, 32, 64, 40),
                              (128, 64, 32, 24), (96, 32, 32, 50)):
        assert visited_tiles(seq, bq, bk, True, band) == fs.band_tiles(
            seq, bq, bk, band
        )


def test_band_kernel_costs_by_hand():
    tile = 2 * 512 * 512 * 128  # one matmul of one tile
    q, kv = 28 * 16384 * 128 * 2, 4 * 16384 * 128 * 2  # one bf16 tensor
    rows = (28 + 1) * 16384 * 4
    by_hand = {
        "flash_band_fwd": (2, 2 * q + 2 * kv),  # q o | k v
        "flash_band_bwd_dq": (3, 4 * q + 2 * kv),  # q dO O dq | k v
        "flash_band_bwd_dkv": (4, 3 * q + 4 * kv),  # q dO O | k v dk dv
    }
    for kernel, (matmuls, tensors) in by_hand.items():
        flops, bytes_ = fs.band_kernel_cost(
            kernel, 1, 28, 4, 16384, 128, 512, 512, 4096
        )
        assert flops == tile * matmuls * 252 * 28
        assert bytes_ == tensors + rows
        # a band as long as the sequence costs what the causal kernel costs
        assert fs.band_kernel_cost(
            kernel, 1, 28, 4, 16384, 128, 512, 512, 16384
        ) == flops_lfm2.gqa_kernel_cost(
            kernel.replace("band", "gqa"), 1, 28, 4, 16384, 128, 512, 512
        )
    # compute binds on a v5e: 0.947 TFLOP against 0.27 GB -> 4.81 ms
    least, which = roofline_seconds(
        *fs.band_kernel_cost("flash_band_fwd", 1, 28, 4, 16384, 128, 512,
                             512, 4096),
        peaks.chip_peaks("TPU v5 lite"),
    )
    assert which == "compute" and least == pytest.approx(4.81e-3, rel=0.01)
    with pytest.raises(KeyError):
        fs.band_kernel_cost("flash_gqa_fwd", 1, 28, 4, 16384, 128, 512, 512,
                            4096)


def test_model_flops_and_parameters_by_hand_and_against_the_program():
    sizes = _config()["sizes"]
    seq = 16384
    assert fs.smallthinker_parameters(sizes) == 370_547_200
    projections = 2 * 20_971_520
    routed = 2 * 2560 * 64 + 2 * 3 * 2560 * 768 * 6 * 8 / 64
    pair = 2 * 2 * 28 * 128
    head = 2 * 2560 * 18992
    per_token = (
        4 * (projections + routed) + head
        + pair * (134_225_920 + 3 * 58_722_304) / seq
    )
    assert fs.smallthinker_train_flops_per_sample(sizes, seq) == (
        pytest.approx(3 * per_token * seq, rel=1e-12)
    )
    # 28.2 TFLOP a row, ~47 % of it attention (the configuration's
    # ``deployment`` says so): band layers 27.6 %, the global layer 19.9 %
    assert 3 * per_token * seq == pytest.approx(28.18e12, rel=0.001)
    attention = pair * (134_225_920 + 3 * 58_722_304) / seq
    assert attention / per_token == pytest.approx(0.475, abs=0.005)
    assert head / per_token == pytest.approx(0.170, abs=0.005)
    # the program's own model says the same
    from dedloc_tpu.models.smallthinker import (
        SmallThinkerConfig,
        smallthinker_train_tflops_per_sample,
    )

    cfg = SmallThinkerConfig(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 8)
    )
    assert smallthinker_train_tflops_per_sample(cfg, seq) * 1e12 == (
        pytest.approx(fs.smallthinker_train_flops_per_sample(sizes, seq),
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
    run.seq_length = lambda: 16384
    run.program = lambda logical: _Role.PROGRAMS[logical]
    return run


def test_reducers_read_the_trace_and_stay_under_the_peaks():
    from benchmark import trace as T

    held = "f32[8,2560,768]"
    ops = [
        ("%flash_band_fwd.1 = bf16[1,16384,3584]", 0, 12.6e6),
        ("%flash_band_bwd_dq.1 = bf16[1,16384,3584]", 0, 8.0e6),
        ("%flash_band_bwd_dkv.1 = (bf16[1,16384,512]", 0, 10.7e6),
        ("%flash_gqa_fwd.1 = bf16[1,16384,3584]", 0, 26.1e6),
        ("%flash_gqa_bwd_dq.1 = bf16[1,16384,3584]", 0, 17.2e6),
        ("%flash_gqa_bwd_dkv.1 = (bf16[1,16384,512]", 0, 22.4e6),
        (f"%while.3 = (s32[], {held}, bf16[16384,2560]) while(...)", 0, 9e6),
        ("%sort.9 = (f32[16384,64]) sort(...)", 0, 1e6),
        ("%while.4 = (s32[], f32[1,8,2560,768]) while(...)", 0, 300e6),
    ]
    trace = {"dev0": {
        T.OPS: ops, T.MODULES: [("jit_accumulate_step(1)", 0, 400e6)],
    }}
    run = _run(trace)
    shares = {
        kernel: band_kernel_roofline.reduce(run, {"kernel": kernel})
        for kernel in ("flash_band_fwd", "flash_band_bwd_dq",
                       "flash_band_bwd_dkv")
    }
    assert shares["flash_band_fwd"] == pytest.approx(38.2, abs=0.2)
    assert all(30 < share < 100 for share in shares.values()), shares
    full = {
        kernel: gqa_kernel_roofline.reduce(run, {"kernel": kernel})
        for kernel in ("flash_gqa_fwd", "flash_gqa_bwd_dq",
                       "flash_gqa_bwd_dkv")
    }
    assert full["flash_gqa_fwd"] == pytest.approx(38.6, abs=0.2)
    assert all(30 < share < 100 for share in full.values()), full
    assert smallthinker_mfu.reduce(run, {}) == pytest.approx(
        100 * 28.18e12 / 0.400 / 197e12, rel=0.01
    )
    assert moe_routed_time.reduce(run, {}) == pytest.approx(10.0)
    empty = _run({"dev0": {T.OPS: [], T.MODULES: []}})
    for reducer, params in (
        (band_kernel_roofline, {"kernel": "flash_band_fwd"}),
        (smallthinker_mfu, {}), (moe_routed_time, {}),
    ):
        assert reducer.reduce(empty, params) is None
        assert reducer.reduce(_run(None), params) is None
