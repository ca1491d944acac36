"""``flops_laguna.py`` against hand counts at the cell's shapes, the mask's
arithmetic against the kernels' own ``visited_tiles`` and an explicit
[S, S] mask, and the reducers that read it: no roofline or peak share can
pass 100 % unless a call runs faster than the chip's peaks allow."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import flops_laguna as fl, flops_lfm2, flops_smallthinker, peaks
from benchmark.flops import roofline_seconds
from benchmark.reducers import (
    laguna_kernel_roofline,
    laguna_mfu,
    moe_routed_time,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


def _config():
    path = os.path.join(HERE, "configs", "laguna_xs2_33b_a3b_s8192.json")
    with open(path) as f:
        return json.load(f)


def test_a_band_equal_to_the_tile():
    """31 of the triangle's 136 tiles, half of whose pairs are visible —
    against the kernels' own count and against an explicit mask."""
    from dedloc_tpu.ops.flash_attention import visited_tiles

    band_tiles, band_pairs = (
        flops_smallthinker.band_tiles, flops_smallthinker.band_pairs
    )
    assert band_tiles(SEQ, 512, 512, 512) == 31
    assert band_tiles(SEQ, 512, 512, SEQ) == 136
    assert band_pairs(SEQ, 512) == 4_063_488
    assert fl.band_visible_share(_config()["sizes"], SEQ) == (
        4_063_488 / (31 * 512 * 512)
    )
    for seq, bq, bk, band in ((SEQ, 512, 512, 512), (SEQ, 512, 256, 512),
                              (SEQ, 512, 128, 512), (128, 32, 32, 32),
                              (128, 32, 32, 16)):
        assert visited_tiles(seq, bq, bk, True, band) == band_tiles(
            seq, bq, bk, band
        )
    # an explicit mask at a small size: the pairs, and the tiles that hold one
    seq, block, band = 128, 32, 32
    i = np.arange(seq)
    seen = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < band)
    assert int(seen.sum()) == band_pairs(seq, band)
    tiles = seen.reshape(seq // block, block, seq // block, block).any((1, 3))
    assert int(tiles.sum()) == band_tiles(seq, block, block, band) == 7
    # ... and none of the visited tiles is whole: every one is crossed
    whole = seen.reshape(seq // block, block, seq // block, block).all((1, 3))
    assert int(whole.sum()) == 0


def test_kernel_costs_by_hand():
    sizes = _config()["sizes"]
    tile = 2 * 512 * 512 * 128  # one matmul of one tile
    kv = 8 * SEQ * 128 * 2  # one bf16 tensor at the kv heads' width
    by_hand = {  # matmuls a tile; q-side and kv-side tensors
        "fwd": (2, 2, 2), "bwd_dq": (3, 4, 2), "bwd_dkv": (4, 3, 4),
    }
    for kernel, (matmuls, q_tensors, kv_tensors) in by_hand.items():
        for family, heads, tiles in (("flash_band", 64, 31),
                                     ("flash_gqa", 48, 136)):
            q = heads * SEQ * 128 * 2
            rows = (heads + 1) * SEQ * 4
            flops, bytes_ = fl.kernel_cost(f"{family}_{kernel}", 1, sizes, SEQ)
            assert flops == tile * matmuls * tiles * heads
            assert bytes_ == q_tensors * q + kv_tensors * kv + rows
    # the full layers' call IS the grouped causal kernel's cost at 48 / 8
    assert fl.kernel_cost("flash_gqa_fwd", 1, sizes, SEQ) == (
        flops_lfm2.gqa_kernel_cost("flash_gqa_fwd", 1, 48, 8, SEQ, 128, 512,
                                   512)
    )
    # compute binds on a v5e: 0.266 TFLOP against 0.30 GB -> 1.35 ms a band
    # forward, 0.876 TFLOP -> 4.45 ms a full one
    chip = peaks.chip_peaks("TPU v5 lite")
    least, which = roofline_seconds(
        *fl.kernel_cost("flash_band_fwd", 1, sizes, SEQ), chip
    )
    assert which == "compute" and least == pytest.approx(1.352e-3, rel=0.01)
    least, which = roofline_seconds(
        *fl.kernel_cost("flash_gqa_fwd", 1, sizes, SEQ), chip
    )
    assert which == "compute" and least == pytest.approx(4.447e-3, rel=0.01)
    with pytest.raises(KeyError):
        fl.kernel_cost("flash_causal_fwd", 1, sizes, SEQ)


def test_model_flops_and_parameters_by_hand_and_against_the_program():
    sizes = _config()["sizes"]
    assert fl.laguna_parameters(sizes) == 389_634_048
    h = 2048
    full = 2 * h * (48 + 16) * 128 + 2 * 48 * 128 * h + 2 * h * 48
    sliding = 2 * h * (64 + 16) * 128 + 2 * 64 * 128 * h + 2 * h * 64
    pairs = (2 * 2 * 2 * 48 * 128 * (SEQ * (SEQ + 1) // 2)
             + 3 * 2 * 2 * 64 * 128 * 4_063_488) / SEQ
    dense = 2 * 3 * h * 8192
    sparse = 2 * h * 256 + 2 * 3 * h * 512 * 8 * 8 / 256 + 2 * 3 * h * 512
    head = 2 * h * 12544
    per_token = 2 * full + 3 * sliding + pairs + dense + 4 * sparse + head
    assert fl.laguna_train_flops_per_sample(sizes, SEQ) == pytest.approx(
        3 * per_token * SEQ, rel=1e-12
    )
    # 19.24 TFLOP a row, by part as the configuration's ``deployment`` says
    assert 3 * per_token * SEQ == pytest.approx(19.24e12, rel=0.001)
    part = fl.laguna_flops_per_token_by_part(sizes, SEQ)
    share = {name: 100 * value / per_token for name, value in part.items()}
    assert share["full_attention.projections"] + share[
        "sliding_attention.projections"
    ] == pytest.approx(44.1, abs=0.1)
    assert share["full_attention.pairs"] == pytest.approx(25.7, abs=0.1)
    assert share["sliding_attention.pairs"] == pytest.approx(6.2, abs=0.1)
    assert share["dense_ffn"] == pytest.approx(12.9, abs=0.1)
    assert share["head"] == pytest.approx(6.6, abs=0.1)
    assert share["shared_expert"] == pytest.approx(3.2, abs=0.1)
    assert share["router"] == pytest.approx(0.5, abs=0.1)
    assert share["held_experts"] == pytest.approx(0.8, abs=0.1)
    # the program's own model says the same
    from dedloc_tpu.models.laguna import (
        LagunaConfig,
        laguna_train_tflops_per_sample,
    )

    cfg = LagunaConfig(
        num_hidden_layers=5, vocab_size=12544, expert_shard=(0, 32)
    )
    assert laguna_train_tflops_per_sample(cfg, SEQ) * 1e12 == pytest.approx(
        fl.laguna_train_flops_per_sample(sizes, SEQ), rel=1e-12
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
    run.seq_length = lambda: SEQ
    run.program = lambda logical: _Role.PROGRAMS[logical]
    return run


def test_reducers_read_the_trace_and_stay_under_the_peaks():
    from benchmark import trace as T

    held = "f32[8,2048,512]"
    ops = [
        ("%flash_band_fwd.1 = bf16[1,8192,8192]", 0, 2.0e6),
        ("%flash_band_bwd_dq.1 = bf16[1,8192,8192]", 0, 2.7e6),
        ("%flash_band_bwd_dkv.1 = (bf16[1,8192,1024]", 0, 3.4e6),
        ("%flash_gqa_fwd.1 = bf16[1,8192,6144]", 0, 5.6e6),
        ("%flash_gqa_bwd_dq.1 = bf16[1,8192,6144]", 0, 7.6e6),
        ("%flash_gqa_bwd_dkv.1 = (bf16[1,8192,1024]", 0, 9.7e6),
        (f"%while.3 = (s32[], {held}, bf16[8192,2048]) while(...)", 0, 9e6),
        ("%sort.9 = (f32[8192,256]) sort(...)", 0, 1e6),
        # the shared expert's matrices are not a tile loop's state
        ("%while.4 = (s32[], f32[2048,512]) while(...)", 0, 300e6),
    ]
    trace = {"dev0": {
        T.OPS: ops, T.MODULES: [("jit_accumulate_step(1)", 0, 250e6)],
    }}
    run = _run(trace)
    shares = {
        kernel: laguna_kernel_roofline.reduce(run, {"kernel": kernel})
        for kernel in ("flash_band_fwd", "flash_band_bwd_dq",
                       "flash_band_bwd_dkv", "flash_gqa_fwd",
                       "flash_gqa_bwd_dq", "flash_gqa_bwd_dkv")
    }
    assert shares["flash_band_fwd"] == pytest.approx(67.6, abs=0.2)
    assert shares["flash_gqa_fwd"] == pytest.approx(79.4, abs=0.2)
    assert all(30 < share < 100 for share in shares.values()), shares
    assert laguna_mfu.reduce(run, {}) == pytest.approx(
        100 * 19.24e12 / 0.250 / 197e12, rel=0.01
    )
    assert moe_routed_time.reduce(run, {}) == pytest.approx(10.0)
    empty = _run({"dev0": {T.OPS: [], T.MODULES: []}})
    for reducer, params in (
        (laguna_kernel_roofline, {"kernel": "flash_band_fwd"}),
        (laguna_kernel_roofline, {"kernel": "flash_gqa_fwd"}),
        (laguna_mfu, {}), (moe_routed_time, {}),
    ):
        assert reducer.reduce(empty, params) is None
        assert reducer.reduce(_run(None), params) is None
