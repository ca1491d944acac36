"""``flops_sdar.py`` against hand counts at the cell's shapes and against
the kernels' own count of what they visit, and the reducers that read it: no
roofline or peak share can pass 100 % unless a call runs faster than the
chip's peaks allow."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import flops_sdar as fs, peaks
from benchmark.flops import roofline_seconds
from benchmark.reducers import bd_kernel_roofline, moe_routed_time, sdar_mfu

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    path = os.path.join(HERE, "configs", "sdar_30b_a3b_s4096.json")
    with open(path) as f:
        return json.load(f)


def _visible(length, block):
    """The rule's three sentences as an explicit [2L, 2L] mask."""
    i = np.arange(2 * length)
    clean, blk = i >= length, (i % length) // block
    qc, kc = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return np.where(
        qc, kc & (kb <= qb), (~kc & (kb == qb)) | (kc & (kb < qb))
    )


GEOMETRY = [(64, 4, 32, 32), (64, 32, 32, 32), (96, 16, 32, 32),
            (128, 4, 32, 64), (128, 8, 64, 32), (96, 3, 48, 24),
            (32, 4, 32, 32)]


@pytest.mark.parametrize("length,block,bq,bk", GEOMETRY)
def test_tiles_and_pairs_against_the_explicit_mask(length, block, bq, bk):
    seen = _visible(length, block)
    assert fs.bd_pairs(length, block) == int(seen.sum())
    assert fs.bd_tiles(length, bq, bk, block) == sum(
        bool(seen[q:q + bq, k:k + bk].any())
        for q in range(0, 2 * length, bq) for k in range(0, 2 * length, bk)
    )
    # the kernels' own count of what they visit (the mask description in
    # ops/flash_attention.py) agrees with the arithmetic re-stated here
    from dedloc_tpu.ops.flash_attention import visited_tiles

    assert visited_tiles(
        2 * length, bq, bk, False, block_diffusion=block
    ) == fs.bd_tiles(length, bq, bk, block)


def test_tiles_and_pairs_at_the_cells_shapes():
    from dedloc_tpu.ops.flash_attention import visited_tiles

    assert fs.bd_tiles(4096, 512, 512, 4) == 80  # 36 + 36 + 8
    assert visited_tiles(8192, 512, 512, False, block_diffusion=4) == 80
    assert fs.bd_pairs(4096, 4) == 16_793_600  # of 67.1 M a dense call has
    assert fs.bd_pairs(4096, 4) == (
        4096 * 4100 // 2 + 4096 * 4092 // 2 + 4096 * 4
    )


def test_kernel_costs_by_hand():
    tile = 2 * 512 * 512 * 128  # one matmul of one tile
    q, kv = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2  # one bf16 tensor
    rows = (32 + 1) * 8192 * 4
    by_hand = {
        "flash_bd_fwd": (2, 2 * q + 2 * kv),  # q o | k v
        "flash_bd_bwd_dq": (3, 4 * q + 2 * kv),  # q dO O dq | k v
        "flash_bd_bwd_dkv": (4, 3 * q + 4 * kv),  # q dO O | k v dk dv
    }
    for kernel, (matmuls, tensors) in by_hand.items():
        flops, bytes_ = fs.bd_kernel_cost(
            kernel, 1, 32, 4, 4096, 128, 512, 512, 4
        )
        assert flops == tile * matmuls * 80 * 32
        assert bytes_ == tensors + rows
    # compute binds on a v5e: 0.344 TFLOP against 0.15 GB -> 1.74 ms
    least, which = roofline_seconds(
        *fs.bd_kernel_cost("flash_bd_fwd", 1, 32, 4, 4096, 128, 512, 512, 4),
        peaks.chip_peaks("TPU v5 lite"),
    )
    assert which == "compute" and least == pytest.approx(1.744e-3, rel=0.01)
    with pytest.raises(KeyError):
        fs.bd_kernel_cost("flash_gqa_fwd", 1, 32, 4, 4096, 128, 512, 512, 4)


def test_model_flops_and_parameters_by_hand_and_against_the_program():
    sizes = _config()["sizes"]
    length = 4096
    assert fs.sdar_parameters(sizes) == 456_346_624
    layer = (
        18_874_368 + 256 + 262_144 + 4_096 + 75_497_472
    )  # attention, q / k norms, router, two norms, 16 experts
    assert layer == 94_638_336
    assert 4 * layer + 2 * 18992 * 2048 + 2048 == 456_346_624
    part = fs.sdar_parts_flops_per_row(sizes, length)
    assert part == {
        "projections": 4 * 8192 * 2 * 18_874_368,
        "attention": 4 * 2 * 2 * 32 * 128 * 16_793_600,
        "router": 4 * 8192 * 2 * 2048 * 128,
        "routed": 4 * 8192 * 2 * 3 * 2048 * 768 * 8 * 16 / 128,
        "head": 4096 * 2 * 2048 * 18992,
    }
    total = fs.sdar_train_flops_per_sample(sizes, length)
    # 8.95 TFLOP a row (the configuration's ``deployment`` says so)
    assert total == pytest.approx(8.948e12, rel=0.001)
    share = {k: 3 * v / total for k, v in part.items()}
    assert share["attention"] == pytest.approx(0.369, abs=0.001)
    assert share["projections"] == pytest.approx(0.415, abs=0.001)
    assert share["routed"] == pytest.approx(0.104, abs=0.001)
    assert share["head"] == pytest.approx(0.107, abs=0.001)
    # the program's own model says the same
    from dedloc_tpu.models.sdar_moe import (
        SdarMoeConfig,
        sdar_moe_train_tflops_per_sample,
    )

    cfg = SdarMoeConfig(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 8)
    )
    assert sdar_moe_train_tflops_per_sample(cfg, length) * 1e12 == (
        pytest.approx(total, rel=1e-12)
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

    held = "f32[16,2048,768]"
    ops = [
        ("%flash_bd_fwd.1 = bf16[1,8192,4096]", 0, 4.0e6),
        ("%flash_bd_bwd_dq.1 = bf16[1,8192,4096]", 0, 4.2e6),
        ("%flash_bd_bwd_dkv.1 = (bf16[1,8192,512]", 0, 5.0e6),
        (f"%while.3 = (s32[], {held}, bf16[8192,2048]) while(...)", 0, 9e6),
        ("%sort.9 = (f32[8192,128]) sort(...)", 0, 1e6),
        ("%while.4 = (s32[], f32[1,16,2048,768]) while(...)", 0, 100e6),
    ]
    trace = {"dev0": {
        T.OPS: ops, T.MODULES: [("jit_accumulate_step(1)", 0, 130e6)],
    }}
    run = _run(trace)
    shares = {
        kernel: bd_kernel_roofline.reduce(run, {"kernel": kernel})
        for kernel in ("flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv")
    }
    assert shares["flash_bd_fwd"] == pytest.approx(43.6, abs=0.2)
    assert all(30 < share < 100 for share in shares.values()), shares
    assert sdar_mfu.reduce(run, {}) == pytest.approx(
        100 * 8.948e12 / 0.130 / 197e12, rel=0.01
    )
    assert moe_routed_time.reduce(run, {}) == pytest.approx(10.0)
    empty = _run({"dev0": {T.OPS: [], T.MODULES: []}})
    for reducer, params in (
        (bd_kernel_roofline, {"kernel": "flash_bd_fwd"}),
        (sdar_mfu, {}), (moe_routed_time, {}),
    ):
        assert reducer.reduce(empty, params) is None
        assert reducer.reduce(_run(None), params) is None
