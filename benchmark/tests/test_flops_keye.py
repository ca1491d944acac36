"""``flops_keye.py`` against hand counts at the cell's shapes, the selected
pairs and the triangle's tiles against an explicit mask and the kernels' own
count of what they visit, and the reducers that read it: no roofline or
peak share can pass 100 % unless a call runs faster than the chip's peaks
allow; the routed metric counts none of the selection's loops."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import flops_keye as fk, peaks
from benchmark.flops import roofline_seconds
from benchmark.reducers import (
    keye_kernel_roofline,
    keye_mfu,
    moe_routed_time,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 16384


def _config():
    path = os.path.join(HERE, "configs", "keye_vl2_30b_a3b_s16384.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize(
    "seq,top_k,bq,bk", [(64, 8, 16, 16), (64, 64, 32, 16), (96, 200, 32, 32),
                        (128, 16, 32, 64), (32, 1, 8, 8)],
)
def test_pairs_and_tiles_against_an_explicit_mask(seq, top_k, bq, bk):
    from dedloc_tpu.ops.flash_attention import visited_tiles

    i = np.arange(seq)
    causal = i[None, :] <= i[:, None]
    # ANY selection keeps min(t + 1, top_k) keys a query
    assert fk.selected_pairs(seq, top_k) == int(
        np.minimum(causal.sum(-1), top_k).sum()
    )
    assert fk.triangle_pairs(seq) == int(causal.sum())
    tiles = sum(
        bool(causal[q:q + bq, k:k + bk].any())
        for q in range(0, seq, bq) for k in range(0, seq, bk)
    )
    # the arithmetic re-stated here, the explicit mask and the kernels' own
    # count of the tiles a selected call's sweeps visit agree
    assert fk.triangle_tiles(seq, bq, bk) == tiles == visited_tiles(
        seq, bq, bk, True
    )


def test_pairs_and_tiles_at_the_cells_shape():
    assert fk.selected_pairs(SEQ, 2048) == 31_458_304
    assert fk.triangle_pairs(SEQ) == 134_225_920
    assert fk.selected_pairs(SEQ, 2048) / fk.triangle_pairs(SEQ) == (
        pytest.approx(0.2344, abs=1e-4)
    )
    assert fk.selected_pairs(8192, 2048) / fk.triangle_pairs(8192) == (
        pytest.approx(0.4375, abs=1e-3)
    )
    assert fk.selected_pairs(2048, 2048) == fk.triangle_pairs(2048)
    assert fk.triangle_tiles(SEQ, 512, 512) == 528


def test_kernel_costs_by_hand():
    tile = 2 * 512 * 512 * 128  # one matmul of one tile
    q, kv = 32 * SEQ * 128 * 2, 4 * SEQ * 128 * 2  # one bf16 tensor
    rows = (32 + 1) * SEQ * 4
    by_hand = {
        "flash_sel_fwd": (2, 2 * q + 2 * kv),  # q o | k v
        "flash_sel_bwd_dq": (3, 4 * q + 2 * kv),  # q dO O dq | k v
        "flash_sel_bwd_dkv": (4, 3 * q + 4 * kv),  # q dO O | k v dk dv
    }
    for kernel, (matmuls, tensors) in by_hand.items():
        for share in (1.0, 0.25):
            flops, bytes_ = fk.sel_kernel_cost(
                kernel, 1, 32, 4, SEQ, 128, 512, 512, share
            )
            tiles = share * 528
            assert flops == tile * matmuls * tiles * 32
            # ... the int8 selection's computed tiles, each read once
            assert bytes_ == tensors + rows + tiles * 512 * 512
    # compute-bound at the cell's shape: 11.5 / 17.3 / 23.0 ms
    chip = peaks.chip_peaks("TPU v5 lite")
    least, which = roofline_seconds(
        *fk.sel_kernel_cost("flash_sel_fwd", 1, 32, 4, SEQ, 128, 512, 512),
        chip,
    )
    assert which == "compute" and least == pytest.approx(0.01151, rel=1e-3)
    with pytest.raises(KeyError):
        fk.sel_kernel_cost("flash_gqa_fwd", 1, 32, 4, SEQ, 128, 512, 512)


def test_model_flops_and_parameters_by_hand_and_against_the_program():
    sizes = _config()["sizes"]
    part = fk.keye_parts_flops_per_row(sizes, SEQ)
    assert part["projections"] == 4 * SEQ * (
        2 * 2048 * (32 + 8) * 128 + 2 * 32 * 128 * 2048
    )
    assert part["indexer_projections"] == 4 * SEQ * 2 * 2048 * (
        16 * 64 + 64 + 16
    )
    assert part["attention"] == 4 * 2 * 2 * 32 * 128 * 31_458_304
    assert part["index_scores"] == 4 * 2048 * 134_225_920  # 2,048 a pair
    assert part["routed"] == 4 * SEQ * 2 * 3 * 2048 * 768 * 8 * 8 / 128
    assert part["head"] == SEQ * 2 * 2048 * 18992
    total = fk.keye_train_flops_per_sample(sizes, SEQ)
    assert total / 1e12 == pytest.approx(22.65, abs=0.01)
    # a masked-triangle version EXECUTES 4.27x the attention counted
    assert fk.triangle_pairs(SEQ) / fk.selected_pairs(SEQ, 2048) == (
        pytest.approx(4.267, abs=1e-3)
    )
    assert fk.keye_parameters(sizes) == 314_396_160
    # the program's own formula and its own parameter tree say the same
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.models.keye_vl2 import (
        KeyeVL2Config,
        KeyeVL2ForCausalLM,
        keye_vl2_flops_per_row,
        keye_vl2_train_tflops_per_sample,
    )

    cfg = KeyeVL2Config(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 16)
    )
    assert keye_vl2_flops_per_row(cfg, SEQ) == part
    assert keye_vl2_train_tflops_per_sample(cfg, SEQ) * 1e12 == (
        pytest.approx(total)
    )
    shapes = jax.eval_shape(
        lambda: KeyeVL2ForCausalLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 314_396_160


def _run(trace, records=()):
    config = _config()
    args = types.SimpleNamespace(training=types.SimpleNamespace(
        seq_length=SEQ, per_device_batch_size=1,
    ))
    role = types.SimpleNamespace(
        PROGRAMS={"accumulate": "accumulate_step"},
        microbatch_rows_per_device=lambda a: a.training.per_device_batch_size,
    )
    run = types.SimpleNamespace(
        trace=trace, config=config, args=args, role=role,
        device_kind="TPU v5 lite", step_records=list(records),
        seq_length=lambda: SEQ, program=lambda name: role.PROGRAMS[name],
    )
    return run


def test_reducers_read_the_trace_and_stay_under_the_peaks():
    ms = 1e6  # ns
    held = "bf16[8,2048,768]"
    ops = [
        ("%flash_sel_fwd.1 = bf16[1,16384,4096] custom-call(...)", 0, 20 * ms),
        ("%flash_sel_bwd_dq.2 = bf16[1,16384,4096] custom-call()", 0, 30 * ms),
        ("%flash_sel_bwd_dkv.3 = (bf16[1,16384,512]) custom-call()", 0,
         40 * ms),
        ("%flash_sel_bwd_tiled.4 = (bf16[1,16384,4096]) custom-call()", 0,
         32 * ms),
        # the selection's and the loss's block loops of the tree before
        # PR 52 / PR 54 (kernels since): no routed metric may count them
        ("%while.10 = (s32[], s8[64,256,16384], bf16[64,256,16,64]) "
         "while(...)", 0, 90 * ms),
        ("%while.11 = (u32[], u32[256], u32[256,16384]) while(...)", 0,
         30 * ms),
        ("%while.20 = (s32[], f32[128], s8[128,128,16384]) while(...)", 0,
         140 * ms),
        ("%while.21 = (s32[], s8[128,128,16384], f32[1,16384,64]) "
         "while(...)", 0, 200 * ms),
        # the routed path: a sort and a tile loop that carries the held
        # matrices
        ("%sort.5 = (f32[16384,128], s32[16384,128]) sort(...)", 0, 2 * ms),
        (f"%while.30 = (s32[], {held}, bf16[16384,2048]) while(...)", 0,
         5 * ms),
    ]
    trace = {"/device:TPU:0": {
        "XLA Modules": [("jit_accumulate_step(1)", 0, 3000 * ms)],
        "XLA Ops": ops,
    }}
    run = _run(trace)
    fwd = keye_kernel_roofline.reduce(run, {"kernel": "flash_sel_fwd"})
    assert fwd == pytest.approx(100 * 11.511 / 20, rel=1e-3)
    # fewer tiles hold a selected pair: the least time falls with them
    sparse = _run(trace, [{"attn.select_tile_share": 0.5},
                          {"attn.select_tile_share": 0.5}, {}])
    assert keye_kernel_roofline.tile_share(sparse) == 0.5
    assert keye_kernel_roofline.reduce(
        sparse, {"kernel": "flash_sel_fwd"}
    ) == pytest.approx(fwd / 2, rel=0.02)
    for kernel in ("flash_sel_bwd_dq", "flash_sel_bwd_dkv"):
        share = keye_kernel_roofline.reduce(run, {"kernel": kernel})
        assert 50 < share < 60
    # the one sweep: five products a tile, 28.78 ms at the peak
    assert keye_kernel_roofline.reduce(
        run, {"kernel": "flash_sel_bwd_tiled"}
    ) == pytest.approx(100 * 28.778 / 32, rel=1e-3)
    assert keye_kernel_roofline.reduce(run, {"kernel": "flash_gqa_fwd"}) is None
    assert keye_mfu.reduce(run, {}) == pytest.approx(
        100 * 22.6488e12 / 3.0 / 197e12, rel=1e-3
    )
    # the routed metric counts the sort and the loop with the held matrices
    # — and none of the selection's or the loss's loops
    assert moe_routed_time.reduce(run, {}) == pytest.approx(7.0)
    # a program without such ops (the parent) gives nothing, and raises
    # nothing
    bare = _run({"/device:TPU:0": {
        "XLA Modules": [("jit_accumulate_step(1)", 0, 100 * ms)],
        "XLA Ops": [("%fusion.1 = f32[8] fusion()", 0, ms)],
    }})
    for reducer, params in (
        (keye_kernel_roofline, {"kernel": "flash_sel_fwd"}),
        (keye_kernel_roofline, {"kernel": "flash_sel_bwd_tiled"}),
    ):
        assert reducer.reduce(bare, params) is None
        assert reducer.reduce(_run(None), params) is None
    assert keye_mfu.reduce(_run(None), {}) is None
