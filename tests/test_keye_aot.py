"""Keye-VL-2.0's device programs compiled for a TPU v5e WITHOUT a chip
(``tools/tpu_aot.py``, libtpu's compile-only client): the other models'
rows of the same kind are ``tests/test_bringup.py``'s, whose helper this
uses; a file of its own because that one is one worker's 11 minutes
(ROADMAP C9)."""
from tests.test_bringup import _tpu_aot


def test_keye_accumulate_step_reads_a_selection_and_holds_nothing_heads_by_s_by_s():
    """Keye-VL-2.0's language model at the cell's cut (four layers, 1 row of
    16,384), compiled for a v5e alone and inside its accumulate_step: the
    selected kernels — the int8 [S, S] selection a tile operand, the tile
    flags in SMEM — get through Mosaic at 32 query heads over 4 kv heads;
    under the model's default remat ``kernel_operands`` no kernel is
    replayed (4 sites each: the selection is KEPT with the operands), the
    selection is ONE ``index_select`` call a layer and the indexer's loss
    its own kernel pair over the same tiles, and nothing of either's XLA
    block loop is left; the
    tile loop's backward sums into the accumulator's twelve expert leaves;
    NOTHING of size [heads, S, S] is materialised — the largest array the
    compiled module names is 256 MB (the int8 selection itself), where ONE
    head's float32 scores are 1,024 MB and 32
    heads' bf16 ones 17 GB —; and the program's scratch beside 28 bytes a
    parameter of state with a draining snapshot (+ the held experts' bf16
    copies) stays under the 15.3 GB line."""
    rows = _tpu_aot("sel_kernels", "index_loss_kernels", "select_kernels",
                    "keye_accumulate_step")
    # the selection's kernel alone (``ops/index_select.py``): Mosaic takes a
    # block of 256 query rows' ordered keys, [256, 16384] int32, as VMEM
    # scratch beside the resident key head and the int8 rows it writes
    assert rows.pop("select_kernels")["kernel_calls"] == {"index_select": 1}
    # the indexer's loss kernels alone (``ops/index_loss.py``): Mosaic takes
    # the forward sweep and the one backward sweep that holds the key
    # head's whole gradient, [16384, 128] float32, in VMEM
    assert rows.pop("index_loss_kernels")["kernel_calls"] == {
        "index_loss_fwd": 1, "index_loss_bwd": 1,
    }
    heads = {"heads": 32, "kv_heads": 4}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_sel_fwd": heads, "flash_sel_bwd_dq": heads,
            "flash_sel_bwd_dkv": heads,
        }
    assert rows["sel_kernels"]["kernel_calls"] == {
        "flash_sel_fwd": 1, "flash_sel_bwd_dq": 1, "flash_sel_bwd_dkv": 1,
    }
    row = rows["keye_accumulate_step"]
    # ... and the loss's pair: one forward sweep a layer (its logZ rides in
    # a Pallas output, which the policy keeps: no replay), one backward
    assert row["kernel_calls"] == {
        "flash_sel_fwd": 4, "flash_sel_bwd_dq": 4, "flash_sel_bwd_dkv": 4,
        "index_loss_fwd": 4, "index_loss_bwd": 4, "index_select": 4,
    }
    assert row["tpu_custom_calls"] == 24
    # no float32 [128, 16, 16384] index scores, no [4, 8, 128, 16384] main
    # scores, no selection cut into the loss's blocks of 128 rows — what the
    # XLA block loop made, 128 blocks a layer and direction (PR 51) — in the
    # lowered or the compiled module
    assert row["loss_block_transients"] == []
    # ... and no float32 [256, 16, 16384] index scores, no selection written
    # a block of 256 rows at a time (``s8[64,256,16384]``: the XLA loop's
    # slabs, 64 steps a layer, PR 51)
    assert row["select_block_transients"] == []
    grads = row["expert_grad_passes"]
    assert (grads["adds"], grads["zero_fills"], grads["held_casts"]) == (
        0, 0, 0
    )
    assert row["remat_policy"] == "kernel_operands"
    largest = row["largest_buffers_mb"]
    assert largest and max(mb for _shape, mb in largest) <= 256.0, largest
    assert any(shape == "s8[1,16384,16384]" for shape, _mb in largest)
    # 5,336,333,312 bytes of scratch (PR 51) beside 8.80 + 0.30 GB
    assert row["memory"]["temp_bytes"] <= 5.5e9
    held = 4 * 8 * 3 * 2048 * 768
    assert 314_396_160 * 28 + held * 2 + row["memory"]["temp_bytes"] <= 15.3e9
