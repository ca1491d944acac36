"""``tests/test_remat_operands.py``'s three assertions on the
``lfm2`` row of ``remat_cases.TINY`` / ``PUBLISHED``, and what the model's
accumulate_step keeps and replays once it is compiled for a TPU v5e (its row
of ``tools/tpu_aot.py``, no chip: ``tests/tpu_aot_rows.py``)."""
import pytest

import remat_cases as cases
from tpu_aot_rows import tpu_aot

CASES = [("lfm2", policy) for policy in cases.POLICIES]


def test_the_parameters_do_not_depend_on_the_policy():
    cases.check_the_parameters_do_not_depend_on_the_policy("lfm2")


@pytest.mark.parametrize("family,policy", CASES)
def test_the_default_policy_gives_the_same_bits(family, policy):
    cases.check_the_default_policy_gives_the_same_bits(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_the_projections_that_feed_a_kernel_run_once(family, policy):
    cases.check_the_projections_that_feed_a_kernel_run_once(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_kept_bytes_is_the_shapes_arithmetic(family, policy):
    cases.check_kept_bytes_is_the_shapes_arithmetic(family, policy)


def test_lfm2_accumulate_step_keeps_what_its_backward_reads():
    """LFM2-24B-A2B at the cell's cut (5 layers: four short-convolution
    mixers, one grouped-query attention; 1 row of 4,096), compiled for a
    v5e alone and inside its accumulate_step: the grouped kernels read k / v
    at 8 heads beside q's 32 (their metadata says so; no window metadata);
    under remat ``kernel_outputs`` every kernel's outputs are kept, so each
    forward kernel has ONE call site per mixer and the backward replays
    none — short_conv 4 + 4, flash_gqa 1 + 1 (ONE backward kernel); and the program's scratch
    stays where the cell was sized: beside 28 bytes a parameter (13.14 GB)
    under the allocator's 16.91 GB with 1 GB to spare (the tree holds 16
    since PR 62; the bound stays, so that scratch does not grow into the
    room unnoticed)."""
    rows = tpu_aot("gqa_kernels", "lfm2_accumulate_step")
    heads = {"heads": 32, "kv_heads": 8}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_gqa_fwd": heads, "flash_gqa_bwd_tiled": heads,
        }
        # the four column blocks over ONE column block of two kv heads
        assert row["flash_heads"] == {
            "flash_gqa_fwd": 8, "flash_gqa_bwd_tiled": 8,
        }
    row = rows["lfm2_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_gqa_fwd": 1, "flash_gqa_bwd_tiled": 1,
        "short_conv_fwd": 4, "short_conv_bwd": 4,
    }
    assert row["tpu_custom_calls"] == 10
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 1}
    assert 469_285_248 * 28 + row["memory"]["temp_bytes"] <= 15.9e9
    # gradient sinks (PR 33): the tile loops' backward starts from the
    # accumulator's twelve expert leaves and leaves the sums there — no
    # zeroed float32 carry, no ``grad_acc + result`` pass (12 + 12 before),
    # and the scratch those buffers took is gone (1,170,841,600 before)
    # … no float32 -> bf16 pass over a held matrix (PR 50: 24 before, the
    # forward's and the remat replay's twelve; the step is handed the bf16
    # matrices, cast once a global step: ``held_casts``) …
    # … and the walk's loops (PR 42): four routed layers x two directions x
    # the bulk and the tail loop (8 loops with the single-size walk),
    # every backward loop's three ``old + term`` adds inside the fusion of
    # their weight-gradient dot: a slice read and written once, no ``term``
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["memory"]["temp_bytes"] <= 1_170_841_600
    # since PR 41 the conv layers keep B | C | u and the attention layer
    # q / k / v for their backward kernels (remat ``kernel_operands``: the
    # call sites above are unchanged): 721,006,080 bytes of scratch against
    # 657,255,424 under ``kernel_outputs`` (1,028,988,928 without the
    # barrier before the flash call); since PR 46 every layer the stream
    # after its mixer and the attention layer its q / k norm's input too
    # (remat ``whole_mixer``, +104,857,600 kept): 814,876,672
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 0.84e9
