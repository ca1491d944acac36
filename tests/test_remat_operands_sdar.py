"""``tests/test_remat_operands.py``'s three assertions on the
``sdar`` row of ``remat_cases.TINY`` / ``PUBLISHED``, and what the model's
accumulate_step keeps and replays once it is compiled for a TPU v5e (its row
of ``tools/tpu_aot.py``, no chip: ``tests/tpu_aot_rows.py``)."""
import pytest

import remat_cases as cases
from tpu_aot_rows import tpu_aot

CASES = [("sdar", policy) for policy in cases.POLICIES]


def test_the_parameters_do_not_depend_on_the_policy():
    cases.check_the_parameters_do_not_depend_on_the_policy("sdar")


@pytest.mark.parametrize("family,policy", CASES)
def test_the_default_policy_gives_the_same_bits(family, policy):
    cases.check_the_default_policy_gives_the_same_bits(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_the_projections_that_feed_a_kernel_run_once(family, policy):
    cases.check_the_projections_that_feed_a_kernel_run_once(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_kept_bytes_is_the_shapes_arithmetic(family, policy):
    cases.check_kept_bytes_is_the_shapes_arithmetic(family, policy)


def test_sdar_accumulate_step_takes_the_block_rule_and_a_group_of_eight():
    """SDAR-30B-A3B-Chat at the cell's cut (four layers; 1 row of 4,096
    clean tokens = 8,192 positions, a noisy stream then a clean one),
    compiled for a v5e alone and inside its accumulate_step: the
    block-diffusion kernels carry their blocks (4), their streams' length
    (4,096) and their head counts (32 over 4: a whole group of eight a
    program gets through Mosaic); under remat ``kernel_outputs`` no kernel
    is replayed — 4 sites a kernel, one a layer of the unrolled period; the
    SiLU-gated tile loop's backward sums into the accumulator's twelve
    expert leaves (a scan over single layers zero-filled, copied and cast
    3.0 GB of stacked expert matrices: ``models/sdar_moe._Period``); and the
    program's scratch stays where the cell was sized: beside 28 bytes a
    parameter under the 15.3 GB line (the tree holds 16 since PR 62; the
    bound stays, so that scratch does not grow into the room unnoticed)."""
    rows = tpu_aot("bd_kernels", "sdar_accumulate_step")
    blocks = {"heads": 32, "kv_heads": 4, "block": 4, "stream": 4096}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_bd_fwd": blocks, "flash_bd_bwd_tiled": blocks,
        }
        assert row["flash_heads"] == {  # a whole group a program
            "flash_bd_fwd": 8, "flash_bd_bwd_tiled": 8,
        }
    row = rows["sdar_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_bd_fwd": 4, "flash_bd_bwd_tiled": 4,
    }
    assert row["tpu_custom_calls"] == 8
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 4}
    # … and the walk's loops (PR 42): four routed layers x two directions x
    # the bulk and the tail loop (8 loops with the single-size walk),
    # every backward loop's three ``old + term`` adds inside the fusion of
    # their weight-gradient dot: a slice read and written once, no ``term``
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["layer_body_copies"] == []
    assert 456_346_624 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
    # since PR 41 the layers keep q / k / v for their backward kernels (remat
    # ``kernel_operands``) behind a barrier that makes them buffers of their
    # own: 1,282,795,008 bytes of scratch — UNDER the 1,574,085,632 the
    # program read before either (2,545,200,128 without the barrier); since
    # PR 46 the q / k norm's input and the stream after attention too (remat
    # ``whole_mixer``, +436,207,616 kept): 1,697,340,416
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 1.75e9
