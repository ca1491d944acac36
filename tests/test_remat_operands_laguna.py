"""``tests/test_remat_operands.py``'s three assertions on the
``laguna`` row of ``remat_cases.TINY`` / ``PUBLISHED``, and what the model's
accumulate_step keeps and replays once it is compiled for a TPU v5e (its row
of ``tools/tpu_aot.py``, no chip: ``tests/tpu_aot_rows.py``)."""
import pytest

import remat_cases as cases
from tpu_aot_rows import tpu_aot

CASES = [("laguna", policy) for policy in cases.POLICIES]


def test_the_parameters_do_not_depend_on_the_policy():
    cases.check_the_parameters_do_not_depend_on_the_policy("laguna")


@pytest.mark.parametrize("family,policy", CASES)
def test_the_default_policy_gives_the_same_bits(family, policy):
    cases.check_the_default_policy_gives_the_same_bits(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_the_projections_that_feed_a_kernel_run_once(family, policy):
    cases.check_the_projections_that_feed_a_kernel_run_once(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_kept_bytes_is_the_shapes_arithmetic(family, policy):
    cases.check_kept_bytes_is_the_shapes_arithmetic(family, policy)


def test_laguna_accumulate_step_takes_a_band_equal_to_the_tile_and_a_group_of_six():
    """Laguna-XS.2 at the cell's cut (the dense layer + a period of three
    window-512 layers and a full one; 1 row of 8,192), compiled for a v5e
    alone and inside its accumulate_step: the band kernels carry their band
    (512 = the tile) and their head counts (64 over 8), the full layers'
    grouped causal ones theirs (48 over 8: a whole group of SIX a program
    gets through Mosaic), and the per-head gate's pair compiles at both head
    counts; under remat ``whole_mixer`` no flash kernel is replayed — 3
    sites a band kernel, 2 a full one — and the gate's forward is (10 sites
    for 5 layers: its output is kept by no rung, ``remat.REPLAYED_KERNELS``);
    the gate writes no float32 array of the context's size (XLA's expression
    wrote 7,267 MB of float32 under ``attn_gate``: PR 48) and adds no
    relayout copy to a layer body, the tile loop's backward sums into the
    accumulator's twelve expert leaves; and the program's scratch beside 28
    bytes a parameter of state with a draining snapshot stays under the 15.3
    GB line this tree's cells are sized under."""
    rows = tpu_aot(
        "laguna_kernels", "head_gate_kernels", "laguna_accumulate_step"
    )
    band = {"heads": 64, "kv_heads": 8, "band": 512}
    full = {"heads": 48, "kv_heads": 8}
    for name in ("laguna_kernels", "laguna_accumulate_step"):
        assert rows[name]["flash_windows"] == {
            "flash_band_fwd": band, "flash_band_bwd_tiled": band,
            "flash_gqa_fwd": full, "flash_gqa_bwd_tiled": full,
        }
        assert rows[name]["flash_heads"] == {  # whole groups: 8 and 6
            "flash_band_fwd": 8, "flash_band_bwd_tiled": 8,
            "flash_gqa_fwd": 6, "flash_gqa_bwd_tiled": 6,
        }
    assert rows["head_gate_kernels"]["kernel_calls"] == {
        "head_gate_fwd": 2, "head_gate_bwd": 2,  # 64 heads, 48 heads
    }
    row = rows["laguna_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_band_fwd": 3, "flash_band_bwd_tiled": 3,
        "flash_gqa_fwd": 2, "flash_gqa_bwd_tiled": 2,
        "head_gate_fwd": 10, "head_gate_bwd": 5,
    }
    assert row["tpu_custom_calls"] == 25
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 5}
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["layer_body_copies"] == []
    # the gates and their gradients, [1, 8192, 64 | 48]: 9.4 MB
    assert row["attn_gate_float32_mb"] <= 32
    assert row["remat_policy"] == "whole_mixer"
    # 2,466,401,792 bytes of scratch beside 10.91 GB (2,935,357,952 with
    # the gate as XLA's expression, PR 47; 2,984,545,792 with the kernel's
    # output kept)
    assert row["memory"]["temp_bytes"] <= 2.6e9
    assert 389_634_048 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
