"""``tests/test_remat_operands.py``'s three assertions on the
``smallthinker`` row of ``remat_cases.TINY`` / ``PUBLISHED``, and what the model's
accumulate_step keeps and replays once it is compiled for a TPU v5e (its row
of ``tools/tpu_aot.py``, no chip: ``tests/tpu_aot_rows.py``)."""
import pytest

import remat_cases as cases
from tpu_aot_rows import tpu_aot

CASES = [("smallthinker", policy) for policy in cases.POLICIES]


def test_the_parameters_do_not_depend_on_the_policy():
    cases.check_the_parameters_do_not_depend_on_the_policy("smallthinker")


@pytest.mark.parametrize("family,policy", CASES)
def test_the_default_policy_gives_the_same_bits(family, policy):
    cases.check_the_default_policy_gives_the_same_bits(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_the_projections_that_feed_a_kernel_run_once(family, policy):
    cases.check_the_projections_that_feed_a_kernel_run_once(family, policy)


@pytest.mark.parametrize("family,policy", CASES)
def test_kept_bytes_is_the_shapes_arithmetic(family, policy):
    cases.check_kept_bytes_is_the_shapes_arithmetic(family, policy)


def test_smallthinker_accumulate_step_takes_the_band_and_a_group_of_seven():
    """SmallThinker-21BA3B at the cell's cut (one period: a global NoPE
    layer and three band-4096 RoPE layers; 1 row of 16,384), compiled for a
    v5e alone and inside its accumulate_step: the band kernels carry their
    band and head counts (28 over 4: a whole group of seven a program gets
    through Mosaic — the backward kernels inside the default scoped VMEM,
    the forward with the 23.75 MiB it asks for since its heads overlap,
    ``_fwd_vmem``), the global layer's are
    the grouped causal kernels with the metadata they always had; under
    remat ``kernel_outputs`` no kernel is replayed — 3 + 1 sites a kind;
    the ReLU-gated tile loop's backward sums into the accumulator's twelve
    expert leaves (gradient sinks); and the program's scratch beside 28
    bytes a parameter of state with a draining snapshot stays under the
    15.3 GB line this tree's cells are sized under."""
    rows = tpu_aot("band_kernels", "smallthinker_accumulate_step")
    heads = {"heads": 28, "kv_heads": 4}
    band = dict(heads, band=4096)
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_band_fwd": band, "flash_band_bwd_tiled": band,
            "flash_gqa_fwd": heads, "flash_gqa_bwd_tiled": heads,
        }
        assert set(row["flash_heads"].values()) == {7}  # as before PR 58
    row = rows["smallthinker_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_band_fwd": 3, "flash_band_bwd_tiled": 3,
        "flash_gqa_fwd": 1, "flash_gqa_bwd_tiled": 1,
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
    assert 370_547_200 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
    # since PR 41 the layers keep q / k / v for their backward kernels
    # (remat ``kernel_operands``: still no kernel replayed, above) as the
    # bf16 buffers the kernels read: 2,530,225,152 bytes of scratch against
    # 2,504,165,376 under ``kernel_outputs``. Without the barrier before the
    # flash call XLA keeps the float32 pieces of RoPE's last add instead
    # (4,004,325,376); since PR 46 the stream after attention too (remat
    # ``whole_mixer``, +335,544,320 kept): 2,913,385,984
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 3.0e9
