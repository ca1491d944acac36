"""``flops_lm.py`` against hand counts, and the reducers that read it."""
import pytest

from benchmark import flops_lm, peaks
from benchmark.flops import roofline_seconds


def test_causal_tiles_by_hand():
    # equal tiles: the diagonal and everything under it, n(n+1)/2
    assert flops_lm.causal_tiles(4096, 512, 512) == 36
    assert flops_lm.causal_tiles(512, 512, 512) == 1
    # 4 query tiles of 32 over 2 key tiles of 64: 1 + 1 + 2 + 2
    assert flops_lm.causal_tiles(128, 32, 64) == 6
    # 2 query tiles of 64 over 4 key tiles of 32: 2 + 4
    assert flops_lm.causal_tiles(128, 64, 32) == 6


def test_causal_kernel_costs_by_hand():
    # one row, 16 heads x 128, S=4,096, tiles of 512: 36 tiles a head
    tile = 2 * 512 * 512 * 128  # one matmul of one tile
    tensor = 16 * 4096 * 128 * 2  # one bf16 operand
    rows = (16 + 1) * 4096 * 4  # lse per head + bias per row, float32
    for kernel, matmuls, tensors in (
        ("flash_causal_fwd", 2, 4), ("flash_causal_bwd_dq", 3, 6),
        ("flash_causal_bwd_dkv", 4, 7),
    ):
        flops, bytes_ = flops_lm.causal_kernel_cost(
            kernel, 1, 16, 4096, 128, 512, 512
        )
        assert flops == matmuls * tile * 36 * 16
        assert bytes_ == tensors * tensor + rows
    # the triangle: 36 of 64 tiles, 56 % of the non-causal work
    full = 2 * 2.0 * 16 * 4096 * 4096 * 128
    assert flops_lm.causal_kernel_cost(
        "flash_causal_fwd", 1, 16, 4096, 128, 512, 512
    )[0] == pytest.approx(full * 36 / 64)
    # compute binds on a v5e: 77 GFLOP against 67 MB
    least, which = roofline_seconds(
        *flops_lm.causal_kernel_cost(
            "flash_causal_fwd", 1, 16, 4096, 128, 512, 512
        ), peaks.chip_peaks("TPU v5 lite"),
    )
    assert which == "compute" and least == pytest.approx(3.92e-4, rel=0.01)
    with pytest.raises(KeyError):
        flops_lm.causal_kernel_cost("flash_fwd", 1, 16, 4096, 128, 512, 512)


def test_model_flops_by_hand_and_equal_to_the_programs():
    # per token and layer: q k v o, SwiGLU, the causal triangle; per pass
    # the untied head; four passes; backward twice the forward
    layer = 2 * 4 * 2048 ** 2 + 2 * 3 * 2048 * 5632 + 2 * 2 * 2048 * 2048.5
    want = 3 * 4 * (3 * layer + 2 * 2048 * 49152) * 4096
    got = flops_lm.ouro_train_flops_per_sample(
        2048, 5632, 16, 128, 49152, 3, 4, 4096
    )
    assert got == pytest.approx(want)
    # the recorder's own FLOP model (its MFU gauge) is the same arithmetic
    from dedloc_tpu.models.ouro import OuroConfig, ouro_train_tflops_per_sample

    cfg = OuroConfig.ouro_2p6b(num_hidden_layers=3)
    assert ouro_train_tflops_per_sample(cfg, 4096) * 1e12 == pytest.approx(got)
    # the head's share: ~36 % at 3 layers, ~4 % at the published 48
    head = 3 * 4 * 2 * 2048 * 49152 * 4096
    assert head / got == pytest.approx(0.36, abs=0.01)
    deep = flops_lm.ouro_train_flops_per_sample(
        2048, 5632, 16, 128, 49152, 48, 4, 4096
    )
    assert head / deep == pytest.approx(0.034, abs=0.003)
