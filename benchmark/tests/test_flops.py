"""Operations and bytes against hand counts, and the peak table."""
import pytest

from benchmark import flops, peaks


def test_albert_large_flops_per_sample():
    """By hand for ALBERT-large at S=512: per token and layer
    8*1024^2 + 4*1024*512 + 4*1024*4096 = 27,262,976; x 24 layers x 512
    tokens = 335,007,449,088; embedding projection 2*128*1024*512 =
    134,217,728; MLM head 80 * 2 * (1024*128 + 128*30000) = 635,371,520; SOP
    4,096; forward 335,777,042,432, training x3 = 1.0073 TFLOP (bench.py and
    PERF.md's 1.007)."""
    assert flops.max_predictions_for(512) == 80
    value = flops.albert_train_flops_per_sample(
        1024, 4096, 128, 30000, 24, 512, 80
    )
    assert value == 3 * (335_007_449_088 + 134_217_728 + 635_371_520 + 4_096)
    assert value / 1e12 == pytest.approx(1.0073, abs=1e-4)


def test_flash_costs():
    """12 rows x 16 heads, S=512, D=64. One score matmul is 2*512*512*64 =
    33,554,432 FLOPs a head; forward has 2, the fused backward 5. One bf16
    tensor is 192*512*64*2 = 12,582,912 bytes; one f32 row set 192*512*4 =
    393,216."""
    f, b = flops.flash_fwd_cost(12, 16, 512, 64)
    assert f == 2 * 192 * 33_554_432
    assert b == 4 * 12_582_912 + 2 * 393_216
    f, b = flops.flash_bwd_fused_cost(12, 16, 512, 64)
    assert f == 5 * 192 * 33_554_432
    assert b == 7 * 12_582_912 + 3 * 393_216


def test_kernel_cost_by_name():
    assert flops.kernel_cost("flash_bwd_fused", 12, 16, 512, 64) == (
        flops.flash_bwd_fused_cost(12, 16, 512, 64)
    )
    with pytest.raises(KeyError):
        flops.kernel_cost("no_such_kernel", 12, 16, 512, 64)


def test_roofline_says_which_bound_binds():
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert v5e == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    seconds, which = flops.roofline_seconds(*flops.flash_fwd_cost(12, 16, 512, 64), v5e)
    # 12.885 GFLOP / 197 TFLOP/s = 65.4 us; 51.1 MB / 819 GB/s = 62.4 us
    assert which == "compute" and seconds == pytest.approx(65.4e-6, rel=0.005)
    # a call that only moves bytes is bound by memory: 819 MB take 1 ms
    assert flops.roofline_seconds(1.0, 819e6, v5e) == (pytest.approx(1e-3), "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v9 imaginary")
