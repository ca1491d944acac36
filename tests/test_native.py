"""Native wire codec: C++ path vs numpy reference, plus fallback parity.

The native library is the in-tree equivalent of the reference's native wire
dependencies (SURVEY.md §2.7). These tests pin down: bit-exact fp16 over the
full 16-bit domain, quantizer parity with the numpy fallback, checksum
agreement between the C++ and pure-python CRC32C, and corrupt-frame
rejection in the serialization layer.
"""
import numpy as np
import pytest

from dedloc_tpu import native
from dedloc_tpu.core.serialization import (
    CompressionType,
    deserialize_array,
    serialize_array,
)


def test_native_library_loaded():
    # the image ships g++; the lazy build must succeed here
    assert native.AVAILABLE


def test_f32_to_f16_bit_exact():
    rng = np.random.default_rng(0)
    x = np.concatenate(
        [
            rng.standard_normal(50_000),
            rng.standard_normal(1_000) * 1e-6,  # subnormal range
            [0.0, -0.0, np.inf, -np.inf, np.nan, 65504.0, 70000.0, 1e-45],
        ]
    ).astype(np.float32)
    assert np.array_equal(
        native.f32_to_f16(x).view(np.uint16), x.astype(np.float16).view(np.uint16)
    )


def test_f16_to_f32_bit_exact_full_domain():
    all_h = np.arange(65536, dtype=np.uint16).view(np.float16)
    ours = native.f16_to_f32(all_h)
    ref = all_h.astype(np.float32)
    # hardware F16C (VCVTPH2PS) quietens signaling NaNs per IEEE-754 while
    # scalar/numpy preserve raw payloads — NaN payloads carry no information
    # on the gradient wire, so NaNs compare as a class, everything else
    # bit-exactly
    nan = np.isnan(ref)
    assert np.array_equal(
        ours.view(np.uint32)[~nan], ref.view(np.uint32)[~nan]
    )
    assert np.isnan(ours[nan]).all()


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(10_000).astype(np.float32) * 3
    q, lo, scale = native.quantize_uint8(x)
    back = native.dequantize_uint8(q, lo, scale)
    assert np.abs(back - x).max() <= scale * 0.5 + 1e-6


def test_quantize_constant_array():
    x = np.full(100, 2.5, np.float32)
    q, lo, scale = native.quantize_uint8(x)
    assert np.allclose(native.dequantize_uint8(q, lo, scale), 2.5)


@pytest.mark.parametrize("use_native", [True, False])
def test_decoders_write_into_the_callers_destination(monkeypatch, use_native):
    """``f16_to_f32`` / ``dequantize_uint8`` with ``out=``: the same bits as
    without, written into (a slice of) the caller's float32 array and
    nowhere else — native path and numpy fallback alike; a destination of
    another size, dtype or layout is refused before any pointer is used."""
    if not use_native:
        monkeypatch.setattr(native, "_lib", None)
    elif not native.AVAILABLE:
        pytest.skip("no native codec here")
    rng = np.random.default_rng(5)
    h = rng.standard_normal(1001).astype(np.float16)
    q = rng.integers(0, 256, 1001).astype(np.uint8)
    for decode, src in (
        (native.f16_to_f32, h),
        (lambda x, out=None: native.dequantize_uint8(x, -1.5, 0.0123, out=out),
         q),
    ):
        fresh = decode(src)
        buf = np.full(1001 + 8, np.nan, np.float32)
        got = decode(src, out=buf[4:-4])
        assert got.base is buf
        np.testing.assert_array_equal(got.view(np.uint32),
                                      fresh.view(np.uint32))
        assert np.isnan(buf[:4]).all() and np.isnan(buf[-4:]).all()
        for bad in (np.empty(1000, np.float32), np.empty(1001, np.float64),
                    np.empty(2002, np.float32)[::2]):
            with pytest.raises(ValueError):
                decode(src, out=bad)
        read_only = np.empty(1001, np.float32)
        read_only.flags.writeable = False
        with pytest.raises(ValueError):
            decode(src, out=read_only)


def test_crc32c_reads_any_contiguous_buffer():
    """The crc is taken over the encoded ARRAY (send side) and over the
    frame's memoryview (receive side): the same number as over its bytes."""
    x = np.random.default_rng(6).standard_normal(999).astype(np.float16)
    want = native.crc32c(x.tobytes())
    assert native.crc32c(x) == want
    assert native.crc32c(memoryview(x.tobytes())) == want
    assert native.crc32c(np.empty(0, np.float16)) == native.crc32c(b"")


def test_axpy_and_scale():
    rng = np.random.default_rng(2)
    x = rng.standard_normal(1000).astype(np.float32)
    acc = np.zeros_like(x)
    native.axpy(acc, x, 2.5)
    native.axpy(acc, x, 0.5)
    assert np.allclose(acc, 3.0 * x, rtol=1e-6)
    native.scale(acc, 1.0 / 3.0)
    assert np.allclose(acc, x, rtol=1e-5)


def test_crc32c_known_vector():
    # RFC 3720 check value for "123456789"
    assert native.crc32c(b"123456789") == 0xE3069283
    assert native._crc32c_py(b"123456789") == 0xE3069283


def test_crc32c_native_matches_python():
    rng = np.random.default_rng(3)
    for n in (0, 1, 7, 256, 4096):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert native.crc32c(data) == native._crc32c_py(data)


def test_corrupt_frame_rejected():
    x = np.arange(100, dtype=np.float32)
    blob = bytearray(serialize_array(x, CompressionType.FLOAT16, checksum=True))
    # flip a bit somewhere in the payload (the tail of the msgpack blob)
    blob[-10] ^= 0x40
    with pytest.raises(ValueError, match="checksum"):
        deserialize_array(bytes(blob))
    # untampered frame still passes
    y = deserialize_array(serialize_array(x, CompressionType.FLOAT16, checksum=True))
    assert np.allclose(y, x)
