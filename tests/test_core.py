import numpy as np
import pytest

from dedloc_tpu.core.serialization import (
    CompressionType,
    decode_array,
    deserialize_array,
    deserialize_tree,
    encode_array,
    serialize_array,
    serialize_tree,
    wire_roundtrip,
)
from dedloc_tpu.core.timeutils import PerformanceEMA, ValueWithExpiration, get_dht_time
from dedloc_tpu.core.config import (
    CollaborationArguments,
    Registry,
    parse_config,
)


def test_serialize_roundtrip_none(rng):
    x = rng.standard_normal((17, 5)).astype(np.float32)
    y = deserialize_array(serialize_array(x, CompressionType.NONE))
    np.testing.assert_array_equal(x, y)


def test_serialize_roundtrip_float16(rng):
    x = rng.standard_normal((64,)).astype(np.float32)
    y = deserialize_array(serialize_array(x, CompressionType.FLOAT16))
    np.testing.assert_allclose(x, y, atol=1e-2, rtol=1e-2)
    assert y.dtype == np.float32


def test_serialize_roundtrip_uint8(rng):
    x = rng.standard_normal((1000,)).astype(np.float32)
    y = deserialize_array(serialize_array(x, CompressionType.UINT8))
    span = x.max() - x.min()
    assert np.abs(x - y).max() <= span / 255.0 + 1e-6


def test_serialize_tree(rng):
    tree = {"a": rng.standard_normal((3, 3)).astype(np.float32), "b": np.arange(5)}
    out = deserialize_tree(serialize_tree(tree))
    assert set(out) == {"a", "b"}
    np.testing.assert_array_equal(out["b"], tree["b"])


def _golden_input():
    return (np.arange(12, dtype=np.float32).reshape(3, 4) - 5.5) / 3


# ``serialize_array`` / ``serialize_tree`` of ``_golden_input()`` as commit
# a691aa3 wrote them (before the by-reference codec they now share): disk,
# serving, gossip and state transfer keep their byte format
_HEAD = "82a16883a57368617065920304a56474797065a33c6634ab636f6d7072657373696f6e"
_HEAD_CRC = _HEAD.replace("82a16883", "82a16884")
GOLDEN_ARRAYS = {
    ("none", False): _HEAD + (
        "a46e6f6e65a170c430abaaeabf0000c0bf555595bf555555bf000000bfabaa2abe"
        "abaa2a3e0000003f5555553f5555953f0000c03fabaaea3f"),
    ("none", True): _HEAD_CRC + (
        "a46e6f6e65a3637263ce95f03451a170c430abaaeabf0000c0bf555595bf555555"
        "bf000000bfabaa2abeabaa2a3e0000003f5555553f5555953f0000c03fabaaea3f"),
    ("float16", False): _HEAD + (
        "a7666c6f61743136a170c41855bf00beabbcabba00b855b155310038ab3aab3c00"
        "3e553f"),
    ("float16", True): _HEAD_CRC + (
        "a7666c6f61743136a3637263ce5285ed3ca170c41855bf00beabbcabba00b855b1"
        "55310038ab3aab3c003e553f"),
    ("uint8", False): _HEAD.replace("82a16883", "82a16885") + (
        "a575696e7438a26c6fcbbffd555560000000a57363616c65cb3f8d72c820000000"
        "a170c40c00172e465d748ba2b9d1e8ff"),
    ("uint8", True): _HEAD.replace("82a16883", "82a16886") + (
        "a575696e7438a26c6fcbbffd555560000000a57363616c65cb3f8d72c820000000"
        "a3637263ce84ab1b0da170c40c00172e465d748ba2b9d1e8ff"),
}
GOLDEN_TREE = (
    "82a161c44b82a16885a573686170659104a56474797065a33c6634ab636f6d70726573"
    "73696f6ea575696e7438a26c6fcbbffd555560000000a57363616c65cb3f7010102000"
    "0000a170c4040055aaffa162c44c82a16885a57368617065920104a56474797065a33c"
    "6634ab636f6d7072657373696f6ea575696e7438a26c6fcbbfe0000000000000a57363"
    "616c65cb3f70101020000000a170c4040055aaff"
)


@pytest.mark.parametrize("compression, checksum", sorted(GOLDEN_ARRAYS))
def test_serialize_array_byte_format_is_unchanged(compression, checksum):
    data = serialize_array(
        _golden_input(), CompressionType(compression), checksum=checksum
    )
    assert data.hex() == GOLDEN_ARRAYS[(compression, checksum)]


def test_serialize_tree_byte_format_is_unchanged():
    x = _golden_input()
    tree = {"a": x[0], "b": x[1:2]}
    assert serialize_tree(tree, CompressionType.UINT8).hex() == GOLDEN_TREE


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
@pytest.mark.parametrize("compression", list(CompressionType),
                         ids=lambda c: c.value)
def test_by_reference_codec_is_the_bytes_codec(rng, compression, dtype):
    """``encode_array`` + ``decode_array`` are ``serialize_array`` +
    ``deserialize_array`` without the bytes in between: the same header,
    the same payload, the same values — from a ``bytes``, a memoryview or
    the encoded array itself — and, into a float32 ``out``, the values
    ``wire_roundtrip`` gives, bit for bit."""
    import msgpack

    x = (rng.standard_normal((6, 50)) * 4).astype(dtype)
    header, wire = encode_array(x, compression, checksum=True)
    packed = msgpack.unpackb(
        serialize_array(x, compression, checksum=True), raw=False
    )
    assert packed["h"] == header and packed["p"] == wire.tobytes()
    assert wire.flags["C_CONTIGUOUS"]
    if compression is CompressionType.NONE:
        assert wire is x  # nothing to encode: the payload IS the array
    want = deserialize_array(serialize_array(x, compression, checksum=True))
    for payload in (wire, wire.tobytes(), memoryview(wire.tobytes())):
        got = decode_array(header, payload)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    out = np.full(x.size + 10, np.nan, np.float32)
    assert decode_array(header, wire, out=out[5:-5]) is not None
    assert np.isnan(out[:5]).all() and np.isnan(out[-5:]).all()
    expected = wire_roundtrip(x.astype(np.float32), compression).reshape(-1)
    np.testing.assert_array_equal(out[5:-5].view(np.uint32),
                                  expected.view(np.uint32))


@pytest.mark.parametrize("compression", list(CompressionType),
                         ids=lambda c: c.value)
def test_decode_array_refuses_a_wrong_destination_or_payload(rng, compression):
    x = rng.standard_normal(64).astype(np.float32)
    header, wire = encode_array(x, compression, checksum=True)
    for bad_out in (
        np.empty(63, np.float32),            # not the payload's size
        np.empty(64, np.float64),            # not float32
        np.empty(128, np.float32)[::2],      # not contiguous
    ):
        with pytest.raises(ValueError):
            decode_array(header, wire, out=bad_out)
    tampered = bytearray(wire.tobytes())
    tampered[7] ^= 0x01
    with pytest.raises(ValueError, match="checksum"):
        decode_array(header, bytes(tampered), out=np.empty(64, np.float32))
    # verify=False is for a payload that never left the process
    decode_array(header, bytes(tampered), out=np.empty(64, np.float32),
                 verify=False)
    with pytest.raises(ValueError):  # a payload shorter than its shape
        decode_array(dict(header, crc=None), wire.tobytes()[:-4], verify=False)


def test_performance_ema():
    ema = PerformanceEMA(alpha=0.5)
    ema.update(10)
    first = ema.samples_per_second
    assert first > 0
    ema.pause()
    ema.update(10)  # should not change while paused
    assert ema.samples_per_second == first
    ema.resume()
    ema.update(10)
    assert ema.samples_per_second > 0


def test_value_with_expiration():
    v = ValueWithExpiration("x", get_dht_time() + 100)
    assert not v.expired()
    v2 = ValueWithExpiration("x", get_dht_time() - 1)
    assert v2.expired()


def test_registry():
    r = Registry("thing")

    @r.register("foo")
    def foo():
        return 42

    assert r.get("foo")() == 42
    assert "foo" in r
    with pytest.raises(KeyError):
        r.get("bar")
    with pytest.raises(KeyError):
        r.register("foo")(foo)


def test_parse_config_defaults():
    cfg = parse_config(CollaborationArguments, argv=[])
    assert cfg.optimizer.target_batch_size == 4096
    assert cfg.averager.target_group_size == 256
    assert cfg.training.seq_length == 512


def test_parse_config_overrides():
    cfg = parse_config(
        CollaborationArguments,
        argv=[
            "--optimizer.target_batch_size", "128",
            "--dht.initial_peers", "a:1", "b:2",
            "--dht.client_mode", "true",
        ],
    )
    assert cfg.optimizer.target_batch_size == 128
    assert cfg.dht.initial_peers == ["a:1", "b:2"]
    assert cfg.dht.client_mode is True


def test_parse_config_respects_parent_default_factory_overrides():
    # SwAVCollaborationArguments overrides its optimizer field's
    # target_batch_size via default_factory (32768, sgd_collaborative.py:153)
    # — parse_config must honor it, not the nested class's own default.
    from dedloc_tpu.core.config import SwAVCollaborationArguments

    args = parse_config(SwAVCollaborationArguments, [])
    assert args.optimizer.target_batch_size == 32768
    args = parse_config(
        SwAVCollaborationArguments, ["--optimizer.target_batch_size", "64"]
    )
    assert args.optimizer.target_batch_size == 64


def test_make_mesh_rejects_out_of_range_offset():
    import pytest as _pytest

    from dedloc_tpu.parallel.mesh import make_mesh

    with _pytest.raises(ValueError, match="exceeds"):
        make_mesh(4, device_offset=8)
