"""Tensor wire (de)serialization with lossy compression.

Capability parity with the reference's ``CompressionType.Value("FLOAT16")``
wire format for averaging rounds (albert/arguments.py:75-77) plus a
uint8 per-chunk affine quantizer for lower-bandwidth links. The framing is
msgpack (self-describing, protobuf-free — see SURVEY.md §2.7).

All encoders take/return numpy arrays: device arrays are fetched to host by
the caller at the jit↔asyncio seam (SURVEY.md §7 hard-part b).

Two forms of one codec: ``encode_array`` / ``decode_array`` work BY REFERENCE
(header dict + the encoded array; decode from any buffer, into the caller's
float32 destination) for the all-reduce, whose payloads ride RPC frames as
attachments; ``serialize_array`` / ``deserialize_array`` wrap them into
self-contained ``bytes`` (msgpack of header + payload) for disk, serving,
gossip and state transfer.
"""
from __future__ import annotations

import enum
from typing import Any, Dict, Optional, Tuple

import msgpack
import numpy as np

from dedloc_tpu import native


class CompressionType(enum.Enum):
    NONE = "none"
    FLOAT16 = "float16"
    UINT8 = "uint8"  # per-tensor affine quantization with fp32 scale/zero-point


def encode_array(
    x: np.ndarray,
    compression: CompressionType = CompressionType.NONE,
    checksum: bool = False,
) -> Tuple[Dict[str, Any], np.ndarray]:
    """Encode ``x`` for the wire BY REFERENCE: ``(header, wire)`` where
    ``wire`` is the contiguous array whose buffer IS the payload (``x``
    itself under ``NONE`` when it is contiguous) and ``header`` the small
    dict that decodes it (shape, dtype, compression, ``lo`` / ``scale``,
    ``crc`` over the payload bytes). The all-reduce hands ``wire`` to the
    socket as a frame attachment (``dht/protocol.Blob``); callers that want
    self-contained ``bytes`` use ``serialize_array``."""
    x = np.asarray(x)
    header: Dict[str, Any] = {
        "shape": list(x.shape),
        "dtype": x.dtype.str,
        "compression": compression.value,
    }
    if compression is CompressionType.NONE:
        wire = np.ascontiguousarray(x)
    elif compression is CompressionType.FLOAT16:
        if x.dtype == np.float16:
            wire = np.ascontiguousarray(x)
        else:
            wire = native.f32_to_f16(x.astype(np.float32, copy=False))
    elif compression is CompressionType.UINT8:
        wire, lo, scale = native.quantize_uint8(
            x.astype(np.float32, copy=False)
        )
        header["lo"], header["scale"] = lo, scale
    else:  # pragma: no cover
        raise ValueError(f"unknown compression {compression}")
    if checksum:
        header["crc"] = native.crc32c(wire)
    return header, wire


def decode_array(
    header: Dict[str, Any],
    payload,
    out: Optional[np.ndarray] = None,
    verify: bool = True,
) -> np.ndarray:
    """Decode one payload (any buffer: ``bytes``, a frame attachment's
    memoryview, an encoded array) under ``header``. With ``out`` — a
    contiguous float32 destination of the payload's size, e.g. a slice of
    the round's result — the values are written INTO it, whatever dtype the
    header names, and ``out`` is returned; without, a fresh array of the
    header's dtype. ``verify=False`` skips the crc: for a payload that
    never left this process."""
    if verify and "crc" in header and native.crc32c(payload) != header["crc"]:
        raise ValueError("wire chunk checksum mismatch (corrupt frame)")
    shape = tuple(header["shape"])
    dtype = np.dtype(header["dtype"])
    compression = CompressionType(header["compression"])
    if compression is CompressionType.NONE:
        x = np.frombuffer(payload, dtype=dtype).reshape(shape)
        if out is None:
            return x.copy()
        out = native.f32_destination(out, x)
        np.copyto(out, x.reshape(out.shape), casting="unsafe")
        return out
    if compression is CompressionType.FLOAT16:
        h = np.frombuffer(payload, dtype=np.float16).reshape(shape)
        if out is not None:
            return native.f16_to_f32(h, out=out)
        if dtype == np.float16:
            return h.copy()
        return native.f16_to_f32(h).astype(dtype, copy=False)
    if compression is CompressionType.UINT8:
        q = np.frombuffer(payload, dtype=np.uint8).reshape(shape)
        x = native.dequantize_uint8(q, header["lo"], header["scale"], out=out)
        return x if out is not None else x.astype(dtype, copy=False)
    raise ValueError(f"unknown compression {compression}")  # pragma: no cover


def serialize_array(
    x: np.ndarray,
    compression: CompressionType = CompressionType.NONE,
    checksum: bool = False,
) -> bytes:
    """``encode_array`` as self-contained ``bytes`` (msgpack of header +
    payload): disk, serving, gossip and state paths."""
    header, wire = encode_array(x, compression, checksum)
    return msgpack.packb({"h": header, "p": wire.tobytes()}, use_bin_type=True)


def deserialize_array(data: bytes) -> np.ndarray:
    obj = msgpack.unpackb(data, raw=False)
    return decode_array(obj["h"], obj["p"])


def wire_roundtrip(
    x: np.ndarray, compression: CompressionType
) -> np.ndarray:
    """What the receiving side of the wire reconstructs for ``x`` — encode
    then decode, skipping the msgpack framing. Used by the optimizer's
    error-feedback residual to measure this round's quantization error
    without touching the network."""
    x = np.asarray(x, dtype=np.float32)
    if compression is CompressionType.NONE:
        return x
    if compression is CompressionType.FLOAT16:
        return native.f16_to_f32(native.f32_to_f16(x))
    if compression is CompressionType.UINT8:
        q, lo, scale = native.quantize_uint8(x)
        return native.dequantize_uint8(q, lo, scale).reshape(x.shape)
    raise ValueError(f"unknown compression {compression}")  # pragma: no cover


def serialize_tree(
    tree: Dict[str, np.ndarray],
    compression: CompressionType = CompressionType.NONE,
) -> bytes:
    """Serialize a flat {name: array} mapping (e.g. flattened params/grads)."""
    return msgpack.packb(
        {k: serialize_array(v, compression) for k, v in tree.items()},
        use_bin_type=True,
    )


def deserialize_tree(data: bytes) -> Dict[str, np.ndarray]:
    obj = msgpack.unpackb(data, raw=False)
    return {k: deserialize_array(v) for k, v in obj.items()}


def pack_obj(obj: Any, default=None) -> bytes:
    """msgpack helper for small control-plane objects (DHT values, metadata)
    and RPC frames; ``default`` is msgpack's hook for types it does not
    know (``dht/protocol``'s attachments)."""
    return msgpack.packb(obj, use_bin_type=True, default=default)


def unpack_obj(data, ext_hook=msgpack.ExtType) -> Any:
    return msgpack.unpackb(data, raw=False, ext_hook=ext_hook)
