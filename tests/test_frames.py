"""The RPC frame format (``dht/protocol.py``): plain frames are byte for byte
what they always were (golden bytes taken from the tree before attachments
existed); a message that holds a ``Blob`` travels as an attachment frame —
small msgpack part, payload bytes raw behind it, by reference on both
sides — at any depth, in requests and replies, through a relay and under
fault injection; malformed frames are refused; the always-on accumulators
and the ``net.bytes_*`` counters count header + attachments."""
import asyncio
import struct

import msgpack
import numpy as np
import pytest

from dedloc_tpu.core.serialization import pack_obj
from dedloc_tpu.dht import protocol
from dedloc_tpu.dht.protocol import (
    MAX_FRAME,
    Blob,
    RelayService,
    RPCClient,
    RPCServer,
    read_frame,
    write_frame,
)
from dedloc_tpu.telemetry import registry
from dedloc_tpu.telemetry.registry import Telemetry

_LEN = struct.Struct("!I")
_ATTACHED = 1 << 31


class _Sink:
    """The writer surface ``write_frame`` touches; keeps what it was handed."""

    def __init__(self):
        self.writes = []

    def write(self, data):
        self.writes.append(data)

    def bytes(self):
        return b"".join(bytes(w) for w in self.writes)


def _written(obj):
    sink = _Sink()
    sent = write_frame(sink, obj)
    return sink, sent


def _read(raw: bytes, owner=None):
    async def go():
        reader = asyncio.StreamReader()
        reader.feed_data(raw)
        reader.feed_eof()
        return await read_frame(reader, owner)

    return asyncio.run(go())


# ------------------------------------------------ plain frames: golden bytes

# written by ``write_frame`` of commit a691aa3 (the tree before attachment
# frames): every message without a Blob must still produce exactly these
GOLDEN = {
    "request": (
        {"id": 1, "method": "echo", "args": {"x": 7, "s": "hi"}},
        "0000001f83a2696401a66d6574686f64a46563686fa46172677382a17807a173a2"
        "6869",
    ),
    "reply_ok": (
        {"id": 1, "ok": True, "result": {"echo": {"x": 7, "s": "hi"}}},
        "0000001f83a2696401a26f6bc3a6726573756c7481a46563686f82a17807a173a2"
        "6869",
    ),
    "reply_error": (
        {"id": 9, "ok": False, "error": "KeyError('nope')"},
        "0000002083a2696409a26f6bc2a56572726f72b04b65794572726f7228276e6f70"
        "652729",
    ),
    "traced_request": (
        {"id": 3, "method": "dht.find",
         "args": {"key": b"\x00\x01\xfe\xff", "k": 20},
         "tc": ["a1b2c3", 17, "peer-0"]},
        "0000003b84a2696403a66d6574686f64a86468742e66696e64a46172677382a36b"
        "6579c4040001feffa16b14a2746393a661316232633311a6706565722d30",
    ),
    "binary_and_floats": (
        {"id": 4, "ok": True,
         "result": {"data": bytes(range(40)), "weight": 2.5, "none": None,
                    "list": [1, -2, 3.0, "x"]}},
        "0000006983a2696404a26f6bc3a6726573756c7484a464617461c4280001020304"
        "05060708090a0b0c0d0e0f101112131415161718191a1b1c1d1e1f202122232425"
        "2627a6776569676874cb4004000000000000a46e6f6e65c0a46c6973749401fecb"
        "4008000000000000a178",
    ),
    "relay_wrapped": (
        {"id": 5, "method": "relay.call",
         "args": {"to": "70656572", "method": "avg.part",
                  "args": {"round_id": "r", "chunk": -1, "data": None},
                  "timeout": 30.0}},
        "0000006883a2696405a66d6574686f64aa72656c61792e63616c6ca46172677384"
        "a2746fa83730363536353732a66d6574686f64a86176672e70617274a461726773"
        "83a8726f756e645f6964a172a56368756e6bffa464617461c0a774696d656f7574"
        "cb403e000000000000",
    ),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_plain_frame_is_byte_identical_to_the_parents(name):
    obj, golden = GOLDEN[name]
    sink, sent = _written(obj)
    assert sent is None  # no attachment: nothing to count
    assert sink.bytes().hex() == golden
    # the same two writes as ever: the length word, then msgpack's bytes
    assert [len(w) for w in sink.writes] == [4, len(pack_obj(obj))]
    assert _read(sink.bytes()) == obj


# ------------------------------------------------- attachments: the round trip


def _payloads():
    rng = np.random.default_rng(35)
    return {
        "f16": rng.standard_normal(1000).astype(np.float16),
        "u8": rng.integers(0, 255, 333).astype(np.uint8),
        "f32_2d": rng.standard_normal((7, 9)).astype(np.float32),
        "bytes": bytes(range(200)),
        "empty_array": np.empty(0, np.float16),
        "empty_bytes": b"",
    }


ATTACHED_MESSAGES = {
    "depth_1_request": lambda p: {
        "id": 1, "method": "avg.part",
        "args": {"round_id": "r", "chunk": 3, "data": Blob(p["f16"])}},
    "nested_under_relay_call": lambda p: {
        "id": 2, "method": "relay.call",
        "args": {"to": "ab", "method": "avg.part", "timeout": 5.0,
                 "args": {"round_id": "r", "data": Blob(p["f16"])}}},
    "reply": lambda p: {
        "id": 3, "ok": True,
        "result": {"h": {"shape": [333]}, "data": Blob(p["u8"]),
                   "weight": 2.0}},
    "several_in_one_frame": lambda p: {
        "id": 4, "ok": True,
        "result": {"parts": [Blob(p["f16"]), Blob(p["bytes"]),
                             {"deep": [Blob(p["f32_2d"])]}],
                   "plain": b"packed in line"}},
    "zero_length": lambda p: {
        "id": 5, "method": "m",
        "args": {"a": Blob(p["empty_array"]), "b": Blob(p["empty_bytes"]),
                 "c": Blob(p["u8"])}},
}


def _blobs(obj):
    """Every Blob in ``obj``, in msgpack's traversal order."""
    if isinstance(obj, Blob):
        return [obj]
    if isinstance(obj, dict):
        return [b for v in obj.values() for b in _blobs(v)]
    if isinstance(obj, list):
        return [b for v in obj for b in _blobs(v)]
    return []


def _strip(obj):
    """``obj`` with each Blob replaced by its bytes (to compare messages)."""
    if isinstance(obj, Blob):
        return bytes(obj.view)
    if isinstance(obj, dict):
        return {k: _strip(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_strip(v) for v in obj]
    return obj


@pytest.mark.parametrize("name", sorted(ATTACHED_MESSAGES))
def test_attachments_round_trip_by_reference(name):
    msg = ATTACHED_MESSAGES[name](_payloads())
    sent_blobs = _blobs(msg)
    sink, sent = _written(msg)
    assert sent == (len(sent_blobs), sum(len(b) for b in sent_blobs))
    raw = sink.bytes()
    # layout: flagged length word, msgpack length, msgpack, payloads raw
    (word,) = _LEN.unpack_from(raw)
    assert word & _ATTACHED and (word & ~_ATTACHED) == len(raw) - 4
    (packed,) = _LEN.unpack_from(raw, 4)
    assert 8 + packed + sent[1] == len(raw)
    assert raw[8 + packed:] == b"".join(bytes(b.view) for b in sent_blobs)
    # the msgpack part holds placeholders, never the payload
    assert packed < 200 + len(b"packed in line")
    # by reference on the way out: each non-empty payload is handed to the
    # writer as a view of the wrapped object's OWN buffer, not a copy
    handed = sink.writes[1:]
    assert len(handed) == sum(1 for b in sent_blobs if len(b))
    for view, blob in zip(handed, (b for b in sent_blobs if len(b))):
        assert isinstance(view, memoryview) and view.obj is blob.view.obj
    # and on the way in: Blobs over the frame's own buffer, same bytes
    got = _read(raw)
    assert _strip(got) == _strip(msg)
    got_blobs = _blobs(got)
    assert len(got_blobs) == len(sent_blobs)
    bodies = {id(b.view.obj) for b in got_blobs if len(b)}
    assert len(bodies) <= 1  # all views of the one buffer readexactly gave
    # a Blob that came off a frame re-attaches when the message is sent on
    relayed, resent = _written({"id": 9, "ok": True, "result": got})
    assert resent == sent
    assert _strip(_read(relayed.bytes())["result"]) == _strip(msg)


def test_array_decodes_in_place_from_the_frames_buffer():
    """``np.frombuffer`` on the received view sees the sender's values and
    shares the frame's memory (no unpack in between)."""
    x = np.arange(4096, dtype=np.float16)
    sink, _ = _written({"id": 1, "args": {"data": Blob(x)}})
    blob = _read(sink.bytes())["args"]["data"]
    y = np.frombuffer(blob.view, dtype=np.float16)
    np.testing.assert_array_equal(x, y)
    assert not y.flags["OWNDATA"] and not y.flags["WRITEABLE"]


# ------------------------------------------------------- send-side refusals


@pytest.mark.parametrize("name, make", [
    ("non_contiguous", lambda: np.arange(100, dtype=np.float32)[::2]),
    ("transposed", lambda: np.zeros((4, 6), np.float16).T),
    ("object_dtype", lambda: np.array([1, "a", None], dtype=object)),
    ("datetime_dtype", lambda: np.zeros(3, dtype="datetime64[s]")),
    ("not_a_buffer", lambda: [1, 2, 3]),
])
def test_blob_refuses_what_has_no_plain_contiguous_buffer(name, make):
    with pytest.raises((TypeError, ValueError)):
        Blob(make())


@pytest.mark.parametrize("name, make", [
    ("bare_array", lambda: np.zeros(4, np.float16)),
    ("set", lambda: {1, 2}),
    ("plain_object", lambda: object()),
])
def test_write_frame_refuses_unknown_types_as_msgpack_does(name, make):
    sink = _Sink()
    with pytest.raises(TypeError):
        write_frame(sink, {"id": 1, "args": {"data": make()}})
    assert sink.writes == []  # refused before a byte is written


def test_write_frame_refuses_an_attachment_frame_over_max_frame(monkeypatch):
    monkeypatch.setattr(protocol, "MAX_FRAME", 1000)
    sink = _Sink()
    with pytest.raises(ValueError, match="frame too large"):
        write_frame(sink, {"id": 1, "args": {"data": Blob(bytes(2000))}})
    assert sink.writes == []


# ------------------------------------------------------- read-side refusals


def _attached_frame(packed_len, meta, tail, body_len=None):
    body = _LEN.pack(packed_len) + meta + tail
    n = len(body) if body_len is None else body_len
    return _LEN.pack(_ATTACHED | n) + body


def _meta(*sizes):
    return msgpack.packb(
        {"id": 1, "b": [msgpack.ExtType(66, _LEN.pack(n)) for n in sizes]},
        use_bin_type=True,
    )


@pytest.mark.parametrize("name, raw, error", [
    ("plain_length_over_max_frame",
     _LEN.pack(MAX_FRAME + 1), "frame too large"),
    ("attached_bit_and_body_over_max_frame",
     _LEN.pack(_ATTACHED | (MAX_FRAME + 1)), "frame too large"),
    ("body_shorter_than_its_header",
     _LEN.pack(_ATTACHED | 2) + b"\x00\x00", "shorter"),
    ("msgpack_part_overruns_the_frame",
     _attached_frame(500, _meta(), b""), "overruns"),
    ("attachment_overruns_the_frame",
     _attached_frame(len(_meta(10)), _meta(10), b"12345"), "overruns"),
    ("bytes_no_placeholder_claims",
     _attached_frame(len(_meta(2)), _meta(2), b"12345"), "no placeholder"),
])
def test_malformed_frames_are_refused(name, raw, error):
    with pytest.raises(ValueError, match=error):
        _read(raw)


def test_a_peer_without_attachments_refuses_the_frame_cleanly():
    """What the previous ``read_frame`` does with an attachment frame: it
    reads the flagged word as a length, finds it over ``MAX_FRAME`` and
    raises — for ANY body length, so a frame is never mis-read."""
    for body in (0, 1, 300_000, MAX_FRAME):
        (as_length,) = _LEN.unpack(_LEN.pack(_ATTACHED | body))
        assert as_length > MAX_FRAME


# ------------------------------------------------------------ over real RPC


async def _echo_pair(tele_srv=None, tele_cli=None):
    server = RPCServer("127.0.0.1", 0, telemetry_registry=tele_srv)

    async def echo(peer, args):
        # hand the attachments back (by reference: views of the request's
        # buffer) with something computed from them
        blobs = _blobs(args)
        return {"back": args, "sizes": [len(b) for b in blobs]}

    server.register("echo", echo)
    await server.start()
    client = RPCClient(request_timeout=5.0, telemetry_registry=tele_cli)
    return server, client


def test_counters_count_headers_and_attachments():
    """``frames`` / ``attached`` / ``attached_bytes`` on both ends and the
    process-wide ``net.bytes_*`` counters, over one echo with two
    attachments and one plain call."""
    x = np.arange(5000, dtype=np.float16)
    raw = bytes(range(77))
    tele = Telemetry(peer="frames")
    registry.install(tele)

    async def run():
        server, client = await _echo_pair()
        try:
            ep = ("127.0.0.1", server.port)
            args = {"x": Blob(x), "nest": {"raw": Blob(raw)}, "n": 7}
            reply = await client.call(ep, "echo", args)
            assert _strip(reply["back"]) == _strip(args)
            assert reply["sizes"] == [x.nbytes, len(raw)]
            after_attached = (client.attached, client.attached_bytes,
                              server.attached, server.attached_bytes)
            frames = (client.frames, server.frames)
            plain = await client.call(ep, "echo", {"n": 8})
            assert plain == {"back": {"n": 8}, "sizes": []}
            return after_attached, frames, (
                client.attached, client.attached_bytes,
                server.attached, server.attached_bytes,
                client.frames, server.frames,
            )
        finally:
            await client.close()
            await server.stop()

    try:
        attached, frames, final = asyncio.run(run())
    finally:
        registry.uninstall()
    payload = x.nbytes + len(raw)
    # two out and two back, on each end (request written + reply read)
    assert attached == (4, 2 * payload, 4, 2 * payload)
    assert frames == (2, 2)
    # the plain call: two more frames an end, no attachment anywhere
    assert final == (4, 2 * payload, 4, 2 * payload, 4, 4)
    counters = tele.counters
    # every byte written was read (loopback), headers and payloads included
    assert counters["net.bytes_out"].value == counters["net.bytes_in"].value
    # (one process holds both ends: the request and the reply, once each)
    assert counters["net.bytes_out"].value > 2 * payload
    assert counters["net.bytes_out"].value < 2 * payload + 4 * 200


def test_attachments_cross_a_relay_by_reference(monkeypatch):
    """A relayed call wraps ``args`` inside ``relay.call``'s ``args``; the
    relay pipes them down the registered connection (``call_over``) and the
    reply back: four hops, each an attachment frame — the relay never packs
    the payload."""
    x = np.arange(3000, dtype=np.float16)
    packed_sizes = []
    real_packb = msgpack.packb

    def spy(obj, **kwargs):
        out = real_packb(obj, **kwargs)
        packed_sizes.append(len(out))
        return out

    monkeypatch.setattr(msgpack, "packb", spy)

    async def run():
        relay_server = RPCServer("127.0.0.1", 0)
        await relay_server.start()
        relay = RelayService(relay_server)
        private = RPCClient(request_timeout=5.0)

        async def double(peer, args):
            got = np.frombuffer(args["data"].view, dtype=np.float16)
            return {"h": args["h"], "data": Blob(got * 2)}

        private.reverse_handlers["double"] = double
        ep = await private.register_with_relay(
            ("127.0.0.1", relay_server.port), b"private-peer"
        )
        caller = RPCClient(request_timeout=5.0)
        try:
            reply = await caller.call(
                ep, "double", {"h": {"n": 3000}, "data": Blob(x)}
            )
            assert list(relay.piped_methods) == ["double"]
            return (reply, caller.attached, private.attached,
                    relay_server.attached, relay_server.attached_bytes)
        finally:
            await caller.close()
            await private.close()
            await relay_server.stop()

    reply, caller_n, private_n, relay_n, relay_bytes = asyncio.run(run())
    assert reply["h"] == {"n": 3000}
    np.testing.assert_array_equal(
        np.frombuffer(reply["data"].view, dtype=np.float16), x * 2
    )
    assert caller_n == 2 and private_n == 2  # one out, one in, each
    assert relay_n == 4 and relay_bytes == 4 * x.nbytes
    assert max(packed_sizes) < 1024  # no hop packed the 6,000-byte payload


def test_attachments_pass_under_fault_injection():
    """With a fault schedule installed every request takes the task path
    and a ``delay`` fault sleeps before the call: the attachment (a view of
    the caller's array) is written after the delay and arrives whole."""
    from dedloc_tpu.testing.faults import FaultSchedule

    x = np.arange(2048, dtype=np.float16)

    async def run():
        server, client = await _echo_pair()
        try:
            return await client.call(
                ("127.0.0.1", server.port), "echo", {"data": Blob(x)}
            )
        finally:
            await client.close()
            await server.stop()

    with FaultSchedule(seed=0) as schedule:
        schedule.inject("rpc.client.call", "delay", times=-1, delay=0.01)
        schedule.inject("rpc.server.dispatch", "delay", times=-1, delay=0.01)
        reply = asyncio.run(run())
        assert len(schedule.fired) == 2
    np.testing.assert_array_equal(
        np.frombuffer(reply["back"]["data"].view, dtype=np.float16), x
    )
    assert reply["sizes"] == [x.nbytes]


def test_spies_still_see_writer_and_obj(monkeypatch):
    """``protocol.write_frame`` stays a two-argument function looked up at
    call time: a spy that swallows its return value sees every message,
    Blob and all, and the transfer still works (the attachment counters on
    the WRITE side then miss it, the read side's do not)."""
    seen = []
    real = protocol.write_frame

    def spy(writer, obj):
        seen.append(obj)
        real(writer, obj)

    monkeypatch.setattr(protocol, "write_frame", spy)
    x = np.arange(100, dtype=np.float16)

    async def run():
        server, client = await _echo_pair()
        try:
            reply = await client.call(
                ("127.0.0.1", server.port), "echo", {"data": Blob(x)}
            )
            return reply, client.attached, server.attached
        finally:
            await client.close()
            await server.stop()

    reply, client_n, server_n = asyncio.run(run())
    assert bytes(reply["back"]["data"].view) == x.tobytes()
    assert [m.get("method", "reply") for m in seen] == ["echo", "reply"]
    assert isinstance(seen[0]["args"]["data"], Blob)
    assert client_n == 1 and server_n == 1  # what each READ
