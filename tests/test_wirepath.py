"""Pipelined-allreduce wire path: chunk streaming, compression equivalence,
error feedback, zero-copy flatten. Tier-1 tests here ride in-process
loopback RPC with small vectors (cheap); the latency-injection variant
needs real sockets plus injected delays and is additionally marked slow."""
import asyncio

import numpy as np
import pytest

from dedloc_tpu.averaging.allreduce import (
    AllreduceFailed,
    GroupAllReduce,
    span_chunks,
)
from dedloc_tpu.averaging.partition import (
    TreeLayout,
    flatten_tree,
    partition_weighted,
    unflatten_tree,
)
from dedloc_tpu.collaborative.error_feedback import ErrorFeedback
from dedloc_tpu.core.serialization import CompressionType, wire_roundtrip
from dedloc_tpu.dht.protocol import RPCClient, RPCServer

pytestmark = pytest.mark.wirepath


# ------------------------------------------------------------ span chunking


def test_span_chunks_cover_exactly():
    for lo, hi, chunk in [(0, 100, 30), (7, 7, 10), (5, 105, 100),
                          (0, 100, 100), (0, 100, 1), (3, 1000, 333)]:
        chunks = span_chunks(lo, hi, chunk)
        if hi <= lo:
            assert chunks == []
            continue
        assert chunks[0][0] == lo and chunks[-1][1] == hi
        for (a, b), (c, d) in zip(chunks, chunks[1:]):
            assert b == c and a < b
        assert all(b - a <= chunk for a, b in chunks)


def test_span_chunks_monolithic_mode():
    assert span_chunks(3, 50, 0) == [(3, 50)]
    assert span_chunks(3, 50, -1) == [(3, 50)]


# ------------------------------------------- partition_weighted edge cases


def test_partition_single_hostable_peer_takes_everything():
    spans = partition_weighted(97, [0.0, 5.0, 0.0],
                               can_host=[False, True, False])
    assert spans[1] == (0, 97)
    assert spans[0][0] == spans[0][1] and spans[2][0] == spans[2][1]


def test_partition_all_zero_bandwidth_mixed_client_mode():
    # the equal-split fallback distributes ONLY among hosting-capable
    # members even when every advertised bandwidth is zero
    spans = partition_weighted(
        100, [0.0, 0.0, 0.0, 0.0],
        can_host=[True, False, True, False],
    )
    assert spans[1][0] == spans[1][1] and spans[3][0] == spans[3][1]
    assert (spans[0][1] - spans[0][0]) + (spans[2][1] - spans[2][0]) == 100


def test_partition_zero_size_vector():
    spans = partition_weighted(0, [1.0, 2.0, 3.0])
    assert spans == [(0, 0), (0, 0), (0, 0)]


def test_partition_exact_cover_invariance_largest_remainder():
    # property sweep: largest-remainder rounding must cover [0, total)
    # exactly for adversarial bandwidth mixes — and never hand a single
    # element to a non-hostable member
    rng = np.random.default_rng(7)
    for trial in range(200):
        n = int(rng.integers(1, 9))
        total = int(rng.integers(0, 10_000))
        bw = rng.random(n) * (10.0 ** rng.integers(-3, 4, n))
        hostable = rng.random(n) < 0.7
        if not hostable.any():
            hostable[int(rng.integers(0, n))] = True
        spans = partition_weighted(total, list(bw), can_host=list(hostable))
        assert spans[0][0] == 0 and spans[-1][1] == total
        covered = 0
        for i, (a, b) in enumerate(spans):
            assert a <= b
            covered += b - a
            if not hostable[i]:
                assert a == b, "non-hostable member got a span"
        assert covered == total


# ------------------------------------------------- zero-copy flatten layout


def test_tree_layout_reuses_buffer_across_rounds(rng):
    tree = {
        "b/w": rng.standard_normal((3, 4)).astype(np.float32),
        "a/k": rng.standard_normal((5,)).astype(np.float64),
        "c": np.array(2.5, np.float32),
    }
    layout = TreeLayout.for_tree(tree)
    assert layout.matches(tree)
    flat1 = layout.flatten_into(tree)
    flat2 = layout.flatten_into(tree)
    assert flat1 is flat2, "layout must reuse its preallocated buffer"
    ref, spec = flatten_tree(tree)
    np.testing.assert_array_equal(flat1, ref)
    assert [s[0] for s in spec] == [s[0] for s in layout.spec]
    # layout invalidates on schema change
    other = dict(tree, extra=np.zeros((2,), np.float32))
    assert not layout.matches(other)
    assert not layout.matches({"b/w": tree["b/w"]})
    assert not TreeLayout.for_tree(
        {"b/w": tree["b/w"].astype(np.float16)}
    ).matches({"b/w": tree["b/w"]})


def test_unflatten_skips_copy_for_matching_dtype(rng):
    tree = {
        "w": rng.standard_normal((4, 4)).astype(np.float32),
        "k": rng.standard_normal((3,)).astype(np.float64),
    }
    flat, spec = flatten_tree(tree)
    out = unflatten_tree(flat, spec)
    # fp32 tensors come back as views of the flat vector (no copy)...
    assert out["w"].base is not None and out["w"].base is flat
    # ...while dtype-converting tensors still get their own storage
    assert out["k"].dtype == np.float64
    np.testing.assert_allclose(out["k"], tree["k"], rtol=1e-6)
    np.testing.assert_array_equal(out["w"], tree["w"])


# ----------------------------------------------- chunked round equivalence


# the one loopback swarm harness, shared with the averaging suite — a
# GroupAllReduce constructor/lifecycle change must only be fixed there
from test_averaging import _allreduce_swarm as _pipelined_swarm  # noqa: E402


def test_chunked_f16_round_matches_unchunked_fp32_reference(rng):
    """Acceptance: a chunked + float16-compressed round over 4 peers (one
    aux, one client-mode) produces the same weighted mean as the unchunked
    fp32 path within fp16 tolerance — on every member."""
    n, dim = 4, 2000
    vectors = [rng.standard_normal(dim).astype(np.float32) for _ in range(n)]
    weights = [2.0, 1.0, 0.0, 1.0]  # member 2 is aux (weight 0)
    client_mask = [False, False, False, True]  # member 3 is client-mode
    bandwidths = [3.0, 1.0, 2.0, 1.0]
    expected = (
        sum(w * v for w, v in zip(weights, vectors)) / sum(weights)
    )

    # unchunked fp32 reference through the same engine
    ref = asyncio.run(
        _pipelined_swarm(vectors, weights, bandwidths, client_mask,
                         CompressionType.NONE, chunk_size=0)
    )
    for r in ref:
        np.testing.assert_allclose(r, expected, atol=1e-5)

    # chunked (many small chunks) + float16 wire
    out = asyncio.run(
        _pipelined_swarm(vectors, weights, bandwidths, client_mask,
                         CompressionType.FLOAT16, chunk_size=128)
    )
    for r in out:
        np.testing.assert_allclose(r, expected, atol=5e-3)
        np.testing.assert_allclose(r, ref[0], atol=5e-3)
    # all members gathered identical spans (bit-identical: each chunk is
    # reduced once, on one host, and served from its wire cache)
    for r in out[1:]:
        np.testing.assert_array_equal(out[0], r)


def test_chunked_round_straggler_dropped_consistently(rng):
    """Acceptance: a straggler-dropped sender still yields identical
    gathered spans on all members — the survivors' chunked result equals
    the weighted mean without the straggler."""
    n, dim = 4, 1500
    vectors = [rng.standard_normal(dim).astype(np.float32) for _ in range(n)]
    weights = [2.0, 1.0, 0.0, 1.0]
    client_mask = [False, False, False, True]
    bandwidths = [1.0, 1.0, 1.0, 1.0]
    # member 3 (client-mode sender) never runs: dropped at the straggler
    # window; survivors reduce without its contribution
    out = asyncio.run(
        _pipelined_swarm(vectors, weights, bandwidths, client_mask,
                         CompressionType.FLOAT16, chunk_size=256, dead=(3,),
                         straggler_timeout=0.6)
    )
    expected = (2.0 * vectors[0] + 1.0 * vectors[1]) / 3.0
    for r in out:
        np.testing.assert_allclose(r, expected, atol=5e-3)
    for r in out[1:]:
        np.testing.assert_array_equal(out[0], r)


def test_chunked_uint8_round_stays_close(rng):
    n, dim = 3, 999
    vectors = [rng.standard_normal(dim).astype(np.float32) for _ in range(n)]
    out = asyncio.run(
        _pipelined_swarm(vectors, [1.0] * n, [1.0, 5.0, 2.0], [False] * n,
                         CompressionType.UINT8, chunk_size=200)
    )
    expected = sum(vectors) / n
    # uint8 grid over a ~[-4, 4] range: ~0.03 per element worst case
    for r in out:
        np.testing.assert_allclose(r, expected, atol=0.05)


def test_all_aux_chunked_group_serves_local_spans(rng):
    """Every member weight 0 (all-aux): nothing to average; each host
    serves its own span and the round still completes chunked."""
    n, dim = 3, 700
    vectors = [np.full(dim, float(i + 1), np.float32) for i in range(n)]
    out = asyncio.run(
        _pipelined_swarm(vectors, [0.0] * n, [1.0] * n, [False] * n,
                         CompressionType.FLOAT16, chunk_size=100)
    )
    spans = partition_weighted(dim, [1.0] * n)
    expected = np.empty(dim, np.float32)
    for i, (lo, hi) in enumerate(spans):
        expected[lo:hi] = float(i + 1)
    for r in out:
        np.testing.assert_allclose(r, expected, atol=5e-3)


# ------------------------------- the by-reference wire path (attachments)


def _parents_round(vectors, weights, eff_bw, compression, chunk_size,
                   normalize=True):
    """The round's result computed the way the tree BEFORE attachment frames
    computed it, chunk by chunk: every part through ``serialize_array`` /
    ``deserialize_array`` (a host's own part through ``wire_roundtrip``),
    the same native axpy / scale in the same order (own part first), the
    reduced chunk served through ``serialize_array`` and adopted by its
    host through ``wire_roundtrip``. At most two members contribute data in
    the cases below, so the order the remote parts arrive in cannot change
    a bit (``a + b == b + a``)."""
    from dedloc_tpu import native
    from dedloc_tpu.core.serialization import (
        deserialize_array,
        serialize_array,
    )

    dim = len(vectors[0])
    spans = partition_weighted(dim, list(eff_bw), [bw > 0 for bw in eff_bw])
    served = np.empty(dim, np.float32)  # what a gatherer decodes
    adopted = np.empty(dim, np.float32)  # what the host itself keeps
    for host, (lo, hi) in enumerate(spans):
        for clo, chi in span_chunks(lo, hi, chunk_size):
            acc, total = None, 0.0
            order = [host] + [i for i in range(len(vectors)) if i != host]
            for i in order:
                if weights[i] <= 0:
                    continue
                piece = vectors[i][clo:chi]
                if i == host:
                    part = (wire_roundtrip(piece, compression)
                            if compression is not CompressionType.NONE
                            else piece)
                else:
                    part = deserialize_array(
                        serialize_array(piece, compression, checksum=True)
                    )
                if acc is None:
                    acc = native.scale(
                        np.array(part, dtype=np.float32), weights[i]
                    )
                else:
                    native.axpy(acc, part, weights[i])
                total += weights[i]
            reduced = native.scale(acc, 1.0 / total) if normalize else acc
            served[clo:chi] = deserialize_array(
                serialize_array(reduced, compression, checksum=True)
            )
            adopted[clo:chi] = (
                wire_roundtrip(reduced, compression)
                if compression is not CompressionType.NONE else reduced
            )
    # the two readings of one chunk agree in the parent too
    np.testing.assert_array_equal(served.view(np.uint32),
                                  adopted.view(np.uint32))
    return served


# (weights, client_mask, bandwidths, run kwargs): at most two data senders
_BIT_EQUAL_GROUPS = {
    "two_peers": ([1.0, 3.0], [False, False], [1.0, 2.0], {}),
    "three_peers_one_aux": (
        [2.0, 1.0, 0.0], [False, False, False], [1.0, 1.0, 3.0], {}),
    "three_peers_one_client_mode": (
        [0.0, 1.0, 2.0], [False, False, True], [2.0, 1.0, 1.0], {}),
    "two_peers_sum_mode": (
        [1.0, 3.0], [False, False], [1.0, 1.0], {"normalize": False}),
}


@pytest.mark.parametrize("group", sorted(_BIT_EQUAL_GROUPS))
@pytest.mark.parametrize("compression", list(CompressionType),
                         ids=lambda c: c.value)
def test_round_is_bit_equal_to_the_parents_wire_path(rng, compression, group):
    """Attachments change how a chunk's bytes TRAVEL, not one bit of what
    arrives: for every codec the result equals the value computed with the
    parent's ``serialize_array`` / ``wire_roundtrip`` path, and every member
    holds the same bytes."""
    weights, client_mask, bandwidths, run_kwargs = _BIT_EQUAL_GROUPS[group]
    n, dim, chunk = len(weights), 5_000, 700  # a ragged last chunk
    vectors = [
        (rng.standard_normal(dim) * 3).astype(np.float32) for _ in range(n)
    ]
    results = asyncio.run(_pipelined_swarm(
        vectors, weights, bandwidths, client_mask, compression,
        chunk_size=chunk, **run_kwargs,
    ))
    eff_bw = [0.0 if c else bw for c, bw in zip(client_mask, bandwidths)]
    expected = _parents_round(
        vectors, weights, eff_bw, compression, chunk,
        normalize=run_kwargs.get("normalize", True),
    )
    if run_kwargs.get("normalize", True) is False:
        assert all(w == sum(weights) for _out, w in results)
        results = [out for out, _w in results]
    for r in results:
        assert r.dtype == np.float32 and r.shape == (dim,)
        np.testing.assert_array_equal(r.view(np.uint32),
                                      expected.view(np.uint32))
    for r in results[1:]:
        assert r.tobytes() == results[0].tobytes()


@pytest.mark.parametrize("hop", ["part", "reduced"])
@pytest.mark.parametrize("compression", list(CompressionType),
                         ids=lambda c: c.value)
def test_corrupted_attachment_fails_the_round(rng, compression, hop):
    """One flipped bit in one attachment — a part on its way to its host,
    or a reduced chunk on its way back — is caught by the crc over the
    frame's buffer and costs the round (``AllreduceFailed``), never a
    silently wrong average."""
    from dedloc_tpu.dht.protocol import Blob

    n, dim = 2, 3_000
    vectors = [rng.standard_normal(dim).astype(np.float32) for _ in range(n)]
    flipped = []

    def corrupt(blob):
        raw = bytearray(blob.view)
        raw[len(raw) // 2] ^= 0x10
        flipped.append(len(raw))
        return Blob(bytes(raw))

    def fault_setup(clients, endpoints):
        real_call = clients[0].call

        async def call(endpoint, method, args=None, timeout=None):
            if hop == "part" and method == "avg.part" and not flipped:
                args = dict(args, data=corrupt(args["data"]))
            reply = await real_call(endpoint, method, args, timeout)
            if hop == "reduced" and method == "avg.get_reduced" and not flipped:
                reply = dict(reply, data=corrupt(reply["data"]))
            return reply

        clients[0].call = call

    with pytest.raises(AllreduceFailed, match="checksum"):
        asyncio.run(_pipelined_swarm(
            vectors, [1.0] * n, [1.0] * n, [False] * n, compression,
            chunk_size=500, fault_setup=fault_setup, straggler_timeout=0.5,
            timeout=3.0,
        ))
    assert len(flipped) == 1


def test_round_copies_no_payload_through_msgpack_or_tobytes(rng, monkeypatch):
    """The copy count: during a round nothing larger than 4 KiB goes through
    ``msgpack.packb`` / ``unpackb`` (the chunk payloads are 16 KiB of fp16
    here), ``ndarray.tobytes`` is never called, and each peer counts every
    payload as an attachment: parts out and in, reduced chunks served and
    gathered — 4 x the chunks it hosts in a two-peer group."""
    import sys

    import msgpack

    n, dim, chunk = 2, 65_536, 8_192
    vectors = [rng.standard_normal(dim).astype(np.float32) for _ in range(n)]
    packed, unpacked, c_calls = [], [], []
    real_packb, real_unpackb = msgpack.packb, msgpack.unpackb

    def packb(obj, **kwargs):
        out = real_packb(obj, **kwargs)
        packed.append(len(out))
        return out

    def unpackb(data, **kwargs):
        unpacked.append(len(data))
        return real_unpackb(data, **kwargs)

    monkeypatch.setattr(msgpack, "packb", packb)
    monkeypatch.setattr(msgpack, "unpackb", unpackb)

    def profile(frame, event, arg):
        # built-in methods cannot be patched: the interpreter's own C-call
        # hook sees them by name
        if event == "c_call" and arg.__name__ in ("tobytes", "tostring"):
            c_calls.append(arg.__qualname__)

    reducers = []
    sys.setprofile(profile)
    try:
        results = asyncio.run(_pipelined_swarm(
            vectors, [1.0] * n, [1.0] * n, [False] * n,
            CompressionType.FLOAT16, chunk_size=chunk, reducers_out=reducers,
        ))
    finally:
        sys.setprofile(None)
    np.testing.assert_allclose(results[0], sum(vectors) / n, atol=5e-3)
    assert c_calls == []
    assert packed and unpacked
    assert max(packed) <= 4096 and max(unpacked) <= 4096
    hosted = dim // n // chunk
    for reducer in reducers:
        trace = reducer.last_trace
        assert trace.attached_chunks == 4 * hosted
        assert trace.attached_bytes == 4 * hosted * chunk * 2  # fp16
        # and outside a round nothing is attached: the counters stand still
        ends = (reducer.client, reducer.server)
        assert sum(e.attached for e in ends) == trace.attached_chunks


def test_dead_host_still_fails_chunked_round():
    """The host-failure contract survives chunking: a member that hosts a
    span and never runs fails the round for everyone, within the timeout."""

    async def run():
        n, dim = 3, 300
        vectors = [np.ones(dim, np.float32) * i for i in range(n)]
        servers, clients, reducers, endpoints = [], [], [], []
        for i in range(n):
            client = RPCClient(request_timeout=2.0)
            server = RPCServer("127.0.0.1", 0)
            await server.start()
            clients.append(client)
            servers.append(server)
            reducers.append(
                GroupAllReduce(client, server, timeout=2.0, chunk_size=64)
            )
            endpoints.append(("127.0.0.1", server.port))
        try:
            results = await asyncio.gather(
                reducers[0].run("r", 0, vectors[0], 1.0, endpoints, [1.0] * n),
                reducers[1].run("r", 1, vectors[1], 1.0, endpoints, [1.0] * n),
                return_exceptions=True,
            )
            assert all(isinstance(r, AllreduceFailed) for r in results)
        finally:
            for c in clients:
                await c.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())


# ------------------------------------------------------------ error feedback


def test_error_feedback_uint8_unbiased_over_rounds(rng):
    """Acceptance: with uint8 compression, residual feedback keeps the
    cumulative transmitted gradient tracking the cumulative true gradient
    (bounded residual, no drift) over >= 20 simulated rounds — while the
    naive (no-feedback) wire drifts linearly on a biased signal."""
    rounds = 25
    # a constant gradient whose values fall BETWEEN uint8 grid points plus
    # small noise: the worst case for a quantizer (consistent per-round
    # bias), the textbook case for error feedback
    base = rng.standard_normal(257).astype(np.float32)
    ef = ErrorFeedback(CompressionType.UINT8)
    sum_true = np.zeros_like(base)
    sum_ef = np.zeros_like(base)
    sum_naive = np.zeros_like(base)
    residual_norms = []
    for t in range(rounds):
        grad = base + 0.01 * rng.standard_normal(base.shape).astype(np.float32)
        sum_true += grad
        contrib, commit = ef.prepare({"g": grad})
        sum_ef += wire_roundtrip(contrib["g"], CompressionType.UINT8)
        commit()
        residual_norms.append(ef.residual_norm())
        sum_naive += wire_roundtrip(grad, CompressionType.UINT8)

    # EF identity: cumulative transmitted = cumulative true - final residual
    ef_err = float(np.max(np.abs(sum_ef - sum_true)))
    naive_err = float(np.max(np.abs(sum_naive - sum_true)))
    # one uint8 step over this range is ~8/255 ≈ 0.03; the EF error stays
    # within ~one step FOREVER, the naive error accumulates per round
    assert ef_err < 0.1, f"error feedback drifted: {ef_err}"
    assert naive_err > 3 * ef_err, (
        f"naive wire should drift visibly: naive={naive_err} ef={ef_err}"
    )
    # residual norm is bounded (no growth): late-round residuals are the
    # same magnitude as early ones
    early = max(residual_norms[:5])
    late = max(residual_norms[-5:])
    assert late < 4 * early + 1e-6, f"residual norm grew: {residual_norms}"


def test_device_ef_uint8_drift_free_through_wire_requantize(rng):
    """PR 13 acceptance: the DEVICE-quantized contribution
    (averaging/device_flat.py) stays drift-free over 25 simulated rounds
    even though the network wire RE-quantizes the decoded form per chunk
    with its own affine grid. The device residual only models the D2H
    leg; the wire's re-quantization of an already-on-grid signal is
    second-order and must stay bounded (the approximation
    collaborative/error_feedback.py documents), while the naive
    no-feedback wire drifts visibly on the same signal."""
    import jax.numpy as jnp

    from dedloc_tpu.averaging.device_flat import DeviceFlatPipeline

    rounds = 25
    base = rng.standard_normal(257).astype(np.float32)
    pipe = DeviceFlatPipeline.for_tree(
        {"g": jnp.asarray(base)}, compression="uint8", chunk_elems=100
    )

    def wire(flat):
        # the network leg: per-chunk uint8 re-encode of the contribution
        out = np.empty_like(flat)
        for lo in range(0, flat.size, 100):
            out[lo:lo + 100] = wire_roundtrip(
                flat[lo:lo + 100], CompressionType.UINT8
            )
        return out

    sum_true = np.zeros_like(base)
    sum_ef = np.zeros_like(base)
    sum_naive = np.zeros_like(base)
    for t in range(rounds):
        grad = base + 0.01 * rng.standard_normal(base.shape).astype(
            np.float32
        )
        sum_true += grad
        fetch = pipe.fetch({"g": jnp.asarray(grad)}, use_ef=True)
        sum_ef += wire(fetch.result().flat)
        pipe.commit(fetch)
        sum_naive += wire(
            wire_roundtrip(grad, CompressionType.UINT8)
        )
    ef_err = float(np.max(np.abs(sum_ef - sum_true)))
    naive_err = float(np.max(np.abs(sum_naive - sum_true)))
    assert ef_err < 0.15, f"device EF drifted through the wire: {ef_err}"
    assert naive_err > 3 * ef_err, (
        f"naive double-quantized wire should drift: naive={naive_err} "
        f"ef={ef_err}"
    )


def test_error_feedback_none_is_identity(rng):
    ef = ErrorFeedback("none")
    assert not ef.enabled
    g = {"w": rng.standard_normal(17).astype(np.float32)}
    contrib, commit = ef.prepare(g)
    assert contrib is g
    commit()
    assert ef.residual_norm() == 0.0


def test_error_feedback_commit_discipline(rng):
    """An uncommitted prepare (failed round) must not change the residual:
    the retry re-derives the same contribution."""
    ef = ErrorFeedback(CompressionType.UINT8)
    g = {"w": rng.standard_normal(64).astype(np.float32)}
    c1, commit1 = ef.prepare(g)
    c2, _commit2 = ef.prepare(g)
    np.testing.assert_array_equal(c1["w"], c2["w"])
    commit1()
    c3, _ = ef.prepare(g)
    assert not np.array_equal(c1["w"], c3["w"]), (
        "after a committed round the residual must feed forward"
    )
    ef.reset()
    c4, _ = ef.prepare(g)
    np.testing.assert_array_equal(c1["w"], c4["w"])


# --------------------------------------- latency injection (real sockets)


@pytest.mark.slow
def test_pipelined_round_correct_under_injected_latency(rng):
    """Chunk streaming under per-message delay (the volunteer-link regime):
    the round completes, stays exact, and the straggler window is NOT
    tripped by uniformly slow messages. Real sockets + real timers — slow."""
    from dedloc_tpu.testing.faults import FaultSchedule

    async def run(schedule):
        n, dim = 3, 6000
        vectors = [
            rng.standard_normal(dim).astype(np.float32) for _ in range(n)
        ]
        servers, clients, reducers, endpoints = [], [], [], []
        for i in range(n):
            client = RPCClient(request_timeout=30.0)
            server = RPCServer("127.0.0.1", 0)
            await server.start()
            clients.append(client)
            servers.append(server)
            reducers.append(
                GroupAllReduce(client, server,
                               compression=CompressionType.FLOAT16,
                               timeout=30.0, straggler_timeout=5.0,
                               chunk_size=512)
            )
            endpoints.append(("127.0.0.1", server.port))
        try:
            results = await asyncio.gather(
                *(
                    reducers[i].run("lat", i, vectors[i], 1.0, endpoints,
                                    [1.0] * n)
                    for i in range(n)
                )
            )
            expected = sum(vectors) / n
            for r in results:
                np.testing.assert_allclose(r, expected, atol=5e-3)
        finally:
            for c in clients:
                await c.close()
            for s in servers:
                await s.stop()

    with FaultSchedule(seed=0) as schedule:
        # every avg.part message pays a fixed delay — the injected
        # per-message latency the pipeline is built to hide
        schedule.inject(
            "rpc.client.call", "delay", times=-1, delay=0.02,
            match=lambda ctx: ctx.get("method") == "avg.part",
        )
        asyncio.run(run(schedule))
        delayed = [
            1 for point, ctx in schedule.fired
            if point == "rpc.client.call"
        ]
        assert len(delayed) >= 12, "expected many delayed chunk messages"


def test_late_straggler_part_cannot_mutate_finalized_chunk(rng):
    """A part landing AFTER the straggler window finalized its chunk must
    not touch the already-served mean (the finalized accumulator is scaled
    in place and may have been handed to gatherers)."""

    async def run():
        server = RPCServer("127.0.0.1", 0)
        await server.start()
        client = RPCClient(request_timeout=5.0)
        reducer = GroupAllReduce(client, server,
                                 compression=CompressionType.NONE,
                                 timeout=5.0, straggler_timeout=0.3,
                                 chunk_size=50)
        endpoints = [("127.0.0.1", server.port), None]
        vec = np.ones(100, np.float32)
        try:
            # member 1 (client-mode sender) never sends: dropped at the
            # straggler window; host finalizes with only its own part
            result = await reducer.run("late", 0, vec, 1.0, endpoints,
                                       [1.0, 0.0])
            np.testing.assert_allclose(result, vec, atol=1e-6)
            # the round state is still serving (deferred cleanup): the
            # straggler's part arrives LATE
            from dedloc_tpu.core.serialization import (
                decode_array,
                encode_array,
            )
            from dedloc_tpu.dht.protocol import Blob

            header, wire = encode_array(
                np.full(50, 100.0, np.float32), CompressionType.NONE,
                checksum=True,
            )
            await client.call(
                endpoints[0], "avg.part",
                {"round_id": "late", "sender": 1, "weight": 1.0,
                 "chunk": 0, "h": header, "data": Blob(wire)},
            )
            reply = await client.call(
                endpoints[0], "avg.get_reduced",
                {"round_id": "late", "chunk": 0},
            )
            served = decode_array(reply["h"], reply["data"].view)
            np.testing.assert_allclose(served, np.ones(50, np.float32),
                                       atol=1e-6)
        finally:
            await client.close()
            await server.stop()

    asyncio.run(run())


def test_group_chunk_size_negotiation():
    """Chunk geometry rides the signed member record and the round uses the
    group minimum — one legacy/monolithic member drops the whole group to
    monolithic spans instead of timing out on phantom chunk ids."""
    from dedloc_tpu.averaging.matchmaking import GroupInfo, Member

    def member(pid, chunk_size):
        return Member(pid, ("127.0.0.1", 1), 1.0, b"", False, chunk_size)

    # min wins
    g = GroupInfo("r", [member(b"a", 4096), member(b"b", 131072)], 0)
    assert g.chunk_size == 4096
    # any non-chunking member (explicit monolithic or legacy record with no
    # field) forces monolithic for everyone
    g = GroupInfo("r", [member(b"a", 4096), member(b"b", 0)], 0)
    assert g.chunk_size == 0
    # the field survives the wire encoding, and an OLD record (shorter
    # list) unpacks as chunk_size 0
    m = member(b"a", 512)
    assert Member.unpack(m.pack()).chunk_size == 512
    assert Member.unpack(m.pack()[:5]).chunk_size == 0
