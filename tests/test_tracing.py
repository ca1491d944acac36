"""Cross-peer distributed tracing + per-link network telemetry.

Tentpole acceptance (ISSUE 7): a 3-peer loopback all-reduce under an
injected asymmetric-latency link must produce (1) ONE stitched cross-peer
trace whose ``runlog_summary --trace`` critical path names the slow LINK
(not just the slow peer), and (2) a ``--topology`` link matrix whose
RTT/goodput estimates rank that link worst — while telemetry disabled adds
ZERO bytes to the wire framing.

Satellite: a leader-death + slow-link replay on FakeClock/FaultSchedule
whose stitched trace attributes the stall to the injected link and REPORTS
the orphaned child spans (a parent whose peer died / whose log was never
collected) instead of silently dropping them.

Everything here is loopback with tiny vectors; injected delays are ~0.1s
and overlap, per memory/tier1-timing-budget.md.
"""
import asyncio
import contextlib
import importlib.util
import io
import json
from pathlib import Path

import numpy as np
import pytest

from dedloc_tpu.averaging.allreduce import GroupAllReduce
from dedloc_tpu.averaging.matchmaking import Matchmaking
from dedloc_tpu.dht import protocol
from dedloc_tpu.dht.node import DHTNode
from dedloc_tpu.dht.protocol import RPCClient, RPCServer
from dedloc_tpu.telemetry import Telemetry, registry
from dedloc_tpu.telemetry.links import LinkTable, endpoint_key
from dedloc_tpu.testing.faults import FakeClock, FaultSchedule

from tests.test_averaging import _allreduce_swarm

pytestmark = pytest.mark.telemetry

_spec = importlib.util.spec_from_file_location(
    "runlog_summary_for_tracing",
    Path(__file__).resolve().parent.parent / "tools" / "runlog_summary.py",
)
runlog_summary = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(runlog_summary)


def _render(fn, *args):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        fn(*args)
    return out.getvalue()


# ------------------------------------------------------- linkage unit tests


def test_span_linkage_and_deterministic_trace_seed():
    """Nested spans share one trace and chain parent ids; two peers seeding
    from the same round_id derive the SAME trace id with no handshake."""
    a, b = Telemetry(peer="a"), Telemetry(peer="b")
    with a.span("avg.round", trace_seed="step7") as _:
        a.event("point", k=1)
        with a.span("mm.form_group"):
            pass
    with b.span("avg.round", trace_seed="step7"):
        pass
    ev_a = {e["event"]: e for e in a.events}
    ev_b = {e["event"]: e for e in b.events}
    tid = registry.trace_id_for("step7")
    assert ev_a["avg.round"]["trace"] == tid
    assert ev_b["avg.round"]["trace"] == tid
    assert ev_a["avg.round"]["span"] != ev_b["avg.round"]["span"]
    # nesting: inner span and point event parent on the outer span
    assert ev_a["mm.form_group"]["parent"] == ev_a["avg.round"]["span"]
    assert ev_a["point"]["parent"] == ev_a["avg.round"]["span"]
    assert "parent" not in ev_a["avg.round"]  # root
    assert registry.current_trace() is None  # context restored


def test_adopt_trace_records_remote_parent_and_caller():
    t = Telemetry(peer="server")
    with registry.adopt_trace(["cafe" * 4, "beef" * 4, "client-peer"]):
        with t.span("mm.join.serve") as ctx:
            ctx["ok"] = True
    (event,) = list(t.events)
    assert event["trace"] == "cafe" * 4
    assert event["parent"] == "beef" * 4
    assert event["caller"] == "client-peer"
    # malformed tc must be ignored, never raise
    with registry.adopt_trace(None):
        pass
    with registry.adopt_trace(42):
        pass


def test_link_table_estimates_and_eviction():
    lt = LinkTable(alpha=0.5, max_links=2)
    lt.observe_rtt(("10.0.0.1", 1), 0.010)
    lt.observe_rtt(("10.0.0.1", 1), 0.030)
    link = lt.top()[0]
    assert link.dst == "10.0.0.1:1"
    assert abs(link.rtt_s - 0.020) < 1e-9  # EWMA alpha=0.5
    lt.observe_transfer(("10.0.0.2", 2), 10, 1.0)  # slow thin link
    lt.observe_transfer(("10.0.0.1", 1), 1000, 0.001)
    flat = lt.flat(top_k=8)
    assert flat["link.10.0.0.1:1.goodput_bps"] == pytest.approx(1e6)
    assert flat["link.10.0.0.2:2.goodput_bps"] == pytest.approx(10.0)
    assert flat["link.10.0.0.1:1.rtt_s"] == pytest.approx(0.020)
    # bounded by EVICTION, not refusal: a new destination displaces the
    # least-recently-OBSERVED link (.2), never the one still in use — on a
    # churning swarm the live partners stay tracked and departed peers age
    # out instead of squatting the table forever
    lt.observe_transfer(("10.0.0.3", 3), 99, 1.0)
    assert {l.dst for l in lt.top()} == {"10.0.0.1:1", "10.0.0.3:3"}
    # top_k truncation keeps the busiest link
    only = lt.flat(top_k=1)
    assert only and all(k.startswith("link.10.0.0.1:1.") for k in only)
    assert endpoint_key("already:formed") == "already:formed"


# -------------------------------------- wire framing: the zero-byte contract


def test_frames_carry_tc_only_when_telemetry_traces(monkeypatch):
    """Request frames carry the compact trace context ONLY when telemetry is
    enabled and a span is live — disabled telemetry leaves the framing
    byte-identical (no ``tc`` key at all). The server side adopts the
    context so its serve span records the remote parent + caller."""
    captured = []
    orig = protocol.write_frame

    def spy(writer, obj):
        captured.append(obj)
        orig(writer, obj)

    monkeypatch.setattr(protocol, "write_frame", spy)

    tele_srv = Telemetry(peer="srv")
    tele_cli = Telemetry(peer="cli")

    async def run():
        server = RPCServer("127.0.0.1", 0, telemetry_registry=tele_srv)

        async def echo(peer, args):
            with tele_srv.span("echo.serve") as ctx:
                ctx["ok"] = True
            return {}

        server.register("echo", echo)
        await server.start()
        endpoint = ("127.0.0.1", server.port)

        # 1) telemetry fully disabled: no tc on any frame
        bare = RPCClient(request_timeout=5.0)
        await bare.call(endpoint, "echo", {})
        assert captured, "spy saw no frames"
        assert all("tc" not in m for m in captured if isinstance(m, dict))
        await bare.close()

        # 2) enabled but NO live span: still no tc (nothing to link to)
        cli = RPCClient(request_timeout=5.0, telemetry_registry=tele_cli)
        await cli.call(endpoint, "echo", {})
        assert all("tc" not in m for m in captured if isinstance(m, dict))

        # 3) enabled inside a span: tc = [trace, parent span, caller peer]
        with tele_cli.span("avg.round", trace_seed="r9"):
            await cli.call(endpoint, "echo", {})
        tagged = [m for m in captured if isinstance(m, dict) and "tc" in m]
        assert len(tagged) == 1
        await cli.close()
        await server.stop()
        return tagged[0]["tc"]

    tc = asyncio.run(run())
    outer = [e for e in tele_cli.events if e["event"] == "avg.round"][-1]
    assert tc == [registry.trace_id_for("r9"), outer["span"], "cli"]
    # the server-side serve span recorded the REMOTE parent
    serves = [e for e in tele_srv.events if e["event"] == "echo.serve"]
    adopted = [e for e in serves if e.get("parent") == outer["span"]]
    assert len(adopted) == 1
    assert adopted[0]["trace"] == registry.trace_id_for("r9")
    assert adopted[0]["caller"] == "cli"
    # the un-traced serves (cases 1 and 2) carry no remote linkage
    assert all("caller" not in e for e in serves if e is not adopted[0])


def test_frame_work_is_summed_on_the_client_and_the_server():
    """Every ``RPCClient`` and ``RPCServer`` keeps an always-on sum of its
    synchronous frame work (pack + write, unpack of what it reads), telemetry
    on or off — what an all-reduce round reads as its ``ar_frame`` delta.
    A call is two frames on each side: the client writes the request and
    reads the reply, the server the reverse."""

    async def run():
        server = RPCServer("127.0.0.1", 0)

        async def echo(peer, args):
            return {"n": len(args["blob"])}

        server.register("echo", echo)
        await server.start()
        client = RPCClient(request_timeout=5.0)
        assert (client.frames, client.frame_s) == (0, 0.0)
        assert (server.frames, server.frame_s) == (0, 0.0)
        try:
            for k in range(3):
                reply = await client.call(
                    ("127.0.0.1", server.port), "echo",
                    {"blob": b"x" * 200_000},
                )
                assert reply == {"n": 200_000}
                assert client.frames == server.frames == 2 * (k + 1)
            assert 0 < client.frame_s < 5 and 0 < server.frame_s < 5
        finally:
            await client.close()
            await server.stop()

    assert registry.active() is None  # telemetry off: still summed
    asyncio.run(run())


# ---------------------------------------------- tentpole acceptance scenario


def _asymmetric_round(tmp_path, round_id="round1", delay=0.12):
    """3-peer loopback all-reduce with one injected slow directed link
    (p0 -> p2). Returns (event log paths, endpoints, telemetries)."""
    teles = [
        Telemetry(peer=f"p{i}", event_log_path=str(tmp_path / f"p{i}.jsonl"))
        for i in range(3)
    ]
    n, dim = 3, 240
    vectors = [np.full(dim, float(i + 1), np.float32) for i in range(n)]
    captured_eps = {}

    def fault_setup(clients, endpoints):
        captured_eps["eps"] = list(endpoints)
        schedule.inject(
            "rpc.client.call", "delay", times=-1, delay=delay,
            match=lambda ctx: ctx["client"] is clients[0]
            and tuple(ctx["endpoint"]) == tuple(endpoints[2]),
        )

    with FaultSchedule(seed=0) as schedule:
        results = asyncio.run(
            _allreduce_swarm(
                vectors, [1.0] * n, [1.0] * n, chunk_size=40,
                telemetries=teles, round_id=round_id,
                fault_setup=fault_setup,
            )
        )
        assert schedule.fired, "the slow-link fault never triggered"
    expected = sum(vectors) / n
    for r in results:
        np.testing.assert_allclose(r, expected, atol=1e-5)
    for t in teles:
        t.close()  # flush link.stats events
    paths = [str(tmp_path / f"p{i}.jsonl") for i in range(3)]
    return paths, captured_eps["eps"], teles


def test_acceptance_slow_link_trace_and_topology(tmp_path):
    """The ISSUE 7 acceptance criterion end to end."""
    paths, endpoints, teles = _asymmetric_round(tmp_path)
    slow_dst = endpoint_key(endpoints[2])

    rows = runlog_summary.load_events(paths)
    # ONE stitched trace: every peer's allreduce.round span derived the
    # same trace id from the shared round_id
    trace_rows, traces = runlog_summary.select_trace(rows, "round1")
    assert len(traces) == 1
    assert {r.get("peer") for r in trace_rows} >= {"p0", "p1", "p2"}

    out = _render(runlog_summary.print_trace, rows, "round1")
    # the critical path names the slow LINK: p0 waited on p0 -> p2
    critical = [l for l in out.splitlines() if l.startswith("critical path")]
    assert len(critical) == 1
    assert "p0 waited" in critical[0]
    assert f"p0 -> p2 ({slow_dst})" in critical[0]

    # --topology ranks that link worst by its RTT/goodput estimates
    topo = _render(
        runlog_summary.print_topology, runlog_summary.load_jsonl_rows(paths)
    )
    worst = [l for l in topo.splitlines() if l.startswith("worst link")]
    assert len(worst) == 1
    assert "p0 -> p2" in worst[0]
    # and the per-peer snapshot that would ride the metrics bus carries the
    # same estimate (flat link.* keys, bounded top-K)
    snap = teles[0].snapshot()
    slow_key = f"link.{slow_dst}.goodput_bps"
    assert slow_key in snap
    other = [
        v for k, v in snap.items()
        if k.startswith("link.") and k.endswith(".goodput_bps")
        and k != slow_key
    ]
    assert other and all(snap[slow_key] < v for v in other)


# ------------------------- satellite: leader death + slow link, with orphans


def test_trace_stitching_under_leader_death_and_slow_link(tmp_path):
    """FakeClock/FaultSchedule replay: the declared leader dies
    mid-matchmaking (joins dropped with process-death semantics), the
    survivors regroup and run the round over a slow link. The stitched
    trace must attribute the stall to the injected link; re-stitching
    WITHOUT the joiner's log must REPORT its spans as orphaned."""
    teles = [
        Telemetry(peer=f"m{i}", event_log_path=str(tmp_path / f"m{i}.jsonl"))
        for i in range(3)
    ]

    state = {}

    async def scenario(clock, schedule):
        first = await DHTNode.create(listen_host="127.0.0.1")
        nodes = [first] + [
            await DHTNode.create(listen_host="127.0.0.1",
                                 initial_peers=[first.endpoint])
            for _ in range(2)
        ]
        servers, clients, mms = [], [], []
        for node, tele in zip(nodes, teles):
            client = RPCClient(request_timeout=10.0, telemetry_registry=tele)
            server = RPCServer("127.0.0.1", 0, telemetry_registry=tele)
            await server.start()
            tele.event(
                "peer.endpoint", endpoint=f"127.0.0.1:{server.port}"
            )
            clients.append(client)
            servers.append(server)
            mms.append(
                Matchmaking(
                    node, client, server, "tracemm",
                    node.node_id.to_bytes(), ("127.0.0.1", server.port),
                    bandwidth=1.0, averaging_expiration=30.0,
                    telemetry_registry=tele,
                )
            )
        try:
            lead_task = asyncio.ensure_future(mms[0].form_group("r1"))
            for _ in range(400):
                if any(
                    lid == mms[0].peer_id
                    for lid, _ep in await mms[1]._live_leaders("r1")
                ):
                    break
                await asyncio.sleep(0.02)
            else:
                raise AssertionError("leader record never appeared")
            # process-death semantics for the leader, both directions
            schedule.inject(
                "rpc.server.dispatch", "drop", times=-1,
                match=lambda ctx: ctx["server"] is servers[0]
                and ctx["method"] == "mm.join",
            )
            schedule.inject(
                "rpc.client.call", "drop", times=-1,
                match=lambda ctx: ctx["client"] is clients[0]
                and ctx["method"] == "mm.join",
            )
            g1, g2 = await asyncio.gather(
                mms[1].form_group("r1", expected_size=2),
                mms[2].form_group("r1", expected_size=2),
            )
            assert {m.peer_id for m in g1.members} == {
                mms[1].peer_id, mms[2].peer_id
            }

            # the surviving pair now runs the round over one slow link
            # (survivor1 -> survivor2), same round id => same trace
            reducers = [
                GroupAllReduce(clients[i], servers[i], timeout=10.0,
                               straggler_timeout=5.0, chunk_size=20,
                               telemetry_registry=teles[i])
                for i in (1, 2)
            ]
            endpoints = [
                ("127.0.0.1", servers[1].port), ("127.0.0.1", servers[2].port)
            ]
            state["slow_dst"] = endpoint_key(endpoints[1])
            schedule.inject(
                "rpc.client.call", "delay", times=-1, delay=0.1,
                match=lambda ctx: ctx["client"] is clients[1]
                and tuple(ctx["endpoint"]) == endpoints[1]
                and ctx["method"].startswith("avg."),
            )
            vec = [np.full(60, float(i), np.float32) for i in range(2)]
            r1, r2 = await asyncio.gather(
                reducers[0].run("r1", 0, vec[0], 1.0, endpoints, [1.0, 1.0]),
                reducers[1].run("r1", 1, vec[1], 1.0, endpoints, [1.0, 1.0]),
            )
            np.testing.assert_allclose(r1, (vec[0] + vec[1]) / 2, atol=1e-5)

            clock.advance(120.0)  # expire the dead leader's window
            with contextlib.suppress(Exception):
                await asyncio.wait_for(lead_task, timeout=30)
        finally:
            for c in clients:
                await c.close()
            for s in servers:
                await s.stop()
            for node in nodes:
                await node.shutdown()

    with FakeClock(start=50_000.0) as clock, \
            FaultSchedule(seed=0) as schedule:
        asyncio.run(scenario(clock, schedule))
    for t in teles:
        t.close()
    paths = [str(tmp_path / f"m{i}.jsonl") for i in range(3)]

    # (1) full stitch: one trace, stall attributed to the injected link
    rows = runlog_summary.load_events(paths)
    _, traces = runlog_summary.select_trace(rows, "r1")
    assert len(traces) == 1
    out = _render(runlog_summary.print_trace, rows, "r1")
    critical = [l for l in out.splitlines() if l.startswith("critical path")]
    assert len(critical) == 1
    assert "m1 waited" in critical[0]
    assert state["slow_dst"] in critical[0]

    # (2) the surviving leader's serve span names the joiner's span as its
    # remote parent; stitching WITHOUT the joiner's log must report it as
    # orphaned, not silently drop it
    by_path = {p: runlog_summary.load_events([p]) for p in paths}
    serve_logs = [
        p for p, rs in by_path.items()
        if any(r.get("event") == "mm.join.serve" and r.get("ok")
               for r in rs)
    ]
    assert len(serve_logs) == 1, "exactly one survivor led the regroup"
    serve = next(
        r for r in by_path[serve_logs[0]]
        if r.get("event") == "mm.join.serve" and r.get("ok")
    )
    assert serve.get("parent"), "serve span must carry the remote parent"
    assert serve.get("caller") in {"m1", "m2"}
    joiner_log = next(
        p for p, rs in by_path.items()
        if any(r.get("span") == serve["parent"] for r in rs)
    )
    partial = [p for p in paths if p != joiner_log]
    out2 = _render(
        runlog_summary.print_trace, runlog_summary.load_events(partial), "r1"
    )
    assert "orphaned spans" in out2
    assert "mm.join.serve" in out2.split("orphaned spans")[1]


# --------------------------- satellite: provider goodput in the shard fetch


def test_fetcher_records_provider_goodput_and_bytes(tmp_path):
    from dedloc_tpu.checkpointing import build_manifest
    from dedloc_tpu.checkpointing.fetcher import fetch_shards
    from dedloc_tpu.core.serialization import CompressionType, serialize_array

    tree = {"w": np.arange(64, dtype=np.float32)}
    manifest, flat = build_manifest(tree, step=3, shard_size=16)
    tele = Telemetry(peer="joiner")

    class FakeClient:
        async def call(self, ep, method, args, timeout=None):
            assert method == "ckpt.shard"
            lo = args["index"] * 16
            return {
                "data": serialize_array(
                    flat[lo: lo + 16], CompressionType.NONE
                )
            }

    async def run():
        providers = [(("10.0.0.9", 1), None), (("10.0.0.8", 2), None)]
        pb = {}
        shards = await fetch_shards(
            FakeClient(), manifest, providers, parallelism=2,
            telemetry_registry=tele, provider_bytes=pb,
        )
        assert len(shards) == manifest.num_shards
        return pb

    provider_bytes = asyncio.run(run())
    snap = tele.snapshot()
    assert snap["ckpt.provider_goodput.count"] == manifest.num_shards
    assert snap["ckpt.provider_goodput.mean"] > 0
    # bytes attributed per provider endpoint, and the link estimator fed
    assert sum(provider_bytes.values()) == manifest.total_bytes
    assert set(provider_bytes) == {"10.0.0.9:1", "10.0.0.8:2"}
    assert any(k.startswith("link.10.0.0.") for k in snap)


# ------------------------ satellite: health fold tolerates old-schema peers


def test_swarm_health_topology_and_old_schema_tolerance():
    from dedloc_tpu.collaborative.metrics import LocalMetrics
    from dedloc_tpu.telemetry import build_swarm_health

    def rec(step, peer, tail=None, endpoint=None):
        return LocalMetrics(
            step=step, samples_per_second=1.0, samples_accumulated=8,
            loss=1.0, mini_steps=1, peer=peer, telemetry=tail,
            endpoint=endpoint,
        )

    new_peer = rec(
        5, "aa",
        tail={
            "rpc.client.calls": 3.0,
            "link.10.0.0.2:7000.rtt_s": 0.002,
            "link.10.0.0.2:7000.goodput_bps": 5e6,
            "link.10.0.0.3:7000.rtt_s": 0.150,
            "link.10.0.0.3:7000.goodput_bps": 1e4,
        },
        endpoint="10.0.0.1:7000",
    )
    # pre-link-schema peers: a bare tail, and NO tail at all — both must
    # keep their per-peer row (degrade, don't drop)
    old_peer = rec(5, "bb", tail={"rpc.client.calls": 1.0})
    bare_peer = rec(4, "cc")
    dst_peer = rec(5, "dd", tail={}, endpoint="10.0.0.2:7000")

    health = build_swarm_health([new_peer, old_peer, bare_peer, dst_peer])
    assert {p["peer"] for p in health["peers"]} == {"aa", "bb", "cc", "dd"}
    topo = health["topology"]
    # only the new-schema peer contributes links; dst resolves to a peer
    # label when some record advertises that endpoint
    assert {l["src"] for l in topo["links"]} == {"aa"}
    by_dst = {l["dst"]: l for l in topo["links"]}
    assert by_dst["dd"]["dst_endpoint"] == "10.0.0.2:7000"
    assert by_dst["10.0.0.3:7000"]["rtt_s"] == pytest.approx(0.150)
    assert topo["peers"]["bb"] is None

    # an all-old swarm simply has no topology — the pre-link health view
    health_old = build_swarm_health([old_peer, bare_peer])
    assert "topology" not in health_old
    assert {p["peer"] for p in health_old["peers"]} == {"bb", "cc"}


def test_trace_view_exits_cleanly_on_unknown_round(tmp_path):
    p = tmp_path / "e.jsonl"
    p.write_text(json.dumps({"t": 1.0, "peer": "x", "event": "noop"}) + "\n")
    with pytest.raises(SystemExit):
        runlog_summary.print_trace(
            runlog_summary.load_events([str(p)]), "missing-round"
        )
