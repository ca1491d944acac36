"""Telemetry-replay digital twin (ISSUE 11): fit the simulator from run
logs, replay in virtual time, report fidelity.

Acceptance (deterministic, virtual-time, no wall-clock sleeps): a
simulated 24-peer averaging scenario with a KNOWN asymmetric network — one
thin-uplink peer, one high-latency directed link — dumps its telemetry
JSONL; a TwinModel fitted from those logs ALONE replays to a predicted
round-wall p50 within ±20% of the source run, reproduces the worst-link
ranking's bottleneck, and ``twin_sweep`` over the fitted model recommends
the known-better config (larger chunk_size) on the fat-link variant.

Everything here runs on the discrete-event engine (``run_scenario`` /
``replay_twin`` own their SimEngine+FakeClock) — seconds of wall for
minutes of scenario time.
"""
import copy
import glob
import importlib.util
import json
import os
from pathlib import Path

import pytest

from dedloc_tpu.simulator.network import LinkSpec
from dedloc_tpu.simulator.scenarios import run_scenario
from dedloc_tpu.telemetry.links import LinkTable
from dedloc_tpu.twin.fit import (
    DEFAULT_COMPUTE_S,
    TwinModel,
    fit_twin,
)
from dedloc_tpu.twin.replay import fidelity_report, replay_twin

pytestmark = pytest.mark.simulator

_TOOLS = Path(__file__).resolve().parent.parent / "tools"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


runlog_summary = _load_tool("runlog_summary")
twin_sweep = _load_tool("twin_sweep")


# the known asymmetric network the twin must rediscover from telemetry:
# peer-0002 has a thin 1 MB/s uplink on a swarm of 8 MB/s links, and the
# directed pair peer-0005 -> peer-0009 carries 80 ms latency
SOURCE_SPEC = {
    "scenario": "averaging", "peers": 24, "seed": 7,
    "link": {"latency_s": 0.004, "bandwidth_bps": 8e6},
    "links": [
        {"src": "peer-0002", "dst": "*", "bandwidth_bps": 1e6},
        {"src": "peer-0005", "dst": "peer-0009", "latency_s": 0.08},
    ],
    "avg_rounds": 6, "group_size": 6,
    "span_bytes": 96 * 1024, "chunk_bytes": 24 * 1024,
    "boundaries": 2, "compute_s": 0.05, "compute_skew": 0.5,
    "window_s": 2.0,
}


@pytest.fixture(scope="module")
def source_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("twinsrc")
    report = run_scenario(dict(SOURCE_SPEC), out_dir=str(out))
    paths = sorted(glob.glob(os.path.join(str(out), "*.jsonl")))
    assert paths, "source scenario dumped no event logs"
    rows = runlog_summary.load_jsonl_rows(paths)
    return report, rows, paths


@pytest.fixture(scope="module")
def fitted(source_run):
    _report, rows, _paths = source_run
    return fit_twin(rows)


# ------------------------------------------------- fit-friendly telemetry


def test_link_table_records_jitter_min_and_peak():
    table = LinkTable()
    for rtt in (0.010, 0.008, 0.014, 0.009):
        table.observe_rtt(("host", 1), rtt)
    table.observe_transfer(("host", 1), 1000, 0.001)  # 1 MB/s burst
    table.observe_transfer(("host", 1), 1000, 0.010)  # contended
    (rec,) = table.records()
    assert rec["rtt_min_s"] == pytest.approx(0.008)
    assert rec["rtt_jitter_s"] > 0.0
    assert rec["peak_bps"] == pytest.approx(1e6, rel=0.01)
    # the EWMA goodput sits below the peak: contention drags it down
    assert rec["goodput_bps"] < rec["peak_bps"]
    # the flat (metrics-bus) view carries the same fit-friendly keys
    flat = table.flat(top_k=4)
    assert "link.host:1.rtt_min_s" in flat
    assert "link.host:1.peak_bps" in flat


def test_linkspec_from_estimate_halves_rtt_and_keeps_defaults():
    default = LinkSpec(latency_s=0.02, bandwidth_bps=5e6, loss=0.01,
                       jitter_s=0.002)
    spec = LinkSpec.from_estimate(rtt_s=0.010, default=default)
    assert spec.latency_s == pytest.approx(0.005)
    # unmeasured dimensions inherit the DEFAULT, not the ideal
    assert spec.bandwidth_bps == 5e6
    assert spec.loss == 0.01
    assert spec.jitter_s == 0.002
    spec = LinkSpec.from_estimate(
        goodput_bps=1e6, loss=0.9, rtt_jitter_s=0.004, default=default
    )
    assert spec.latency_s == 0.02
    assert spec.bandwidth_bps == 1e6
    assert spec.loss == 0.5  # clamped to the simulator's meaningful range
    # round-trip deviation halves into one-way jitter, like the latency
    assert spec.jitter_s == pytest.approx(0.002)


# ----------------------------------------------------------- fitting


def test_fit_reads_recorded_config_and_rediscovers_physics(fitted):
    model = fitted
    # the run.config event beats inference: exact workload shape
    w = model.workload
    assert w["group_size"] == 6
    assert w["span_bytes"] == 96 * 1024
    assert w["chunk_bytes"] == 24 * 1024
    assert w["boundaries"] == 2
    assert w["window_s"] == pytest.approx(2.0)
    assert w["rounds"] == 6 and w["overlap"] is False
    # physics rediscovered from telemetry alone: the thin peer's uplink
    # lands near 1 MB/s, a healthy peer's well above it
    thin = [
        spec["bandwidth_bps"] for key, spec in model.links.items()
        if key.startswith("peer-0002|")
    ]
    assert thin, "no fitted links for the thin peer"
    assert 0.5e6 <= max(thin) <= 2e6, thin
    fast = [
        spec["bandwidth_bps"] for key, spec in model.links.items()
        if key.startswith("peer-0001|")
    ]
    assert fast and min(fast) > 3e6, fast
    # latency: one-way ~4 ms from the connect-handshake RTT probe
    lats = sorted(spec["latency_s"] for spec in model.links.values())
    assert 0.003 <= lats[len(lats) // 2] <= 0.006
    # per-peer compute: the deterministic skew (0.05 * (1 + 0.5*(i%4)))
    assert model.peers["peer-0000"]["compute_s"] == pytest.approx(
        0.05, rel=0.05
    )
    assert model.peers["peer-0001"]["compute_s"] == pytest.approx(
        0.075, rel=0.05
    )
    # coverage: everything was measured, and it says so
    cov = model.coverage
    assert cov["peers_with_compute"] == 24
    assert cov["links_with_bandwidth"] > 0
    assert cov["defaults_used"] == []


def test_round_trip_fidelity_acceptance(source_run, fitted):
    """THE acceptance: fit from logs alone, replay, and the prediction
    matches the source run within ±20% on round-wall p50 (also checked
    against the scenario's own report, independent of the fitter) while
    the worst-link ranking still points at the thin peer."""
    report, _rows, _paths = source_run
    fid = fidelity_report(fitted, seed=0)

    p50 = fid["metrics"]["round_wall_p50_s"]
    assert p50["error"] is not None and abs(p50["error"]) <= 0.20, p50
    # cross-check against the source scenario's independently measured
    # report (driver numbers, not fitter numbers)
    source_p50 = report["averaging"]["round_wall_p50_s"]
    assert abs(p50["predicted"] - source_p50) <= 0.20 * source_p50

    spsec = fid["metrics"]["samples_per_sec"]
    assert spsec["error"] is not None and abs(spsec["error"]) <= 0.20, spsec

    # worst-link ranking: both sides name the thin-uplink peer as the
    # bottleneck, and both top-1 links touch it
    worst = fid["worst_links"]
    assert worst["bottleneck_match"] is True
    assert worst["bottleneck_observed"] == "peer-0002"
    assert "peer-0002" in worst["observed"][0]
    assert "peer-0002" in worst["predicted"][0]

    # the sweep's confidence interval is bounded by what was just measured
    assert fid["sweep_error_bound"] is not None
    assert fid["sweep_error_bound"] <= 0.20


def test_twin_sweep_recommends_larger_chunks_on_fat_links(fitted):
    """Acceptance satellite: on the fat-link variant (every uplink raised
    to >= 40 MB/s) the known-better config is a larger chunk size — fewer
    per-chunk request/ack round trips with no bandwidth penalty — and the
    sweep recommends exactly that."""
    fat = TwinModel.from_dict(copy.deepcopy(fitted.to_dict()))
    for spec in fat.links.values():
        spec["bandwidth_bps"] = max(spec["bandwidth_bps"], 40e6)
        spec["loss"] = 0.0
    fat.default_link["bandwidth_bps"] = 40e6
    grid = [
        {"chunk_size": c, "compression": "none", "group_size": 6,
         "overlap": False}
        for c in (2048, 6144, 24576)  # 8 KB .. 96 KB chunks, 96 KB spans
    ]
    results = twin_sweep.sweep(fat, grid, seed=7, rounds=3)
    assert all("error" not in r for r in results), results
    assert results[0]["config"]["chunk_size"] == 24576, results
    # and the round wall improves monotonically with chunk size
    by_chunk = {
        r["config"]["chunk_size"]: r["round_wall_p50_s"] for r in results
    }
    assert by_chunk[24576] < by_chunk[6144] < by_chunk[2048], by_chunk


def test_twin_sweep_cli_fits_saves_and_brackets_with_fidelity(
    source_run, fitted, tmp_path, capsys
):
    model_path = tmp_path / "twin.json"
    fitted.save(str(model_path))
    rc = twin_sweep.main([
        "--model", str(model_path), "--json", "--seed", "7", "--rounds", "2",
        "--chunk-sizes", "24576", "--compressions", "none",
        "--overlap", "off",
    ])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "twin_sweep"
    assert doc["recommended"] is not None
    assert len(doc["configs"]) == 1
    lo, hi = doc["recommended_interval"]
    predicted = doc["recommended"]["samples_per_sec"]
    bound = doc["fidelity_error_bound"]
    # the interval endpoints are rounded to 3 decimals in the document
    assert lo == pytest.approx(predicted * (1 - bound), abs=5e-3)
    assert hi == pytest.approx(predicted * (1 + bound), abs=5e-3)


def test_runlog_summary_twin_view_text_and_json(source_run, capsys):
    # a SUBSET of the peer logs (incl. the thin peer's): partial log
    # collection is the realistic operator case, and fitting 10 peers
    # keeps the two CLI-shaped fit+replay passes tier-1 cheap
    _report, _rows, paths = source_run
    paths = paths[:10]
    runlog_summary.main(["--twin", "--json"] + paths)
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "twin"
    assert "round_wall_p50_s" in doc["metrics"]
    assert doc["worst_links"]["bottleneck_observed"] == "peer-0002"
    assert doc["coverage"]["peers_total"] == 10

    runlog_summary.main(["--twin"] + paths)
    out = capsys.readouterr().out
    assert "twin fidelity (predicted vs observed)" in out
    assert "| round_wall_p50_s |" in out
    assert "bottleneck peer:" in out and "MATCH" in out
    assert "sweep error bound" in out


# --------------------------------------------------- hostile-input fits


def _event(t, peer, event, **attrs):
    return {"t": t, "peer": peer, "event": event, **attrs}


def test_fit_survives_jammed_and_truncated_logs(tmp_path, capsys):
    """The fit rides the SAME hardened loader as every other view: jammed
    lines are split, the truncated tail is dropped (and reported), and the
    salvaged rows still fit."""
    rows = [
        _event(1.0, "a", "peer.endpoint", endpoint="a:1"),
        _event(1.1, "a", "run.config", window_s=1.5, group_size=2,
               span_bytes=8192, chunk_bytes=8192, boundaries=1,
               samples_per_boundary=4, overlap=False),
        _event(2.0, "a", "link.stats", dst="b:1", rtt_s=0.01,
               rtt_min_s=0.01, goodput_bps=1e6, peak_bps=2e6, bytes=8192,
               transfers=2),
        _event(3.0, "b", "avg.round", dur_s=0.5, round_id="r0", ok=True,
               group_size=2),
    ]
    p = tmp_path / "jam.jsonl"
    p.write_text(
        json.dumps(rows[0]) + "\n"
        + json.dumps(rows[1]) + json.dumps(rows[2]) + "\n"  # jammed line
        + json.dumps(rows[3]) + "\n"
        + '{"t": 9.0, "peer": "a", "eve'  # killed mid-write
    )
    loaded = runlog_summary.load_jsonl_rows([str(p)])
    assert "skipped" in capsys.readouterr().err
    model = fit_twin(loaded)
    assert set(model.peers) == {"a", "b"}
    assert model.workload["window_s"] == pytest.approx(1.5)  # jammed row in
    assert "a|b" in model.links


def test_fit_pre_link_schema_degrades_to_defaults_with_report(capsys):
    """Peers on builds that predate link telemetry (no link.* keys, no
    allreduce.link rows): the fit degrades to default links and default
    compute, and SAYS so in the coverage summary — never silently."""
    rows = [
        _event(1.0, "old-a", "mm.form_group", dur_s=0.8, round_id="r0",
               ok=True),
        _event(1.5, "old-b", "rpc.client.failure", method="x",
               error="TimeoutError"),
    ]
    model = fit_twin(rows)
    assert set(model.peers) == {"old-a", "old-b"}
    assert model.links == {}
    assert set(model.coverage["defaults_used"]) >= {"links", "compute"}
    assert any("no link telemetry" in w for w in
               model.coverage["warnings"])
    assert any("no step-phase telemetry" in w for w in
               model.coverage["warnings"])
    assert model.peers["old-a"]["compute_s"] == DEFAULT_COMPUTE_S
    # ...and such a model still REPLAYS (default links everywhere) once
    # the caller supplies the workload shape the logs could not
    report = replay_twin(model, overrides={
        "rounds": 1, "group_size": 2, "span_bytes": 4096,
        "chunk_bytes": 4096, "boundaries": 1, "window_s": 1.0,
    }, seed=0)
    assert report["rounds"] == 1
    assert report["round_wall_p50_s"] > 0


def test_fit_all_old_swarm_from_coordinator_jsonl():
    """A coordinator metrics JSONL from an all-old swarm: swarm_health rows
    carry peers but no phases, no topology, no link keys — every peer rows
    in with defaults, reported in coverage."""
    rows = [
        {"step": 5, "swarm_health": {
            "current_step": 5,
            "peers": [
                {"peer": "v1", "step": 5, "rpc_calls": 100.0},
                {"peer": "v2", "step": 4, "rpc_calls": 80.0},
            ],
        }},
    ]
    model = fit_twin(rows)
    assert set(model.peers) == {"v1", "v2"}
    assert model.links == {}
    assert model.coverage["peers_with_compute"] == 0
    assert model.coverage["health_records"] == 1
    assert "links" in model.coverage["defaults_used"]


def test_fit_sanitizes_separator_in_peer_labels():
    """A peer label carrying the link-key separator is hostile input for
    the 'src|dst' serialized table: sanitized at ingestion, never a
    crash."""
    rows = [
        _event(1.0, "host|8080", "peer.endpoint", endpoint="h:1"),
        _event(1.1, "host|8080", "link.stats", dst="other:1", rtt_s=0.01,
               rtt_min_s=0.01, goodput_bps=1e6, bytes=100, transfers=1),
        _event(2.0, "other", "peer.endpoint", endpoint="other:1"),
    ]
    model = fit_twin(rows)
    assert "host_8080" in model.peers
    assert "host_8080|other" in model.links
    # the key round trip stays unambiguous
    assert model.link_spec("host_8080", "other").latency_s > 0


def test_fit_with_no_peers_raises_helpfully():
    with pytest.raises(ValueError, match="no peers identifiable"):
        fit_twin([{"not": "telemetry"}, {"also": "nothing"}])
    with pytest.raises(ValueError):
        fit_twin([])


def test_fit_coordinator_jsonl_with_topology_and_phases():
    """The folded coordinator path: topology links + per-peer phases fold
    into a usable model without any per-peer event logs."""
    rows = [
        {"step": 9, "swarm_health": {
            "current_step": 9,
            "peers": [
                {"peer": "aa", "step": 9, "rpc_calls": 50.0,
                 "conns_lost": 5.0,
                 "phases": {"fwd_bwd": 0.4, "data_wait": 0.05}},
                # compute is fwd_bwd (the enqueue) + drain (the wait for
                # the device, on the 1 boundary in 4 that has one)
                {"peer": "bb", "step": 9, "rpc_calls": 60.0,
                 "phases": {"fwd_bwd": 0.02, "drain": 0.72},
                 "phase_counts": {"fwd_bwd": 40.0, "drain": 10.0}},
            ],
            "topology": {
                "peers": {"aa": "10.0.0.1:7", "bb": "10.0.0.2:7"},
                "links": [
                    {"src": "aa", "dst": "bb", "dst_endpoint": "10.0.0.2:7",
                     "rtt_s": 0.05, "rtt_min_s": 0.04, "goodput_bps": 2e6,
                     "peak_bps": 4e6, "transfers": 10},
                ],
            },
        }},
    ]
    model = fit_twin(rows)
    assert model.peers["aa"]["compute_s"] == pytest.approx(0.4)
    assert model.peers["bb"]["compute_s"] == pytest.approx(0.2)
    link = model.links["aa|bb"]
    assert link["latency_s"] == pytest.approx(0.02)  # rtt_min / 2
    # per-flow fallback scaled by recorded concurrency (no rounds: 1x)
    assert link["bandwidth_bps"] > 0
    # loss from the coordinator's conns_lost / rpc_calls fold
    assert link["loss"] == pytest.approx(0.1)


# ------------------------------------------------------ replay integration


def _tiny_model():
    peers = {
        f"p{i}": {"compute_s": 0.01, "samples_per_boundary": 4}
        for i in range(4)
    }
    links = {}
    for a in peers:
        for b in peers:
            if a != b:
                links[f"{a}|{b}"] = {
                    "latency_s": 0.002, "jitter_s": 0.0,
                    "bandwidth_bps": 4e6, "loss": 0.0,
                }
    return TwinModel(
        peers=peers, links=links,
        default_link={"latency_s": 0.002, "bandwidth_bps": 4e6,
                      "loss": 0.0, "jitter_s": 0.0},
        workload={"rounds": 1, "group_size": 4, "span_bytes": 8192,
                  "chunk_bytes": 8192, "boundaries": 1, "window_s": 1.0,
                  "overlap": False, "restores": 0},
    )


def test_twin_replay_scenario_rides_run_scenario(tmp_path):
    """The twin_replay scenario: a saved TwinModel JSON replays through the
    standard scenario entry point (and the CLI's --spec path), dumping
    event logs the observability tools read."""
    model = _tiny_model()
    path = tmp_path / "tiny_twin.json"
    model.save(str(path))
    out = tmp_path / "replay_logs"
    report = run_scenario(
        {"scenario": "twin_replay", "twin_path": str(path), "seed": 3},
        out_dir=str(out),
    )
    assert report["scenario"] == "twin_replay"
    assert report["peers"] == 4
    assert report["round_wall_p50_s"] > 0
    assert report["event_logs"], "replay dumped no event logs"
    rows = runlog_summary.load_jsonl_rows(report["event_logs"])
    assert any(r.get("event") == "avg.round" for r in rows)
    # inline twin dict works too
    report2 = run_scenario({
        "scenario": "twin_replay", "twin": model.to_dict(), "seed": 3,
    })
    assert report2["rounds"] == 1


def test_workload_restore_leg_and_fetch_parallelism(tmp_path):
    """The checkpoint-restore leg: a source run with restores fits a
    workload that replays the restore (the fetch_parallelism sweep axis),
    and ckpt.provider_goodput telemetry lands in the logs."""
    out = tmp_path / "restore_logs"
    report = run_scenario({
        "scenario": "averaging", "peers": 6, "seed": 2,
        "link": {"latency_s": 0.002, "bandwidth_bps": 4e6},
        "avg_rounds": 1, "group_size": 3, "span_bytes": 16384,
        "chunk_bytes": 8192, "boundaries": 1, "window_s": 1.0,
        "restore_bytes": 64 * 1024, "restore_providers": 3,
        "fetch_parallelism": 2,
    }, out_dir=str(out))
    restore = report["averaging"]["restore"]
    assert restore["ok"] is True
    assert restore["restore_s"] > 0
    assert restore["providers_used"] >= 2
    rows = runlog_summary.load_jsonl_rows(
        sorted(glob.glob(os.path.join(str(out), "*.jsonl")))
    )
    assert any(r.get("event") == "ckpt.restore" for r in rows)
    model = fit_twin(rows)
    assert model.workload["restores"] == 1
    assert model.workload["restore_bytes"] > 0
    rep = replay_twin(model, overrides={"fetch_parallelism": 4, "rounds": 1},
                      seed=2)
    assert rep["restore"]["ok"] is True
    assert rep["restore"]["fetch_parallelism"] == 4
