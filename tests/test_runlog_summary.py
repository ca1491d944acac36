"""tools/runlog_summary.py: the wall-clock rebasing across checkpoint-resume
segments must detect both resume signatures (step regression with a LARGER
first wall_s, and same-step restarts with a wall_s drop) — BASELINE.md
tables are built from its output."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

spec = importlib.util.spec_from_file_location(
    "runlog_summary",
    Path(__file__).resolve().parent.parent / "tools" / "runlog_summary.py",
)
runlog_summary = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runlog_summary)


def _write(tmp_path, rows):
    p = tmp_path / "log.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return str(p)


def test_resume_rebases_wall_clock_on_step_regression(tmp_path):
    """Second segment replays steps (resume from an older checkpoint) and
    its first wall_s EXCEEDS the first segment's last — the step counter,
    not the wall clock, must trigger the rebase."""
    rows = [
        {"wall_s": 10.0, "step": 1, "loss": 11.0},
        {"wall_s": 40.0, "step": 5, "loss": 10.0},
        # resume from checkpoint-3: step regresses, wall restarts HIGHER
        {"wall_s": 46.8, "step": 4, "loss": 10.1},
        {"wall_s": 60.0, "step": 6, "loss": 9.8},
    ]
    loaded = runlog_summary.load(_write(tmp_path, rows))
    assert [round(r["wall_s"], 1) for r in loaded] == [10.0, 40.0, 86.8, 100.0]


def test_resume_rebases_wall_clock_on_wall_drop(tmp_path):
    rows = [
        {"wall_s": 100.0, "step": 10, "loss": 9.0},
        {"wall_s": 5.0, "step": 11, "loss": 8.9},  # restart, steps continue
    ]
    loaded = runlog_summary.load(_write(tmp_path, rows))
    assert [r["wall_s"] for r in loaded] == [100.0, 105.0]


def test_missing_requested_steps_warn(tmp_path, capsys):
    rows = [{"wall_s": 1.0, "step": 1, "loss": 2.0}]
    picked = runlog_summary.pick_steps(
        runlog_summary.load(_write(tmp_path, rows)), [1, 500]
    )
    assert picked == [1]
    assert "500" in capsys.readouterr().err


# ------------------------------------------------- smoke: old + new schemas
# (the tool must not drift from the emitters: roles/trainer.py writes the
# train_log schema, telemetry/registry.py writes the event-log schema)


def test_main_smoke_over_old_trainlog_schema(tmp_path, capsys):
    rows = [
        {"wall_s": 10.0, "step": 1, "loss": 11.0, "boundary_ms": 120.0,
         "seam_ms": {"apply": 3.0}},
        {"wall_s": 40.0, "step": 2, "loss": 10.0, "boundary_ms": 110.0,
         "seam_ms": {"apply": 2.5}},
    ]
    runlog_summary.main([_write(tmp_path, rows)])
    out = capsys.readouterr().out
    assert "| global step | wall (min) | train loss |" in out
    assert "| 2 |" in out
    assert "total: 2 global steps" in out


def _write_events(tmp_path, rows, name="events.jsonl"):
    p = tmp_path / name
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return str(p)


def test_health_view_renders_rounds_faults_and_per_peer_table(
    tmp_path, capsys
):
    events = [
        {"t": 100.0, "peer": "peerA", "event": "avg.round", "dur_s": 0.5,
         "round_id": "step1", "ok": True, "group_size": 2},
        {"t": 100.2, "peer": "peerB", "event": "fault.applied",
         "point": "averager.state_get", "action": "truncate"},
        {"t": 100.25, "peer": "peerA", "event": "state_sync.checksum_failure",
         "provider": ["127.0.0.1", 4567], "attempt": 1},
        {"t": 100.3, "peer": "peerA", "event": "state_sync.retry",
         "attempt": 1, "backoff_s": 0.05},
        {"t": 100.4, "peer": "peerA", "event": "rpc.client.failure",
         "method": "state.get", "error": "TimeoutError"},
        {"t": 100.5, "peer": "peerA", "event": "mm.join_failed",
         "round_id": "step1", "error": "ConnectionResetError"},
    ]
    runlog_summary.main(["--health", _write_events(tmp_path, events)])
    out = capsys.readouterr().out
    assert "round timeline:" in out
    assert "step1" in out and "group=2" in out and " ok" in out
    assert "injected faults:" in out and "truncate" in out
    # per-peer table: peerA has 1 retry, 1 checksum fail, 1 rpc failure,
    # 1 join failure
    (row_a,) = [ln for ln in out.splitlines() if ln.startswith("| peerA |")]
    assert row_a == "| peerA | 5 | 0 | 1 | 1 | 1 | 1 | 0 |"
    (row_b,) = [ln for ln in out.splitlines() if ln.startswith("| peerB |")]
    assert row_b == "| peerB | 1 | 1 | 0 | 0 | 0 | 0 | 0 |"


@pytest.mark.parametrize("as_json", [False, True])
def test_health_wire_path_table_splits_the_round_by_kind(
    tmp_path, capsys, as_json
):
    """The wire-path table puts a round's wall down to what the loop thread
    did (encode / decode / reduce / copy / frame), the wait that is none of
    those, the partner's lag and the loop's CPU — means over the peer's
    ``allreduce.round`` events, with the payloads its frames carried by
    reference (``attached_chunks`` / ``attached_bytes``); a pre-ISSUE-34
    event (reduce_s and gather_wait_s only) still folds, with zeros."""
    round_ = {"t": 100.0, "peer": "peerA", "event": "allreduce.round",
              "dur_s": 0.7, "round_id": "r1", "ok": True, "chunks": 136,
              "gather_wait_s": 0.6, "encode_s": 0.2, "decode_s": 0.1,
              "reduce_s": 0.04, "copy_s": 0.02, "frame_s": 0.06,
              "wait_s": 0.3, "partner_lag_s": 0.01, "loop_cpu_s": 0.35,
              "attached_chunks": 272, "attached_bytes": 71_303_168}
    events = [
        round_,
        dict(round_, t=101.0, round_id="r2", encode_s=0.4, wait_s=0.1),
        {"t": 100.0, "peer": "old", "event": "allreduce.round", "dur_s": 0.5,
         "round_id": "r1", "ok": True, "chunks": 4, "reduce_s": 0.1,
         "gather_wait_s": 0.4},
    ]
    path = _write_events(tmp_path, events)
    if as_json:
        runlog_summary.main(["--json", "--health", path])
        wire = json.loads(capsys.readouterr().out)["wire"]
        assert wire["peerA"] == {
            "rounds": 2, "dur_mean_s": 0.7, "gather_wait_mean_s": 0.6,
            "chunks_mean": 136.0, "attached_mean": 272.0,
            "attached_mb_mean": 71.303, "encode_mean_s": 0.3,
            "decode_mean_s": 0.1,
            "reduce_mean_s": 0.04, "copy_mean_s": 0.02, "frame_mean_s": 0.06,
            "wait_mean_s": 0.2, "partner_lag_mean_s": 0.01,
            "loop_cpu_mean_s": 0.35,
        }
        assert wire["old"]["reduce_mean_s"] == 0.1
        assert wire["old"]["encode_mean_s"] == wire["old"]["wait_mean_s"] == 0
        assert wire["old"]["attached_mean"] == 0
        return
    runlog_summary.main(["--health", path])
    out = capsys.readouterr().out
    header = next(ln for ln in out.splitlines()
                  if ln.startswith("| peer | rounds | dur"))
    assert header == (
        "| peer | rounds | dur | encode | decode | reduce | copy | frame |"
        " wait | partner lag | loop cpu | gather wait | chunks | attached |"
        " attached MB |"
    )
    (row,) = [ln for ln in out.splitlines()
              if ln.startswith("| peerA | 2 | 0.700s")]
    assert row == (
        "| peerA | 2 | 0.700s | 0.300s | 0.100s | 0.040s | 0.020s | 0.060s |"
        " 0.200s | 0.010s | 0.350s | 0.600s | 136.0 | 272.0 | 71.3 |"
    )


def test_health_view_renders_checkpoint_restore_section(tmp_path, capsys):
    """The checkpoint/restore table renders manifest writes, restore spans
    and per-peer shard failure counts next to the wire-path view (ISSUE 5
    satellite; emitters: roles/coordinator.py, checkpointing/fetcher.py,
    averaging/averager.py)."""
    events = [
        {"t": 50.0, "peer": "coord", "event": "ckpt.manifest_written",
         "step": 100, "shards": 8, "bytes": 1048576},
        {"t": 60.0, "peer": "joiner", "event": "ckpt.shard_fetch_failed",
         "shard": 3, "provider": ["127.0.0.1", 1], "attempt": 1,
         "error": "ConnectionResetError"},
        {"t": 60.1, "peer": "joiner", "event": "ckpt.shard_verify_failure",
         "shard": 5, "provider": ["127.0.0.1", 2], "attempt": 1},
        {"t": 61.0, "peer": "joiner", "event": "ckpt.restore",
         "dur_s": 1.25, "mode": "sharded", "ok": True, "step": 100,
         "shards": 8, "bytes": 1048576, "providers": 3},
    ]
    runlog_summary.main(["--health", _write_events(tmp_path, events)])
    out = capsys.readouterr().out
    assert "checkpoint / restore:" in out
    assert "manifest written step=100 shards=8" in out
    (restore_row,) = [ln for ln in out.splitlines()
                      if ln.startswith("| joiner | sharded |")]
    assert restore_row == (
        "| joiner | sharded | ok | 1.250s | 8 | 1048576 | 3 |"
    )
    (fail_row,) = [ln for ln in out.splitlines()
                   if ln.startswith("| joiner | 1 |")]
    assert fail_row == "| joiner | 1 | 1 |"


def test_health_view_merges_logs_and_skips_old_schema_rows(tmp_path, capsys):
    """Several peers' event logs merge into one timeline (sorted by t), and
    an old-schema train_log row mixed into a file is skipped, not fatal."""
    a = _write_events(
        tmp_path,
        [{"t": 200.0, "peer": "a", "event": "avg.round", "dur_s": 0.1,
          "round_id": "step2", "ok": True},
         {"wall_s": 1.0, "step": 1, "loss": 2.0}],  # old schema: ignored
        name="a.jsonl",
    )
    b = _write_events(
        tmp_path,
        [{"t": 100.0, "peer": "b", "event": "avg.round", "dur_s": 0.2,
          "round_id": "step1", "ok": False}],
        name="b.jsonl",
    )
    runlog_summary.main(["--health", a, b])
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if "avg.round" in ln]
    assert len(lines) == 2
    assert "step1" in lines[0] and "FAILED" in lines[0]  # earliest t first
    assert "step2" in lines[1]


# ------------------------- the one hardened loader (ISSUE 7 satellite)


def test_hardened_loader_survives_truncated_tail_and_interleaved_writers(
    tmp_path, capsys
):
    """All telemetry views share load_jsonl_rows: a truncated final line (a
    peer killed mid-write) is skipped, and a line where two writers jammed
    their objects together is SPLIT — every complete object is salvaged."""
    good1 = {"t": 1.0, "peer": "a", "event": "e1"}
    good2 = {"t": 2.0, "peer": "b", "event": "e2"}
    good3 = {"t": 3.0, "peer": "a", "event": "e3"}
    p = tmp_path / "events.jsonl"
    p.write_text(
        json.dumps(good1) + "\n"
        # interleaved writers: two objects jammed onto one line
        + json.dumps(good2) + json.dumps(good3) + "\n"
        # garbage prefix before a valid object
        + 'xx%%' + json.dumps({"t": 4.0, "peer": "c", "event": "e4"}) + "\n"
        # truncated tail: the peer died mid-write
        + '{"t": 5.0, "peer": "a", "eve'
    )
    rows = runlog_summary.load_jsonl_rows([str(p)])
    err = capsys.readouterr().err
    assert [r["event"] for r in rows] == ["e1", "e2", "e3", "e4"]
    assert "skipped" in err  # the drops are reported, not silent

    events = runlog_summary.load_events([str(p)])
    assert [r["event"] for r in events] == ["e1", "e2", "e3", "e4"]


def test_trace_and_topology_ride_the_same_loader(tmp_path, capsys):
    """--trace and --topology must not re-grow their own parsers: rows that
    only the hardened loader can extract (jammed line) appear in both
    views."""
    span_id = "a" * 16
    rows = [
        {"t": 1.0, "peer": "p0", "event": "peer.endpoint",
         "endpoint": "127.0.0.1:1"},
        {"t": 2.0, "peer": "p0", "event": "avg.round", "dur_s": 0.4,
         "round_id": "step3", "ok": True, "trace": "t" * 16, "span": span_id},
        {"t": 2.1, "peer": "p1", "event": "mm.join.serve", "dur_s": 0.1,
         "round_id": "step3", "ok": True, "trace": "t" * 16,
         "span": "b" * 16, "parent": span_id, "caller": "p0"},
        {"t": 3.0, "peer": "p1", "event": "link.stats",
         "dst": "127.0.0.1:1", "rtt_s": 0.02, "goodput_bps": 1000.0,
         "bytes": 64, "transfers": 2},
    ]
    p = tmp_path / "jammed.jsonl"
    # everything on ONE line: only the raw_decode loader can read this
    p.write_text("".join(json.dumps(r) for r in rows) + "\n")

    runlog_summary.main(["--trace", "step3", str(p)])
    out = capsys.readouterr().out
    assert "mm.join.serve" in out
    assert "for p0's avg.round" in out  # cross-peer linkage resolved

    runlog_summary.main(["--topology", str(p)])
    out = capsys.readouterr().out
    assert "worst link: p1 -> p0" in out


def test_topology_degrades_to_allreduce_link_rows(tmp_path, capsys):
    """Logs from peers killed mid-run hold per-hop allreduce.link rows but
    no link.stats flush (that happens on the snapshot throttle / close) —
    --topology must rebuild estimates from the hop rows instead of exiting
    with 'no link telemetry'."""
    rows = [
        {"t": 1.0, "peer": "p0", "event": "peer.endpoint",
         "endpoint": "127.0.0.1:2"},
        # p1 -> p0: 1000 wire bytes over 0.001s send wall = fast
        {"t": 2.0, "peer": "p1", "event": "allreduce.link",
         "round_id": "step1", "dst": "127.0.0.1:2", "sent_bytes": 1000,
         "recv_bytes": 1000, "chunks_sent": 2, "chunks_recv": 2,
         "send_s": 0.001, "wait_s": 0.002, "max_chunk_s": 0.001},
        # p0 -> p1: same bytes over 0.5s = the slow link
        {"t": 2.1, "peer": "p0", "event": "allreduce.link",
         "round_id": "step1", "dst": "127.0.0.1:3", "sent_bytes": 1000,
         "recv_bytes": 1000, "chunks_sent": 2, "chunks_recv": 2,
         "send_s": 0.5, "wait_s": 0.6, "max_chunk_s": 0.3},
    ]
    runlog_summary.main(["--topology", _write_events(tmp_path, rows)])
    out = capsys.readouterr().out
    assert "link matrix" in out
    assert "worst link: p0 -> 127.0.0.1:3" in out  # unresolved dst kept raw
    assert "2.0KB/s" in out  # 1000 B / 0.5 s


# ----------------------------- --json machine-readable mode (ISSUE 11)
# (one JSON document per view, so the twin pipeline and future tooling
# consume summaries without screen-scraping; smoke over BOTH schemas —
# per-peer event logs and coordinator metrics JSONL)


def test_json_mode_health_view(tmp_path, capsys):
    events = [
        {"t": 100.0, "peer": "peerA", "event": "avg.round", "dur_s": 0.5,
         "round_id": "step1", "ok": True, "group_size": 2},
        {"t": 100.3, "peer": "peerA", "event": "state_sync.retry",
         "attempt": 1},
        {"t": 101.0, "peer": "peerB", "event": "fault.applied",
         "point": "averager.state_get", "action": "truncate"},
        {"t": 102.0, "peer": "joiner", "event": "ckpt.restore",
         "dur_s": 1.25, "mode": "sharded", "ok": True, "shards": 8,
         "bytes": 1048576, "providers": 3},
    ]
    runlog_summary.main(
        ["--json", "--health", _write_events(tmp_path, events)]
    )
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "health"
    assert doc["per_peer"]["peerA"]["retries"] == 1
    assert doc["per_peer"]["peerB"]["faults"] == 1
    assert doc["rounds"][0]["round_id"] == "step1"
    assert doc["checkpoint"]["restores"][0]["mode"] == "sharded"


def test_json_mode_steps_view_both_schemas(tmp_path, capsys):
    events = [
        {"t": 1.0, "peer": "p0", "event": "step.record", "step": 0,
         "dur_s": 0.6, "samples": 16, "untimed_s": 0.0,
         "phases": {"fwd_bwd": 0.5, "data_wait": 0.1}},
        {"t": 2.0, "peer": "p1", "event": "step.record", "step": 0,
         "dur_s": 2.1, "samples": 16, "untimed_s": 0.0,
         "phases": {"fwd_bwd": 0.5, "data_wait": 1.6}},
    ]
    runlog_summary.main(["--json", "--steps", _write_events(tmp_path, events)])
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "steps"
    assert doc["per_peer"]["p1"]["dominant"] == "data_wait"
    assert doc["skew"][0]["phase"] == "data_wait"
    assert doc["skew"][0]["peer"] == "p1"

    # coordinator schema: swarm_health.peers[].phases
    coord = {"t": 1.0, "swarm_health": {"current_step": 3, "peers": [
        {"peer": "fast", "step": 3, "phases": {"fwd_bwd": 0.6}},
        {"peer": "slow", "step": 3,
         "phases": {"fwd_bwd": 0.6, "data_wait": 1.8}},
    ]}}
    p = tmp_path / "coord.jsonl"
    p.write_text(json.dumps(coord) + "\n")
    runlog_summary.main(["--json", "--steps", str(p)])
    doc = json.loads(capsys.readouterr().out)
    assert doc["per_peer"]["slow"]["dominant"] == "data_wait"


def test_json_mode_topology_and_trace_views(tmp_path, capsys):
    span_id = "a" * 16
    rows = [
        {"t": 1.0, "peer": "p0", "event": "peer.endpoint",
         "endpoint": "127.0.0.1:1"},
        {"t": 2.0, "peer": "p0", "event": "avg.round", "dur_s": 0.4,
         "round_id": "step3", "ok": True, "trace": "t" * 16,
         "span": span_id},
        {"t": 2.1, "peer": "p1", "event": "mm.join.serve", "dur_s": 0.1,
         "round_id": "step3", "ok": True, "trace": "t" * 16,
         "span": "b" * 16, "parent": "c" * 16, "caller": "ghost"},
        {"t": 3.0, "peer": "p1", "event": "link.stats",
         "dst": "127.0.0.1:1", "rtt_s": 0.02, "goodput_bps": 1000.0,
         "bytes": 64, "transfers": 2},
    ]
    path = _write_events(tmp_path, rows)
    runlog_summary.main(["--json", "--topology", path])
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "topology"
    assert doc["worst_link"] == {"src": "p1", "dst": "p0"}
    assert doc["links"][0]["goodput_bps"] == 1000.0

    runlog_summary.main(["--json", "--trace", "step3", path])
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "trace"
    assert doc["peers"] == ["p0", "p1"]
    # the orphaned span is reported, never dropped
    assert doc["orphans"][0]["parent"] == "c" * 16


def test_json_mode_trainlog_view(tmp_path, capsys):
    # 8 rows: the percentile block skips the first 5 (warmup), matching
    # the text view
    rows = [
        {"wall_s": 10.0 * (i + 1), "step": i + 1, "loss": 11.0 - i,
         "boundary_ms": 120.0 - i}
        for i in range(8)
    ]
    runlog_summary.main(["--json", _write(tmp_path, rows)])
    doc = json.loads(capsys.readouterr().out)
    assert doc["view"] == "train_log"
    assert doc["steps"][-1]["step"] == 8
    assert doc["total_steps"] == 8
    assert "boundary_ms" in doc["phase_percentiles_ms"]


def test_json_and_text_modes_agree_on_the_same_data(tmp_path, capsys):
    """The JSON document and the rendered table are two faces of one
    computation — the dominant phase named in the text must be the one in
    the document."""
    events = [
        {"t": 1.0, "peer": "p0", "event": "step.record", "step": 0,
         "dur_s": 1.0, "samples": 8, "untimed_s": 0.0,
         "phases": {"avg_wire": 0.9, "fwd_bwd": 0.1}},
    ]
    path = _write_events(tmp_path, events)
    runlog_summary.main(["--steps", path])
    text = capsys.readouterr().out
    runlog_summary.main(["--json", "--steps", path])
    doc = json.loads(capsys.readouterr().out)
    assert "dominant avg_wire" in text
    assert doc["per_peer"]["p0"]["dominant"] == "avg_wire"


def test_topology_plan_section_previews_hierarchical_averaging(
    tmp_path, capsys
):
    """ISSUE 15 satellite: --topology renders the two-level plan the
    runtime planner (averaging/topology.py) would build from the SAME
    folded link table — clique assignment + elected delegate as a `plan`
    column on the links rows and a dedicated plan section — so operators
    preview the hierarchy before enabling --averager.topology_plan."""
    eps = {f"p{i}": f"127.0.0.1:{i + 1}" for i in range(4)}
    rows = [
        {"t": 1.0, "peer": p, "event": "peer.endpoint", "endpoint": ep}
        for p, ep in eps.items()
    ]
    cliques = [("p0", "p1"), ("p2", "p3")]
    fat = {"p1", "p3"}  # fattest uplink per clique: the elected delegates
    for a, b in cliques:
        for s, d in ((a, b), (b, a)):
            rows.append({
                "t": 2.0, "peer": s, "event": "link.stats", "dst": eps[d],
                "rtt_s": 0.004,
                "goodput_bps": 5e8 if s in fat else 1e8,
                "bytes": 1000, "transfers": 3,
            })
    for s in ("p0", "p1"):
        for d in ("p2", "p3"):
            for src, dst in ((s, d), (d, s)):
                rows.append({
                    "t": 2.0, "peer": src, "event": "link.stats",
                    "dst": eps[dst], "rtt_s": 0.12,
                    "goodput_bps": 5e8 if src in fat else 1e8,
                    "bytes": 1000, "transfers": 3,
                })
    path = _write_events(tmp_path, rows)

    runlog_summary.main(["--json", "--topology", path])
    doc = json.loads(capsys.readouterr().out)
    plan = doc["plan"]
    assert plan["mode"] == "hierarchical"
    assert [c["members"] for c in plan["cliques"]] == [
        ["p0", "p1"], ["p2", "p3"]
    ]
    assert [c["delegate"] for c in plan["cliques"]] == ["p1", "p3"]

    runlog_summary.main(["--topology", path])
    out = capsys.readouterr().out
    assert "hierarchical plan (hierarchical): 2 cliques" in out
    assert "| c0 | p1 | p0, p1 |" in out
    assert "| c1 | p3 | p2, p3 |" in out
    # the links table's plan column tags each src with its clique,
    # delegates starred
    assert "| plan |" in out
    assert " c0* |" in out and " c1* |" in out

    # a table too sparse for a hierarchy says so instead of hiding the
    # section (the fallback the runtime would take too)
    sparse = _write_events(tmp_path, rows[:5], name="sparse.jsonl")
    runlog_summary.main(["--topology", sparse])
    out = capsys.readouterr().out
    assert "hierarchical plan (flat)" in out


def test_topology_accepts_coordinator_folded_record(tmp_path, capsys):
    """--topology also renders a coordinator metrics JSONL whose
    swarm_health.topology already folded the per-peer link views."""
    row = {
        "step": 9,
        "swarm_health": {
            "current_step": 9,
            "topology": {
                "peers": {"aa": "10.0.0.1:7", "bb": "10.0.0.2:7"},
                "links": [
                    {"src": "aa", "dst": "bb",
                     "dst_endpoint": "10.0.0.2:7",
                     "rtt_s": 0.002, "goodput_bps": 5e6, "bytes": 100},
                    {"src": "bb", "dst": "aa",
                     "dst_endpoint": "10.0.0.1:7",
                     "rtt_s": 0.2, "goodput_bps": 1e3, "bytes": 100},
                ],
            },
        },
    }
    p = tmp_path / "coordinator_metrics.jsonl"
    p.write_text(json.dumps(row) + "\n")
    runlog_summary.main(["--topology", str(p)])
    out = capsys.readouterr().out
    assert "worst link: bb -> aa" in out
    assert "5.0MB/s" in out
