"""Summarize a trainer train_log.jsonl into the BASELINE.md table format,
or render swarm views from telemetry event logs.

Usage:
    python tools/runlog_summary.py train_log.jsonl [step step ...]
    python tools/runlog_summary.py --health events.jsonl [events2.jsonl ...]
    python tools/runlog_summary.py --trace ROUND_ID events.jsonl [...]
    python tools/runlog_summary.py --topology events.jsonl [...]
    python tools/runlog_summary.py --steps events.jsonl [...]
    python tools/runlog_summary.py --twin events.jsonl [...]
    python tools/runlog_summary.py --incidents coordinator_metrics.jsonl [...]
    python tools/runlog_summary.py --contributions coordinator_ledger.jsonl [...]

Any view also accepts ``--json``: one machine-readable JSON document on
stdout (schema: the ``*_data`` builders below, each tagged with a
``view`` field) instead of the rendered tables — the twin pipeline and
future tooling consume summaries without screen-scraping.

Default mode prints a markdown `| global step | wall (min) | loss |` table at
the given checkpoints (default: a log-spaced selection plus the final step)
and the phase-telemetry percentiles (boundary/data-wait/allreduce/seam) the
trainer records per global step.

``--health`` mode reads per-peer telemetry event logs (the
``--telemetry.event_log_path`` JSONL, schema in docs/observability.md) —
several peers' logs can be merged in one invocation — and renders the round
timeline plus a per-peer fault/retry table: which rounds ran, how long each
took, who injected/suffered faults, who retried state syncs, whose joins
failed.

``--trace ROUND_ID`` stitches every peer's events for ONE round into a
cross-peer causal timeline using the trace-context linkage fields
(``trace``/``span``/``parent``/``caller``, threaded through the RPC framing
— docs/observability.md "Cross-peer trace propagation"): who waited on whom
across RPC hops, per-hop wire vs reduce vs straggler time, the critical
path (the slowest link, not just the slowest peer), and any ORPHANED spans
whose parent never appears in the collected logs (a peer that died
mid-round, or whose log was not collected).

``--topology`` renders the swarm link matrix from per-link telemetry
(``link.stats`` / ``allreduce.link`` / ``peer.endpoint`` events; it also
accepts a coordinator metrics JSONL whose ``swarm_health.topology`` record
already folded the per-peer views): per-link RTT/goodput estimates ranked
worst-first, low-RTT clique candidates, and fat/thin peers — the input the
hierarchical matchmaker reads (ROADMAP item 1).

``--twin`` fits a digital twin (``dedloc_tpu/twin``) from the event logs,
replays the recorded workload over it in virtual time, and renders the
FIDELITY report — twin-predicted vs observed round wall / formation /
samples-per-sec / overlap efficiency, per peer and swarm-wide, plus the
worst-link ranking agreement and the fit-coverage summary. With ``--json``
the machine-readable fidelity document is printed, so twin drift is itself
monitorable.

``--incidents`` renders the live watchdog's incident timeline
(``dedloc_tpu/telemetry/watch.py``): given a coordinator metrics JSONL it
REPLAYS the stream through the same watchdog the coordinator runs inline
(deterministic — the replayed timeline is the live one); given the
coordinator's incident JSONL it renders the recorded transitions as-is.
Each incident shows severity, the metric that moved and by how much
against its rolling baseline, open/close fold indices, and the
attribution chain: offending peer and/or directed link, dominant step
phase, and the representative slow round's trace id (feed it to
``--trace``). Reading guide in docs/observability.md.

``--contributions`` renders the volunteer leaderboard from the signed
contribution ledger (``dedloc_tpu/telemetry/ledger.py``): per-peer credited
vs claimed samples (credited = min(claimed, receipt-supported x slack)),
share of swarm, rounds, checkpoint/state bytes served, and any per-peer
discrepancy the receipt fold flagged. Accepts the coordinator's durable
ledger JSONL (recorded folds, last state wins) or per-peer telemetry event
logs (``ledger.claim``/``ledger.receipt`` events — refolded through the
same schemas and fold the coordinator runs). Reading guide in
docs/observability.md; the discrepancy runbook is docs/fleet.md.

``--steps`` renders, ahead of the steps, each peer's set-up record
(``setup.record``: the laps of its start, the first calls inside them, the
compile sums) and the step-phase flight recorder's view (per-step
``step.record`` / ``step.phase`` events from ``telemetry/steps.py``, or a
coordinator metrics JSONL whose ``swarm_health.peers[].phases`` already
folded the per-peer means): a step-time waterfall per peer with the
dominant phase named, the phase-skew ranking across peers (which peer's
phase is furthest off the swarm median — the "who is stalling us and WHY"
answer), and the overlap-averaging ledger per boundary (hidden vs exposed
averaging wall, efficiency) — the debug ladder's final rung: swarm → round
→ link → *phase*.

All telemetry views share ONE hardened loader: truncated final lines
(a peer killed mid-write) and interleaved/jammed lines (two writers on one
file) are skipped or split, never fatal.
"""
from __future__ import annotations

import json
import sys


def load(path):
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    # wall_s is per-process: a checkpoint-resume starts a new segment whose
    # clock restarts. Rebase each segment so wall_s accumulates run-wide.
    # A regressing/repeating step counter is the robust resume signal (the
    # new process may log a first wall_s larger than the old one's last);
    # a wall_s drop catches most same-step restarts. Known blind spot: a
    # restart that both continues the step sequence AND logs a first wall_s
    # above the prior segment's last (short segment + slow startup) is
    # indistinguishable from a long between-steps gap in this schema — the
    # prior segment's wall then goes uncounted.
    offset, prev_wall, prev_step = 0.0, None, None
    for r in rows:
        if prev_wall is not None and (
            r["wall_s"] < prev_wall or r["step"] <= prev_step
        ):
            offset += prev_wall
        prev_wall, prev_step = r["wall_s"], r["step"]
        r["wall_s"] += offset
    return rows


def pick_steps(rows, requested):
    steps = {r["step"] for r in rows}
    if requested:
        missing = [s for s in requested if s not in steps]
        if missing:
            print(f"warning: requested steps not in log: {missing}",
                  file=sys.stderr)
        return [s for s in requested if s in steps]
    last = rows[-1]["step"]
    marks = [1, 10, 25, 50, 100, 200, 300, 500, 700, 1000, 1330, 1500, 2000,
             2500, 3000, 3500, 4000]
    out = [s for s in marks if s in steps and s < last]
    return out + [last]


def percentiles(values):
    if not values:
        return (0.0, 0.0, 0.0)
    s = sorted(values)

    def pct(p):
        return s[min(len(s) - 1, int(p * len(s)))]

    return pct(0.50), pct(0.90), pct(0.99)


# -------------------------------------------------------- telemetry loaders
# (telemetry event-log schema: {"t", "peer", "event", "dur_s"?, ...attrs};
# docs/observability.md. Tolerates rows from older emitters — any line with
# an "event" key renders, unknown events just count toward totals.)


def _repo_on_path():
    """Make ``dedloc_tpu`` importable for the views that need it, exactly
    once (this tool also runs standalone from outside the repo root)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)


def load_jsonl_rows(paths):
    """THE hardened JSONL loader every telemetry view (--health, --trace,
    --topology, --incidents) goes through: truncated final lines are
    skipped, interleaved-writer lines split object-by-object. The ONE
    implementation lives in ``dedloc_tpu/utils/jsonl.py`` — the
    coordinator's self-retune read-back and the swarm_watch tail share it,
    so tolerance rules cannot drift between live and post-hoc paths."""
    _repo_on_path()
    from dedloc_tpu.utils.jsonl import load_jsonl_rows as _load

    return _load(paths)


def load_events(paths):
    """Event rows (telemetry schema), merged across peers, time-ordered."""
    rows = [r for r in load_jsonl_rows(paths) if "event" in r]
    rows.sort(key=lambda r: r.get("t", 0.0))
    return rows


# telemetry names come from the generated catalog (telemetry/events.py):
# the dedlint schema checker guards the constants' emit sites, so a
# producer rename breaks HERE at import instead of silently zeroing a view
_repo_on_path()
from dedloc_tpu.telemetry import events as ev  # noqa: E402

_FAULT_EVENTS = (ev.FAULT_APPLIED, ev.FAULT_INJECTED)
_RETRY_EVENTS = (ev.STATE_SYNC_RETRY,)
_ROUND_EVENTS = (ev.AVG_ROUND, ev.MM_FORM_GROUP, ev.ALLREDUCE_ROUND)


def _health_per_peer(rows):
    """Per-peer fault/retry counters — the --health table's data."""
    per_peer = {}
    for r in rows:
        peer = r.get("peer", "?")
        stats = per_peer.setdefault(
            peer,
            {"faults": 0, "retries": 0, "checksum": 0, "rpc_fail": 0,
             "join_fail": 0, "dropped": 0, "events": 0},
        )
        stats["events"] += 1
        event = r["event"]
        if event in _FAULT_EVENTS:
            stats["faults"] += 1
        elif event in _RETRY_EVENTS:
            stats["retries"] += 1
        elif event == ev.STATE_SYNC_CHECKSUM_FAILURE:
            stats["checksum"] += 1
        elif event == ev.RPC_CLIENT_FAILURE:
            stats["rpc_fail"] += 1
        elif event == ev.MM_JOIN_FAILED:
            stats["join_fail"] += 1
        elif event == ev.OPT_GRADS_DROPPED:
            stats["dropped"] += 1
    return per_peer


def _health_rounds(rows):
    rounds = [r for r in rows if r["event"] == ev.AVG_ROUND]
    if not rounds:  # peers that never reached a full round: show what ran
        rounds = [r for r in rows if r["event"] in _ROUND_EVENTS]
    return rounds


# allreduce.round fields summed per peer for the wire-path table: the kinds
# of loop-thread work inside the round, the wait that is none of them, the
# partner's lag and the loop thread's CPU (docs/observability.md)
_WIRE_FIELDS = (
    ("encode", "encode_s"), ("decode", "decode_s"), ("reduce", "reduce_s"),
    ("copy", "copy_s"), ("frame", "frame_s"), ("wait", "wait_s"),
    ("partner_lag", "partner_lag_s"), ("loop_cpu", "loop_cpu_s"),
)


def _wire_per_peer(rows):
    """Per-peer pipelined-allreduce aggregates: where the loop thread's
    time went inside a round (codec, framing, reduce, copies, waiting)."""
    wire_rounds = [r for r in rows if r["event"] == ev.ALLREDUCE_ROUND
                   and ("reduce_s" in r or "gather_wait_s" in r)]
    per_peer_wire = {}
    for r in wire_rounds:
        acc = per_peer_wire.setdefault(
            r.get("peer", "?"),
            {"rounds": 0, "dur": 0.0, "gather": 0.0, "chunks": 0,
             "attached": 0, "attached_bytes": 0,
             **{key: 0.0 for key, _field in _WIRE_FIELDS}},
        )
        acc["rounds"] += 1
        acc["dur"] += float(r.get("dur_s", 0.0))
        acc["gather"] += float(r.get("gather_wait_s", 0.0))
        acc["chunks"] += int(r.get("chunks", 0))
        # payloads the round's frames carried by reference, both directions
        acc["attached"] += int(r.get("attached_chunks", 0))
        acc["attached_bytes"] += int(r.get("attached_bytes", 0))
        for key, field in _WIRE_FIELDS:
            acc[key] += float(r.get(field, 0.0))
    return per_peer_wire


def _ckpt_failures(rows):
    failures = {}
    for r in rows:
        if r["event"] in (ev.CKPT_SHARD_FETCH_FAILED,
                          ev.CKPT_SHARD_VERIFY_FAILURE):
            acc = failures.setdefault(r.get("peer", "?"),
                                      {"fetch": 0, "verify": 0})
            if r["event"] == ev.CKPT_SHARD_FETCH_FAILED:
                acc["fetch"] += 1
            else:
                acc["verify"] += 1
    return failures


def _event_rates(rows):
    """The watchdog's rule rates recomputed from raw event rows — the
    --health input — so the verdict header evaluates the SAME thresholds
    (telemetry/health.RULE_THRESHOLDS) the live watchdog applies to folded
    records. Only the rates this input can support are produced; the rest
    are skipped, never guessed."""
    rates = {}
    forms = [r for r in rows if r["event"] == ev.MM_FORM_GROUP]
    if forms:
        # form_group spans always stamp ok True/False, so from event logs
        # "aborted" and "attempted but never formed" are the SAME set —
        # one rate, not the same defect double-counted in the verdict
        # (the fold-side derive_rates can tell them apart; events cannot)
        rates["round_abort_rate"] = round(
            sum(1 for r in forms if r.get("ok") is not True)
            / len(forms), 4
        )
    lost = [r for r in rows if r["event"] == ev.RPC_CONN_LOST]
    ts = [r.get("t", 0.0) for r in rows]
    span_min = (max(ts) - min(ts)) / 60.0 if len(ts) >= 2 else 0.0
    if span_min > 0:
        rates["conns_lost_per_min"] = round(len(lost) / span_min, 3)
    return rates


def _verdict_line(rows, rates=None):
    """"verdict: OK/DEGRADED (reason)" via the shared rule set."""
    _repo_on_path()
    from dedloc_tpu.telemetry.health import verdict_from_rates

    status, reason = verdict_from_rates(
        _event_rates(rows) if rates is None else rates
    )
    return status, reason


def health_data(rows):
    """The --health view as one JSON-able document."""
    if not rows:
        sys.exit("no telemetry events found (is --telemetry.enabled set?)")
    t0 = min(r.get("t", 0.0) for r in rows)

    def simplify(r, *keys):
        out = {"t": round(r.get("t", 0.0) - t0, 3),
               "peer": r.get("peer", "?"), "event": r["event"]}
        for key in keys:
            if r.get(key) is not None:
                out[key] = r[key]
        return out

    rates = _event_rates(rows)
    status, reason = _verdict_line(rows, rates)
    return {
        "view": "health",
        "verdict": {"status": status, "reason": reason},
        "derived": rates,
        "events": len(rows),
        "rounds": [
            simplify(r, "round_id", "dur_s", "ok", "group_size")
            for r in _health_rounds(rows)
        ],
        "faults": [
            simplify(r, "point", "method", "action")
            for r in rows if r["event"] in _FAULT_EVENTS
        ],
        "per_peer": _health_per_peer(rows),
        "wire": {
            peer: {
                "rounds": a["rounds"],
                "dur_mean_s": round(a["dur"] / a["rounds"], 6),
                "gather_wait_mean_s": round(a["gather"] / a["rounds"], 6),
                "chunks_mean": round(a["chunks"] / a["rounds"], 2),
                "attached_mean": round(a["attached"] / a["rounds"], 2),
                "attached_mb_mean": round(
                    a["attached_bytes"] / a["rounds"] / 1e6, 3
                ),
                **{
                    f"{key}_mean_s": round(a[key] / a["rounds"], 6)
                    for key, _field in _WIRE_FIELDS
                },
            }
            for peer, a in _wire_per_peer(rows).items()
        },
        "checkpoint": {
            "manifests": [
                simplify(r, "step", "shards", "bytes")
                for r in rows if r["event"] == ev.CKPT_MANIFEST_WRITTEN
            ],
            "restores": [
                simplify(r, "mode", "ok", "dur_s", "shards", "bytes",
                         "providers")
                for r in rows if r["event"] == ev.CKPT_RESTORE
            ],
            "shard_failures": _ckpt_failures(rows),
        },
    }


def print_health(rows):
    if not rows:
        sys.exit("no telemetry events found (is --telemetry.enabled set?)")
    t0 = min(r.get("t", 0.0) for r in rows)

    # the one-line verdict, from the SAME rule set the live watchdog runs
    # (telemetry/health.RULE_THRESHOLDS): the post-hoc view and the
    # watchdog cannot disagree about what counts as DEGRADED
    status, reason = _verdict_line(rows)
    print(f"verdict: {status} ({reason})")

    rounds = _health_rounds(rows)
    print("round timeline:")
    if not rounds:
        print("  (no rounds recorded)")
    for r in rounds:
        ok = r.get("ok")
        flag = "" if ok is None else (" ok" if ok else " FAILED")
        group = r.get("group_size")
        group_s = f" group={group}" if group is not None else ""
        print(
            f"  +{r.get('t', 0.0) - t0:8.2f}s  peer={r.get('peer', '?'):<12} "
            f"{r['event']:<14} {r.get('round_id', '?'):<12} "
            f"dur={r.get('dur_s', 0.0):.3f}s{group_s}{flag}"
        )

    faults = [r for r in rows if r["event"] in _FAULT_EVENTS]
    if faults:
        print("\ninjected faults:")
        for r in faults:
            where = r.get("point", r.get("method", "?"))
            print(
                f"  +{r.get('t', 0.0) - t0:8.2f}s  "
                f"peer={r.get('peer', '?'):<12} {r['event']:<14} "
                f"{where} action={r.get('action', '?')}"
            )

    per_peer = _health_per_peer(rows)

    # wire-path attribution (pipelined all-reduce, docs/observability.md):
    # every member's allreduce.round span carries what its loop thread did
    # inside the round by kind (encode_s / decode_s / reduce_s / copy_s /
    # frame_s), wait_s (the round's wall minus those: a socket, the partner,
    # the GIL), the partner's lag, the loop thread's CPU seconds, and
    # gather_wait_s (wall from gather launch to the last reduced chunk
    # landing) — a slow round whose wait dwarfs the kinds is wire- or
    # partner-bound, the reverse is CPU-bound on this peer's loop
    per_peer_wire = _wire_per_peer(rows)
    if per_peer_wire:
        print("\nwire path (mean per all-reduce round):")
        print("| peer | rounds | dur | encode | decode | reduce | copy |"
              " frame | wait | partner lag | loop cpu | gather wait |"
              " chunks | attached | attached MB |")
        print("|" + "---|" * 15)
        for peer in sorted(per_peer_wire):
            a = per_peer_wire[peer]
            k = a["rounds"]
            print(
                f"| {peer} | {k} | {a['dur'] / k:.3f}s | " + " | ".join(
                    f"{a[key] / k:.3f}s" for key, _field in _WIRE_FIELDS
                ) + f" | {a['gather'] / k:.3f}s | {a['chunks'] / k:.1f} |"
                f" {a['attached'] / k:.1f} |"
                f" {a['attached_bytes'] / k / 1e6:.1f} |"
            )

    # checkpoint/restore view (swarm checkpointing, docs/fleet.md restart
    # runbook): manifest writes from the coordinator, each peer's restore
    # span (sharded vs blob, wall, shards, providers), and the per-peer
    # shard fetch/verify failure counts the retry ladder absorbed
    manifests = [r for r in rows if r["event"] == ev.CKPT_MANIFEST_WRITTEN]
    restores = [r for r in rows if r["event"] == ev.CKPT_RESTORE]
    ckpt_failures = _ckpt_failures(rows)
    if manifests or restores or ckpt_failures:
        print("\ncheckpoint / restore:")
        for r in manifests:
            print(
                f"  +{r.get('t', 0.0) - t0:8.2f}s  "
                f"peer={r.get('peer', '?'):<12} manifest written "
                f"step={r.get('step', '?')} shards={r.get('shards', '?')} "
                f"bytes={r.get('bytes', '?')}"
            )
        if restores:
            print("| peer | mode | ok | restore wall | shards | bytes |"
                  " providers |")
            print("|---|---|---|---|---|---|---|")
            for r in restores:
                ok = r.get("ok")
                print(
                    f"| {r.get('peer', '?')} | {r.get('mode', '?')} |"
                    f" {'ok' if ok else 'FAILED'} |"
                    f" {r.get('dur_s', 0.0):.3f}s | {r.get('shards', '-')} |"
                    f" {r.get('bytes', '-')} | {r.get('providers', '-')} |"
                )
        if ckpt_failures:
            print("| peer | shard fetch failures | shard verify failures |")
            print("|---|---|---|")
            for peer in sorted(ckpt_failures):
                f = ckpt_failures[peer]
                print(f"| {peer} | {f['fetch']} | {f['verify']} |")

    print("\n| peer | events | faults | sync retries | checksum fails |"
          " rpc failures | join failures | grads dropped |")
    print("|---|---|---|---|---|---|---|---|")
    for peer in sorted(per_peer):
        s = per_peer[peer]
        print(
            f"| {peer} | {s['events']} | {s['faults']} | {s['retries']} |"
            f" {s['checksum']} | {s['rpc_fail']} | {s['join_fail']} |"
            f" {s['dropped']} |"
        )


# ---------------------------------------------------------------- trace view
# (cross-peer causal timeline for ONE round, stitched over the linkage
# fields the trace-context propagation writes: docs/observability.md)


def _endpoint_map(rows):
    """{endpoint: peer label} from peer.endpoint self-identification
    events — resolves the link destinations peers report into labels."""
    out = {}
    for r in rows:
        if r.get("event") == ev.PEER_ENDPOINT and r.get("endpoint"):
            out[str(r["endpoint"])] = r.get("peer", "?")
    return out


def _fmt_dst(dst, ep_map):
    peer = ep_map.get(str(dst))
    return f"{peer} ({dst})" if peer else str(dst)


def _round_matches(round_id, round_key):
    """Exact round match. Round ids are either the bare optimizer key
    ("step17") or the averager's composite allreduce form
    ("prefix:step17:nonce") — match whole ``:``-separated segments, never
    substrings, or ``--trace step1`` would swallow step10..step19 and
    print a multi-round chimera."""
    rid = str(round_id)
    return rid == round_key or round_key in rid.split(":")


def select_trace(rows, round_key):
    """Rows belonging to one round's cross-peer trace: everything whose
    round_id matches, plus everything sharing those rows' trace ids
    (server-side serve spans carry the trace but not always the round)."""
    matched = [r for r in rows if _round_matches(r.get("round_id", ""), round_key)]
    traces = {r["trace"] for r in matched if r.get("trace")}
    if traces:
        return [
            r for r in rows
            if r.get("trace") in traces
            or _round_matches(r.get("round_id", ""), round_key)
        ], traces
    return matched, traces


def trace_data(rows, round_key):
    """The --trace view as one JSON-able document."""
    trace_rows, traces = select_trace(rows, round_key)
    if not trace_rows:
        sys.exit(
            f"no events for round {round_key!r} (is --telemetry.enabled "
            "set, and are these the right event logs?)"
        )
    ep_map = _endpoint_map(rows)
    spans = {r["span"]: r for r in trace_rows if r.get("span")}
    t0 = min(r.get("t", 0.0) for r in trace_rows)
    hops = [r for r in trace_rows if r.get("event") == ev.ALLREDUCE_LINK]
    doc = {
        "view": "trace",
        "round": round_key,
        "traces": sorted(traces),
        "peers": sorted({r.get("peer", "?") for r in trace_rows}),
        "events": [
            {**{k: v for k, v in r.items() if k != "t"},
             "t": round(r.get("t", 0.0) - t0, 6)}
            for r in sorted(trace_rows, key=lambda r: r.get("t", 0.0))
        ],
        "orphans": [
            {"peer": r.get("peer", "?"), "event": r.get("event", "?"),
             "parent": r["parent"], "caller": r.get("caller")}
            for r in trace_rows
            if r.get("parent") and r["parent"] not in spans
        ],
    }
    if hops:
        worst = max(hops, key=lambda r: float(r.get("wait_s", 0.0)))
        doc["critical_path"] = {
            "peer": worst.get("peer", "?"),
            "dst": _fmt_dst(worst.get("dst"), ep_map),
            "wait_s": float(worst.get("wait_s", 0.0)),
            "reduce_total_s": sum(
                float(r.get("reduce_s", 0.0)) for r in trace_rows
                if r.get("event") == ev.ALLREDUCE_ROUND
            ),
        }
    return doc


def print_trace(rows, round_key):
    trace_rows, traces = select_trace(rows, round_key)
    if not trace_rows:
        sys.exit(
            f"no events for round {round_key!r} (is --telemetry.enabled "
            "set, and are these the right event logs?)"
        )
    ep_map = _endpoint_map(rows)
    peers = sorted({r.get("peer", "?") for r in trace_rows})
    print(f"round {round_key}: {len(trace_rows)} events from "
          f"{len(peers)} peer(s) {peers}, "
          f"trace {sorted(traces) if traces else '(no linkage fields)'}")

    spans = {r["span"]: r for r in trace_rows if r.get("span")}
    t0 = min(r.get("t", 0.0) for r in trace_rows)
    print("\ntimeline (cross-peer, causal):")
    for r in sorted(trace_rows, key=lambda r: r.get("t", 0.0)):
        dur = f" dur={r['dur_s']:.3f}s" if "dur_s" in r else ""
        parent = r.get("parent")
        linked = ""
        if parent:
            parent_row = spans.get(parent)
            if parent_row is not None and parent_row.get("peer") != r.get("peer"):
                # a remote parent: this row happened ON BEHALF of another
                # peer's span — the who-waited-on-whom arrow
                linked = f"  ← for {parent_row.get('peer', '?')}'s " \
                         f"{parent_row.get('event', '?')}"
            elif parent_row is None and r.get("caller"):
                linked = f"  ← for {r['caller']} (parent span not collected)"
        ok = r.get("ok")
        flag = "" if ok is None else (" ok" if ok else " FAILED")
        extra = ""
        if r.get("event") == ev.ALLREDUCE_LINK:
            extra = (
                f" dst={_fmt_dst(r.get('dst'), ep_map)}"
                f" wait={r.get('wait_s', 0.0):.3f}s"
                f" send={r.get('send_s', 0.0):.3f}s"
                f" bytes={int(r.get('sent_bytes', 0) + r.get('recv_bytes', 0))}"
            )
        elif r.get("event") == ev.ALLREDUCE_STRAGGLERS:
            extra = f" missing={r.get('missing')}"
        print(
            f"  +{r.get('t', 0.0) - t0:7.3f}s  {r.get('peer', '?'):<12} "
            f"{r.get('event', '?'):<20}{dur}{flag}{extra}{linked}"
        )

    # per-hop attribution: every member's allreduce.link rows say how long
    # it waited on each link; the host-side allreduce.round spans say how
    # much of a round was reduce CPU; straggler events mark SLA waits
    hops = [r for r in trace_rows if r.get("event") == ev.ALLREDUCE_LINK]
    if hops:
        print("\nper-hop wire time:")
        print("| src | dst | chunks | bytes | send | wait | max chunk |")
        print("|---|---|---|---|---|---|---|")
        for r in sorted(hops, key=lambda r: -float(r.get("wait_s", 0.0))):
            print(
                f"| {r.get('peer', '?')} | {_fmt_dst(r.get('dst'), ep_map)} |"
                f" {int(r.get('chunks_sent', 0) + r.get('chunks_recv', 0))} |"
                f" {int(r.get('sent_bytes', 0) + r.get('recv_bytes', 0))} |"
                f" {r.get('send_s', 0.0):.3f}s | {r.get('wait_s', 0.0):.3f}s |"
                f" {r.get('max_chunk_s', 0.0):.3f}s |"
            )
        worst = max(hops, key=lambda r: float(r.get("wait_s", 0.0)))
        reduce_total = sum(
            float(r.get("reduce_s", 0.0)) for r in trace_rows
            if r.get("event") == ev.ALLREDUCE_ROUND
        )
        stragglers = [
            r for r in trace_rows if r.get("event") == ev.ALLREDUCE_STRAGGLERS
        ]
        print(
            f"\ncritical path: {worst.get('peer', '?')} waited "
            f"{float(worst.get('wait_s', 0.0)):.3f}s on link "
            f"{worst.get('peer', '?')} -> {_fmt_dst(worst.get('dst'), ep_map)}"
            f" (wire); reduce CPU across hosts {reduce_total:.3f}s"
            + (
                f"; straggler SLA waits: "
                f"{[r.get('missing') for r in stragglers]}"
                if stragglers else ""
            )
        )

    # orphaned spans: a parent id that appears in NO collected log — the
    # parent peer died mid-round or its log was never collected. Reported,
    # never silently dropped: the orphan is exactly where the causal chain
    # broke.
    orphans = [
        r for r in trace_rows
        if r.get("parent") and r["parent"] not in spans
    ]
    if orphans:
        print(f"\norphaned spans ({len(orphans)}): parent span never "
              "collected (peer died mid-round, or its log is missing)")
        for r in orphans:
            caller = f" caller={r['caller']}" if r.get("caller") else ""
            print(
                f"  {r.get('peer', '?'):<12} {r.get('event', '?'):<20} "
                f"parent={r['parent']}{caller}"
            )


# ------------------------------------------------------------- topology view
# (per-link RTT/goodput matrix: link.stats events per peer, or a
# coordinator metrics JSONL whose swarm_health.topology already folded them)


def _links_from_events(rows):
    """[{src, dst, rtt_s?, goodput_bps?, ...}] from per-peer link.stats
    events (latest per (src, dst) wins — they are cumulative estimates).

    Degraded mode: logs from peers killed mid-run (the crash/churn
    scenarios this tool debugs) may hold NO link.stats flush — estimates
    are then rebuilt from the per-round allreduce.link rows: goodput =
    scattered wire bytes over pure send wall, aggregated per (src, dst)."""
    latest = {}
    for r in rows:
        if r.get("event") == ev.LINK_STATS and r.get("dst"):
            latest[(r.get("peer", "?"), str(r["dst"]))] = r
    if latest:
        out = []
        for (src, dst), r in sorted(latest.items()):
            link = {"src": src, "dst": dst}
            for key in ("rtt_s", "rtt_min_s", "rtt_jitter_s",
                        "goodput_bps", "peak_bps", "bytes", "transfers",
                        "chunk_p50_s", "chunk_max_s"):
                if key in r:
                    link[key] = float(r[key])
            out.append(link)
        return out
    acc = {}
    for r in rows:
        if r.get("event") != ev.ALLREDUCE_LINK or not r.get("dst"):
            continue
        a = acc.setdefault(
            (r.get("peer", "?"), str(r["dst"])),
            {"bytes": 0.0, "send_s": 0.0, "transfers": 0.0,
             "chunk_max_s": 0.0},
        )
        a["bytes"] += float(r.get("sent_bytes", 0.0))
        a["send_s"] += float(r.get("send_s", 0.0))
        a["transfers"] += float(r.get("chunks_sent", 0.0))
        a["chunk_max_s"] = max(
            a["chunk_max_s"], float(r.get("max_chunk_s", 0.0))
        )
    out = []
    for (src, dst), a in sorted(acc.items()):
        link = {"src": src, "dst": dst, "bytes": a["bytes"],
                "transfers": a["transfers"],
                "chunk_max_s": a["chunk_max_s"]}
        if a["bytes"] > 0 and a["send_s"] > 0:
            link["goodput_bps"] = a["bytes"] / a["send_s"]
        out.append(link)
    return out


def _link_sort_key(link):
    """Worst link first: lowest goodput, then slowest median chunk, then
    highest RTT. Links with no goodput sample yet sort after measured
    ones — an unmeasured link is unknown, not slow."""
    goodput = link.get("goodput_bps")
    return (
        0 if goodput is not None else 1,
        goodput if goodput is not None else 0.0,
        -float(link.get("chunk_p50_s", 0.0)),
        -float(link.get("rtt_s", 0.0)),
    )


def _fmt_rate(bps):
    if bps is None:
        return "-"
    if bps >= 1e6:
        return f"{bps / 1e6:.1f}MB/s"
    if bps >= 1e3:
        return f"{bps / 1e3:.1f}KB/s"
    return f"{bps:.0f}B/s"


def _collect_topology(all_rows):
    """Link records (with ``dst_label`` resolved) from per-peer events or
    the newest folded coordinator topology record — the data both the
    rendered matrix and the --json document are built from."""
    # a coordinator metrics JSONL already carries the folded record: use the
    # newest; otherwise fold per-peer link.stats events here
    folded = [
        r["swarm_health"]["topology"] for r in all_rows
        if isinstance(r.get("swarm_health"), dict)
        and r["swarm_health"].get("topology")
    ]
    event_rows = [r for r in all_rows if "event" in r]
    ep_map = _endpoint_map(event_rows)
    if folded:
        topo = folded[-1]
        links = [dict(l) for l in topo.get("links", [])]
        for label, endpoint in (topo.get("peers") or {}).items():
            if endpoint:
                ep_map.setdefault(str(endpoint), label)
    else:
        links = _links_from_events(event_rows)
    for link in links:
        link["dst_label"] = ep_map.get(
            str(link.get("dst")), str(link.get("dst"))
        )
    return links


def _clique_groups(links):
    """(median rtt, clique candidate groups): peers whose pairwise RTT sits
    well under the swarm median are same-datacenter material. The detector
    itself was PROMOTED to shared library code
    (``dedloc_tpu/averaging/topology.clique_groups``) so this view and the
    runtime hierarchical planner can never disagree about what counts as a
    clique; this wrapper only binds the view's ``dst_label`` key."""
    from dedloc_tpu.averaging.topology import clique_groups

    return clique_groups(links, dst_key="dst_label")


def _topology_plan(links):
    """The two-level plan the runtime planner would build from this very
    link table (averaging/topology.plan_topology with the view's
    ``dst_label`` identity) — the operator preview of hierarchical
    averaging BEFORE enabling it (--averager.topology_plan)."""
    from dedloc_tpu.averaging.topology import plan_topology

    return plan_topology(links, dst_key="dst_label")


def _plan_assignment(plan):
    """{peer label: "c<i>" (+"*" for the clique's delegate)} — the ``plan``
    column of the links table, and the rendered plan section's rows."""
    assignment = {}
    for i, clique in enumerate(plan.cliques):
        for member in clique.members:
            tag = f"c{i}"
            if member == clique.delegate:
                tag += "*"
            assignment[member] = tag
    return assignment


def _fat_thin(links):
    """(per-peer mean inbound goodput, fat peers, thin peers): the
    degenerate-strategy signal (a few fat peers become de-facto parameter
    servers for thin client-mode volunteers)."""
    inbound = {}
    for l in links:
        if l.get("goodput_bps") is not None:
            inbound.setdefault(l["dst_label"], []).append(l["goodput_bps"])
    if len(inbound) < 2:
        return {}, [], []
    means = {p: sum(v) / len(v) for p, v in inbound.items()}
    ordered = sorted(means.values())
    median = ordered[len(ordered) // 2]
    fat = sorted(p for p, m in means.items() if m >= 2.0 * median)
    thin = sorted(p for p, m in means.items() if m <= 0.5 * median)
    return means, fat, thin


def topology_data(all_rows):
    """The --topology view as one JSON-able document."""
    links = _collect_topology(all_rows)
    if not links:
        sys.exit(
            "no link telemetry found (links appear after the first "
            "snapshot/close flush — is --telemetry.enabled set?)"
        )
    ranked = sorted(links, key=_link_sort_key)
    median_rtt, cliques = _clique_groups(links)
    _means, fat, thin = _fat_thin(links)
    worst = ranked[0]
    plan = _topology_plan(links)
    return {
        "view": "topology",
        "links": ranked,
        "worst_link": {"src": worst["src"], "dst": worst["dst_label"]},
        "median_rtt_s": median_rtt,
        "cliques": cliques,
        "fat_peers": fat,
        "thin_peers": thin,
        # the hierarchical plan the runtime planner would install from the
        # SAME folded table (averaging/topology.py) — preview before
        # enabling --averager.topology_plan
        "plan": plan.to_dict(),
    }


def print_topology(all_rows):
    links = _collect_topology(all_rows)
    if not links:
        sys.exit(
            "no link telemetry found (links appear after the first "
            "snapshot/close flush — is --telemetry.enabled set?)"
        )

    print("link matrix (src -> dst: rtt / goodput):")
    srcs = sorted({l["src"] for l in links})
    dsts = sorted({l["dst_label"] for l in links})
    by_pair = {(l["src"], l["dst_label"]): l for l in links}
    print("| src \\ dst | " + " | ".join(dsts) + " |")
    print("|---" * (len(dsts) + 1) + "|")
    for src in srcs:
        cells = []
        for dst in dsts:
            link = by_pair.get((src, dst))
            if link is None:
                cells.append("-")
            else:
                rtt = link.get("rtt_s")
                rtt_s = f"{rtt * 1e3:.1f}ms" if rtt is not None else "-"
                cells.append(f"{rtt_s} / {_fmt_rate(link.get('goodput_bps'))}")
        print(f"| {src} | " + " | ".join(cells) + " |")

    plan = _topology_plan(links)
    assignment = _plan_assignment(plan)

    print("\nlinks, worst first:")
    print("| src | dst | rtt | goodput | chunk p50 | chunk max | bytes |"
          " plan |")
    print("|---|---|---|---|---|---|---|---|")
    ranked = sorted(links, key=_link_sort_key)
    for link in ranked:
        rtt = link.get("rtt_s")
        print(
            f"| {link['src']} | {link['dst_label']} |"
            f" {f'{rtt * 1e3:.1f}ms' if rtt is not None else '-'} |"
            f" {_fmt_rate(link.get('goodput_bps'))} |"
            f" {link.get('chunk_p50_s', 0.0):.3f}s |"
            f" {link.get('chunk_max_s', 0.0):.3f}s |"
            f" {int(link.get('bytes', 0))} |"
            f" {assignment.get(link['src'], '-')} |"
        )
    worst = ranked[0]
    print(
        f"\nworst link: {worst['src']} -> {worst['dst_label']} "
        f"(goodput {_fmt_rate(worst.get('goodput_bps'))}, "
        f"chunk p50 {worst.get('chunk_p50_s', 0.0):.3f}s)"
    )

    median_rtt, groups = _clique_groups(links)
    if groups:
        print(
            "\nclique candidates (pairwise RTT <= 0.5x median "
            f"{median_rtt * 1e3:.1f}ms):"
        )
        for group in groups:
            print(f"  {group}")

    means, fat, thin = _fat_thin(links)
    if fat or thin:
        print("\nfat/thin peers (mean inbound-link goodput vs median):")
        for p in fat:
            print(f"  fat:  {p} ({_fmt_rate(means[p])})")
        for p in thin:
            print(f"  thin: {p} ({_fmt_rate(means[p])})")

    # the hierarchical plan the runtime planner (averaging/topology.py)
    # would install from this same table — what --averager.topology_plan
    # would actually run, previewed before enabling it
    print(f"\nhierarchical plan ({plan.mode}): {plan.reason}")
    if plan.mode == "hierarchical":
        print("| clique | delegate | members |")
        print("|---|---|---|")
        for i, clique in enumerate(plan.cliques):
            print(
                f"| c{i} | {clique.delegate} |"
                f" {', '.join(clique.members)} |"
            )


# ----------------------------------------------------------------- steps view
# (step-phase flight recorder: telemetry/steps.py. One step.record event per
# step carries {phases: {name: s}, untimed_s, samples, dur_s}; the
# coordinator's swarm_health.peers[].phases carries the folded means.)

_CANONICAL_PHASES = (
    "data_wait", "h2d", "fwd_bwd", "drain", "round_plan", "grad_flatten",
    "ef_norm", "avg_wire",
    "d2h_stream", "opt_apply", "backup_wait", "h2d_result", "backup_launch",
    "acc_reset", "collab", "post_step", "loss_sync", "publish", "log",
)


def _phase_order(names):
    """Canonical pipeline order first, then any extra phases alphabetically."""
    extra = sorted(n for n in names if n not in _CANONICAL_PHASES)
    return [n for n in _CANONICAL_PHASES if n in names] + extra


def _steps_from_events(rows):
    """{peer: {"steps": n, "wall": mean_s|None, "untimed": mean_s|None,
    "phases": {name: mean_s}, "holds": [...], "held_s": total}} from
    step.record events (``holds``: the records' hold entries, each with
    its record's ``step``; ``held_s``: the sum of ``held_excess_s``).
    Per-PEER fallback:
    a peer whose step.record rows were lost (truncated/jammed log — the
    churn these views debug) is rebuilt from its bare step.phase events
    (phase means only, no wall/untimed) instead of silently vanishing
    from the waterfall next to healthier peers."""
    per_peer = {}
    for r in rows:
        if r.get("event") != ev.STEP_RECORD:
            continue
        acc = per_peer.setdefault(
            r.get("peer", "?"),
            {"steps": 0, "wall": 0.0, "untimed": 0.0, "phases": {},
             "holds": [], "held_s": 0.0},
        )
        acc["steps"] += 1
        acc["wall"] += float(r.get("dur_s", 0.0))
        acc["untimed"] += float(r.get("untimed_s", 0.0))
        acc["held_s"] += float(r.get("held_excess_s") or 0.0)
        acc["holds"] += [
            {"step": r.get("step"), **h} for h in r.get("holds") or ()
            if isinstance(h, dict)
        ]
        phases = r.get("phases") or {}
        for name, dur in phases.items():
            try:
                acc["phases"][name] = (
                    acc["phases"].get(name, 0.0) + float(dur)
                )
            except (TypeError, ValueError):
                continue
        if r.get("mfu") is not None:
            acc["mfu"] = float(r["mfu"])  # latest online gauge wins
    for acc in per_peer.values():
        n = acc["steps"]
        acc["wall"] /= n
        acc["untimed"] /= n
        acc["phases"] = {k: v / n for k, v in acc["phases"].items()}
    # degraded peers: only per-phase events survive for them
    fallback, counts = {}, {}
    for r in rows:
        peer = r.get("peer", "?")
        if (
            r.get("event") != ev.STEP_PHASE or not r.get("phase")
            or peer in per_peer
        ):
            continue
        acc = fallback.setdefault(
            peer, {"steps": 0, "wall": None, "untimed": None, "phases": {}},
        )
        name = str(r["phase"])
        acc["phases"][name] = acc["phases"].get(name, 0.0) + float(
            r.get("dur_s", 0.0)
        )
        counts.setdefault(peer, {})
        counts[peer][name] = counts[peer].get(name, 0) + 1
    for peer, acc in fallback.items():
        acc["steps"] = max(counts[peer].values())
        acc["phases"] = {
            k: v / counts[peer][k] for k, v in acc["phases"].items()
        }
        per_peer[peer] = acc
    return per_peer


def _steps_from_health(all_rows):
    """Per-peer phase means from the NEWEST swarm_health record that
    carries any (coordinator metrics JSONL input)."""
    per_peer = {}
    for row in all_rows:
        health = row.get("swarm_health")
        if not isinstance(health, dict):
            continue
        found = {}
        for p in health.get("peers", []):
            phases = p.get("phases")
            if not isinstance(phases, dict) or not phases:
                continue
            entry = {
                "steps": None,
                "wall": (
                    p["step_time_ms"] / 1e3
                    if p.get("step_time_ms") is not None else None
                ),
                "untimed": None,
                "phases": {k: float(v) for k, v in phases.items()},
            }
            if p.get("mfu") is not None:
                entry["mfu"] = float(p["mfu"])
            if p.get("overlap_efficiency") is not None:
                entry["overlap_efficiency"] = float(p["overlap_efficiency"])
            found[p.get("peer", "?")] = entry
        if found:
            per_peer = found  # newest record wins
    return per_peer


def _phase_skews(per_peer):
    """[(ratio, phase, worst peer, worst s, median-of-others s)] most
    skewed first — the cross-peer "who is slow and WHY" ranking."""
    all_names = sorted({
        n for acc in per_peer.values() for n in acc["phases"]
    })
    skews = []
    for name in all_names:
        vals = {
            peer: acc["phases"][name]
            for peer, acc in per_peer.items() if name in acc["phases"]
        }
        if len(vals) < 2:
            continue
        worst_peer = max(vals, key=vals.get)
        worst = vals[worst_peer]
        if worst <= 0:
            continue
        # median of the OTHER peers: the worst offender must not drag
        # the reference point toward itself (with 2 peers an inclusive
        # median IS the worst value and every ratio reads 1.0x)
        rest = sorted(v for p, v in vals.items() if p != worst_peer)
        median = rest[len(rest) // 2]
        ratio = worst / median if median > 0 else float("inf")
        skews.append((ratio, name, worst_peer, worst, median))
    skews.sort(key=lambda s: -s[0])
    return skews


def _setup_records(rows):
    """The set-up records (``setup.record``: role entry -> end of the first
    global step, one a start) as the --steps view shows them ahead of the
    steps: per record its laps (top-level spans, by name, in the order they
    first closed), the first calls inside them and the compile sums."""
    out = []
    for r in rows:
        if r.get("event") != ev.SETUP_RECORD:
            continue
        laps, first_calls = {}, {}
        for span in r.get("spans") or []:
            seconds = float(span[5] if len(span) > 4 else span[3] - span[2])
            into = laps if span[1] is None else first_calls
            key = span[0] if span[1] is None else (span[1], span[0])
            into[key] = into.get(key, 0.0) + seconds
        out.append({
            "t": r.get("t"), "peer": r.get("peer", "?"),
            "total_s": float(r.get("dur_s", 0.0)),
            "complete": bool(r.get("complete")),
            "laps": laps,
            "first_calls": [
                {"lap": lap, "span": name, "s": round(seconds, 6)}
                for (lap, name), seconds in first_calls.items()
            ],
            **{
                key: r.get(key, 0)
                for key in ("trace_s", "lower_s", "backend_s", "programs")
            },
            "cache_hits": r.get("cache_hits", 0),
            "cache_misses": r.get("cache_misses", 0),
            "traces": r.get("traces") or {},
        })
    return sorted(out, key=lambda rec: (rec["peer"], rec["t"] or 0.0))


def _print_setup(records):
    print("set-up (role entry -> end of the first global step):")
    for rec in records:
        print(
            f"peer {rec['peer']}  total {rec['total_s']:.3f}s"
            + ("" if rec["complete"] else "  (no global step: incomplete)")
            + f"  trace {rec['trace_s']:.3f}s lower {rec['lower_s']:.3f}s "
            f"backend {rec['backend_s']:.3f}s over {rec['programs']} "
            f"programs (cache hits {rec['cache_hits']}, misses "
            f"{rec['cache_misses']})"
        )
        for name, seconds in rec["laps"].items():
            print(f"  {name:<22} {seconds:9.3f}s  "
                  f"{_bar(seconds, rec['total_s'])}")
            for call in rec["first_calls"]:
                if call["lap"] == name:
                    program = call["span"].removeprefix("first_call.")
                    traced = rec["traces"].get(program)
                    print(
                        f"    {call['span']:<36} {call['s']:9.3f}s"
                        + (f"  traced x{traced}" if traced is not None else "")
                    )
    print()


def steps_data(all_rows):
    """The --steps view as one JSON-able document."""
    event_rows = [r for r in all_rows if "event" in r]
    per_peer = _steps_from_events(event_rows)
    if not per_peer:
        per_peer = _steps_from_health(all_rows)
    if not per_peer:
        sys.exit(
            "no step-phase telemetry found (step.record events appear when "
            "--telemetry.enabled is set on a trainer; a coordinator metrics "
            "JSONL needs swarm_health.peers[].phases)"
        )
    ledgers = [
        r for r in event_rows if r.get("event") == ev.OPT_OVERLAP_LEDGER
    ]
    hidden = sum(float(r.get("hidden_s", 0.0)) for r in ledgers)
    exposed = sum(float(r.get("exposed_s", 0.0)) for r in ledgers)
    doc = {
        "view": "steps",
        "setup": _setup_records(event_rows),
        "per_peer": {
            peer: {
                **acc,
                "dominant": (
                    max(acc["phases"], key=acc["phases"].get)
                    if acc["phases"] else None
                ),
            }
            for peer, acc in per_peer.items()
        },
        "skew": [
            {"phase": name, "peer": peer,
             "ratio": None if ratio == float("inf") else round(ratio, 3),
             "worst_s": round(worst, 6), "median_s": round(median, 6)}
            for ratio, name, peer, worst, median in _phase_skews(per_peer)
        ],
        "overlap_ledger": [
            {k: r.get(k) for k in ("t", "peer", "round_id", "mode",
                                   "hidden_s", "exposed_s", "efficiency")}
            for r in sorted(ledgers, key=lambda r: r.get("t", 0.0))
        ],
    }
    if hidden + exposed > 0:
        doc["overall_overlap_efficiency"] = round(
            hidden / (hidden + exposed), 4
        )
    return doc


def _bar(value, full, width=24):
    if not full or full <= 0:
        return ""
    n = int(round(width * min(1.0, value / full)))
    return "#" * max(n, 1 if value > 0 else 0)


def print_steps(all_rows):
    event_rows = [r for r in all_rows if "event" in r]
    per_peer = _steps_from_events(event_rows)
    if not per_peer:
        per_peer = _steps_from_health(all_rows)
    if not per_peer:
        sys.exit(
            "no step-phase telemetry found (step.record events appear when "
            "--telemetry.enabled is set on a trainer; a coordinator metrics "
            "JSONL needs swarm_health.peers[].phases)"
        )

    setup = _setup_records(event_rows)
    if setup:
        _print_setup(setup)
    print("step-time waterfall (mean per step):")
    for peer in sorted(per_peer):
        acc = per_peer[peer]
        phases = acc["phases"]
        dominant = max(phases, key=phases.get) if phases else None
        total = sum(phases.values())
        wall = acc.get("wall")
        header = f"peer {peer}"
        if acc.get("steps"):
            header += f"  steps={acc['steps']}"
        if wall is not None:
            header += f"  wall {wall:.3f}s"
        if dominant is not None:
            share = phases[dominant] / (wall or total or 1.0)
            header += f"  dominant {dominant} ({share * 100.0:.0f}%)"
        if acc.get("mfu") is not None:
            header += f"  mfu {acc['mfu']:.3f}"
        if acc.get("holds") is not None:
            header += f"  holds={len(acc['holds'])}"
            if acc["holds"]:
                header += f" ({acc['held_s']:.3f}s over their usual)"
        print(header)
        full = wall if wall is not None else total
        for name in _phase_order(phases):
            print(f"  {name:<14} {phases[name]:9.3f}s  "
                  f"{_bar(phases[name], full)}")
        if acc.get("untimed") is not None and wall:
            covered = 100.0 * (wall - acc["untimed"]) / wall
            print(f"  {'(untimed)':<14} {acc['untimed']:9.3f}s  "
                  f"phase coverage {covered:.1f}% of wall")
        # the hold record: spans that overran, with what the operating
        # system said of the blocked thread (docs/observability.md)
        for hold in acc.get("holds") or ():
            state, wchan, syscall = (
                max(hold[key], key=hold[key].get) if hold.get(key) else "-"
                for key in ("state", "wchan", "syscall")
            )
            print(
                f"  held step {hold.get('step')}: {hold.get('span')} "
                f"{hold.get('held_s', 0.0):.3f}s (usual "
                f"{hold.get('usual_s', 0.0):.3f}) cpu "
                f"{hold.get('cpu_s', 0.0):.3f} state {state} wchan "
                f"{wchan} syscall {syscall}"
                + (f" busy {hold['busy_threads'][0][0]}"
                   if hold.get("busy_threads") else "")
                + (f" beside {','.join(b[0] for b in hold['beside'])}"
                   if hold.get("beside") else "")
            )

    # phase skew: for every phase, the peer furthest above the swarm median
    # — the cross-peer "who is slow and WHY" ranking (DeDLOC heterogeneous
    # volunteers: per-peer phase skew is the first-order signal)
    if len(per_peer) >= 2:
        skews = _phase_skews(per_peer)
        if skews:
            print("\nphase skew across peers (worst vs median, "
                  "most skewed first):")
            for ratio, name, peer, worst, median in skews:
                ratio_s = f"{ratio:.1f}x" if ratio != float("inf") else "inf"
                print(f"  {name:<14} {peer}: {worst:.3f}s vs median "
                      f"{median:.3f}s ({ratio_s})")

    # overlap ledger: hidden vs exposed averaging wall per boundary
    # (opt.overlap_ledger events; sync-fallback boundaries report
    # efficiency 0 — the round ran on the critical path)
    ledgers = [r for r in event_rows if r.get("event") == ev.OPT_OVERLAP_LEDGER]
    if ledgers:
        t0 = min(r.get("t", 0.0) for r in ledgers)
        print("\noverlap ledger (per boundary):")
        print("| t | peer | round | mode | hidden | exposed | efficiency |")
        print("|---|---|---|---|---|---|---|")
        for r in sorted(ledgers, key=lambda r: r.get("t", 0.0)):
            print(
                f"| +{r.get('t', 0.0) - t0:.2f}s | {r.get('peer', '?')} |"
                f" {r.get('round_id', '?')} | {r.get('mode', '?')} |"
                f" {r.get('hidden_s', 0.0):.3f}s |"
                f" {r.get('exposed_s', 0.0):.3f}s |"
                f" {r.get('efficiency', 0.0):.2f} |"
            )
        hidden = sum(float(r.get("hidden_s", 0.0)) for r in ledgers)
        exposed = sum(float(r.get("exposed_s", 0.0)) for r in ledgers)
        if hidden + exposed > 0:
            print(f"overall overlap efficiency: "
                  f"{hidden / (hidden + exposed):.2f} "
                  f"({hidden:.3f}s hidden / {exposed:.3f}s exposed)")
    else:
        effs = {
            peer: acc["overlap_efficiency"]
            for peer, acc in per_peer.items()
            if acc.get("overlap_efficiency") is not None
        }
        if effs:
            print("\noverlap efficiency (lifetime, per peer):")
            for peer in sorted(effs):
                print(f"  {peer}: {effs[peer]:.2f}")


# ------------------------------------------------------------- twin view
# (digital-twin fidelity: fit dedloc_tpu/twin from the logs, replay, and
# report predicted vs observed — imported lazily so every other view
# stays stdlib-only)


def twin_fidelity(all_rows, seed=0):
    import os

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from dedloc_tpu.twin.fit import fit_twin
    from dedloc_tpu.twin.replay import fidelity_report

    try:
        model = fit_twin(all_rows)
    except ValueError as e:
        sys.exit(f"cannot fit a twin from these logs: {e}")
    return model, fidelity_report(model, seed=seed)


def print_twin(all_rows, seed=0):
    model, fid = twin_fidelity(all_rows, seed=seed)
    for line in model.describe():
        print(line)
    workload = {k: v for k, v in model.workload.items() if v is not None}
    print(f"recorded workload: {json.dumps(workload, sort_keys=True)}")

    print("\ntwin fidelity (predicted vs observed):")
    print("| metric | observed | predicted | error |")
    print("|---|---|---|---|")
    for name, m in fid["metrics"].items():
        err = (
            f"{m['error'] * 100.0:+.1f}%" if m.get("error") is not None
            else "-"
        )
        obs = "-" if m["observed"] is None else f"{m['observed']:.4g}"
        pred = "-" if m["predicted"] is None else f"{m['predicted']:.4g}"
        print(f"| {name} | {obs} | {pred} | {err} |")

    per_peer = fid.get("per_peer") or {}
    if per_peer:
        print("\nper-peer round wall (observed vs predicted), "
              "worst error first:")
        print("| peer | observed | predicted | error |")
        print("|---|---|---|---|")
        ranked = sorted(
            per_peer.items(),
            key=lambda kv: -abs(kv[1].get("error") or 0.0),
        )
        for peer, m in ranked[:10]:
            err = (
                f"{m['error'] * 100.0:+.1f}%"
                if m.get("error") is not None else "-"
            )
            obs = m.get("observed_round_wall_s")
            pred = m.get("predicted_round_wall_s")
            print(
                f"| {peer} |"
                f" {'-' if obs is None else f'{obs:.4f}s'} |"
                f" {'-' if pred is None else f'{pred:.4f}s'} | {err} |"
            )

    worst = fid.get("worst_links") or {}
    if worst.get("observed") or worst.get("predicted"):
        print("\nworst-link ranking:")
        print(f"  observed : {worst.get('observed')}")
        print(f"  predicted: {worst.get('predicted')}")
        if "bottleneck_match" in worst:
            verdict = "MATCH" if worst["bottleneck_match"] else "MISMATCH"
            print(
                f"  bottleneck peer: observed "
                f"{worst.get('bottleneck_observed')} vs predicted "
                f"{worst.get('bottleneck_predicted')} — {verdict}"
            )
    bound = fid.get("sweep_error_bound")
    if bound is not None:
        print(
            f"\nsweep error bound: ±{bound * 100.0:.1f}% — predictions "
            "from tools/twin_sweep.py carry this confidence interval"
        )


# --------------------------------------------------------- incidents view
# (live-watchdog timeline: replay a coordinator metrics JSONL through the
# same SwarmWatch the coordinator runs inline, or render a recorded
# incident JSONL; imported lazily like the twin view)


def incidents_data(all_rows):
    """The --incidents view as one JSON-able document. Coordinator metrics
    JSONL input is REPLAYED (deterministic: identical to the live run);
    incident-JSONL input (the coordinator's own incident log) renders the
    recorded transitions, last state per incident winning."""
    has_health = any(
        isinstance(r.get("swarm_health"), dict) for r in all_rows
    )
    if has_health:
        _repo_on_path()
        from dedloc_tpu.telemetry.watch import watch_rows

        doc = watch_rows(all_rows).summary()
        doc["view"] = "incidents"
        doc["source"] = "replayed"
        return doc
    final = {}
    for r in all_rows:
        inc = r.get("incident")
        if r.get("watch") == "incident" and isinstance(inc, dict):
            final[inc.get("id", len(final))] = inc
    if not final:
        sys.exit(
            "no swarm_health records and no watchdog incident records "
            "found — feed a coordinator metrics JSONL or the "
            "coordinator's incident JSONL"
        )
    ordered = sorted(
        final.values(),
        key=lambda i: (i.get("status") != "open", i.get("opened_fold", 0)),
    )
    return {
        "view": "incidents",
        "source": "recorded",
        "incidents": ordered,
        "open": sum(1 for i in ordered if i.get("status") == "open"),
    }


def print_incidents(all_rows):
    doc = incidents_data(all_rows)
    import os

    # same-directory tool, loaded lazily; the explicit path keeps this
    # working when runlog_summary itself was loaded from a file location
    # (the test harness) rather than run as a script
    tools_dir = os.path.dirname(os.path.abspath(__file__))
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import swarm_watch as _sw

    verdict = doc.get("verdict") or {}
    if verdict:
        print(f"verdict: {verdict.get('status')} ({verdict.get('reason')})")
    folds = f" over {doc['folds']} fold(s)" if doc.get("folds") else ""
    print(
        f"incident timeline ({doc['source']}): {len(doc['incidents'])} "
        f"incident(s), {doc['open']} open{folds}"
    )
    for inc in doc["incidents"]:
        print(_sw.format_incident(inc))
    if doc["incidents"]:
        print(
            "\nfollow an incident: runlog_summary --trace <round> over the "
            "per-peer event logs resolves its representative trace; the "
            "runbook is docs/fleet.md \"when the watchdog fires\""
        )
    for note in (doc.get("coverage") or {}).get("notes", []):
        print(f"coverage note: {note}")


def contributions_data(all_rows):
    """The --contributions view as one JSON-able document: the volunteer
    leaderboard. Coordinator ledger JSONL input renders the RECORDED fold
    (rows with a ``ledger`` state; the last one wins — folds are
    cumulative); telemetry event-log input REBUILDS the fold from
    ``ledger.claim``/``ledger.receipt`` events through the SAME pydantic
    schemas and ``fold_ledger`` the coordinator runs. Both paths are
    deterministic for fixed inputs, so replaying a dumped ledger JSONL
    reproduces the leaderboard bit-identically."""
    _repo_on_path()
    from dedloc_tpu.telemetry.ledger import fold_ledger, leaderboard

    notes = []
    ledger = None
    source = "recorded"
    for r in all_rows:
        if isinstance(r.get("ledger"), dict):
            ledger = r["ledger"]  # last recorded fold wins (cumulative)
    if ledger is None:
        from dedloc_tpu.telemetry.ledger import (
            ContributionClaim,
            RoundReceipt,
        )

        # last event per peer wins: both record families are cumulative,
        # and a peer's ring buffer may have evicted its early events
        claims_raw, receipts_raw = {}, {}
        for r in all_rows:
            name = r.get("event")
            if name == ev.LEDGER_CLAIM and r.get("peer"):
                prev = claims_raw.get(r["peer"])
                if prev is None or (
                    float(r.get("t", 0.0)) >= float(prev.get("t", 0.0))
                ):
                    claims_raw[r["peer"]] = r
            elif name == ev.LEDGER_RECEIPT and r.get("signer"):
                prev = receipts_raw.get(r["signer"])
                if prev is None or (
                    float(r.get("t", 0.0)) >= float(prev.get("t", 0.0))
                ):
                    receipts_raw[r["signer"]] = r
        if not claims_raw and not receipts_raw:
            sys.exit(
                "no contribution-ledger records found — feed the "
                "coordinator's ledger JSONL (rows with a 'ledger' fold) "
                "or per-peer telemetry event logs carrying ledger.claim/"
                "ledger.receipt events. A pre-ledger swarm emits neither: "
                "upgrade the peers (or enable --optimizer ledger_claims) "
                "and re-collect."
            )
        claims, receipts, dropped = [], [], 0
        for r in claims_raw.values():
            try:
                claims.append(ContributionClaim.model_validate({
                    "peer": r.get("peer"),
                    "samples": r.get("samples"),
                    "rounds": r.get("rounds"),
                    "train_seconds": r.get("train_seconds"),
                    "bytes_served": r.get("bytes_served"),
                    "requests_served": r.get("requests_served") or 0,
                    "time": float(r.get("t", 0.0)),
                }))
            except Exception:  # noqa: BLE001 — malformed event row
                dropped += 1
        for r in receipts_raw.values():
            try:
                receipts.append(RoundReceipt.model_validate({
                    "signer": r.get("signer"),
                    "round_id": r.get("round_id"),
                    "step": r.get("step"),
                    "leg": r.get("leg"),
                    "members": r.get("members"),
                    "weights": r.get("weights"),
                    "witness": r.get("witness") or {},
                    "time": float(r.get("t", 0.0)),
                }))
            except Exception:  # noqa: BLE001 — malformed event row
                dropped += 1
        if dropped:
            notes.append(
                f"{dropped} malformed ledger event(s) dropped by schema "
                "re-validation"
            )
        if not claims and not receipts:
            sys.exit(
                "every collected ledger event failed schema validation — "
                "the logs are jammed or from an incompatible version"
            )
        # deterministic fold stamp: the newest record's time, never the
        # reader's wall clock (replay bit-identity is the contract)
        times = [c.time for c in claims] + [r.time for r in receipts]
        ledger = fold_ledger(
            None, claims, receipts, now=max(times) if times else 0.0
        )
        source = "replayed"
    board = leaderboard(ledger)
    pre = sum(1 for e in board if e.get("coverage") == "pre-ledger")
    if pre:
        notes.append(
            f"{pre} peer(s) predate receipts (no receipt exists anywhere) "
            "— credited as claimed, not checkable yet"
        )
    stale = sum(1 for e in board if e.get("coverage") == "stale")
    if stale:
        notes.append(
            f"{stale} peer(s) carry a stale entry (records expired since "
            "their last fold)"
        )
    return {
        "view": "contributions",
        "source": source,
        "t": ledger.get("t"),
        "slack": ledger.get("slack"),
        "claims": ledger.get("claims"),
        "receipt_signers": ledger.get("receipt_signers"),
        "total_credited_samples": ledger.get("total_credited_samples"),
        "discrepancies": ledger.get("discrepancies"),
        "leaderboard": board,
        "notes": notes,
    }


def _fmt_bytes_served(n):
    n = float(n or 0)
    for unit in ("B", "KB", "MB", "GB"):
        if n < 1024 or unit == "GB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024


def print_contributions(all_rows):
    doc = contributions_data(all_rows)
    slack = doc.get("slack")
    print(
        f"volunteer leaderboard ({doc['source']}): "
        f"{len(doc['leaderboard'])} peer(s), "
        f"{doc['discrepancies']} discrepancy(ies)"
        + (f", over-claim slack x{slack}" if slack is not None else "")
    )
    print(
        f"{'#':>3} {'peer':<14} {'credited':>10} {'claimed':>10} "
        f"{'share':>6} {'rounds':>6} {'served':>9} {'reqs':>6}  coverage"
    )
    for i, e in enumerate(doc["leaderboard"], 1):
        peer = str(e.get("peer") or "?")
        short = peer[:12] + ".." if len(peer) > 14 else peer
        disc = e.get("discrepancy") or {}
        flag = ""
        if disc:
            flag = f"  !! {disc.get('kind', 'discrepancy').upper()}"
            if disc.get("ratio"):
                flag += f" x{disc['ratio']}"
        print(
            f"{i:>3} {short:<14} {e['credited_samples']:>10} "
            f"{e['claimed_samples']:>10} "
            f"{e['share'] * 100:>5.1f}% {e['credited_rounds']:>6} "
            f"{_fmt_bytes_served(e['bytes_served']):>9} "
            f"{e.get('requests_served') or 0:>6}  "
            f"{e.get('coverage') or '?'}{flag}"
        )
    if doc["discrepancies"]:
        print(
            "\ndiscrepancies: credited = min(claimed, receipt-supported x "
            "slack) — the runbook is docs/fleet.md \"reading the "
            "leaderboard\""
        )
    for note in doc["notes"]:
        print(f"coverage note: {note}")


def trainlog_data(rows, requested):
    """The default (train_log) view as one JSON-able document."""
    by_step = {r["step"]: r for r in rows}
    t0 = rows[0]["wall_s"] - rows[0].get("step_wall_s", 0.0)
    doc = {
        "view": "train_log",
        "steps": [
            {
                "step": s,
                "wall_min": round((by_step[s]["wall_s"] - t0) / 60, 3),
                "loss": by_step[s]["loss"],
            }
            for s in pick_steps(rows, requested)
        ],
        "phase_percentiles_ms": {},
        "total_steps": rows[-1]["step"],
        "total_wall_min": round((rows[-1]["wall_s"] - t0) / 60, 2),
    }
    for key in ("boundary_ms", "data_wait_ms", "allreduce_ms", "seam_ms"):
        vals = [r[key] for r in rows[5:] if key in r]
        if vals and isinstance(vals[0], dict):  # seam_ms: per-phase subkeys
            for sub in sorted({sub for v in vals for sub in v}):
                p50, p90, p99 = percentiles(
                    [v[sub] for v in vals if sub in v]
                )
                doc["phase_percentiles_ms"][f"{key}.{sub}"] = [p50, p90, p99]
            continue
        if vals:
            doc["phase_percentiles_ms"][key] = list(percentiles(vals))
    return doc


def main(argv):
    # --json anywhere switches any view to its machine-readable document
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]

    def emit(doc):
        print(json.dumps(doc, indent=1, default=str))

    if argv and argv[0] == "--health":
        if not argv[1:]:
            sys.exit("usage: runlog_summary.py --health events.jsonl [...]")
        rows = load_events(argv[1:])
        emit(health_data(rows)) if as_json else print_health(rows)
        return
    if argv and argv[0] == "--trace":
        if len(argv) < 3:
            sys.exit(
                "usage: runlog_summary.py --trace ROUND_ID events.jsonl [...]"
            )
        rows = load_events(argv[2:])
        if as_json:
            emit(trace_data(rows, argv[1]))
        else:
            print_trace(rows, argv[1])
        return
    if argv and argv[0] == "--topology":
        if not argv[1:]:
            sys.exit("usage: runlog_summary.py --topology events.jsonl [...]")
        rows = load_jsonl_rows(argv[1:])
        emit(topology_data(rows)) if as_json else print_topology(rows)
        return
    if argv and argv[0] == "--steps":
        if not argv[1:]:
            sys.exit("usage: runlog_summary.py --steps events.jsonl [...]")
        rows = load_jsonl_rows(argv[1:])
        emit(steps_data(rows)) if as_json else print_steps(rows)
        return
    if argv and argv[0] == "--twin":
        if not argv[1:]:
            sys.exit("usage: runlog_summary.py --twin events.jsonl [...]")
        rows = load_jsonl_rows(argv[1:])
        if as_json:
            _model, fid = twin_fidelity(rows)
            emit(fid)
        else:
            print_twin(rows)
        return
    if argv and argv[0] == "--incidents":
        if not argv[1:]:
            sys.exit(
                "usage: runlog_summary.py --incidents "
                "coordinator_metrics.jsonl [...]"
            )
        rows = load_jsonl_rows(argv[1:])
        emit(incidents_data(rows)) if as_json else print_incidents(rows)
        return
    if argv and argv[0] == "--contributions":
        if not argv[1:]:
            sys.exit(
                "usage: runlog_summary.py --contributions "
                "coordinator_ledger.jsonl | events.jsonl [...]"
            )
        rows = load_jsonl_rows(argv[1:])
        if as_json:
            emit(contributions_data(rows))
        else:
            print_contributions(rows)
        return
    rows = load(argv[0])
    if not rows:
        sys.exit(f"{argv[0]}: no log rows")
    requested = [int(a) for a in argv[1:]]
    # text and --json render from the SAME collector (like every other
    # view): two copies of the warmup-skip / percentile logic would drift
    doc = trainlog_data(rows, requested)
    if as_json:
        emit(doc)
        return
    print("| global step | wall (min) | train loss |")
    print("|---|---|---|")
    for entry in doc["steps"]:
        print(f"| {entry['step']} | {entry['wall_min']:.1f} |"
              f" {entry['loss']:.3f} |")
    for key, (p50, p90, p99) in doc["phase_percentiles_ms"].items():
        print(f"{key}: p50/p90/p99 = {p50:.0f}/{p90:.0f}/{p99:.0f} ms")
    print(f"total: {doc['total_steps']} global steps in "
          f"{doc['total_wall_min']:.0f} min wall")


if __name__ == "__main__":
    main(sys.argv[1:])
