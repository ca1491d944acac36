"""Bench regression gate: fail CI when a fresh bench run regresses the
recorded perf trajectory.

A trajectory of driver records (``BENCH_r*.json`` and its siblings below)
is only a contract if something checks it — a PR that silently cost 5%
throughput would otherwise surface when a human diffed the JSONs. This tool
machine-guards it, mirroring ``tools/t1_budget.py --gate``. (The single-chip
``BENCH_r01``–``r06`` records were removed in PR 21: they were taken on an
installation that is gone. Until new records exist that metric gates as the
bootstrap case; the chip record is ``PERF_LEDGER.jsonl``.)

    # gate a fresh bench JSON against the committed trajectory
    python bench.py > /tmp/fresh.txt   # or any file holding the JSON line
    python tools/bench_gate.py /tmp/fresh.json
    # explicit baselines + custom tolerance
    python tools/bench_gate.py --tolerance 0.05 fresh.json BENCH_r04.json ...

Exit code 0 when the fresh run's ``value`` (samples/sec) and ``mfu`` (when
both sides have one) are within ``--tolerance`` (default 0.03 = −3%) of the
BEST comparable baseline round; 1 on a regression. Robustness contract,
same spirit as the t1 gate:

- baseline rounds are filtered to the fresh run's ``metric`` name — a
  distributed-path bench never gates against the single-chip headline;
- a missing round (sparse glob, pruned file) is simply absent from the
  baseline set, never an error;
- a malformed baseline JSON warns on stderr and is skipped — a corrupt
  artifact must not wedge the gate (a malformed FRESH file fails: that is
  the thing under test);
- no comparable baseline at all warns and exits 0 (nothing to gate
  against — the bootstrap case for a brand-new metric).

Accepted file shapes: a driver record (``{"n": 5, "parsed": {...}}``,
the BENCH_r*.json layout), the bare bench line (``{"metric": ...,
"value": ...}``), a file whose last ``{``-prefixed line is that bench
line (raw ``python bench.py`` output), or a MULTICHIP driver record
(``{"n_devices": 8, "ok": true, "tail": "...log..."}``): the swarm
throughput is derived from the tail's timestamped ``global step N applied
(group=G, samples~S)`` optimizer lines, under the metric name
``multichip<n>_swarm_samples_per_sec`` so different device counts never
gate against each other. MULTICHIP rounds whose tail carries no applied
steps (an early driver that captured only the jax banner) are simply
absent from the baseline set — the same missing-round rule as a sparse
glob.

The simulator-engine trajectory (SIMBENCH_r*.json, DEDLOC_BENCH=sim_engine)
rides the same machinery: it uses the BENCH_r*.json driver layout, its
headline ``sim_mixed<peers>_timer_events_per_wall_sec`` is higher-is-better
like every other gated metric, and the roster size in the metric name keeps
CI smokes (DEDLOC_BENCH_TINY=1, 100 peers) from gating against full runs.
Gate sim records with ``--tolerance 0.15`` — single-core wall variance is
far wider than a TPU's (SIMBENCH_r01.json note).
"""
from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE_GLOB = os.path.join(REPO_ROOT, "BENCH_r*.json")
MULTICHIP_BASELINE_GLOB = os.path.join(REPO_ROOT, "MULTICHIP_r*.json")
# the simulator-engine trajectory (DEDLOC_BENCH=sim_engine): same driver
# layout as BENCH_r*.json, gated on the events/sec headline. Single-core
# wall variance is ~±15%, so gate sim metrics with --tolerance 0.15
# (SIMBENCH_r01.json note) rather than the TPU default.
SIMBENCH_BASELINE_GLOB = os.path.join(REPO_ROOT, "SIMBENCH_r*.json")
# the serving-plane trajectory (DEDLOC_BENCH=serving): requests resolved
# per wall second through the 1,000-peer serving scenario. Same driver
# layout, same single-core wall-variance caveat as SIMBENCH — gate with
# --tolerance 0.15.
SERVEBENCH_BASELINE_GLOB = os.path.join(REPO_ROOT, "SERVEBENCH_r*.json")

# "[2026-08-01 21:43:54.504][INFO][dedloc_tpu.collaborative.optimizer]
#  global step 189 applied (group=1, samples~48)"
_APPLIED_RE = re.compile(
    r"\[(\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}\.\d{3})\]"
    r".*global step (\d+) applied \(group=(\d+), samples~(\d+)\)"
)


def parse_multichip(record: Dict, path: str) -> Optional[Dict]:
    """Synthesize a bench record from a MULTICHIP driver record's log
    tail, or None (with a stderr warning) when the round is not gateable:
    failed/skipped runs, and tails without at least two applied-step lines
    (no rate is derivable from a single timestamp)."""
    if not record.get("ok") or record.get("skipped") or record.get("rc"):
        print(f"warning: skipping {path}: multichip round not ok/complete",
              file=sys.stderr)
        return None
    matches = _APPLIED_RE.findall(str(record.get("tail", "")))
    if len(matches) < 2:
        print(
            f"warning: skipping {path}: multichip tail has "
            f"{len(matches)} applied-step line(s); need >= 2 for a rate",
            file=sys.stderr,
        )
        return None

    def stamp(raw: str) -> datetime.datetime:
        return datetime.datetime.strptime(raw, "%Y-%m-%d %H:%M:%S.%f")

    t_first = stamp(matches[0][0])
    t_last = stamp(matches[-1][0])
    span = (t_last - t_first).total_seconds()
    if span <= 0:
        print(f"warning: skipping {path}: applied-step timestamps do not "
              "advance", file=sys.stderr)
        return None
    # samples attributed to the interval: everything AFTER the first
    # applied line (the first stamp opens the measurement window)
    samples = sum(int(s) for _t, _step, _g, s in matches[1:])
    n_devices = int(record.get("n_devices", 0))
    return {
        "metric": f"multichip{n_devices}_swarm_samples_per_sec",
        "value": round(samples / span, 3),
        "unit": "samples/sec",
        "steps": len(matches),
        "n_devices": n_devices,
    }


def load_bench(path: str) -> Optional[Dict]:
    """The bench record in ``path``, or None (with a stderr warning) when
    the file is unreadable/malformed — see the robustness contract above."""
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
    except OSError as e:
        print(f"warning: skipping {path}: {e}", file=sys.stderr)
        return None
    record = None
    try:
        record = json.loads(text)
    except ValueError:
        # raw bench stdout: the bench contract is ONE {-prefixed JSON line
        # (test_bench_contract.py); take the last one so warmup noise and
        # jax warnings above it don't matter
        for line in reversed(text.strip().splitlines()):
            if line.startswith("{"):
                try:
                    record = json.loads(line)
                except ValueError:
                    pass
                break
    if isinstance(record, dict) and isinstance(record.get("parsed"), dict):
        record = record["parsed"]  # BENCH_r*.json driver layout
    if (
        isinstance(record, dict)
        and "metric" not in record
        and "tail" in record
        and "n_devices" in record
    ):
        return parse_multichip(record, path)  # MULTICHIP_r*.json layout
    if (
        not isinstance(record, dict)
        or "metric" not in record
        or not isinstance(record.get("value"), (int, float))
    ):
        print(f"warning: skipping {path}: not a bench record", file=sys.stderr)
        return None
    return record


def best_baseline(
    records: List[Dict], metric: str
) -> Tuple[Optional[float], Optional[float]]:
    """(best value, best mfu) over the comparable baseline rounds."""
    values = [
        float(r["value"]) for r in records if r.get("metric") == metric
    ]
    mfus = [
        float(r["mfu"]) for r in records
        if r.get("metric") == metric
        and isinstance(r.get("mfu"), (int, float))
    ]
    return (max(values) if values else None, max(mfus) if mfus else None)


def gate(
    fresh: Dict, baselines: List[Dict], tolerance: float = 0.03
) -> Tuple[str, int]:
    """(report text, exit code): 0 within tolerance, 1 on regression."""
    out: List[str] = []
    metric = fresh.get("metric", "?")
    base_value, base_mfu = best_baseline(baselines, metric)
    if base_value is None:
        out.append(
            f"warning: no comparable baseline for metric {metric!r} — "
            "nothing to gate against (bootstrap case)"
        )
        return "\n".join(out), 0
    failures: List[str] = []
    value = float(fresh["value"])
    floor = base_value * (1.0 - tolerance)
    if value < floor:
        failures.append(
            f"samples/sec regressed: {value:.3f} vs best baseline "
            f"{base_value:.3f} (floor {floor:.3f}, "
            f"{(1.0 - value / base_value) * 100.0:.1f}% drop)"
        )
    else:
        out.append(
            f"ok: value {value:.3f} vs best baseline {base_value:.3f} "
            f"(floor {floor:.3f})"
        )
    mfu = fresh.get("mfu")
    if isinstance(mfu, (int, float)) and base_mfu is not None:
        mfu_floor = base_mfu * (1.0 - tolerance)
        if float(mfu) < mfu_floor:
            failures.append(
                f"MFU regressed: {float(mfu):.4f} vs best baseline "
                f"{base_mfu:.4f} (floor {mfu_floor:.4f})"
            )
        else:
            out.append(
                f"ok: mfu {float(mfu):.4f} vs best baseline {base_mfu:.4f} "
                f"(floor {mfu_floor:.4f})"
            )
    elif base_mfu is not None:
        # CPU smoke runs have no MFU block — the value check still gates
        out.append("note: fresh record has no mfu field; MFU not gated")
    if failures:
        out.append("")
        out.append(
            f"GATE FAILED: the perf trajectory must not silently regress "
            f"more than {tolerance * 100.0:.0f}% (ROADMAP item 4):"
        )
        out.extend(f"  {f}" for f in failures)
        return "\n".join(out), 1
    out.append("gate passed")
    return "\n".join(out), 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
    )
    parser.add_argument(
        "fresh", help="fresh bench JSON (or raw bench stdout) to gate"
    )
    parser.add_argument(
        "baselines", nargs="*",
        help=f"baseline bench JSONs (default: {DEFAULT_BASELINE_GLOB} "
             f"+ {MULTICHIP_BASELINE_GLOB} + {SIMBENCH_BASELINE_GLOB} "
             f"+ {SERVEBENCH_BASELINE_GLOB})",
    )
    parser.add_argument(
        "--tolerance", type=float, default=0.03,
        help="fractional regression allowed vs the best baseline "
             "(0.03 = -3%%)",
    )
    args = parser.parse_args(argv)
    fresh = load_bench(args.fresh)
    if fresh is None:
        print(f"error: fresh bench file {args.fresh} is not a bench record",
              file=sys.stderr)
        return 2
    # all three trajectories ride the default baseline set: the fresh
    # record's metric name filters out the incomparable ones
    paths = args.baselines or sorted(
        glob.glob(DEFAULT_BASELINE_GLOB)
        + glob.glob(MULTICHIP_BASELINE_GLOB)
        + glob.glob(SIMBENCH_BASELINE_GLOB)
        + glob.glob(SERVEBENCH_BASELINE_GLOB)
    )
    baselines = [r for r in (load_bench(p) for p in paths) if r is not None]
    text, code = gate(fresh, baselines, tolerance=args.tolerance)
    print(text)
    return code


if __name__ == "__main__":
    sys.exit(main())
