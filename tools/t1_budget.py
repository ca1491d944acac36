"""Tier-1 timing budget: where the suite's test-seconds are, against the cap.

The driver runs tier-1 under ``timeout 1470`` with six xdist workers and
``--dist loadfile`` and leaves a junit file (``/tmp/_t1.xml``); a run cut at
the cap counts only as far as it got. This tool turns that file into the
table a test-repair needs: per-file sums (``loadfile`` spreads FILES, so the
heaviest file is a floor under the wall), the sum over workers divided by
their number against the wall the run took (what imbalance costs), the wall
against the cap, and the slowest tests.

Usage::

    # after the driver's command (ROADMAP.md "Tier-1 verify"):
    python tools/t1_budget.py /tmp/_t1.xml
    python tools/t1_budget.py --cap 1470 --top 25 /tmp/_t1.xml

    # CI gate: exit nonzero when a baselined test regressed >25%
    python tools/t1_budget.py --gate tools/t1_baseline.json /tmp/_t1.xml
    # refresh the baseline from a trusted idle-box run
    python tools/t1_budget.py --record-baseline tools/t1_baseline.json /tmp/_t1.xml

Only stdlib, no pytest plugin; the junit file is the one input (stdin when
no file is given).

``--gate`` compares each test named in the baseline JSON (``{"test id":
seconds}``) against the measured total and exits nonzero when any
regressed more than ``--gate-tolerance`` (default 0.25 = +25%) beyond a
small absolute slack (``--gate-slack``, default 1s — sub-second tests jitter
by whole multiples on a loaded box). Tests in the baseline but absent from
the file are reported as warnings, not failures (a deselected or renamed
test must not wedge CI, but it must not vanish silently either).
"""
from __future__ import annotations

import argparse
import json
import sys
import xml.etree.ElementTree as ET
from collections import defaultdict
from typing import Dict, List, Tuple

TIER1_CAP_S = 1470.0  # the driver's ``timeout``
TIER1_WORKERS = 6  # its ``-n``

def parse_junit(text: str) -> Tuple[List[Tuple[str, float]], float]:
    """((test id, seconds) rows, the run's wall seconds) from a pytest junit
    file: a testcase's ``time`` is its setup + call + teardown, the
    testsuite's the whole run's."""
    root = ET.fromstring(text)
    suites = [root] if root.tag == "testsuite" else root.findall("testsuite")
    rows = [
        (
            f"{case.get('classname', '').replace('.', '/')}.py"
            f"::{case.get('name')}",
            float(case.get("time") or 0.0),
        )
        for suite in suites for case in suite.iter("testcase")
    ]
    return rows, sum(float(suite.get("time") or 0.0) for suite in suites)


def aggregate(rows) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Seconds per test and per file."""
    per_test: Dict[str, float] = defaultdict(float)
    per_file: Dict[str, float] = defaultdict(float)
    for test_id, seconds in rows:
        per_test[test_id] += seconds
        per_file[test_id.split("::", 1)[0]] += seconds
    return dict(per_test), dict(per_file)


def report(
    rows, cap: float = TIER1_CAP_S, top: int = 20,
    slow_threshold: float = 10.0, wall: float = 0.0,
) -> str:
    if not rows:
        return "no testcase in the junit file"
    per_test, per_file = aggregate(rows)
    total = sum(seconds for _t, seconds in rows)
    out = []
    heaviest, heaviest_s = max(per_file.items(), key=lambda kv: kv[1])
    balanced = total / TIER1_WORKERS
    out.append(
        f"test-seconds: {total:.0f}s in {len(per_test)} tests; over "
        f"{TIER1_WORKERS} workers {balanced:.0f}s at perfect balance; heaviest "
        f"file {heaviest} {heaviest_s:.0f}s"
    )
    out.append(f"wall {wall:.0f}s: imbalance costs {wall - balanced:.0f}s")
    out.append(f"{wall:.0f}s of the tier-1 cap {cap:.0f}s "
               f"({wall / cap * 100:.0f}% of budget)")
    if wall > cap:
        out.append(
            f"OVER BUDGET by {wall - cap:.0f}s — the cap kills the run "
            "before the suite finishes; slow-mark or split the offenders"
        )
    out.append("")
    out.append(f"top {top} tests:")
    out.append("| test | total s | % of cap |")
    out.append("|---|---|---|")
    ranked = sorted(per_test.items(), key=lambda kv: -kv[1])[:top]
    for test_id, seconds in ranked:
        out.append(f"| {test_id} | {seconds:.1f} | {seconds / cap * 100:.1f}% |")
    out.append("")
    out.append("per-file totals:")
    out.append("| file | total s |")
    out.append("|---|---|")
    for path, seconds in sorted(per_file.items(), key=lambda kv: -kv[1]):
        out.append(f"| {path} | {seconds:.1f} |")
    candidates = [
        test_id for test_id, seconds in per_test.items()
        if seconds >= slow_threshold
    ]
    if candidates:
        out.append("")
        out.append(
            f"slow-mark candidates (>= {slow_threshold:.0f}s; verify each is "
            "an integration scenario with a cheap tier-1 sibling first):"
        )
        for test_id in sorted(candidates, key=lambda t: -per_test[t]):
            out.append(f"  {test_id}  ({per_test[test_id]:.1f}s)")
    return "\n".join(out)


def gate(
    rows,
    baseline: Dict[str, float],
    tolerance: float = 0.25,
    slack_s: float = 1.0,
) -> Tuple[str, int]:
    """Compare measured per-test totals against a recorded baseline.

    Returns (report text, exit code): 0 when every baselined test that ran
    stayed within ``baseline * (1 + tolerance) + slack_s``, 1 when any
    regressed past it. Tests missing from the file only warn — but they DO
    warn, so a silent rename/deselection stays visible."""
    per_test, _per_file = aggregate(rows)
    out: List[str] = []
    regressed: List[Tuple[str, float, float]] = []
    missing: List[str] = []
    for test_id, base_s in sorted(baseline.items()):
        measured = per_test.get(test_id)
        if measured is None:
            missing.append(test_id)
            continue
        limit = float(base_s) * (1.0 + tolerance) + slack_s
        if measured > limit:
            regressed.append((test_id, float(base_s), measured))
        else:
            out.append(
                f"ok: {test_id}  {measured:.1f}s (baseline {base_s:.1f}s, "
                f"limit {limit:.1f}s)"
            )
    for test_id in missing:
        out.append(
            f"warning: baselined test not in this run (deselected or "
            f"renamed?): {test_id}"
        )
    if regressed:
        out.append("")
        out.append(
            f"GATE FAILED: {len(regressed)} test(s) regressed more than "
            f"{tolerance * 100:.0f}% (+{slack_s:.1f}s slack) vs baseline — "
            "the suite's clock must not silently worsen:"
        )
        for test_id, base_s, measured in regressed:
            # a 0.0 baseline (legal JSON, and what rounding a sub-5ms test
            # would produce) must fail with a report, not a ZeroDivisionError
            ratio = (
                f"{measured / base_s:.2f}x" if base_s > 0 else "baseline 0"
            )
            out.append(
                f"  {test_id}: {measured:.1f}s vs baseline {base_s:.1f}s "
                f"({ratio})"
            )
        return "\n".join(out), 1
    out.append("")
    out.append(
        f"gate passed: "
        f"{len(baseline) - len(missing)}"
        f"/{len(baseline)} baselined tests within budget"
    )
    return "\n".join(out), 0


def record_baseline(rows, tests: List[str]) -> Dict[str, float]:
    """Measured totals for ``tests`` (all parsed tests when empty) — the
    JSON written back as the next baseline. Values floor at 0.01s so a
    recorded baseline can never round to the 0.0 the gate treats as an
    unconditional (slack-only) budget."""
    per_test, _ = aggregate(rows)
    if tests:
        picked = {t: per_test[t] for t in tests if t in per_test}
    else:
        picked = per_test
    return {
        t: max(0.01, round(s, 2)) for t, s in sorted(picked.items())
    }


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("junit", nargs="?",
                        help="the run's junit file (default: stdin)")
    parser.add_argument("--cap", type=float, default=TIER1_CAP_S,
                        help="tier-1 wall cap in seconds (the driver's "
                             "timeout: 1470)")
    parser.add_argument("--top", type=int, default=20)
    parser.add_argument("--slow-threshold", type=float, default=10.0,
                        help="per-test seconds above which to suggest a "
                             "slow mark")
    parser.add_argument("--gate", metavar="BASELINE_JSON",
                        help="compare against a recorded baseline and exit "
                             "nonzero on a >tolerance regression")
    parser.add_argument("--gate-tolerance", type=float, default=0.25,
                        help="fractional regression allowed vs baseline "
                             "(0.25 = +25%%)")
    parser.add_argument("--gate-slack", type=float, default=1.0,
                        help="absolute seconds of slack on top of the "
                             "tolerance (sub-second tests jitter in whole "
                             "multiples)")
    parser.add_argument("--record-baseline", metavar="BASELINE_JSON",
                        help="re-record measured totals into this JSON and "
                             "exit: an existing file keeps its curated test "
                             "set (values refreshed only), a new file "
                             "records every parsed test")
    args = parser.parse_args(argv)
    if args.junit:
        with open(args.junit, encoding="utf-8", errors="replace") as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    rows, wall = parse_junit(text)
    if args.record_baseline:
        # refreshing an EXISTING baseline re-records only the tests it
        # already curates — a whole run's file must not replace a
        # hand-picked gate set with hundreds of entries. A new file records
        # everything (the bootstrap case).
        curated: List[str] = []
        try:
            with open(args.record_baseline, encoding="utf-8") as f:
                curated = list(json.load(f))
        except (OSError, ValueError):
            pass
        baseline = record_baseline(rows, curated)
        with open(args.record_baseline, "w", encoding="utf-8") as f:
            json.dump(baseline, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"recorded {len(baseline)} test durations to "
              f"{args.record_baseline}")
        return
    if args.gate:
        with open(args.gate, encoding="utf-8") as f:
            baseline = json.load(f)
        text, code = gate(
            rows, baseline, tolerance=args.gate_tolerance,
            slack_s=args.gate_slack,
        )
        print(text)
        sys.exit(code)
    print(report(rows, cap=args.cap, top=args.top,
                 slow_threshold=args.slow_threshold, wall=wall))


if __name__ == "__main__":
    main()
