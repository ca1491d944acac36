"""tools/t1_budget.py: the junit file the tier-1 command leaves, as per-file
sums, the balance over the six workers and the wall against the cap, and the
``--gate`` over a recorded baseline."""
import importlib.util
from pathlib import Path

spec = importlib.util.spec_from_file_location(
    "t1_budget",
    Path(__file__).resolve().parent.parent / "tools" / "t1_budget.py",
)
t1_budget = importlib.util.module_from_spec(spec)
spec.loader.exec_module(t1_budget)



def _junit(cases, wall):
    """A junit file as pytest writes it: (file stem, test name, seconds)."""
    body = "".join(
        f'<testcase classname="tests.{stem}" name="{name}" time="{seconds}" />'
        for stem, name, seconds in cases
    )
    return (
        '<?xml version="1.0" encoding="utf-8"?><testsuites><testsuite '
        f'name="pytest" errors="0" failures="0" skipped="0" '
        f'tests="{len(cases)}" time="{wall}">{body}</testsuite></testsuites>'
    )


_RUN = _junit(
    [("test_scale", "test_32_peers", 120.5),
     ("test_faults", "test_leader_death", 12.3),
     ("test_faults", "test_follower_death", 0.2),
     ("test_core", "test_quick", 3.0)],
    wall=140.0,
)
_ROWS, _WALL = t1_budget.parse_junit(_RUN)


def test_parse_and_aggregate():
    assert len(_ROWS) == 4 and _WALL == 140.0
    per_test, per_file = t1_budget.aggregate(_ROWS)
    assert per_test["tests/test_faults.py::test_leader_death"] == 12.3
    assert per_file["tests/test_faults.py"] == 12.5
    assert per_file["tests/test_scale.py"] == 120.5


def test_report_ranks_and_flags_slow_candidates():
    report = t1_budget.report(
        _ROWS, cap=100.0, top=2, slow_threshold=10.0, wall=_WALL
    )
    assert "OVER BUDGET by 40s" in report  # a wall of 140 s against 100
    lines = report.splitlines()
    table = [l for l in lines if l.startswith("| tests/")]
    assert "test_32_peers" in table[0]  # ranked worst-first
    assert "slow-mark candidates" in report
    assert "test_quick" not in report.split("slow-mark candidates")[1]


def test_report_of_an_empty_run_says_so():
    assert "no testcase" in t1_budget.report([])


def test_the_drivers_junit_file_gives_files_balance_and_wall(tmp_path, capsys):
    """What the driver's command leaves: per-file sums, the heaviest file,
    the sum over workers against the wall, the wall against the cap."""
    text = _junit(
        [("test_a", "test_x[whole]", 60.0), ("test_a", "test_y", 30.0),
         ("test_b", "test_z", 390.0)],
        wall=100.0,
    )
    rows, wall = t1_budget.parse_junit(text)
    assert wall == 100.0 and len(rows) == 3
    per_test, per_file = t1_budget.aggregate(rows)
    assert per_test["tests/test_a.py::test_x[whole]"] == 60.0
    assert per_file == {"tests/test_a.py": 90.0, "tests/test_b.py": 390.0}
    path = tmp_path / "_t1.xml"
    path.write_text(text)
    t1_budget.main(["--cap", "90", str(path)])
    text = capsys.readouterr().out
    assert "test-seconds: 480s in 3 tests" in text
    assert "over 6 workers 80s at perfect balance" in text
    assert "heaviest file tests/test_b.py 390s" in text
    assert "wall 100s: imbalance costs 20s" in text
    assert "OVER BUDGET by 10s" in text


# ------------------------------------------------- --gate regression mode


def test_gate_passes_within_tolerance_and_fails_on_regression():
    # measured: test_leader_death = 12.3s, test_quick = 3.0s
    ok_baseline = {
        "tests/test_faults.py::test_leader_death": 11.0,  # +12% < 25%
        "tests/test_core.py::test_quick": 3.0,
    }
    text, code = t1_budget.gate(_ROWS, ok_baseline, tolerance=0.25)
    assert code == 0
    assert "gate passed: 2/2" in text

    # 12.3s vs 6.0s baseline = 2.05x — over 25% + 1s slack
    bad_baseline = {"tests/test_faults.py::test_leader_death": 6.0}
    text, code = t1_budget.gate(_ROWS, bad_baseline, tolerance=0.25)
    assert code == 1
    assert "GATE FAILED" in text
    assert "test_leader_death" in text
    assert "2.05x" in text


def test_gate_absolute_slack_absorbs_subsecond_jitter():
    """A 0.2s test measuring 0.5s is a 2.5x 'regression' — but the absolute
    slack keeps sub-second noise from wedging CI."""
    rows = [("tests/test_x.py::test_tiny", 0.5)]
    text, code = t1_budget.gate(
        rows, {"tests/test_x.py::test_tiny": 0.2}, tolerance=0.25,
        slack_s=1.0,
    )
    assert code == 0
    text, code = t1_budget.gate(
        rows, {"tests/test_x.py::test_tiny": 0.2}, tolerance=0.25,
        slack_s=0.0,
    )
    assert code == 1


def test_gate_warns_but_does_not_fail_on_missing_tests():
    baseline = {
        "tests/test_core.py::test_quick": 3.0,
        "tests/test_gone.py::test_renamed_away": 5.0,
    }
    text, code = t1_budget.gate(_ROWS, baseline)
    assert code == 0
    assert "warning" in text and "test_renamed_away" in text


def test_gate_counts_every_missing_test_out_of_the_passed():
    """The junit file names every test that ran, however short: one
    baselined at the 0.01 s recording floor that is absent was deselected or
    renamed like any other, and warns like any other."""
    baseline = {
        "tests/test_core.py::test_quick": 3.0,
        "tests/test_fast.py::test_sub_5ms": 0.01,
        "tests/test_gone.py::test_renamed_away": 5.0,
    }
    text, code = t1_budget.gate(_ROWS, baseline)
    assert code == 0
    warn_lines = [l for l in text.splitlines() if "warning" in l]
    assert len(warn_lines) == 2
    assert "gate passed: 1/3" in text


def test_record_baseline_roundtrips_into_gate():
    baseline = t1_budget.record_baseline(_ROWS, [])
    assert baseline["tests/test_faults.py::test_leader_death"] == 12.3
    _text, code = t1_budget.gate(_ROWS, baseline)
    assert code == 0  # a freshly recorded baseline always passes


def test_gate_zero_baseline_fails_with_report_not_zerodivision():
    """A 0.0 baseline entry (legal JSON) must produce the GATE FAILED
    report, never an unhandled ZeroDivisionError that loses the output."""
    rows = [("tests/test_x.py::test_t", 2.0)]
    text, code = t1_budget.gate(
        rows, {"tests/test_x.py::test_t": 0.0}, slack_s=1.0
    )
    assert code == 1
    assert "GATE FAILED" in text and "baseline 0" in text


def test_record_baseline_floors_subsecond_and_respects_curation(tmp_path):
    """record_baseline floors values at 0.01 (a rounded-to-0.0 entry would
    gate on slack alone), and --record-baseline over an EXISTING file
    refreshes only its curated tests instead of swallowing the suite."""
    rows = [("tests/test_a.py::test_tiny", 0.004),
            ("tests/test_a.py::test_other", 5.0)]
    assert t1_budget.record_baseline(rows, [])[
        "tests/test_a.py::test_tiny"] == 0.01
    # selective: only the named test is recorded
    only = t1_budget.record_baseline(rows, ["tests/test_a.py::test_tiny"])
    assert list(only) == ["tests/test_a.py::test_tiny"]

    import json

    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"tests/test_a.py::test_other": 4.0}))
    log = tmp_path / "_t1.xml"
    log.write_text(_junit(
        [("test_a", "test_other", 5.0), ("test_a", "test_tiny", 0.5)],
        wall=6.0,
    ))
    t1_budget.main(["--record-baseline", str(path), str(log)])
    refreshed = json.loads(path.read_text())
    assert refreshed == {"tests/test_a.py::test_other": 5.0}

    # bootstrap: a missing file records everything
    fresh = tmp_path / "fresh.json"
    t1_budget.main(["--record-baseline", str(fresh), str(log)])
    assert set(json.loads(fresh.read_text())) == {
        "tests/test_a.py::test_other", "tests/test_a.py::test_tiny"
    }


def test_repo_baseline_file_covers_this_prs_tests():
    """The committed baseline must name this PR's new tier-1 tests so the
    gate can catch them regressing (ISSUE 7 satellite)."""
    baseline_path = (
        Path(__file__).resolve().parent.parent / "tools" / "t1_baseline.json"
    )
    import json

    baseline = json.loads(baseline_path.read_text())
    assert any("test_tracing.py" in k for k in baseline)
    assert all(isinstance(v, (int, float)) and v > 0
               for v in baseline.values())
