"""The two host-counter metrics of PR 49 (layer ``collaborative step``,
moving ``samples_per_s_per_chip``): their files load for every cell in traced
runs, and the one reducer reads what each says off fixture ``step.record``
events — a window with a hold, one without, and the records of an older
program (no ``cpu_s``), which give nothing and raise nothing. (ISSUE 49's
``step.loop_preempted_per_s`` and ``step.loop_faults_per_step`` wait for a
chip machine whose kernel counts them: gVisor's ``getrusage`` reads 0.)"""
import importlib
import json
import os
import types

import pytest

from benchmark import run as bench
from benchmark.rundata import RunData

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ["step.held_pct", "step.loop_cpu_pct"]


def _metric(name):
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        return json.load(f)


def _reduce(name, records):
    metric = _metric(name)
    reducer = importlib.import_module(f"benchmark.reducers.{metric['reducer']}")
    run = RunData(
        recorder=None, cell={}, config={}, role=types.SimpleNamespace(),
        args=None, chips=1, device_kind="cpu", process_start=0.0, memory={},
        step_records=records,
    )
    return reducer.reduce(run, metric.get("params", {}))


def _record(dur_s, stepped=False, held=0.0, **counters):
    """One ``step.record`` event as ``telemetry/steps.py`` publishes it."""
    record = {
        "event": "step.record", "dur_s": dur_s, "spans": [["fwd_bwd", None, 0, dur_s]],
        "untimed_s": 0.0, "cpu_s": 0.0, "sys_s": 0.0, "minflt": 0,
        "majflt": 0, "nivcsw": 0, "holds": [], "held_excess_s": held,
        **counters,
    }
    if stepped:
        record["stepped"] = True
    if held:
        record["holds"] = [{"span": "drain", "excess_s": held}]
    return record


# two global steps of two boundaries, 2 s each but for the held one (4 s)
CLEAN = [
    _record(2.0, cpu_s=0.5, minflt=100, nivcsw=1),
    _record(2.0, stepped=True, cpu_s=0.25, minflt=300, majflt=1, nivcsw=2),
    _record(2.0, cpu_s=0.5, minflt=100, nivcsw=0),
    _record(2.0, stepped=True, cpu_s=0.25, minflt=298, majflt=1, nivcsw=1),
]
HELD = CLEAN[:3] + [
    _record(4.0, stepped=True, held=2.0, cpu_s=0.25, minflt=298, majflt=1,
            nivcsw=21),
]
OLDER = [
    {"event": "step.record", "dur_s": 2.0, "untimed_s": 0.0, "stepped": True,
     "spans": [["fwd_bwd", None, 0, 2.0]]},
]


@pytest.mark.parametrize("records, expected", [
    (CLEAN, {"step.held_pct": 0.0, "step.loop_cpu_pct": 100 * 1.5 / 8}),
    (HELD, {"step.held_pct": 20.0, "step.loop_cpu_pct": 15.0}),
    (OLDER, dict.fromkeys(NAMES)),
    ([], dict.fromkeys(NAMES)),
    # an older program's records beside the new one's: only the new count
    (OLDER + CLEAN, {"step.held_pct": 0.0, "step.loop_cpu_pct": 18.75}),
], ids=["clean", "held", "older_program", "no_records", "mixed"])
@pytest.mark.parametrize("name", NAMES)
def test_the_reducer_reads_what_the_metric_says(name, records, expected):
    value = _reduce(name, records)
    if expected[name] is None:
        assert value is None
    else:
        assert value == pytest.approx(expected[name])


def test_a_window_without_a_global_step_still_reads():
    assert _reduce("step.loop_cpu_pct", CLEAN[:1]) == pytest.approx(25.0)


def test_the_benchmark_declares_what_has_a_file_and_no_dead_metric():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    on_file = {
        name.removesuffix(".json")
        for name in os.listdir(os.path.join(HERE, "metrics"))
        if name.startswith("step.")
    }
    assert set(NAMES) <= declared and set(NAMES) <= on_file
    assert {n for n in declared if n.startswith("step.")} == on_file
    assert "gVisor" in _metric("step.loop_cpu_pct")["what"]


@pytest.mark.parametrize("name", NAMES)
def test_each_file_declares_the_collaborative_step_layer(name):
    metric = _metric(name)
    assert metric["kind"] == "per_layer" and metric["better"] == "lower"
    assert metric["layer"] == "collaborative step"
    assert metric["moves"] == "samples_per_s_per_chip"
    assert "workloads" not in metric  # every cell
    assert metric["source"] == (
        "program_span" if name == "step.held_pct" else "program_counter"
    )


def test_both_load_in_traced_runs_of_every_cell():
    for path in os.listdir(os.path.join(HERE, "workloads")):
        with open(os.path.join(HERE, "workloads", path)) as f:
            cell = json.load(f)
        per_layer = {m["name"] for m in bench.load_metrics(cell, "per_layer")}
        end_to_end = {m["name"] for m in bench.load_metrics(cell, "end_to_end")}
        assert set(NAMES) <= per_layer and not set(NAMES) & end_to_end
