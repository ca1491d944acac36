"""The eight set-up metrics of ISSUE 44 (layer ``set-up``, moving
``setup_s``): their files load for every cell in traced runs, and the two
reducers read what they say off a synthetic ``Recorder`` — log lines stamped
on the harness's clock, compile tuples, a window. Three parts TILE
``setup_s``; in a cell of two peers every part comes off the slowest peer's
line. On a run whose program logs no ``set-up:`` line (the parent's) the
five that read the line give nothing and raise nothing."""
import importlib
import json
import logging
import os
import types

import pytest

from benchmark import run as bench
from benchmark.instrument import Recorder
from benchmark.reducers import setup as setup_s
from benchmark.reducers import setup_compile, setup_phase
from benchmark.rundata import RunData

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = [
    "setup.harness_s", "setup.role_s", "setup.role_first_calls_s",
    "setup.role_state_s", "setup.warmup_s", "setup.trace_lower_s",
    "setup.backend_s", "setup.accumulate_traces",
]
FROM_THE_LINE = NAMES[:5]
PROCESS_START = 1000.0
LINE = (
    "set-up: total={total:.3f} complete=1 prepare=0.020 dht=0.000 "
    "init_state=4.000 resume=0.125 state_from_peers=1.500 mesh_commit=0.250 "
    "seed_state_sharing=0.375 first_micro_batch=9.000 first_boundary=6.000 "
    "| first_calls=12.500 trace=3.000 lower=2.000 backend=8.000 programs=40 "
    "hits=38 misses=2 traces[accumulate_step]=1"
)


def _metric(name):
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        return json.load(f)


def _reduce(name, run):
    metric = _metric(name)
    reducer = importlib.import_module(f"benchmark.reducers.{metric['reducer']}")
    return reducer.reduce(run, metric.get("params", {}))


def _run(lines, compiles=()):
    """Two peers; the window opens when the SECOND completes global step 2
    (t = 1094) and closes at step 5. ``lines``: (t, peer, message)."""
    recorder = Recorder(2, 1, 45.0)
    recorder.start_step, recorder.final_step = 2, 5
    for peer, lag in zip(recorder.peers, (0.0, 4.0)):
        peer.opt_calls = [
            (1070.0 + 8 * step + lag, 1070.0 + 8 * step + lag + 4.0, True, step)
            for step in range(1, 6)
        ]
    recorder.log = [
        (t, logging.INFO, peer, "dedloc_tpu.roles.trainer", message)
        for t, peer, message in lines
    ]
    recorder.compiles = list(compiles)
    return RunData(
        recorder=recorder, cell={}, config={},
        role=types.SimpleNamespace(PROGRAMS={"accumulate": "accumulate_step"}),
        args=None, chips=1, device_kind="cpu", process_start=PROCESS_START,
        memory={},
    )


def test_the_eight_load_in_traced_runs_only_and_the_warm_up_where_there_is_one():
    for path in os.listdir(os.path.join(HERE, "workloads")):
        with open(os.path.join(HERE, "workloads", path)) as f:
            cell = json.load(f)
        per_layer = {m["name"] for m in bench.load_metrics(cell, "per_layer")}
        end_to_end = {m["name"] for m in bench.load_metrics(cell, "end_to_end")}
        # a cell whose warm-up is its first global step has no step between
        # the record's close and the window: no warm-up tile to report
        several = cell["warmup_steps"] > 1
        assert ("setup.warmup_s" in per_layer) == several
        assert several == (cell["name"] in _metric("setup.warmup_s")["workloads"])
        assert set(NAMES) - {"setup.warmup_s"} <= per_layer
        assert not set(NAMES) & end_to_end
        assert "setup_s" in end_to_end  # what they move, in every cell


@pytest.mark.parametrize("name", NAMES)
def test_each_file_declares_the_set_up_layer(name):
    metric = _metric(name)
    assert metric["kind"] == "per_layer" and metric["layer"] == "set-up"
    assert metric["moves"] == "setup_s" and metric["better"] == "lower"
    assert ("workloads" in metric) == (name == "setup.warmup_s")
    assert metric["unit"] == ("count" if name.endswith("_traces") else "s")
    assert metric["reducer"] in ("setup_phase", "setup_compile")


def test_three_parts_tile_setup_s_off_the_slowest_peers_line():
    run = _run([
        (1040.0, 0, "global step 1: loss 6.9"),
        (1082.5, 0, LINE.format(total=50.0)),  # peer 0: ready first
        (1086.25, 1, LINE.format(total=41.5).replace("4.000", "7.000")),
        (1094.0, 1, "global step 2: loss 6.8"),
    ])
    assert run.window()[0] == 1094.0
    parts = {name: _reduce(name, run) for name in NAMES[:5]}
    # all five off peer 1's line, the last to close: the window waits for it
    assert parts["setup.role_s"] == 41.5
    assert parts["setup.harness_s"] == pytest.approx(1086.25 - 41.5 - 1000.0)
    assert parts["setup.warmup_s"] == pytest.approx(1094.0 - 1086.25)
    assert parts["setup.role_first_calls_s"] == 12.5
    assert parts["setup.role_state_s"] == pytest.approx(
        7.0 + 0.125 + 1.5 + 0.25 + 0.375
    )
    tiles = (
        parts["setup.harness_s"] + parts["setup.role_s"]
        + parts["setup.warmup_s"]
    )
    assert tiles == pytest.approx(setup_s.reduce(run, {}), abs=1e-6)
    assert setup_s.reduce(run, {}) == 94.0


def test_a_cell_that_warms_up_for_one_step_is_harness_plus_role():
    """The window opens when ``opt.step`` returns; the record closes a
    ``post_step`` later: the line is stamped AFTER the opening, the cell
    reports no warm-up tile and the two others overshoot ``setup_s`` by
    that ``post_step``."""
    run = _run([(1094.04, 1, LINE.format(total=60.0))])
    assert _reduce("setup.harness_s", run) + _reduce("setup.role_s", run) == (
        pytest.approx(setup_s.reduce(run, {}) + 0.04, abs=1e-6)
    )


def test_a_program_without_the_line_gives_nothing_and_raises_nothing():
    run = _run([
        (1040.0, 0, "global step 1: loss 6.9"),
        # an abandoned record is no start to measure
        (1050.0, 0, LINE.format(total=9.0).replace("complete=1", "complete=0")),
    ], compiles=[(1010.0, "backend_compile_duration", "jit(accumulate_step)", 2.0)])
    for name in FROM_THE_LINE:
        assert _reduce(name, run) is None
    assert _reduce("setup.backend_s", run) == 2.0  # the harness's own listener


def test_compile_sums_stop_at_the_window_and_traces_are_real_traces():
    compiles = [
        # the harness's reference check, then its scratch analysis
        (1010.0, "jaxpr_trace_duration", "accumulate_step", 5.5),
        (1012.0, "jaxpr_to_mlir_module_duration", "jit(accumulate_step)", 1.5),
        (1030.0, "backend_compile_duration", "jit(accumulate_step)", 18.0),
        (1036.0, "jaxpr_trace_duration", "accumulate_step", 5.25),
        # a hit of the trace cache emits the event too, in microseconds
        (1036.5, "jaxpr_trace_duration", "accumulate_step", 2e-5),
        # the role's own jit, under its module name
        (1060.0, "jaxpr_trace_duration", "jit(accumulate_step)", 5.0),
        (1061.0, "jaxpr_to_mlir_module_duration", "jit(accumulate_step)", 1.0),
        (1062.0, "backend_compile_duration", "jit(accumulate_step)", 0.5),
        (1063.0, "jaxpr_trace_duration", "guarded_apply_step", 0.25),
        (1064.0, "backend_compile_duration", "jit(guarded_apply_step)", 0.75),
        # inside the window: not set-up (and `correct` is false for it)
        (1099.0, "jaxpr_trace_duration", "accumulate_step", 5.0),
        (1100.0, "backend_compile_duration", "jit(accumulate_step)", 9.0),
    ]
    run = _run([], compiles)
    assert _reduce("setup.accumulate_traces", run) == 3.0
    assert _reduce("setup.trace_lower_s", run) == pytest.approx(
        5.5 + 1.5 + 5.25 + 2e-5 + 5.0 + 1.0 + 0.25
    )
    assert _reduce("setup.backend_s", run) == pytest.approx(18.0 + 0.5 + 0.75)
    assert _reduce("setup.trace_lower_s", _run([])) is None
    assert _reduce("setup.accumulate_traces", _run([])) == 0.0


def test_a_trace_is_what_the_set_up_record_calls_one(monkeypatch):
    """One rule, the program's: a program without it gives no count."""
    from benchmark.reducers import setup_compile
    from dedloc_tpu.telemetry import steps

    assert setup_compile.TRACE_MIN_S is steps.TRACE_MIN_S
    monkeypatch.setattr(setup_compile, "TRACE_MIN_S", None)
    run = _run([], [(1010.0, "jaxpr_trace_duration", "accumulate_step", 5.5)])
    assert _reduce("setup.accumulate_traces", run) is None
    assert _reduce("setup.trace_lower_s", run) == 5.5
