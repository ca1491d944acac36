"""The set-up record (``telemetry/steps.py``): the start of a peer, role
entry to the end of its first global step, as ONE record of laps whose
``first_call.<program>`` children and compile sums come from JAX's own
compile events, closed with one ``set-up:`` line.

The two tiny roles at the bottom run with telemetry OFF, one start each,
shared by their cases; the same assertions with telemetry ON ride the starts
``tests/test_loop.py`` already makes."""
import importlib.util
import json
import logging
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from benchmark.reducers import setup_phase
from dedloc_tpu.core.config import (
    CollaborationArguments,
    SwAVCollaborationArguments,
    parse_config,
)
from dedloc_tpu.telemetry import registry, steps
from dedloc_tpu.telemetry.registry import Telemetry
from dedloc_tpu.testing.faults import FakeClock

pytestmark = pytest.mark.telemetry

spec = importlib.util.spec_from_file_location(
    "runlog_summary",
    Path(__file__).resolve().parent.parent / "tools" / "runlog_summary.py",
)
runlog_summary = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runlog_summary)


class _Lines(logging.Handler):
    """The ``set-up:`` lines a logger was handed."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines = []

    def emit(self, record):
        if record.getMessage().startswith("set-up:"):
            self.lines.append(record.getMessage())


@pytest.fixture
def log():
    logger = logging.getLogger("dedloc_tpu.test_setup_record")
    logger.setLevel(logging.INFO)
    handler = _Lines()
    logger.addHandler(handler)
    logger.lines = handler.lines
    yield logger
    logger.removeHandler(handler)


# float sums of the scripted advances: the records of this file run on a
# FROZEN FakeClock (``scripted_clock``), as ``tests/test_step_spans.py``'s do
# and for its reason — an offset-only clock rides on the real one, and a
# thread another test left running in the worker holds the interpreter for
# milliseconds: a 1 ms pause between the last lap and the close adds a lap
# called "rest" (``_SetupContext.close``), which failed
# ``test_runlog_summary_steps_prints_the_setup_record_ahead_of_the_steps``
# once in two whole runs under ``-n 6`` (PR 47)
REAL = 5e-3


def scripted_clock() -> FakeClock:
    return FakeClock(frozen=True)


def _filed(record, *events):
    """File compile events as the listener does — (kind, program, t0, t1)
    in the order they END — at offsets from the record's opening."""
    record._pending += [
        (kind, program, record._start + t0, record._start + t1)
        for kind, program, t0, t1 in events
    ]


def _listeners():
    return (
        list(monitoring.get_event_duration_listeners()),
        list(monitoring.get_event_listeners()),
    )


def _program(name, ops=60):
    """A jitted function called ``name`` whose trace takes well over a
    millisecond (``ops`` jnp calls)."""
    def fn(x):
        for i in range(ops):
            x = jnp.sin(x) * (i + 1.0) + jnp.cos(x)
        return x.sum()

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _tree(spans):
    """{name: (parent, t0, t1, total)} of a record's finished spans."""
    return {
        s[0]: (s[1], s[2], s[3], s[5] if len(s) > 4 else s[3] - s[2])
        for s in spans
    }


# ------------------------------------------------------------------- laps


def test_laps_tile_the_record_and_the_line_says_so(log):
    with scripted_clock() as clock:
        with steps.setup_record(log) as record:
            assert steps.current_setup() is record
            clock.advance(0.25)
            steps.lap("prepare")
            clock.advance(1.5)
            steps.lap("init_state")
            clock.advance(0.5)
            steps.lap("accumulate")
            clock.advance(0.25)
            steps.lap("accumulate")  # a name twice: its total is the sum
            steps.close_setup()
            assert steps.current_setup() is None
            steps.lap("after")  # no record: a no-op
    assert record.complete and len(log.lines) == 1
    spans = record.finished_spans()
    assert [s[0] for s in spans] == [
        "prepare", "init_state", "accumulate", "accumulate"
    ]
    # each lap starts where the one before it ended; total = close - open
    assert [s[2] for s in spans] == pytest.approx(
        [0.0, 0.25, 1.75, 2.25], abs=REAL
    )
    assert [s[2] for s in spans[1:]] == [s[3] for s in spans[:-1]]
    assert spans[-1][3] == pytest.approx(2.5, abs=REAL)
    values = setup_phase.parse_line(log.lines[0])
    assert values["total"] == pytest.approx(2.5, abs=REAL)
    assert values["accumulate"] == pytest.approx(0.75, abs=REAL)
    assert sum(record.phases.values()) == pytest.approx(spans[-1][3], abs=1e-5)


def test_a_record_abandoned_before_a_global_step_says_complete_0(log):
    before = _listeners()
    with pytest.raises(RuntimeError):
        with scripted_clock() as clock, steps.setup_record(log) as record:
            clock.advance(0.5)
            steps.lap("dht")
            clock.advance(0.25)
            raise RuntimeError("the role died")
    assert _listeners() == before and steps.current_setup() is None
    assert not record.complete and len(log.lines) == 1
    values = setup_phase.parse_line(log.lines[0])
    assert values["complete"] == 0
    assert values["total"] == pytest.approx(0.75, abs=REAL)
    assert values["rest"] == pytest.approx(0.25, abs=REAL)  # under no lap


def test_the_line_round_trips_through_the_reducers_parser(log):
    with scripted_clock() as clock, steps.setup_record(log) as record:
        clock.advance(0.125)
        steps.lap("prepare")
        _filed(
            record,
            ("trace_s", "accumulate_step", 0.25, 1.25),
            ("lower_s", "accumulate_step", 1.25, 1.75),
            ("backend_s", "accumulate_step", 2.0, 4.0),
        )
        record.cache.update(hits=3, misses=1)
        clock.advance(4.0)
        steps.lap("first_micro_batch")
        steps.close_setup()
    line = log.lines[0]
    values = setup_phase.parse_line(line)
    # the grammar: ``set-up:``, ``key=value`` tokens in this order, one bar
    assert list(values) == [
        "total", "complete", "prepare", "first_micro_batch", "first_calls",
        "trace", "lower", "backend", "programs", "hits", "misses",
        "traces[accumulate_step]",
    ]
    assert line.split(" | ")[1].startswith("first_calls=") and "\n" not in line
    assert values == pytest.approx({
        "total": 4.125, "complete": 1.0, "prepare": 0.125,
        "first_micro_batch": 4.0, "first_calls": 3.75, "trace": 1.0,
        "lower": 0.5, "backend": 2.0, "programs": 1.0, "hits": 3.0,
        "misses": 1.0, "traces[accumulate_step]": 1.0,
    }, abs=REAL)
    # what it parses is what the record holds, to the line's three decimals
    assert values["total"] == pytest.approx(
        record.finished_spans()[-1][3], abs=1e-3
    )
    assert values["first_calls"] == pytest.approx(
        sum(record.first_calls().values()), abs=1e-3
    )
    assert setup_phase.parse_line("global step 3: loss 1.0") is None
    # the first call is its lap's child, and the lap's self time the rest
    parent, t0, t1, _total = _tree(record.finished_spans())[
        "first_call.accumulate_step"
    ]
    assert parent == "first_micro_batch"
    assert (t0, t1) == pytest.approx((0.25, 4.0), abs=1e-5)
    assert record.phases["first_micro_batch"] == pytest.approx(0.25, abs=REAL)


def test_a_trace_inside_a_trace_is_its_parents_time(log):
    with scripted_clock() as clock, steps.setup_record(log) as record:
        _filed(
            record,
            ("trace_s", "_where", 0.5, 0.75),  # an inner jit, traced inside
            ("backend_s", "zeros", 1.0, 1.5),  # an eager compile, inside too
            ("trace_s", "outer", 0.25, 2.0),
            ("lower_s", "outer", 2.0, 2.5),
            ("backend_s", "outer", 2.5, 3.0),
        )
        clock.advance(3.0)
        steps.lap("first_boundary")
        steps.close_setup()
    assert record.compile["first_boundary"] == pytest.approx({
        "trace_s": 1.75, "lower_s": 0.5, "backend_s": 0.5, "programs": 1,
    })
    assert record.traces == {"outer": 1}
    assert record.first_calls() == pytest.approx({"outer": 2.75})


# ----------------------------------------------------------- real compiles


def test_a_jitted_call_is_filed_under_its_lap_and_counted_once_a_trace(
    log, monkeypatch
):
    monkeypatch.setattr(steps, "FIRST_CALL_MIN_S", 0.0)
    program = _program("setup_probe_program")
    with steps.setup_record(log) as record:
        steps.lap("before")
        program(jnp.ones((8,)))
        steps.lap("first_micro_batch")
        assert record.traces["setup_probe_program"] == 1
        program(jnp.ones((8,)))  # the same shape: no trace, nothing filed
        steps.lap("accumulate")
        program(jnp.ones((16,)))  # a second shape: a second trace
        steps.lap("first_boundary")
        steps.close_setup()
    assert record.traces["setup_probe_program"] == 2
    for lap in ("first_micro_batch", "first_boundary"):
        sums = record.compile[lap]
        assert sums["programs"] >= 1
        assert min(sums["trace_s"], sums["lower_s"], sums["backend_s"]) > 0
    assert "accumulate" not in record.compile
    assert "setup_probe_program" not in str(record.compile.get("before"))
    # the first call is a child of the lap it fell in, and inside it
    spans = [
        s for s in record.finished_spans()
        if s[0] == "first_call.setup_probe_program"
    ]
    assert [s[1] for s in spans] == ["first_micro_batch", "first_boundary"]
    laps = _tree([s for s in record.finished_spans() if s[1] is None])
    for name, parent, t0, t1 in spans:
        assert laps[parent][1] <= t0 <= t1 <= laps[parent][2]
    # children never exceed their parent: self times are what is left
    assert all(seconds >= 0 for seconds in record.phases.values())
    assert sum(record.phases.values()) == pytest.approx(
        record.finished_spans()[-1][3], abs=1e-6
    )
    assert "traces[setup_probe_program]=2" in log.lines[0]


def test_after_the_close_nothing_listens_and_a_compile_is_filed_nowhere(log):
    before = _listeners()
    with steps.setup_record(log) as record:
        during = _listeners()
        assert len(during[0]) == len(before[0]) + 1
        assert len(during[1]) == len(before[1]) + 1
        steps.lap("prepare")
        steps.close_setup()
        assert _listeners() == before  # gone at the close, not at the exit
        compiled = dict(record.compile), dict(record.traces)
        _program("setup_probe_late")(jnp.ones((4,)))
    assert _listeners() == before
    assert (record.compile, record.traces) == compiled
    assert not record._pending and len(log.lines) == 1


def test_two_peers_as_two_threads_get_a_record_each(log, monkeypatch):
    monkeypatch.setattr(steps, "FIRST_CALL_MIN_S", 0.0)
    barrier = threading.Barrier(2)
    records, errors = {}, []

    def peer(index):
        try:
            program = _program(f"setup_probe_peer{index}")
            with steps.setup_record(log) as record:
                records[index] = record
                barrier.wait(timeout=60)  # both records open, both listen
                program(jnp.ones((4 + index,)))
                steps.lap(f"first_micro_batch_peer{index}")
                barrier.wait(timeout=60)  # neither closes before both ran
                steps.close_setup()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)
            barrier.abort()

    before = _listeners()
    threads = [threading.Thread(target=peer, args=(i,)) for i in (0, 1)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=120)
    assert not any(thread.is_alive() for thread in threads)
    assert not errors and _listeners() == before
    assert records[0] is not records[1] and len(log.lines) == 2
    for index in (0, 1):
        mine, theirs = f"peer{index}", f"peer{1 - index}"
        text = str(records[index].finished_spans()) + str(
            records[index].traces
        )
        assert f"setup_probe_{mine}" in text
        assert theirs not in text
        assert records[index].traces[f"setup_probe_{mine}"] == 1


@pytest.mark.parametrize("enabled", [False, True], ids=["off", "on"])
def test_the_line_is_always_logged_and_the_event_only_with_telemetry(
    log, enabled
):
    tele = Telemetry(peer="setup-unit") if enabled else None
    with scripted_clock() as clock, steps.setup_record(log) as record:
        _filed(record, ("backend_s", "flat_apply_step", 0.0, 1.0))
        record.cache["hits"] += 1
        clock.advance(1.5)
        steps.lap("first_boundary")
        steps.close_setup(tele)
    assert len(log.lines) == 1 and registry.active() is None
    if tele is None:
        return
    events = [e for e in tele.events if e["event"] == "setup.record"]
    assert len(events) == 1
    event = events[0]
    assert event["dur_s"] == pytest.approx(1.5, abs=REAL)
    assert event["complete"] is True
    assert event["compile"]["first_boundary"]["backend_s"] == pytest.approx(1.0)
    assert _tree(event["spans"])["first_call.flat_apply_step"][0] == (
        "first_boundary"
    )
    assert sum(event["phases"].values()) + event["untimed_s"] == (
        pytest.approx(event["dur_s"])
    )
    assert event["cache_hits"] == 1 and event["cache_misses"] == 0
    assert event["traces"] == {"flat_apply_step": 0}  # loaded, not traced
    # the event is the whole of it: the metrics bus carries no set-up name
    assert not [k for k in tele.snapshot() if k.startswith("setup.")]


def test_runlog_summary_steps_prints_the_setup_record_ahead_of_the_steps(
    log, tmp_path, capsys
):
    path = tmp_path / "events.jsonl"
    tele = Telemetry(peer="setup-view", event_log_path=str(path))
    with scripted_clock() as clock, steps.setup_record(log) as record:
        clock.advance(0.5)
        steps.lap("init_state")
        _filed(
            record,
            ("trace_s", "accumulate_step", 0.5, 1.5),
            ("backend_s", "accumulate_step", 1.5, 2.25),
        )
        clock.advance(2.0)
        steps.lap("first_micro_batch")
        steps.close_setup(tele)
    recorder = steps.StepRecorder(telemetry=tele)
    with recorder.step(step=1, samples=4), steps.phase("fwd_bwd"):
        pass
    tele.close()
    runlog_summary.main(["--steps", str(path)])
    out = capsys.readouterr().out
    assert out.index("set-up (role entry") < out.index("step-time waterfall")
    lines = out.splitlines()
    lap = next(i for i, l in enumerate(lines) if "first_micro_batch" in l)
    assert "first_call.accumulate_step" in lines[lap + 1]
    assert "traced x1" in lines[lap + 1]
    runlog_summary.main(["--json", "--steps", str(path)])
    (shown,) = json.loads(capsys.readouterr().out)["setup"]
    assert shown["peer"] == "setup-view" and shown["complete"] is True
    assert list(shown["laps"]) == ["init_state", "first_micro_batch"]
    assert shown["laps"]["first_micro_batch"] == pytest.approx(2.0, abs=REAL)
    assert shown["trace_s"] == pytest.approx(1.0)
    assert shown["backend_s"] == pytest.approx(0.75) and shown["programs"] == 1
    assert shown["first_calls"] == [{
        "lap": "first_micro_batch", "span": "first_call.accumulate_step",
        "s": pytest.approx(1.75, abs=REAL),
    }]


# ------------------------------------------- the two tiny roles, telemetry off

_ROLE_ARGV = [
    "--dht.listen_host", "127.0.0.1",
    "--training.model_size", "tiny",
    "--training.per_device_batch_size", "2",
    "--training.gradient_accumulation_steps", "2",
    "--training.save_steps", "0",
    "--training.max_local_steps", "5",
    # a global step every two boundaries of 2 x 2 samples: the run makes two
    "--optimizer.target_batch_size", "8",
    "--averager.averaging_expiration", "0.3",
    "--averager.min_refresh_period", "0.1",
    "--averager.default_refresh_period", "0.3",
]
_STARTS = {}


def _start(role, tmp_path_factory):
    """One start of ``role`` at the tiny size with telemetry off: what the
    package logged, the monitoring listeners before and after."""
    if role in _STARTS:
        return _STARTS[role]
    out = tmp_path_factory.mktemp(f"setup-{role}")
    argv = _ROLE_ARGV + ["--training.output_dir", str(out)]
    captured = []

    class Capture(logging.Handler):
        def emit(self, record):
            captured.append((record.name, record.getMessage()))

    handler = Capture(level=logging.INFO)
    package_logger = logging.getLogger("dedloc_tpu")
    before = _listeners()
    assert registry.active() is None
    package_logger.addHandler(handler)
    try:
        if role == "swav":
            from dedloc_tpu.roles.swav import run_swav

            run_swav(parse_config(SwAVCollaborationArguments, argv))
        else:
            from dedloc_tpu.roles.trainer import run_trainer

            run_trainer(parse_config(
                CollaborationArguments, argv + ["--training.seq_length", "32"]
            ))
    finally:
        package_logger.removeHandler(handler)
    _STARTS[role] = captured, before, _listeners()
    return _STARTS[role]


@pytest.fixture(scope="module", params=["trainer", "swav"])
def start(request, tmp_path_factory):
    return (request.param, *_start(request.param, tmp_path_factory))


def test_a_role_logs_one_line_on_its_own_logger_with_telemetry_off(start):
    role, captured, _before, _after = start
    lines = [(name, m) for name, m in captured if m.startswith("set-up:")]
    assert len(lines) == 1
    assert lines[0][0] == f"dedloc_tpu.roles.{role}"
    # ... after the first global step's own line, before the second's
    messages = [m for _name, m in captured]
    at = messages.index(lines[0][1])
    first = [i for i, m in enumerate(messages) if m.startswith("global step 1:")]
    later = [i for i, m in enumerate(messages) if m.startswith("global step 2:")]
    assert first and first[0] < at and (not later or at < later[0])


def test_a_roles_line_names_its_laps_and_tiles(start):
    role, captured, _before, _after = start
    values = setup_phase.parse_line(
        next(m for _n, m in captured if m.startswith("set-up:"))
    )
    assert values["complete"] == 1
    laps = [
        "prepare", "dht", "init_state", "resume", "collab_optimizer",
        "state_from_peers", "seed_state_sharing", "data_source",
        "first_micro_batch", "accumulate", "first_boundary",
        "first_post_step",
    ]
    assert set(laps) <= set(values)
    assert "mesh_commit" not in values  # no mesh in this start: no lap
    named = {
        k: v for k, v in values.items()
        if k in laps or k == "rest"
    }
    # three decimals a lap: the laps tile the total to their rounding
    assert sum(named.values()) == pytest.approx(
        values["total"], abs=1e-3 * len(named)
    )
    # the accumulate program's first call is in the line, traced once
    program = "step" if role == "swav" else "accumulate_step"
    assert values[f"traces[{program}]"] == 1
    assert values["first_calls"] <= values["total"]
    assert values["programs"] >= 3 and values["backend"] > 0


def test_a_roles_listeners_are_gone_when_it_returns(start):
    _role, _captured, before, after = start
    assert after == before and steps.current_setup() is None
