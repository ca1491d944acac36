"""The step record as a span tree on the profiler's clock (ISSUE 23):
nesting and self times, the spans bound, the running totals stamped by
``CollaborativeOptimizer.step``, a real ``jax.profiler`` session holding the
``dedloc/*`` host events, the profile reader (``attribute_idle``) on a
synthetic profile and on one recorded on a TPU v5e, and the slow-step
notice."""
import functools
import glob
import hashlib
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dedloc_tpu.telemetry import profile, registry, steps
from dedloc_tpu.telemetry.registry import Telemetry
from dedloc_tpu.telemetry.steps import MAX_SPANS, StepRecorder
from dedloc_tpu.testing.faults import FakeClock

pytestmark = pytest.mark.telemetry

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "albert_solo_profile_v5e.json.gz",
)


def _span_seconds(span):
    return span[5] if len(span) > 4 else span[3] - span[2]


def scripted_clock() -> FakeClock:
    """The clock every scripted record of this file runs on: FROZEN, so the
    spans read ``advance``'s seconds and nothing else. An offset-only
    FakeClock rides on the real clock, and what runs BETWEEN two advances
    leaks in — microseconds alone, but a thread another test of the worker
    left running (a DHT loop, an averager, a backup thread: xdist's
    ``--dist loadfile`` puts whole files of those in this process) holds
    the interpreter for its 5 ms switch interval, and one such hold inside
    a boundary of 100 micro-batches broke ``approx``'s 2 ms: 5-7 failures
    in 100 with one busy thread beside the test, 0 in 100 frozen."""
    return FakeClock(frozen=True)


def approx(expected):
    """Float sums of the scripted advances: rounding, no real time."""
    return pytest.approx(expected, abs=2e-3)


# ------------------------------------------------------------------- the tree


def test_nested_spans_give_self_times_that_sum_to_the_wall():
    rec = StepRecorder()
    with scripted_clock() as clock:
        with rec.step(step=7) as srec:
            with steps.phase("data_wait"):
                clock.advance(0.25)
            with steps.phase("fwd_bwd"):  # the SwAV shape: a parent
                clock.advance(0.5)  # its own time
                with steps.phase("h2d"):
                    clock.advance(0.125)
                with steps.phase("post_step"):
                    with steps.phase("loss_sync"):
                        clock.advance(1.0)
                    clock.advance(0.0625)
                    with steps.phase("publish"):
                        clock.advance(0.5)
            clock.advance(0.03125)  # nobody's: untimed
            assert srec.total("fwd_bwd") == approx(2.1875)
    record = rec.records[-1]
    assert record["phases"] == approx({
        "data_wait": 0.25, "fwd_bwd": 0.5, "h2d": 0.125,
        "post_step": 0.0625, "loss_sync": 1.0, "publish": 0.5,
    })
    assert record["untimed_s"] == approx(0.03125)
    assert sum(record["phases"].values()) + record["untimed_s"] == (
        approx(record["wall_s"])
    )
    tree = {(s[0], s[1]): (s[2], s[3]) for s in record["spans"]}
    assert tree[("loss_sync", "post_step")] == approx((0.875, 1.875))
    assert tree[("post_step", "fwd_bwd")] == approx((0.875, 2.4375))
    assert tree[("fwd_bwd", None)] == approx((0.25, 2.4375))
    assert record["boundary"] == 0 and record["step"] == 7
    with rec.step():
        pass
    assert rec.records[-1]["boundary"] == 1  # the recorder's own index


def test_added_span_counts_and_attached_span_does_not():
    """``add`` is this thread's time under the open span (the exposed D2H
    wait inside ``avg_wire``); ``attach`` is another thread's reading of the
    same wall (the averager's matchmaking / all-reduce split): in ``spans``,
    never in ``phases``."""
    rec = StepRecorder()
    with scripted_clock() as clock:
        with rec.step():
            with steps.phase("avg_wire") as wire:
                start = registry.monotonic_clock()
                clock.advance(1.0)
                steps.add("d2h_stream", 0.25)
                steps.attach("matchmaking", start, start + 0.75)
                steps.attach("allreduce", start + 0.75, start + 1.0)
    assert wire.dur_s == approx(1.0)
    record = rec.records[-1]
    assert record["phases"] == approx(
        {"avg_wire": 0.75, "d2h_stream": 0.25}
    )
    assert record["untimed_s"] == approx(0.0)
    spans = {s[0]: s for s in record["spans"]}
    assert spans["matchmaking"][1] == spans["allreduce"][1] == "avg_wire"
    assert _span_seconds(spans["matchmaking"]) + _span_seconds(
        spans["allreduce"]
    ) == approx(_span_seconds(spans["avg_wire"]))


def test_a_span_outside_any_record_still_times():
    """``CollaborativeOptimizer.seam_ms`` reads ``dur_s`` whether or not a
    role is recording."""
    assert steps.current() is None
    with scripted_clock() as clock:
        with steps.phase("opt_apply") as span:
            clock.advance(0.5)
    assert span.dur_s == approx(0.5)


def test_spans_are_bounded_by_folding_repeated_leaves():
    rec = StepRecorder()
    with scripted_clock() as clock:
        with rec.step():
            for _ in range(100):  # a boundary of 100 micro-batches
                with steps.phase("data_wait"):
                    clock.advance(0.01)
                with steps.phase("fwd_bwd"):
                    clock.advance(0.02)
            with steps.phase("post_step"):
                with steps.phase("publish"):
                    clock.advance(0.5)
    record = rec.records[-1]
    assert len(record["spans"]) <= MAX_SPANS
    by_name = {s[0]: s for s in record["spans"]}
    assert by_name["data_wait"][4:] == [100, approx(1.0)]
    assert by_name["fwd_bwd"][4:] == [100, approx(2.0)]
    assert by_name["fwd_bwd"][2:4] == approx([0.01, 3.0])
    assert by_name["publish"][:2] == ["publish", "post_step"]
    assert by_name["publish"][2:] == approx([3.0, 3.5])
    # the fold loses no time: phases and wall are untouched by it
    assert record["phases"]["fwd_bwd"] == approx(2.0)
    assert sum(record["phases"].values()) == approx(record["wall_s"])


def _record_with_attached_kinds(clock, rec, micro_batches):
    """A networked boundary: ``avg_wire`` with the averager's split and,
    inside ``allreduce``, two stages and one folded kind, all ATTACHED."""
    with rec.step() as srec:
        for _ in range(micro_batches):
            with steps.phase("fwd_bwd"):
                clock.advance(0.01)
        with steps.phase("avg_wire"):
            start = registry.monotonic_clock()
            clock.advance(1.0)
            steps.attach("allreduce", start, start + 1.0)
            steps.attach("ar_scatter", start, start + 0.4, parent="allreduce")
            steps.attach("ar_gather", start + 0.4, start + 1.0,
                         parent="allreduce")
            steps.attach("ar_straggler", start + 0.4, start + 0.5,
                         parent="ar_gather")
            # 68 encode sections between +0.1 and +0.9 that took 0.25 s
            steps.attach("ar_encode", start + 0.1, start + 0.9,
                         parent="allreduce", count=68, total_s=0.25)
        srec.attrs["stepped"] = True
    return rec.records[-1]


@pytest.mark.parametrize("micro_batches", [2, 100])
def test_attached_folded_span_keeps_its_total_and_stays_out_of_phases(
    micro_batches
):
    """``attach(..., parent=, count=, total_s=)`` lands as a folded entry
    under the parent it names — in a short record, which it does not make
    fold, and in one that overflows ``MAX_SPANS`` and folds around it — and
    is nobody's phase: another thread's time."""
    rec = StepRecorder()
    with scripted_clock() as clock:
        record = _record_with_attached_kinds(clock, rec, micro_batches)
    spans = {s[0]: s for s in record["spans"]}
    assert spans["ar_encode"][:2] == ["ar_encode", "allreduce"]
    assert spans["ar_encode"][4:] == [68, approx(0.25)]
    assert spans["ar_encode"][3] - spans["ar_encode"][2] == approx(0.8)
    assert spans["ar_scatter"][1] == spans["ar_gather"][1] == "allreduce"
    assert spans["ar_straggler"][1] == "ar_gather"
    assert spans["allreduce"][1] == "avg_wire"
    # stages tile their parent
    assert _span_seconds(spans["ar_scatter"]) + _span_seconds(
        spans["ar_gather"]
    ) == approx(_span_seconds(spans["allreduce"]))
    assert set(record["phases"]) == {"fwd_bwd", "avg_wire"}
    assert record["phases"]["avg_wire"] == approx(1.0)
    assert sum(record["phases"].values()) == approx(record["wall_s"])
    # a short record is left as it was: an attached folded span folds nothing
    fwd = [s for s in record["spans"] if s[0] == "fwd_bwd"]
    assert len(fwd) == (2 if micro_batches == 2 else 1)
    assert len(record["spans"]) <= MAX_SPANS


def test_the_benchmark_span_reducers_read_a_folded_total():
    """``benchmark/reducers/span.py`` reads a folded entry's ``total_s`` (not
    its first-to-last extent), and ``span_residual.py`` what is left of a
    span after the named ones: ``allreduce`` minus the kinds, floored at 0;
    a record that carries none of them (the parent's program) gives nothing."""
    import types

    from benchmark.reducers import span, span_residual

    rec = StepRecorder()
    with scripted_clock() as clock:
        record = _record_with_attached_kinds(clock, rec, 2)
    run = types.SimpleNamespace(step_records=[record])
    assert span.reduce(
        run, {"name": "ar_encode", "stepped": True}
    ) == approx(250.0)
    assert span.reduce(
        run, {"name": "ar_gather", "stepped": True}
    ) == approx(600.0)
    residual = {"of": "allreduce", "names": ["ar_encode", "ar_decode"],
                "stepped": True}
    assert span_residual.reduce(run, residual) == approx(750.0)
    assert span_residual.reduce(
        run, dict(residual, names=["ar_decode"])
    ) is None  # none of the kinds recorded: nothing, not the whole span
    assert span_residual.reduce(run, dict(residual, stepped=False)) is None
    greedy = dict(record, spans=record["spans"] + [
        ["ar_decode", "allreduce", 0.0, 2.0, 9, 2.0]
    ])
    assert span_residual.reduce(
        types.SimpleNamespace(step_records=[greedy]), residual
    ) == 0.0


# ------------------------------------------------- published when telemetry is on


def test_enabled_telemetry_publishes_the_tree():
    tele = Telemetry(peer="p0")
    rec = StepRecorder(telemetry=tele)
    with scripted_clock() as clock:
        with rec.step(step=3):
            with steps.phase("opt_apply"):
                clock.advance(0.25)
                with steps.phase("h2d_result"):
                    clock.advance(0.125)
    (event,) = [e for e in tele.events if e["event"] == "step.record"]
    assert [s[:2] for s in event["spans"]] == [
        ["h2d_result", "opt_apply"], ["opt_apply", None],
    ]
    assert [s[2:] for s in event["spans"]] == [
        approx([0.25, 0.375]), approx([0.0, 0.375]),
    ]
    assert event["boundary"] == 0
    snapshot = tele.snapshot()
    assert snapshot["step.phase.opt_apply.mean"] == approx(0.25)
    assert snapshot["step.phase.h2d_result.mean"] == approx(0.125)


# ------------------------------- the optimizer fills the record, in the loop


def test_loop_record_with_a_real_optimizer_is_disjoint_and_counted(tmp_path):
    """The one loop's shape (``roles/loop.py``), for every model: draw, H2D
    and dispatch are SIBLINGS of the spans ``opt.step`` opens, and
    ``post_step`` with its ``loss_sync`` is on stepping records only. The
    record stays disjoint, and ``opt.step`` itself stamps it."""
    from dedloc_tpu.core.config import CollaborationArguments, parse_config
    from dedloc_tpu.optim import lamb
    from dedloc_tpu.parallel.train_step import TrainState, make_accumulate_step
    from dedloc_tpu.roles.common import (
        build_collaborative_optimizer,
        build_dht,
    )
    from dedloc_tpu.roles.loop import LoopModel, run_boundary_loop

    def toy_loss(params, batch, rng):
        loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
        return loss, {"loss": loss}

    args = parse_config(CollaborationArguments, [
        "--dht.listen_host", "127.0.0.1",
        "--dht.experiment_prefix", "loopspans",
        "--training.max_local_steps", "12",
        "--training.gradient_accumulation_steps", "1",
        "--training.output_dir", str(tmp_path),
        "--optimizer.target_batch_size", "32",
        "--averager.metadata_expiration", "0.2",
        "--averager.averaging_expiration", "0.5",
        "--averager.averaging_timeout", "5.0",
        "--averager.min_refresh_period", "0.05",
        "--averager.default_refresh_period", "0.1",
    ])
    dht, public_key = build_dht(args)
    tx = lamb(0.05, weight_decay=0.0)
    opt = build_collaborative_optimizer(
        args, tx, dht, public_key, batch_size_per_step=16,
        flat_opt_factory=None,
    )
    time.sleep(0.5)  # past the cold-start grace: the solo path
    params = {"w": jnp.array([[0.5], [0.5]])}
    accumulate = make_accumulate_step(toy_loss)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    batch = {"x": x, "y": x @ jnp.array([[1.0], [-2.0]])}

    def micro_step(state, grad_acc, n_acc, data):
        return accumulate(
            state.params, grad_acc, n_acc, data, jax.random.PRNGKey(0)
        )

    tele = registry.install(Telemetry(peer="loop"))
    try:
        run_boundary_loop(  # shuts the optimizer and the DHT down itself
            args,
            LoopModel(
                batches=iter([batch] * 12), micro_step=micro_step,
                save=None, put=jax.device_put,
            ),
            TrainState.create(params, tx), opt, dht, public_key,
            None, lambda: None,
        )
    finally:
        registry.uninstall(tele)

    records = [e for e in tele.events if e["event"] == "step.record"]
    assert len(records) == 12
    assert any(r.get("stepped") for r in records)
    running = 0
    for record in records:
        assert record["samples"] == 16
        running += record["samples"]
        assert record["samples_total"] == running  # monotone, the running sum
        wall = record["dur_s"]
        assert sum(record["phases"].values()) <= wall + 1e-9
        assert sum(record["phases"].values()) + record["untimed_s"] == (
            pytest.approx(wall)
        )
        assert 0 < record["opt_step_s"] <= wall
        parents = {s[0]: s[1] for s in record["spans"]}
        for name in ("data_wait", "h2d", "fwd_bwd", "collab"):
            assert parents[name] is None, (name, record["spans"])
        if record.get("stepped"):
            assert parents["loss_sync"] == "post_step"
        else:
            assert "post_step" not in parents and "loss_sync" not in parents
    steps_seen = [r["global_steps_total"] for r in records]
    assert steps_seen == sorted(steps_seen) and steps_seen[-1] >= 1
    assert [r["boundaries_total"] for r in records] == list(range(1, 13))
    stepping = next(r for r in records if r.get("stepped"))
    parents = {s[0]: s[1] for s in stepping["spans"]}
    for name in ("drain", "grad_flatten", "opt_apply", "backup_launch",
                 "post_step"):
        assert parents[name] is None, (name, stepping["spans"])
    for name in ("publish", "log"):
        assert parents[name] == "post_step"
    # the apply donates the state a backup reads in place: it asks whether
    # a read is still open on EVERY stepping record, and here none was
    for record in records:
        if record.get("stepped"):
            assert ["backup_wait", "opt_apply"] in [
                s[:2] for s in record["spans"]
            ]
        assert "opt.backup_waits" not in record


# ------------------------------------------------------- the profiler's clock


def test_a_profiler_session_holds_the_spans_on_the_host_plane(tmp_path):
    """A real ``jax.profiler`` session on the CPU backend: the host plane
    carries ``dedloc/boundary`` (with its ``step_num``) and one
    ``dedloc/<span>`` per span, and their durations are the record's."""
    rec = StepRecorder()
    double = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    double(x).block_until_ready()
    profile.start_session(str(tmp_path))
    try:
        for step in range(3):
            with rec.step(step=step):
                with steps.phase("fwd_bwd"):
                    y = double(x)
                with steps.phase("drain"):
                    y.block_until_ready()
                    time.sleep(0.003)
                    with steps.phase("inner"):
                        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    loaded = profile.load_profile(str(tmp_path))
    (thread,) = [
        t for t, spans in loaded["hosts"].items()
        if any(n == "boundary" for n, _s, _d in spans)
    ]
    events = loaded["hosts"][thread]
    for name in ("boundary", "fwd_bwd", "drain", "inner"):
        on_plane = [d / 1e9 for n, _s, d in events if n == name]
        if name == "boundary":
            in_record = [r["wall_s"] for r in rec.records]
        else:
            in_record = [
                _span_seconds(s) for r in rec.records for s in r["spans"]
                if s[0] == name
            ]
        assert len(on_plane) == len(in_record) == 3
        assert on_plane == pytest.approx(in_record, abs=1e-3), name
    # nested on the plane as in the record: inner sits inside its drain
    drains = [(s, s + d) for n, s, d in events if n == "drain"]
    for _n, s, d in (e for e in events if e[0] == "inner"):
        assert any(a <= s and s + d <= b for a, b in drains)


def test_telemetry_spans_are_on_the_profiler_plane_too(tmp_path):
    tele = Telemetry(peer="p0")
    profile.start_session(str(tmp_path))
    try:
        with tele.span("mm.form_group", round_id="r1"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = [
        ev for spans in profile.load_profile(str(tmp_path))["hosts"].values()
        for ev in spans
    ]
    (span,) = [ev for ev in events if ev[0] == "mm.form_group"]
    (logged,) = [e for e in tele.events if e["event"] == "mm.form_group"]
    assert span[2] / 1e9 == pytest.approx(logged["dur_s"], abs=1e-3)


# -------------------------------------------------------------- attribute_idle


def _ms(*events):
    return [(n, s * 1e6, d * 1e6) for n, s, d in events]


def test_attribute_idle_on_a_synthetic_profile():
    """Two devices; the idler one decides. Its gaps: 10-20 ms (the host in
    ``opt_apply``, then ``backup_launch``), 120-130 ms (``data_wait`` then
    ``fwd_bwd`` on peer 0; peer 1 in ``avg_wire``), 230-232 ms (between spans:
    the root).
    A 50 us helper program stays inside its gap."""
    synthetic = {
        "devices": {
            "/device:TPU:0": _ms(
                ("jit_accumulate_step(1)", 0, 10),
                ("jit_convert_element_type(2)", 12, 0.05),
                ("jit_accumulate_step(1)", 20, 100),
                ("jit_accumulate_step(1)", 130, 100),
                ("jit_accumulate_step(1)", 232, 10),
            ),
            "/device:TPU:1": _ms(
                ("jit_accumulate_step(1)", 0, 120),
                ("jit_accumulate_step(1)", 121, 121),
            ),
        },
        "hosts": {
            "bench-peer0": _ms(
                ("boundary", 0, 125), ("boundary", 125, 120),
                ("opt_apply", 5, 11), ("h2d_result", 6, 2),
                ("backup_launch", 16, 6), ("data_wait", 110, 15),
                ("data_wait", 125, 4.5), ("fwd_bwd", 129.5, 1),
                ("fwd_bwd", 225, 5),
            ),
            "bench-peer1": _ms(
                ("boundary", 100, 100), ("avg_wire", 110, 80),
                ("d2h_stream", 121, 4),
            ),
            "dht-loop": _ms(("mm.form_group", 119, 3)),
        },
    }
    result = profile.attribute_idle(synthetic)
    assert result["device"] == "/device:TPU:0"
    assert result["idle_s"] == pytest.approx(0.022)
    assert result["window_s"] == pytest.approx(0.242)
    peer0 = result["threads"]["bench-peer0"]
    assert peer0["peer"] and peer0["idle_s"] == pytest.approx(0.022)
    assert peer0["spans"] == pytest.approx({
        "opt_apply": 0.006, "backup_launch": 0.004,
        "data_wait": 0.005 + 0.0045, "boundary": 0.002,
        "fwd_bwd": 0.0005,
    })
    assert peer0["named_share"] == pytest.approx(1 - 0.002 / 0.022)
    # the other peer thread is named too, over what ITS records bracket
    peer1 = result["threads"]["bench-peer1"]
    assert peer1["peer"] and peer1["spans"] == pytest.approx(
        {"avg_wire": 0.006, "d2h_stream": 0.004}
    )
    # a thread with spans but no records (a DHT loop): reported, not a peer
    loop = result["threads"]["dht-loop"]
    assert not loop["peer"]
    assert loop["spans"] == pytest.approx({"mm.form_group": 0.002})


def test_attribute_idle_needs_a_device_plane():
    with pytest.raises(ValueError, match="no device plane"):
        profile.attribute_idle({"devices": {}, "hosts": {}})


def test_profile_cli_prints_gaps_by_span(tmp_path, capsys):
    saved = tmp_path / "p.json.gz"
    profile.save_profile({
        "devices": {"/device:TPU:0": _ms(("jit_a(1)", 0, 10), ("jit_a(1)", 20, 10))},
        "hosts": {"main": _ms(("boundary", 0, 30), ("drain", 9, 12))},
    }, str(saved))
    assert profile.main([str(saved)]) == 0
    out = capsys.readouterr().out
    assert "idle 0.0100 s of 0.0300 s" in out
    assert "peer thread main" in out and "100.0 % under a named span" in out
    assert "drain" in out


def test_attribute_idle_on_a_recorded_chip_profile():
    """``albert_large_s512.solo`` on a TPU v5e, 30 boundaries profiled
    through ``--telemetry.profile_dir`` (my chip run, PR 23): the host's
    spans and the device's programs of ONE xplane, in the neutral form. The
    clocks are shared to a millisecond or two: the host's ``drain`` (a read
    that waits for the queued accumulates) returns just after the last
    ``accumulate_step`` ends on the device, the solo boundary's programs
    start on the device inside the host spans that launch them — and so
    the idle time between programs can be charged to spans by name."""
    assert os.path.getsize(FIXTURE) <= 200 * 1024
    recorded = profile.load_saved(FIXTURE)
    (device,) = recorded["devices"]
    (thread,) = recorded["hosts"]
    programs, spans = recorded["devices"][device], recorded["hosts"][thread]

    def host(name):
        return [(s, s + d) for n, s, d in spans if n == name]

    def on_device(name):
        return [(s, s + d) for n, s, d in programs if n.startswith(f"jit_{name}(")]

    assert len(host("boundary")) == 30 and len(on_device("accumulate_step")) == 60
    drains = host("drain")
    assert len(drains) == 2  # two global steps
    for _start, end in drains:
        last_accumulate = max(
            e for _s, e in on_device("accumulate_step") if e <= end + 5e6
        )
        assert 0 <= end - last_accumulate <= 5e6  # ns: read 1.5 ms on the chip
    for launched, span in (("_fused_mean_clip", "grad_flatten"),
                           ("guarded_apply_step", "opt_apply")):
        for start, _end in on_device(launched):
            assert any(a <= start <= b for a, b in host(span)), (launched, span)

    result = profile.attribute_idle(recorded)
    row = result["threads"][thread]
    assert result["device"] == device and row["peer"]
    assert result["idle_s"] == pytest.approx(0.156, abs=1e-3)
    assert row["idle_s"] == pytest.approx(result["idle_s"])
    assert row["named_share"] >= 0.99
    # after a drain the device waits for the boundary's own host work
    assert list(row["spans"])[:3] == ["collab", "backup_launch", "acc_reset"]


# ------------------------------------------------------------ slow-step notice


def test_slow_global_step_is_one_info_line(caplog):
    rec = StepRecorder()

    def global_step(clock, step, wire):
        for boundary in range(3):
            with rec.step(step=step) as srec:
                with steps.phase("fwd_bwd"):
                    clock.advance(0.25)
                if boundary == 1 and wire > 1:
                    # the backup thread's transfer, attached beside whatever
                    # this thread was doing: only the slow step has one
                    now = registry.monotonic_clock()
                    steps.attach("backup_transfer", now - 1.5, now)
                if boundary == 2:
                    with steps.phase("avg_wire"):
                        start = registry.monotonic_clock()
                        clock.advance(wire)
                        steps.attach("allreduce", start, start + wire)
                        steps.attach(
                            "ar_encode", start, start + wire,
                            parent="allreduce", count=4, total_s=wire / 2,
                        )
                    srec.attrs["stepped"] = True

    package_logger = logging.getLogger("dedloc_tpu")  # does not propagate
    package_logger.addHandler(caplog.handler)
    try:
        with scripted_clock() as clock:
            for step in range(8):
                global_step(clock, step, 0.25)
            assert not [r for r in caplog.records if "slow global" in r.message]
            global_step(clock, 8, 2.0)  # 2.75 s against a median of 1.0 s
            global_step(clock, 9, 0.25)
    finally:
        package_logger.removeHandler(caplog.handler)
    lines = [r for r in caplog.records if "slow global step" in r.message]
    assert len(lines) == 1
    assert lines[0].levelno == logging.INFO  # a WARNING is a failed step
    message = lines[0].getMessage()
    assert "slow global step 8: 2.750 s against a median of 1.000 s" in message
    assert "avg_wire 2.000 (+1.750)" in message
    assert message.index("avg_wire") < message.index("fwd_bwd")
    # what OTHER threads ran beside the held step (attached spans never
    # enter ``totals``), against their own medians, marked as off-thread
    own, _, beside = message.partition("; on other threads beside it: ")
    assert "backup_transfer" not in own and "allreduce" not in own
    assert "allreduce 2.000 (+1.750)" in beside
    assert "backup_transfer 1.500 (+1.500)" in beside
    assert "ar_encode 1.000 (+0.875)" in beside  # a folded span: its total_s
    assert beside.index("allreduce") < beside.index("ar_encode")
    # and the step's holds, by the span rule (2.0 s against a usual 0.25)
    assert message.endswith("; held: avg_wire +1.750")
    (held,) = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("held: ")
    ]
    assert held.startswith("held: span=avg_wire 2.000 s (usual 0.250)")
    # beside it, with where each began and ended against the hold's start
    # (the backup's transfer was another boundary's: not beside this span)
    assert "backup_transfer" not in held
    assert "beside: allreduce +0.000..+2.000, ar_encode +0.000..+2.000" in held
    # the record's own counters close the line (0 on a scripted clock)
    assert held.endswith(
        "| step 8 boundary 26: 2.250 s, cpu 0.000 of them 0.000 in the "
        "kernel, faults 0+0, preempted 0"
    )


# ------------------------------------------------------------ the hold record


class FastRecorder(StepRecorder):
    """The hold rule at a tenth of its scale, so a case takes well under a
    second: a span is held past max(50 ms, 2x its usual)."""

    HOLD_MIN_S = 0.05
    WATCH_PERIOD_S = 0.01
    SAMPLE_PERIOD_S = 0.005


def spin(seconds):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def run_spans(rec, name, durations, work=time.sleep, last_work=None):
    """One global step a duration, each with ONE span called ``name`` (the
    first, behind the set-up, only shows what is usual). The real-time cases
    warm up with exactly HOLD_MIN_SPANS short spans: none of THOSE can fire,
    however late a loaded machine wakes a sleep."""
    for index, seconds in enumerate(durations):
        last = index == len(durations) - 1
        with rec.step(step=index) as boundary:
            boundary.attrs["stepped"] = True  # each boundary a global step
            with steps.phase(name):
                (last_work if last and last_work else work)(seconds)
    return rec.records[-1]


@pytest.fixture
def package_log(caplog):
    package_logger = logging.getLogger("dedloc_tpu")  # does not propagate
    package_logger.addHandler(caplog.handler)
    caplog.set_level(logging.INFO, logger="dedloc_tpu")
    try:
        yield caplog
    finally:
        package_logger.removeHandler(caplog.handler)


def held_lines(caplog):
    return [r for r in caplog.records if r.getMessage().startswith("held: ")]


def once_more(case):
    """A case on the machine's own clock gets a second go before it fails:
    six workers run beside it, and a watcher brought late or a thread kept
    off its CPU says nothing about the recorder."""

    @functools.wraps(case)
    def run(**fixtures):
        try:
            return case(**fixtures)
        except AssertionError:
            for fixture in fixtures.values():
                if hasattr(fixture, "clear"):  # the log caught so far
                    fixture.clear()
            return case(**fixtures)

    return run


@functools.lru_cache(maxsize=None)
def kernel_says():
    """Which of the files a hold's entry MAY carry this kernel gives for a
    sleeping thread: gVisor has no ``wchan``, no ``syscall`` and no
    /proc/pressure, and the recorder rightly leaves them empty there."""
    tids = []

    def sleeper():
        tids.append(threading.get_native_id())
        time.sleep(0.3)

    thread = threading.Thread(target=sleeper)
    thread.start()
    while not tids:
        time.sleep(0.001)
    time.sleep(0.05)
    task = f"/proc/self/task/{tids[0]}"
    found = {
        name for name in ("wchan", "syscall")
        if steps._read(f"{task}/{name}").strip() not in ("", "0")
    }
    if steps._read("/proc/pressure/cpu"):
        found.add("psi")
    thread.join()
    return found


@pytest.mark.parametrize("telemetry_on", [False, True])
@once_more
def test_a_sleeping_span_is_held_and_sampled_while_it_sleeps(
    package_log, telemetry_on
):
    tele = Telemetry(peer="p0") if telemetry_on else None
    rec = FastRecorder(telemetry=tele)
    slept = []

    def sleep(seconds):
        start = time.monotonic()
        time.sleep(seconds)
        slept.append(time.monotonic() - start)

    try:
        record = run_spans(rec, "d2h_stream", [0.005] * 3 + [0.4], work=sleep)
    finally:
        rec.close()
    assert all(not r["holds"] for r in list(rec.records)[:-1])
    (hold,) = record["holds"]
    assert hold["span"] == "d2h_stream" and hold["parent"] is None
    usual = sorted(slept[:3])[1]
    assert hold["usual_s"] == pytest.approx(usual, abs=0.005)
    # the overrun, to 50 ms of the test's own reading of the same sleep
    assert hold["excess_s"] == pytest.approx(slept[3] - usual, abs=0.05)
    assert hold["excess_s"] == pytest.approx(
        hold["held_s"] - hold["usual_s"], abs=1e-5
    )
    assert record["held_excess_s"] == hold["excess_s"]
    # the operating system's view, taken WHILE the thread slept
    assert hold["samples"] >= 1
    assert hold["state"].get("S", 0) > hold["samples"] / 2
    assert hold["cpu_s"] < 0.02
    assert any("run_spans" in frame for frame in hold["frames"])
    # the closing reading, the watcher's too: the record waited for it
    assert hold["busy_threads"] is not None and "minflt" in hold["psi"]
    # ONE line at INFO, telemetry on or off; a WARNING is a failed step
    (line,) = held_lines(package_log)
    assert line.levelno == logging.INFO
    message = line.getMessage()
    assert message.startswith("held: span=d2h_stream 0.")
    assert " state S " in message
    assert not [r for r in package_log.records if r.levelno > logging.INFO]
    # what only some kernels say (not gVisor: docs/observability.md)
    for key in ("wchan", "syscall"):
        assert bool(hold[key]) == (key in kernel_says())
        assert (f" {key} " in message) == (key in kernel_says())
    assert ("cpu_s" in hold["psi"]) == ("psi" in kernel_says())
    if tele is not None:
        snapshot = tele.snapshot()
        assert snapshot["step.holds"] == 1.0
        assert snapshot["step.held_s"] == pytest.approx(hold["excess_s"])
        (summary,) = [
            e for e in tele.events
            if e["event"] == "step.record" and e["holds"]
        ]
        assert summary["held_excess_s"] == hold["excess_s"]


@once_more
def test_a_span_in_a_busy_loop_reads_running_and_its_cpu():
    rec = FastRecorder()
    try:
        record = run_spans(
            rec, "fwd_bwd", [0.005] * 3 + [0.4], last_work=spin
        )
    finally:
        rec.close()
    (hold,) = record["holds"]
    assert hold["state"].get("R", 0) > hold["samples"] / 2
    # ON the CPU for its wall, as far as the machine's other tenants let it
    assert hold["cpu_s"] > 0.1 * hold["held_s"]
    assert record["cpu_s"] >= hold["cpu_s"]


@once_more
def test_every_read_of_proc_is_the_watchers(monkeypatch):
    """A held span's own thread reads nothing at its close — every thread's
    ``stat`` is 16-22 ms of a process that holds a chip — it wakes the
    watcher, and the record has the closing reading all the same."""
    readers = set()

    def logged(real):
        def read(*args):
            readers.add(threading.current_thread().name)
            return real(*args)

        return read

    for name in ("_read", "_threads", "_machine"):
        monkeypatch.setattr(steps, name, logged(getattr(steps, name)))
    rec = FastRecorder()
    try:
        record = run_spans(rec, "drain", [0.005] * 3 + [0.3])
    finally:
        rec.close()
    (hold,) = record["holds"]
    assert hold["samples"] >= 1 and hold["busy_threads"] is not None
    assert hold["watched_s"] <= hold["held_s"]
    assert readers == {"dedloc-hold-watcher"}


@once_more
def test_a_span_that_keeps_the_interpreter_lock_keeps_the_watcher_out(
    package_log,
):
    """One C call that never lets the lock go: no Python thread runs, the
    watcher included. The entry says for how long the watcher got no look
    while the span was open — what tells this from a span between two looks."""
    rec = FastRecorder()

    start = time.monotonic()
    sum(range(2_000_000))
    per_item = (time.monotonic() - start) / 2_000_000

    def in_one_c_call(_seconds):
        sum(range(int(0.6 / per_item)))  # ~0.6 s on any machine

    try:
        record = run_spans(
            rec, "opt_apply", [0.005] * 3 + [0.0], last_work=in_one_c_call
        )
    finally:
        rec.close()
    (hold,) = record["holds"]
    # at most the look it was let in for when the call returned: taken at
    # the hold's END, so its state says nothing of the hold
    assert hold["samples"] <= 1
    assert hold["held_s"] > 0.25
    assert hold["watcher_away_s"] > 0.5 * hold["held_s"]
    assert hold["cpu_s"] > 0.1 * hold["held_s"]
    (line,) = held_lines(package_log)
    assert f"watcher kept out {hold['watcher_away_s']:.3f} s" in line.getMessage()


def test_a_thread_burning_cpu_beside_the_hold_is_first_in_busy_threads():
    stop = threading.Event()
    block = bytes(1 << 22)

    def burn():
        # on a CPU and OFF the interpreter lock, as a runtime's thread is: a
        # pure-Python spin would make every one of the watcher's reads of
        # /proc wait a switch interval for the lock
        while not stop.is_set():
            hashlib.sha256(block).digest()

    burner = threading.Thread(target=burn, name="burner", daemon=True)
    rec = FastRecorder()
    try:
        run_spans(rec, "drain", [0.005] * 3)
        burner.start()
        for _ in range(3):  # a loaded machine may bring the watcher late
            record = run_spans(rec, "drain", [0.4])
            if record["holds"][0].get("busy_threads"):
                break
    finally:
        stop.set()
        rec.close()
        burner.join(timeout=5)
    assert not burner.is_alive()
    (hold,) = record["holds"]
    name, cpu_s, sys_s = hold["busy_threads"][0]
    assert name == "burner" and cpu_s > 0.05 and 0 <= sys_s <= cpu_s
    assert "busy: burner" in steps.hold_line(hold)
    # and where every other Python thread WAS while the span was held
    others = dict(hold["others"])
    assert "test_step_spans.py" in others["burner"]
    assert others["burner"].endswith(" burn")
    assert "others: " in steps.hold_line(hold)


@pytest.mark.parametrize("usual", [0.005, 0.07, 0.7, 2.5])
def test_spans_at_their_usual_length_never_fire(package_log, usual):
    """A usual LONG span is over HOLD_MIN_S every time and never held:
    0.07 s at a median of 0.07 (0.7 at the production rule's ten times:
    ``drain``), SwAV's 2.5 s ``data_wait``. On a scripted clock, where the
    rule at a span's close reads exact durations."""
    rec = FastRecorder()
    try:
        with scripted_clock() as clock:
            run_spans(
                rec, "drain", [usual, 1.2 * usual, 0.8 * usual] * 3
                + [1.9 * usual], work=clock.advance,
            )
    finally:
        rec.close()
    assert all(r["holds"] == [] for r in rec.records)
    assert all(r["held_excess_s"] == 0 for r in rec.records)
    assert not held_lines(package_log)


@pytest.mark.parametrize("case", ["first_three", "set_up"])
def test_the_first_three_spans_and_the_set_up_never_fire(package_log, case):
    rec = FastRecorder()
    try:
        if case == "first_three":
            # the first ones hold compilation: no usual to hold them to
            run_spans(rec, "fwd_bwd", [0.005, 0.005, 0.1])
            assert not any(r["holds"] for r in rec.records)
            run_spans(rec, "fwd_bwd", [0.3])  # a fourth: 3x the longest
        else:
            with steps.setup_record(logging.getLogger("dedloc_tpu.test")):
                run_spans(rec, "fwd_bwd", [0.005] * 4 + [0.3])
                assert not any(r["holds"] for r in rec.records)
                steps.close_setup()
            # nor do its spans, which hold compilation, say what is usual:
            # the first long span BEHIND the set-up is not held to them
            run_spans(rec, "fwd_bwd", [0.1, 0.005, 0.005])
            assert not any(r["holds"] for r in rec.records)
            run_spans(rec, "fwd_bwd", [0.3])
    finally:
        rec.close()
    assert len(rec.records[-1]["holds"]) == 1
    assert len(held_lines(package_log)) == 1


def test_a_span_of_several_usual_lengths_is_held_to_the_longer(package_log):
    """Ouro's ``fwd_bwd`` on the chip (PR 49), at a tenth of its scale: the
    enqueue returns at once while the runtime has room, waits for ONE
    program (278 ms) when it has none and, once a global step, behind the
    apply, for TWO (540 ms) — all usual. The first global step behind the
    set-up only shows them; a median flips between the first two lengths
    and holds the third whenever the shortest has the majority."""
    rec = FastRecorder()
    room, one, two = 0.0002, 0.0278, 0.054

    def global_step(clock, *first_boundary):
        holds = []
        for boundary in range(8):
            with rec.step() as record:
                record.attrs["stepped"] = boundary == 7
                for seconds in first_boundary if boundary == 0 else (room, one):
                    with steps.phase("fwd_bwd"):
                        clock.advance(seconds)
            holds += rec.records[-1]["holds"]
        return holds

    try:
        with scripted_clock() as clock:
            for _ in range(4):  # the first one is not judged: it teaches
                assert global_step(clock, room, room, room, two) == []
            (hold,) = global_step(clock, room, room, room, 0.2)
    finally:
        rec.close()
    assert hold["usual_s"] == approx(one)  # the ninth decile: two is rarer
    assert hold["excess_s"] == approx(0.2 - one)
    assert len(held_lines(package_log)) == 1


@once_more
def test_an_added_child_takes_the_samples_of_the_span_it_held(package_log):
    """``d2h_stream`` is ``add``-ed when ``avg_wire`` already knows how long
    the round waited for it: never open, so the watcher samples
    ``avg_wire`` — and the close hands the samples to the child whose
    overrun explains the parent's."""
    rec = FastRecorder()

    def boundary(wire, d2h):
        with rec.step() as record:
            record.attrs["stepped"] = True
            with steps.phase("avg_wire"):
                time.sleep(wire)
                steps.add("d2h_stream", d2h)

    try:
        for _ in range(3):
            boundary(0.01, 0.002)
        boundary(0.3, 0.285)
    finally:
        rec.close()
    (hold,) = rec.records[-1]["holds"]
    assert hold["span"] == "d2h_stream" and hold["parent"] == "avg_wire"
    assert hold["sampled_in"] == "avg_wire" and "note" not in hold
    assert hold["watcher_away_s"] < 0.25  # it looked all along
    assert "watcher kept out" not in steps.hold_line(hold)
    assert hold["samples"] > 0 and hold["state"].get("S")
    assert hold["excess_s"] == pytest.approx(0.283, abs=1e-5)  # 0.285 - 0.002
    (line,) = held_lines(package_log)
    assert "span=d2h_stream" in line.getMessage()
    assert "sampled in avg_wire" in line.getMessage()


@once_more
def test_two_recorders_on_two_threads_each_get_their_own_hold():
    recorders = [FastRecorder(), FastRecorder()]
    tids = []

    def peer(rec):
        tids.append(threading.get_native_id())
        run_spans(rec, "opt_apply", [0.005] * 3 + [0.4])

    threads = [
        threading.Thread(target=peer, args=(rec,)) for rec in recorders
    ]
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=30)
    finally:
        for rec in recorders:
            rec.close()
    assert not any(thread.is_alive() for thread in threads)
    assert len(set(tids)) == 2
    for rec in recorders:
        assert sum(len(r["holds"]) for r in rec.records) == 1
        (hold,) = rec.records[-1]["holds"]
        assert hold["samples"] >= 1 and hold["state"].get("S")


def scripted_hold():
    rec = FastRecorder()
    try:
        with scripted_clock() as clock:
            record = run_spans(
                rec, "avg_wire", [0.25] * 4 + [2.0], work=clock.advance
            )
    finally:
        rec.close()
    return record


def test_under_a_fake_clock_nothing_is_sampled_and_the_record_says_so():
    record = scripted_hold()
    (hold,) = record["holds"]
    assert hold["samples"] == 0 and hold["note"] == "scripted clock"
    assert hold["excess_s"] == approx(1.75) and hold["usual_s"] == approx(0.25)
    assert "state" not in hold and "busy_threads" not in hold
    assert "others" not in hold
    assert "unsampled (scripted clock)" in steps.hold_line(hold)
    # the host's counters have no place on a scripted timeline
    for key in ("cpu_s", "sys_s", "minflt", "majflt", "nivcsw"):
        assert record[key] == 0
    assert "cpu" not in record and "nvcsw" not in record
    again = scripted_hold()
    again["spans"] = approx(again["spans"][0][2:])
    record["spans"] = record["spans"][0][2:]
    again["holds"][0]["t0_s"] = approx(again["holds"][0]["t0_s"])
    assert record == again  # as deterministic as it was


def test_after_the_last_close_no_watcher_thread_is_alive():
    import gc

    def watchers():
        return [
            t for t in threading.enumerate()
            if t.name == "dedloc-hold-watcher"
        ]

    gc.collect()  # recorders other tests of this process never closed
    for leaked in list(steps._WATCHER._recorders):
        leaked.close()
    assert not watchers()
    first, second = FastRecorder(), FastRecorder()
    assert len(watchers()) == 1  # ONE a process, shared
    first.close()
    assert len(watchers()) == 1
    second.close()
    assert not watchers()
    # and a recorder nobody closes goes with its last reference
    third = FastRecorder()
    assert len(watchers()) == 1
    del third
    gc.collect()
    deadline = time.monotonic() + 5
    while watchers() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not watchers()
