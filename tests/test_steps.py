"""Step-phase flight recorder (telemetry/steps.py): in-situ hot-path
attribution, the overlap-averaging ledger, the swarm-health phase fold and
the ``runlog_summary --steps`` views.

Acceptance scenario (ISSUE 10, loopback + FaultSchedule): a 2-peer run with
an injected data-stall on one peer and a slow wire on the other must come
out of ``runlog_summary --steps`` with ``data_wait`` named dominant on the
first and ``avg_wire`` on the second, with per-peer phase sums within 5% of
the recorded step walls; an overlap-averaging run must report overlap
efficiency ~1 for a round that hid behind accumulation and ~0 when a fault
forces the synchronous fallback.
"""
import concurrent.futures
import importlib.util
import json
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.telemetry import registry, steps
from dedloc_tpu.telemetry.health import build_swarm_health
from dedloc_tpu.telemetry.registry import Telemetry
from dedloc_tpu.telemetry.steps import StepRecorder
from dedloc_tpu.testing.faults import FakeClock, FaultSchedule

pytestmark = pytest.mark.telemetry

spec = importlib.util.spec_from_file_location(
    "runlog_summary",
    Path(__file__).resolve().parent.parent / "tools" / "runlog_summary.py",
)
runlog_summary = importlib.util.module_from_spec(spec)
spec.loader.exec_module(runlog_summary)


# ------------------------------------------------------------ recorder units


def test_recorder_times_but_publishes_nothing_when_telemetry_disabled(
    tmp_path, monkeypatch
):
    monkeypatch.chdir(tmp_path)
    assert registry.active() is None
    rec = StepRecorder()  # no injected registry, no global installed
    with FakeClock() as clock:
        with rec.step(step=1, samples=8) as srec:
            # the timing is always on: the record is live, and the
            # module-level helper times into it
            assert srec is steps.current()
            with steps.phase("data_wait"):
                clock.advance(0.5)
    assert steps.current() is None
    assert rec.records[-1]["phases"] == pytest.approx(
        {"data_wait": 0.5}, abs=2e-3
    )
    # publishing is not: no registry appeared, no file of any kind
    assert registry.active() is None
    assert not list(tmp_path.iterdir())


def test_recorder_records_phases_events_histograms():
    tele = Telemetry(peer="p0")
    rec = StepRecorder(telemetry=tele)
    with FakeClock() as clock:
        with rec.step(step=3, samples=64) as srec:
            assert srec is not None
            with srec.phase("data_wait"):
                clock.advance(0.5)
            # the module-level helper times into the SAME live record —
            # this is how the collaborative optimizer attributes its
            # grad_flatten/avg_wire/opt_apply seams without holding the
            # recorder
            with steps.phase("fwd_bwd"):
                clock.advance(1.0)
            srec.add("avg_wire", 0.25)
            srec.attrs["stepped"] = True
    record = rec.records[-1]
    assert record["step"] == 3 and record["samples"] == 64
    assert record["stepped"] is True
    assert record["phases"]["data_wait"] == pytest.approx(0.5, abs=0.05)
    assert record["phases"]["fwd_bwd"] == pytest.approx(1.0, abs=0.05)
    assert record["phases"]["avg_wire"] == 0.25
    assert record["dominant"] == "fwd_bwd"
    assert record["wall_s"] >= 1.5
    # sums track the wall: untimed residual is only real execution glue
    assert sum(record["phases"].values()) >= 0.95 * record["wall_s"]
    # events: one step.phase per phase + one step.record summary
    names = [e["event"] for e in tele.events]
    assert names.count("step.phase") == 3
    assert names.count("step.record") == 1
    summary = [e for e in tele.events if e["event"] == "step.record"][-1]
    assert summary["dominant"] == "fwd_bwd"
    # histograms ride the snapshot as step.phase.<name>.mean keys — the
    # coordinator's swarm-health fold reads exactly these
    snap = tele.snapshot()
    assert snap["step.phase.data_wait.mean"] == pytest.approx(0.5, abs=0.05)
    assert snap["step.wall.count"] == 1.0


def test_recorder_mfu_gauge_tracks_ring_throughput():
    tele = Telemetry(peer="p0")
    rec = StepRecorder(
        telemetry=tele, model_tflops_per_sample=2.0, peak_tflops=100.0
    )
    with FakeClock() as clock:
        for _ in range(3):
            with rec.step(samples=50):
                with steps.phase("fwd_bwd"):
                    clock.advance(1.0)
    # 50 samples / ~1s → 50 samples/s x 2 TFLOP / 100 TFLOP/s peak = ~1.0
    mfu = tele.gauges["step.mfu"].value
    assert 0.9 <= mfu <= 1.0
    assert rec.records[-1]["mfu"] == pytest.approx(mfu)
    assert tele.gauges["step.samples_per_sec"].value == pytest.approx(
        50.0, rel=0.1
    )


def test_boundary_loop_records_step_phases():
    """The one loop (roles/loop.py) with no model to speak of: every
    boundary is a record, its draw / upload / enqueue are spans."""
    import optax

    from dedloc_tpu.core.config import CollaborationArguments
    from dedloc_tpu.parallel.train_step import TrainState
    from dedloc_tpu.roles.loop import LoopModel, run_boundary_loop

    class NeverSteps:  # what the loop touches of an optimizer and a DHT
        local_step, batch_size_per_step, closed = 0, 1, 0

        def step(self, state, grad_acc, n_acc, samples):
            return state, grad_acc, n_acc, False

        def shutdown(self):
            self.closed += 1

    opt = NeverSteps()
    args = CollaborationArguments()
    args.training.max_local_steps = 3
    tele = registry.install(Telemetry(peer="core"))
    try:
        def micro_step(state, grad_acc, n_acc, batch):
            return grad_acc, n_acc + 1, {"loss": batch * 0.5}

        state = run_boundary_loop(
            args,
            LoopModel(
                batches=iter([jnp.ones([])] * 5), micro_step=micro_step,
                save=None, put=jax.device_put,
            ),
            TrainState.create({"w": jnp.zeros([])}, optax.sgd(0.1)),
            opt, opt, b"key", None, lambda: None,
        )
        assert int(state.step) == 0
        assert opt.closed == 2  # as the optimizer and as the DHT
        records = [e for e in tele.events if e["event"] == "step.record"]
        assert len(records) == 3
        assert {"data_wait", "h2d", "fwd_bwd"} <= set(records[-1]["phases"])
    finally:
        registry.uninstall(tele)


# ------------------------------------------------- swarm-health phase fold


def test_swarm_health_folds_phases_mfu_and_overlap():
    from dedloc_tpu.collaborative.metrics import LocalMetrics

    fast = LocalMetrics(
        step=5, samples_per_second=100.0, samples_accumulated=64, loss=2.0,
        mini_steps=4, peer="fast",
        telemetry={
            "step.phase.data_wait.mean": 0.01,
            "step.phase.fwd_bwd.mean": 0.4,
            "step.phase.avg_wire.mean": 0.1,
            "step.mfu": 0.57,
            "opt.overlap_hidden_s": 9.0,
            "opt.overlap_exposed_s": 1.0,
        },
    )
    stalled = LocalMetrics(
        step=5, samples_per_second=10.0, samples_accumulated=64, loss=2.0,
        mini_steps=4, peer="stalled",
        telemetry={
            "step.phase.data_wait.mean": 2.0,
            "step.phase.fwd_bwd.mean": 0.4,
        },
    )
    old_schema = LocalMetrics(
        step=5, samples_per_second=50.0, samples_accumulated=64, loss=2.0,
        mini_steps=4, peer="oldpeer",  # pre-recorder build: no phase keys
    )
    health = build_swarm_health([fast, stalled, old_schema])
    rows = {p["peer"]: p for p in health["peers"]}
    assert rows["fast"]["dominant_phase"] == "fwd_bwd"
    assert rows["fast"]["mfu"] == pytest.approx(0.57)
    assert rows["fast"]["overlap_efficiency"] == pytest.approx(0.9)
    assert rows["stalled"]["dominant_phase"] == "data_wait"
    assert rows["stalled"]["phases"]["data_wait"] == pytest.approx(2.0)
    # tolerant fold: the pre-recorder peer keeps its row, just no phases
    assert "phases" not in rows["oldpeer"]
    assert "overlap_efficiency" not in rows["oldpeer"]


# ------------------------------------------------------- --steps view units


def _write_jsonl(tmp_path, name, rows, tail=""):
    p = tmp_path / name
    text = "\n".join(json.dumps(r) for r in rows) + "\n" + tail
    p.write_text(text)
    return str(p)


def _step_record(peer, step, phases, t=0.0, **extra):
    wall = sum(phases.values()) + extra.pop("untimed_s", 0.0)
    return {
        "t": t, "peer": peer, "event": "step.record", "step": step,
        "dur_s": wall, "samples": 64, "phases": phases,
        "untimed_s": max(0.0, wall - sum(phases.values())), **extra,
    }


def test_runlog_steps_waterfall_skew_and_overlap(tmp_path, capsys):
    rows_a = [
        _step_record("stall", i, {"data_wait": 1.0, "fwd_bwd": 0.2,
                                  "avg_wire": 0.1}, t=float(i))
        for i in range(3)
    ]
    rows_b = [
        _step_record("wire", i, {"data_wait": 0.01, "fwd_bwd": 0.2,
                                 "avg_wire": 0.9}, t=float(i))
        for i in range(3)
    ] + [
        {"t": 3.0, "peer": "wire", "event": "opt.overlap_ledger",
         "round_id": "step3", "mode": "overlap", "hidden_s": 0.8,
         "exposed_s": 0.2, "efficiency": 0.8},
        {"t": 4.0, "peer": "wire", "event": "opt.overlap_ledger",
         "round_id": "step4", "mode": "sync", "hidden_s": 0.0,
         "exposed_s": 1.0, "efficiency": 0.0},
    ]
    pa = _write_jsonl(tmp_path, "a.jsonl", rows_a)
    pb = _write_jsonl(tmp_path, "b.jsonl", rows_b)
    runlog_summary.main(["--steps", pa, pb])
    out = capsys.readouterr().out
    stall_line = next(l for l in out.splitlines() if l.startswith("peer stall"))
    wire_line = next(l for l in out.splitlines() if l.startswith("peer wire"))
    assert "dominant data_wait" in stall_line
    assert "dominant avg_wire" in wire_line
    # skew ranking: the stalled peer's data_wait is the most skewed phase
    assert "phase skew across peers" in out
    skew_section = out.split("phase skew across peers")[1]
    first_skew = skew_section.splitlines()[1]
    assert "data_wait" in first_skew and "stall" in first_skew
    # overlap ledger: per-boundary table + overall efficiency
    assert "| step4 | sync |" in out and "| 0.00 |" in out
    assert "overall overlap efficiency" in out


@pytest.mark.parametrize("as_json", [False, True])
def test_runlog_steps_shows_the_holds_off_the_same_records(
    tmp_path, capsys, as_json
):
    """The recorder's own events through ``--steps``: a peer's header counts
    its holds, and each is a line with what the record says of it."""
    tele = Telemetry(peer="held")
    rec = StepRecorder(telemetry=tele)
    try:
        with FakeClock(frozen=True) as clock:
            for step, wire in enumerate([0.25] * 4 + [2.25]):
                with rec.step(step=step) as record:
                    record.attrs["stepped"] = True
                    with steps.phase("avg_wire"):
                        clock.advance(wire)
    finally:
        rec.close()
    rows = [e for e in tele.events if e["event"].startswith("step.")]
    rows += [_step_record("clean", i, {"avg_wire": 0.25}, t=float(i))
             for i in range(3)]  # an older program's records: no counters
    path = _write_jsonl(tmp_path, "held.jsonl", rows)
    runlog_summary.main((["--json"] if as_json else []) + ["--steps", path])
    out = capsys.readouterr().out
    if as_json:
        doc = json.loads(out)["per_peer"]
        (hold,) = doc["held"]["holds"]
        assert hold["span"] == "avg_wire" and hold["step"] == 4
        assert doc["held"]["held_s"] == pytest.approx(2.0)
        assert doc["clean"]["holds"] == [] and doc["clean"]["held_s"] == 0
        return
    header = next(l for l in out.splitlines() if l.startswith("peer held"))
    assert "holds=1 (2.000s over their usual)" in header
    assert "holds=0" in next(
        l for l in out.splitlines() if l.startswith("peer clean")
    )
    (line,) = [l for l in out.splitlines() if l.startswith("  held step")]
    assert "held step 4: avg_wire 2.250s (usual 0.250)" in line


def test_runlog_steps_survives_jammed_and_truncated_logs(tmp_path, capsys):
    rows = [_step_record("p0", 0, {"data_wait": 0.5, "fwd_bwd": 0.1})]
    jammed = (
        json.dumps(_step_record("p0", 1, {"data_wait": 0.5}))
        + json.dumps(_step_record("p0", 2, {"data_wait": 0.5}))
        + "\n"
        + '{"t": 3, "peer": "p0", "event": "step.record", "trunca'
    )
    path = _write_jsonl(tmp_path, "jam.jsonl", rows, tail=jammed)
    runlog_summary.main(["--steps", path])
    captured = capsys.readouterr()
    assert "steps=3" in captured.out  # both jammed records salvaged
    assert "unparseable fragment" in captured.err


def test_runlog_steps_keeps_degraded_peer_next_to_healthy_one(
    tmp_path, capsys
):
    """Per-peer fallback: a peer whose step.record rows were lost (killed
    mid-write, jammed log) is rebuilt from its bare step.phase events and
    stays IN the waterfall next to a healthy peer — it must not silently
    vanish just because some other peer's records survived."""
    rows = [
        _step_record("healthy", 0, {"data_wait": 0.1, "fwd_bwd": 0.5}),
        # the degraded peer has ONLY per-phase events (no step.record)
        {"t": 1.0, "peer": "degraded", "event": "step.phase",
         "phase": "avg_wire", "dur_s": 2.0, "step": 0},
        {"t": 2.0, "peer": "degraded", "event": "step.phase",
         "phase": "fwd_bwd", "dur_s": 0.5, "step": 0},
    ]
    runlog_summary.main(["--steps", _write_jsonl(tmp_path, "mix.jsonl", rows)])
    out = capsys.readouterr().out
    assert any(l.startswith("peer healthy") for l in out.splitlines())
    degraded = next(
        l for l in out.splitlines() if l.startswith("peer degraded")
    )
    assert "dominant avg_wire" in degraded


def test_runlog_steps_reads_coordinator_health_jsonl(tmp_path, capsys):
    health_row = {
        "t": 1.0,
        "swarm_health": {
            "current_step": 7,
            "peers": [
                {"peer": "fast", "step": 7, "step_time_ms": 700.0,
                 "phases": {"fwd_bwd": 0.6, "data_wait": 0.05},
                 "mfu": 0.55, "overlap_efficiency": 0.93},
                {"peer": "slow", "step": 7, "step_time_ms": 2500.0,
                 "phases": {"fwd_bwd": 0.6, "data_wait": 1.8}},
            ],
        },
    }
    path = _write_jsonl(tmp_path, "coord.jsonl", [health_row])
    runlog_summary.main(["--steps", path])
    out = capsys.readouterr().out
    slow_line = next(l for l in out.splitlines() if l.startswith("peer slow"))
    assert "dominant data_wait" in slow_line
    fast_line = next(l for l in out.splitlines() if l.startswith("peer fast"))
    assert "dominant fwd_bwd" in fast_line and "mfu 0.550" in fast_line
    assert "overlap efficiency (lifetime, per peer)" in out
    assert "fast: 0.93" in out


def test_runlog_steps_exits_helpfully_on_no_step_telemetry(tmp_path):
    path = _write_jsonl(
        tmp_path, "other.jsonl",
        [{"t": 1.0, "peer": "x", "event": "rpc.client.failure"}],
    )
    with pytest.raises(SystemExit) as exc:
        runlog_summary.main(["--steps", path])
    assert "no step-phase telemetry" in str(exc.value)


# --------------------------------------------------------- overlap ledger
# (deterministic delayed-future harness, the test_overlap.py shape)


def _collab_state(step=0, ready=True, peers=2):
    from dedloc_tpu.collaborative.progress import CollaborationState

    return CollaborationState(
        optimizer_step=step,
        samples_accumulated=100 if ready else 0,
        target_batch_size=32,
        num_peers=peers,
        num_clients=0,
        eta_next_step=0.0,
        next_fetch_time=0.0,
        num_aux=0,
        num_peers_at_step=peers,
        num_peers_near_step=peers,
    )


class _StubAverager:
    def __init__(self, real):
        self._real = real
        self.calls = []
        self.pending = None
        self.sync_results = []

    def __call__(self, tree, weight, round_id, return_future=False,
                 expected_size=None, window=None):
        if hasattr(tree, "result") and not isinstance(tree, dict):
            tree = tree.result()  # device-flat FlatFetch -> FlatTree
        self.calls.append({"tree": tree, "return_future": return_future})
        if return_future:
            assert self.pending is None
            self.pending = concurrent.futures.Future()
            return self.pending
        self._real.last_contributors = 2
        return self.sync_results.pop(0)

    def resolve(self, value, contributors=2):
        self._real.last_contributors = contributors
        fut, self.pending = self.pending, None
        fut.set_result(value)


@pytest.fixture
def overlap_opt_with_telemetry():
    from dedloc_tpu.collaborative import CollaborativeOptimizer
    from dedloc_tpu.dht import DHT
    from dedloc_tpu.optim import lamb

    tele = Telemetry(peer="ovl")
    dht = DHT(start=True, listen_host="127.0.0.1")
    opt = CollaborativeOptimizer(
        lamb(0.05, weight_decay=0.0), dht, "ovlsteps",
        target_batch_size=32,
        averaging_expiration=0.5,
        averaging_timeout=5.0,
        allow_state_sharing=False,
        overlap_averaging=True,
        listen_host="127.0.0.1",
        telemetry_registry=tele,
    )
    holder = {"state": _collab_state(), "reports": []}
    opt.tracker.fetch_collaboration_state = (
        lambda force=False: holder["state"]
    )
    opt.tracker.report_local_progress = holder["reports"].append
    stub = _StubAverager(opt.averager)
    opt.averager.step = stub
    try:
        yield opt, stub, holder, tele
    finally:
        opt.shutdown()
        dht.shutdown()


def test_overlap_ledger_reports_hidden_round_as_efficient(
    overlap_opt_with_telemetry,
):
    opt, stub, _holder, tele = overlap_opt_with_telemetry
    params = {"w": jnp.array([[0.5], [0.5]])}
    from dedloc_tpu.parallel import TrainState

    state = TrainState.create(params, opt.tx)
    ones = jax.tree.map(jnp.ones_like, params)
    with FakeClock() as clock:
        # boundary 1: round launched in the background
        state, grad_acc, n_acc, stepped = opt.step(
            state, ones, jnp.asarray(1, jnp.int32), samples=16
        )
        assert stub.pending is not None
        # one boundary of accumulation passes while the round flies
        clock.advance(1.0)
        state, grad_acc, n_acc, stepped = opt.step(
            state, ones, jnp.asarray(1, jnp.int32), samples=8
        )
        assert not stepped
        # the round lands 0.5s later, mid-accumulation
        clock.advance(0.5)
        contrib = stub.calls[0]["tree"]
        stub.resolve(
            ({k: np.full_like(v, 0.25) for k, v in contrib.items()}, 2)
        )
        # harvest boundary: the ledger settles
        state, grad_acc, n_acc, stepped = opt.step(
            state, grad_acc, n_acc, samples=8
        )
        assert stepped
    # the whole ~1.5s round wall was hidden behind accumulation
    assert tele.counters["opt.overlap_hidden_s"].value == pytest.approx(
        1.5, abs=0.2
    )
    assert tele.counters["opt.overlap_exposed_s"].value == pytest.approx(
        0.0, abs=0.1
    )
    assert tele.gauges["opt.overlap_efficiency"].value > 0.9
    ledgers = [e for e in tele.events if e["event"] == "opt.overlap_ledger"]
    assert len(ledgers) == 1 and ledgers[0]["mode"] == "overlap"


def test_overlap_ledger_drops_to_zero_on_sync_fallback(
    overlap_opt_with_telemetry,
):
    """Acceptance: when a fault forces the synchronous fallback, the
    boundary's round runs on the critical path and the ledger must report
    overlap efficiency ~0 (everything exposed, nothing hidden)."""
    opt, stub, _holder, tele = overlap_opt_with_telemetry
    params = {"w": jnp.array([[0.5], [0.5]])}
    from dedloc_tpu.parallel import TrainState
    from dedloc_tpu.parallel.train_step import zeros_like_grads

    state = TrainState.create(params, opt.tx)
    ones = jax.tree.map(jnp.ones_like, params)
    with FakeClock() as clock:
        state, grad_acc, n_acc, stepped = opt.step(
            state, ones, jnp.asarray(1, jnp.int32), samples=16
        )
        assert stub.pending is not None
        # the in-flight round FAILS (the fault): fallback goes synchronous
        stub.resolve((None, 2))

        def slow_sync(tree, weight, round_id, return_future=False,
                      expected_size=None, window=None):
            # the synchronous fallback round takes 2.0 visible seconds ON
            # the trainer's critical path
            assert not return_future
            clock.advance(2.0)
            opt.averager.last_contributors = 2
            if hasattr(tree, "result") and not isinstance(tree, dict):
                tree = tree.result()  # device-flat FlatFetch
            return {k: np.full_like(v, 0.25) for k, v in tree.items()}, 2

        opt.averager.step = slow_sync
        state, grad_acc, n_acc, stepped = opt.step(
            state, zeros_like_grads(params), jnp.zeros([], jnp.int32),
            samples=0,
        )
    assert stepped, "the synchronous fallback round must land"
    ledgers = [e for e in tele.events if e["event"] == "opt.overlap_ledger"]
    sync_ledgers = [e for e in ledgers if e["mode"] == "sync"]
    assert sync_ledgers, f"no sync-fallback ledger event in {ledgers}"
    assert sync_ledgers[-1]["efficiency"] == 0.0
    assert sync_ledgers[-1]["exposed_s"] == pytest.approx(2.0, abs=0.2)
    assert tele.gauges["opt.overlap_efficiency"].value == 0.0


# ----------------------------------------------- 2-peer attribution (E2E)


def test_attribution_data_stall_vs_slow_wire_two_peers(tmp_path, capsys):
    """ISSUE 10 acceptance: loopback 2-peer run, one peer data-stalled, the
    other behind a slow wire (FaultSchedule delay on its averaging RPCs) —
    ``runlog_summary --steps`` over the two event logs names ``data_wait``
    dominant on the stalled peer and ``avg_wire`` on the wire peer, and
    each peer's recorded phase sums cover >= 95% of its step walls."""
    from dedloc_tpu.collaborative import CollaborativeOptimizer
    from dedloc_tpu.dht import DHT
    from dedloc_tpu.optim import lamb
    from dedloc_tpu.parallel import TrainState, make_accumulate_step
    from dedloc_tpu.parallel.train_step import zeros_like_grads

    def toy_loss(params, batch, rng):
        pred = batch["x"] @ params["w"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {"loss": loss}

    logs = {
        "stall": str(tmp_path / "stall.jsonl"),
        "wire": str(tmp_path / "wire.jsonl"),
    }
    teles = {
        name: Telemetry(peer=name, event_log_path=path)
        for name, path in logs.items()
    }
    dht_a = DHT(start=True, listen_host="127.0.0.1")
    dht_b = DHT(start=True, listen_host="127.0.0.1",
                initial_peers=[dht_a.get_visible_address()])
    tx = lamb(0.05, weight_decay=0.0)
    kwargs = dict(
        target_batch_size=64,
        # the window must comfortably cover the injected 2.4s data stall,
        # or the healthy peer forms a singleton round before the stalled
        # one arrives and the slow-wire fault never sees an avg.* RPC
        averaging_expiration=5.0,
        averaging_timeout=20.0,
        min_refresh_period=0.1,
        default_refresh_period=0.3,
        allow_state_sharing=False,
        listen_host="127.0.0.1",
    )
    opts = {
        "stall": CollaborativeOptimizer(
            tx, dht_a, "steps2p", telemetry_registry=teles["stall"], **kwargs
        ),
        "wire": CollaborativeOptimizer(
            tx, dht_b, "steps2p", telemetry_registry=teles["wire"], **kwargs
        ),
    }
    recorders = {
        name: StepRecorder(telemetry=teles[name]) for name in opts
    }
    schedule = FaultSchedule(seed=0)
    wire_client = opts["wire"].averager.client
    schedule.inject(
        "rpc.client.call", "delay", times=-1, delay=0.06,
        match=lambda ctx: (
            str(ctx.get("method", "")).startswith("avg.")
            and ctx.get("client") is wire_client
        ),
    )
    errors = []
    # the stalled peer must get its step-0 progress record onto the bus
    # BEFORE the fast peer's first round launches: with no visible partner
    # the optimizer grants only the short near-step grace, the fast peer
    # rounds as a singleton, steps, exits — and the slow-wire fault never
    # meets an avg.* RPC. The fast peer therefore starts only after the
    # stalled peer's first boundary (fully stalled — its dominance sample)
    # has been reported.
    stall_visible = threading.Event()

    def peer(name, stall_s):
        try:
            if name == "wire":
                assert stall_visible.wait(timeout=60), (
                    "stalled peer never published its first boundary"
                )
            opt, rec = opts[name], recorders[name]
            params = {"w": jnp.array([[0.5], [0.5]])}
            state = TrainState.create(params, tx)
            acc_fn = make_accumulate_step(toy_loss)
            k = jax.random.PRNGKey(0)
            w_true = jnp.array([[1.0], [-2.0]])
            x = jax.random.normal(k, (16, 2))
            batch = {"x": x, "y": x @ w_true}
            grad_acc = zeros_like_grads(params)
            n_acc = jnp.zeros([], jnp.int32)
            stepped = False
            deadline = time.time() + 90
            while not stepped and time.time() < deadline:
                with rec.step(step=opt.local_step, samples=16) as srec:
                    with steps.phase("data_wait"):
                        # the injected input-pipeline stall (peer "stall")
                        # or a healthy fast pipeline (peer "wire")
                        time.sleep(stall_s)
                    with steps.phase("fwd_bwd"):
                        grad_acc, n_acc, _ = acc_fn(
                            state.params, grad_acc, n_acc, batch,
                            jax.random.PRNGKey(0),
                        )
                        jax.block_until_ready((grad_acc, n_acc))
                    state, grad_acc, n_acc, stepped = opt.step(
                        state, grad_acc, n_acc, samples=16
                    )
                    if srec is not None:
                        srec.attrs["stepped"] = stepped
                if name == "stall":
                    stall_visible.set()  # first stalled boundary reported
            assert stepped, f"{name} never performed a global step"
            # the residual's norm was LAUNCHED on the round's path (the
            # ef_norm span syncs nothing) and is read by the next opt.step
            launched = opt._pending_ef_norm
            ran_ef_norm = any(
                s[0] == "ef_norm" for r in rec.records for s in r["spans"]
            )
            assert (launched is not None) == ran_ef_norm
            opt.step(state, grad_acc, n_acc, samples=0)
            assert opt._pending_ef_norm is None
            if ran_ef_norm:
                assert teles[name].gauges[
                    "opt.ef_residual_norm"
                ].value == pytest.approx(float(launched))
        except Exception as e:  # noqa: BLE001
            errors.append((name, e))

    with schedule:
        threads = [
            # 2.4s stall vs 0.06s wire delays: the dominance margin is
            # ~40x and the phase-coverage margin ~2x even when the
            # single-core tier-1 box schedules these threads unfairly
            # (memory/tier1-box-facts.md — was 1.2s, which flaked under
            # full-suite contention)
            threading.Thread(target=peer, args=("stall", 2.4), daemon=True),
            threading.Thread(target=peer, args=("wire", 0.01), daemon=True),
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            for opt in opts.values():
                opt.shutdown()
            dht_b.shutdown()
            dht_a.shutdown()
            for tele in teles.values():
                tele.close()
    assert not errors, errors
    assert schedule.fired, "the slow-wire fault never fired"

    # per-peer phase sums within 5% of the recorded step walls
    for name, rec in recorders.items():
        assert rec.records, f"{name} recorded no steps"
        wall = sum(r["wall_s"] for r in rec.records)
        phase_sum = sum(sum(r["phases"].values()) for r in rec.records)
        assert phase_sum >= 0.95 * wall, (
            f"{name}: phases cover only {phase_sum / wall:.1%} of wall "
            f"(records: {rec.records})"
        )

    # a networked round's record splits avg_wire two ways: its own child
    # d2h_stream, and the averager's reading of the same wall on the DHT
    # loop's thread (last_round_timing) — the wait for the group, then the
    # all-reduce — which together account for the round (the hop onto the
    # loop and back is all that is left out)
    for name, rec in recorders.items():
        networked = [
            r for r in rec.records
            if any(s[0] == "matchmaking" for s in r["spans"])
        ]
        assert networked, f"{name}: no round with the averager's split"
        for record in networked:
            spans = {s[0]: s for s in record["spans"]}
            assert spans["matchmaking"][1] == spans["allreduce"][1] == "avg_wire"
            wire = spans["avg_wire"][3] - spans["avg_wire"][2]
            split = (
                spans["matchmaking"][3] - spans["matchmaking"][2]
                + spans["allreduce"][3] - spans["allreduce"][2]
            )
            assert 0 < split <= wire + 1e-6
            assert split >= wire - 0.05, (wire, split)
            # the attached split is not this thread's time: phases keep
            # today's meaning (avg_wire net of the exposed D2H wait)
            assert "matchmaking" not in record["phases"]
            assert record["phases"]["avg_wire"] == pytest.approx(
                wire - record["phases"].get("d2h_stream", 0.0), abs=1e-6
            )
            # inside allreduce, the averager's span tree (ISSUE 34): the
            # stages of the round's coroutine tile it, the kinds of work the
            # loop thread summed are folded entries beside them — all
            # attached, so none is a phase of this thread
            allreduce = spans["allreduce"][3] - spans["allreduce"][2]
            stages = [
                s for s in record["spans"]
                if s[0] in ("ar_resolve", "ar_prepare", "ar_scatter",
                            "ar_gather", "ar_finish")
            ]
            assert stages and all(s[1] == "allreduce" for s in stages)
            assert sum(s[3] - s[2] for s in stages) == pytest.approx(
                allreduce, abs=1e-3
            )
            assert not [n for n in record["phases"] if n.startswith("ar_")]
            if len(stages) == 5:  # a group of two: the wire was crossed
                for kind in ("ar_encode", "ar_decode", "ar_reduce",
                             "ar_copy", "ar_frame"):
                    assert spans[kind][1] == "allreduce"
                    assert len(spans[kind]) == 6 and spans[kind][4] >= 1
                assert 0 <= record["ar_loop_cpu_s"] <= allreduce + 0.05

    # the operator view: --steps over the two event logs names the phases
    runlog_summary.main(["--steps", logs["stall"], logs["wire"]])
    out = capsys.readouterr().out
    stall_line = next(
        l for l in out.splitlines() if l.startswith("peer stall")
    )
    wire_line = next(l for l in out.splitlines() if l.startswith("peer wire"))
    assert "dominant data_wait" in stall_line, out
    assert "dominant avg_wire" in wire_line, out
