"""The boundary loop's contract (``roles/loop.py``), held to every model
family through its own role entry point at the tiny size: ONE loop iterates
boundaries for ALBERT, Ouro and SwAV, so each behaviour below is asserted
once per family, on two runs per family that the cases share (a first run
to ``max_local_steps`` with a jump of the collaborative counter, a second
that resumes from the first one's checkpoints on a finite source)."""
import contextlib
import dataclasses
import itertools
import logging
from typing import Dict, List

import jax
import numpy as np
import pytest

from dedloc_tpu.collaborative.optimizer import CollaborativeOptimizer
from dedloc_tpu.core.config import (
    CollaborationArguments,
    SwAVCollaborationArguments,
    parse_config,
)
from dedloc_tpu.dht.dht import DHT
from dedloc_tpu.roles.common import model_family
from dedloc_tpu.telemetry import registry
from dedloc_tpu.telemetry.registry import Telemetry
from dedloc_tpu.utils.checkpoint import list_checkpoints

BOUNDARIES = 12  # the first run's max_local_steps
SAVE_STEPS = 3
JUMP = 5  # added to opt.local_step after the second global step
FINITE = 5  # micro-batches of the second run: two boundaries and a half
MODEL_SIZE = {"albert": "tiny", "ouro": "ouro_tiny", "swav": "tiny"}


@dataclasses.dataclass
class Observed:
    """What one run of a role showed from outside."""

    state: object = None  # the last one opt.step returned
    returned: object = None  # what the role returned
    opt: CollaborativeOptimizer = None
    # (opt.local_step before the call, stepped, opt.local_step after)
    opt_calls: List[tuple] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    closed: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"opt": 0, "dht": 0}
    )
    log: List[logging.LogRecord] = dataclasses.field(default_factory=list)
    records: List[dict] = dataclasses.field(default_factory=list)
    setup_records: List[dict] = dataclasses.field(default_factory=list)

    def messages(self, level=logging.INFO):
        return [r.getMessage() for r in self.log if r.levelno >= level]


def _argv(family, out):
    argv = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", MODEL_SIZE[family],
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--training.save_steps", str(SAVE_STEPS),
        "--training.save_total_limit", "100",
        "--training.output_dir", str(out),
        # a global step every two or three boundaries of 2 x 2 samples
        "--optimizer.target_batch_size", "8",
        "--averager.averaging_expiration", "0.3",
        "--averager.min_refresh_period", "0.1",
        "--averager.default_refresh_period", "0.3",
    ]
    if family != "swav":
        argv += ["--training.seq_length", "32"]
    return argv


@contextlib.contextmanager
def _observe(family, jump=0, finite=0, poison=()):
    """Watch a role from outside, as the benchmark does: the optimizer's
    ``step`` / ``report_loss`` / ``shutdown``, the DHT's ``shutdown``, the
    package's log, the step records. ``jump`` bumps the collaborative
    counter after the second global step (what adopting a collaboration's
    counter does); ``finite`` cuts the role's batch source to that many
    micro-batches; ``poison`` turns those micro-batches (by index) to NaN."""
    from dedloc_tpu.roles import swav as swav_role
    from dedloc_tpu.roles import trainer as trainer_role

    seen = Observed()
    orig = (CollaborativeOptimizer.step, CollaborativeOptimizer.report_loss,
            CollaborativeOptimizer.shutdown, DHT.shutdown,
            trainer_role._make_batches, swav_role.synthetic_multicrop_batches)

    def step(self, state, grad_acc, n_acc, samples):
        before = self.local_step
        out = orig[0](self, state, grad_acc, n_acc, samples)
        if out[3] and jump and sum(c[1] for c in seen.opt_calls) == 1:
            self.local_step += jump
        seen.opt, seen.state = self, out[0]
        seen.opt_calls.append((before, bool(out[3]), self.local_step))
        return out

    def report_loss(self, loss):
        seen.losses.append(float(loss))
        return orig[1](self, loss)

    def opt_shutdown(self):
        seen.closed["opt"] += 1
        return orig[2](self)

    def dht_shutdown(self):
        seen.closed["dht"] += 1
        return orig[3](self)

    def source(make):
        def cut(*args, **kwargs):
            batches = make(*args, **kwargs)
            if poison:
                batches = (
                    jax.tree.map(lambda x: np.full_like(x, np.nan), batch)
                    if i in poison else batch
                    for i, batch in enumerate(batches)
                )
            return itertools.islice(batches, finite) if finite else batches
        return cut

    class Capture(logging.Handler):
        def emit(self, record):
            seen.log.append(record)

    handler = Capture(level=logging.INFO)
    package_logger = logging.getLogger("dedloc_tpu")  # does not propagate
    CollaborativeOptimizer.step = step
    CollaborativeOptimizer.report_loss = report_loss
    CollaborativeOptimizer.shutdown = opt_shutdown
    DHT.shutdown = dht_shutdown
    trainer_role._make_batches = source(orig[4])
    swav_role.synthetic_multicrop_batches = source(orig[5])
    package_logger.addHandler(handler)
    tele = registry.install(Telemetry(peer=f"loop-{family}"))
    try:
        yield seen
    finally:
        registry.uninstall(tele)
        package_logger.removeHandler(handler)
        (CollaborativeOptimizer.step, CollaborativeOptimizer.report_loss,
         CollaborativeOptimizer.shutdown, DHT.shutdown,
         trainer_role._make_batches,
         swav_role.synthetic_multicrop_batches) = orig
        seen.records = [
            e for e in tele.events if e["event"] == "step.record"
        ]
        seen.setup_records = [
            e for e in tele.events if e["event"] == "setup.record"
        ]


def _run(family, argv):
    if family == "swav":
        from dedloc_tpu.roles.swav import run_swav

        return run_swav(parse_config(SwAVCollaborationArguments, argv))
    from dedloc_tpu.roles.trainer import run_trainer

    return run_trainer(parse_config(CollaborationArguments, argv))


_RUNS: Dict[str, tuple] = {}


def _runs_of(family, tmp_path_factory):
    """(family, first run, second run, steps saved by the first): the first
    runs ``BOUNDARIES`` boundaries with the counter's jump; the second
    resumes from its checkpoints on ``FINITE`` micro-batches and no step
    limit. Made once per family, shared by the cases."""
    if family in _RUNS:
        return _RUNS[family]
    out = tmp_path_factory.mktemp(f"loop-{family}") / "out"
    argv = _argv(family, out)
    if family == "swav":
        # two of the thirteen flags run_swav used to parse and drop
        argv += ["--averager.plan_follow", "false"]
    with _observe(family, jump=JUMP) as first:
        first.returned = _run(
            family, argv + ["--training.max_local_steps", str(BOUNDARIES)]
        )
    saved = [step for step, _path in list_checkpoints(str(out))]
    with _observe(family, finite=FINITE) as second:
        second.returned = _run(family, argv)
    _RUNS[family] = family, first, second, saved
    return _RUNS[family]


@pytest.fixture(scope="module", params=sorted(MODEL_SIZE))
def runs(request, tmp_path_factory):
    return _runs_of(request.param, tmp_path_factory)


def test_a_start_is_one_setup_record_closed_at_its_first_global_step(runs):
    """Telemetry on (off: ``tests/test_setup_record.py``): every start logs
    ONE ``set-up:`` line and writes ONE ``setup.record`` event, closed at the
    end of the first global step; a run that resumes is a start too, and
    one whose source ends before a global step leaves its record
    ``complete=0``."""
    family, first, second, _saved = runs
    program = "step" if family == "swav" else "accumulate_step"
    for run in (first, second):
        lines = [m for m in run.messages() if m.startswith("set-up:")]
        assert len(lines) == 1 and len(run.setup_records) == 1
        record = run.setup_records[0]
        stepped = any(c[1] for c in run.opt_calls)
        assert record["complete"] is stepped
        assert f"complete={int(stepped)}" in lines[0]
        tree = {s[0]: s for s in record["spans"]}
        for lap in ("init_state", "resume", "first_micro_batch") + (
                        ("first_boundary", "first_post_step") if stepped
                        else ()):
            assert tree[lap][1] is None, lap  # a lap: top level
        assert ("first_boundary" in tree) == stepped
        # the accumulate program's first call lies inside the first
        # micro-batch's lap, traced once this start
        call = tree[f"first_call.{program}"]
        assert call[1] == "first_micro_batch"
        assert tree["first_micro_batch"][2] <= call[2] <= call[3] <= (
            tree["first_micro_batch"][3]
        )
        assert record["traces"][program] == 1
        assert record["compile"]["first_micro_batch"]["programs"] >= 1
        # laps tile the record: nothing of it is under no span
        assert sum(record["phases"].values()) == pytest.approx(record["dur_s"])
        assert record["untimed_s"] == pytest.approx(0.0, abs=1e-6)
        # it closes where the first global step's post_step ends: before the
        # second global step's first record opens
        stepping = [r for r in run.records if r.get("stepped")]
        assert len(stepping) < 2 or record["t"] <= stepping[1]["t"]
    assert first.setup_records[0]["complete"]
    # the step records of the first global step are what they were: the
    # set-up record is their parent in time, not a copy of their spans
    assert not {"first_micro_batch", "first_boundary"} & {
        s[0] for r in first.records for s in r["spans"]
    }
    assert not {"fwd_bwd", "opt_apply", "post_step"} & set(
        first.setup_records[0]["phases"]
    )


def test_record_is_one_span_tree_and_the_loss_is_read_once_a_global_step(runs):
    family, first, _second, _saved = runs
    records = [r for r in first.records if "fwd_bwd" in r["phases"]]
    stepping = [r for r in records if r.get("stepped")]
    assert len(stepping) >= 3 and len(stepping) < len(records)
    for record in records:
        parents = {s[0]: s[1] for s in record["spans"]}
        # the draw, the upload and the enqueue are siblings of what
        # opt.step opens: nothing nests under fwd_bwd
        for name in ("data_wait", "fwd_bwd", "collab"):
            assert parents[name] is None, (name, record["spans"])
        assert "fwd_bwd" not in parents.values()
        assert ("h2d" in parents) == (family == "swav")  # no mesh here
        assert parents.get("h2d") is None
        if record.get("stepped"):
            assert parents["post_step"] is None
            for name in ("loss_sync", "publish", "log"):
                assert parents[name] == "post_step"
        else:
            assert not {"post_step", "loss_sync"} & set(parents)
        assert sum(record["phases"].values()) + record["untimed_s"] == (
            pytest.approx(record["dur_s"])
        )
    # report_loss: exactly once per global step, the mean over its
    # micro-batches (finite, and not a sum: SwAV's and ALBERT's alike)
    assert len(first.losses) == sum(c[1] for c in first.opt_calls)
    assert len(first.losses) == len(stepping)
    assert np.isfinite(first.losses).all()
    logged = [
        float(m.rsplit("loss ", 1)[1]) for m in first.messages()
        if m.startswith("global step ") and ": loss " in m
    ]
    assert logged == pytest.approx(first.losses, abs=1e-4)


def test_the_trace_s_gauge_is_on_every_stepping_record(runs):
    """``remat.kept_bytes``: read on the host off the accumulate step's own
    trace (no output of the device program), logged when the step is traced
    — the policy's name at build — and stamped on every record that made a
    global step; SwAV's role has no remat'd layer and no such gauge."""
    family, first, _second, _saved = runs
    stepping = [r for r in first.records if r.get("stepped")]
    policy = [m for m in first.messages() if m.startswith("remat: ")]
    traced = [
        m for m in first.messages() if m.startswith("accumulate_step traced")
    ]
    if family == "swav":
        assert not policy and not traced
        assert not any("remat.kept_bytes" in r for r in stepping)
        return
    cfg = model_family(MODEL_SIZE[family]).config.named(MODEL_SIZE[family])()
    assert policy == [f"remat: remat_policy={cfg.remat_policy}"]
    (line,) = traced  # one shape of batch, one trace
    kept = int(line.rsplit("kept_bytes=", 1)[1])
    assert kept > 0
    assert [r["remat.kept_bytes"] for r in stepping] == [
        float(kept)
    ] * len(stepping)


def test_the_source_s_draws_are_counted_on_the_stepping_records(runs):
    """``data.draws`` / ``data.draws_ready``: the synthetic multicrop
    source's running totals (``data/multicrop.py``), handed to the loop as
    ``LoopModel.host_counters`` by SwAV's role alone: what they grew by
    since the last stepping record. The tiny batch is built in line, so
    none was ready ahead of its consumer."""
    family, first, _second, _saved = runs
    stepping = [i for i, r in enumerate(first.records) if r.get("stepped")]
    if family != "swav":
        assert not any("data.draws" in r for r in first.records)
        return
    draws = [first.records[i]["data.draws"] for i in stepping]
    # gradient_accumulation_steps 2: two draws a boundary, every one counted
    assert draws == [
        2.0 * (i - before)
        for before, i in zip([-1] + stepping[:-1], stepping)
    ]
    assert {first.records[i]["data.draws_ready"] for i in stepping} == {0.0}
    quiet = set(range(len(first.records))) - set(stepping)
    assert not any("data.draws" in first.records[i] for i in quiet)


def test_max_local_steps_ends_the_run_and_shuts_everything_down(runs):
    _family, first, _second, _saved = runs
    assert len(first.opt_calls) == BOUNDARIES
    assert len(first.records) == BOUNDARIES
    assert f"reached max_local_steps={BOUNDARIES}; stopping" in first.messages()
    assert first.closed == {"opt": 1, "dht": 1}
    assert int(first.state.step) == sum(c[1] for c in first.opt_calls)


def test_a_finite_source_ends_the_run_gracefully(runs):
    _family, _first, second, _saved = runs
    assert second.returned is not None  # returned, did not raise
    assert "the batch source ended; stopping" in second.messages()
    # two whole boundaries reached the optimizer; the half one was dropped
    assert len(second.opt_calls) == FINITE // 2
    assert second.closed == {"opt": 1, "dht": 1}


def test_save_cadence_by_distance_survives_a_jump_of_the_counter(runs):
    family, first, _second, saved = runs
    after = [c[2] for c in first.opt_calls if c[1]]  # local_step per step
    assert after[1] - after[0] == 1 + JUMP
    expected, last = [], 0
    for step in after:
        if step - last >= SAVE_STEPS:
            expected.append(step)
            last = step
    if family == "swav" and after[-1] not in expected:
        expected.append(after[-1])  # run_swav saves when the run ends
    assert saved == expected
    # the jump landed off the multiples: a modulo test fires on other steps
    assert after[1] % SAVE_STEPS and after[1] in saved
    assert saved != [s for s in after if s % SAVE_STEPS == 0]


def test_disk_resume_continues_the_collaborative_counter(runs):
    _family, first, second, saved = runs
    assert any(
        m.startswith("resumed from local checkpoint") for m in second.messages()
    )
    # the second run's first boundary is counted from the newest checkpoint
    assert second.opt_calls[0][0] == saved[-1] > first.opt_calls[0][0]
    assert second.records[0]["step"] == saved[-1]


def test_run_swav_hands_the_averager_flags_to_the_optimizer(tmp_path_factory):
    """``run_swav`` built its optimizer from 28 of the 44 arguments and
    dropped thirteen flags it had parsed; it now goes through
    ``roles/common.build_collaborative_optimizer`` like the trainer."""
    _family, first, _second, _saved = _runs_of("swav", tmp_path_factory)
    assert first.opt.tracker.default_refresh_period == 0.3
    assert first.opt.tracker.min_refresh_period == 0.1
    assert first.opt.averager.plan_follow is False


def test_swav_nan_crops_roll_back_and_leave_finite_parameters(tmp_path):
    """What guards SwAV now that no per-boundary host read of the loss
    raises: the guarded apply's all-finite reduce and rollback, announced
    one boundary late at WARNING. The poisoned round's error-feedback
    residual goes with it (carried, it made every later update non-finite
    too: found here), so training goes on."""
    argv = _argv("swav", tmp_path / "out") + [
        "--training.save_steps", "0",
        "--training.max_local_steps", "12",
    ]
    with _observe("swav", poison=(2,)) as seen:
        state = _run("swav", argv)
    rollbacks = [
        m for m in seen.messages(logging.WARNING)
        if "update was rolled back" in m
    ]
    # the poisoned round, and the next one (its residual was folded in
    # before the verdict was read, one boundary late)
    assert 1 <= len(rollbacks) <= 2, seen.messages(logging.WARNING)
    assert all(
        np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(state.params)
    )
    stepped = sum(c[1] for c in seen.opt_calls)
    assert stepped >= 4 and int(state.step) == stepped - len(rollbacks) >= 1
    # the poisoned step's loss was published as it was; the others finite
    assert np.isnan(seen.losses[0]) and np.isfinite(seen.losses[1:]).all()
