"""Hook pipeline + perf stats (vissl hooks/perf_stats capability)."""
import math
import time

import pytest

from dedloc_tpu.core.hooks import (
    CheckNanLossHook,
    CheckpointHook,
    Hook,
    HookList,
    LogLossLrEtaHook,
    LoopContext,
    MetricsPublisherHook,
    default_hooks,
)
from dedloc_tpu.telemetry.profile import ProfileGate, profile_gate
from dedloc_tpu.utils.perf import PerfStats


class Recorder(Hook):
    def __init__(self):
        self.events = []

    def __getattribute__(self, name):
        if name.startswith("on_"):
            return lambda ctx: object.__getattribute__(self, "events").append(name)
        return object.__getattribute__(self, name)


def test_dispatch_order_and_events():
    r1, r2 = Recorder(), Recorder()
    hooks = HookList([r1, r2])
    ctx = LoopContext()
    for ev in ("on_start", "on_step_begin", "on_loss", "on_step_end", "on_end"):
        hooks.dispatch(ev, ctx)
    assert r1.events == r2.events == [
        "on_start", "on_step_begin", "on_loss", "on_step_end", "on_end",
    ]


def test_dispatch_rejects_unknown_event():
    with pytest.raises(ValueError):
        HookList().dispatch("on_banana", LoopContext())


def test_nan_loss_hook_raises():
    hook = CheckNanLossHook()
    ctx = LoopContext(loss=1.0)
    hook.on_loss(ctx)  # finite: fine
    ctx.loss = float("nan")
    with pytest.raises(FloatingPointError):
        hook.on_loss(ctx)
    ctx.loss = float("inf")
    with pytest.raises(FloatingPointError):
        hook.on_loss(ctx)


def test_checkpoint_hook_cadence():
    saves = []
    hook = CheckpointHook(lambda ctx: saves.append(ctx.local_step), every=3)
    ctx = LoopContext()
    for step in range(1, 8):
        ctx.local_step = step
        hook.on_step_end(ctx)
    hook.on_phase_end(ctx)
    assert saves == [3, 6, 7]  # every-3 plus phase-end


def test_metrics_publisher_fires_on_global_step_advance():
    published = []
    hook = MetricsPublisherHook(lambda ctx: published.append(ctx.global_step))
    ctx = LoopContext()
    for local, global_ in [(1, 0), (2, 0), (3, 1), (4, 1), (5, 2)]:
        ctx.local_step, ctx.global_step = local, global_
        hook.on_step_end(ctx)
    assert published == [0, 1, 2]


def test_default_hooks_compose():
    hooks = default_hooks(save_fn=lambda ctx: None, save_every=10)
    assert len(hooks.hooks) == 4
    ctx = LoopContext(loss=0.5, local_step=10, max_steps=100)
    hooks.dispatch("on_phase_start", ctx)
    hooks.dispatch("on_loss", ctx)
    hooks.dispatch("on_step_end", ctx)


def test_perf_stats_timers():
    stats = PerfStats()
    for _ in range(3):
        with stats.timer("phase_a"):
            time.sleep(0.003)
    s = stats.report()["phase_a"]
    assert s["count"] == 3
    assert s["mean_ms"] >= 2.0
    assert s["min_ms"] <= s["mean_ms"] <= s["max_ms"] + 1e-9
    assert "phase_a" in stats.report_str()


def test_perf_stats_block_on_jax_array():
    import jax.numpy as jnp

    stats = PerfStats()
    with stats.timer("step", block_on=jnp.ones((8, 8)) @ jnp.ones((8, 8))):
        pass
    assert stats.report()["step"]["count"] == 1


def test_perf_stats_disabled_is_noop():
    stats = PerfStats(enabled=False)
    with stats.timer("x"):
        pass
    assert stats.report() == {}


def test_profile_gate_absent_without_dir_or_telemetry():
    from dedloc_tpu.core.config import TelemetryArguments

    assert profile_gate(TelemetryArguments(enabled=True, profile_dir="")) is None
    # the gate sits behind --telemetry.enabled like everything that writes
    assert profile_gate(
        TelemetryArguments(enabled=False, profile_dir="/tmp/x")
    ) is None


def test_profile_gate_writes_its_window(tmp_path):
    import jax.numpy as jnp

    gate = ProfileGate(str(tmp_path), first=1, count=2)
    gate.at_boundary(0)
    assert not any(tmp_path.rglob("*"))  # before the window: nothing started
    for boundary in (1, 2):
        gate.at_boundary(boundary)
        (jnp.ones((4, 4)) * 2).block_until_ready()
    gate.at_boundary(3)  # the window is over: stopped, on its own thread
    gate.close()  # waits until the profile is on disk
    assert any(tmp_path.rglob("*.xplane.pb"))
    gate.close()  # idempotent


def test_device_stats_hook_runs(monkeypatch, caplog):
    import logging

    from dedloc_tpu.core.hooks import DeviceStatsHook

    hook = DeviceStatsHook(log_every=1)
    ctx = LoopContext(local_step=1)
    hook.on_step_end(ctx)  # CPU devices expose no stats -> silently skips
    ctx.local_step = 3
    DeviceStatsHook(log_every=2).on_step_end(ctx)  # off-cadence no-op

    # exercise the formatting/logging branch with a stubbed accelerator
    class FakeDevice:
        platform = "tpu"
        id = 0

        def memory_stats(self):
            return {
                "bytes_in_use": 3 * 2**30,
                "peak_bytes_in_use": 5 * 2**30,
                "bytes_limit": 16 * 2**30,
            }

    import jax

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDevice()])
    # the package logger doesn't propagate to root (own stderr handler), so
    # attach caplog's handler to it directly
    pkg_logger = logging.getLogger("dedloc_tpu.core.hooks")
    pkg_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="dedloc_tpu.core.hooks"):
            DeviceStatsHook(log_every=1).on_step_end(
                LoopContext(local_step=1)
            )
    finally:
        pkg_logger.removeHandler(caplog.handler)
    assert any(
        "3.00GiB in use" in r.getMessage()
        and "peak 5.00GiB" in r.getMessage()
        and "16.00GiB" in r.getMessage()
        for r in caplog.records
    )
