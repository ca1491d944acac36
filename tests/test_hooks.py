"""Perf stats and the profiler gate (vissl perf_stats capability). The hook
pipeline they once sat beside is gone: the loop's log / perf / checkpoint /
publish cadence is tests/test_loop.py's."""
import time

from dedloc_tpu.telemetry.profile import ProfileGate, profile_gate
from dedloc_tpu.utils.perf import PerfStats


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
