"""utils/perf.py coverage (previously untested): the TPU-native blocking
timer path, the enabled=False no-op, the falsy profiler gate, and the
report formatting BASELINE tables are copied from."""
import pytest

from dedloc_tpu.utils.perf import PerfMetric, PerfStats


def test_timer_block_on_blocks_before_stopping_the_clock():
    """``block_on`` is the TPU analogue of CUDA-event timing: the timer must
    call jax.block_until_ready on the pytree before recording — an async
    dispatch must not be timed as ~0."""
    jnp = pytest.importorskip("jax.numpy")

    stats = PerfStats()
    result = {}
    with stats.timer("forward", block_on=result):
        # the pytree handed to block_on is resolved at exit time, so the
        # value produced INSIDE the block is what gets blocked on
        result["out"] = jnp.arange(128) * 2
    m = stats.metric("forward")
    assert m.count == 1
    assert m.total > 0.0
    # the blocked-on value is fully materialized after the timer exits
    assert int(result["out"][3]) == 6


def test_disabled_stats_record_nothing():
    stats = PerfStats(enabled=False)
    with stats.timer("forward"):
        pass
    with stats.timer("backward", block_on=None):
        pass
    assert stats.metrics == {}, "disabled stats must not allocate metrics"
    assert stats.report() == {}


def test_profile_gate_rejects_a_malformed_window():
    """``--telemetry.profile_boundaries`` is ``<first>:<count>``; anything
    else fails at role start, not at the window."""
    import pytest

    from dedloc_tpu.core.config import TelemetryArguments
    from dedloc_tpu.telemetry.profile import profile_gate

    gate = profile_gate(TelemetryArguments(
        enabled=True, profile_dir="/tmp/p", profile_boundaries="8:4"
    ))
    assert (gate.first, gate.count) == (8, 4)
    for bad in ("8", "a:b", "4:0", "-1:3"):
        with pytest.raises(ValueError, match="profile_boundaries"):
            profile_gate(TelemetryArguments(
                enabled=True, profile_dir="/tmp/p", profile_boundaries=bad
            ))


def test_report_str_formats_known_values():
    stats = PerfStats()
    stats.metric("read_sample").update(0.5)  # 500 ms
    stats.metric("read_sample").update(0.25)  # recent mean 375 ms
    text = stats.report_str()
    lines = text.splitlines()
    assert lines[0].startswith("phase")
    (row,) = [ln for ln in lines[1:] if "read_sample" in ln]
    assert "2" in row  # count
    assert "375.00" in row  # mean/recent over [500, 250]
    assert "500.00" in row  # max
    # reset drops everything back to the bare header
    stats.reset()
    assert stats.report_str().splitlines() == [lines[0]]


def test_perf_metric_window_and_extremes():
    m = PerfMetric()
    for v in (0.1, 0.2, 0.3):
        m.update(v)
    assert m.count == 3
    assert m.min == pytest.approx(0.1)
    assert m.max == pytest.approx(0.3)
    assert m.mean == pytest.approx(0.2)
    s = m.summary()
    assert s["mean_ms"] == pytest.approx(200.0)
    # empty metric reports 0 min (not inf) so tables never print "inf"
    assert PerfMetric().summary()["min_ms"] == 0.0


def test_timer_emits_through_active_telemetry_registry():
    """Unified timing systems (ISSUE 10 satellite): when a telemetry
    registry is active, every PerfStats block timing is ALSO observed into
    its ``perf.<name>`` histogram — one clock source (the FakeClock-aware
    registry monotonic clock), one sink on the metrics bus — instead of
    living only in PerfStats' private store."""
    from dedloc_tpu.telemetry import registry
    from dedloc_tpu.telemetry.registry import Telemetry
    from dedloc_tpu.testing.faults import FakeClock

    tele = registry.install(Telemetry(peer="perf"))
    try:
        stats = PerfStats()
        with FakeClock() as clock:
            with stats.timer("boundary"):
                clock.advance(2.0)
        # the private store still feeds report_str/recent_mean consumers...
        assert stats.metric("boundary").total == pytest.approx(2.0, abs=0.1)
        # ...and the SAME timing (same clock: the fake advance is visible)
        # landed in the registry histogram that rides snapshots
        h = tele.histograms["perf.boundary"]
        assert h.count == 1
        assert h.total == pytest.approx(2.0, abs=0.1)
        assert "perf.boundary.mean" in tele.snapshot()
    finally:
        registry.uninstall(tele)


def test_timer_component_scoped_registry_wins_over_global():
    from dedloc_tpu.telemetry import registry
    from dedloc_tpu.telemetry.registry import Telemetry

    scoped = Telemetry(peer="scoped")
    installed = registry.install(Telemetry(peer="global"))
    try:
        stats = PerfStats(telemetry=scoped)
        with stats.timer("x"):
            pass
        assert "perf.x" in scoped.histograms
        assert "perf.x" not in installed.histograms
    finally:
        registry.uninstall(installed)
