"""Driver-facing contracts: bench.py's single JSON line and the graft
entry's jittable forward."""
import json
import os
import subprocess
import sys

import jax
import pytest


def test_bench_tiny_prints_one_json_line():
    env = dict(
        os.environ,
        DEDLOC_BENCH_TINY="1",
        JAX_PLATFORMS="cpu",
    )
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    record = json.loads(json_lines[0])
    required = {"metric", "value", "unit", "vs_baseline"}
    # on TPU the same line carries the MFU block (BENCH_r0*.json schema);
    # the contract is: required keys always, optional keys only from this set
    optional = {"mfu", "model_tflops_per_sample", "chip"}
    assert required <= set(record), record
    assert set(record) <= required | optional, record
    assert record["value"] > 0
    # a CPU smoke never reports under a per-chip metric name
    assert record["metric"] == "albert_tiny_smoke_samples_per_sec"


def test_graft_entry_compiles():
    # the path entry must survive entry()'s lazy dedloc_tpu imports
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import __graft_entry__ as g

    fn, args = g.entry()
    shapes = jax.eval_shape(fn, *args)
    assert shapes is not None


def test_bench_codec_mode_contract():
    env = dict(os.environ, DEDLOC_BENCH="codec", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    record = json.loads(json_lines[0])
    assert record["metric"] == "wirecodec_fp16_serialize_ms"
    assert record["value"] > 0 and record["deserialize_ms"] > 0
    assert record["n_params"] > 17_000_000  # the real ALBERT-large tree


def test_bench_sim_engine_mode_contract():
    """Virtual-time engine bench smoke (DEDLOC_BENCH=sim_engine): the tiny
    roster runs the mixed scenario end-to-end and prints one JSON line with
    the gate-facing keys. The metric name carries the roster size, so this
    100-peer smoke can never gate against a full 1,000-peer round
    (tools/bench_gate.py filters baselines by metric name).
    DEDLOC_BENCH_TIMING=0 skips the 10,000-peer diurnal half — minutes of
    scenario the tier-1 budget cannot carry."""
    env = dict(os.environ, DEDLOC_BENCH="sim_engine",
               DEDLOC_BENCH_TINY="1", DEDLOC_BENCH_TIMING="0",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    record = json.loads(json_lines[0])
    assert record["metric"] == "sim_mixed100_timer_events_per_wall_sec"
    assert record["unit"] == "events/sec"
    assert record["value"] > 0 and record["wall_s"] > 0
    assert record["events_scheduled"] > 0
    assert record["peak_rss_mb"] > 0
    assert record["vs_baseline"] == 1.0  # smoke roster: no anchor
    assert "diurnal_10k" not in record  # the timing half was skipped


def test_bench_serving_mode_contract():
    """Serving-plane bench smoke (DEDLOC_BENCH=serving): the tiny fleet
    runs the serving scenario end-to-end and prints one JSON line with the
    gate-facing keys. The metric name carries the roster size, so this
    40-peer smoke never gates against a full 1,000-peer round."""
    env = dict(os.environ, DEDLOC_BENCH="serving",
               DEDLOC_BENCH_TINY="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    record = json.loads(json_lines[0])
    assert record["metric"] == "serving40_requests_per_wall_sec"
    assert record["unit"] == "requests/sec"
    assert record["value"] > 0 and record["wall_s"] > 0
    assert record["wedged"] == 0
    assert record["served"] + record["requests"] * record[
        "fall_through_rate"] == pytest.approx(record["requests"], abs=1)
    assert record["latency_p99_s"] >= record["latency_p50_s"]


def _run_pipeline_bench(timing=True):
    env = dict(os.environ, DEDLOC_BENCH="allreduce_pipeline",
               DEDLOC_BENCH_TINY="1", JAX_PLATFORMS="cpu",
               DEDLOC_BENCH_TIMING="1" if timing else "0")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    return json.loads(json_lines[0])


def test_bench_allreduce_pipeline_contract():
    """Wire-path bench, deterministic half only (DEDLOC_BENCH_TIMING=0
    skips the seconds of simulated-uplink sleeps — tier-1 budget): one JSON
    line; float16 ~halves and uint8 ~quarters wire bytes per round (the
    framing header keeps the f16 ratio a hair under the ideal 2.0). Timing
    assertions live in the slow-marked variant below — wall-clock ordering
    on a loaded tier-1 box is not a contract."""
    record = _run_pipeline_bench(timing=False)
    assert record["metric"] == "allreduce_pipeline_effective_bytes_per_sec"
    assert record["value"] > 0
    assert record["vs_baseline"] == 0.0  # timing half skipped
    wire = record["wire_bytes_per_round"]
    assert wire["none"] / wire["float16"] >= 1.95, wire
    assert wire["none"] / wire["uint8"] >= 3.5, wire


def _run_grad_pipeline_bench(compression="float16"):
    env = dict(os.environ, DEDLOC_BENCH="grad_pipeline",
               DEDLOC_BENCH_TINY="1", JAX_PLATFORMS="cpu",
               DEDLOC_BENCH_TIMING="0",
               DEDLOC_BENCH_COMPRESSION=compression)
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    return json.loads(json_lines[0])


def test_bench_grad_pipeline_contract():
    """Boundary-seam bench (PR 13), deterministic byte-accounting half
    (DEDLOC_BENCH_TIMING=0): the device-flat pipeline's D2H bytes are
    exactly half the legacy fp32 seam under float16 and ~quarter under
    uint8 (per-block lo/scale meta keeps the ratio a hair under 4.0);
    fp32 ('none') moves the same bytes, just fewer transfers."""
    f16 = _run_grad_pipeline_bench("float16")
    assert f16["metric"] == "grad_pipeline_d2h_bytes_per_boundary"
    assert f16["legacy_d2h_bytes"] == f16["n_params"] * 4
    assert f16["vs_baseline"] == 2.0
    u8 = _run_grad_pipeline_bench("uint8")
    assert u8["vs_baseline"] >= 3.5
    raw = _run_grad_pipeline_bench("none")
    assert raw["vs_baseline"] == 1.0


def _run_restore_bench(timing=True):
    env = dict(os.environ, DEDLOC_BENCH="checkpoint_restore",
               DEDLOC_BENCH_TINY="1", JAX_PLATFORMS="cpu",
               DEDLOC_BENCH_TIMING="1" if timing else "0")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    json_lines = [
        l for l in out.stdout.strip().splitlines() if l.startswith("{")
    ]
    assert len(json_lines) == 1, out.stdout
    return json.loads(json_lines[0])


@pytest.mark.checkpointing
def test_bench_checkpoint_restore_contract():
    """Restore bench, deterministic half (DEDLOC_BENCH_TIMING=0 skips the
    simulated-uplink sleeps): the JSON must record bytes AND provider
    counts for both bootstrap paths, and the sharded path's wire bytes may
    exceed the blob's only by per-shard framing (< 1%)."""
    record = _run_restore_bench(timing=False)
    assert record["metric"] == "checkpoint_restore_sharded_bytes_per_sec"
    assert record["value"] > 0
    assert record["vs_baseline"] == 0.0  # timing half skipped
    assert record["monolithic"]["providers"] == 1
    assert record["sharded"]["providers"] > 1
    state = record["state_bytes"]
    assert state <= record["monolithic"]["wire_bytes"] < state * 1.01
    assert state <= record["sharded"]["wire_bytes"] < state * 1.01
    assert record["num_shards"] >= record["sharded"]["providers"]


@pytest.mark.slow
@pytest.mark.checkpointing
def test_bench_checkpoint_restore_sharded_beats_monolithic():
    """Restore bench, timing half (real sockets + simulated per-provider
    uplinks, so slow-marked): pulling distinct shards from N providers must
    beat the one-uplink blob download."""
    record = _run_restore_bench(timing=True)
    assert record["vs_baseline"] > 1.0, record
    assert record["sharded"]["wall_ms"] < record["monolithic"]["wall_ms"]


@pytest.mark.slow
@pytest.mark.wirepath
def test_bench_allreduce_pipeline_beats_monolithic():
    """Wire-path bench, timing half (real sockets + simulated link, so
    slow-marked per the wirepath test policy): the chunk-streamed pipeline
    must beat the monolithic-span path under the injected per-message
    latency + serialized-uplink model."""
    record = _run_pipeline_bench(timing=True)
    assert record["vs_baseline"] > 1.0, record
    assert record["pipelined_wall_ms"] > 0
    assert record["monolithic_wall_ms"] > 0
    assert record["pipelined_wall_ms"] < record["monolithic_wall_ms"], record


# ------------------------------------------------------------- bench gate
# (tools/bench_gate.py: a recorded trajectory is machine-guarded, mirroring
# t1_budget.py --gate. These tests check the gate's LOGIC on synthetic
# driver records written to tmp_path — they never run the bench and never
# read a committed record.)

import importlib.util

_REPO = os.path.join(os.path.dirname(__file__), "..")
_spec = importlib.util.spec_from_file_location(
    "bench_gate", os.path.join(_REPO, "tools", "bench_gate.py")
)
bench_gate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_gate)

_HEADLINE = "albert_large_train_samples_per_sec_per_chip"
# a five-round trajectory in the driver's record layout, rising then flat
_TRAJECTORY = {
    1: (85.0, None), 2: (85.2, 0.436), 3: (99.2, 0.507),
    4: (112.3, 0.574), 5: (112.6, 0.576),
}


@pytest.fixture
def bench_rounds(tmp_path, monkeypatch):
    """Synthetic BENCH_r01..r05 driver records in tmp_path, installed as
    the gate's default single-chip baseline glob. Returns {round: path}."""
    paths = {}
    for r, (value, mfu) in _TRAJECTORY.items():
        parsed = {"metric": _HEADLINE, "value": value,
                  "unit": "samples/sec", "vs_baseline": value / 10.0}
        if mfu is not None:
            parsed["mfu"] = mfu
        path = tmp_path / f"BENCH_r{r:02d}.json"
        path.write_text(json.dumps(
            {"n": r, "cmd": "python bench.py", "rc": 0, "parsed": parsed}
        ))
        paths[r] = str(path)
    monkeypatch.setattr(
        bench_gate, "DEFAULT_BASELINE_GLOB", str(tmp_path / "BENCH_r*.json")
    )
    return paths


def test_bench_gate_passes_on_trajectory(bench_rounds):
    """The best round of a trajectory gates clean against the default
    BENCH_r*.json glob (no explicit baselines) — so a later, better round
    never breaks the gate."""
    loaded = {r: bench_gate.load_bench(p) for r, p in bench_rounds.items()}
    best = max(loaded, key=lambda r: loaded[r]["value"])
    assert bench_gate.main([bench_rounds[best]]) == 0


def test_bench_gate_catches_synthetic_regression(bench_rounds, tmp_path,
                                                 capsys):
    """Acceptance: a fresh bench JSON regressed >3% on samples/sec exits
    nonzero (and an MFU-only regression is caught independently)."""
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps({
        "metric": _HEADLINE,
        "value": 100.0, "unit": "samples/sec", "vs_baseline": 10.0,
    }))
    assert bench_gate.main([str(slow)]) == 1
    assert "GATE FAILED" in capsys.readouterr().out
    low_mfu = tmp_path / "low_mfu.json"
    low_mfu.write_text(json.dumps({
        "metric": _HEADLINE,
        "value": 112.6, "unit": "samples/sec", "vs_baseline": 11.3,
        "mfu": 0.50,
    }))
    assert bench_gate.main([str(low_mfu)]) == 1
    assert "MFU regressed" in capsys.readouterr().out


def test_bench_gate_tolerates_missing_rounds(bench_rounds):
    """A sparse trajectory (pruned/missing rounds) still gates: r04 vs only
    {r01, r04} passes without r02/r03/r05 existing in the baseline set."""
    assert bench_gate.main(
        [bench_rounds[4], bench_rounds[1], bench_rounds[4]]
    ) == 0


def test_bench_gate_malformed_baseline_warns_not_wedges(bench_rounds,
                                                        tmp_path, capsys):
    """A corrupt baseline artifact warns on stderr and is skipped; the gate
    still judges against the healthy baselines. A corrupt FRESH file is a
    hard error (it IS the thing under test)."""
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    rc = bench_gate.main([bench_rounds[5], str(garbage), bench_rounds[4]])
    captured = capsys.readouterr()
    assert rc == 0
    assert "skipping" in captured.err and "garbage.json" in captured.err
    assert bench_gate.main([str(garbage), bench_rounds[4]]) == 2


def test_bench_gate_unknown_metric_warns_and_passes(tmp_path, capsys):
    """A brand-new metric has no comparable baseline: warn, don't wedge
    (the t1_budget missing-test contract)."""
    novel = tmp_path / "novel.json"
    novel.write_text(json.dumps({
        "metric": "some_new_bench_metric", "value": 1.0,
        "unit": "things/sec", "vs_baseline": 1.0,
    }))
    assert bench_gate.main([str(novel)]) == 0
    assert "no comparable baseline" in capsys.readouterr().out


# ------------------------------------------- MULTICHIP trajectory gate
# (the gated value is the swarm samples/sec derived from a driver record's
# tail: timestamped "global step N applied (group=G, samples~S)" optimizer
# lines. Synthetic records only.)


def _multichip_tail(rates, n_steps=6, samples=48, start="2026-08-02 10:00"):
    """A synthetic driver tail: applied-step lines at 1/rates steps/sec."""
    import datetime

    t = datetime.datetime.strptime(start, "%Y-%m-%d %H:%M")
    lines = []
    for i in range(n_steps):
        stamp = t.strftime("%Y-%m-%d %H:%M:%S.%f")[:-3]
        lines.append(
            f"[{stamp}][INFO][dedloc_tpu.collaborative.optimizer] "
            f"global step {i + 1} applied (group=2, samples~{samples})"
        )
        t += datetime.timedelta(seconds=1.0 / rates)
    return "\n".join(lines) + "\n"


@pytest.fixture
def multichip_rounds(tmp_path, monkeypatch):
    """Synthetic MULTICHIP_r01..r05 driver records: r01-r03 captured only a
    start-up banner (no applied steps), r04/r05 carry a rate. Installed as
    the gate's default multichip baseline glob. Returns {round: path}."""
    tails = {
        1: "WARNING: platform banner only\n",
        2: "WARNING: platform banner only\n",
        3: "WARNING: platform banner only\n",
        4: _multichip_tail(2.0),
        5: _multichip_tail(2.5),
    }
    paths = {}
    for r, tail in tails.items():
        path = tmp_path / f"MULTICHIP_r{r:02d}.json"
        path.write_text(json.dumps({
            "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": tail,
        }))
        paths[r] = str(path)
    monkeypatch.setattr(
        bench_gate, "MULTICHIP_BASELINE_GLOB",
        str(tmp_path / "MULTICHIP_r*.json"),
    )
    return paths


def test_multichip_trajectory_parses_and_gates_clean(multichip_rounds,
                                                     capsys):
    """Rounds whose tail carries applied steps parse to a swarm samples/sec
    under a device-count-scoped metric name; the best round gates clean
    against the default set."""
    loaded = {
        r: bench_gate.load_bench(p) for r, p in multichip_rounds.items()
    }
    capsys.readouterr()  # drain the expected early-round warnings
    parseable = {r: rec for r, rec in loaded.items() if rec is not None}
    assert sorted(parseable) == [4, 5]
    for rec in parseable.values():
        assert rec["metric"] == "multichip8_swarm_samples_per_sec"
        assert rec["value"] > 0 and rec["steps"] >= 2
    # 5 intervals x 48 samples over 5 / 2.5 s
    assert parseable[5]["value"] == pytest.approx(120.0)
    best = max(parseable, key=lambda r: parseable[r]["value"])
    assert bench_gate.main([multichip_rounds[best]]) == 0


def test_multichip_rounds_without_steps_are_absent_not_fatal(
        multichip_rounds, capsys):
    """Rounds whose tail captured only a start-up banner skip with a
    warning — the missing-round rule, not an error."""
    record = bench_gate.load_bench(multichip_rounds[1])
    assert record is None
    assert "applied-step" in capsys.readouterr().err
    # ...and their presence in the baseline set never wedges a gate
    assert bench_gate.load_bench(multichip_rounds[5]) is not None
    assert bench_gate.main(
        [multichip_rounds[5]] + [multichip_rounds[r] for r in (1, 4, 5)]
    ) == 0


def test_multichip_gate_catches_synthetic_regression(multichip_rounds,
                                                     tmp_path, capsys):
    """A fresh multichip round 50% slower than the recorded trajectory
    exits 1; a failed/skipped fresh round is exit 2 (not gateable); a
    different device count gates its own (empty) trajectory and passes as
    the bootstrap case."""
    best = max(
        (bench_gate.load_bench(multichip_rounds[r]) for r in (4, 5)),
        key=lambda rec: rec["value"],
    )
    capsys.readouterr()
    slow_rate = best["value"] / 48 / 2.0  # steps/sec at half throughput
    slow = tmp_path / "slow_multichip.json"
    slow.write_text(json.dumps({
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": _multichip_tail(slow_rate),
    }))
    assert bench_gate.main([str(slow)]) == 1
    assert "GATE FAILED" in capsys.readouterr().out

    failed = tmp_path / "failed_multichip.json"
    failed.write_text(json.dumps({
        "n_devices": 8, "rc": 1, "ok": False, "skipped": False,
        "tail": _multichip_tail(10.0),
    }))
    assert bench_gate.main([str(failed)]) == 2

    other_devices = tmp_path / "multichip4.json"
    other_devices.write_text(json.dumps({
        "n_devices": 4, "rc": 0, "ok": True, "skipped": False,
        "tail": _multichip_tail(1.0),
    }))
    assert bench_gate.main([str(other_devices)]) == 0
    assert "no comparable baseline" in capsys.readouterr().out
