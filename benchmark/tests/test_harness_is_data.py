"""A later PR adds a cell, a configuration, a per-layer metric (and its
reducer) as NEW FILES ONLY. Shown here: a temporary copy of the benchmark
gains all of them, no existing file is touched, and ``--rehearse`` runs the
new cell and reports the new metric."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_new_cell_config_and_metric_are_files_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        HERE, copy / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    os.symlink(os.path.join(ROOT, "dedloc_tpu"), copy / "dedloc_tpu")
    before = {
        p: os.path.getmtime(p)
        for d, _s, fs in os.walk(copy / "benchmark") for p in
        (os.path.join(d, f) for f in fs)
    }

    with open(os.path.join(HERE, "configs", "albert_large_s512.json")) as f:
        config = json.load(f)
    config["name"] = "albert_other"
    config["rehearse_flags"]["--training.seq_length"] = 32
    _write(copy / "benchmark/configs/albert_other.json", config)
    _write(copy / "benchmark/workloads/albert_other.trio.json", {
        "name": "albert_other.trio", "config": "albert_other",
        "traffic": "trio", "chips": 1, "peers": 1, "warmup_steps": 1,
        "why": "test cell", "flags": {
            "--optimizer.target_batch_size": 24,
            "--averager.metadata_expiration": 1,
        },
        "path": {"required": ["accumulate", "solo_mean"], "forbidden": ["prepare"]},
        # opts in to a metric whose own file lists other cells
        "metrics": ["collab.solo_boundary_ms"],
    })
    _write(copy / "benchmark/metrics/collab.step_calls.json", {
        "name": "collab.step_calls", "kind": "per_layer", "unit": "calls",
        "better": "lower", "source": "program_counter",
        "layer": "collaborative step", "moves": "samples_per_s_per_chip",
        "reducer": "count_calls", "workloads": ["albert_other.trio"],
    })
    with open(copy / "benchmark/reducers/count_calls.py", "w") as f:
        f.write(
            "def reduce(run, params):\n"
            "    return float(len(run.opt_calls_in_window(True))"
            " + len(run.opt_calls_in_window(False)))\n"
        )

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "albert_other.trio",
         "--seed", "5", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stdout[-3000:]
    assert result["rehearsal"] is True
    metrics = result["metrics"]
    assert metrics["smoke.collab.step_calls"]["value"] > 0
    assert "smoke.collab.solo_boundary_ms" in metrics  # opted in by the cell
    assert "smoke.data.wait_pct" in metrics  # lists no cells: every cell
    assert "smoke.avg.wire_ms" not in metrics  # lists other cells only
    # no device metric under its own name, ever, from a CPU run
    assert all(name.startswith("smoke.") for name in metrics)
    assert all(m["unit"] == "cpu_count" for m in metrics.values())
    # and nothing that was there was edited
    assert all(os.path.getmtime(p) == t for p, t in before.items())


def test_no_accelerator_means_no_result(tmp_path):
    """Without ``--rehearse`` a CPU-only machine gets exit code 2 and no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "albert_large_s512.solo", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_outside_a_checkout_means_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and ``benchmark/``: exit
    code other than 0, no result."""
    shutil.copytree(
        HERE, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "albert_large_s512.solo", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode not in (0, None)
    assert not any(l.startswith("{") for l in out.stdout.splitlines())
