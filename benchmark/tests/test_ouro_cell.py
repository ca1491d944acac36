"""The looped decoder's cell: ``--rehearse`` runs it on the CPU through the
real role (tiny preset) and reports every new metric that needs no device
trace; the float32 reference agrees with the role at the tiny size."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_lm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def test_rehearse_ouro_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ouro_2p6b_s4096.solo", "--seed", "3000000011", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stdout[-3000:]
    metrics = result["metrics"]
    assert metrics["smoke.collab.backup_transfer_ms"]["value"] > 0
    assert "smoke.collab.solo_boundary_ms" in metrics  # opted in by the cell
    # no device trace on the CPU: the trace-read metrics are left out
    assert "smoke.lm.mfu_pct" not in metrics
    assert "smoke.flash_causal_fwd_roofline" not in metrics
    assert all(name.startswith("smoke.") for name in metrics)


@pytest.mark.parametrize("seed", [0, 3])
def test_ouro_reference_matches_role(seed):
    with open(os.path.join(HERE, "configs", "ouro_2p6b_s4096.json")) as f:
        config = json.load(f)
    config["check"]["seed"] = seed
    args = trainer_lm.parse(trainer_lm.build_argv(
        config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
        False, True,
    ))
    result = trainer_lm.reference_check(config, args, rehearse=True)
    assert result["ok"], result
    assert result["grad_rel_l2"] > 0.0 and sum(result["exit_prob"]) == (
        pytest.approx(1.0, abs=1e-4)
    )
