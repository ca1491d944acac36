"""The conv-hybrid expert decoder's cell: its files say what the source and
the issue say; ``--rehearse`` runs it on the CPU through the real role (tiny
preset) and reports every metric a CPU can; the float32 reference agrees
with the role at the tiny size by all four comparisons."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_lfm2_lm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2_24b_a2b_s4096.solo"


def _config():
    with open(os.path.join(HERE, "configs", "lfm2_24b_a2b_s4096.json")) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        intermediate_size=11776, moe_intermediate_size=1536,
        num_experts_per_tok=4, routed_scaling_factor=1, conv_L_cache=3,
        norm_eps=1e-5, conv_bias=False, use_expert_bias=True,
        norm_topk_prob=True, max_position_embeddings=128000,
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_parameters"]["rope_theta"] == 1000000
    assert len(config["layer_types"]) == 40  # the published pattern, whole
    assert config["sizes"]["num_experts"] == 64  # the router's width
    assert config["sizes"]["head_dim"] * 32 == 2048
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"
    ]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert config["published"] == dict(
        num_hidden_layers=40, num_dense_layers=2, num_experts=64,
        vocab_size=65536,
    )
    assert config["vocab_size"] * 8 == 65536
    assert "469,285,248" in config["reduced_why"]["bytes"]
    assert "eight chips" in config["deployment"].lower()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f) if e["name"] == "LFM2-24B-A2B"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.lfm2_moe import Lfm2MoeConfig

    cfg = Lfm2MoeConfig(
        num_hidden_layers=5, vocab_size=8192, expert_shard=(0, 8)
    )
    for key, value in config["sizes"].items():
        if hasattr(cfg, key) and not isinstance(getattr(cfg, key), tuple):
            assert getattr(cfg, key) == value, key
    assert cfg.held_experts == (0, config["sizes"]["held_experts"])
    assert list(cfg.layer_types) == config["layer_types"]
    kinds = [kind for _i, kind, _s in cfg.layer_plan]
    assert kinds.count("conv") == config["sizes"]["conv_layers"]
    assert kinds.count("full_attention") == config["sizes"]["attention_layers"]


def test_the_cell_is_the_issues():
    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] in (1, 2)
    assert cell["flags"]["--averager.metadata_expiration"] == 2
    assert config["flags"]["--training.expert_shard"] == "0/8"
    assert config["flags"]["--training.vocab_size"] == 8192
    assert config["flags"]["--training.per_device_batch_size"] == 1
    assert config["flags"]["--training.seq_length"] == 4096
    assert cell["path"] == {
        "required": ["accumulate", "solo_mean", "guarded_apply"],
        "forbidden": ["prepare"],
    }
    # "the NEXT boundary sees the target met": target = rows a step - 2; the
    # issue's 32 boundaries, moved in steps of 4
    target = cell["flags"]["--optimizer.target_batch_size"]
    assert target % 2 == 0 and ((target + 2) // 2 - 32) % 4 == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    mine = [m["name"] for m in declared["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == [
        "lfm2.mfu_pct", "flash_gqa_fwd_roofline",
        "flash_gqa_bwd_tiled_roofline", "short_conv_fwd_roofline",
        "short_conv_bwd_roofline", "lfm2.routed_device_ms",
    ]
    # membership, not "the last entry": later cells come after this one
    (entry,) = [w for w in declared["workloads"] if w["name"] == CELL]
    assert entry["why"] == cell["why"] and entry["config"] == config["name"]


def test_rehearse_lfm2_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3200000011", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stdout[-3000:]
    metrics = result["metrics"]
    assert "smoke.collab.solo_boundary_ms" in metrics  # opted in by the cell
    # every list-less per-layer metric that needs no device trace reads a
    # number here
    for name in ("accumulate.dispatch_ms", "boundary.apply_host_ms",
                 "collab.backup_launch_ms", "collab.drain_ms",
                 "collab.post_step_ms", "collab.report_ms", "data.wait_pct",
                 "device.peak_hbm_gb", "step.untimed_pct"):
        assert f"smoke.{name}" in metrics, name
    # no device trace on the CPU: the trace-read metrics are left out
    for name in ("lfm2.mfu_pct", "flash_gqa_fwd_roofline",
                 "short_conv_bwd_roofline", "lfm2.routed_device_ms"):
        assert f"smoke.{name}" not in metrics
    assert all(name.startswith("smoke.") for name in metrics)


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration does not know its name: the role's
    ``parse`` raises at once (seconds, before any device work), which is
    how the driver learns the cell is measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.setitem(common.MODEL_FAMILIES, "lfm2_24b_a2b", None)
    monkeypatch.delitem(common.MODEL_FAMILIES, "lfm2_24b_a2b")
    config = _config()
    with pytest.raises(ValueError, match="unknown model_size 'lfm2_24b_a2b'"):
        trainer_lfm2_lm.parse(trainer_lfm2_lm.build_argv(
            config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
            False, False,
        ))


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_matches_role(seed):
    config = _config()
    config["check"]["seed"] = seed
    args = trainer_lfm2_lm.parse(trainer_lfm2_lm.build_argv(
        config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
        False, True,
    ))
    result = trainer_lfm2_lm.reference_check(config, args, rehearse=True)
    assert result["ok"], result
    assert result["grad_rel_l2"] > 0.0 and result["dropped_slots"] == 0.0
    assert result["held_experts"] == [0, 8]  # the rehearsal's share: 0/2
    assert len(result["load_max_over_mean"]) == 4  # the period's four layers
    # a check that cannot fail checks nothing: the exact comparison does
    config["check"]["rehearse_tolerance"]["score_abs"] = 0.0
    assert not trainer_lfm2_lm.reference_check(config, args, rehearse=True)["ok"]
