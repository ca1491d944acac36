"""The block-diffusion expert decoder's cell: its files say what the source
and the issue say; ``--rehearse`` runs it on the CPU through the real role
(tiny preset) and reports every metric a CPU can; the float32 reference
agrees with the role at the tiny size by every comparison."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_sdar_lm as role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "sdar_30b_a3b_s4096.solo"


def _config():
    path = os.path.join(HERE, "configs", "sdar_30b_a3b_s4096.json")
    with open(path) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, moe_intermediate_size=768, num_experts_per_tok=8,
        intermediate_size=6144, rope_theta=1000000, rms_norm_eps=1e-6,
        max_position_embeddings=32768, norm_topk_prob=True,
        tie_word_embeddings=False, rope_scaling=None, hidden_act="silu",
        attention_bias=False, decoder_sparse_step=1, mlp_only_layers=[],
        model_type="sdar_moe", sliding_window=None, use_sliding_window=False,
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["sizes"]["num_experts"] == 128  # the router's width
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"
    ]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    assert config["published"] == dict(
        num_hidden_layers=48, num_experts=128, vocab_size=151936,
    )
    assert config["vocab_size"] * 8 == 151936
    assert "456,346,624" in config["reduced_why"]["bytes"]
    assert "eight chips" in config["deployment"].lower()
    for key in ("block_length", "noise_schedule", "mask_token_id", "target",
                "positions", "qk_norm", "aux_loss", "initializer_range",
                "optimizer"):
        assert key in config["assumed"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f)
                if e["name"] == "SDAR-30B-A3B-Chat"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.sdar_moe import SdarMoeConfig

    cfg = SdarMoeConfig(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 8)
    )
    sizes = role.program_sizes(cfg)
    assert {k: sizes[k] for k in config["sizes"]} == config["sizes"]
    assert cfg.mask_token_id == 18991 and cfg.block_length == 4


def test_the_cell_is_the_issues():
    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] == 1
    assert cell["flags"]["--averager.metadata_expiration"] == 2
    assert config["flags"] == {
        "--training.model_size": "sdar_30b_a3b",
        "--training.num_hidden_layers": 4, "--training.vocab_size": 18992,
        "--training.expert_shard": "0/8", "--training.seq_length": 4096,
        "--training.per_device_batch_size": 1,
    }
    assert cell["path"] == {
        "required": ["accumulate", "solo_mean", "guarded_apply"],
        "forbidden": ["prepare"],
    }
    # "the NEXT boundary sees the target met": target = rows a step - 2
    assert cell["flags"]["--optimizer.target_batch_size"] % 2 == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    mine = [m["name"] for m in declared["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == [
        "sdar.mfu_pct", "flash_bd_fwd_roofline",
        "flash_bd_bwd_tiled_roofline", "sdar.routed_device_ms",
        "flash_bd_bwd_tiled.device_us",
    ]
    # membership, not "the last entry": later cells come after this one
    (entry,) = [w for w in declared["workloads"] if w["name"] == CELL]
    assert entry["why"] == cell["why"] and entry["config"] == config["name"]
    assert config["name"] in [c["name"] for c in declared["configs"]]
    # each limit lies between its two readings, both in the file
    why = config["check"]["tolerance_why"]
    for name in config["check"]["tolerance"]:
        assert name in why, name


def test_rehearse_sdar_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3600000011", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stdout[-3000:]
    metrics = result["metrics"]
    assert "smoke.collab.solo_boundary_ms" in metrics  # opted in by the cell
    for name in ("accumulate.dispatch_ms", "boundary.apply_host_ms",
                 "collab.backup_launch_ms", "collab.drain_ms",
                 "collab.post_step_ms", "collab.report_ms", "data.wait_pct",
                 "device.peak_hbm_gb", "step.untimed_pct"):
        assert f"smoke.{name}" in metrics, name
    # no device trace on the CPU: the trace-read metrics are left out
    for name in ("sdar.mfu_pct", "flash_bd_fwd_roofline",
                 "flash_bd_bwd_tiled_roofline", "sdar.routed_device_ms"):
        assert f"smoke.{name}" not in metrics
    assert all(name.startswith("smoke.") for name in metrics)
    # the role's gauges, on the reference check's line of the log
    line = next(
        line for line in out.stdout.splitlines() if "reference check: " in line
    )
    check = json.loads(line.split("reference check: ", 1)[1])
    assert check["grad_sink_leaves"] == 9.0 and check["dropped_slots"] == 0.0
    assert check["bd_tile_share"] == 1.0  # L = 32: one tile a stream
    assert 0.0 < check["masked_share"] < 1.0
    assert check["masked_tokens"] == check["masked_share"] * 32


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration does not know its name: the role's
    ``parse`` raises at once (seconds, before any device work), which is
    how the driver learns the cell is measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.delitem(common.MODEL_FAMILIES, "sdar_30b_a3b")
    config = _config()
    with pytest.raises(
        ValueError, match="unknown model_size 'sdar_30b_a3b'"
    ):
        role.parse(role.build_argv(
            config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
            False, False,
        ))


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_matches_role(seed):
    config = _config()
    config["check"]["seed"] = seed
    args = role.parse(role.build_argv(
        config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
        False, True,
    ))
    result = role.reference_check(config, args, rehearse=True)
    assert result["ok"], result
    assert result["grad_rel_l2"] > 0.0 and result["dropped_slots"] == 0.0
    assert result["held_experts"] == [0, 8]  # the rehearsal's share: 0/2
    assert len(result["load_max_over_mean"]) == 3  # the tiny preset's layers
    assert result["grad_sink_leaves"] == 9.0  # 3 a routed layer
    # a check that cannot fail checks nothing: the exact comparison does
    config["check"]["rehearse_tolerance"]["logit_abs"] = 0.0
    assert not role.reference_check(config, args, rehearse=True)["ok"]
