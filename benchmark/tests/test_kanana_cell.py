"""The expert decoder's cell: its files say what the source and the issue
say; ``--rehearse`` runs it on the CPU through the real role (tiny preset);
the float32 reference agrees with the role at the tiny size by all four
comparisons."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_moe_lm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(HERE, "configs", "kanana2_30b_a3b_s4096.json")) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        hidden_size=2048, num_attention_heads=32, qk_nope_head_dim=128,
        qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
        kv_lora_rank=512, intermediate_size=6144, moe_intermediate_size=768,
        num_experts_per_tok=6, n_shared_experts=2,
        routed_scaling_factor=2.448, first_k_dense_replace=1,
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["sizes"]["n_routed_experts"] == 128  # the router's width
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    ]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 16032)
    assert config["published"] == dict(
        num_hidden_layers=48, n_routed_experts=128, vocab_size=128256
    )
    assert config["vocab_size"] * 8 == 128256
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f)
                if e["name"] == "kanana-2-30b-a3b-instruct-2601"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.deepseek_v3 import DeepseekV3Config

    cfg = DeepseekV3Config(
        num_hidden_layers=5, vocab_size=16032, expert_shard=(0, 16)
    )
    for key, value in config["sizes"].items():
        if hasattr(cfg, key) and not isinstance(getattr(cfg, key), tuple):
            assert getattr(cfg, key) == value, key
    assert cfg.held_experts == (0, config["sizes"]["held_experts"])


def test_the_cell_is_the_issues():
    with open(os.path.join(
        HERE, "workloads", "kanana2_30b_a3b_s4096.solo.json"
    )) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] == 1
    assert cell["flags"]["--averager.metadata_expiration"] == 2
    assert config["flags"]["--training.expert_shard"] == "0/16"
    assert config["flags"]["--training.per_device_batch_size"] == 1
    assert config["flags"]["--training.seq_length"] == 4096
    assert cell["path"] == {
        "required": ["accumulate", "solo_mean", "guarded_apply"],
        "forbidden": ["prepare"],
    }
    # "the NEXT boundary sees the target met": target = rows a step - 2
    assert cell["flags"]["--optimizer.target_batch_size"] % 2 == 0


def test_rehearse_kanana_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "kanana2_30b_a3b_s4096.solo", "--seed", "3000000011", "--seconds",
         "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stdout[-3000:]
    metrics = result["metrics"]
    assert "smoke.collab.solo_boundary_ms" in metrics  # opted in by the cell
    # no device trace on the CPU: the trace-read metrics are left out
    for name in ("moe_lm.mfu_pct", "flash_mla_fwd_roofline",
                 "moe.routed_device_ms"):
        assert f"smoke.{name}" not in metrics
    assert all(name.startswith("smoke.") for name in metrics)


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_matches_role(seed):
    config = _config()
    config["check"]["seed"] = seed
    args = trainer_moe_lm.parse(trainer_moe_lm.build_argv(
        config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
        False, True,
    ))
    result = trainer_moe_lm.reference_check(config, args, rehearse=True)
    assert result["ok"], result
    assert result["grad_rel_l2"] > 0.0 and result["dropped_slots"] == 0.0
    assert result["held_experts"] == [0, 8]  # the rehearsal's share: 0/2
    # a check that cannot fail checks nothing: the exact comparison does
    config["check"]["rehearse_tolerance"]["score_abs"] = 0.0
    assert not trainer_moe_lm.reference_check(config, args, rehearse=True)["ok"]
