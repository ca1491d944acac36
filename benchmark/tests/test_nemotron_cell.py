"""Nemotron-H's cell: its files say what the source and the issue say;
``--rehearse`` runs it on the CPU through the real role (tiny preset) and
reports every metric a CPU can; the float32 reference agrees with the role
at the tiny size by every comparison, the Mamba mixers' small leaves among
them; a program without the model fails in ``parse``."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_nemotron_lm as role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3_nano_30b_a3b_s8192.solo"
CONFIG = "nemotron3_nano_30b_a3b_s8192"
TRACE_METRICS = [
    "nemotron.mfu_pct", "nemotron.ssd_fwd_roofline",
    "nemotron.ssd_bwd_roofline", "nemotron.ssd_device_ms",
    "nemotron.flash_gqa_fwd_roofline",
    "nemotron.flash_gqa_bwd_tiled_roofline", "nemotron.routed_device_ms",
]
GAUGE_METRICS = [
    "nemotron.ssd_dt_mean", "nemotron.ssd_chunk_log_decay_min",
    "nemotron.ssd_state_abs_max",
]
LIMITS = {
    "loss_rel", "grad_rel_l2", "leaf_rel_l2", "ssd_leaf_rel_l2", "score_abs",
    "choice_disagree_share", "load_abs",
}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size", "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads"]


def _config():
    path = os.path.join(HERE, "configs", f"{CONFIG}.json")
    with open(path) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        model_type="nemotron_h", hidden_size=2688, mamba_head_dim=64,
        ssm_state_size=128, conv_kernel=4, chunk_size=128, expand=2,
        head_dim=128, intermediate_size=1856, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, num_experts_per_tok=6,
        n_shared_experts=1, routed_scaling_factor=2.5, n_group=1,
        topk_group=1, norm_topk_prob=True, mlp_hidden_act="relu2",
        mamba_hidden_act="silu", use_conv_bias=True, use_bias=False,
        mamba_proj_bias=False, attention_bias=False, mlp_bias=False,
        layer_norm_epsilon=1e-5, norm_eps=1e-5, tie_word_embeddings=False,
        rescale_prenorm_residual=True, time_step_min=0.001,
        time_step_max=0.1, time_step_floor=0.0001, rope_theta=10000,
        partial_rotary_factor=1, max_position_embeddings=262144,
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == REDUCED
    assert [config[key] for key in REDUCED] == [
        7, "MEMEM*E", 8, 16384, 32, 4, 16, 1
    ]
    assert config["published"] == {
        "num_hidden_layers": 52,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "n_routed_experts": 128, "vocab_size": 131072, "mamba_num_heads": 64,
        "n_groups": 8, "num_attention_heads": 32, "num_key_value_heads": 2,
    }
    assert config["published"]["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"]
    )
    assert config["vocab_size"] * 8 == 131072
    assert "458,281,632" in config["reduced_why"]["bytes"]
    assert "31,577,940,288" in config["reduced_why"]["bytes"]
    assert "9-window" in config["reduced_why"]["num_hidden_layers"]
    deployment = config["deployment"].lower()
    for said in ("16 chips", "pairs of chips", "vocabulary over 8",
                 "pipeline stages", "partial sum", "what the cut distorts",
                 "29.5 %", "cheap by design"):
        assert said in deployment, said
    for key in ("rotary_embedding", "gate_and_norm", "in_projection_order",
                "initialisers", "router", "bias_update", "aux_loss",
                "weight_decay", "optimizer", "data", "remat"):
        assert key in config["assumed"], key
        if key in ("rotary_embedding", "gate_and_norm", "initialisers"):
            assert "Not taken" in config["assumed"][key], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f)
                if e["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig(
        num_hidden_layers=7, vocab_size=16384, expert_shard=(0, 16),
        head_shard=(0, 2),
    )
    assert role.program_sizes(cfg) == config["sizes"]
    assert cfg.layer_kinds == config["hybrid_override_pattern"]
    assert NemotronHConfig().hybrid_override_pattern == config["published"][
        "hybrid_override_pattern"
    ]
    assert cfg.remat_policy in config["assumed"]["remat"]


def test_the_cell_is_the_issues():
    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] == 1
    # a global step every 16 boundaries = 32 rows of 8,192
    assert cell["flags"] == {
        "--optimizer.target_batch_size": 30,
        "--averager.metadata_expiration": 2,
    }
    assert config["flags"] == {
        "--training.model_size": "nemotron3_nano_30b_a3b",
        "--training.num_hidden_layers": 7, "--training.vocab_size": 16384,
        "--training.expert_shard": "0/16", "--training.head_shard": "0/2",
        "--training.seq_length": 8192,
        "--training.per_device_batch_size": 1,
    }
    assert cell["path"] == {
        "required": ["accumulate", "solo_mean", "guarded_apply"],
        "forbidden": ["prepare"],
    }
    assert cell["metrics"] == ["collab.solo_boundary_ms"]
    assert "Who sends this traffic" in cell["notes"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    mine = [m["name"] for m in declared["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == TRACE_METRICS + GAUGE_METRICS
    (entry,) = [w for w in declared["workloads"] if w["name"] == CELL]
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    assert entry["config"] == config["name"] and entry["traffic"] == "solo"
    (declared_config,) = [
        c for c in declared["configs"] if c["name"] == config["name"]
    ]
    assert declared_config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert declared_config["reduced"] == config["reduced"]
    assert declared_config["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in declared["workloads"]) == 1
    for name in mine:
        with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
            metric = json.load(f)
        assert metric["workloads"] == [CELL] and metric["kind"] == "per_layer"
        assert os.path.exists(
            os.path.join(HERE, "reducers", f"{metric['reducer']}.py")
        )
    # each limit lies between its two readings, both in the file
    why = config["check"]["tolerance_why"]
    for name in config["check"]["tolerance"]:
        assert name in why, name
    assert set(config["check"]["tolerance"]) == LIMITS


def test_rehearse_nemotron_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5700000011", "--seconds", "3", "--trace", "1", "--rehearse"],
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
    # no device trace on the CPU: the trace-read metrics are left out; the
    # program's own gauges are on the step records anywhere
    for name in TRACE_METRICS:
        assert f"smoke.{name}" not in metrics
    for name in GAUGE_METRICS:
        assert f"smoke.{name}" in metrics
    assert metrics["smoke.nemotron.ssd_chunk_log_decay_min"]["value"] < 0.0
    assert 0.0 < metrics["smoke.nemotron.ssd_dt_mean"]["value"] < 0.1
    assert all(name.startswith("smoke.") for name in metrics)
    line = next(
        line for line in out.stdout.splitlines() if "reference check: " in line
    )
    check = json.loads(line.split("reference check: ", 1)[1])
    assert check["dropped_slots"] == 0.0 and check["held_heads"] == 2
    assert check["held_mamba_heads"] == 4 and check["held_kv_heads"] == 1
    assert len(check["chunk_log_decay_min"]) == 3  # the cut's Mamba layers
    assert len(check["load_max_over_mean"]) == 3


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration does not know its name: the role's
    ``parse`` raises at once (seconds, before any device work), which is how
    the driver learns the cell is measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.delitem(common.MODEL_FAMILIES, "nemotron3_nano_30b_a3b")
    with pytest.raises(
        ValueError, match="unknown model_size 'nemotron3_nano_30b_a3b'"
    ):
        role.parse(role.build_argv(
            _config(), {"name": "test", "flags": {}}, 0, 0, "/tmp/unused",
            "", False, False,
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
    assert result["held_heads"] == 2  # 0/2 of four query heads
    assert result["held_mamba_heads"] == 4  # one of the two groups
    assert 0.0 < result["ssd_leaf_rel_l2"] < 0.3 and result["ssd_leaf_worst"]
    assert result["load_abs"] < 1e-7
    # a check that cannot fail checks nothing
    for name in ("score_abs", "ssd_leaf_rel_l2", "grad_rel_l2"):
        tight = json.loads(json.dumps(config))
        tight["check"]["rehearse_tolerance"][name] = 0.0
        assert not role.reference_check(tight, args, rehearse=True)["ok"]
