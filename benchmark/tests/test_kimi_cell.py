"""Kimi Linear's cell: its files say what the source and the issue say;
``--rehearse`` runs it on the CPU through the real role (tiny preset) and
reports every metric a CPU can; the float32 reference agrees with the role
at the tiny size by every comparison, the KDA mixers' small leaves among
them; a program without the model fails in ``parse``."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_kimi_lm as role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "kimi_linear_48b_a3b_s8192.solo"
TRACE_METRICS = [
    "kimi.mfu_pct", "kimi.kda_fwd_roofline", "kimi.kda_bwd_roofline",
    "kimi.kda_device_ms", "kimi.flash_mla_fwd_roofline",
    "kimi.flash_mla_bwd_tiled_roofline", "kimi.routed_device_ms",
]
GAUGE_METRICS = [
    "kimi.kda_chunk_log_decay_min", "kimi.kda_beta_mean",
    "kimi.kda_state_abs_max",
]
# no ``loss_rel``: bf16's grid at this loss leaves no room between the two
# readings (the file's ``tolerance_why``)
LIMITS = {
    "grad_rel_l2", "leaf_rel_l2", "kda_leaf_rel_l2", "score_abs",
    "choice_disagree_share", "load_abs",
}


def _config():
    path = os.path.join(HERE, "configs", "kimi_linear_48b_a3b_s8192.json")
    with open(path) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        model_type="kimi_linear", hidden_size=2304, intermediate_size=9216,
        moe_intermediate_size=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_experts_per_token=8,
        num_shared_experts=1, routed_scaling_factor=2.446,
        first_k_dense_replace=1, mla_use_nope=True, rms_norm_eps=1e-5,
        moe_router_activation_func="sigmoid", tie_word_embeddings=False,
        num_key_value_heads=32, rope_scaling=None, q_lora_rank=None,
    )
    for key, value in published.items():
        assert config[key] == value, key
    group = config["linear_attn_config"]
    assert (group["num_heads"], group["head_dim"],
            group["short_conv_kernel_size"]) == (32, 128, 4)
    assert group["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "num_attention_heads"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_attention_heads"]) == (
        5, 8, 20480, 8)
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "num_attention_heads": 32, "linear_attn_config.num_heads": 32,
    }
    assert config["vocab_size"] * 8 == 163840
    assert "464,825,120" in config["reduced_why"]["bytes"]
    deployment = config["deployment"].lower()
    for said in ("32 chips", "groups of 4 chips", "vocabulary over 8",
                 "pipeline stages", "partial sum", "what the cut distorts",
                 "43.5 %"):
        assert said in deployment, said
    for key in ("kda_gate_rank", "gate_bias", "decay_parameters",
                "convolution", "l2_norm", "beta", "bias_update", "aux_loss",
                "initializer_range", "weight_decay", "optimizer", "data",
                "remat"):
        assert key in config["assumed"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f)
                if e["name"] == "Kimi-Linear-48B-A3B-Instruct"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.kimi_linear import KimiLinearConfig

    cfg = KimiLinearConfig(
        num_hidden_layers=5, vocab_size=20480, expert_shard=(0, 32),
        head_shard=(0, 4),
    )
    assert role.program_sizes(cfg) == config["sizes"]
    assert list(cfg.full_attn_layers) == group["full_attn_layers"]
    assert [n for n, mixer, _s in KimiLinearConfig().layer_plan
            if mixer == "kda"] == group["kda_layers"]
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
        "--training.model_size": "kimi_linear_48b_a3b",
        "--training.num_hidden_layers": 5, "--training.vocab_size": 20480,
        "--training.expert_shard": "0/32", "--training.head_shard": "0/4",
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
    assert declared_config["file"] == (
        "benchmark/configs/kimi_linear_48b_a3b_s8192.json"
    )
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
    assert "NO loss_rel" in why


def test_rehearse_kimi_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5300000011", "--seconds", "3", "--trace", "1", "--rehearse"],
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
    assert metrics["smoke.kimi.kda_chunk_log_decay_min"]["value"] < 0.0
    assert 0.3 < metrics["smoke.kimi.kda_beta_mean"]["value"] < 0.7
    assert all(name.startswith("smoke.") for name in metrics)
    line = next(
        line for line in out.stdout.splitlines() if "reference check: " in line
    )
    check = json.loads(line.split("reference check: ", 1)[1])
    assert check["dropped_slots"] == 0.0 and check["held_heads"] == 2
    assert len(check["chunk_log_decay_min"]) == 4  # the cut's KDA layers
    assert len(check["load_max_over_mean"]) == 4


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration knows neither its name nor
    ``--training.head_shard``: the role's ``parse`` raises at once (seconds,
    before any device work), which is how the driver learns the cell is
    measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.delitem(common.MODEL_FAMILIES, "kimi_linear_48b_a3b")
    with pytest.raises(
        ValueError, match="unknown model_size 'kimi_linear_48b_a3b'"
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
    assert result["held_heads"] == 2  # 0/2 of four heads
    assert 0.0 < result["kda_leaf_rel_l2"] < 0.6 and result["kda_leaf_worst"]
    assert result["load_abs"] < 1e-7
    # a check that cannot fail checks nothing
    for name in ("score_abs", "kda_leaf_rel_l2", "grad_rel_l2"):
        tight = json.loads(json.dumps(config))
        tight["check"]["rehearse_tolerance"][name] = 0.0
        assert not role.reference_check(tight, args, rehearse=True)["ok"]
