"""Laguna-XS.2's cell: its files say what the source and the issue say;
``--rehearse`` runs it on the CPU through the real role (tiny preset) and
reports every metric a CPU can; the float32 reference agrees with the role
at the tiny size by every comparison."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_laguna_lm as role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "laguna_xs2_33b_a3b_s8192.solo"
METRICS = [
    "laguna.mfu_pct", "laguna.flash_band_fwd_roofline",
    "laguna.flash_band_bwd_tiled_roofline", "laguna.flash_full_fwd_roofline",
    "laguna.flash_full_bwd_tiled_roofline", "laguna.routed_device_ms",
]


def _config():
    path = os.path.join(HERE, "configs", "laguna_xs2_33b_a3b_s8192.json")
    with open(path) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        model_type="laguna", hidden_size=2048, intermediate_size=8192,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=262144, attention_bias=False,
        rms_norm_eps=1e-6, num_experts_per_tok=8, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, tie_word_embeddings=False,
        gating=True, sliding_window=512,
        moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
        moe_routed_scaling_factor=2.5,
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_parameters"]["full_attention"] == dict(
        rope_theta=500000, rope_type="yarn", factor=64,
        original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
        attention_factor=1.4158883083359672, partial_rotary_factor=0.5,
    )
    assert config["rope_parameters"]["sliding_attention"] == dict(
        rope_type="default", rope_theta=10000, partial_rotary_factor=1,
    )
    # the three published lists, whole
    assert config["layer_types"] == (
        ["full_attention"] + ["sliding_attention"] * 3
    ) * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["sizes"]["num_experts"] == 256  # the router's width
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    assert config["published"] == dict(
        num_hidden_layers=40, num_experts=256, vocab_size=100352,
    )
    assert config["vocab_size"] * 8 == 100352
    assert "389,634,048" in config["reduced_why"]["bytes"]
    deployment = config["deployment"].lower()
    for said in ("32 chips", "vocabulary over 8", "pipeline stages",
                 "what the cut distorts"):
        assert said in deployment, said
    for key in ("gate", "qk_norm", "router", "rotary_lanes", "yarn",
                "window", "aux_loss", "initializer_range", "optimizer"):
        assert key in config["assumed"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f) if e["name"] == "Laguna-XS.2"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig(
        num_hidden_layers=5, vocab_size=12544, expert_shard=(0, 32)
    )
    assert role.program_sizes(cfg) == config["sizes"]
    assert list(cfg.layer_types) == config["layer_types"]
    assert list(cfg.mlp_layer_types) == config["mlp_layer_types"]
    assert list(cfg.num_attention_heads_per_layer) == config[
        "num_attention_heads_per_layer"
    ]
    rope = role.reference_kwargs(cfg)["rope"]
    for kind, group in config["rope_parameters"].items():
        if isinstance(group, dict):
            assert rope[kind] == group, kind


def test_the_cell_is_the_issues():
    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] == 1
    assert cell["flags"]["--averager.metadata_expiration"] == 2
    assert config["flags"] == {
        "--training.model_size": "laguna_xs2_33b_a3b",
        "--training.num_hidden_layers": 5, "--training.vocab_size": 12544,
        "--training.expert_shard": "0/32", "--training.seq_length": 8192,
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
    assert mine == METRICS
    (entry,) = [w for w in declared["workloads"] if w["name"] == CELL]
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    assert "32x" in cell["why"]  # attention sees more than its share
    assert config["name"] in [c["name"] for c in declared["configs"]]
    # each limit lies between its two readings, both in the file
    why = config["check"]["tolerance_why"]
    for name in config["check"]["tolerance"]:
        assert name in why, name


def test_rehearse_laguna_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4700000011", "--seconds", "3", "--trace", "1", "--rehearse"],
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
    for name in METRICS:
        assert f"smoke.{name}" not in metrics
    assert all(name.startswith("smoke.") for name in metrics)
    # the role's gauges, on the reference check's line of the log
    line = next(
        line for line in out.stdout.splitlines() if "reference check: " in line
    )
    check = json.loads(line.split("reference check: ", 1)[1])
    assert check["grad_sink_leaves"] == 12.0 and check["dropped_slots"] == 0.0
    assert 0.0 < check["band_tile_share"] <= 1.0
    assert 0.0 < check["band_visible_share"] <= 1.0
    assert set(check["gate_mean"]) == {"full_attention", "sliding_attention"}
    assert len(check["load_max_over_mean"]) == 4


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration does not know its name: the role's
    ``parse`` raises at once (seconds, before any device work), which is
    how the driver learns the cell is measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.delitem(common.MODEL_FAMILIES, "laguna_xs2_33b_a3b")
    config = _config()
    with pytest.raises(
        ValueError, match="unknown model_size 'laguna_xs2_33b_a3b'"
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
    assert len(result["load_max_over_mean"]) == 4  # the four sparse layers
    assert result["grad_sink_leaves"] == 12.0  # 3 a routed layer
    assert result["gate_mean_apart"] < 1e-3
    # a check that cannot fail checks nothing: the exact comparison does
    config["check"]["rehearse_tolerance"]["logit_abs"] = 0.0
    assert not role.reference_check(config, args, rehearse=True)["ok"]
