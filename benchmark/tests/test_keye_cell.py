"""Keye-VL-2.0's cell: its files say what the source and the issue say;
``--rehearse`` runs it on the CPU through the real role (tiny preset) and
reports every metric a CPU can; the float32 reference agrees with the role
at the tiny size by every comparison, the selection's among them."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.roles import trainer_keye_lm as role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "keye_vl2_30b_a3b_s16384.solo"
# the cell's own per-layer metrics, in BENCHMARK.json's order: PR 51's, the
# kernels' times of PR 52, PR 54 and PR 56, the one-sweep backward's share of
# PR 61 (which retired the two block-loop times); the gauge is the program's
# own and is on the step records anywhere
TRACE_METRICS = [
    "keye.mfu_pct", "keye.flash_sel_fwd_roofline",
    "keye.flash_sel_bwd_tiled_roofline", "keye.routed_device_ms",
    "keye.index_loss_fwd.device_us", "keye.index_loss_bwd.device_us",
    "keye.select.device_us", "flash_sel_bwd_tiled.device_us",
]
GAUGE_METRICS = ["keye.select_tie_block_share"]
METRICS = TRACE_METRICS[:7] + GAUGE_METRICS + TRACE_METRICS[7:]


def _config():
    path = os.path.join(HERE, "configs", "keye_vl2_30b_a3b_s16384.json")
    with open(path) as f:
        return json.load(f)


def test_the_file_holds_every_published_width():
    config = _config()
    published = dict(
        model_type="KeyeVL2", hidden_size=2048, intermediate_size=6144,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        max_position_embeddings=262144, attention_bias=False,
        rms_norm_eps=1e-6, num_experts_per_tok=8, moe_intermediate_size=768,
        num_local_experts=128, tie_word_embeddings=False,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
        rope_theta=10000000, hidden_act="silu",
    )
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == dict(
        mrope_section=[16, 24, 24], rope_type="default", type="default"
    )
    assert config["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048,
    )
    assert config["sizes"]["num_experts"] == 128  # the router's width
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 8, 18992)
    assert config["published"] == dict(
        num_hidden_layers=48, num_experts=128, vocab_size=151936,
    )
    assert config["vocab_size"] * 8 == 151936
    assert "314,396,160" in config["reduced_why"]["bytes"]
    assert "tower" in config["what"].lower()
    deployment = config["deployment"].lower()
    for said in ("16 chips", "vocabulary over 8", "pipeline stages",
                 "tower", "what the cut distorts"):
        assert said in deployment, said
    for key in ("indexer", "chunk_sizes", "indexer_inputs", "qk_norm",
                "mrope", "image_positions", "recipe", "aux_loss",
                "initializer_range", "tower", "remat", "optimizer"):
        assert key in config["assumed"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(
                e for e in map(json.loads, f)
                if e["name"] == "Keye-VL-2.0-30B-A3B"
            )
        assert config["source"] == entry["source_url"]
        differs = {
            k for k, v in entry["config"].items() if config.get(k, "?") != v
        }
        assert differs == set(config["reduced"])
    # the program's own config says the same
    from dedloc_tpu.models.keye_vl2 import KeyeVL2Config

    cfg = KeyeVL2Config(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 16)
    )
    assert role.program_sizes(cfg) == config["sizes"]
    assert list(cfg.mrope_section) == config["rope_scaling"]["mrope_section"]
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        config["sa_config"]["indexer_num_heads"],
        config["sa_config"]["indexer_head_dim"], config["sa_config"]["topk"],
    )
    assert cfg.remat_policy in config["assumed"]["remat"]


def test_the_cell_is_the_issues():
    with open(os.path.join(HERE, "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    config = _config()
    assert cell["chips"] == 1 and cell["peers"] == 1
    assert cell["warmup_steps"] == 1
    # the issue's mix: a global step every 4 boundaries = 8 rows of 16,384
    assert cell["flags"] == {
        "--optimizer.target_batch_size": 6,
        "--averager.metadata_expiration": 2,
    }
    assert config["flags"] == {
        "--training.model_size": "keye_vl2_30b_a3b",
        "--training.num_hidden_layers": 4, "--training.vocab_size": 18992,
        "--training.expert_shard": "0/16", "--training.seq_length": 16384,
        "--training.per_device_batch_size": 1,
        "--training.image_token_share": 0.25,
    }
    assert cell["path"] == {
        "required": ["accumulate", "solo_mean", "guarded_apply"],
        "forbidden": ["prepare"],
    }
    assert cell["metrics"] == ["collab.solo_boundary_ms"]
    # the step's length at the rate found, outside the cells' usual 4-6 s,
    # and why the boundary count was NOT moved, are in the notes
    assert "target_batch_size 6" in cell["notes"]
    assert "NOT moved" in cell["notes"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    # membership, not "the last entry": later cells come after this one
    mine = [m["name"] for m in declared["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == METRICS
    (entry,) = [w for w in declared["workloads"] if w["name"] == CELL]
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    assert entry["config"] == config["name"] and entry["traffic"] == "solo"
    (declared_config,) = [
        c for c in declared["configs"] if c["name"] == config["name"]
    ]
    assert declared_config["file"] == (
        "benchmark/configs/keye_vl2_30b_a3b_s16384.json"
    )
    assert declared_config["reduced"] == config["reduced"]
    assert declared_config["source"] == config["source"]
    assert sum(w["chips"] == 4 for w in declared["workloads"]) == 1
    for name in METRICS:
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
    assert set(config["check"]["tolerance"]) == {
        "loss_rel", "index_kl_rel", "grad_rel_l2", "leaf_rel_l2",
        "logit_abs", "choice_disagree_share", "select_disagree_share",
        "index_leaf_rel_l2",
    }


def test_rehearse_keye_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5100000011", "--seconds", "3", "--trace", "1", "--rehearse"],
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
    for name in TRACE_METRICS:
        assert f"smoke.{name}" not in metrics
    for name in GAUGE_METRICS:
        assert f"smoke.{name}" in metrics
    assert all(name.startswith("smoke.") for name in metrics)
    # the role's gauges, on the reference check's line of the log
    line = next(
        line for line in out.stdout.splitlines() if "reference check: " in line
    )
    check = json.loads(line.split("reference check: ", 1)[1])
    assert check["grad_sink_leaves"] == 6.0 and check["dropped_slots"] == 0.0
    assert check["select_kept_share"] == pytest.approx(228 / 528)
    assert check["select_tile_share"] == 1.0
    assert check["selected_triples"] == 2 * 228
    assert check["index_kl"] > 0.0 and len(check["index_peak"]) == 2
    assert len(check["load_max_over_mean"]) == 2


def test_a_program_without_the_model_fails_in_parse(monkeypatch):
    """The parent of this configuration does not know its name (nor the
    cell's ``--training.image_token_share``): the role's ``parse`` raises at
    once (seconds, before any device work), which is how the driver learns
    the cell is measured on the change alone."""
    from dedloc_tpu.roles import common

    monkeypatch.delitem(common.MODEL_FAMILIES, "keye_vl2_30b_a3b")
    config = _config()
    with pytest.raises(
        ValueError, match="unknown model_size 'keye_vl2_30b_a3b'"
    ):
        role.parse(role.build_argv(
            config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
            False, False,
        ))


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_matches_role(seed, monkeypatch):
    from dedloc_tpu.data import causal_lm

    # image spans that fit a quarter of the rehearsal's rows of 32
    monkeypatch.setattr(causal_lm, "IMAGE_GRIDS", ((2, 2), (2, 3)))
    config = _config()
    config["check"]["seed"] = seed
    args = role.parse(role.build_argv(
        config, {"name": "test", "flags": {}}, 0, 0, "/tmp/unused", "",
        False, True,
    ))
    result = role.reference_check(config, args, rehearse=True)
    assert result["ok"], result
    assert result["grad_rel_l2"] > 0.0 and result["dropped_slots"] == 0.0
    assert result["held_experts"] == [0, 4]  # the rehearsal's share: 0/2
    assert len(result["load_max_over_mean"]) == 2
    assert result["grad_sink_leaves"] == 6.0  # 3 a routed layer
    assert 0.0 <= result["select_disagree_share"] <= 0.05
    assert result["index_kl_rel"] < 0.05
    assert 0.1 < result["image_token_share"] <= 0.3
    # the indexer's own leaves, which the common worst-leaf limit skips here
    assert 0.0 < result["index_leaf_rel_l2"] < 0.2
    assert result["index_grad_norm_share"] < 0.05
    # a check that cannot fail checks nothing: the exact comparisons do
    for name in ("logit_abs", "index_kl_rel", "index_leaf_rel_l2"):
        tight = json.loads(json.dumps(config))
        tight["check"]["rehearse_tolerance"][name] = 0.0
        assert not role.reference_check(tight, args, rehearse=True)["ok"]
