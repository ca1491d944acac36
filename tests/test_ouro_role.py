"""The looped decoder through the trainer role: ``--training.model_size
ouro_tiny`` makes global steps solo on the CPU through the same
``run_trainer`` / ``CollaborativeOptimizer`` path as ALBERT, and its step
records carry what this model adds to the tracing; and the model's row of
``tools/tpu_aot.py`` (its accumulate_step compiled for a TPU v5e WITHOUT a
chip: ``tests/tpu_aot_rows.py``)."""
import json

import numpy as np
import pytest

import decoder_cases as cases
from dedloc_tpu.roles.common import (
    ALBERT,
    OURO,
    build_loss_fn,
    build_model,
    drop_collator_keys,
    model_family,
)
from tpu_aot_rows import tpu_aot


def test_ouro_tiny_trainer_makes_global_steps_and_traces_them(tmp_path):
    train_log = tmp_path / "train.jsonl"
    state, stepped, records = cases.run_tiny_trainer(
        tmp_path, "ouro_tiny", ["--training.train_log_path", str(train_log)],
        max_local_steps=7,
    )
    rows = [json.loads(line) for line in train_log.read_text().splitlines()]
    assert len(rows) >= 2 and all(np.isfinite(r["loss"]) for r in rows)
    passes = 3  # ouro_tiny's total_ut_steps
    for rec in stepped:
        exit_prob = [rec[f"lm.exit_prob.{t}"] for t in range(1, passes + 1)]
        assert sum(exit_prob) == pytest.approx(1.0, abs=1e-4)
        assert all(0.0 < p < 1.0 for p in exit_prob)
        assert all(
            np.isfinite(rec[f"lm.loss.{t}"]) and rec[f"lm.loss.{t}"] > 0
            for t in range(1, passes + 1)
        )
    # the state snapshot's transfer, timed on its own thread, lands on a
    # later boundary's record as a span with its bytes
    carried = [r for r in records if "opt.backup_bytes" in r]
    assert carried
    for rec in carried:
        spans = [s for s in rec["spans"] if s[0] == "backup_transfer"]
        assert spans and all(s[3] >= s[2] for s in spans)
        # float32 params + two LAMB moments (+ counters): 12 bytes a parameter
        n_params = sum(x.size for x in _leaves(state.params))
        assert rec["opt.backup_bytes"] >= 12 * n_params


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def test_one_table_builds_both_families():
    for size, family in (("tiny", ALBERT), ("large", ALBERT),
                         ("ouro_tiny", OURO), ("ouro_2p6b", OURO)):
        assert model_family(size) is family
    with pytest.raises(ValueError, match="unknown model_size"):
        model_family("medium")
    cfg, model = build_model("ouro_tiny", num_hidden_layers=3)
    assert model_family(model) is OURO and cfg.num_hidden_layers == 3
    # no width is an override of build_model: only the depth
    assert cfg.hidden_size == 32 and cfg.head_dim == 16
    cfg_a, model_a = build_model("tiny", "fused_ln", "flash")
    assert model_family(cfg_a) is ALBERT and cfg_a.fused_ln
    assert callable(build_loss_fn(model)) and callable(build_loss_fn(model_a))
    batch = next(OURO.synthetic_batches(cfg, 2, 16, 0))
    kept = drop_collator_keys(batch)
    assert set(kept) == {"input_ids", "labels"}
    assert kept["input_ids"].shape == (2, 16)
    assert OURO.tflops_per_sample(cfg, 16) > 0
    assert ALBERT.tflops_per_sample(cfg_a, 64) > 0
    with pytest.raises(ValueError, match="ALBERT's"):
        build_model("ouro_tiny", moe_experts=4)


def test_solo_mean_takes_the_accumulators_buffers():
    """The solo boundary's mean is the same program as the networked
    path's, with the accumulator donated: same values, same name to a
    trace or a compile listener, and the accumulator is gone after it."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.collaborative import optimizer

    acc = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
    kept = optimizer._fused_mean_clip(acc, 2, 0.0)
    assert not acc["w"].is_deleted()
    in_place = optimizer._fused_mean_clip_in_place(
        jax.tree.map(jnp.copy, acc), 2, 0.0
    )
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(in_place)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(kept["w"], np.arange(6.0).reshape(2, 3) / 2)
    # clipped: the global norm of the mean is capped
    clipped = optimizer._fused_mean_clip(acc, 1, 1.0)
    norm = np.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(clipped)))
    assert norm == pytest.approx(1.0, rel=1e-5)
    for fn in (optimizer._fused_mean_clip, optimizer._fused_mean_clip_in_place):
        assert fn.__wrapped__.__name__ == "_fused_mean_clip"


def test_ouro_accumulate_step_keeps_the_flash_outputs_and_fits_the_cap():
    """The looped decoder's accumulate_step (Ouro-2.6B cut to the cell's 3
    layers, 1 row of 4,096), compiled for a v5e: the layer's remat policy
    keeps the causal flash kernel's out + lse, so the lowered module calls
    the forward kernel ONCE (the forward scan's body) and the backward's
    replay of the layer holds none — 2 call sites under policy ``nothing``,
    12 of 24 executions a micro-batch (PR 28). The stash is paid in the
    program's scratch: 5.30 GB against 5.04 — 5.16 since the kernels'
    operands sit behind ``decoder.GroupedQueryAttention``'s barrier (PR 45:
    the 11 float32 relayouts of RoPE's pieces left the layer bodies) —
    which with a draining snapshot's 9.96 GB of state stays under the 15.3
    GB the cell is sized by; a policy that also kept ``flash_qkv`` would
    read 6.2 GB here. 5.21 since the tiled kernels take the heads their
    VMEM holds (PR 58: eight forward, four backward, 30 + 61 MiB asked for
    where the backward alone asked 32.5 — XLA places 47 MB less of the
    program around them in fast memory)."""
    row = tpu_aot("ouro_accumulate_step")["ouro_accumulate_step"]
    assert row["remat_policy"] == "kernel_outputs"
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 1}
    assert row["flash_windows"] == {  # D=128: a head is one lane tile
        name: "block" for name in (
            "flash_causal_fwd", "flash_causal_bwd_tiled"
        )
    }
    # eight heads a program forward, four backward (dk / dv of the
    # program's own heads are resident): what each asks for says it
    assert row["flash_vmem_mb"] == {
        "flash_causal_fwd": 30.0, "flash_causal_bwd_tiled": 61.0,
    }
    # forward and the one-sweep backward: one site each
    assert row["tpu_custom_calls"] == 2
    assert row["memory"]["temp_bytes"] <= 5.25e9, row["memory"]
    copies = row["layer_body_copies"]
    assert not [shape for shape in copies if shape.startswith("f32")], copies
