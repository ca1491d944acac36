"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running Nemotron-H
(``models/nemotron_h.py``: layers of ONE sublayer each — a Mamba-2 mixer,
NoPE grouped attention or sigmoid top-6 of 128 un-gated relu² experts beside
a shared expert) at ONE chip's share: ``trainer_moe_lm``'s shape (the same
role, entry points, batch-source wrapper and program names), with BOTH
shares in the model it builds — ``--training.expert_shard`` and
``--training.head_shard`` (whole groups of every Mamba mixer, the query
heads over their key head of attention) — and the reference check made for
this model's tree against ``benchmark/reference/nemotron_h.py``, the Mamba
mixers' small leaves compared apart. A program without this model does not
know its name: there ``parse`` fails at once."""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401
# what ``trainer_kimi_lm`` has that names no model: ``parse`` (the model's
# name must be one the program knows), ``_build`` (the model of the flags,
# BOTH shares in it) and the compiler's scratch for one micro-batch
from benchmark.roles.trainer_kimi_lm import (  # noqa: F401
    _build,
    accumulate_scratch_bytes,
    parse,
)
from benchmark.roles.trainer_moe_lm import (  # noqa: F401
    BIAS,
    PROGRAMS,
    STOP,
    _accumulate,
    _bias_apart,
    install_source,
    microbatch_rows_per_device,
    run,
)

# a Mamba mixer's leaves that are sums over every token of a row and carry
# well under 1 % of the gradient's norm: ``leaf_rel_l2`` never looks at them
SSD_SMALL_LEAVES = ("A_log", "D", "dt_bias", "conv", "conv_bias", "norm")


def program_sizes(cfg) -> Dict[str, object]:
    """The program's own sizes under the names of the configuration file's
    ``sizes``: the config's numbers, both shares and the layers by kind."""
    sizes = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if isinstance(getattr(cfg, f.name), (int, float))
        and not isinstance(getattr(cfg, f.name), bool)
    }
    kinds = cfg.layer_kinds
    sizes.update(
        held_experts=cfg.held_experts[1],
        expert_shard_count=cfg.expert_shard[1],
        held_mamba_heads=cfg.held_mamba_heads, held_groups=cfg.held_groups,
        held_heads=cfg.held_heads, held_kv_heads=cfg.held_kv_heads,
        head_shard_count=cfg.head_shard[1],
        mamba_layers=kinds.count("M"), attention_layers=kinds.count("*"),
        routed_ffn_layers=kinds.count("E"), ssd_chunk=cfg.chunk_size,
    )
    return sizes


def _layers_in_order(tree):
    """The layers' subtrees of a parameter (or gradient) tree as the model
    applies them: the reference's own walk."""
    from benchmark.reference.nemotron_h import layers_in_order

    return layers_in_order(tree)


def _bias_by_layer(tree):
    """[L, E]: the correction-bias leaves in the sparse layers' order."""
    import numpy as np

    return np.stack([
        np.asarray(layer["mixer"][BIAS]) for layer in _layers_in_order(tree)
        if BIAS in layer["mixer"]
    ])


def ssd_small_leaf_error(role_grads, ref_grads) -> Dict[str, object]:
    """The worst relative L2 error over the Mamba mixers' small leaves
    (``SSD_SMALL_LEAVES``, each layer's apart), and which it was."""
    import jax
    import numpy as np

    worst, which = 0.0, ""
    for n, (role, ref) in enumerate(zip(
        _layers_in_order(role_grads), _layers_in_order(ref_grads)
    )):
        mixer = role["mixer"]
        if "A_log" not in mixer:
            continue
        for name in SSD_SMALL_LEAVES:
            for a, b in zip(jax.tree.leaves(mixer[name]),
                            jax.tree.leaves(ref["mixer"][name])):
                a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
                error = float(
                    np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
                )
                if not error <= worst:  # a NaN is the worst
                    worst, which = error, f"layer {n} {name}"
    return {"ssd_leaf_rel_l2": worst, "ssd_leaf_worst": which}


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """``trainer_moe_lm.reference_check``'s four comparisons — (a) router
    scores, (b) the share of choices the reference would not have made, (c)
    loss, whole gradient and worst leaf with the reference routed by the
    PROGRAM's choices, (d) the load statistic on the bias leaves — and (e)
    ``ssd_leaf_rel_l2``, the worst of the Mamba mixers' small leaves, of ONE
    execution of the role's accumulate step (bf16: the scan's kernel pair,
    the grouped causal kernels at the held heads, the two-matrix routed tile
    loop, the chunked head) against ``benchmark/reference/nemotron_h.py``
    (float32, matmul precision 'highest', the token-by-token recurrence,
    dense attention, a loop over the held experts, whole logits; the same
    head and expert shares and vocabulary slice) on ONE fixed batch and ONE
    fixed set of weights (``config['check']``), on the cell's device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import nemotron_h as reference
    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.common import drop_collator_keys

    cfg, model, family, _rows, seq = _build(args)
    sizes = program_sizes(cfg)
    mismatched = {
        k: (v, sizes[k]) for k, v in config.get("sizes", {}).items()
        if not rehearse and k in sizes and sizes[k] != v
    }
    check = config["check"]
    rows, seed = int(check["rows"]), int(check["seed"])
    batch = drop_collator_keys(
        next(family.synthetic_batches(cfg, rows, seq, seed))
    )
    params = jax.jit(
        lambda r: model.init(r, jnp.zeros((rows, seq), jnp.int32))["params"]
    )(jax.random.PRNGKey(seed))
    grads, _n, metrics = _accumulate(model)(
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32), batch,
        jax.random.PRNGKey(seed + 1),
    )
    # to the host before the reference runs: the float32 reference's
    # activations must not stand on top of the role's gradients
    grads, metrics = jax.device_get((grads, metrics))
    scores = metrics["moe.scores"]
    choice = metrics["moe.choice"]

    def ref(p, b, choices):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(
                p, b, state=cfg.ssm_state_size, head_dim=cfg.head_dim,
                eps=cfg.rms_norm_eps, top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor, held=cfg.held_experts,
                choices=choices, checkpoint=True,
            )
        return out["loss"], (out["scores"], out["load_excess"])

    (ref_loss, (ref_scores, ref_load)), ref_grads = jax.device_get(
        jax.jit(jax.value_and_grad(ref, has_aux=True))(
            params, batch, jnp.asarray(choice)
        )
    )
    bias = _bias_by_layer(jax.device_get(params))
    del params
    # (b) what the reference would have chosen from ITS scores, as sets
    own = np.argsort(
        -(ref_scores + bias[:, None, :]), axis=-1, kind="stable"
    )[..., :cfg.num_experts_per_tok]
    chosen = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    disagree = float(np.mean(~np.take_along_axis(chosen, choice, axis=-1)))
    score_abs = float(np.max(np.abs(scores - ref_scores)))
    role_load = _bias_by_layer(grads)
    role_grads, _taken = _bias_apart(grads)
    ref_grads, _zero = _bias_apart(ref_grads)
    load_abs = float(np.max(np.abs(role_load - ref_load)))
    small = ssd_small_leaf_error(role_grads, ref_grads)

    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {
        name: tolerance.pop(name)
        for name in ("score_abs", "choice_disagree_share", "load_abs",
                     "ssd_leaf_rel_l2")
    }
    result = compare_with_reference(
        metrics["loss"], role_grads, ref_loss, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    result.update(
        rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        held_mamba_heads=cfg.held_mamba_heads, held_heads=cfg.held_heads,
        held_kv_heads=cfg.held_kv_heads,
        score_abs=score_abs, choice_disagree_share=disagree,
        load_abs=load_abs, **small,
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        load_max_over_mean=[
            float(x) for x in metrics["moe.load_max_over_mean"]
        ],
        dt_mean=[float(x) for x in metrics["ssd.dt_mean"]],
        chunk_log_decay_min=[
            float(x) for x in metrics["ssd.chunk_log_decay_min"]
        ],
        state_abs_max=[float(x) for x in metrics["ssd.state_abs_max"]],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and score_abs <= own_bounds["score_abs"]
        and disagree <= own_bounds["choice_disagree_share"]
        and load_abs <= own_bounds["load_abs"]
        and small["ssd_leaf_rel_l2"] <= own_bounds["ssd_leaf_rel_l2"]
        and result["dropped_slots"] == 0.0
    )
    del grads, ref_grads, role_grads
    gc.collect()
    return result
