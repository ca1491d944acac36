"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running the
conv-hybrid expert decoder (``models/lfm2_moe.py``) at ONE chip's share:
``trainer_moe_lm``'s shape (the same role, entry points, batch-source
wrapper, program names, share flags and scratch measurement), with the
reference check made for this model's tree — layers of more than one kind,
a correction bias per expert LAYER spread over several leaves — against
``benchmark/reference/lfm2_moe.py``. A program without this model does not
know its name: there ``parse`` fails at once."""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401
from benchmark.roles.trainer import parse as _parse
from benchmark.roles.trainer_moe_lm import (  # noqa: F401
    BIAS,
    PROGRAMS,
    STOP,
    _accumulate,
    _bias_apart,
    _build,
    accumulate_scratch_bytes,
    install_source,
    microbatch_rows_per_device,
    run,
)


def parse(argv):
    from dedloc_tpu.roles.common import model_family

    args = _parse(argv)
    model_family(args.training.model_size)  # unknown to an older program
    return args


def _bias_by_layer(tree):
    """[L, E]: the correction-bias leaves of ``tree`` in the order the model
    applies its expert layers (the scanned periods' leaves are stacked over
    the periods, one leaf per position in the period; then the tail)."""
    import numpy as np

    rows = []
    if "layers" in tree:
        period = tree["layers"]
        positions = sorted(period, key=lambda name: int(name.split("_")[-1]))
        stacked = np.stack(
            [np.asarray(period[p]["feed_forward"][BIAS]) for p in positions],
            axis=1,
        )  # [periods, positions, E]
        rows.append(stacked.reshape(-1, stacked.shape[-1]))
    i = 0
    while f"tail_layer_{i}" in tree:
        rows.append(
            np.asarray(tree[f"tail_layer_{i}"]["feed_forward"][BIAS])[None]
        )
        i += 1
    return np.concatenate(rows)


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """``trainer_moe_lm.reference_check``'s four comparisons — (a) router
    scores, (b) the share of choices the reference would not have made, (c)
    loss, whole gradient and worst leaf with the reference routed by the
    PROGRAM's choices, (d) the load statistic on the bias leaves — of ONE
    execution of the role's accumulate step (bf16: the short-convolution and
    grouped-query kernels, the routed tile loop, the tied chunked head)
    against ``benchmark/reference/lfm2_moe.py`` (float32, matmul precision
    'highest', a shifted-sum convolution, dense attention with k / v
    repeated per group, a loop over the held experts, whole logits; the same
    expert share and vocabulary slice) on ONE fixed batch and ONE fixed set
    of weights (``config['check']``), on the cell's device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import lfm2_moe as reference
    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.common import drop_collator_keys

    cfg, model, family, _rows, seq = _build(args)
    sizes = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if isinstance(getattr(cfg, f.name), (int, float))
        and not isinstance(getattr(cfg, f.name), bool)
    }
    sizes["held_experts"] = cfg.held_experts[1]
    sizes["expert_shard_count"] = cfg.expert_shard[1]
    kinds = [
        (mixer, sparse) for _i, mixer, sparse in cfg.layer_plan
    ]
    sizes["conv_layers"] = sum(m == "conv" for m, _s in kinds)
    sizes["attention_layers"] = len(kinds) - sizes["conv_layers"]
    sizes["routed_ffn_layers"] = sum(s for _m, s in kinds)
    sizes["dense_ffn_layers"] = len(kinds) - sizes["routed_ffn_layers"]
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
                p, b, num_heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor, route_eps=cfg.route_eps,
                held=cfg.held_experts, choices=choices, checkpoint=True,
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

    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {
        name: tolerance.pop(name)
        for name in ("score_abs", "choice_disagree_share", "load_abs")
    }
    result = compare_with_reference(
        metrics["loss"], role_grads, ref_loss, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    result.update(
        rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        score_abs=score_abs, choice_disagree_share=disagree,
        load_abs=load_abs,
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        load_max_over_mean=[
            float(x) for x in metrics["moe.load_max_over_mean"]
        ],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and score_abs <= own_bounds["score_abs"]
        and disagree <= own_bounds["choice_disagree_share"]
        and load_abs <= own_bounds["load_abs"]
        and result["dropped_slots"] == 0.0
    )
    del grads, ref_grads, role_grads
    gc.collect()
    return result
