"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running Keye-VL-2.0's
language model (``models/keye_vl2.py``) at ONE chip's share:
``trainer_sdar_lm``'s shape (the same role, entry points, batch-source
wrapper, program names, share flags and routing comparison), with the
reference check made for this model — a SECOND discrete choice, the keys a
learned indexer selects for every query, beside the router's; a second loss
term; positions and weights from the batch — against
``benchmark/reference/keye_vl2.py``. A program without this model does not
know its name: there ``parse`` fails at once."""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401
from benchmark.roles.trainer_moe_lm import (  # noqa: F401
    PROGRAMS,
    STOP,
    _accumulate,
    accumulate_scratch_bytes,
    install_source,
    microbatch_rows_per_device,
    run,
)
from benchmark.roles.trainer_sdar_lm import program_sizes
from benchmark.roles.trainer_smallthinker_lm import (  # noqa: F401
    ROUTING_BOUNDS,
    parse,
    routing_apart,
)

# the selection's own bound, and the second loss term's: its value, and its
# gradient — the INDEXER's leaves, which L_I alone reaches
OWN_BOUNDS = ROUTING_BOUNDS + (
    "select_disagree_share", "index_kl_rel", "index_leaf_rel_l2",
)


def _build(args, **overrides):
    """(cfg, model, family, rows, seq) of the cell's recipe."""
    from dedloc_tpu.roles.common import build_model, model_family

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size,
        num_hidden_layers=t.num_hidden_layers, expert_shard=t.expert_shard,
    )
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
        model = model_family(cfg).module(cfg)
    seq = min(t.seq_length, cfg.max_position_embeddings)
    return cfg, model, model_family(cfg), t.per_device_batch_size, seq


def indexer_leaves_apart(grads, ref_grads) -> Dict[str, float]:
    """``index_leaf_rel_l2``: the worst relative L2 error of an INDEXER's
    gradient leaf — every one of them, whatever it weighs: the common
    ``leaf_rel_l2`` reads only leaves that carry >= 1 % of the whole
    gradient's norm, and L_I's gradient is a small part of it
    (``index_grad_norm_share``), so without this a fault on L_I's backward
    path passes every limit."""
    import jax
    import numpy as np

    worst, index_sq, all_sq = 0.0, 0.0, 0.0
    for (path, got), want in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(ref_grads),
    ):
        want = np.asarray(want, np.float64)
        norm_sq = float(np.sum(want ** 2))
        all_sq += norm_sq
        if "indexer" in jax.tree_util.keystr(path) and norm_sq > 0:
            index_sq += norm_sq
            worst = max(worst, float(
                np.sqrt(np.sum((np.asarray(got, np.float64) - want) ** 2))
            ) / norm_sq ** 0.5)
    return {
        "index_leaf_rel_l2": worst,
        "index_grad_norm_share": (index_sq / max(all_sq, 1e-300)) ** 0.5,
    }


def reference_kwargs(cfg) -> Dict[str, object]:
    """``benchmark/reference/keye_vl2.forward``'s arguments for ``cfg``."""
    return dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        sections=cfg.mrope_section, index_heads=cfg.index_n_heads,
        index_top_k=cfg.index_topk, top_k=cfg.num_experts_per_tok,
        held=cfg.held_experts,
    )


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """ONE execution of the role's accumulate step (bf16: the indexer's
    score pass and exact top-k, the selected flash kernels at a group of
    eight reading that selection, the indexer's loss in blocks of query rows
    from the kernels' log-sum-exp, the SiLU-gated tile loop with its
    gradient sinks, the untied chunked head, the weighted loss) — its
    gradients, its router choices and logits and its SELECTION — against
    ``benchmark/reference/keye_vl2.py`` (float32, matmul precision
    'highest', dense attention under an explicit [S, S] mask in blocks of
    query rows, a loop over the held experts, whole logits; the same expert
    share, vocabulary slice, positions and weights) on ONE fixed batch and
    ONE fixed set of weights (``config['check']``), on the cell's device.
    The step is the role's with ONE more output: the model is built with
    ``emit_selection`` so that the loss's metrics carry every layer's int8
    selection (1 GB at the cell's shape: never on the normal path); nothing
    else of the program differs. Both choices are discrete, so the reference
    is given the PROGRAM's router choices and selection for L_LM, L_I, the
    whole gradient and the worst leaf, and both are compared apart:
    ``routing_apart`` on the router's logits, and ``select_disagree_share``
    — the share of the program's selected (layer, query, key) triples that
    are not in the reference's top-k of ITS index scores given the same
    upstream. The indexer's leaves have a limit of their own
    (``indexer_leaves_apart``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import keye_vl2 as reference
    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.common import drop_collator_keys

    cfg, model, family, _rows, seq = _build(args, emit_selection=True)
    sizes = program_sizes(cfg)
    mismatched = {
        k: (v, sizes[k]) for k, v in config.get("sizes", {}).items()
        if not rehearse and k in sizes and sizes[k] != v
    }
    check = config["check"]
    rows, seed = int(check["rows"]), int(check["seed"])
    batch = drop_collator_keys(next(family.synthetic_batches(
        cfg, rows, seq, seed,
        image_token_share=args.training.image_token_share,
    )))
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
    choice = metrics["moe.choice"]
    selection = metrics.pop("attn.selection")  # [L, B, S, S] int8

    def ref(p, b, choices, selections):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(
                p, b, choices=choices, selections=selections,
                checkpoint=True, **reference_kwargs(cfg),
            )
        # ``select_missed``: how many of the program's selected triples the
        # reference's own top-k (of ITS scores, given the same upstream)
        # does not hold, a layer
        return out["loss"], (out["scores"], out["lm"], out["index_kl"],
                             jnp.sum(out["select_missed"]))

    (_total, (ref_scores, ref_lm, ref_kl, missed)), ref_grads = (
        jax.device_get(jax.jit(jax.value_and_grad(ref, has_aux=True))(
            params, batch, jnp.asarray(choice), jnp.asarray(selection)
        ))
    )
    del params
    apart = routing_apart(
        metrics["moe.scores"], choice, ref_scores, cfg.num_experts_per_tok
    )
    selected = int(np.count_nonzero(selection))
    apart["select_disagree_share"] = float(missed) / max(selected, 1)
    apart["index_kl_rel"] = abs(
        float(metrics["loss.index_kl"]) - float(ref_kl)
    ) / max(abs(float(ref_kl)), 1e-12)
    apart.update(indexer_leaves_apart(grads, ref_grads))
    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {name: tolerance.pop(name) for name in OWN_BOUNDS}
    result = compare_with_reference(
        metrics["loss.lm"], grads, ref_lm, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    result.update(
        apart, rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        index_kl=float(metrics["loss.index_kl"]),
        reference_index_kl=float(ref_kl),
        selected_triples=selected,
        select_kept_share=float(metrics["attn.select_kept_share"]),
        select_tile_share=float(metrics["attn.select_tile_share"]),
        index_peak=[float(x) for x in metrics["attn.index_peak"]],
        image_token_share=float(metrics["data.image_token_share"]),
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        grad_sink_leaves=float(metrics["moe.grad_sink_leaves"]),
        load_max_over_mean=[
            float(x) for x in metrics["moe.load_max_over_mean"]
        ],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and all(apart[name] <= own_bounds[name] for name in OWN_BOUNDS)
        and result["dropped_slots"] == 0.0
    )
    del grads, ref_grads, selection
    gc.collect()
    return result
