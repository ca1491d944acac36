"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running the
band-and-global expert decoder (``models/smallthinker.py``) at ONE chip's
share: ``trainer_moe_lm``'s shape (the same role, entry points, batch-source
wrapper, program names, share flags and scratch measurement), with the
reference check made for this model — two kinds of attention layer, a
softmax router without a bias leaf, so no load statistic to compare —
against ``benchmark/reference/smallthinker.py``. A program without this
model does not know its name: there ``parse`` fails at once."""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401
from benchmark.roles.trainer import parse as _parse
from benchmark.roles.trainer_moe_lm import (  # noqa: F401
    PROGRAMS,
    STOP,
    _accumulate,
    _build,
    accumulate_scratch_bytes,
    install_source,
    microbatch_rows_per_device,
    run,
)

ROUTING_BOUNDS = ("logit_abs", "choice_disagree_share")


def parse(argv):
    from dedloc_tpu.roles.common import model_family

    args = _parse(argv)
    model_family(args.training.model_size)  # unknown to an older program
    return args


def program_sizes(cfg) -> Dict[str, float]:
    """The program's own sizes under its own names (the configuration
    file's ``sizes`` are held to them), with the counts the FLOP model
    multiplies by."""
    sizes = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if isinstance(getattr(cfg, f.name), (int, float))
        and not isinstance(getattr(cfg, f.name), bool)
    }
    sizes["held_experts"] = cfg.held_experts[1]
    sizes["expert_shard_count"] = cfg.expert_shard[1]
    sizes["band_layers"] = sum(banded for _rope, banded in cfg.layer_plan)
    sizes["global_layers"] = len(cfg.layer_plan) - sizes["band_layers"]
    return sizes


def reference_kwargs(cfg) -> Dict[str, object]:
    """``benchmark/reference/smallthinker.forward``'s arguments for ``cfg``."""
    layers = cfg.num_hidden_layers
    return dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, window=cfg.sliding_window_size,
        rope_layout=cfg.rope_layout[:layers],
        window_layout=cfg.sliding_window_layout[:layers],
        held=cfg.held_experts,
    )


def routing_apart(logits, choice, ref_logits, top_k: int) -> Dict[str, float]:
    """The routing compared on its own: the largest difference of a router
    logit, and the share of the given choices that are not among the
    reference's top k of ITS logits given the same upstream."""
    import numpy as np

    own = np.argsort(-ref_logits, axis=-1, kind="stable")[..., :top_k]
    chosen = np.zeros(ref_logits.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    return {
        "logit_abs": float(np.max(np.abs(logits - ref_logits))),
        "choice_disagree_share": float(
            np.mean(~np.take_along_axis(chosen, choice, axis=-1))
        ),
    }


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """ONE execution of the role's accumulate step (bf16: the band and the
    grouped causal flash kernels at a group of seven, the ReGLU tile loop
    with its gradient sinks, the untied chunked head) — its gradients, its
    choices and its router logits — against ``benchmark/reference/
    smallthinker.py`` (float32, matmul precision 'highest', dense attention
    with k / v repeated per group and an explicit [S, S] mask in blocks of
    query rows, a loop over the held experts, whole logits; the same expert
    share and vocabulary slice) on ONE fixed batch and ONE fixed set of
    weights (``config['check']``), on the cell's device. The top-k is
    discrete, so the reference is ROUTED BY THE PROGRAM'S CHOICES for the
    loss, the whole gradient and the worst leaf, and the routing is compared
    apart (``routing_apart``). There is no bias leaf, so no load statistic
    to compare: a replay that re-routes shows in the leaves alone."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import smallthinker as reference
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
    choice = metrics["moe.choice"]

    def ref(p, b, choices):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(
                p, b, choices=choices, checkpoint=True,
                **reference_kwargs(cfg),
            )
        return out["loss"], out["scores"]

    (ref_loss, ref_logits), ref_grads = jax.device_get(
        jax.jit(jax.value_and_grad(ref, has_aux=True))(
            params, batch, jnp.asarray(choice)
        )
    )
    del params
    routing = routing_apart(
        metrics["moe.scores"], choice, ref_logits, cfg.num_experts_per_tok
    )
    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {name: tolerance.pop(name) for name in ROUTING_BOUNDS}
    result = compare_with_reference(
        metrics["loss"], grads, ref_loss, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    result.update(
        routing, rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        grad_sink_leaves=float(metrics["moe.grad_sink_leaves"]),
        band_tile_share=float(metrics["attn.band_tile_share"]),
        load_max_over_mean=[
            float(x) for x in metrics["moe.load_max_over_mean"]
        ],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and all(routing[name] <= own_bounds[name] for name in ROUTING_BOUNDS)
        and result["dropped_slots"] == 0.0
    )
    del grads, ref_grads
    gc.collect()
    return result
