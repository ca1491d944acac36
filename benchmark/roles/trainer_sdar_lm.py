"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running the
block-diffusion expert decoder (``models/sdar_moe.py``) at ONE chip's share:
``trainer_smallthinker_lm``'s shape (the same role, entry points,
batch-source wrapper, program names, share flags, scratch measurement and
routing comparison), with the reference check made for this model — a batch
of three arrays a row (the noisy ids, the clean ids, the weights), one kind
of layer, the two-stream block rule — against
``benchmark/reference/sdar_moe.py``. A program without this model does not
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
    _build,
    accumulate_scratch_bytes,
    install_source,
    microbatch_rows_per_device,
    run,
)
from benchmark.roles.trainer_smallthinker_lm import (  # noqa: F401
    ROUTING_BOUNDS,
    parse,
    routing_apart,
)


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
    return sizes


def reference_kwargs(cfg) -> Dict[str, object]:
    """``benchmark/reference/sdar_moe.forward``'s arguments for ``cfg``."""
    return dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, block=cfg.block_length,
        held=cfg.held_experts,
    )


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """ONE execution of the role's accumulate step (bf16: the
    block-diffusion flash kernels at a group of eight over both streams, the
    SiLU-gated tile loop with its gradient sinks, the untied chunked head
    over the noisy stream, the weighted loss) — its gradients, its choices
    and its router logits — against ``benchmark/reference/sdar_moe.py``
    (float32, matmul precision 'highest', dense attention with k / v
    repeated per group and an explicit [2L, 2L] mask in blocks of query
    rows, a loop over the held experts, whole logits; the same expert share
    and vocabulary slice) on ONE fixed batch — rows, noise and weights — and
    ONE fixed set of weights (``config['check']``), on the cell's device.
    The top-k is discrete, so the reference is ROUTED BY THE PROGRAM'S
    CHOICES for the loss, the whole gradient and the worst leaf, and the
    routing is compared apart (``routing_apart``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sdar_moe as reference
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
        lambda r: model.init(r, jnp.zeros((rows, 2 * seq), jnp.int32))[
            "params"
        ]
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
        bd_tile_share=float(metrics["attn.bd_tile_share"]),
        masked_share=float(metrics["diffusion.masked_share"]),
        masked_tokens=float(metrics["diffusion.masked_tokens"]),
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
