"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running Laguna
(``models/laguna.py``) at ONE chip's share: ``trainer_smallthinker_lm``'s
shape (the same role, entry points, batch-source wrapper, program names,
share flags, scratch measurement and routing comparison), with the reference
check made for this model — two kinds of attention layer with a head count,
a RoPE and a gate of their own, a leading dense layer, a sigmoid router
without a bias leaf beside a shared expert — against
``benchmark/reference/laguna.py``. A program without this model does not
know its name: there ``parse`` fails at once."""
from __future__ import annotations

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
from benchmark.roles.trainer_sdar_lm import program_sizes as _share_sizes
from benchmark.roles.trainer_smallthinker_lm import (  # noqa: F401
    ROUTING_BOUNDS,
    parse,
    routing_apart,
)

FULL, SLIDING = "full_attention", "sliding_attention"


def program_sizes(cfg) -> Dict[str, float]:
    """The program's own sizes under its own names (the configuration
    file's ``sizes`` are held to them), with the counts the FLOP model
    multiplies by: the layers of each kind the cut runs and each kind's
    query heads."""
    sizes = _share_sizes(cfg)
    plan = cfg.layer_plan
    for kind in (FULL, SLIDING):
        heads = {n for k, n, _sparse in plan if k == kind}
        sizes[f"{kind}_layers"] = sum(k == kind for k, _n, _s in plan)
        sizes[f"{kind}_heads"] = heads.pop() if len(heads) == 1 else 0
    sizes["sparse_layers"] = sum(sparse for _k, _n, sparse in plan)
    sizes["dense_layers"] = len(plan) - sizes["sparse_layers"]
    return sizes


def reference_kwargs(cfg) -> Dict[str, object]:
    """``benchmark/reference/laguna.forward``'s arguments for ``cfg``: the
    config's own lists and its ``rope_parameters``, a group a kind."""
    layers = cfg.num_hidden_layers
    return dict(
        layer_types=cfg.layer_types[:layers],
        heads_per_layer=cfg.num_attention_heads_per_layer[:layers],
        mlp_layer_types=cfg.mlp_layer_types[:layers],
        kv_heads=cfg.num_key_value_heads, eps=cfg.rms_norm_eps,
        window=cfg.sliding_window,
        rope={
            "head_dim": cfg.head_dim,
            FULL: dict(
                rope_type="yarn", rope_theta=cfg.full_rope_theta,
                partial_rotary_factor=cfg.full_partial_rotary_factor,
                factor=cfg.full_yarn_factor,
                original_max_position_embeddings=(
                    cfg.full_yarn_original_max_position_embeddings
                ),
                beta_fast=cfg.full_yarn_beta_fast,
                beta_slow=cfg.full_yarn_beta_slow,
                attention_factor=cfg.full_yarn_attention_factor,
            ),
            SLIDING: dict(
                rope_type="default", rope_theta=cfg.sliding_rope_theta,
                partial_rotary_factor=1,
            ),
        },
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        held=cfg.held_experts,
    )


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """ONE execution of the role's accumulate step (bf16: the grouped causal
    flash kernels at a whole group of six and the band ones at a band equal
    to the tile, partial rotary under YaRN, a gate a head on the kernels'
    output, the SwiGLU tile loop with its gradient sinks beside the shared
    expert, the untied chunked head) — its gradients, its choices and its
    router scores — against ``benchmark/reference/laguna.py`` (float32,
    matmul precision 'highest', dense attention under an explicit [S, S]
    mask per kind in blocks of query rows, a loop over the held experts,
    whole logits; the same expert share and vocabulary slice) on ONE fixed
    batch and ONE fixed set of weights (``config['check']``), on the cell's
    device. The top-k is discrete, so the reference is ROUTED BY THE
    PROGRAM'S CHOICES for the loss, the whole gradient and the worst leaf,
    and the routing is compared apart (``routing_apart``, on the sigmoid
    scores). There is no bias leaf, so no load statistic to compare."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import laguna as reference
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
        return out["loss"], (out["scores"], out["gate_mean"])

    (ref_loss, (ref_scores, ref_gates)), ref_grads = jax.device_get(
        jax.jit(jax.value_and_grad(ref, has_aux=True))(
            params, batch, jnp.asarray(choice)
        )
    )
    del params
    routing = routing_apart(
        metrics["moe.scores"], choice, ref_scores, cfg.num_experts_per_tok
    )
    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {name: tolerance.pop(name) for name in ROUTING_BOUNDS}
    result = compare_with_reference(
        metrics["loss"], grads, ref_loss, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    kinds = np.asarray(cfg.layer_types[:cfg.num_hidden_layers])
    gate_mean = {
        kind: float(metrics[f"attn.gate_mean.{kind}"])
        for kind in (FULL, SLIDING) if kind in kinds
    }
    result.update(
        routing, rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        grad_sink_leaves=float(metrics["moe.grad_sink_leaves"]),
        band_tile_share=float(metrics["attn.band_tile_share"]),
        band_visible_share=float(metrics["attn.band_visible_share"]),
        gate_mean=gate_mean,
        gate_mean_apart=max(
            abs(mean - float(np.mean(ref_gates[kinds == kind])))
            for kind, mean in gate_mean.items()
        ),
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
