"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running the
latent-attention expert decoder (``models/deepseek_v3.py``) at ONE chip's
share: ``trainer_lm``'s shape (the same role, entry points, batch-source
wrapper and program names), with the share in the model it builds
(``--training.expert_shard``, ``--training.vocab_size``) and a reference
check made for a discrete router. A program older than the routed layer
does not know the share's flag: there ``parse`` fails at once."""
from __future__ import annotations

import dataclasses
import gc
from typing import Dict

from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401
from benchmark.roles.trainer import (  # noqa: F401
    PROGRAMS,
    STOP,
    install_source,
    microbatch_rows_per_device,
    parse,
    run,
)

BIAS = "e_score_correction_bias"


def _build(args):
    """(cfg, model, family, rows, seq) of the cell's recipe."""
    from dedloc_tpu.roles.common import build_model, model_family

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size,
        num_hidden_layers=t.num_hidden_layers, expert_shard=t.expert_shard,
    )
    seq = min(t.seq_length, cfg.max_position_embeddings)
    return cfg, model, model_family(cfg), t.per_device_batch_size, seq


def _accumulate(model):
    from dedloc_tpu.parallel.train_step import make_accumulate_step
    from dedloc_tpu.roles.common import build_loss_fn

    return make_accumulate_step(build_loss_fn(model))


def accumulate_scratch_bytes(args) -> int:
    """Scratch the role's ``accumulate_step`` needs for ONE device's
    micro-batch, from the compiler's memory analysis of the program the cell
    runs (``trainer_lm.accumulate_scratch_bytes`` with this model's share)."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.common import drop_collator_keys

    cfg, model, family, rows, seq = _build(args)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((rows, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    batch = drop_collator_keys(next(family.synthetic_batches(cfg, rows, seq, 0)))
    compiled = _accumulate(model).lower(
        params, jax.eval_shape(zeros_like_grads, params),
        jax.ShapeDtypeStruct((), jnp.int32), batch, jax.random.PRNGKey(0),
    ).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def _bias_apart(tree):
    """(tree with the correction-bias leaves zeroed, those leaves): the
    bias leaf carries the load statistic, not a gradient."""
    import jax
    import numpy as np

    taken = []

    def split(path, x):
        if path[-1].key != BIAS:
            return x
        taken.append(np.asarray(x))
        return np.zeros_like(x)

    return jax.tree_util.tree_map_with_path(split, tree), taken


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """The role's own accumulate step — its gradients, its choices and its
    router scores, all of ONE execution — (its model table, the cell's recipe:
    bf16, the two-width causal kernels, the routed tile loop, the chunked
    head) against ``benchmark/reference/deepseek_v3.py`` (float32, matmul
    precision 'highest', dense attention, a loop over the held experts,
    whole logits; the same expert share and vocabulary slice) on ONE fixed
    batch and ONE fixed set of weights (``config['check']``), on the cell's
    device. The top-k is discrete, so four things are compared:

    (a) the router's scores as continuous values (``score_abs``);
    (b) the share of (token, slot) choices the reference, given the same
        upstream, would not have made (``choice_disagree_share``);
    (c) loss, whole gradient and worst leaf with the reference routed by the
        PROGRAM's choices (``loss_rel``, ``grad_rel_l2``, ``leaf_rel_l2``);
    (d) the load statistic — the bias leaf's cotangent — against the
        reference's count of the same choices (``load_abs``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import deepseek_v3 as reference
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
                nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                rank=cfg.kv_lora_rank, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor, held=cfg.held_experts,
                choices=choices, checkpoint=True,
            )
        return out["loss"], (out["scores"], out["load_excess"])

    (ref_loss, (ref_scores, ref_load)), ref_grads = jax.device_get(
        jax.jit(jax.value_and_grad(ref, has_aux=True))(
            params, batch, jnp.asarray(choice)
        )
    )
    bias = np.asarray(params["layers"]["block"]["mlp"][BIAS])
    del params
    # (b) what the reference would have chosen from ITS scores, as sets
    own = np.argsort(
        -(ref_scores + bias[:, None, :]), axis=-1, kind="stable"
    )[..., :cfg.num_experts_per_tok]
    chosen = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    disagree = float(np.mean(~np.take_along_axis(chosen, choice, axis=-1)))
    score_abs = float(np.max(np.abs(scores - ref_scores)))
    role_grads, role_load = _bias_apart(grads)
    ref_grads, _zero = _bias_apart(ref_grads)
    load_abs = float(np.max(np.abs(role_load[0] - ref_load)))

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
