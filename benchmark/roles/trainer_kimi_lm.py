"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running Kimi Linear
(``models/kimi_linear.py``: Kimi Delta Attention in three layers of four
beside latent attention without RoPE, a leading dense layer, sigmoid top-8
of 256 experts beside a shared one) at ONE chip's share:
``trainer_moe_lm``'s shape (the same role, entry points, batch-source
wrapper and program names), with BOTH shares in the model it builds —
``--training.expert_shard`` and ``--training.head_shard`` (the heads held of
every mixer) — and the reference check made for this model's tree against
``benchmark/reference/kimi_linear.py``, the mixer's small leaves compared
apart. A program without this model knows neither its name nor the head
share's flag: there ``parse`` fails at once."""
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
    install_source,
    microbatch_rows_per_device,
    run,
)

# a KDA mixer's leaves that are sums over every token of a row and carry
# well under 1 % of the gradient's norm: ``leaf_rel_l2`` never looks at them
KDA_SMALL_LEAVES = ("A_log", "dt_bias", "b_proj", "q_conv", "k_conv",
                    "v_conv", "o_norm", "g_b_bias")


def parse(argv):
    from dedloc_tpu.roles.common import model_family

    args = _parse(argv)
    model_family(args.training.model_size)  # unknown to an older program
    return args


def _build(args):
    """(cfg, model, family, rows, seq) of the cell's recipe."""
    from dedloc_tpu.roles.common import build_model, model_family

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size,
        num_hidden_layers=t.num_hidden_layers, expert_shard=t.expert_shard,
        head_shard=t.head_shard,
    )
    return cfg, model, model_family(cfg), t.per_device_batch_size, t.seq_length


def accumulate_scratch_bytes(args) -> int:
    """``trainer_moe_lm.accumulate_scratch_bytes`` with this model's two
    shares: the compiler's scratch for ONE device's micro-batch."""
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


def program_sizes(cfg) -> Dict[str, object]:
    """The program's own sizes under the names of the configuration file's
    ``sizes``: the config's numbers, both shares and the layers by kind."""
    from dedloc_tpu.ops.kda import CHUNK

    sizes = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if isinstance(getattr(cfg, f.name), (int, float))
        and not isinstance(getattr(cfg, f.name), bool)
    }
    kinds = [(mixer, sparse) for _n, mixer, sparse in cfg.layer_plan]
    sizes.update(
        held_experts=cfg.held_experts[1],
        expert_shard_count=cfg.expert_shard[1],
        held_heads=cfg.held_kda_heads, head_shard_count=cfg.head_shard[1],
        kda_layers=sum(m == "kda" for m, _s in kinds),
        mla_layers=sum(m == "mla" for m, _s in kinds),
        routed_ffn_layers=sum(s for _m, s in kinds),
        dense_ffn_layers=sum(not s for _m, s in kinds),
        kda_chunk=CHUNK,
    )
    return sizes


def _layers_in_order(tree):
    """The layers' subtrees of a parameter (or gradient) tree as the model
    applies them: the reference's own walk."""
    from benchmark.reference.kimi_linear import layers_in_order

    return layers_in_order(tree)


def _bias_by_layer(tree):
    """[L, E]: the correction-bias leaves in the sparse layers' order."""
    import numpy as np

    return np.stack([
        np.asarray(layer["mlp"][BIAS]) for layer in _layers_in_order(tree)
        if BIAS in layer["mlp"]
    ])


def kda_small_leaf_error(role_grads, ref_grads) -> Dict[str, object]:
    """The worst relative L2 error over the KDA mixers' small leaves
    (``KDA_SMALL_LEAVES``, each layer's apart), and which it was."""
    import jax
    import numpy as np

    worst, which = 0.0, ""
    for n, (role, ref) in enumerate(zip(
        _layers_in_order(role_grads), _layers_in_order(ref_grads)
    )):
        mixer = role["self_attn"]
        if "A_log" not in mixer:
            continue
        for name in KDA_SMALL_LEAVES:
            for a, b in zip(jax.tree.leaves(mixer[name]),
                            jax.tree.leaves(ref["self_attn"][name])):
                a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
                error = float(
                    np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
                )
                if not error <= worst:  # a NaN is the worst
                    worst, which = error, f"layer {n + 1} {name}"
    return {"kda_leaf_rel_l2": worst, "kda_leaf_worst": which}


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """``trainer_moe_lm.reference_check``'s four comparisons — (a) router
    scores, (b) the share of choices the reference would not have made, (c)
    loss, whole gradient and worst leaf with the reference routed by the
    PROGRAM's choices, (d) the load statistic on the bias leaves — and (e)
    ``kda_leaf_rel_l2``, the worst of the KDA mixers' small leaves, of ONE
    execution of the role's accumulate step (bf16: the KDA kernel pair, the
    two-width causal kernels at the held heads, the routed tile loop, the
    chunked head) against ``benchmark/reference/kimi_linear.py`` (float32,
    matmul precision 'highest', the token-by-token recurrence, dense
    attention, a loop over the held experts, whole logits; the same head and
    expert shares and vocabulary slice) on ONE fixed batch and ONE fixed set
    of weights (``config['check']``), on the cell's device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import kimi_linear as reference
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
                p, b, nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                eps=cfg.rms_norm_eps, top_k=cfg.num_experts_per_token,
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
    )[..., :cfg.num_experts_per_token]
    chosen = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    disagree = float(np.mean(~np.take_along_axis(chosen, choice, axis=-1)))
    score_abs = float(np.max(np.abs(scores - ref_scores)))
    role_load = _bias_by_layer(grads)
    role_grads, _taken = _bias_apart(grads)
    ref_grads, _zero = _bias_apart(ref_grads)
    load_abs = float(np.max(np.abs(role_load - ref_load)))
    small = kda_small_leaf_error(role_grads, ref_grads)

    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    own_bounds = {
        name: tolerance.pop(name)
        for name in ("score_abs", "choice_disagree_share", "load_abs",
                     "kda_leaf_rel_l2")
    }
    result = compare_with_reference(
        metrics["loss"], role_grads, ref_loss, ref_grads, tolerance
    )
    result["tolerance"] = dict(tolerance, **own_bounds)
    result.update(
        rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl, held_experts=list(cfg.held_experts),
        held_heads=cfg.held_kda_heads,
        score_abs=score_abs, choice_disagree_share=disagree,
        load_abs=load_abs, **small,
        local_slot_share=float(metrics["moe.local_slot_share"]),
        dropped_slots=float(metrics["moe.dropped_slots"]),
        load_max_over_mean=[
            float(x) for x in metrics["moe.load_max_over_mean"]
        ],
        chunk_log_decay_min=[
            float(x) for x in metrics["kda.chunk_log_decay_min"]
        ],
        beta_mean=[float(x) for x in metrics["kda.beta_mean"]],
        state_abs_max=[float(x) for x in metrics["kda.state_abs_max"]],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and score_abs <= own_bounds["score_abs"]
        and disagree <= own_bounds["choice_disagree_share"]
        and load_abs <= own_bounds["load_abs"]
        and small["kda_leaf_rel_l2"] <= own_bounds["kda_leaf_rel_l2"]
        and result["dropped_slots"] == 0.0
    )
    del grads, ref_grads, role_grads
    gc.collect()
    return result
