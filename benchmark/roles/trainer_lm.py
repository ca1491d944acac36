"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` running a causal
language model (Ouro, the looped decoder): the same role, entry points,
batch-source wrapper and program names as ``roles/trainer.py`` (ALBERT), with
its own reference check and scratch measurement — both go through the role's
model table (``roles/common.model_family``), which a program older than the
table does not have: there this module fails at once."""
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


def _build(args):
    """(cfg, model, family, rows, seq) of the cell's recipe."""
    from dedloc_tpu.roles.common import build_model, model_family

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size,
        num_hidden_layers=t.num_hidden_layers,
    )
    seq = min(t.seq_length, cfg.max_position_embeddings)
    return cfg, model, model_family(cfg), t.per_device_batch_size, seq


def accumulate_scratch_bytes(args) -> int:
    """Scratch the role's ``accumulate_step`` needs for ONE device's
    micro-batch, from the compiler's memory analysis of the program the cell
    runs (compiled here it lands in the persistent cache, where the role's
    own jit of the same program finds it)."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn, drop_collator_keys

    cfg, model, family, rows, seq = _build(args)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((rows, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    batch = drop_collator_keys(next(family.synthetic_batches(cfg, rows, seq, 0)))
    compiled = make_accumulate_step(build_loss_fn(model)).lower(
        params, jax.eval_shape(zeros_like_grads, params),
        jax.ShapeDtypeStruct((), jnp.int32), batch, jax.random.PRNGKey(0),
    ).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """The role's own loss and gradients (its model table, its accumulate
    step, the cell's recipe: bf16, causal flash attention, the chunked head)
    against ``benchmark/reference/ouro.py`` (float32, matmul precision
    'highest', dense attention, whole logits) on ONE fixed batch and ONE
    fixed set of weights (``config['check']``: ``seed``, ``rows`` rows at the
    cell's sequence length), on the cell's device. Beside loss and gradients
    it reports the per-pass losses and the exit distribution of both."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import ouro as reference
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn, drop_collator_keys

    cfg, model, family, _rows, seq = _build(args)
    sizes = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if isinstance(getattr(cfg, f.name), (int, float))
        and not isinstance(getattr(cfg, f.name), bool)
    }
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
    grads, _n, metrics = make_accumulate_step(build_loss_fn(model))(
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32), batch,
        jax.random.PRNGKey(seed + 1),
    )
    # to the host before the reference runs: the float32 reference's
    # activations must not stand on top of the role's gradients
    grads, metrics = jax.device_get((grads, metrics))

    def ref(p, b):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(
                p, b, num_heads=cfg.num_attention_heads, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta, passes=cfg.total_ut_steps,
                beta=cfg.exit_entropy_beta, checkpoint=True,
            )
        return out["loss"], (
            jnp.mean(out["ce"], axis=(1, 2)), jnp.mean(out["p"], axis=(1, 2))
        )

    (ref_loss, (ref_ce, ref_p)), ref_grads = jax.device_get(
        jax.jit(jax.value_and_grad(ref, has_aux=True))(params, batch)
    )
    del params
    tolerance = dict(check["rehearse_tolerance" if rehearse else "tolerance"])
    # the two bounds this model adds, held here; the rest by the shared
    # comparison
    pass_loss_tol = tolerance.pop("pass_loss_rel")
    exit_prob_tol = tolerance.pop("exit_prob_abs")
    result = compare_with_reference(
        metrics["loss"], grads, ref_loss, ref_grads, tolerance
    )
    pass_loss_rel = float(np.max(
        np.abs(metrics["lm.loss"] - ref_ce) / np.abs(ref_ce)
    ))
    exit_prob_abs = float(np.max(np.abs(metrics["lm.exit_prob"] - ref_p)))
    result["tolerance"] = dict(
        tolerance, pass_loss_rel=pass_loss_tol, exit_prob_abs=exit_prob_tol
    )
    result.update(
        rows=rows, seed=seed, seq=seq,
        compute_dtype=str(jnp.dtype(cfg.dtype)),
        attention_impl=cfg.attention_impl,
        pass_loss_rel=pass_loss_rel, exit_prob_abs=exit_prob_abs,
        exit_prob=[float(x) for x in metrics["lm.exit_prob"]],
        sizes_mismatched=mismatched,
    )
    result["ok"] = bool(
        result["ok"] and not mismatched
        and pass_loss_rel <= pass_loss_tol and exit_prob_abs <= exit_prob_tol
    )
    del grads, ref_grads
    gc.collect()
    return result
