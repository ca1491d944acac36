"""Held-out evaluation: MLM+SOP loss of a checkpoint over a tokenized set.

The reference measures progress by training loss and downstream fine-tunes
(sahajbert/train_ner.py, train_ncc.py); this role adds the direct
pretraining metric — masked-LM cross-entropy (and perplexity) on a held-out
corpus — so BASELINE curves can report generalization, not just fit.

Run:
    python -m dedloc_tpu.roles.evaluate \\
        --training.dataset_path data/holdout_tokenized \\
        --training.output_dir outputs  # newest checkpoint-<step> wins \\
        --eval.max_batches 50

Deterministic: the mask RNG is fixed per run (seed flag), so two
evaluations of the same checkpoint are comparable.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.parallel.train_step import TrainState
from dedloc_tpu.roles.common import (
    build_loss_fn,
    build_model,
    drop_collator_keys,
)
from dedloc_tpu.utils.backend import ensure_compile_cache
from dedloc_tpu.utils.checkpoint import load_latest_checkpoint
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class EvalArguments:
    max_batches: int = 50
    checkpoint_path: str = ""  # explicit checkpoint dir; empty = newest in
    # training.output_dir (or fresh init when none exists — smoke mode)


@dataclass
class EvalCLIArguments(CollaborationArguments):
    eval: EvalArguments = field(default_factory=EvalArguments)


def run_eval(args: CollaborationArguments,
             extra: EvalArguments) -> dict:
    ensure_compile_cache()
    from dedloc_tpu.roles.common import single_device_attention_impl

    impl = single_device_attention_impl(args.training.attention_impl)
    cfg, model = build_model(
        args.training.model_size,
        args.training.remat_policy,
        impl,
        args.training.vocab_size,
        num_hidden_layers=args.training.num_hidden_layers,
    )
    if not args.training.dataset_path:
        raise ValueError("--training.dataset_path: a tokenized dir is required")

    seq = min(args.training.seq_length, cfg.max_position_embeddings)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((args.training.per_device_batch_size, seq), jnp.int32),
    )["params"]

    step = 0
    if extra.checkpoint_path:
        from dedloc_tpu.utils.checkpoint import load_checkpoint

        tree, meta = load_checkpoint(extra.checkpoint_path)
        step = int(meta.get("local_step", meta.get("step", 0)))
        params = _restore(tree, params)
    else:
        resumed = load_latest_checkpoint(args.training.output_dir)
        if resumed is not None:
            step, tree, _meta = resumed
            params = _restore(tree, params)
        else:
            logger.warning("no checkpoint found; evaluating a fresh init")

    loss_fn = build_loss_fn(model)

    @jax.jit
    def eval_step(params, batch, rng):
        loss, metrics = loss_fn(params, batch, rng)
        return metrics

    from dedloc_tpu.data.disk import tokenized_dataset_batches

    batches = tokenized_dataset_batches(
        args.training.dataset_path, cfg,
        args.training.per_device_batch_size, seq, seed=args.training.seed,
    )
    rng = jax.random.PRNGKey(args.training.seed)
    total_mlm = total_sop = 0.0
    n = 0
    for _ in range(extra.max_batches):
        batch = drop_collator_keys(next(batches))
        rng, sub = jax.random.split(rng)
        metrics = eval_step(params, batch, sub)
        total_mlm += float(metrics.get("mlm_loss", metrics["loss"]))
        total_sop += float(metrics.get("sop_loss", 0.0))
        n += 1
    result = {
        "checkpoint_step": step,
        "eval_batches": n,
        "mlm_loss": total_mlm / max(n, 1),
        "mlm_perplexity": float(jnp.exp(total_mlm / max(n, 1))),
        "sop_loss": total_sop / max(n, 1),
    }
    print(json.dumps(result))
    return result


def _restore(tree, params_template):
    """Checkpoint trees hold (params, opt_state) named leaves from the
    trainer's _save; accept either that pair layout or bare params."""
    import numpy as np

    from dedloc_tpu.utils.checkpoint import named_to_tree

    host_template = jax.device_get(params_template)
    try:
        params, _opt = named_to_tree(tree, (host_template, None))
        return jax.device_put(params)
    except (KeyError, TypeError, ValueError):
        pass
    # pair template failed (opt layout unknown here): strip the leading
    # tuple index from the trainer's "[0]..." key paths instead
    stripped = {
        k[3:]: v for k, v in tree.items() if k.startswith("[0]")
    }
    if stripped:
        params = named_to_tree(stripped, host_template)
        return jax.device_put(params)
    params = named_to_tree(tree, host_template)
    return jax.device_put(params)


def main(argv=None) -> None:
    args = parse_config(EvalCLIArguments, argv)
    run_eval(args, args.eval)


if __name__ == "__main__":
    main()
