"""Adapter for ``dedloc_tpu.roles.trainer.run_trainer`` (ALBERT)."""
from __future__ import annotations

import dataclasses
from typing import Dict

from benchmark.instrument import InstrumentedSource, Recorder, WindowOver
from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401

# logical name -> the jitted function's own name (trace modules are jit_<name>)
PROGRAMS = {
    "accumulate": "accumulate_step",
    "solo_mean": "_fused_mean_clip",
    "prepare": "grad_flat_prepare",
    "flat_apply": "flat_apply_step",
    "guarded_apply": "guarded_apply_step",
}
STOP = WindowOver


def parse(argv):
    from dedloc_tpu.core.config import CollaborationArguments, parse_config

    return parse_config(CollaborationArguments, argv)


def run(args):
    from dedloc_tpu.roles.trainer import run_trainer

    return run_trainer(args)


def install_source(recorder: Recorder, seed: int):
    """Wrap ``roles.trainer._make_batches``. The role seeds its data from a
    per-process public key; the benchmark hands it a key made from
    ``--seed`` and the peer's index, so the same seed draws the same rows."""
    from dedloc_tpu.roles import trainer as role

    orig = role._make_batches

    def make_batches(args, cfg, public_key, slice_batch=None):
        peer = recorder.peer()
        if peer is None:
            return orig(args, cfg, public_key, slice_batch)
        key = f"benchmark-seed-{seed}-peer-{peer.index}".encode()
        rows = slice_batch or args.training.per_device_batch_size
        return InstrumentedSource(
            orig(args, cfg, key, slice_batch), recorder, peer, rows, STOP
        )

    role._make_batches = make_batches

    def uninstall():
        role._make_batches = orig

    return uninstall


def microbatch_rows_per_device(args) -> int:
    return args.training.per_device_batch_size


def accumulate_scratch_bytes(args) -> int:
    """Scratch (activations) the role's ``accumulate_step`` needs for ONE
    device's micro-batch, from the compiler's own memory analysis of the
    program the cell runs. Compiled here it also lands in the persistent
    cache, where the role's own jit of the same program finds it."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import (
        build_loss_fn,
        build_model,
        drop_collator_keys,
        synthetic_mlm_batches,
    )

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size
    )
    rows = t.per_device_batch_size
    seq = min(t.seq_length, cfg.max_position_embeddings)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((rows, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    batch = drop_collator_keys(next(synthetic_mlm_batches(cfg, rows, seq, 0)))
    compiled = make_accumulate_step(build_loss_fn(model)).lower(
        params, jax.eval_shape(zeros_like_grads, params),
        jax.ShapeDtypeStruct((), jnp.int32), batch, jax.random.PRNGKey(0),
    ).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """The role's own loss and gradients (``build_model`` / ``build_loss_fn``
    / the accumulate step, the cell's recipe and compute type) against the
    float32 reference, on ONE fixed batch and ONE fixed set of weights
    (``config['check']``: ``seed``, ``rows`` rows at the cell's sequence
    length), on one device: the same computation in every run, so the bounds
    sit close to what it measures and ``correct`` does not depend on
    ``--seed``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import albert as reference
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import (
        build_loss_fn,
        build_model,
        drop_collator_keys,
        synthetic_mlm_batches,
    )

    t = args.training
    cfg, model = build_model(
        t.model_size, t.remat_policy, t.attention_impl, t.vocab_size
    )
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
    seq = min(t.seq_length, cfg.max_position_embeddings)
    batch = drop_collator_keys(
        next(synthetic_mlm_batches(cfg, rows, seq, seed))
    )
    params = jax.jit(
        lambda r: model.init(r, jnp.zeros((rows, seq), jnp.int32))["params"]
    )(jax.random.PRNGKey(seed))
    grads, _n, metrics = make_accumulate_step(build_loss_fn(model))(
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32), batch,
        jax.random.PRNGKey(seed + 1),
    )

    def ref(p, b):
        with jax.default_matmul_precision("highest"):
            return reference.loss_fn(
                p, b, cfg.num_hidden_layers, cfg.num_attention_heads,
                cfg.layer_norm_eps,
            )

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref))(params, batch)
    result = compare_with_reference(
        metrics["loss"], grads, ref_loss, ref_grads,
        check["rehearse_tolerance" if rehearse else "tolerance"],
    )
    result["rows"] = rows
    result["seed"] = seed
    result["compute_dtype"] = str(jnp.dtype(cfg.dtype))
    result["sizes_mismatched"] = mismatched
    result["ok"] = bool(result["ok"] and not mismatched)
    return result
