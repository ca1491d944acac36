"""Adapter for ``dedloc_tpu.roles.swav.run_swav`` (SwAV ResNet-50)."""
from __future__ import annotations

from typing import Dict

from benchmark.instrument import InstrumentedSource, Recorder
from benchmark.roles.common import build_argv, compare_with_reference  # noqa: F401

# the SwAV accumulate step's jitted function is called ``step``
PROGRAMS = {
    "accumulate": "step",
    "solo_mean": "_fused_mean_clip",
    "prepare": "grad_flat_prepare",
    "flat_apply": "flat_apply_step",
    "guarded_apply": "guarded_apply_step",
}
STOP = StopIteration  # run_swav ends gracefully on finite data


def parse(argv):
    from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config

    return parse_config(SwAVCollaborationArguments, argv)


def run(args):
    from dedloc_tpu.roles.swav import run_swav

    return run_swav(args)


def install_source(recorder: Recorder, seed: int):
    """Wrap ``roles.swav.synthetic_multicrop_batches`` (the role seeds it
    from ``--training.seed``, which the harness sets from ``--seed``; peers
    of one cell share the weights' seed, so the data's gets the index)."""
    from dedloc_tpu.roles import swav as role

    orig = role.synthetic_multicrop_batches

    def batches(spec, batch_size, seed=0, **kw):
        peer = recorder.peer()
        if peer is None:
            return orig(spec, batch_size, seed=seed, **kw)
        inner = orig(spec, batch_size, seed=seed + peer.index, **kw)
        return InstrumentedSource(inner, recorder, peer, batch_size, STOP)

    role.synthetic_multicrop_batches = batches

    def uninstall():
        role.synthetic_multicrop_batches = orig

    return uninstall


def microbatch_rows_per_device(args) -> int:
    return args.training.per_device_batch_size


def accumulate_scratch_bytes(args) -> int:
    """Scratch (activations) the role's accumulate program needs for one
    device's micro-batch, from the compiler's memory analysis; compiled
    here it also lands in the persistent cache for the role's own jit."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.models.swav import make_swav_accumulate_step
    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.swav import build_swav

    cfg, spec, model, _tx = build_swav(args)
    rows = args.training.per_device_batch_size
    crops = [
        jax.ShapeDtypeStruct((count * rows, size, size, spec.channels),
                             jnp.float32)
        for size, count in zip(spec.sizes, spec.counts)
    ]
    variables = jax.eval_shape(
        lambda r, c: model.init(r, c, True), jax.random.PRNGKey(0), crops
    )
    params = variables["params"]
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = make_swav_accumulate_step(
        model, cfg, num_crop_groups=len(spec.sizes)
    ).lower(
        params, variables["batch_stats"], None,
        jax.eval_shape(zeros_like_grads, params), scalar, crops, scalar, False,
    ).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def reference_check(config, args, rehearse: bool = False) -> Dict[str, object]:
    """The role against the float32 reference on ONE fixed multicrop batch
    and ONE fixed set of weights (``config['check']``: ``seed``, ``rows``
    images), so the check is the same computation in every run and its bounds
    sit close to what it measures. Two parts, each the role's own accumulate
    step (``build_swav`` + ``make_swav_accumulate_step``, every line of it)
    against ``benchmark/reference/swav.py`` run in float32 at matmul
    precision "highest" on the host's CPU backend (on the TPU that reference
    is an 80 MB cache entry that takes four minutes to compile — my offline
    compile, PR 22):

    - MATHEMATICS (``float32``): the step with only ``ResNetConfig.dtype``
      set to float32, on the host CPU too: loss and gradients, relative L2.
    - THE PROGRAM THE CELL MEASURES (``recipe``, ``recipe_head``): the step
      as the recipe computes (bf16 convolutions, float32 batch norm and
      head), jitted for and run on the accelerator. At random weights its
      trunk gradient is uncorrelated with the float32 one (cosine 0.01 on
      the chip, relative L2 1.41, at 8 and at 64 rows alike: PERF.md, open
      questions), so no bound on its direction could tell a right backward
      from a wrong one. What still agrees is held: the loss, the whole
      gradient's norm and every sizeable leaf's norm, and the direction of
      the float32 head's leaves; the whole gradient's cosine is reported.

    Tolerances, the readings they come from and what they cannot catch:
    ``config['check']``.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark.reference import swav as reference
    from dedloc_tpu.data.multicrop import synthetic_multicrop_batches
    from dedloc_tpu.models.swav import SwAVModel, make_swav_accumulate_step
    from dedloc_tpu.parallel.train_step import zeros_like_grads
    from dedloc_tpu.roles.swav import build_swav

    cfg, spec, model, _tx = build_swav(args)
    check = config["check"]
    rows, seed = int(check["rows"]), int(check["seed"])
    host, chip = jax.devices("cpu")[0], jax.devices()[0]
    crops = jax.device_put(
        next(synthetic_multicrop_batches(spec, rows, seed=seed)), host
    )
    variables = jax.jit(lambda r, c: model.init(r, c, True))(
        jax.device_put(jax.random.PRNGKey(seed), host), crops
    )
    params, batch_stats = variables["params"], variables["batch_stats"]
    sizes = {
        "width": cfg.trunk.width,
        "num_prototypes": cfg.num_prototypes[0],
        "proj_hidden_dim": cfg.proj_dims[1],
        "proj_out_dim": cfg.proj_dims[2],
        "num_crops": cfg.num_crops,
        "trunk_blocks": sum(cfg.trunk.stage_sizes),
    }
    mismatched = {
        k: (v, sizes[k]) for k, v in config.get("sizes", {}).items()
        if not rehearse and k in sizes and sizes[k] != v
    }

    def ref(p, c):
        with jax.default_matmul_precision("highest"):
            return reference.loss_fn(
                p, c, tuple(cfg.trunk.stage_sizes), cfg.num_crops,
                tuple(cfg.crops_for_assign), cfg.temperature, cfg.epsilon,
                cfg.sinkhorn_iters,
            )

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref))(params, crops)

    def role_step(step_cfg, device):
        """Loss and gradients of one micro-batch from the role's own step,
        past the prototype freeze (their gradient is compared too)."""
        put = lambda x: jax.device_put(x, device)  # noqa: E731
        grads, _n, _bn, _q, metrics = make_swav_accumulate_step(
            SwAVModel(step_cfg), step_cfg, num_crop_groups=len(spec.sizes)
        )(
            put(params), put(batch_stats), None,
            jax.jit(zeros_like_grads)(put(params)),
            put(jnp.zeros([], jnp.int32)), put(crops),
            put(jnp.asarray(cfg.freeze_prototypes_steps + 1, jnp.int32)),
            False,
        )
        return metrics["loss"], grads

    float32_cfg = dataclasses.replace(
        cfg, trunk=dataclasses.replace(cfg.trunk, dtype=jnp.float32)
    )
    tolerances = check["rehearse_tolerance" if rehearse else "tolerance"]
    result = {
        "float32": compare_with_reference(
            *role_step(float32_cfg, host), ref_loss, ref_grads,
            tolerances["float32"],
        ),
    }
    recipe_loss, recipe_grads = role_step(cfg, chip)
    result["recipe"] = compare_with_reference(
        recipe_loss, recipe_grads, ref_loss, ref_grads, tolerances["recipe"],
    )
    result["recipe_head"] = compare_with_reference(
        recipe_loss, recipe_grads["head"], ref_loss, ref_grads["head"],
        tolerances["recipe_head"],
    )
    result.update({
        "ok": bool(
            all(part["ok"] for part in result.values()) and not mismatched
        ),
        "recipe_platform": chip.platform,
        "rows": rows,
        "seed": seed,
        "compute_dtype": str(jnp.dtype(cfg.trunk.dtype)),
        "sizes_mismatched": mismatched,
    })
    return result
