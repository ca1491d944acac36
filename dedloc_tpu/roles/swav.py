"""SwAV collaborative trainer peer.

Capability parity with the reference's swav workload driver (reference:
swav/vissl/vissl/trainer/trainer_main.py:138-204 phase loop +
swav/ClassyVision/classy_vision/optim/sgd_collaborative.py:132-171): build
ResNet-50 trunk + prototypes head, LARC-SGD with warmup-cosine schedule,
DHT + CollaborativeOptimizer (target_batch_size 32768), multicrop pipeline,
and run the phase-loop Trainer with the default hook pipeline.

TPU-native shape (SURVEY.md §3.4): the reference's two communication worlds —
NCCL all_reduce inside the sinkhorn loop and hivemind averaging per optimizer
step — become (a) ICI psums XLA inserts when the jitted step is sharded over
a mesh and (b) the DHT/DCN averaging in CollaborativeOptimizer. The GLOBAL
collaboration step (not the local one) gates the queue and the prototype
freeze, exactly as the fork feeds collaboration_state.optimizer_step to the
loss (standard_train_step.py:153).
"""
from __future__ import annotations

from typing import Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from dedloc_tpu.collaborative.optimizer import CollaborativeOptimizer
from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config
from dedloc_tpu.core.hooks import default_hooks
from dedloc_tpu.core.trainer import Trainer
from dedloc_tpu.data.multicrop import (
    MultiCropSpec,
    image_folder_multicrop_batches,
    synthetic_multicrop_batches,
)
from dedloc_tpu.models.swav import (
    SwAVConfig,
    SwAVModel,
    SwAVQueue,
    make_prototype_post_apply,
    make_swav_accumulate_step,
)
from dedloc_tpu.optim.lars import lars
from dedloc_tpu.optim.schedules import linear_warmup_cosine_annealing
from dedloc_tpu.parallel.train_step import TrainState, zeros_like_grads
from dedloc_tpu.telemetry import steps
from dedloc_tpu.telemetry.profile import profile_gate
from dedloc_tpu.telemetry.steps import StepRecorder
from dedloc_tpu.roles.common import (
    build_dht,
    checkpoint_kwargs,
    open_train_log,
    publish_step_metrics,
)
from dedloc_tpu.utils.backend import ensure_compile_cache
from dedloc_tpu.utils.checkpoint import save_checkpoint
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def build_swav(args: SwAVCollaborationArguments):
    """(cfg, spec, model, tx) for the requested model size."""
    t = args.training
    if t.model_size == "tiny":
        cfg = SwAVConfig.tiny(
            queue_length=t.queue_length, queue_start_step=t.queue_start_step
        )
        spec = MultiCropSpec.tiny()
    else:
        cfg = SwAVConfig(
            queue_length=t.queue_length, queue_start_step=t.queue_start_step
        )
        spec = MultiCropSpec()
    model = SwAVModel(cfg)
    schedule = linear_warmup_cosine_annealing(
        t.learning_rate, t.warmup_steps, t.total_steps
    )
    tx = lars(
        learning_rate=schedule,
        momentum=t.momentum,
        weight_decay=t.weight_decay,
        trust_coefficient=t.trust_coefficient,
    )
    return cfg, spec, model, tx


def _build_flat_lars_factory(t):
    """(spec, params) -> optim.flat.FlatLars mirroring ``build_swav``'s
    LARS hyperparameters (fused flat apply; --optimizer.flat_apply)."""
    schedule = linear_warmup_cosine_annealing(
        t.learning_rate, t.warmup_steps, t.total_steps
    )

    def factory(spec, params):
        from dedloc_tpu.optim.flat import FlatLars

        # build_swav's lars() passes no exclude_mask_fn: no skipped spans
        return FlatLars(
            spec, [False] * len(spec), schedule,
            momentum=t.momentum,
            weight_decay=t.weight_decay,
            trust_coefficient=t.trust_coefficient,
        )

    return factory


def run_swav(args: SwAVCollaborationArguments) -> TrainState:
    ensure_compile_cache()
    t = args.training
    cfg, spec, model, tx = build_swav(args)
    dht, _public_key = build_dht(args)
    logger.info(f"swav peer DHT listening on {dht.port}")
    # swarm telemetry (--telemetry.*, docs/observability.md): same wiring as
    # the ALBERT trainer; disabled (default) costs nothing
    from dedloc_tpu.roles.common import configure_role_telemetry

    tele, tele_close = configure_role_telemetry(args, _public_key)

    # slice-as-one-peer (same mapping as the ALBERT trainer): crops shard
    # over the data axis, so the sinkhorn sums inside the jitted loss ride
    # ICI psums — the reference's NCCL all_reduce world, compiler-inserted
    mesh = None
    slice_factor = max(1, t.mesh_devices)
    if t.mesh_devices > 1:
        from dedloc_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(t.mesh_devices, device_offset=t.mesh_device_offset)
        logger.info(f"swav slice mesh: {mesh.shape}")
    slice_batch = t.per_device_batch_size * slice_factor
    if slice_batch < 8:
        # sinkhorn equipartitions THIS peer's local batch over the
        # prototypes: at a handful of global-crop embeddings the transport
        # is pure noise and the peer's gradients carry ~19x the per-sample
        # energy of a B=16 peer (measured at init; core/config.py
        # contrib_clip_per_sample). The clip bounds the damage, but such a
        # peer contributes little signal — prefer a larger batch or aux.
        logger.warning(
            f"per-peer batch {slice_batch} is too small for a stable "
            "sinkhorn assignment; this peer's gradients will be mostly "
            "noise (clipped by optimizer.contrib_clip_per_sample). "
            "Raise --training.per_device_batch_size (>=8) or join as an "
            "aux bandwidth donor instead."
        )

    rng = jax.random.PRNGKey(t.seed)
    init_crops = [
        jnp.zeros((count * t.per_device_batch_size, size, size, spec.channels))
        for size, count in zip(spec.sizes, spec.counts)
    ]
    variables = model.init(rng, init_crops, True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    state = jax.jit(lambda p: TrainState.create(p, tx))(params)
    queue = (
        SwAVQueue.create(cfg, jax.random.PRNGKey(t.seed + 1))
        if cfg.queue_length
        else None
    )

    opt = CollaborativeOptimizer(
        tx,
        dht,
        prefix=args.dht.experiment_prefix,
        target_batch_size=args.optimizer.target_batch_size,
        batch_size_lead=args.optimizer.batch_size_lead,
        batch_size_per_step=(
            slice_batch * t.gradient_accumulation_steps
        ),
        bandwidth=args.averager.bandwidth,
        compression=args.averager.compression,
        chunk_size=args.averager.chunk_size,
        error_feedback=args.optimizer.error_feedback,
        overlap_averaging=args.optimizer.overlap_averaging,
        target_group_size=args.averager.target_group_size,
        averaging_expiration=args.averager.averaging_expiration,
        averaging_timeout=args.averager.averaging_timeout,
        metadata_expiration=args.averager.metadata_expiration,
        statistics_expiration=args.optimizer.statistics_expiration,
        contrib_clip_per_sample=args.optimizer.contrib_clip_per_sample,
        ramp_rounds=args.optimizer.ramp_rounds,
        health_gate_loss_ratio=args.optimizer.health_gate_loss_ratio,
        state_sync_retries=args.averager.state_sync_retries,
        state_sync_backoff=args.averager.state_sync_backoff,
        # device-resident gradient pipeline + fused flat LARS apply (same
        # knobs as the ALBERT trainer; docs/perf.md round 6)
        device_flat=args.optimizer.device_flat,
        flat_opt_factory=(
            _build_flat_lars_factory(t)
            if args.optimizer.flat_apply else None
        ),
        # swarm checkpointing (--checkpoint.*): same wiring as the ALBERT
        # trainer — sharded serving/catalog/restore with blob fallback
        **checkpoint_kwargs(args, _public_key),
        client_mode=args.dht.client_mode,
        relay=args.dht.relay or None,
        listen_port=args.averager.listen_port,
        advertised_host=args.dht.advertised_host or None,
        mesh=mesh,
        post_apply=make_prototype_post_apply(),
        verbose=True,
    )
    # disk resume (same contract as the ALBERT trainer): newest checkpoint
    # restores params + batch_stats and seeds the collaborative counter; a
    # LIVE collaboration below still wins. LARC momentum is not part of the
    # swav checkpoint (the reference's vissl phase resume also rebuilds the
    # optimizer) — it re-warms within a few steps.
    from dedloc_tpu.collaborative.optimizer import _named_to_tree
    from dedloc_tpu.utils.checkpoint import load_latest_checkpoint

    resumed = load_latest_checkpoint(t.output_dir)
    if resumed is not None:
        ckpt_step, tree, meta = resumed
        template = jax.device_get((state.params, batch_stats))
        try:
            params_t, bs_t = _named_to_tree(tree, template)
            state = state.replace(
                step=jnp.asarray(ckpt_step, jnp.int32),
                params=jax.device_put(params_t),
            )
            batch_stats = jax.device_put(bs_t)
            opt.local_step = int(meta.get("local_step", ckpt_step))
            logger.info(f"resumed from local checkpoint at step {ckpt_step}")
        except (KeyError, ValueError) as e:
            logger.warning(f"checkpoint incompatible ({e!r}); starting fresh")
            resumed = None  # genuinely fresh: keep cold-start adoption below
    # a DEEPER live collaboration wins over the disk checkpoint; the
    # reverse race (fresh partner raced ahead while we compiled) must not
    # (only_if_newer — see load_state_from_peers). Cold starts keep the
    # unconditional adopt so simultaneous fresh replicas begin identical.
    state = opt.load_state_from_peers(
        state, only_if_newer=resumed is not None
    )
    # share a pre-training snapshot (same as the ALBERT trainer): partners
    # that start while this peer is still compiling must find a provider —
    # and a resumed peer's deep state must be visible before its first step
    opt.seed_state_sharing(state)

    accumulate = make_swav_accumulate_step(
        model, cfg, mesh=mesh, num_crop_groups=len(spec.sizes)
    )
    grad_acc = zeros_like_grads(state.params)
    n_acc = jnp.zeros([], jnp.int32)
    if t.image_folder:
        # real JPEGs through the full SSL augmentation stack
        # (ImgPilToMultiCrop + flip + color distortion + blur + normalize)
        batches = image_folder_multicrop_batches(
            t.image_folder, spec, slice_batch, seed=t.seed
        )
    else:
        batches = synthetic_multicrop_batches(spec, slice_batch, seed=t.seed)
    samples = slice_batch * t.gradient_accumulation_steps

    # mutable local (non-collaborative) state, closed over by the step fn
    local = {"batch_stats": batch_stats, "queue": queue,
             "grad_acc": grad_acc, "n_acc": n_acc}

    def step_fn(state, micro_batches: List[List[np.ndarray]]):
        # one trainer step = one accumulation boundary
        loss = jnp.zeros([])
        for crops in micro_batches:
            use_queue = bool(
                cfg.queue_length and opt.local_step >= cfg.queue_start_step
            )
            if use_queue and not local.get("queue_engaged"):
                local["queue_engaged"] = True
                logger.info(
                    f"queue engaged at global step {opt.local_step} "
                    f"(queue_start_step={cfg.queue_start_step}, "
                    f"length={cfg.queue_length})"
                )
                if cfg.queue_start_step < 2 * t.warmup_steps:
                    # measured negative (BASELINE.md round 5): engaging the
                    # queue on a near-random trunk fills it with embeddings
                    # that mislead sinkhorn and collapse the representation
                    # (linear probe BELOW the random-trunk control); the
                    # reference engages its queue deep into training
                    # (swav/README.md:28, queue.start_iter ~98-100k)
                    logger.warning(
                        "queue engaged before the trunk is trained "
                        f"(start {cfg.queue_start_step} < 2x warmup "
                        f"{t.warmup_steps}); stale near-random embeddings "
                        "can collapse the representation — prefer a later "
                        "--training.queue_start_step"
                    )
            with steps.phase("h2d"):
                device_crops = _put_crops(crops)
            local["grad_acc"], local["n_acc"], local["batch_stats"], \
                local["queue"], metrics = accumulate(
                    state.params,
                    local["batch_stats"],
                    local["queue"],
                    local["grad_acc"],
                    local["n_acc"],
                    device_crops,
                    jnp.asarray(opt.local_step, jnp.int32),
                    use_queue,
                )
            loss = metrics["loss"]
        state, local["grad_acc"], local["n_acc"], _stepped = opt.step(
            state, local["grad_acc"], local["n_acc"], samples
        )
        if _stepped:
            with steps.phase("post_step"):
                _after_global_step(loss)
        return state, {"loss": loss, "global_step": opt.local_step}

    def _after_global_step(loss) -> None:
        """The tail of a global step, as the ALBERT trainer's: one host
        read of the loss, the signed metrics bus, the train log — spans
        ``loss_sync`` / ``publish`` / ``log`` under ``post_step``."""
        with steps.phase("loss_sync"):
            # advertise the loss for the trunk-health gate — one host sync
            # per GLOBAL step, the same cadence the ALBERT trainer pays
            loss_host = float(loss)
        opt.report_loss(loss_host)
        sps = float(opt.performance_ema.samples_per_second)
        row = steps.train_log_row(steps.current())
        with steps.phase("publish"):
            # ride the signed metrics bus like the ALBERT trainer: the
            # coordinator's throughput/loss aggregate and swarm-health view
            # work for SwAV fleets too
            publish_step_metrics(
                dht, args, _public_key, opt, tele, row,
                samples=samples, loss=loss_host, mini_steps=1, sps=sps,
            )
        if train_log is not None:
            with steps.phase("log"):
                train_log.write(opt, row, loss_host, sps)

    def _put_crops(crops):
        if mesh is None:
            return [jnp.asarray(c) for c in crops]
        from jax.sharding import NamedSharding, PartitionSpec as P

        data = NamedSharding(mesh, P("data"))
        return [jax.device_put(jnp.asarray(c), data) for c in crops]

    def grouped(it: Iterator, k: int) -> Iterator[list]:
        while True:
            group = []
            for _ in range(k):
                try:
                    group.append(next(it))
                except StopIteration:
                    # PEP 479: returning (not leaking StopIteration) ends the
                    # generator so Trainer stops gracefully on finite data
                    return
            yield group

    def save_fn(ctx):
        host = jax.device_get(
            (ctx.train_state.params, local["batch_stats"])
        )
        from dedloc_tpu.collaborative.optimizer import _tree_to_named

        save_checkpoint(
            t.output_dir,
            opt.local_step,
            _tree_to_named(host),
            metadata={"local_step": opt.local_step},
            save_total_limit=t.save_total_limit,
        )

    trainer = Trainer(
        step_fn,
        hooks=default_hooks(
            log_every=t.log_every,
            save_fn=save_fn if t.save_steps else None,
            save_every=t.save_steps,
            device_stats_every=t.device_stats_every,
        ),
        recorder=StepRecorder(
            telemetry=tele, profile=profile_gate(args.telemetry)
        ),
    )
    train_log = open_train_log(t.train_log_path)
    try:
        state, _ctx = trainer.train(
            state,
            grouped(batches, t.gradient_accumulation_steps),
            max_steps=t.max_local_steps or 10**9,
        )
    finally:
        if train_log is not None:
            train_log.close()
        tele_close()
        opt.shutdown()
        dht.shutdown()
    return state


def main(argv=None) -> None:
    run_swav(parse_config(SwAVCollaborationArguments, argv))


if __name__ == "__main__":
    main()
