"""SwAV collaborative trainer peer.

Capability parity with the reference's swav workload driver (reference:
swav/vissl/vissl/trainer/trainer_main.py:138-204 phase loop +
swav/ClassyVision/classy_vision/optim/sgd_collaborative.py:132-171): build
ResNet-50 trunk + prototypes head, LARC-SGD with warmup-cosine schedule,
DHT + CollaborativeOptimizer (target_batch_size 32768), multicrop pipeline —
a BUILDER: what iterates boundaries is ``roles/loop.py``, as for every model.

TPU-native shape (SURVEY.md §3.4): the reference's two communication worlds —
NCCL all_reduce inside the sinkhorn loop and hivemind averaging per optimizer
step — become (a) ICI psums XLA inserts when the jitted step is sharded over
a mesh and (b) the DHT/DCN averaging in CollaborativeOptimizer. The GLOBAL
collaboration step (not the local one) gates the queue and the prototype
freeze, exactly as the fork feeds collaboration_state.optimizer_step to the
loss (standard_train_step.py:153).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config
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
from dedloc_tpu.parallel.train_step import TrainState
from dedloc_tpu.roles.common import (
    build_collaborative_optimizer,
    build_dht,
    configure_role_telemetry,
)
from dedloc_tpu.roles.loop import LoopModel, run_boundary_loop
from dedloc_tpu.telemetry import steps
from dedloc_tpu.utils.backend import ensure_compile_cache
from dedloc_tpu.utils.checkpoint import (
    load_latest_checkpoint,
    named_to_tree,
    save_checkpoint,
    tree_to_named,
)
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
    LARS hyperparameters (the fused flat apply)."""
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


def make_put_crops(crop_sharding=None, upload=jnp.asarray):
    """Host crop groups -> device arrays (onto ``crop_sharding`` under a
    mesh), for ``LoopModel.put``. ``upload`` is asynchronous: the runtime may
    read a host array until the transfer is done, and the synthetic source
    rewrites a batch's arrays once ``VALID_DRAWS`` + 1 later batches are
    drawn (``data/multicrop.py``) — so each call first waits for the
    PREVIOUS batch's arrays: when batch k + 2 is drawn, batch k is on the
    device. (An upload has long finished by then; the wait is for the
    contract, not for time.)"""
    in_flight = None

    def put_crops(crops):
        nonlocal in_flight
        jax.block_until_ready(in_flight)
        # dropped BEFORE this batch goes up: the last one's device arrays
        # are not held beside it for the wait's sake
        in_flight = None
        in_flight = [upload(c) for c in crops]
        if crop_sharding is not None:
            in_flight = [jax.device_put(c, crop_sharding) for c in in_flight]
        return in_flight

    return put_crops


def run_swav(args: SwAVCollaborationArguments) -> TrainState:
    # this peer's set-up record (telemetry/steps.py), under the same lap
    # names as ``run_trainer``; a phase this role lacks has no lap
    with steps.setup_record(logger):
        return _run_swav(args)


def _run_swav(args: SwAVCollaborationArguments) -> TrainState:
    ensure_compile_cache()
    t = args.training
    cfg, spec, model, tx = build_swav(args)
    steps.lap("prepare")
    dht, public_key = build_dht(args)
    logger.info(f"swav peer DHT listening on {dht.port}")
    # swarm telemetry (--telemetry.*, docs/observability.md): same wiring as
    # the ALBERT trainer; disabled (default) costs nothing
    tele, tele_close = configure_role_telemetry(args, public_key)
    steps.lap("dht")

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
    # the EAGER init: one small program per initializer and shape
    steps.lap("init_state")

    opt = build_collaborative_optimizer(
        args, tx, dht, public_key,
        batch_size_per_step=slice_batch * t.gradient_accumulation_steps,
        flat_opt_factory=_build_flat_lars_factory(t),
        mesh=mesh,
        post_apply=make_prototype_post_apply(),
    )
    steps.lap("collab_optimizer")
    # disk resume (same contract as the ALBERT trainer): newest checkpoint
    # restores params + batch_stats and seeds the collaborative counter; a
    # LIVE collaboration below still wins. LARC momentum is not part of the
    # swav checkpoint (the reference's vissl phase resume also rebuilds the
    # optimizer) — it re-warms within a few steps.
    resumed = load_latest_checkpoint(t.output_dir)
    if resumed is not None:
        ckpt_step, tree, meta = resumed
        template = jax.device_get((state.params, batch_stats))
        try:
            params_t, bs_t = named_to_tree(tree, template)
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
    steps.lap("resume")
    # a DEEPER live collaboration wins over the disk checkpoint; the
    # reverse race (fresh partner raced ahead while we compiled) must not
    # (only_if_newer — see load_state_from_peers). Cold starts keep the
    # unconditional adopt so simultaneous fresh replicas begin identical.
    state = opt.load_state_from_peers(
        state, only_if_newer=resumed is not None
    )
    steps.lap("state_from_peers")
    # share a pre-training snapshot (same as the ALBERT trainer): partners
    # that start while this peer is still compiling must find a provider —
    # and a resumed peer's deep state must be visible before its first step
    opt.seed_state_sharing(state)
    steps.lap("seed_state_sharing")

    accumulate = make_swav_accumulate_step(
        model, cfg, mesh=mesh, num_crop_groups=len(spec.sizes)
    )
    # the synthetic source's running totals (``data.draws``,
    # ``data.draws_ready``): onto every stepping record
    data_counters: dict = {}
    if t.image_folder:
        # real JPEGs through the full SSL augmentation stack
        # (ImgPilToMultiCrop + flip + color distortion + blur + normalize)
        batches = image_folder_multicrop_batches(
            t.image_folder, spec, slice_batch, seed=t.seed
        )
    else:
        batches = synthetic_multicrop_batches(
            spec, slice_batch, seed=t.seed, stats=data_counters
        )
    steps.lap("data_source")

    queue_engaged = False
    crop_sharding = (
        None if mesh is None else NamedSharding(mesh, PartitionSpec("data"))
    )

    def micro_step(state, grad_acc, n_acc, crops):
        # batch_stats and the queue are local (non-collaborative) state,
        # carried from micro-batch to micro-batch here
        nonlocal batch_stats, queue, queue_engaged
        use_queue = bool(
            cfg.queue_length and opt.local_step >= cfg.queue_start_step
        )
        if use_queue and not queue_engaged:
            queue_engaged = True
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
        grad_acc, n_acc, batch_stats, queue, metrics = accumulate(
            state.params, batch_stats, queue, grad_acc, n_acc, crops,
            jnp.asarray(opt.local_step, jnp.int32), use_queue,
        )
        return grad_acc, n_acc, metrics

    def save(state, step):
        host = jax.device_get((state.params, batch_stats))
        save_checkpoint(
            t.output_dir,
            step,
            tree_to_named(host),
            metadata={"local_step": step},
            save_total_limit=t.save_total_limit,
        )

    try:
        state = run_boundary_loop(
            args,
            LoopModel(
                batches=batches, micro_step=micro_step, save=save,
                put=make_put_crops(crop_sharding),
                host_counters=data_counters,
            ),
            state, opt, dht, public_key, tele, tele_close,
        )
    finally:
        # the source's threads end with the run, not with the last
        # reference to it (a wrapper that cannot close drops it instead)
        getattr(batches, "close", lambda: None)()
    if t.save_steps:
        # vissl saves at every phase end (log_hooks.py:268-330): the run
        # that ends leaves its last state on disk, whatever the cadence
        save(state, opt.local_step)
    return state


def main(argv=None) -> None:
    run_swav(parse_config(SwAVCollaborationArguments, argv))


if __name__ == "__main__":
    main()
