"""Trainer peer: the builder of the canonical collaborative training run.

Capability parity with albert/run_trainer.py:210-297 — build model + LAMB +
DHT + CollaborativeOptimizer, resume from the latest local checkpoint, pull
newer state from peers at start (on_train_begin semantics :124-128), then
hand the boundary loop (``roles/loop.py``) the model's micro-step: jitted
accumulate per micro-batch; at every accumulation boundary the
collaborative optimizer takes control (:130-170).

TPU-native shape: the hot path is ONE jitted accumulate step with a donated
device-resident grad accumulator; the jit↔Python seam is crossed once per
accumulation boundary, not per micro-batch (SURVEY.md §7 hard-part b).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.data.streaming import peer_shuffle_seed
from dedloc_tpu.parallel.train_step import TrainState, make_accumulate_step
from dedloc_tpu.roles.common import (
    ALBERT,
    build_collaborative_optimizer,
    build_dht,
    build_flat_opt_factory,
    build_loss_fn,
    build_model,
    build_optimizer,
    configure_role_telemetry,
    drop_collator_keys,
    model_family,
)
from dedloc_tpu.roles.loop import LoopModel, run_boundary_loop
from dedloc_tpu.telemetry import steps
from dedloc_tpu.utils.backend import describe_backend, ensure_compile_cache
from dedloc_tpu.utils.checkpoint import (
    load_latest_checkpoint,
    named_to_tree,
    save_checkpoint,
    tree_to_named,
)
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def run_trainer(args: CollaborationArguments) -> TrainState:
    # the start of this peer, entry -> end of its first global step, is ONE
    # set-up record (telemetry/steps.py): each ``steps.lap`` below closes the
    # phase that ends there, the boundary loop closes the record
    with steps.setup_record(logger):
        return _run_trainer(args)


def _run_trainer(args: CollaborationArguments) -> TrainState:
    cache_dir = ensure_compile_cache()
    # JAX lands on the CPU without a word when it finds no accelerator:
    # say where this peer computes, and how its Pallas kernels will run
    logger.info(
        "backend: "
        + " ".join(f"{k}={v!r}" for k, v in describe_backend().items())
        + f" compile_cache={cache_dir!r}"
    )
    # gated runs: token handshake BEFORE any heavy setup, so bad credentials
    # fail in milliseconds (contributor notebook cell-2 ordering)
    from dedloc_tpu.roles.common import build_authorizer

    authorizer, authority_public_key = build_authorizer(args)
    # slice-as-one-peer: with mesh_devices > 1 this process drives a
    # data-parallel mesh; the micro-batch grad mean lowers to ICI psums and
    # the collaboration sees the whole slice as a single member. A
    # mesh_seq_devices factor carves a "seq" axis out of the slice for
    # sequence parallelism (ring attention).
    mesh = None
    if args.training.mesh_devices > 1:
        from dedloc_tpu.parallel.mesh import make_mesh, put_batch

        sp = max(1, args.training.mesh_seq_devices)
        tp = max(1, args.training.mesh_model_devices)
        pp = max(1, args.training.mesh_pipe_devices)
        ep = max(1, args.training.mesh_expert_devices)
        if args.training.mesh_devices % (sp * tp * pp * ep):
            raise ValueError(
                f"mesh_seq_devices ({sp}) x mesh_model_devices ({tp}) x "
                f"mesh_pipe_devices ({pp}) x mesh_expert_devices ({ep}) "
                f"must divide mesh_devices ({args.training.mesh_devices})"
            )
        if pp > 1 and (sp > 1 or tp > 1):
            # the pipeline stage body runs inside its own shard_map; ring
            # attention ("seq") and the TP layouts ("model") place their
            # collectives via GSPMD annotations, which don't apply there
            raise ValueError(
                "mesh_pipe_devices composes with the data axis only; "
                "seq/model axes need collectives inside the pipeline stage"
            )
        if ep > 1 and not args.training.moe_experts:
            raise ValueError(
                "mesh_expert_devices > 1 needs --training.moe_experts > 0"
            )
        if args.training.moe_experts and (
            args.training.moe_experts % ep
        ):
            raise ValueError(
                f"moe_experts ({args.training.moe_experts}) must divide "
                f"evenly over mesh_expert_devices ({ep})"
            )
        dp = args.training.mesh_devices // (sp * tp * pp * ep)
        names, dims = ["data"], [dp]
        if tp > 1:
            names.append("model"); dims.append(tp)
        if sp > 1:
            names.append("seq"); dims.append(sp)
        if pp > 1:
            names.append("pipe"); dims.append(pp)
        if ep > 1:
            names.append("expert"); dims.append(ep)
        mesh = make_mesh(
            args.training.mesh_devices,
            axis_names=tuple(names),
            shape=tuple(dims) if len(dims) > 1 else None,
            device_offset=args.training.mesh_device_offset,
        )
        logger.info(f"slice mesh: {mesh.shape}")
    elif (
        args.training.mesh_seq_devices > 1
        or args.training.mesh_model_devices > 1
        or args.training.mesh_pipe_devices > 1
        or args.training.mesh_expert_devices > 1
    ):
        raise ValueError(
            "mesh_seq/model/pipe/expert_devices > 1 require mesh_devices > 1"
        )
    if args.training.attention_impl == "ring" and (
        mesh is None or "seq" not in mesh.axis_names
    ):
        # fail here with the cause, not deep inside the first jitted trace
        raise ValueError(
            "attention_impl='ring' needs a sequence-parallel mesh axis: set "
            "--training.mesh_seq_devices > 1 (and mesh_devices divisible by it)"
        )

    cfg, model = build_model(
        args.training.model_size,
        args.training.remat_policy,
        args.training.attention_impl,
        args.training.vocab_size,
        mesh=mesh,
        pipe_mesh=(
            mesh if mesh is not None and "pipe" in mesh.axis_names else None
        ),
        pipe_microbatches=args.training.pipe_microbatches,
        moe_experts=args.training.moe_experts,
        moe_mesh=(
            mesh if mesh is not None and "expert" in mesh.axis_names else None
        ),
        moe_capacity_factor=args.training.moe_capacity_factor,
        moe_aux_weight=args.training.moe_aux_weight,
        num_hidden_layers=args.training.num_hidden_layers,
        expert_shard=args.training.expert_shard,
        head_shard=args.training.head_shard,
    )
    family = model_family(cfg)
    tx = build_optimizer(args)
    # the backend, the token handshake, the mesh, the model and optimizer
    # OBJECTS: no array yet
    steps.lap("prepare")
    # gated: record-sign with the token key, so the signed subkey digests
    # to this peer's verified identity (ledger binding, roles/common.py)
    dht, public_key = build_dht(
        args,
        private_key=(
            authorizer.local_private_key if authorizer is not None else None
        ),
    )
    logger.info(f"trainer DHT listening on {dht.port}")
    # swarm telemetry (--telemetry.*, docs/observability.md): disabled
    # (default) => None and the instrumented seams stay free
    tele, tele_close = configure_role_telemetry(args, public_key)
    steps.lap("dht")

    rng = jax.random.PRNGKey(args.training.seed)
    seq = min(args.training.seq_length, cfg.max_position_embeddings)
    slice_batch = args.training.per_device_batch_size * max(
        1, args.training.mesh_devices
    )
    # ONE jitted init: the init-time forward is dead code under jit (only
    # the initializers run, whatever the batch), and with a mesh the state
    # is born replicated on it instead of being staged through device 0
    state = jax.jit(
        lambda r: TrainState.create(
            model.init(r, jnp.zeros((slice_batch, seq), jnp.int32))["params"],
            tx,
        ),
        out_shardings=None if mesh is None else NamedSharding(mesh, P()),
    )(rng)
    # the init's trace, lowering and compile; the device runs it beside what
    # follows, up to the first host read of the state
    steps.lap("init_state")

    # local resume (run_trainer.py:56-70): newest checkpoint* dir wins
    resumed = load_latest_checkpoint(args.training.output_dir)
    resumed_local_step = 0
    if resumed is not None:
        step, tree, meta = resumed
        template = jax.device_get((state.params, state.opt_state))
        params_t, opt_t = named_to_tree(tree, template)
        state = state.replace(
            step=jnp.asarray(step, jnp.int32),
            params=jax.device_put(params_t),
            opt_state=jax.device_put(opt_t),
        )
        # carry the COLLABORATIVE counter too: when a whole collaboration
        # restarts from disk (fresh DHT, nobody to pull state from), round
        # ids and published metrics must continue from the checkpoint's
        # global step, not restart at 0
        resumed_local_step = int(meta.get("local_step", step))
        logger.info(f"resumed from local checkpoint at step {step}")
    steps.lap("resume")

    if args.training.zero_sharding and mesh is None:
        raise ValueError(
            "--training.zero_sharding shards optimizer moments over a slice "
            "mesh; set --training.mesh_devices > 1"
        )
    # tensor parallelism: Megatron-style param layout over the "model" axis
    # (parallel/sharding.py rules); moments follow their params' layout.
    # EP composes by rule concatenation: the expert-stacked MoE leaves
    # shard over "expert", everything TP doesn't claim stays replicated.
    param_sharding = None
    shard_rules = None
    if mesh is not None and (
        "model" in mesh.axis_names or "expert" in mesh.axis_names
    ):
        if family is not ALBERT:
            raise ValueError(
                "mesh_model_devices / mesh_expert_devices: the tensor- and "
                "expert-parallel layouts are ALBERT's (parallel/sharding.py)"
            )
        from dedloc_tpu.parallel.sharding import (
            ALBERT_EP_RULES,
            ALBERT_TP_RULES,
            partition_specs,
        )

        shard_rules = tuple(
            (ALBERT_TP_RULES if "model" in mesh.axis_names else ())
        ) + tuple(
            (ALBERT_EP_RULES if "expert" in mesh.axis_names else ())
        )
        param_sharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            partition_specs(state.params, shard_rules),
        )
    opt_sharding = None
    if mesh is not None and (args.training.zero_sharding
                             or param_sharding is not None):
        # ZeRO-1: LAMB moments shard over the slice's data axis; GSPMD
        # inserts the gathers the elementwise update needs (parallel/zero.py).
        # With TP/EP, moments of sharded params follow the param layout and
        # ZeRO (when enabled) shards only the rest.
        from dedloc_tpu.parallel.zero import opt_state_shardings

        opt_sharding = opt_state_shardings(
            state.opt_state, mesh,
            axis="data" if args.training.zero_sharding else None,
            tp_rules=shard_rules,
        )

    opt = build_collaborative_optimizer(
        args, tx, dht, public_key,
        batch_size_per_step=(
            slice_batch * args.training.gradient_accumulation_steps
        ),
        flat_opt_factory=build_flat_opt_factory(args),
        mesh=mesh,
        opt_state_sharding=opt_sharding,
        param_sharding=param_sharding,
        sign_step_mask=family.sign_step_mask,
        authorizer=authorizer,
        authority_public_key=authority_public_key,
    )
    steps.lap("collab_optimizer")
    # catch up with the collaboration before training (:124-128)
    # disk-resume seeds the collaborative counter; a DEEPER live
    # collaboration below still wins — only_if_newer guards the reverse
    # race (a fresh partner that advanced the counter while we compiled
    # must not beat the resumed checkpoint)
    opt.local_step = max(opt.local_step, resumed_local_step)
    # only_if_newer ONLY when a checkpoint was actually restored: a fresh
    # cold-start peer must still adopt a same-step provider's params so
    # simultaneously-starting replicas begin identical
    state = opt.load_state_from_peers(
        state, only_if_newer=resumed_local_step > 0
    )
    steps.lap("state_from_peers")
    if mesh is not None:
        # commit state onto the mesh once — otherwise accumulate's
        # replicated in_shardings would re-broadcast the full params from
        # the default device on every micro-batch until the first global step
        repl = NamedSharding(mesh, P())
        state = state.replace(
            step=jax.device_put(state.step, repl),
            params=jax.device_put(state.params, param_sharding or repl),
            opt_state=jax.device_put(
                state.opt_state, opt_sharding or repl
            ),
        )
        steps.lap("mesh_commit")
    # share a pre-training snapshot: partners that miss the first rounds
    # (slow hosts still compiling) must find a state provider immediately
    opt.seed_state_sharing(state)
    steps.lap("seed_state_sharing")

    loss_fn = build_loss_fn(model)
    # what the layers keep for their backward is a name of the table in
    # models/remat.py; its bytes are the gauge ``remat.kept_bytes``
    logger.info(f"remat: remat_policy={cfg.remat_policy}")
    accumulate = make_accumulate_step(
        loss_fn,
        mesh=mesh,
        # sequence-parallel layout: shard batch seq dims over the mesh's
        # "seq" axis so ring attention sees its expected layout with zero
        # per-layer relayout (ADVICE r2: activations were full-S per device)
        seq_axis="seq" if (mesh is not None and "seq" in mesh.axis_names)
        else None,
        seq_length=seq,
        param_sharding=param_sharding,
    )
    data_rng = jax.random.PRNGKey(peer_shuffle_seed(public_key))

    def micro_step(state, grad_acc, n_acc, batch):
        nonlocal data_rng
        data_rng, sub = jax.random.split(data_rng)
        return accumulate(state.params, grad_acc, n_acc, batch, sub)

    def put(batch):
        return put_batch(
            batch, mesh,
            seq_axis="seq" if "seq" in mesh.axis_names else None,
            seq_length=seq,
        )

    def save(state, step):
        host = jax.device_get((state.params, state.opt_state))
        save_checkpoint(
            args.training.output_dir,
            step,
            tree_to_named(host),
            metadata={"step": int(state.step), "local_step": step},
            save_total_limit=args.training.save_total_limit,
        )

    # drop_collator_keys runs inside the draw: timed as data_wait
    batches = map(
        drop_collator_keys, _make_batches(args, cfg, public_key, slice_batch)
    )
    steps.lap("data_source")
    return run_boundary_loop(
        args,
        LoopModel(
            batches=batches,
            micro_step=micro_step,
            save=save,
            put=put if mesh is not None else None,
            step_gauges=family.step_gauges,
            step_counters=family.step_counters,
            # ``remat.kept_bytes``, once the step has been traced
            host_gauges=accumulate.gauges,
            # ``moe.compute_copy_builds``, where the step keeps copies
            host_counters=accumulate.counters,
            # the MFU gauge uses the same analytic model-FLOPs formula and
            # peak table as bench.py
            tflops_per_sample=family.tflops_per_sample(cfg, seq),
        ),
        state, opt, dht, public_key, tele, tele_close,
        log_perf_steps=args.training.log_perf_steps,
    )


def _make_batches(
    args: CollaborationArguments, cfg, public_key: bytes,
    slice_batch: Optional[int] = None,
):
    """Synthetic fixture by default; a tokenized-on-disk dataset when
    ``dataset_path`` is set (tokenize_wikitext103 output layout)."""
    seed = peer_shuffle_seed(public_key)  # per-peer independent shuffling
    batch_size = slice_batch or args.training.per_device_batch_size
    family = model_family(cfg)
    share = args.training.image_token_share
    if share > 0 and not family.batch_positions:
        raise ValueError(
            f"model_size {args.training.model_size!r} reads no positions "
            f"from its batches (--training.image_token_share {share})"
        )
    if not args.training.streaming_files and not args.training.dataset_path:
        data = {"image_token_share": share} if family.batch_positions else {}
        return family.synthetic_batches(
            cfg, batch_size, args.training.seq_length, seed, **data
        )
    if family is not ALBERT:
        raise ValueError(
            "--training.streaming_files / --training.dataset_path read "
            "ALBERT's MLM corpora; a causal-LM corpus goes through "
            "data/causal_lm.pack_rows"
        )
    if args.training.streaming_files:
        # sahajbert-style streaming mode (dataset_streaming.py capability):
        # weighted lazy mix + per-peer shuffle buffer + on-the-fly tokenize
        from dedloc_tpu.data.mlm import SpecialTokens, max_predictions_for
        from dedloc_tpu.data.streaming import (
            make_text_source,
            prefetch,
            split_sentences,
            streaming_mlm_batches,
        )
        from dedloc_tpu.data.tokenizer import load_fast_tokenizer

        tok = load_fast_tokenizer(args.training.tokenizer_path)
        if tok.vocab_size > cfg.vocab_size:
            # fail fast: ids past the embedding table would be silently
            # clamped by XLA's gather, corrupting training without an error
            raise ValueError(
                f"tokenizer vocab ({tok.vocab_size}) exceeds the model's "
                f"vocab_size ({cfg.vocab_size}); retrain the tokenizer or "
                "use a larger model vocab"
            )
        tokens = SpecialTokens(
            cls_id=tok.cls_id, sep_id=tok.sep_id, pad_id=tok.pad_id,
            mask_id=tok.mask_id, vocab_size=tok.vocab_size,
        )
        weights = args.training.streaming_weights or (
            [1.0] * len(args.training.streaming_files)
        )
        seq = min(args.training.seq_length, cfg.max_position_embeddings)
        # http(s):// specs stream remotely with retry/resume; the bounded
        # prefetch overlaps network/tokenization with the training step
        return prefetch(streaming_mlm_batches(
            [make_text_source(p) for p in args.training.streaming_files],
            weights,
            lambda doc: [
                tok.encode_ids(s, add_special_tokens=False)
                for s in split_sentences(doc)
            ],
            tokens,
            batch_size,
            seq,
            seed,
            buffer_size=args.training.streaming_buffer_size,
            max_predictions=max_predictions_for(seq),
        ), size=8)
    from dedloc_tpu.data.disk import tokenized_dataset_batches

    return tokenized_dataset_batches(
        args.training.dataset_path,
        cfg,
        batch_size,
        args.training.seq_length,
        seed,
    )


def main(argv=None) -> None:
    run_trainer(parse_config(CollaborationArguments, argv))


if __name__ == "__main__":
    main()
