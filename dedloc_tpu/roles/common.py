"""Shared builders for role entry points: model, optimizer, DHT, data."""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dedloc_tpu.collaborative.metrics import make_validators
from dedloc_tpu.collaborative.optimizer import CollaborativeOptimizer
from dedloc_tpu.core.config import CollaborationArguments
from dedloc_tpu.data.mlm import SpecialTokens, mask_tokens, max_predictions_for
from dedloc_tpu.dht.dht import DHT
from dedloc_tpu.models.albert import (
    AlbertConfig,
    AlbertForPreTraining,
    albert_pretraining_loss,
    albert_pretraining_loss_gathered,
)
from dedloc_tpu.models.decoder import routed_grad_sink_mask, sign_step_mask
from dedloc_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    deepseek_v3_loss,
    deepseek_v3_train_tflops_per_sample,
    deepseek_v3_weight_decay_mask,
)
from dedloc_tpu.models.keye_vl2 import (
    KeyeVL2Config,
    KeyeVL2ForCausalLM,
    keye_vl2_loss,
    keye_vl2_train_tflops_per_sample,
    keye_vl2_weight_decay_mask,
)
from dedloc_tpu.models.kimi_linear import (
    KDA_GAUGES,
    KimiLinearConfig,
    KimiLinearForCausalLM,
    kimi_linear_loss,
    kimi_linear_train_tflops_per_sample,
    kimi_linear_weight_decay_mask,
)
from dedloc_tpu.models.laguna import (
    LagunaConfig,
    LagunaForCausalLM,
    laguna_loss,
    laguna_train_tflops_per_sample,
    laguna_weight_decay_mask,
)
from dedloc_tpu.models.lfm2_moe import (
    Lfm2MoeConfig,
    Lfm2MoeForCausalLM,
    lfm2_moe_loss,
    lfm2_moe_train_tflops_per_sample,
    lfm2_moe_weight_decay_mask,
)
from dedloc_tpu.models.nemotron_h import (
    SSD_GAUGES,
    NemotronHConfig,
    NemotronHForCausalLM,
    nemotron_h_loss,
    nemotron_h_train_tflops_per_sample,
    nemotron_h_weight_decay_mask,
)
from dedloc_tpu.models.ouro import (
    OuroConfig,
    OuroForCausalLM,
    ouro_loss,
    ouro_train_tflops_per_sample,
    ouro_weight_decay_mask,
)
from dedloc_tpu.models.sdar_moe import (
    SdarMoeConfig,
    SdarMoeForDiffusionLM,
    sdar_moe_loss,
    sdar_moe_train_tflops_per_sample,
)
from dedloc_tpu.models.smallthinker import (
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
    smallthinker_loss,
    smallthinker_train_tflops_per_sample,
    smallthinker_weight_decay_mask,
)
from dedloc_tpu.optim import (
    albert_weight_decay_mask,
    lamb,
    linear_warmup_linear_decay,
)
from dedloc_tpu.parallel.train_step import GradSinkLoss
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    """Everything the trainer role needs to know about a kind of model:
    the one table from a ``--training.model_size`` name to its config,
    module, loss, synthetic batch source and analytic FLOPs."""

    config: Any  # the config class; ``config.named(size)`` -> constructor
    module: Callable  # cfg -> nn.Module
    loss: Callable  # module -> loss_fn(params, batch, rng)
    # (cfg, batch_size, seq_length, seed) -> iterator of host batches
    synthetic_batches: Callable
    # (cfg, seq) -> analytic model TFLOPs of one fwd+bwd sample
    tflops_per_sample: Callable
    # params -> tree of bools, True where LAMB's weight decay applies
    weight_decay_mask: Callable
    # metrics of the loss that become gauges: summed on the device with the
    # loss and read with it, once per global step, as means over the step's
    # micro-batches — a vector (over passes, over expert layers) as
    # ``name.1`` .. ``name.n``, a scalar as ``name``
    step_gauges: Tuple[str, ...] = ()
    # metrics of the loss that are counts: the step's total, into a counter
    step_counters: Tuple[str, ...] = ()
    # params -> tree of bools, True for leaves stepped by the sign of their
    # cotangent (``optim.lamb.sign_stepped``) at ``sign_step``; None: none
    sign_step_mask: Optional[Callable] = None
    sign_step: float = 0.0
    # params -> tree of bools, True for leaves whose gradient the loss's
    # backward can add into the float32 accumulator in place: the loss then
    # takes ``grad_sinks`` (``parallel/train_step.GradSinkLoss``); None: none
    grad_sink_mask: Optional[Callable] = None
    # the model reads a token's positions from the batch: its synthetic
    # source takes the run's ``image_token_share`` and builds
    # ``position_ids`` and ``loss_weights`` (``data/causal_lm.py``)
    batch_positions: bool = False


def _albert_loss(model: AlbertForPreTraining) -> Callable:
    """Gathered masked-position loss when the batch carries ``mlm_positions``
    (the fast TPU layout); dense per-position loss otherwise. With an MoE
    config the Switch load-balancing aux loss (sowed into the "losses"
    collection by the encoder) is added at ``cfg.moe_aux_weight``."""
    moe = getattr(model.cfg, "moe_experts", 0) > 0

    def loss_fn(params, batch, rng):
        gathered = "mlm_positions" in batch
        apply_kwargs = dict(
            mlm_positions=batch["mlm_positions"] if gathered else None,
        )
        if moe:
            (mlm_logits, sop_logits), mutated = model.apply(
                {"params": params},
                batch["input_ids"],
                batch["attention_mask"],
                batch["token_type_ids"],
                mutable=("losses",),
                **apply_kwargs,
            )
        else:
            mlm_logits, sop_logits = model.apply(
                {"params": params},
                batch["input_ids"],
                batch["attention_mask"],
                batch["token_type_ids"],
                **apply_kwargs,
            )
        if gathered:
            loss, metrics = albert_pretraining_loss_gathered(
                mlm_logits,
                sop_logits,
                batch["mlm_label_ids"],
                batch["mlm_weights"],
                batch["sop_labels"],
            )
        else:
            loss, metrics = albert_pretraining_loss(
                mlm_logits, sop_logits, batch["mlm_labels"], batch["sop_labels"]
            )
        if moe:
            aux = sum(
                jnp.sum(leaf)
                for leaf in jax.tree_util.tree_leaves(mutated["losses"])
            )
            loss = loss + model.cfg.moe_aux_weight * aux
            metrics = dict(metrics, moe_aux=aux)
        return loss, metrics

    return loss_fn


def _albert_tflops(cfg: AlbertConfig, seq: int) -> float:
    from dedloc_tpu.telemetry.steps import albert_tflops_per_sample

    return albert_tflops_per_sample(cfg, seq, max_predictions_for(seq))


def _without_rng(loss: Callable) -> Callable:
    """A (model, params, batch, **kwargs) loss in the table's form: module
    -> loss_fn(params, batch, rng, **kwargs)."""
    return lambda model: lambda params, batch, rng, **kwargs: loss(
        model, params, batch, **kwargs
    )


def _ouro_batches(cfg, batch_size: int, seq_length: int,
                  seed: int) -> Iterator[Dict[str, np.ndarray]]:
    from dedloc_tpu.data.causal_lm import synthetic_causal_lm_batches

    return synthetic_causal_lm_batches(
        cfg.vocab_size, batch_size,
        min(seq_length, cfg.max_position_embeddings), seed,
    )


def _keye_batches(cfg, batch_size: int, seq_length: int, seed: int,
                  image_token_share: float = 0.0,
                  ) -> Iterator[Dict[str, np.ndarray]]:
    from dedloc_tpu.data.causal_lm import synthetic_causal_lm_batches

    # three position streams a token and the weight of every label, with
    # image spans at the run's share (0: text alone)
    return synthetic_causal_lm_batches(
        cfg.vocab_size, batch_size,
        min(seq_length, cfg.max_position_embeddings), seed,
        image_token_share=image_token_share, positions=True,
    )


def _sdar_batches(cfg, batch_size: int, seq_length: int,
                  seed: int) -> Iterator[Dict[str, np.ndarray]]:
    from dedloc_tpu.data.block_diffusion import (
        synthetic_block_diffusion_batches,
    )

    return synthetic_block_diffusion_batches(
        cfg.vocab_size, batch_size,
        min(seq_length, cfg.max_position_embeddings), cfg.block_length, seed,
    )


ALBERT = ModelFamily(
    config=AlbertConfig, module=AlbertForPreTraining, loss=_albert_loss,
    synthetic_batches=lambda *a: synthetic_mlm_batches(*a),
    tflops_per_sample=_albert_tflops,
    weight_decay_mask=albert_weight_decay_mask,
)
OURO = ModelFamily(
    config=OuroConfig, module=OuroForCausalLM,
    loss=_without_rng(ouro_loss),
    synthetic_batches=_ouro_batches,
    tflops_per_sample=ouro_train_tflops_per_sample,
    weight_decay_mask=ouro_weight_decay_mask,
    step_gauges=("lm.exit_prob", "lm.loss"),
)
DEEPSEEK_V3 = ModelFamily(
    config=DeepseekV3Config, module=DeepseekV3ForCausalLM,
    loss=_without_rng(deepseek_v3_loss),
    # ids over the held slice of the vocabulary (``cfg.vocab_size`` rows)
    synthetic_batches=_ouro_batches,
    tflops_per_sample=deepseek_v3_train_tflops_per_sample,
    weight_decay_mask=deepseek_v3_weight_decay_mask,
    step_gauges=(
        "moe.load_max_over_mean", "moe.local_slot_share", "moe.bias_abs_max",
        "moe.grad_sink_leaves", "moe.compute_copy_leaves",
        "moe.bulk_row_share",
    ),
    step_counters=("moe.dropped_slots",),
    sign_step_mask=sign_step_mask,
    sign_step=DeepseekV3Config.bias_update_speed,
    grad_sink_mask=routed_grad_sink_mask,
)
LFM2_MOE = dataclasses.replace(
    DEEPSEEK_V3,  # the same source, gauges, counter, sign rule and sinks
    config=Lfm2MoeConfig, module=Lfm2MoeForCausalLM,
    loss=_without_rng(lfm2_moe_loss),
    tflops_per_sample=lfm2_moe_train_tflops_per_sample,
    weight_decay_mask=lfm2_moe_weight_decay_mask,
    sign_step=Lfm2MoeConfig.bias_update_speed,
)
SMALLTHINKER = dataclasses.replace(
    DEEPSEEK_V3,  # the same source, counter and sinks; no bias: no sign rule
    config=SmallThinkerConfig, module=SmallThinkerForCausalLM,
    loss=_without_rng(smallthinker_loss),
    tflops_per_sample=smallthinker_train_tflops_per_sample,
    weight_decay_mask=smallthinker_weight_decay_mask,
    step_gauges=(
        "moe.load_max_over_mean", "moe.local_slot_share",
        "moe.grad_sink_leaves", "moe.compute_copy_leaves",
        "moe.bulk_row_share", "attn.band_tile_share",
    ),
    sign_step_mask=None, sign_step=0.0,
)
SDAR_MOE = dataclasses.replace(
    SMALLTHINKER,  # the same counter's rule, sinks and decay mask; no bias
    config=SdarMoeConfig, module=SdarMoeForDiffusionLM,
    loss=_without_rng(sdar_moe_loss),
    # a row, its noisy copy and the weight of every position's loss
    synthetic_batches=_sdar_batches,
    tflops_per_sample=sdar_moe_train_tflops_per_sample,
    step_gauges=(
        "moe.load_max_over_mean", "moe.local_slot_share",
        "moe.grad_sink_leaves", "moe.compute_copy_leaves",
        "moe.bulk_row_share", "attn.bd_tile_share", "diffusion.masked_share",
    ),
    step_counters=("moe.dropped_slots", "diffusion.masked_tokens"),
)
LAGUNA = dataclasses.replace(
    SMALLTHINKER,  # the same source, counter and sinks; no bias: no sign rule
    config=LagunaConfig, module=LagunaForCausalLM,
    loss=_without_rng(laguna_loss),
    tflops_per_sample=laguna_train_tflops_per_sample,
    weight_decay_mask=laguna_weight_decay_mask,
    step_gauges=SMALLTHINKER.step_gauges + (
        "attn.band_visible_share", "attn.gate_mean.full_attention",
        "attn.gate_mean.sliding_attention",
    ),
)
KEYE_VL2 = dataclasses.replace(
    SMALLTHINKER,  # the same counter and sinks; no bias: no sign rule
    config=KeyeVL2Config, module=KeyeVL2ForCausalLM,
    loss=_without_rng(keye_vl2_loss),
    synthetic_batches=_keye_batches, batch_positions=True,
    tflops_per_sample=keye_vl2_train_tflops_per_sample,
    weight_decay_mask=keye_vl2_weight_decay_mask,
    step_gauges=(
        "moe.load_max_over_mean", "moe.local_slot_share",
        "moe.grad_sink_leaves", "moe.compute_copy_leaves",
        "moe.bulk_row_share", "attn.select_kept_share",
        "attn.select_tile_share", "attn.index_loss_tile_share",
        "attn.index_peak", "attn.select_tie_block_share", "loss.index_kl",
        "data.image_token_share",
    ),
)
KIMI_LINEAR = dataclasses.replace(
    DEEPSEEK_V3,  # the same source, gauges, counter, sign rule and sinks
    config=KimiLinearConfig, module=KimiLinearForCausalLM,
    loss=_without_rng(kimi_linear_loss),
    tflops_per_sample=kimi_linear_train_tflops_per_sample,
    weight_decay_mask=kimi_linear_weight_decay_mask,
    step_gauges=DEEPSEEK_V3.step_gauges + KDA_GAUGES,
    sign_step=KimiLinearConfig.bias_update_speed,
)
NEMOTRON_H = dataclasses.replace(
    DEEPSEEK_V3,  # the same source, gauges, counter, sign rule and sinks
    config=NemotronHConfig, module=NemotronHForCausalLM,
    loss=_without_rng(nemotron_h_loss),
    tflops_per_sample=nemotron_h_train_tflops_per_sample,
    weight_decay_mask=nemotron_h_weight_decay_mask,
    step_gauges=DEEPSEEK_V3.step_gauges + SSD_GAUGES,
    sign_step=NemotronHConfig.bias_update_speed,
)
MODEL_FAMILIES: Dict[str, ModelFamily] = {
    "tiny": ALBERT, "large": ALBERT, "ouro_tiny": OURO, "ouro_2p6b": OURO,
    "kanana2_tiny": DEEPSEEK_V3, "kanana2_30b_a3b": DEEPSEEK_V3,
    "lfm2_tiny": LFM2_MOE, "lfm2_24b_a2b": LFM2_MOE,
    "smallthinker_tiny": SMALLTHINKER, "smallthinker_21b_a3b": SMALLTHINKER,
    "sdar_tiny": SDAR_MOE, "sdar_30b_a3b": SDAR_MOE,
    "laguna_tiny": LAGUNA, "laguna_xs2_33b_a3b": LAGUNA,
    "keye_vl2_tiny": KEYE_VL2, "keye_vl2_30b_a3b": KEYE_VL2,
    "kimi_linear_tiny": KIMI_LINEAR, "kimi_linear_48b_a3b": KIMI_LINEAR,
    "nemotron_h_tiny": NEMOTRON_H, "nemotron3_nano_30b_a3b": NEMOTRON_H,
}


def model_family(model) -> ModelFamily:
    """The family of a ``--training.model_size`` name, a config or a
    module."""
    if isinstance(model, str):
        if model not in MODEL_FAMILIES:
            raise ValueError(
                f"unknown model_size {model!r} "
                f"(expected one of {sorted(MODEL_FAMILIES)})"
            )
        return MODEL_FAMILIES[model]
    cfg = getattr(model, "cfg", model)
    for family in MODEL_FAMILIES.values():
        if isinstance(cfg, family.config):
            return family
    raise TypeError(f"no model family for {type(cfg).__name__}")


def build_model(
    model_size: str,
    remat_policy: str = "",
    attention_impl: str = "",
    vocab_size: int = 0,
    mesh=None,
    pipe_mesh=None,
    pipe_microbatches: int = 0,
    moe_experts: int = 0,
    moe_mesh=None,
    moe_capacity_factor: float = 0.0,
    moe_aux_weight: float = -1.0,
    num_hidden_layers: int = 0,
    expert_shard: str = "0/1",
    head_shard: str = "0/1",
):
    """(config, module) of a ``--training.model_size`` name, with the
    trainer's overrides. No WIDTH is an override: the depth is the one size
    a run may cut (a chip's share of a deeper deployment)."""
    family = model_family(model_size)
    overrides = {}
    if remat_policy:
        overrides["remat_policy"] = remat_policy
    if attention_impl:
        overrides["attention_impl"] = attention_impl
    if vocab_size:
        overrides["vocab_size"] = vocab_size
    if mesh is not None:
        overrides["mesh"] = mesh
    if num_hidden_layers:
        overrides["num_hidden_layers"] = num_hidden_layers
    if expert_shard != "0/1":
        if "expert_shard" not in family.config.__dataclass_fields__:
            raise ValueError(
                f"model_size {model_size!r} has no routed expert layer to "
                f"hold a share of (--training.expert_shard {expert_shard})"
            )
        index, _, count = expert_shard.partition("/")
        overrides["expert_shard"] = (int(index), int(count))
    if head_shard != "0/1":
        if "head_shard" not in family.config.__dataclass_fields__:
            raise ValueError(
                f"model_size {model_size!r} has no mixer that holds a share "
                f"of its heads (--training.head_shard {head_shard})"
            )
        index, _, count = head_shard.partition("/")
        overrides["head_shard"] = (int(index), int(count))
    if family is ALBERT:
        if remat_policy:
            from dedloc_tpu.models.albert import fused_ln_for_policy

            overrides["fused_ln"] = fused_ln_for_policy(remat_policy)
        if pipe_mesh is not None:
            overrides["pipe_mesh"] = pipe_mesh
            overrides["pipe_microbatches"] = pipe_microbatches
        if moe_experts:
            overrides["moe_experts"] = moe_experts
            if moe_mesh is not None:
                overrides["moe_mesh"] = moe_mesh
            if moe_capacity_factor > 0:
                overrides["moe_capacity_factor"] = moe_capacity_factor
            if moe_aux_weight >= 0:
                overrides["moe_aux_weight"] = moe_aux_weight
    elif pipe_mesh is not None or moe_experts:
        raise ValueError(
            f"model_size {model_size!r}: the pipeline and MoE paths are "
            "ALBERT's"
        )
    cfg = family.config.named(model_size)(**overrides)
    return cfg, family.module(cfg)


def build_optimizer(args: CollaborationArguments):
    """LAMB + linear warmup/decay (reference recipe,
    albert/arguments.py:104-121 via run_trainer.py:73-100)."""
    schedule = linear_warmup_linear_decay(
        args.training.learning_rate,
        warmup_steps=args.training.warmup_steps,
        total_steps=args.training.total_steps,
    )
    family = model_family(args.training.model_size)
    return lamb(
        learning_rate=schedule,
        weight_decay=args.training.weight_decay,
        clamp_value=args.training.clamp_value,
        max_grad_norm=args.training.max_grad_norm,
        weight_decay_mask=family.weight_decay_mask,
        sign_step_mask=family.sign_step_mask,
        sign_step=family.sign_step,
    )


def build_flat_opt_factory(args: CollaborationArguments):
    """(spec, params) -> optim.flat.FlatLamb for the SAME hyperparameters
    as ``build_optimizer`` — the fused flat apply's math twin of the
    per-leaf chain (equivalence locked by tests/test_optim.py). Returns a
    factory because the TreeLayout spec
    only exists once the first gradient tree does."""
    schedule = linear_warmup_linear_decay(
        args.training.learning_rate,
        warmup_steps=args.training.warmup_steps,
        total_steps=args.training.total_steps,
    )

    def factory(spec, params):
        from dedloc_tpu.optim.flat import FlatLamb, tree_flags

        family = model_family(args.training.model_size)
        names = [name for name, _shape, _dtype in spec]
        signed = family.sign_step_mask
        return FlatLamb(
            spec, tree_flags(family.weight_decay_mask(params), params, names),
            schedule,
            weight_decay=args.training.weight_decay,
            clamp_value=args.training.clamp_value,
            max_grad_norm=args.training.max_grad_norm,
            sign_flags=(
                tree_flags(signed(params), params, names)
                if signed is not None else None
            ),
            sign_step=family.sign_step,
        )

    return factory


def single_device_attention_impl(impl: str) -> str:
    """Attention impl for shape-only / single-device roles (aux template
    fallback, evaluate): 'ring' needs the trainer's sequence-parallel mesh
    to trace, but every impl is exact and shares one param tree, so it
    safely degrades to 'dense' outside the trainer."""
    return "dense" if impl == "ring" else impl


def build_authorizer(args: CollaborationArguments):
    """Gated-run handshake (contributor notebook cell 2 / huggingface_auth
    capability): when --auth.username is set, fetch a signed access token
    from the AuthService (default host: the first initial peer, where the
    coordinator attaches it) and return (authorizer, authority_public_key);
    (None, None) for open runs."""
    if not args.auth.username:
        return None, None
    spec = args.auth.endpoint or (
        args.dht.initial_peers[0] if args.dht.initial_peers else ""
    )
    if not spec:
        raise ValueError(
            "--auth.username given but no --auth.endpoint and no "
            "--dht.initial_peers to default to"
        )
    host, _, port = spec.rpartition(":")
    from dedloc_tpu.core.auth import remote_auth_handshake

    authorizer = remote_auth_handshake(
        (host, int(port)), args.auth.username, args.auth.credential
    )
    from dedloc_tpu.core.timeutils import get_dht_time

    remaining = authorizer._token.expiration_time - get_dht_time()
    logger.info(
        f"authorized as {args.auth.username!r} "
        f"(token valid for {remaining:.0f}s; auto-refreshes)"
    )
    return authorizer, authorizer.authority_public_key


def build_dht(
    args: CollaborationArguments,
    client_mode: Optional[bool] = None,
    private_key=None,
):
    """DHT with the signed-metrics validator chain. Returns (dht, subkey).

    ``private_key`` lets a gated peer sign DHT records with its TOKEN key
    (pass ``authorizer.local_private_key``): the owner-tag subkey then
    digests to the same peer id matchmaking verified from the token, so
    contribution-ledger records are identity-bound end to end
    (telemetry/ledger.subkey_owner_id). Open runs leave it None and get a
    fresh per-process key."""
    validators, public_key = make_validators(
        args.dht.experiment_prefix, private_key
    )
    dht = DHT(
        initial_peers=args.dht.initial_peers,
        start=True,
        listen_host=args.dht.listen_host,
        listen_port=args.dht.listen_port,
        client_mode=args.dht.client_mode if client_mode is None else client_mode,
        record_validators=validators,
        advertised_host=args.dht.advertised_host or None,
    )
    return dht, public_key


def checkpoint_kwargs(args, public_key: bytes) -> Dict:
    """Resolve ``--checkpoint.*`` knobs into CollaborativeOptimizer kwargs
    (docs/fleet.md restart runbook). THE one resolution point for the shard
    cache dir: empty = ``<output_dir>/shard_cache`` (restores resume across
    process restarts), "none" = no cache."""
    ck = args.checkpoint
    if ck.cache_dir == "none":
        cache_dir = None
    else:
        cache_dir = ck.cache_dir or os.path.join(
            args.training.output_dir, "shard_cache"
        )
    return dict(
        checkpoint_shard_size=ck.shard_size,
        checkpoint_fetch_parallelism=ck.fetch_parallelism,
        checkpoint_max_providers=ck.providers,
        checkpoint_dir=cache_dir,
        # catalog announcements ride the peer's SIGNED metrics subkey, so
        # the existing validator chain signature-binds them to this peer
        signed_subkey=public_key,
    )


def build_collaborative_optimizer(
    args, tx, dht, public_key: bytes, *, batch_size_per_step: int,
    flat_opt_factory: Callable, mesh=None, opt_state_sharding=None,
    param_sharding=None, post_apply=None, sign_step_mask=None,
    authorizer=None, authority_public_key=None,
) -> CollaborativeOptimizer:
    """THE wiring of ``--dht.*`` / ``--averager.*`` / ``--optimizer.*`` /
    ``--checkpoint.*`` into a training peer's ``CollaborativeOptimizer``,
    for either trainer role's argument tree. A role passes only what is its
    own: its batch, its mesh and shardings, its flat twin of ``tx``
    (``flat_opt_factory``: the fused flat apply; the optimizer itself falls
    back to the per-leaf apply on a mesh or a failed build), its
    ``post_apply`` and, where its tree has an ``auth`` group, the
    authorizer."""
    return CollaborativeOptimizer(
        tx,
        dht,
        prefix=args.dht.experiment_prefix,
        target_batch_size=args.optimizer.target_batch_size,
        batch_size_per_step=batch_size_per_step,
        batch_size_lead=args.optimizer.batch_size_lead,
        bandwidth=args.averager.bandwidth,
        compression=args.averager.compression,
        chunk_size=args.averager.chunk_size,
        # hierarchical two-level averaging (--averager.topology_plan):
        # clique-first reduction per the operator-installed plan
        topology_plan=args.averager.topology_plan or None,
        # live re-planning: follow the coordinator's plan record UNLESS
        # the operator pinned a manual plan (pin = opt-out, docs/fleet.md)
        plan_follow=(
            args.averager.plan_follow and not args.averager.topology_plan
        ),
        plan_refresh_period=args.averager.plan_refresh_period,
        error_feedback=args.optimizer.error_feedback,
        overlap_averaging=args.optimizer.overlap_averaging,
        # signed contribution ledger (--optimizer.ledger_claims /
        # --averager.ledger_receipts; docs/observability.md)
        ledger_claims=args.optimizer.ledger_claims,
        claim_period=args.optimizer.claim_period,
        ledger_receipts=args.averager.ledger_receipts,
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
        flat_opt_factory=flat_opt_factory,
        # swarm checkpointing (--checkpoint.*): sharded state serving +
        # catalog announcements + multi-peer restore, blob as fallback
        **checkpoint_kwargs(args, public_key),
        min_refresh_period=args.averager.min_refresh_period,
        max_refresh_period=args.averager.max_refresh_period,
        default_refresh_period=args.averager.default_refresh_period,
        expected_drift_peers=args.averager.expected_drift_peers,
        expected_drift_rate=args.averager.expected_drift_rate,
        performance_ema_alpha=args.averager.performance_ema_alpha,
        client_mode=args.dht.client_mode,
        relay=args.dht.relay or None,
        listen_port=args.averager.listen_port,
        advertised_host=args.dht.advertised_host or None,
        allow_state_sharing=args.optimizer.allow_state_sharing,
        mesh=mesh,
        opt_state_sharding=opt_state_sharding,
        param_sharding=param_sharding,
        post_apply=post_apply,
        sign_step_mask=sign_step_mask,
        authorizer=authorizer,
        authority_public_key=authority_public_key,
        verbose=True,
    )


def configure_role_telemetry(args, public_key: bytes):
    """Install the process-global swarm-telemetry registry for a role
    (docs/observability.md, ``--telemetry.*`` knobs). THE one place the
    peer label is derived: the sha1 fingerprint ``fetch_metrics`` computes
    from the signed metrics subkey, so per-peer event logs and the
    coordinator's swarm-health rows join on the same id. Returns
    ``(registry_or_None, close_fn)``; call ``close_fn()`` on shutdown."""
    import hashlib

    from dedloc_tpu import telemetry

    tele = telemetry.configure(
        args.telemetry, peer=hashlib.sha1(public_key).hexdigest()[:12]
    )

    def close() -> None:
        if tele is not None:
            tele.close()
            telemetry.uninstall(tele)

    return tele, close


class TrainLog:
    """``--training.train_log_path``: one JSON line per global step, the
    same in both trainer roles, read off the step record (THIS step's
    values: ``telemetry.steps.train_log_row``)."""

    def __init__(self, path: str) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._file = open(path, "a", buffering=1)
        self._start = time.perf_counter()  # the origin of ``wall_s``

    def write(self, opt, row: Dict, loss: float, sps: float) -> None:
        self._file.write(json.dumps({
            "wall_s": time.perf_counter() - self._start,
            "step": opt.local_step,
            "loss": loss,
            "samples_per_second": sps,
            **row,
            # jit↔host seam breakdown (SURVEY §7b): grads device_get /
            # apply / async backup. list() snapshots atomically under the
            # GIL — the backup thread may insert its key mid-step
            "seam_ms": {k: round(v, 2) for k, v in list(opt.seam_ms.items())},
        }) + "\n")

    def close(self) -> None:
        self._file.close()


def open_train_log(path: str) -> Optional[TrainLog]:
    return TrainLog(path) if path else None


def publish_step_metrics(
    dht, args, public_key: bytes, opt, tele, row: Dict, *, samples: int,
    loss: float, mini_steps: int, sps: float,
    hbm_bytes: Optional[int] = None,
) -> None:
    """One global step's signed ``LocalMetrics`` record onto the DHT
    metrics bus (run_first_peer.py:176-218 aggregation), for both trainer
    roles: this step's timings off its record (``row``), and with
    telemetry on the throttled counter snapshot for the coordinator's
    swarm-health fold (refreshed at most once per period;
    stale-but-present between refreshes) and the advertised RPC endpoint,
    which lets the coordinator resolve OTHER peers' link destinations to
    this peer's label in the swarm topology fold."""
    from dedloc_tpu.collaborative.metrics import LocalMetrics, publish_metrics
    from dedloc_tpu.telemetry.links import endpoint_key

    publish_metrics(
        dht,
        args.dht.experiment_prefix,
        public_key,
        LocalMetrics(
            step=opt.local_step,
            samples_per_second=sps,
            samples_accumulated=samples,
            loss=loss,
            mini_steps=mini_steps,
            step_time_ms=row["boundary_ms"],
            data_wait_ms=row["data_wait_ms"],
            allreduce_ms=row["allreduce_ms"],
            hbm_bytes=hbm_bytes,
            telemetry=(
                tele.maybe_snapshot(args.telemetry.snapshot_period)
                if tele is not None else None
            ),
            endpoint=(
                endpoint_key(opt.averager.endpoint)
                if tele is not None and opt.averager.endpoint is not None
                else None
            ),
        ),
        expiration=args.optimizer.statistics_expiration,
    )


def build_loss_fn(model) -> Callable:
    """The family's loss for ``model``: (params, batch, rng) -> (loss,
    metrics) — a ``GradSinkLoss`` where the family marks sink leaves (it
    also says the dtype the model computes in: the step hands it those
    leaves already cast), so whoever builds ``make_accumulate_step`` on it
    builds the same program."""
    family = model_family(model)
    loss_fn = family.loss(model)
    if family.grad_sink_mask is None:
        return loss_fn
    return GradSinkLoss(loss_fn, family.grad_sink_mask, model.cfg.dtype)


def synthetic_mlm_batches(
    cfg: AlbertConfig,
    batch_size: int,
    seq_length: int,
    seed: int,
) -> Iterator[Dict[str, np.ndarray]]:
    """Synthetic fixture stream (SURVEY.md §4 SyntheticImageDataset pattern):
    random token documents, real masking path. Deterministic per peer seed."""
    rng = np.random.default_rng(seed)
    tokens = SpecialTokens(vocab_size=cfg.vocab_size)
    seq_length = min(seq_length, cfg.max_position_embeddings)
    max_predictions = max_predictions_for(seq_length)
    while True:
        ids = rng.integers(
            tokens.num_reserved, cfg.vocab_size, (batch_size, seq_length)
        ).astype(np.int32)
        batch = {
            "input_ids": ids,
            "attention_mask": np.ones((batch_size, seq_length), np.int32),
            "token_type_ids": np.zeros((batch_size, seq_length), np.int32),
            "special_tokens_mask": np.zeros((batch_size, seq_length), np.int32),
            "sop_labels": rng.integers(0, 2, (batch_size,)).astype(np.int32),
        }
        yield mask_tokens(batch, rng, tokens, max_predictions=max_predictions)


def drop_collator_keys(batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
    """Keep only what the jitted loss consumes (static arg structure)."""
    if "mlm_positions" in batch:
        keep = (
            "input_ids",
            "attention_mask",
            "token_type_ids",
            "mlm_positions",
            "mlm_label_ids",
            "mlm_weights",
            "sop_labels",
        )
    elif "position_ids" in batch:  # causal LM with positions from the data
        keep = ("input_ids", "labels", "position_ids", "loss_weights")
    elif "loss_weights" in batch:  # block diffusion: x~, x and 1 / t
        keep = ("input_ids", "labels", "loss_weights")
    elif "labels" in batch:  # causal LM: inputs and next-token labels
        keep = ("input_ids", "labels")
    else:
        keep = (
            "input_ids",
            "attention_mask",
            "token_type_ids",
            "mlm_labels",
            "sop_labels",
        )
    return {k: jnp.asarray(batch[k]) for k in keep}
