"""SmallThinker (``model_name: smallthinker_21b_instruct``; the published
model this file was written for is PowerInfer/SmallThinker-21BA3B-Instruct),
in Flax: a pre-norm decoder whose attention layers are of two KINDS — a
global layer without positions in one layer of four, a sliding-window layer
with RoPE in the other three — and whose every layer ends in a dropless
routed FFN of ReLU-gated experts with the router fed from BEFORE attention.
``benchmark/reference/smallthinker.py`` carries the same equations in plain
``jax.numpy``:

    x [S, H]; eps 1e-6:
    n  = RMSNorm_in(x)
    r  = W_r n                              [E] logits, float32: the router
                                            reads n, BEFORE attention
    q, k, v = W_q n, W_k n, W_v n           28 / 4 / 4 heads of 128
    layer kind ``band_rope``:   q, k = RoPE(q, k)  (rotate-half, whole head)
               ``global_nope``: no positions at all
    a  = softmax(q kᵀ / sqrt(128) + mask) v   kv head j serves query heads
         mask: key <= query; ``band_rope`` also query - key < window
    h  = x + W_o a
    m  = RMSNorm_post(h)
    C  = top_k(r);  w = softmax(r[C])       no bias, no scale, sums to 1
    y  = h + Σ_{e in C} w_e W_down,e(relu(W_gate,e m) ⊙ W_up,e m)
    after the stack a final RMSNorm; an untied head; loss: mean next-token
    cross-entropy; NO auxiliary or balancing term (``config.json`` names
    none), no leaf stepped by a sign

The program's shape: ``rope_layout`` and ``sliding_window_layout`` (the
published patterns) say what each layer is; the stack is
``decoder.scan_periods``' at the pattern's period (four: global, band, band,
band), every layer a remat'd block of its own kind. The blocks, the
grouped-query attention, the routed layer (``decoder.RoutedGLU``:
``parallel/moe.routed_experts`` with ``activation="relu"`` and its gradient
sinks), the loss tail and the leaf mask are ``models/decoder.py``'s; the
kernels are the grouped-query mode of ``ops/flash_attention.py`` — a group of
SEVEN, a whole group a program — causal for the global layers and with
``band=`` for the others.

**A chip's share**, as for the other expert decoders: ``expert_shard`` (the
experts held of every layer), ``vocab_size`` (rows held of the embedding AND
of the head) and ``num_hidden_layers`` (the first layers of the pattern:
there is no leading dense layer).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    RMSNorm,
    RoutedGLU,
    Visibility,
    embed_tokens,
    expert_lm_loss,
    held_range,
    mixer_residual,
    named_config,
    period_of,
    rope_tables,
    scan_periods,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.flash_attention import visited_tiles

GLOBAL_NOPE, BAND_ROPE = "global_nope", "band_rope"


def _published_layout(layers: int) -> Tuple[int, ...]:
    """SmallThinker-21BA3B's ``rope_layout`` = ``sliding_window_layout``:
    0 (global, no positions) in layers 0, 4, 8, ..., 1 in the others."""
    return tuple(int(i % 4 != 0) for i in range(layers))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published (``config.json``); what it
    does not fix is in ``benchmark/configs/smallthinker_21b_a3b_s16384.json``
    under ``assumed``."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    rope_layout: Tuple[int, ...] = _published_layout(52)
    sliding_window_layout: Tuple[int, ...] = _published_layout(52)
    sliding_window_size: int = 4096
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768  # moe_ffn_hidden_size
    num_experts: int = 64  # moe_num_primary_experts
    num_experts_per_tok: int = 6  # moe_num_active_primary_experts
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table. "whole_mixer": the layer keeps
    # q / k / v as the flash kernels read them beside out + lse, and the
    # stream after attention, so the backward's replay runs no q / k / v / o
    # projection, RoPE or relayout (RoPE's own backward is linear: it needs
    # no stash): 16,384 x ((28 + 2·4) x 128 + 2,560) x 2 bytes = 235 MB a
    # layer a micro-batch (151 of them "kernel_operands"'), 0.94 GB in the
    # benchmark's cell of four layers, where accumulate_step's scratch reads
    # 2.91 GB (2.53 under "kernel_operands") beside 5.93 GB of state and
    # accumulator. A smaller chip or a larger share:
    # --training.remat_policy kernel_operands, then kernel_outputs
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises
        if len(self.rope_layout) != len(self.sliding_window_layout):
            raise ValueError("the two layouts name the same layers")
        if not 1 <= self.num_hidden_layers <= len(self.rope_layout):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the layouts "
                f"have {len(self.rope_layout)} layers"
            )

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @property
    def layer_plan(self) -> List[Tuple[bool, bool]]:
        """(rotated?, banded?) of every layer run: the first
        ``num_hidden_layers`` of the published layouts."""
        return [
            (bool(rope), bool(band)) for rope, band in zip(
                self.rope_layout[:self.num_hidden_layers],
                self.sliding_window_layout,
            )
        ]

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "smallthinker_21b_a3b": SmallThinkerConfig.smallthinker_21b_a3b,
            "smallthinker_tiny": SmallThinkerConfig.tiny,
        })

    @staticmethod
    def smallthinker_21b_a3b(**overrides) -> "SmallThinkerConfig":
        return SmallThinkerConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "SmallThinkerConfig":
        """Test-sized: every mechanism (two periods of a global layer
        without positions and three banded ones with RoPE, a group of SEVEN
        query heads on one kv head, a band of 8 at S=32, 8 ReLU-gated experts
        top-2 routed from before attention, a chunked untied head), no
        published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=8,
            rope_layout=_published_layout(8),
            sliding_window_layout=_published_layout(8),
            sliding_window_size=8, num_attention_heads=7,
            num_key_value_heads=1, head_dim=8, moe_intermediate_size=16,
            num_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128, moe_row_tile=8,
            attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return SmallThinkerConfig(**base)


class DecoderLayer(nn.Module):
    """n = RMSNorm(x); h = x + Attn(n); y = h + Experts(RMSNorm(h)) routed
    by W_r n. Returns (y, routing)."""

    cfg: SmallThinkerConfig
    rotated: bool
    banded: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        x = RMSNorm(cfg, name="input_layernorm")(hidden)
        band = cfg.sliding_window_size if self.banded else None
        hidden = mixer_residual(hidden, GroupedQueryAttention(
            cfg, Visibility(causal=True, band=band), rotated=self.rotated,
            name="self_attn",
        )(x, rope))
        y, routing = RoutedGLU(cfg, name="block_sparse_moe")(
            RMSNorm(cfg, name="post_attention_layernorm")(hidden), x
        )
        return hidden + y, routing


class SmallThinkerForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the layers in
    order). The head's weight is the parameter ``lm_head`` [H, V], applied
    by ``smallthinker_loss`` a chunk of tokens at a time."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids)
        rope = rope_tables(input_ids.shape[1], cfg.head_dim, cfg.rope_theta)
        kinds = cfg.layer_plan
        hidden, routing = scan_periods(
            functools.partial(remat_layer, DecoderLayer, cfg), kinds,
            period_of(kinds), hidden, rope,
        )
        return RMSNorm(cfg, name="norm")(hidden), routing


def band_tile_share(cfg: SmallThinkerConfig, seq: int) -> float:
    """(query tile, key tile) pairs a band layer's kernels visit over the
    causal triangle's, from the shapes: 252 / 528 at S=16,384, a band of
    4,096 and 512 x 512 tiles; 1 where the band is the sequence."""
    block = cfg.attention_block_size
    return visited_tiles(
        seq, block, block, True, cfg.sliding_window_size
    ) / visited_tiles(seq, block, block, True)


def smallthinker_loss(model: SmallThinkerForCausalLM, params,
                      batch: Dict[str, jnp.ndarray], grad_sinks=None,
                      compute_copies=None):
    """``decoder.expert_lm_loss`` under the untied head (``moe.scores`` holds
    the router's LOGITS; no bias to report), with ``attn.band_tile_share``
    as a gauge."""
    share = band_tile_share(model.cfg, batch["input_ids"].shape[1])
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["lm_head"].astype(model.cfg.dtype),
        gauges={"attn.band_tile_share": lambda _p, _r: jnp.float32(share)},
    )


# decayed: every matrix; not the RMSNorm ``weight``s
smallthinker_weight_decay_mask = weight_decay_mask


def smallthinker_layer_flops_per_token(cfg: SmallThinkerConfig,
                                       seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of one layer, by kind — a band layer
    counted at its band: min(i + 1, window) keys for query i — and of the
    untied head over the held rows; routed work for the HELD experts at the
    expected share of slots."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    band = min(cfg.sliding_window_size, seq)
    pairs = {
        GLOBAL_NOPE: seq * (seq + 1) / 2,
        BAND_ROPE: band * (band + 1) / 2 + (seq - band) * band,
    }
    shared = (
        2 * h * (heads + 2 * kv) * d + 2 * heads * d * h  # q k v, out
        + 2 * h * cfg.num_experts  # the router
        + 2 * 3 * h * cfg.moe_intermediate_size * cfg.num_experts_per_tok
        * cfg.held_experts[1] / cfg.num_experts
    )
    return {
        **{kind: shared + 2 * 2 * heads * d * n / seq  # QKᵀ, PV
           for kind, n in pairs.items()},
        "head": 2 * h * cfg.vocab_size,
    }


def smallthinker_train_tflops_per_sample(cfg: SmallThinkerConfig,
                                         seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted)."""
    part = smallthinker_layer_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(
        part[BAND_ROPE if banded else GLOBAL_NOPE]
        for _rotated, banded in cfg.layer_plan
    )
    return 3.0 * per_token * seq / 1e12
