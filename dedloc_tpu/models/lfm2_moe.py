"""LFM2-MoE (``model_type: lfm2_moe``; the published model this file was
written for is LiquidAI/LFM2-24B-A2B), in Flax: a pre-norm decoder whose
layers are of more than one KIND — a doubly gated short convolution in three
layers of four, grouped-query attention in the fourth — over a dense SwiGLU
FFN in the first layers and a dropless, bias-balanced routed FFN in the
others. ``benchmark/reference/lfm2_moe.py`` carries the same equations in
plain ``jax.numpy``:

    x [S, H]; eps 1e-5:  h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Mixer ``conv``:  (B | C | u) = W_in x  (H -> 3H)
              y = W_out (C ⊙ conv3(B ⊙ u))   causal, depthwise, 3 taps, no
              bias, no activation
    Mixer ``full_attention``:  q = W_q x [S, 32, 64]; k, v = W_k x, W_v x
              [S, 8, 64]; RMSNorm over each head's 64 lanes of q and of k
              (one weight for q, one for k), THEN RoPE (rotate-half);
              out = W_o softmax_causal(q kᵀ / 8) v, a kv head for 4 heads
    FFN, the first ``num_dense_layers`` layers: SwiGLU, dense width
    FFN, the others: s = sigmoid(W_r u) in float32 over ALL experts
              choice = top_k(s + b);  w = s[choice] / (Σ s[choice] + 1e-6)
              FFN(u) = Σ_{e in choice} w_e SwiGLU_e(u)   (no shared expert)
    after the stack a final RMSNorm; the head is the embedding, transposed
    loss: mean next-token cross-entropy; b stepped by the sign of its load
    excess after every GLOBAL step (``decoder.RoutedFFN``'s rule)

The program's shape. ``layer_types`` (the published pattern) and
``num_dense_layers`` say what each layer is. The leading dense layers are
unrolled; the expert layers are ``decoder.scan_periods``' stack at the
pattern's period (four layers: attention, conv, conv, conv), every layer a
remat'd block of its own kind. The blocks, the grouped-query attention (here
with a q / k norm, ``out_proj``), the routed layer (``decoder.RoutedFFN`` at
this model's sizes), the loss tail and the leaf masks are
``models/decoder.py``'s; the mixers' kernels are ``ops/short_conv.py`` and
the grouped-query mode of ``ops/flash_attention.py``.

**A chip's share**, as for the other expert decoder: ``expert_shard`` (the
experts held of every layer), ``vocab_size`` (rows held) and
``num_hidden_layers``. A depth below the published one keeps ONE leading
dense layer and then the published pattern from the first expert layer on
(``layer_plan``): 5 of LFM2-24B-A2B's 40 are its layers 0, 2, 3, 4, 5.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    BIAS,
    GroupedQueryAttention,
    RMSNorm,
    RoutedFFN,
    SwiGLU,
    Visibility,
    dense,
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
from dedloc_tpu.ops.short_conv import TAPS, short_conv

CONV, ATTENTION = "conv", "full_attention"


def _published_pattern(layers: int) -> Tuple[str, ...]:
    """LFM2-24B-A2B's ``layer_types``: attention in layers 2, 6, 10, ..."""
    return tuple(ATTENTION if i % 4 == 2 else CONV for i in range(layers))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """LFM2-24B-A2B as published (``config.json``); what it does not fix is
    in ``benchmark/configs/lfm2_24b_a2b_s4096.json`` under ``assumed``."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = _published_pattern(40)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6
    conv_L_cache: int = 3
    max_position_embeddings: int = 128000
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    bias_update_speed: float = 0.001  # as DeepseekV3Config's
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table. "whole_mixer": a conv layer keeps
    # B | C | u as ``short_conv_bwd`` reads it beside y, the attention layer
    # q / k / v beside out + lse and the q / k norm's INPUT (what its
    # backward reads), every layer the stream after its mixer, so the
    # backward's replay runs no matmul of a mixer — ``in_proj``,
    # q / k / v_proj, ``out_proj`` —, no RoPE and no relayout: 4,096 x
    # (3 + 1) x 2,048 x 2 bytes = 67 MB a conv layer a micro-batch and
    # 4,096 x ((32 + 2·8) x 64 + (32 + 8) x 64 + 2,048) x 2 = 63 MB the
    # attention layer, 0.33 GB in the benchmark's cell (0.23 of them
    # "kernel_operands"'), where accumulate_step's scratch reads 0.81 GB
    # (0.72 under "kernel_operands") beside 8.1 GB of state, accumulator and
    # bf16 copies. A smaller chip or a larger share: --training.remat_policy
    # kernel_operands, then kernel_outputs
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises
        if self.conv_L_cache != TAPS:
            raise ValueError(f"the conv kernels take {TAPS} taps")
        if not 1 <= self.num_hidden_layers <= len(self.layer_types):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the pattern "
                f"has {len(self.layer_types)} layers"
            )

    # the routed layer's field under ``RoutedFFN``'s name
    n_routed_experts = property(lambda self: self.num_experts)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @property
    def layer_plan(self) -> List[Tuple[int, str, bool]]:
        """(published layer, mixer kind, routed FFN?) of every layer run:
        the published stack, or — cut in depth — ONE leading dense layer
        and the published pattern from the first expert layer on."""
        if self.num_hidden_layers == len(self.layer_types):
            kept = list(range(self.num_hidden_layers))
            dense = self.num_dense_layers
        else:
            kept = [0] + list(range(
                self.num_dense_layers,
                self.num_dense_layers + self.num_hidden_layers - 1,
            ))
            dense = 1
        return [
            (i, self.layer_types[i], n >= dense) for n, i in enumerate(kept)
        ]

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "lfm2_24b_a2b": Lfm2MoeConfig.lfm2_24b_a2b,
            "lfm2_tiny": Lfm2MoeConfig.tiny,
        })

    @staticmethod
    def lfm2_24b_a2b(**overrides) -> "Lfm2MoeConfig":
        return Lfm2MoeConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "Lfm2MoeConfig":
        """Test-sized: every mechanism (two dense layers, a period of
        attention + three convolutions and a layer over, grouped heads
        with their q / k norm, 16 experts top-3, a chunked tied head), no
        published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=7,
            num_dense_layers=2, layer_types=_published_pattern(7),
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            intermediate_size=48, moe_intermediate_size=16, num_experts=16,
            num_experts_per_tok=3, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return Lfm2MoeConfig(**base)


class ShortConvMixer(nn.Module):
    """W_out (C ⊙ conv3(B ⊙ u)) with (B | C | u) = W_in x: the kernel reads
    ``in_proj``'s output as it is and writes what ``out_proj`` reads."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        bcu = dense(TAPS * cfg.hidden_size, cfg, "in_proj")(hidden)
        taps = self.param(
            "conv", nn.initializers.normal(cfg.initializer_range),
            (cfg.hidden_size, cfg.conv_L_cache), jnp.float32,
        )
        y = short_conv(bcu, taps, mesh=cfg.mesh)
        return dense(cfg.hidden_size, cfg, "out_proj")(y)


class DecoderLayer(nn.Module):
    """h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h)): ``mixer`` is
    ``conv`` or ``full_attention``, the FFN a dense SwiGLU (``sparse``
    False; returns y) or the routed layer (returns (y, routing))."""

    cfg: Lfm2MoeConfig
    mixer: str
    sparse: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        x = RMSNorm(cfg, name="operator_norm")(hidden)
        if self.mixer == CONV:
            mixed = ShortConvMixer(cfg, name="conv")(x)
        else:
            mixed = GroupedQueryAttention(
                cfg, Visibility(causal=True),
                qk_norms=("q_layernorm", "k_layernorm"), out_name="out_proj",
                name="self_attn",
            )(x, rope)
        hidden = mixer_residual(hidden, mixed)
        x = RMSNorm(cfg, name="ffn_norm")(hidden)
        if not self.sparse:
            return hidden + SwiGLU(
                cfg, cfg.intermediate_size, name="feed_forward"
            )(x)
        y, routing = RoutedFFN(cfg, name="feed_forward")(x)
        return hidden + y, routing


class Lfm2MoeForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the expert layers
    in order). The head is ``embed_tokens`` transposed, applied by
    ``lfm2_moe_loss`` a chunk of tokens at a time."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids, tied_head=True)
        rope = rope_tables(input_ids.shape[1], cfg.head_dim, cfg.rope_theta)
        layer = functools.partial(remat_layer, DecoderLayer, cfg)
        kinds = [(mixer, sparse) for _i, mixer, sparse in cfg.layer_plan]
        for i, kind in enumerate(kind for kind in kinds if not kind[1]):
            hidden = layer(*kind, name=f"dense_layer_{i}")(hidden, rope)
        routed = [kind for kind in kinds if kind[1]]
        hidden, routing = scan_periods(
            layer, routed, period_of(routed), hidden, rope
        )
        return RMSNorm(cfg, name="norm")(hidden), routing


def lfm2_moe_loss(model: Lfm2MoeForCausalLM, params,
                  batch: Dict[str, jnp.ndarray], grad_sinks=None,
                  compute_copies=None):
    """``decoder.expert_lm_loss`` under the TIED head, with the largest bias
    magnitude of any layer as a gauge."""
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["embed_tokens"].astype(model.cfg.dtype).T,
        gauges={"moe.bias_abs_max": lambda p, _r: jnp.max(jnp.stack([
            jnp.max(jnp.abs(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(p)
            if path[-1].key == BIAS
        ]))},
    )


# decayed: every matrix and the conv taps; not the RMSNorm ``weight``s nor
# the correction bias
lfm2_moe_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", BIAS)
)


def lfm2_moe_layer_flops_per_token(cfg: Lfm2MoeConfig,
                                   seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of one layer part, by kind: the two
    mixers, the two FFNs (routed work for the HELD experts at the expected
    share of slots) and the tied head over the held rows."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    held_share = cfg.held_experts[1] / cfg.num_experts
    return {
        CONV: 2 * h * TAPS * h + 2 * h * h,  # in_proj, out_proj
        ATTENTION: (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h  # q k v, out
            + 2 * 2 * heads * d * (seq + 1) / 2  # QKᵀ, PV over the triangle
        ),
        "dense_ffn": 2 * 3 * h * cfg.intermediate_size,
        "routed_ffn": (
            2 * h * cfg.num_experts
            + 2 * 3 * h * cfg.moe_intermediate_size
            * cfg.num_experts_per_tok * held_share
        ),
        "head": 2 * h * cfg.vocab_size,
    }


def lfm2_moe_train_tflops_per_sample(cfg: Lfm2MoeConfig, seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays and the
    element-wise convolution not counted)."""
    part = lfm2_moe_layer_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(
        part[mixer] + part["routed_ffn" if sparse else "dense_ffn"]
        for _i, mixer, sparse in cfg.layer_plan
    )
    return 3.0 * per_token * seq / 1e12
