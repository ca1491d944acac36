"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``), in Flax: latent
attention and a dropless, bias-balanced expert layer. The published model
this file was written for is kanana-2-30b-a3b (kakaocorp; ``q_lora_rank:
null``), whose equations — ``benchmark/reference/deepseek_v3.py`` carries the
same in plain ``jax.numpy`` — are:

    x [S, H]; pre-norm:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn(u):  q = W_q u -> [S, heads, nope + rope]
              c = W_kva u -> (c_kv [S, rank] | k_rope [S, rope], ONE head)
              (k_nope | v) = W_kvb RMSNorm(c_kv) -> [S, heads, nope + v]
              RoPE on q_rope and k_rope, pairs (2i, 2i+1) (rope_interleave)
              q = (q_nope | q_rope), k = (k_nope | k_rope for every head)
              out = W_o softmax_causal(q kᵀ / sqrt(nope + rope)) v
    FFN, the first ``first_k_dense_replace`` layers: SwiGLU, dense width
    FFN, the others: s = sigmoid(W_r u) in float32 over ALL experts
              choice = top_k(s + b);  w = s[choice] / Σ s[choice] · scale
              FFN(u) = Σ_{e in choice} w_e SwiGLU_e(u) + SwiGLU_shared(u)
    loss: mean next-token cross-entropy (no auxiliary loss: noaux_tc)
    b: not trained by a gradient — after every GLOBAL step
              b_e <- b_e − γ · sign(load_e − mean load)

The program's shape: the leading dense layer(s) outside the scan, the expert
layers stacked under ``nn.scan`` + remat as Ouro's are; the blocks, the
latent attention itself (``LatentAttention``: Kimi Linear runs it too, at a
share of heads and without RoPE), the routed layer (``RoutedFFN``), the loss
tail and the leaf masks are ``models/decoder.py``'s. The kernels take q and k 192 wide beside v 128 wide
as they are (``ops/flash_attention.py``: two column-block widths, nothing
padded); the one rotary key head is broadcast into k's layout first.

**A chip's share.** ``expert_shard = (index, count)`` tells every expert
layer which ``n_routed_experts / count`` experts it holds: the parameters
exist for those alone, the router scores all ``n_routed_experts``, and slots
that chose an absent expert add nothing (``parallel/moe.routed_experts``).
``vocab_size`` is the rows of the vocabulary held: a sliced vocabulary is a
smaller vocabulary.

The load statistic of the bias rule leaves the backward as the bias leaf's
cotangent (``parallel/moe.with_load_cotangent``), so it is accumulated over
micro-batches and averaged over peers as a gradient is;
``decoder.sign_step_mask`` marks those leaves for ``optim``'s sign rule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    BIAS,
    LatentAttention,
    RMSNorm,
    RoutedFFN,
    ScannedBlock,
    SwiGLU,
    dense,
    embed_tokens,
    expert_lm_loss,
    held_range,
    named_config,
    rope_tables,
    scan_layers,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """kanana-2-30b-a3b as published (``config.json``); what it does not
    fix is in ``benchmark/configs/kanana2_30b_a3b_s4096.json`` under
    ``assumed``."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    route_eps: float = 1e-20  # added to the chosen scores' sum (HF's own)
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # γ of the bias rule (DeepSeek-V3, arXiv 2412.19437, section 2.1.2)
    bias_update_speed: float = 0.001
    # (index, count): this chip holds experts [index·E/count, (index+1)·E/count)
    expert_shard: Tuple[int, int] = (0, 1)
    # rows of one expert's tile in the routed loop (parallel/moe.py)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    remat_policy: str = "kernel_outputs"  # as Ouro: layer input + out + lse
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.n_routed_experts)  # raises

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.n_routed_experts)

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "kanana2_30b_a3b": DeepseekV3Config.kanana2_30b_a3b,
            "kanana2_tiny": DeepseekV3Config.tiny,
        })

    @staticmethod
    def kanana2_30b_a3b(**overrides) -> "DeepseekV3Config":
        return DeepseekV3Config(**overrides)

    @staticmethod
    def tiny(**overrides) -> "DeepseekV3Config":
        """Test-sized: every mechanism (a dense layer and two expert
        layers, two q/k widths, a latent, 16 experts top-3, two shared
        experts, a chunked head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=16, intermediate_size=48,
            moe_intermediate_size=16, n_routed_experts=16,
            num_experts_per_tok=3, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return DeepseekV3Config(**base)


class DecoderLayer(nn.Module):
    """Pre-norm: h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h)); the FFN
    is a dense SwiGLU (``sparse=False``) or the routed layer."""

    cfg: DeepseekV3Config
    sparse: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        hidden = hidden + LatentAttention(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(hidden), rope
        )
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        if not self.sparse:
            return hidden + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(x)
        y, routing = RoutedFFN(
            cfg, shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size,
            name="mlp",
        )(x)
        return hidden + y, routing


class DeepseekV3ForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the expert
    layers). The head's weight is the parameter ``lm_head`` [H, V], applied
    by ``deepseek_v3_loss`` a chunk of tokens at a time."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids)
        cos, sin = rope_tables(
            input_ids.shape[1], cfg.qk_rope_head_dim, cfg.rope_theta
        )
        half = cfg.qk_rope_head_dim // 2
        rope = (cos[:, :half], sin[:, :half])  # one column per pair
        for i in range(cfg.first_k_dense_replace):
            hidden = remat_layer(
                DecoderLayer, cfg, False, name=f"dense_layer_{i}"
            )(hidden, rope)
        # a layer a step, not ``scan_periods``' unrolled period (ROADMAP A3.1)
        hidden, routing = scan_layers(ScannedBlock, cfg.num_expert_layers)(
            functools.partial(remat_layer, DecoderLayer, cfg, True),
            name="layers",
        )(hidden, rope)
        return RMSNorm(cfg, name="norm")(hidden), routing


def deepseek_v3_loss(model: DeepseekV3ForCausalLM, params,
                     batch: Dict[str, jnp.ndarray], grad_sinks=None,
                     compute_copies=None):
    """``decoder.expert_lm_loss`` under the untied head, with the largest
    bias magnitude as a gauge."""
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["lm_head"].astype(model.cfg.dtype),
        gauges={"moe.bias_abs_max": lambda p, _r: jnp.max(jnp.abs(
            p["layers"]["block"]["mlp"][BIAS]
        ))},
    )


# decayed: every matrix; not the RMSNorm ``weight``s nor the correction bias
deepseek_v3_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", BIAS)
)


def deepseek_v3_train_tflops_per_sample(cfg: DeepseekV3Config,
                                        seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted, causal attention at its triangle with its two widths, routed
    work for the HELD experts only, at the expected share of slots)."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    attention = (
        2 * h * heads * qk  # W_q
        + 2 * h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)  # W_kva
        + 2 * cfg.kv_lora_rank * heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim
        )  # W_kvb
        + 2 * heads * cfg.v_head_dim * h  # W_o
        + 2 * heads * (qk + cfg.v_head_dim) * (seq + 1) / 2  # QKᵀ, PV
    )
    f = cfg.moe_intermediate_size
    held_share = cfg.held_experts[1] / cfg.n_routed_experts
    sparse = (
        2 * h * cfg.n_routed_experts  # router
        + 2 * 3 * h * f * cfg.n_shared_experts
        + 2 * 3 * h * f * cfg.num_experts_per_tok * held_share
    )
    per_token = (
        cfg.num_hidden_layers * attention
        + cfg.first_k_dense_replace * 2 * 3 * h * cfg.intermediate_size
        + cfg.num_expert_layers * sparse
        + 2 * h * cfg.vocab_size
    )
    return 3.0 * per_token * seq / 1e12
