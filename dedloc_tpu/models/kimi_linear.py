"""Kimi Linear (``model_type: kimi_linear``; the published model this file
was written for is moonshotai/Kimi-Linear-48B-A3B-Instruct, arXiv
2510.26692), in Flax: a pre-norm decoder whose mixers are of two KINDS —
Kimi Delta Attention, a gated delta rule with a decay per key channel and a
[128, 128] state a head, in three layers of four; latent attention WITHOUT
rotary embedding in the fourth (the KDA layers carry position in their
decay) — over one leading dense SwiGLU layer and dropless, bias-balanced
routed layers beside a shared expert after it.
``benchmark/reference/kimi_linear.py`` carries the same equations in plain
``jax.numpy``:

    x [S, H]; eps 1e-5:  h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Mixer ``kda`` (heads of d = 128), u the normed input:
        q~, k~, v~ = W_q u, W_k u, W_v u
        q', k', v = SiLU(conv4(.))    causal, depthwise, 4 taps, zeros before
        q = q' / |q'|_2 · d^-1/2,  k = k' / |k'|_2        per head
        g = -exp(A_log) · softplus(W_fb (W_fa u) + dt_bias)   [heads, d] <= 0
        beta = sigmoid(W_b u)                                 [heads]
        S_t = (I - beta_t k_t k_tᵀ) Diag(e^{g_t}) S_{t-1} + beta_t k_t v_tᵀ
        o_t = S_tᵀ q_t                         (``ops/kda.py``)
        out = W_o [RMSNorm_d(o) · w_o ⊙ sigmoid(W_gb (W_ga u) + b_g)]
    Mixer ``mla``: ``decoder.LatentAttention`` (kanana-2's) with its two RoPE
        lines struck: q = W_q u [S, heads, 128 + 64]; (c_kv | k_pe, ONE head)
        = W_kva u; (k_nope | v) = W_kvb RMSNorm(c_kv); k = (k_nope | k_pe for
        every head); out = W_o softmax_causal(q kᵀ / sqrt(192)) v
    FFN, layer 1: SwiGLU 9,216; the others ``decoder.RoutedFFN``: sigmoid
        scores over all 256, top-8 of s + b, renormalised x 2.446, a shared
        SwiGLU of 1,024; b stepped by the sign of the load a GLOBAL step
    loss: mean next-token cross-entropy under the untied head

The program's shape: layers are numbered from 1 as ``linear_attn_config``
numbers them; a depth below the published one keeps the FIRST layers (5 of
27: KDA + dense | KDA, KDA, MLA, KDA). The dense layer is unrolled, the
routed ones are ``decoder.scan_periods``' stack at a period of four. The
kernel runs behind ``attention_impl`` "flash"; "dense" is the token-by-token
recurrence in float32 (``ops/kda.kda_recurrence``), the CPU tests' oracle.
The prelude — convolution + SiLU, the L2 norm, both gates, the gated norm —
is XLA's.

**A chip's share**: ``expert_shard``, ``vocab_size`` and
``num_hidden_layers`` as for the other expert decoders, and ``head_shard =
(index, count)``: every mixer holds ``heads / count`` of its heads — q / k /
v, the taps, ``A_log``, ``W_fb``, ``dt_bias``, ``W_b``, ``W_gb`` and
``W_kvb`` by columns, ``W_o`` by rows; ``W_fa``, ``W_ga``, ``W_kva`` and its
norm whole. ``W_o`` of the held heads gives the mixer's PARTIAL sum, and
that is what joins the residual stream: the layer runs without the exchange
that would add the other chips' parts, as a slot that chose an absent expert
adds nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    BIAS,
    LatentAttention,
    RMSNorm,
    RoutedFFN,
    SwiGLU,
    causal_conv_silu,
    dense,
    embed_tokens,
    expert_lm_loss,
    held_heads,
    held_range,
    mixer_residual,
    named_config,
    scan_periods,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.kda import CHUNK, kda, kda_recurrence

KDA, MLA = "kda", "mla"
PERIOD = 4  # three KDA layers and a latent-attention one
# a KDA mixer's leaves that are no matrix: exempt from weight decay
KDA_VECTORS = ("A_log", "dt_bias", "q_conv", "k_conv", "v_conv", "g_b_bias")
KDA_GAUGES = ("kda.chunk_log_decay_min", "kda.beta_mean", "kda.state_abs_max")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Kimi-Linear-48B-A3B-Instruct as published (``config.json``); what it
    does not fix is in ``benchmark/configs/kimi_linear_48b_a3b_s8192.json``
    under ``assumed``."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    # ``linear_attn_config``: layers numbered from 1
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128  # assumed: the head dim
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # the one shared key head: no rotation here
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    route_eps: float = 1e-20
    max_position_embeddings: int = 1048576  # ``model_max_length``
    rms_norm_eps: float = 1e-5
    l2_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    bias_update_speed: float = 0.001  # as DeepseekV3Config's
    expert_shard: Tuple[int, int] = (0, 1)
    # (index, count): this chip holds heads / count of every mixer's heads
    head_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises
        for heads in (self.kda_num_heads, self.num_attention_heads):
            held_heads(self.head_shard, heads)  # raises
        if not 1 <= self.num_hidden_layers <= max(self.full_attn_layers):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the published "
                f"lists name {max(self.full_attn_layers)} layers"
            )

    # the routed layer's fields under ``RoutedFFN``'s names
    n_routed_experts = property(lambda self: self.num_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @property
    def held_kda_heads(self) -> int:
        return held_heads(self.head_shard, self.kda_num_heads)

    @property
    def held_attention_heads(self) -> int:
        return held_heads(self.head_shard, self.num_attention_heads)

    @property
    def layer_plan(self) -> List[Tuple[int, str, bool]]:
        """(published layer, mixer kind, routed FFN?) of every layer run:
        the first ``num_hidden_layers`` of the published lists."""
        return [
            (n, MLA if n in self.full_attn_layers else KDA,
             n > self.first_k_dense_replace)
            for n in range(1, self.num_hidden_layers + 1)
        ]

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "kimi_linear_48b_a3b": KimiLinearConfig.kimi_linear_48b_a3b,
            "kimi_linear_tiny": KimiLinearConfig.tiny,
        })

    @staticmethod
    def kimi_linear_48b_a3b(**overrides) -> "KimiLinearConfig":
        return KimiLinearConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "KimiLinearConfig":
        """Test-sized: every mechanism (a dense layer, a period of KDA, KDA,
        latent attention, KDA and a layer over, four heads a mixer so a head
        share exists, two chunks a row, 16 experts top-3 beside a shared
        one, a chunked untied head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=6,
            full_attn_layers=(4, 8), kda_num_heads=4, kda_head_dim=8,
            kda_gate_rank=8, num_attention_heads=4, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
            intermediate_size=48, moe_intermediate_size=16, num_experts=16,
            num_experts_per_token=3, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense",
            loss_chunk_tokens=32,
        )
        base.update(overrides)
        return KimiLinearConfig(**base)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step log-uniform in [1e-3, 1e-1] (Mamba's
    initialiser)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _taps_init(key, shape, dtype=jnp.float32):
    """A depthwise Conv1d's default: uniform in ±1 / sqrt(taps)."""
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class KimiDeltaAttention(nn.Module):
    """The KDA mixer over the heads this chip holds. Returns (the mixer's
    output — a PARTIAL sum under a head share —, its three gauges)."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        B, S, _ = hidden.shape
        heads, d = cfg.held_kda_heads, cfg.kda_head_dim
        wide = heads * d

        def branch(name):  # projection, convolution + SiLU, per head
            taps = self.param(
                f"{name}_conv", _taps_init,
                (wide, cfg.short_conv_kernel_size), jnp.float32,
            )
            return causal_conv_silu(
                dense(wide, cfg, f"{name}_proj")(hidden), taps
            ).reshape(B, S, heads, d)

        def unit(x, scale=1.0):  # L2 norm over a head's lanes, in float32
            x32 = x.astype(jnp.float32)
            norm = jax.lax.rsqrt(
                jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
                + cfg.l2_norm_eps
            )
            return (x32 * (norm * scale)).astype(cfg.dtype)

        q, k, v = unit(branch("q"), d ** -0.5), unit(branch("k")), branch("v")
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (wide,), jnp.float32)
        decay = dense(wide, cfg, "f_b_proj")(
            dense(cfg.kda_gate_rank, cfg, "f_a_proj")(hidden)
        )
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (decay.astype(jnp.float32) + dt_bias).reshape(B, S, heads, d)
        )
        beta = jax.nn.sigmoid(
            dense(heads, cfg, "b_proj")(hidden).astype(jnp.float32)
        )
        if cfg.attention_impl == "flash":
            out, state = kda(q, k, v, g, beta, return_state=True)
        elif cfg.attention_impl == "dense":
            out, state = kda_recurrence(q, k, v, g, beta, return_state=True)
            out = out.astype(cfg.dtype)
        else:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r}: a decoder takes "
                "'flash' or 'dense'"
            )
        gate_bias = self.param(
            "g_b_bias", nn.initializers.zeros, (wide,), jnp.float32
        )
        gate = jax.nn.sigmoid(dense(wide, cfg, "g_b_proj")(
            dense(cfg.kda_gate_rank, cfg, "g_a_proj")(hidden)
        ).astype(jnp.float32) + gate_bias).reshape(B, S, heads, d)
        normed = RMSNorm(cfg, name="o_norm")(out).astype(jnp.float32)
        y = dense(cfg.hidden_size, cfg, "o_proj")(
            (normed * gate).astype(cfg.dtype).reshape(B, S, wide)
        )
        ragged = -S % CHUNK  # g = 0 behind the row moves no chunk's sum
        chunked = jnp.pad(
            jax.lax.stop_gradient(g), ((0, 0), (0, ragged), (0, 0), (0, 0))
        ).reshape(B, (S + ragged) // CHUNK, CHUNK, heads, d)
        report = jnp.stack([
            # the most negative cumulative log-decay a chunk reaches
            jnp.min(jnp.sum(chunked, axis=2)),
            jnp.mean(jax.lax.stop_gradient(beta)),
            jnp.max(jnp.abs(state)),
        ])
        return y, report


class DecoderLayer(nn.Module):
    """h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h)): ``mixer`` is
    ``kda`` or ``mla``, the FFN a dense SwiGLU or (``sparse``) the routed
    layer. Returns (y, what the layer reports: ``kda`` — its mixer's three
    gauges, zeros from a latent-attention layer — and, routed, its
    routing)."""

    cfg: KimiLinearConfig
    mixer: str
    sparse: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        x = RMSNorm(cfg, name="input_layernorm")(hidden)
        if self.mixer == KDA:
            mixed, gauges = KimiDeltaAttention(cfg, name="self_attn")(x)
        else:
            mixed = LatentAttention(
                cfg, heads=cfg.held_attention_heads, rotated=False,
                name="self_attn",
            )(x, rope)
            gauges = jnp.zeros((len(KDA_GAUGES),), jnp.float32)
        hidden = mixer_residual(hidden, mixed)
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        if not self.sparse:
            return hidden + SwiGLU(
                cfg, cfg.intermediate_size, name="mlp"
            )(x), {"kda": gauges}
        y, routing = RoutedFFN(
            cfg,
            shared_width=cfg.num_shared_experts * cfg.moe_intermediate_size,
            name="mlp",
        )(x)
        return hidden + y, dict(routing, kda=gauges)


class KimiLinearForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the SPARSE layers
    in order, but ``kda`` [layers, 3]: over every layer). The head's weight
    is the parameter ``lm_head`` [H, V], applied by ``kimi_linear_loss`` a
    chunk of tokens at a time."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids)
        layer = functools.partial(remat_layer, DecoderLayer, cfg)
        kinds = [(mixer, sparse) for _n, mixer, sparse in cfg.layer_plan]
        reports = []
        for i, kind in enumerate(kind for kind in kinds if not kind[1]):
            hidden, report = layer(*kind, name=f"dense_layer_{i}")(
                hidden, None
            )
            reports.append(report["kda"][None])
        routed = [kind for kind in kinds if kind[1]]
        hidden, routing = scan_periods(
            layer, routed, min(PERIOD, max(len(routed), 1)), hidden, None
        )
        routing["kda"] = jnp.concatenate(reports + [routing["kda"]])
        return RMSNorm(cfg, name="norm")(hidden), routing


def kimi_linear_loss(model: KimiLinearForCausalLM, params,
                     batch: Dict[str, jnp.ndarray], grad_sinks=None,
                     compute_copies=None):
    """``decoder.expert_lm_loss`` under the untied head, with the largest
    bias magnitude of any layer and the KDA layers' three gauges (a vector
    each, one entry a KDA layer in order)."""
    cfg = model.cfg
    at = jnp.asarray(
        [i for i, (_n, mixer, _s) in enumerate(cfg.layer_plan) if mixer == KDA]
    )
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["lm_head"].astype(cfg.dtype),
        gauges={
            "moe.bias_abs_max": lambda p, _r: jnp.max(jnp.stack([
                jnp.max(jnp.abs(leaf))
                for path, leaf in jax.tree_util.tree_leaves_with_path(p)
                if path[-1].key == BIAS
            ])),
            **{name: lambda _p, r, column=column: r["kda"][at, column]
               for column, name in enumerate(KDA_GAUGES)},
        },
    )


# decayed: every matrix; not the RMSNorm ``weight``s, the correction bias,
# nor a KDA mixer's vectors (A_log, dt_bias, the taps, the gate's bias)
kimi_linear_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", BIAS) + KDA_VECTORS
)


def kimi_linear_parts_flops_per_token(cfg: KimiLinearConfig,
                                      seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of one layer part, by kind, at the heads and
    experts HELD: the KDA mixer (its projections and the chunked rule's own
    products: Akk, Aqk, the triangular solve, U, W, the three state
    products and Aqk V' a chunk), latent attention at its triangle, the two
    FFNs and the untied head over the held rows."""
    h, d, rank = cfg.hidden_size, cfg.kda_head_dim, cfg.kda_gate_rank
    kda_heads, heads = cfg.held_kda_heads, cfg.held_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f = cfg.moe_intermediate_size
    rule = (
        5 * 2 * CHUNK * CHUNK * d + 3 * 2 * CHUNK * d * d + 2 * CHUNK ** 3 / 3
    ) / CHUNK
    return {
        KDA: (
            (3 * 2 * h + 2 * 2 * rank + 2 * h) * kda_heads * d
            + 2 * 2 * h * rank + 2 * h * kda_heads + kda_heads * rule
        ),
        MLA: (
            2 * h * heads * qk + 2 * h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + 2 * cfg.kv_lora_rank * heads * (
                cfg.qk_nope_head_dim + cfg.v_head_dim
            )
            + 2 * heads * cfg.v_head_dim * h
            + 2 * heads * (qk + cfg.v_head_dim) * (seq + 1) / 2
        ),
        "dense_ffn": 2 * 3 * h * cfg.intermediate_size,
        "routed_ffn": (
            2 * h * cfg.num_experts + 2 * 3 * h * f * cfg.num_shared_experts
            + 2 * 3 * h * f * cfg.num_experts_per_token
            * cfg.held_experts[1] / cfg.num_experts
        ),
        "head": 2 * h * cfg.vocab_size,
    }


def kimi_linear_train_tflops_per_sample(cfg: KimiLinearConfig,
                                        seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (backward = 2x forward, remat's replays and the element-wise
    prelude not counted)."""
    part = kimi_linear_parts_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(
        part[mixer] + part["routed_ffn" if sparse else "dense_ffn"]
        for _n, mixer, sparse in cfg.layer_plan
    )
    return 3.0 * per_token * seq / 1e12
