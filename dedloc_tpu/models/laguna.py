"""Laguna (``model_type: laguna``; the published model this file was written
for is poolside/Laguna-XS.2, 33B-A3B), in Flax: a pre-norm decoder whose
attention layers are of two KINDS that differ in more than their mask — a
full-attention layer in one layer of four and a sliding-window layer in the
other three, each kind with a query-head count, a RoPE and an output gate of
its own over the same kv heads — over one leading dense SwiGLU layer and then
a dropless sigmoid top-k routed FFN of fine-grained experts beside a shared
expert. ``benchmark/reference/laguna.py`` carries the same equations in plain
``jax.numpy``:

    x [S, 2048], no bias anywhere, RMSNorm eps 1e-6, layer l:
    n   = RMSNorm_in(x)
    H_l = num_attention_heads_per_layer[l]: 48 where layer_types[l] is
          full_attention, 64 where sliding_attention; 8 kv heads of 128; kv
          head j serves the H_l / 8 ADJACENT query heads (groups of 6, of 8)
    q = W_q n [H_l x 128];  k = W_k n, v = W_v n [8 x 128]
    g = sigmoid(W_g n) [H_l]                       one gate a head
    full_attention:    rotate-half RoPE over lanes 0..63 of each head of q
                       and k, lanes 64..127 pass (partial_rotary_factor 0.5);
                       32 inverse frequencies by YaRN over dim 64
                       (``decoder.yarn_inv_freq``: theta 500000, factor 64,
                       original 4096, beta_fast 64, beta_slow 1); cos and
                       sin x 1.4158883083359672
    sliding_attention: rotate-half RoPE over the whole 128 lanes, theta
                       10000, no scaling
    a_h = softmax(q_h k_jᵀ / sqrt(128) + mask_l) v_j;  mask_l: key <= query,
          and where sliding_attention also query - key < 512 (the window
          counts the query's own position)
    h   = x + W_o concat_h(g_h · a_h)
    m   = RMSNorm_post(h)
    mlp_layer_types[l] dense (layer 0):
          y = h + W_down(silu(W_gate m) ⊙ W_up m), width 8192
    sparse:  s = sigmoid(W_r m) [256], float32 at full precision
             C = top-8(s);  w_e = 2.5 · s_e / Σ_{c in C} s_c, applied to the
             experts' OUTPUTS
             y = h + Σ_{e in C} w_e Expert_e(m) + Shared(m)
             Expert_e, Shared: SwiGLU 2048 → 512 → 2048
    after the stack a final RMSNorm, an untied head; loss: mean next-token
    cross-entropy; no auxiliary term, no selection bias, no leaf stepped by
    a sign

The program's shape: ``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer`` (the published lists) say what each layer
is. The leading dense layer is unrolled; the sparse layers are
``decoder.scan_periods``' stack at the pattern's period (four: sliding,
sliding, sliding, full), every layer a remat'd block whose kind STATES its
head count, its rotary tables and its gate to ``decoder.
GroupedQueryAttention`` — q_proj / o_proj / g_proj have a width per kind.
The blocks, the attention, the routed layer (``decoder.RoutedFFN`` without a
bias, with this model's shared width), the loss tail and the leaf mask are
``models/decoder.py``'s; the kernels are the grouped-query mode of
``ops/flash_attention.py`` — a whole group of SIX a program in the full
layers, a group of eight under ``band=512`` in the others: a band EQUAL to
the tile, every query tile's two key tiles both crossed — and, behind them,
the gate's own pair (``ops/head_gate.py``: ``g_h · a_h`` in the layout the
flash kernel wrote and ``W_o`` reads).

**A chip's share**, as for the other expert decoders: ``expert_shard`` (the
experts held of every sparse layer; the shared expert is on every chip),
``vocab_size`` (rows held of the embedding AND of the head) and
``num_hidden_layers`` (the FIRST layers of the published lists: the leading
dense layer, then the pattern as the model has it there).
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
    RoutedFFN,
    SwiGLU,
    Visibility,
    embed_tokens,
    expert_lm_loss,
    head_gate,
    held_range,
    mixer_residual,
    named_config,
    period_of,
    rope_tables,
    scan_periods,
    weight_decay_mask,
    yarn_inv_freq,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.flash_attention import visited_tiles

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def _published(layers: int, first, others) -> Tuple:
    """Laguna-XS.2's per-layer lists: ``first`` in layers 0, 4, 8, ...."""
    return tuple(first if i % 4 == 0 else others for i in range(layers))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Laguna-XS.2 as published (``config.json``); what it does not fix is
    in ``benchmark/configs/laguna_xs2_33b_a3b_s8192.json`` under
    ``assumed``."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _published(40, FULL, SLIDING)
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    num_attention_heads_per_layer: Tuple[int, ...] = _published(40, 48, 64)
    num_attention_heads: int = 48  # the published key; the list decides
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # rope_parameters, a group a kind of layer
    full_rope_theta: float = 500000.0
    full_partial_rotary_factor: float = 0.5
    full_yarn_factor: float = 64.0
    full_yarn_original_max_position_embeddings: int = 4096
    full_yarn_beta_fast: float = 64.0
    full_yarn_beta_slow: float = 1.0
    full_yarn_attention_factor: float = 1.4158883083359672
    sliding_rope_theta: float = 10000.0
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    routed_scaling_factor: float = 2.5  # moe_routed_scaling_factor
    route_eps: float = 1e-20  # DeepSeek-V3's, whose rule this is
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table. "whole_mixer": a layer keeps q / k
    # / v as the flash kernels read them beside out + lse, the gate's
    # logits and the stream after attention, so the backward's replay runs
    # no projection of the mixer, no RoPE and no relayout; the gated
    # context is made again from ``out`` with one more call of the gate's
    # kernel (``ops/head_gate.py``: 0.37 ms): 8,192 x ((64 + 2·8) x 128 +
    # 64 + 2,048) x 2 bytes = 202 MB a sliding layer a micro-batch, 169 MB
    # a full one; in the benchmark's cell of five layers accumulate_step's
    # scratch reads 2.47 GB (2.94 before the gate was a kernel pair, PR
    # 48; then 2.80 under "kernel_operands", 2.14 under "kernel_outputs")
    # beside 6.23 GB of state and accumulator, and the allocator's peak
    # does not move with it (PERF.md section 5). A smaller
    # chip or a larger share: --training.remat_policy kernel_operands,
    # then kernel_outputs
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises
        lists = (self.layer_types, self.mlp_layer_types,
                 self.num_attention_heads_per_layer)
        if len({len(x) for x in lists}) != 1:
            raise ValueError("the three per-layer lists name the same layers")
        if not 1 <= self.num_hidden_layers <= len(self.layer_types):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the lists "
                f"have {len(self.layer_types)} layers"
            )
        ffns = [sparse for _kind, _heads, sparse in self.layer_plan]
        if sorted(ffns) != ffns:
            raise ValueError("the dense layers lead the stack")

    # the routed layer's field under ``RoutedFFN``'s name
    n_routed_experts = property(lambda self: self.num_experts)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @property
    def layer_plan(self) -> List[Tuple[str, int, bool]]:
        """(attention kind, query heads, routed FFN?) of every layer run:
        the first ``num_hidden_layers`` of the published lists."""
        return [
            (kind, heads, ffn == SPARSE) for kind, heads, ffn in zip(
                self.layer_types[:self.num_hidden_layers],
                self.num_attention_heads_per_layer, self.mlp_layer_types,
            )
        ]

    @property
    def full_rotary_dim(self) -> int:
        """Lanes of a full-attention head that RoPE turns: its first."""
        return int(self.head_dim * self.full_partial_rotary_factor)

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "laguna_xs2_33b_a3b": LagunaConfig.laguna_xs2_33b_a3b,
            "laguna_tiny": LagunaConfig.tiny,
        })

    @staticmethod
    def laguna_xs2_33b_a3b(**overrides) -> "LagunaConfig":
        return LagunaConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "LagunaConfig":
        """Test-sized: every mechanism (a leading dense layer under full
        attention, then a period of three sliding layers and a full one and
        a layer over; 6 and 8 query heads on 2 kv heads — groups of three
        and four —, half a full head's lanes rotated by YaRN-scaled
        frequencies at twice the original length, a band of 8 at S=32, a
        gate a head, 16 experts top-4 x 2.5 beside a shared expert of its
        own width, a chunked untied head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=48,
            num_hidden_layers=6, layer_types=_published(6, FULL, SLIDING),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 5,
            num_attention_heads_per_layer=_published(6, 6, 8),
            num_attention_heads=6, num_key_value_heads=2, head_dim=16,
            sliding_window=8, full_yarn_factor=4.0,
            full_yarn_original_max_position_embeddings=16,
            full_yarn_beta_fast=4.0,
            full_yarn_attention_factor=1.1386294361119891,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
            shared_expert_intermediate_size=24, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return LagunaConfig(**base)


def laguna_rope_tables(cfg: LagunaConfig, seq: int) -> Dict[str, Tuple]:
    """(cos, sin) of each attention kind: [S, rotary width] — half a head's
    lanes under YaRN for the full layers, the whole head at the plain
    frequencies for the sliding ones."""
    dim = cfg.full_rotary_dim
    return {
        FULL: rope_tables(
            seq, dim, cfg.full_rope_theta,
            inv_freq=jnp.asarray(yarn_inv_freq(
                dim, cfg.full_rope_theta, cfg.full_yarn_factor,
                cfg.full_yarn_original_max_position_embeddings,
                cfg.full_yarn_beta_fast, cfg.full_yarn_beta_slow,
            )),
            scale=cfg.full_yarn_attention_factor,
        ),
        SLIDING: rope_tables(seq, cfg.head_dim, cfg.sliding_rope_theta),
    }


class DecoderLayer(nn.Module):
    """n = RMSNorm(x); h = x + W_o(g ⊙ Attn(n)); y = h + FFN(RMSNorm(h)):
    ``kind`` is ``full_attention`` or ``sliding_attention`` over ``heads``
    query heads, the FFN a dense SwiGLU or (``sparse``) the routed layer.
    Returns (y, what the layer reports: its mean gate and, routed, its
    routing)."""

    cfg: LagunaConfig
    kind: str
    heads: int
    sparse: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        x = RMSNorm(cfg, name="input_layernorm")(hidden)
        gate = head_gate(cfg, x, self.heads)
        band = cfg.sliding_window if self.kind == SLIDING else None
        hidden = mixer_residual(hidden, GroupedQueryAttention(
            cfg, Visibility(causal=True, band=band), heads=self.heads,
            name="self_attn",
        )(x, rope[self.kind], gate))
        report = {"gate_mean": jnp.mean(gate)}
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        if not self.sparse:
            return hidden + SwiGLU(
                cfg, cfg.intermediate_size, name="mlp"
            )(x), report
        y, routing = RoutedFFN(
            cfg, shared_width=cfg.shared_expert_intermediate_size,
            biased=False, name="mlp",
        )(x)
        return hidden + y, dict(routing, **report)


class LagunaForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the SPARSE layers
    in order, but ``gate_mean``: over every layer). The head's weight is the
    parameter ``lm_head`` [H, V], applied by ``laguna_loss`` a chunk of
    tokens at a time."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids)
        rope = laguna_rope_tables(cfg, input_ids.shape[1])
        layer = functools.partial(remat_layer, DecoderLayer, cfg)
        gates = []
        for i, kind in enumerate(k for k in cfg.layer_plan if not k[2]):
            hidden, report = layer(*kind, name=f"dense_layer_{i}")(
                hidden, rope
            )
            gates.append(report["gate_mean"][None])
        routed = [kind for kind in cfg.layer_plan if kind[2]]
        hidden, routing = scan_periods(
            layer, routed, period_of(routed), hidden, rope
        )
        routing["gate_mean"] = jnp.concatenate(
            gates + [routing["gate_mean"]]
        )
        return RMSNorm(cfg, name="norm")(hidden), routing


def band_tile_share(cfg: LagunaConfig, seq: int) -> float:
    """(query tile, key tile) pairs a sliding layer's kernels visit over
    the causal triangle's, from the shapes: 31 / 136 at S=8,192, a band of
    512 and 512 x 512 tiles."""
    block = cfg.attention_block_size
    return visited_tiles(
        seq, block, block, True, cfg.sliding_window
    ) / visited_tiles(seq, block, block, True)


def band_visible_share(cfg: LagunaConfig, seq: int) -> float:
    """Visible (query, key) pairs of a sliding layer over the pairs of the
    tiles its kernels visit: what a tile's matmuls are worth under a band
    no longer than the tile — 4,063,488 / (31 x 512²) = 0.50 at S=8,192 (a
    band of 4,096 at S=16,384 reads 0.89)."""
    block = min(cfg.attention_block_size, seq)
    band = min(cfg.sliding_window, seq)
    pairs = band * (band + 1) // 2 + (seq - band) * band
    tiles = visited_tiles(seq, block, block, True, cfg.sliding_window)
    return pairs / (tiles * block * block)


def laguna_loss(model: LagunaForCausalLM, params,
                batch: Dict[str, jnp.ndarray], grad_sinks=None,
                compute_copies=None):
    """``decoder.expert_lm_loss`` under the untied head (no bias to
    report), with the sliding layers' two tile shares and each attention
    kind's mean gate as gauges."""
    cfg = model.cfg
    seq = batch["input_ids"].shape[1]
    shares = {
        "attn.band_tile_share": band_tile_share(cfg, seq),
        "attn.band_visible_share": band_visible_share(cfg, seq),
    }
    layers = {
        kind: [i for i, k in enumerate(cfg.layer_plan) if k[0] == kind]
        for kind in (FULL, SLIDING)
    }
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["lm_head"].astype(cfg.dtype),
        gauges={
            **{name: lambda _p, _r, share=share: jnp.float32(share)
               for name, share in shares.items()},
            **{f"attn.gate_mean.{kind}": lambda _p, r, at=tuple(at): jnp.mean(
                   r["gate_mean"][jnp.asarray(at)]
               ) for kind, at in layers.items() if at},
        },
    )


# decayed: every matrix; not the RMSNorm ``weight``s
laguna_weight_decay_mask = weight_decay_mask


def laguna_layer_flops_per_token(cfg: LagunaConfig,
                                 seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of one layer PART: each attention
    kind's mixer at its own head count — a sliding layer counted at its
    window: min(i + 1, window) keys for query i —, the two FFNs (routed work
    for the HELD experts at the expected share of slots, the shared expert
    whole) and the untied head over the held rows."""
    h, d, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    band = min(cfg.sliding_window, seq)
    pairs = {
        FULL: seq * (seq + 1) / 2,
        SLIDING: band * (band + 1) / 2 + (seq - band) * band,
    }
    heads = {kind: n for kind, n, _sparse in cfg.layer_plan}
    return {
        **{kind: (
            2 * h * (n + 2 * kv) * d + 2 * n * d * h + 2 * h * n  # qkv o g
            + 2 * 2 * n * d * pairs[kind] / seq  # QKᵀ, PV
        ) for kind, n in heads.items()},
        DENSE: 2 * 3 * h * cfg.intermediate_size,
        SPARSE: (
            2 * h * cfg.num_experts
            + 2 * 3 * h * cfg.moe_intermediate_size
            * cfg.num_experts_per_tok * cfg.held_experts[1] / cfg.num_experts
            + 2 * 3 * h * cfg.shared_expert_intermediate_size
        ),
        "head": 2 * h * cfg.vocab_size,
    }


def laguna_train_tflops_per_sample(cfg: LagunaConfig, seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted)."""
    part = laguna_layer_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(
        part[kind] + part[SPARSE if sparse else DENSE]
        for kind, _heads, sparse in cfg.layer_plan
    )
    return 3.0 * per_token * seq / 1e12
