"""SDAR-MoE (``model_type: sdar_moe``; the published model this file was
written for is JetLM/SDAR-30B-A3B-Chat), in Flax: the Qwen3-MoE decoder —
pre-norm, grouped-query attention with an RMSNorm over each head of q and k
before RoPE, every layer a dropless routed FFN of SwiGLU experts — trained
with the BLOCK-DIFFUSION objective (SDAR, arXiv 2510.06303, after Block
Diffusion, arXiv 2503.09573): a row goes through the stack TWICE in one
sequence, noisy copy first. ``benchmark/reference/sdar_moe.py`` carries the
same equations in plain ``jax.numpy``:

    a row: x [L] clean ids; blocks of B = 4: b(p) = p // B; per block a noise
    level t ~ U(0, 1]; x~ = x with each id of a block replaced by the mask
    id M with probability t; w_p = 1 / t_b(p) where x~_p = M, else 0
    (``data/block_diffusion.py`` draws all of it on the host)
    the stack's input: [x~ ; x], 2L positions, position ids [0..L-1, 0..L-1]
    query i sees key j iff   i clean: j clean and b(j) <= b(i)
                             i noisy: (j noisy and b(j) == b(i))
                                      or (j clean and b(j) < b(i))
    layer, eps 1e-6, no bias:
    n  = RMSNorm(h);  q, k, v = W_q n, W_k n, W_v n     32 / 4 / 4 heads of 128
    q, k = RoPE(RMSNorm_q(q)), RoPE(RMSNorm_k(k))       a weight per lane,
                                                        shared by the heads;
                                                        rotate-half, theta 1e6
    a  = softmax(q kᵀ / sqrt(128) + visibility) v       kv head j serves query
                                                        heads 8j .. 8j+7
    h' = h + W_o a;  m = RMSNorm(h');  r = W_r m        [E] logits, float32
    C  = top_8(r);  g = softmax(r)[C] / Σ softmax(r)[C] = softmax(r[C])
    h''= h' + Σ_{e in C} g_e W_down,e(silu(W_gate,e m) ⊙ W_up,e m)
    after the stack, over the NOISY stream's L positions only: a final
    RMSNorm, an untied head, and
    loss = (1 / L) Σ_p w_p · (−log softmax(W_head h_p)[x_p])
    — the prediction for position p is read AT position p (no shift); no
    auxiliary or balancing term, no leaf stepped by a sign.

The program's shape: a uniform stack, ``decoder.scan_periods``' at a period
of ``SCAN_PERIOD`` layers (see there for why not a layer a step). The blocks,
the grouped-query attention (here with a q / k norm), the routed layer
(``decoder.RoutedGLU`` with a SiLU gate) and the routing metrics are
``models/decoder.py``'s; attention is ONE call of ``ops/flash_attention.py``
over both streams with ``block_diffusion=B``: the visibility rule is inside
the grouped kernels (``flash_bd_*``), tiles outside it are neither grid steps
nor fetched.

**A chip's share**, as for the other expert decoders: ``expert_shard`` (the
experts held of every layer), ``vocab_size`` (rows held of the embedding AND
of the head; the LAST held row is the mask id) and ``num_hidden_layers``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    RMSNorm,
    RoutedGLU,
    Visibility,
    apply_with_grad_sinks,
    chunked_cross_entropy,
    embed_tokens,
    held_range,
    mixer_residual,
    named_config,
    rope_tables,
    routed_metrics,
    scan_periods,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.flash_attention import _pick_block, visited_tiles

# Layers a scan step runs, unrolled, each with leaves of its own. Not a layer
# a step: a scan over single layers hands the backward every layer's expert
# gradients STACKED, so the tile loop's gradient sinks are slices of a fresh
# buffer — at four layers of 16 held experts a zero fill, a copy of the
# accumulator's leaves and a bf16 copy of the weights, 3.0 GB of scratch
# beside a state that leaves 2.5 (``tools/tpu_aot.py sdar_accumulate_step``:
# 5.62 GB a layer a step). With a period's layers unrolled the sinks ARE the
# accumulator's leaves.
SCAN_PERIOD = 4


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """SDAR-30B-A3B-Chat as published (``config.json``); what it does not
    fix (the block length among it) is in
    ``benchmark/configs/sdar_30b_a3b_s4096.json`` under ``assumed``."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    block_length: int = 4  # the released Chat checkpoints' ``block_length``
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table. "whole_mixer": the layer keeps
    # q / k / v as the flash kernels read them (after the q / k norm and
    # RoPE) beside out + lse, the q / k norm's INPUT (what its backward
    # reads) and the stream after attention, so the backward's replay runs
    # no matmul of the mixer — q_proj, k_proj, v_proj, o_proj —, no RoPE and
    # no relayout: 8,192 x ((32 + 2·4) x 128 + (32 + 4) x 128 + 2,048) x 2
    # bytes = 193 MB a layer a micro-batch (84 of them "kernel_operands"'),
    # 0.77 GB in the benchmark's cell of four layers, where accumulate_step's
    # scratch reads 1.70 GB (1.28 under "kernel_operands") beside 7.30 GB
    # of state and accumulator. A smaller chip or a larger share:
    # --training.remat_policy kernel_operands, then kernel_outputs
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @property
    def mask_token_id(self) -> int:
        """The absorbing state: the last row of the vocabulary held."""
        return self.vocab_size - 1

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "sdar_30b_a3b": SdarMoeConfig.sdar_30b_a3b,
            "sdar_tiny": SdarMoeConfig.tiny,
        })

    @staticmethod
    def sdar_30b_a3b(**overrides) -> "SdarMoeConfig":
        return SdarMoeConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "SdarMoeConfig":
        """Test-sized: every mechanism (two streams under the block rule,
        a group of eight query heads on one kv head with their q / k norm,
        16 SwiGLU experts top-4, a chunked untied head over the noisy
        stream), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=8, num_key_value_heads=1, head_dim=8,
            moe_intermediate_size=16, num_experts=16, num_experts_per_tok=4,
            max_position_embeddings=128, moe_row_tile=8,
            attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return SdarMoeConfig(**base)


class DecoderLayer(nn.Module):
    """h' = h + Attn(RMSNorm(h)); h'' = h' + Experts(RMSNorm(h')), routed
    by the same normalised stream. Attention is grouped-query over [noisy ;
    clean] under the block rule, q and k normalised per head and rotated by
    their position IN THEIR STREAM. Returns (h'', routing)."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        hidden = mixer_residual(hidden, GroupedQueryAttention(
            cfg, Visibility(block_diffusion=cfg.block_length),
            qk_norms=("q_norm", "k_norm"), name="self_attn",
        )(RMSNorm(cfg, name="input_layernorm")(hidden), rope))
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        y, routing = RoutedGLU(cfg, activation="silu", name="mlp")(x, x)
        return hidden + y, routing


class SdarMoeForDiffusionLM(nn.Module):
    """``__call__(input_ids)``, ``input_ids`` [B, 2L] = [x~ ; x] -> (hidden
    after the final norm, in the compute dtype: the NOISY stream's [B, L, H],
    what the head reads — both streams' [B, 2L, H] with ``both_streams``;
    routing, every entry stacked over the layers, over all 2L positions).
    The head's weight is the parameter ``lm_head`` [H, V], applied by
    ``sdar_moe_loss`` a chunk of tokens at a time."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, input_ids,
                 both_streams: bool = False) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        length = input_ids.shape[1] // 2
        hidden = embed_tokens(self, input_ids)
        # positions 0 .. L-1 TWICE: a position's two copies rotate alike
        rope = tuple(
            jnp.concatenate([table, table]) for table in rope_tables(
                length, cfg.head_dim, cfg.rope_theta
            )
        )
        hidden, routing = scan_periods(
            functools.partial(remat_layer, DecoderLayer, cfg),
            ((),) * cfg.num_hidden_layers,
            min(SCAN_PERIOD, cfg.num_hidden_layers), hidden, rope,
        )
        if not both_streams:
            hidden = hidden[:, :length]
        return RMSNorm(cfg, name="norm")(hidden), routing


def bd_tile_share(cfg: SdarMoeConfig, seq: int) -> float:
    """(query tile, key tile) pairs the block-diffusion kernels visit over
    those of a causal call on the same 2L positions and tiles, from the shapes:
    80 / 136 at L = 4,096 and 512 x 512 tiles; 1 where a tile is a stream."""
    block = _pick_block(seq, cfg.attention_block_size)  # of ONE stream
    return visited_tiles(
        2 * seq, block, block, False, block_diffusion=cfg.block_length
    ) / visited_tiles(2 * seq, block, block, True)


def sdar_moe_loss(model: SdarMoeForDiffusionLM, params,
                  batch: Dict[str, jnp.ndarray], grad_sinks=None,
                  compute_copies=None):
    """(loss, metrics) of one micro-batch of ``data/block_diffusion.py``:
    ``input_ids`` (x~), ``labels`` (x) and ``loss_weights`` (w), [B, L]
    each. The routed metrics are ``decoder.routed_metrics``' (``moe.scores``
    holds the router's LOGITS, over both streams; no bias to report) and
    ``grad_sinks`` and ``compute_copies`` ``decoder.expert_lm_loss``'s;
    beside them the masked
    positions' share and count, and ``attn.bd_tile_share``."""
    cfg = model.cfg
    clean, weights = batch["labels"], batch["loss_weights"]
    hidden, routing = apply_with_grad_sinks(
        model, params,
        jnp.concatenate([batch["input_ids"], clean], axis=1), grad_sinks,
        compute_copies,
    )
    ce = chunked_cross_entropy(
        hidden.reshape(1, -1, cfg.hidden_size),
        params["lm_head"].astype(cfg.dtype), clean.reshape(-1),
        cfg.loss_chunk_tokens,
    )
    loss = jnp.sum(ce * weights.reshape(1, -1)) / clean.size
    masked = jnp.sum(weights > 0)
    share = bd_tile_share(cfg, clean.shape[1])
    return loss, {
        "loss": loss,
        "diffusion.masked_share": masked / clean.size,
        "diffusion.masked_tokens": masked.astype(jnp.float32),
        **routed_metrics(routing, params, {
            "attn.bd_tile_share": lambda _p, _r: jnp.float32(share),
        }),
    }


def sdar_moe_flops_per_row(cfg: SdarMoeConfig, seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs of one row of ``seq`` clean tokens, by part:
    the stack sees 2 x ``seq`` positions, attention its VISIBLE pairs
    (clean x clean L(L + B) / 2, noisy x clean L(L − B) / 2, noisy x noisy
    L·B: L² + L·B), the head the noisy stream's ``seq``; routed work for the
    HELD experts at the expected share of slots."""
    h, d, b = cfg.hidden_size, cfg.head_dim, cfg.block_length
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    layers, positions = cfg.num_hidden_layers, 2 * seq
    return {
        "projections": layers * positions * (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
        ),
        "attention": layers * 2 * 2 * heads * d * (seq * seq + seq * b),
        "router": layers * positions * 2 * h * cfg.num_experts,
        "routed": layers * positions * (
            2 * 3 * h * cfg.moe_intermediate_size * cfg.num_experts_per_tok
            * cfg.held_experts[1] / cfg.num_experts
        ),
        "head": seq * 2 * h * cfg.vocab_size,
    }


def sdar_moe_train_tflops_per_sample(cfg: SdarMoeConfig, seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq`` clean
    tokens — a SAMPLE; the stack's 2 x ``seq`` positions are inside —
    (matmuls only, backward = 2x forward, remat's replays not counted)."""
    return 3.0 * sum(sdar_moe_flops_per_row(cfg, seq).values()) / 1e12
