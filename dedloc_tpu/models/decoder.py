"""What the decoders share, written once; it imports no model's file.

A decoder of this tree (``models/ouro.py``, ``deepseek_v3.py``,
``lfm2_moe.py``, ``smallthinker.py``, ``sdar_moe.py``, ``laguna.py``,
``keye_vl2.py``, ``kimi_linear.py``, ``nemotron_h.py``) is its config, the
mixer that is its own, its layer's wiring of norms and residuals, its FLOP
model and — where the objective is its own — its loss; a new one is one such
module + one ``roles/common.MODEL_FAMILIES`` entry. The rest is here: the
blocks, ONE description of who sees whom (``Visibility``) under ``attend``,
``GroupedQueryAttention`` and ``LatentAttention`` (two models' mixer since
Kimi Linear: its head count and its RoPE are arguments), the causal
depthwise convolution + SiLU of the recurrent mixers (``causal_conv_silu``:
Kimi Linear's, Nemotron-H's with a bias) and the count of a head share
(``held_heads``), the two routed
layers over ONE
``held_expert_ffn``, the stack (``scan_periods``), the head + loss tail and the
leaf masks. What differs between models arrives as an argument (a name, a
rule, a function of ``params``), never by a model's name or config class;
``cfg`` is any config with the fields a function reads.

TPU notes: matmuls in bf16 with float32 accumulation; norms, softmax, the
router and the loss in float32; q/k/v leave their projections as [B, S, H·D]
and go to the flash kernels in that layout, named ``flash_qkv`` (RoPE is
applied before the name, so what a policy stashes is what the kernels read);
the stream after a mixer is named ``mixer_residual`` and a per-head q / k
norm's input ``qk_norm_input``, for the policies of ``models/remat.py``; a
per-head output gate — the one element-wise op on a flash kernel's OUTPUT —
is a kernel pair of its own behind the flash call (``ops/head_gate.py``), in
the [B, S, H·D] layout the kernel wrote and the out-projection reads;
RMSNorm is XLA's.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.parallel.moe import (
    PLAIN_ACTIVATIONS,
    expert_load,
    route_top_k,
    route_top_k_softmax,
    routed_experts,
    with_load_cotangent,
)

BIAS = "e_score_correction_bias"  # the leaf the sign rule steps
# a routed layer's held matrices: the leaves whose gradients the tile loop
# can leave in a float32 accumulator it is handed (``parallel/moe.py``) — as
# the collection GRAD_SINKS beside ``params``, the same names and stacking
EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")
GRAD_SINKS = "grad_sinks"
# the same leaves ALREADY in the compute dtype, a third collection of the same
# names and stacking: what ``parallel/train_step.make_accumulate_step`` casts
# once per set of weights where every micro-batch's forward and remat replay
# cast them again (``held_expert_ffn``)
COMPUTE_COPIES = "compute_copies"


def named_config(model_size: str, ctors: Dict[str, Callable]) -> Callable:
    """The constructor a ``--training.model_size`` name stands for."""
    if model_size not in ctors:
        raise ValueError(
            f"unknown model_size {model_size!r} "
            f"(expected one of {sorted(ctors)})"
        )
    return ctors[model_size]


def held_range(expert_shard: Tuple[int, int],
               num_experts: int) -> Tuple[int, int]:
    """(first expert held, how many) of a chip's share ``expert_shard`` =
    (index, count): experts [index·E/count, (index+1)·E/count)."""
    index, count = expert_shard
    if not (0 <= index < count) or num_experts % count:
        raise ValueError(
            f"expert_shard {index}/{count}: the count must divide the "
            f"{num_experts} routed experts, 0 <= index < count"
        )
    return index * (num_experts // count), num_experts // count


def held_heads(head_shard: Tuple[int, int], heads: int) -> int:
    """How many of a mixer's ``heads`` the share ``head_shard`` = (index,
    count) holds."""
    index, count = head_shard
    if not (0 <= index < count) or heads % count:
        raise ValueError(
            f"head_shard {index}/{count}: the count must divide the "
            f"{heads} heads, 0 <= index < count"
        )
    return heads // count


def dense(features: int, cfg, name: str, init_scale: float = 1.0) -> nn.Dense:
    """A bias-free projection; ``init_scale``: a factor on the initialiser's
    deviation (a model that rescales its out-projections by its depth)."""
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.initializer_range * init_scale),
        name=name,
    )


class RMSNorm(nn.Module):
    """x · rsqrt(mean(x²) + eps) · weight, statistics in float32. ``cfg``:
    any config with ``rms_norm_eps`` and ``dtype``."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (
            x32 * jax.lax.rsqrt(var + self.cfg.rms_norm_eps) * weight
        ).astype(self.cfg.dtype)


def rope_tables(seq: int, head_dim: int, theta: float, inv_freq=None,
                scale=None):
    """cos, sin [S, D] of rotate-half RoPE: the D/2 frequencies repeated
    over both halves. ``head_dim`` is the width that is ROTATED (a table
    narrower than the head rotates the head's first lanes alone,
    ``apply_rope``); ``inv_freq`` [D/2]: frequencies of the caller's own in
    place of theta^(-2i/D) (``yarn_inv_freq``); ``scale``: a factor on both
    tables (YaRN's attention factor)."""
    if inv_freq is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    if scale is None:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def mrope_tables(position_ids, head_dim: int, theta: float,
                 sections: Sequence[int]):
    """cos, sin [B, S, D] of M-RoPE (``rope_scaling.mrope_section``; Qwen2-VL,
    arXiv 2409.12191): ``position_ids`` [3, B, S] are a token's temporal,
    height and width positions, and frequency pair i of the D/2 — f_i =
    theta^(-2i/D) as ever — turns by the stream its CONTIGUOUS section
    names: the first ``sections[0]`` pairs by the first stream, the next
    ``sections[1]`` by the second, the rest by the third; rotate-half, the
    pairs repeated over both halves. Where a token's three positions are
    equal (text) the angles are ``rope_tables``' at that position, the same
    floats: one multiply each."""
    if sum(sections) != head_dim // 2 or len(sections) != 3:
        raise ValueError(
            f"mrope sections {tuple(sections)}: three, and {head_dim // 2} "
            "frequency pairs in all"
        )
    with jax.named_scope("mrope"):
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
        stream = np.repeat(np.arange(3), sections)  # [D/2] of 0 / 1 / 2
        # [B, S, D/2]: each pair's own stream's position
        positions = jnp.moveaxis(position_ids.astype(jnp.float32), 0, -1)
        angles = jnp.take(positions, stream, axis=-1) * inv_freq
        angles = jnp.concatenate([angles, angles], axis=-1)
        return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's dim/2 inverse frequencies (``rope_type: yarn``; arXiv
    2309.00071), float32: f_i = theta^(-2i/dim) kept where a pair turns more
    than ``beta_fast`` times over the ``original`` positions, divided by
    ``factor`` where it turns less than ``beta_slow`` times, and a linear
    ramp between — r_i = clip((i - low) / (high - low), 0, 1) with low =
    floor(c(beta_fast)), high = ceil(c(beta_slow)), c(b) = dim · ln(original
    / (2π b)) / (2 ln theta), both held to [0, dim - 1]; f_i / factor · r_i
    + f_i · (1 - r_i). Host arithmetic in float64: a constant of the
    program."""
    f = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(beta):
        return dim * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0
    )
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def apply_rope(x, cos, sin):
    """x [B, S, H, D]: x·cos + rotate_half(x)·sin, in float32. Tables
    narrower than D (partial rotary) rotate the head's FIRST lanes and pass
    the others as they are; tables [S, D] serve every row, tables [B, S, D]
    (``mrope_tables``) each token its own."""
    width = cos.shape[-1]
    if width < x.shape[-1]:
        with jax.named_scope("rope_partial"):
            return jnp.concatenate(
                [apply_rope(x[..., :width], cos, sin), x[..., width:]],
                axis=-1,
            )
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    if cos.ndim == 3:  # a token's own angles
        return (
            x32 * cos[:, :, None, :] + rotated * sin[:, :, None, :]
        ).astype(x.dtype)
    return (
        x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]
    ).astype(x.dtype)


def swiglu(cfg, x, width: int):
    """down(silu(gate x) · up x) with ``gate_proj`` / ``up_proj`` /
    ``down_proj`` created in the CALLING module's scope (call it inside a
    compact method)."""
    # named for the remat policies that stash the FFN's matmul outputs
    gate = checkpoint_name(dense(width, cfg, "gate_proj")(x), "ffn_up")
    up = checkpoint_name(dense(width, cfg, "up_proj")(x), "ffn_up")
    return dense(cfg.hidden_size, cfg, "down_proj")(nn.silu(gate) * up)


class SwiGLU(nn.Module):
    """``swiglu`` in a scope of its own (a decoder whose layer has more than
    one: a dense FFN, shared experts)."""

    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        return swiglu(self.cfg, x, self.width)


def causal_conv_silu(x, taps, bias=None):
    """SiLU(causal depthwise convolution) of x [B, S, W] with ``taps``
    [W, K] (``taps[:, K - 1]`` multiplies the current position, zeros before
    the row) + ``bias`` [W] where the convolution has one (Mamba-2's), in
    float32, back in x's dtype."""
    seq, width = x.shape[1], taps.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(
        taps[:, k].astype(jnp.float32) * padded[:, k:k + seq]
        for k in range(width)
    )
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    return nn.silu(conv).astype(x.dtype)


class PlainMLP(nn.Module):
    """down(act(up x)): an UN-gated feed-forward of two matrices
    (``up_proj`` / ``down_proj``), ``activation`` a name of
    ``moe.PLAIN_ACTIVATIONS`` ("relu2": Nemotron-H's relu(.)²)."""

    cfg: Any
    width: int
    activation: str = "relu2"
    down_init_scale: float = 1.0

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        up = checkpoint_name(dense(self.width, cfg, "up_proj")(x), "ffn_up")
        return dense(
            cfg.hidden_size, cfg, "down_proj", self.down_init_scale
        )(PLAIN_ACTIVATIONS[self.activation][0](up))


def embed_tokens(module: nn.Module, input_ids, tied_head: bool = False):
    """The ids embedded by ``embed_tokens`` [V, H], in the compute dtype.
    Created in ``module``'s scope (call it inside a compact method), and
    beside it an untied head's ``lm_head`` [H, V]: declared there so the whole
    model is one parameter tree, applied by the loss a chunk at a time."""
    cfg = module.cfg
    init = nn.initializers.normal(cfg.initializer_range)
    embed = module.param(
        "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size), jnp.float32
    )
    if not tied_head:
        module.param(
            "lm_head", init, (cfg.hidden_size, cfg.vocab_size), jnp.float32
        )
    return jnp.take(embed, input_ids, axis=0).astype(cfg.dtype)


@dataclasses.dataclass(frozen=True)
class Visibility:
    """Which keys a query sees, in ``ops/flash_attention.flash_attention``'s
    own keywords: ``causal`` (keys 0..i), with it ``band`` (keys i-band+1..i),
    or ``block_diffusion`` (a noisy then a clean stream, blocks this long),
    or ``selected``: of the keys 0..i those a ``selection`` [B, S, S] marks —
    a visibility that is DATA, handed to ``attend`` beside q / k / v by the
    caller that states it."""

    causal: bool = False
    band: Optional[int] = None
    block_diffusion: Optional[int] = None
    selected: bool = False

    def matrix(self, seq: int, selection=None):
        """[S, S] bool ([B, 1, 1, S, S] under a selection), rows queries:
        the same rule for the dense reference."""
        i = jnp.arange(seq)
        q, k = i[:, None], i[None, :]
        if self.selected:
            return ((selection != 0) & (k <= q))[:, None, None]
        if self.block_diffusion is not None:
            # over [noisy ; clean]: a clean query sees the clean blocks up to
            # its own, a noisy one ITS noisy block and the clean ones BEFORE it
            half = seq // 2
            q_blk, k_blk = ((x % half) // self.block_diffusion for x in (q, k))
            return jnp.where(
                q >= half, (k >= half) & (k_blk <= q_blk),
                jnp.where(k >= half, k_blk < q_blk, k_blk == q_blk),
            )
        visible = k <= q if self.causal else jnp.ones((seq, seq), bool)
        if self.band is not None:
            visible &= q - k < self.band
        return visible


def attend(cfg, q, k, v, visible: Visibility, selection=None):
    """softmax(q kᵀ / sqrt(q's width) + visibility) v for q [B, S, H, D],
    k [B, S, KV, D], v [B, S, KV, Dv], kv head j serving H / KV adjacent query
    heads: the flash kernels (``cfg.attention_impl`` "flash"; [B, S, H, Dv])
    or XLA's materialized S² scores ("dense", for tests and tiny models;
    [B, S, KV, H / KV, Dv] — the same bytes). Under a ``selected``
    visibility the caller hands the ``selection`` [B, S, S] (int8, rows
    queries; named ``attn_selection`` where it is made, for the remat
    policies that keep the kernels' operands) in and gets (context, lse [B, H, S] float32 — each row's
    log-sum-exp over its selected keys, DETACHED: for a loss of the
    caller's on the probabilities) back."""
    if visible.selected and selection is None:
        raise ValueError("a selected visibility takes a selection")
    if cfg.attention_impl == "flash":
        from dedloc_tpu.ops.flash_attention import flash_attention

        mask = dataclasses.asdict(visible)
        if mask.pop("selected"):  # the kernels read it from the operand
            mask["selection"] = selection
        return flash_attention(
            q, k, v, **mask,
            block_q=cfg.attention_block_size,
            block_k=cfg.attention_block_size, mesh=cfg.mesh,
        )
    if cfg.attention_impl != "dense":
        raise ValueError(
            f"attention_impl={cfg.attention_impl!r}: a decoder takes "
            "'flash' or 'dense'"
        )
    B, S, H, D = q.shape
    KV = k.shape[2]
    q, k, v = (checkpoint_name(x, "flash_qkv") for x in (q, k, v))
    logits = jnp.einsum(
        "bqcgd,bkcd->bcgqk", q.reshape(B, S, KV, H // KV, D), k,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(jnp.float32(D))
    logits = jnp.where(visible.matrix(S, selection), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
    ctx = jnp.einsum("bcgqk,bkcd->bqcgd", probs, v)
    if not visible.selected:
        return ctx
    lse = jax.nn.logsumexp(logits, axis=-1).reshape(B, H, S)
    return ctx, jax.lax.stop_gradient(lse)


def mixer_residual(hidden, mixed):
    """``hidden + mixed``, a layer's stream after its mixer, named
    ``mixer_residual``: the post-mixer norm, the router and the experts'
    backward read this sum, so a policy that keeps it takes the mixer's
    out-projection out of the replay."""
    return checkpoint_name(hidden + mixed, "mixer_residual")


def head_gate(cfg, hidden, heads: int, name: str = "g_proj"):
    """sigmoid(W_g hidden) [B, S, heads], float32: ONE gate a query head,
    for ``GroupedQueryAttention``'s ``gate``. ``W_g`` [H, heads] is created
    in the CALLING module's scope (call it inside a compact method); its
    output is named ``attn_gate`` for the policies of ``models/remat.py``
    ([B, S, heads]: 1 MB where the gated context is B·S·heads·D)."""
    logits = checkpoint_name(dense(heads, cfg, name)(hidden), "attn_gate")
    return jax.nn.sigmoid(logits.astype(jnp.float32))


class GroupedQueryAttention(nn.Module):
    """Grouped-query attention under ``visible``: ``q_proj`` / ``k_proj`` /
    ``v_proj`` over ``heads`` query heads (None: ``cfg.num_attention_heads``;
    a stack whose layer kinds differ in their head count states each kind's)
    and ``cfg.num_key_value_heads`` kv heads, an RMSNorm over each head's own
    lanes of q and of k where ``qk_norms`` names the two (a weight each,
    shared by the heads), THEN rotate-half RoPE where ``rotated``, over as
    many of a head's first lanes as the tables of ``rope`` are wide; the
    output projection ``out_name`` (``out_init_scale``: ``dense``'s).
    ``kv_heads``: the kv heads the call HAS where a chip holds a share of
    them (None: ``cfg.num_key_value_heads``). ``gate`` [B, S, heads] (``head_gate``):
    each head's context times its gate, between the kernels and the output
    projection — ``ops/head_gate.gate_heads``' kernels behind the flash
    kernels on one device, XLA's expression behind ``"dense"`` attention and
    on a mesh (the op carries no ``shard_map``; no gated model runs on
    one). Under a ``selected`` visibility the call takes the ``selection``
    and returns (output, (q, k, lse)): the kernels' operands after norm and
    RoPE and ``attend``'s log-sum-exp, what a loss on the attention's
    probabilities is computed from."""

    cfg: Any
    visible: Visibility
    rotated: bool = True
    qk_norms: Tuple[Optional[str], Optional[str]] = (None, None)
    out_name: str = "o_proj"
    heads: Optional[int] = None
    kv_heads: Optional[int] = None
    out_init_scale: float = 1.0

    @nn.compact
    def __call__(self, hidden, rope, gate=None, selection=None):
        cfg = self.cfg
        B, S, _ = hidden.shape
        H, KV, D = (self.heads or cfg.num_attention_heads,
                    self.kv_heads or cfg.num_key_value_heads, cfg.head_dim)
        q = dense(H * D, cfg, "q_proj")(hidden).reshape(B, S, H, D)
        k = dense(KV * D, cfg, "k_proj")(hidden).reshape(B, S, KV, D)
        v = dense(KV * D, cfg, "v_proj")(hidden).reshape(B, S, KV, D)

        def positioned(x, norm):  # per head, over its own lanes; then RoPE
            if norm is not None:
                # the norm's backward reads the norm's INPUT: a policy
                # that keeps it takes the projection out of the replay, as
                # a kept ``flash_qkv`` takes ``v_proj``. Named per head,
                # the shape the matmul's fusion writes and the norm reads:
                # [B, S, H·D] is a relayout away (PERF.md section 6, PR 46)
                x = RMSNorm(cfg, name=norm)(
                    checkpoint_name(x, "qk_norm_input")
                )
            return apply_rope(x, *rope) if self.rotated else x

        q, k = map(positioned, (q, k), self.qk_norms)
        if cfg.attention_impl == "flash":
            # the kernels' operands as buffers of their own. Without the
            # barrier XLA:TPU folds RoPE's last add + cast into each of
            # their consumers and relays the float32 pieces BEFORE that add
            # out around every one (`copy`, `add_convert_fusion`, `reshape`:
            # 20 ms a micro-batch in SDAR's cell), and under
            # "kernel_operands" keeps THEM for the backward (4x the bytes).
            # accumulate_step a micro-batch in the benchmark's cells (PERF.md
            # section 6, PR 41): SmallThinker 395.8 → 374.5 ms with the
            # barrier alone, 361.8 with the operands kept too, scratch 4.00
            # GB without it, 2.53 with it; SDAR 185.9 → 166.2 → 159.9 ms,
            # 2.55 → 1.28 GB; LFM2's scratch 1.03 → 0.72 GB
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        ctx = attend(cfg, q, k, v, self.visible, selection)
        if self.visible.selected:
            ctx, lse = ctx
        # [B, S, KV, H / KV, D] (dense) is [B, S, H, D]: adjacent heads
        ctx = ctx.reshape(B, S, H * D)
        if gate is not None:
            from dedloc_tpu.ops.head_gate import gate_heads, gate_heads_xla

            # on one device, behind the flash kernels: ONE pass over the
            # context in the layout the kernel wrote and o_proj reads
            # (``head_gate_fwd`` / ``head_gate_bwd``; a head width that is
            # no whole lane tile falls back inside). XLA's expression put
            # the gate's heads on sublanes by writing a float32 [B, S, H·D]
            # broadcast of it twice a direction (PERF.md section 6, PR 48)
            fused = cfg.attention_impl == "flash" and cfg.mesh is None
            with jax.named_scope("attn_gate"):
                ctx = (gate_heads if fused else gate_heads_xla)(ctx, gate)
        out = dense(
            cfg.hidden_size, cfg, self.out_name, self.out_init_scale
        )(ctx)
        return (out, (q, k, lse)) if self.visible.selected else out


def apply_rope_interleaved(x, cos, sin):
    """x [B, S, H, D], rotated in pairs (2i, 2i+1) by the i-th frequency
    (``rope_interleave``), in float32; cos, sin [S, D/2]."""
    x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = x32[..., 0], x32[..., 1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    """Latent attention over ``heads`` query heads (None:
    ``cfg.num_attention_heads``; a chip that holds a share of the heads
    states how many), the one shared key head and each query's last
    ``qk_rope_head_dim`` lanes rotated by ``rope`` where ``rotated`` (a
    model without positional embedding in this layer — Kimi Linear — passes
    them as they are). ``cfg``: any config with the fields read here."""

    cfg: Any
    heads: Optional[int] = None
    rotated: bool = True

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        B, S, _ = hidden.shape
        H, rank = self.heads or cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rot, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        q = dense(H * (nope + rot), cfg, "q_proj")(hidden).reshape(
            B, S, H, nope + rot
        )
        latent = dense(rank + rot, cfg, "kv_a_proj_with_mqa")(hidden)
        kv = dense(H * (nope + dv), cfg, "kv_b_proj")(
            RMSNorm(cfg, name="kv_a_layernorm")(latent[..., :rank])
        ).reshape(B, S, H, nope + dv)
        if self.rotated:  # (q's lanes first: the order kanana-2 traced)
            cos, sin = rope
            q_rope = apply_rope_interleaved(q[..., nope:], cos, sin)
            k_rope = apply_rope_interleaved(
                latent[..., rank:].reshape(B, S, 1, rot), cos, sin
            )
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        else:
            k_rope = latent[..., rank:].reshape(B, S, 1, rot)
        # the ONE rotary key head, broadcast into k's 192-wide layout
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rot))],
            axis=-1,
        )
        v = kv[..., nope:]
        # no optimization_barrier (decoder.GroupedQueryAttention's): offline
        # it moves none of this program's 23 layer-body copies (PR 45)
        ctx = attend(cfg, q, k, v, Visibility(causal=True))
        return dense(cfg.hidden_size, cfg, "o_proj")(
            ctx.reshape(B, S, H * dv)
        )


def held_expert_ffn(module: nn.Module, tokens, choice, weights,
                    activation: str = "silu", down_init_scale: float = 1.0):
    """``parallel/moe.routed_experts`` over the experts ``module.cfg`` HOLDS:
    the three ``EXPERT_LEAVES`` created in ``module``'s scope (call it inside
    a compact method) and, where the apply carries the collection
    ``GRAD_SINKS``, this layer's three buffers handed to the tile loop's
    backward. ``tokens`` [T, H]; returns (y [T, H] float32, counts —
    ``routed_experts``' stats and ``compute_copy_leaves``, 3 or 0).
    ``activation``: the expert's FORM with it — a gate's ("silu", "relu":
    three matrices) or an UN-gated expert's (a name of
    ``moe.PLAIN_ACTIVATIONS``, "relu2": ``experts_up`` and ``experts_down``
    alone, two sinks, two copies). ``down_init_scale``: a factor on the
    down matrices' initial deviation (``dense``'s ``init_scale``).

    Who makes the matrices the loop reads in the compute dtype: where the
    apply carries the collection ``COMPUTE_COPIES`` (a ``GradSinkLoss`` on one
    device: the accumulate step casts the marked leaves ONCE per set of
    weights and hands the result in), the loop reads those three arrays and
    this function casts nothing; anywhere else — a mesh, evaluation, a
    float32 reference — the float32 leaves are cast here, whole, in the
    forward and again in a remat replay. The two are the same ``astype`` of
    the same weights. The copies exist exactly where the sinks do, where the
    cast's cotangent is zero anyway (``parallel/moe._grouped_swiglu_bwd``
    leaves the sums in the sinks and hands the matrices ``zeros_like``): no
    gradient ever flowed back through the cast under sinks, so taking it out
    of the differentiated function changes no gradient's arithmetic."""
    cfg = module.cfg
    H, F = tokens.shape[-1], cfg.moe_intermediate_size
    first, held = cfg.held_experts
    init = nn.initializers.normal(cfg.initializer_range)
    gated = activation not in PLAIN_ACTIVATIONS
    leaves = EXPERT_LEAVES if gated else EXPERT_LEAVES[1:]
    inits = (init,) * (len(leaves) - 1) + (
        nn.initializers.normal(cfg.initializer_range * down_init_scale),
    )
    matrices = tuple(
        module.param(name, leaf_init, shape, jnp.float32)
        for name, leaf_init, shape in zip(
            leaves, inits,
            ((held, H, F),) * (len(leaves) - 1) + ((held, F, H),),
        )
    )

    def beside(collection):  # this layer's arrays of it, or None
        if not module.has_variable(collection, leaves[0]):
            return None
        return tuple(
            module.get_variable(collection, name) for name in leaves
        )

    sinks, copies = beside(GRAD_SINKS), beside(COMPUTE_COPIES)
    y, counts = routed_experts(
        tokens, choice, weights, *(() if gated else (None,)),
        *(copies or (w.astype(cfg.dtype) for w in matrices)),
        (first, held), tile=cfg.moe_row_tile, grad_sinks=sinks,
        activation=activation,
    )
    return y, dict(
        counts, compute_copy_leaves=jnp.float32(len(copies or ())),
    )


class RoutedFFN(nn.Module):
    """Σ over the chosen HELD experts + a shared expert ``shared_width``
    wide on every chip (0: the model has none), chosen by sigmoid scores
    (DeepSeek-V3's rule; kanana-2, LFM2, Laguna) — where ``biased`` with
    the stepped ``BIAS`` in the CHOICE — the chosen scores renormalised x
    ``cfg.routed_scaling_factor``. ``activation``: the experts' form,
    ``held_expert_ffn``'s — the shared expert has the same (a SwiGLU, or
    ``PlainMLP`` of two matrices). Returns (y, routing): scores [T, E],
    choice [T, k], load [E] and ``parallel/moe.routed_experts``' counts;
    the load leaves the backward as the bias leaf's cotangent (``moe.py``'s
    rule) where there is one."""

    cfg: Any
    shared_width: int = 0
    biased: bool = True
    activation: str = "silu"
    down_init_scale: float = 1.0

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, H = x.shape
        E = cfg.n_routed_experts
        init = nn.initializers.normal(cfg.initializer_range)
        router = self.param("router", init, (H, E), jnp.float32)
        bias = self.param(
            BIAS, nn.initializers.zeros, (E,), jnp.float32
        ) if self.biased else None
        tokens = x.reshape(B * S, H)
        # the router in float32 at full precision: the top-k is discrete
        scores = jax.nn.sigmoid(jnp.dot(
            tokens.astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST,
        ))
        choice, weights = route_top_k(
            scores, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.route_eps,
        )
        routed, counts = held_expert_ffn(
            self, tokens, choice, weights, self.activation,
            self.down_init_scale,
        )
        routed = routed.reshape(B, S, H)
        if self.shared_width:
            shared = (
                PlainMLP(cfg, self.shared_width, self.activation,
                         self.down_init_scale, name="shared_experts")
                if self.activation in PLAIN_ACTIVATIONS
                else SwiGLU(cfg, self.shared_width, name="shared_experts")
            )
            routed = routed + shared(x).astype(jnp.float32)
        load = expert_load(choice, E)
        y = routed.astype(cfg.dtype)
        if self.biased:
            y = with_load_cotangent(y, bias, load)
        return y, dict(counts, scores=scores, choice=choice, load=load)


class RoutedGLU(nn.Module):
    """Σ_{e in C} w_e GLU_e(x) over the HELD experts (``activation``: the
    gate's, "relu" for SmallThinker, "silu" for SDAR), with C and w — the
    top k and a softmax over the chosen ones — from the router LOGITS of
    ``router_input`` (SmallThinker: the layer's normalised input, from
    before attention); no bias, no scale, no shared expert. Returns
    (y, routing): the logits [T, E] (as ``scores``), choice [T, k], load [E]
    and ``parallel/moe.routed_experts``' counts."""

    cfg: Any
    activation: str = "relu"

    @nn.compact
    def __call__(self, x, router_input):
        cfg = self.cfg
        B, S, H = x.shape
        E = cfg.num_experts
        router = self.param(
            "router", nn.initializers.normal(cfg.initializer_range), (H, E),
            jnp.float32,
        )
        # the router in float32 at full precision: the top-k is discrete
        logits = jnp.dot(
            router_input.reshape(B * S, H).astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST,
        )
        choice, weights = route_top_k_softmax(logits, cfg.num_experts_per_tok)
        routed, counts = held_expert_ffn(
            self, x.reshape(B * S, H), choice, weights, self.activation
        )
        return routed.reshape(B, S, H).astype(cfg.dtype), dict(
            counts, scores=logits, choice=choice, load=expert_load(choice, E)
        )


def period_of(kinds: Sequence) -> int:
    """The shortest period a stack's layer kinds repeat with."""
    for period in range(1, len(kinds) + 1):
        if all(a == b for a, b in zip(kinds, kinds[period:])):
            return period
    return max(len(kinds), 1)


class _Period(nn.Module):
    """Scan body: one period of the pattern, a layer per position
    (``layer_{i}``, leaves of its own). carry = hidden; rope broadcast;
    per-step out = the period's routing, stacked."""

    layer: Callable[..., nn.Module]
    kinds: Tuple[tuple, ...]

    @nn.compact
    def __call__(self, hidden, rope):
        routings = []
        for i, kind in enumerate(self.kinds):
            hidden, routing = self.layer(*kind, name=f"layer_{i}")(
                hidden, rope
            )
            routings.append(routing)
        return hidden, jax.tree.map(lambda *xs: jnp.stack(xs), *routings)


class ScannedBlock(nn.Module):
    """Scan body of a stack of ONE kind, a layer a step, named ``block``:
    carry = hidden; rope broadcast; per-step out = the layer's second
    output (its routing, or None)."""

    layer: Callable[..., nn.Module]

    @nn.compact
    def __call__(self, hidden, rope):
        return self.layer(name="block")(hidden, rope)


def scan_layers(body, length: int):
    """``body`` under ``nn.scan``, ``length`` steps: carry = hidden; rope
    broadcast; every parameter (and gradient sink, and compute-dtype copy)
    of a step stacked on axis 0."""
    return nn.scan(
        body, variable_axes={"params": 0, GRAD_SINKS: 0, COMPUTE_COPIES: 0},
        split_rngs={"params": True}, in_axes=nn.broadcast, length=length,
    )


def scan_periods(layer: Callable[..., nn.Module], kinds: Sequence[tuple],
                 period: int, hidden, rope):
    """A stack of routed layers, from inside the model's compact method:
    ``layer(*kind, name=...)`` makes the (remat'd) layer of one kind, called
    as ``(hidden, rope) -> (hidden, routing)``. Whole PERIODS of ``period``
    kinds run under ONE ``nn.scan`` (``layers``: every parameter stacked over
    the periods, one leaf per position in the period); what is left over
    after the last is unrolled (``tail_layer_{i}``). Returns (hidden, routing
    with every entry stacked over the layers in order)."""
    periods = len(kinds) // period
    routings = []
    if periods:
        hidden, routing = scan_layers(_Period, periods)(
            layer, tuple(kinds[:period]), name="layers"
        )(hidden, rope)
        # [periods, period, ...] -> [layers, ...]
        routings.append(jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), routing
        ))
    for i, kind in enumerate(kinds[periods * period:]):
        hidden, routing = layer(*kind, name=f"tail_layer_{i}")(hidden, rope)
        routings.append(jax.tree.map(lambda x: x[None], routing))
    return hidden, jax.tree.map(lambda *xs: jnp.concatenate(xs), *routings)


def chunked_cross_entropy(hiddens, lm_head, labels, chunk_tokens: int):
    """Per-token CE of every pass, [T, N] float32, from hiddens [T, N, H],
    the head [H, V] (already in the compute dtype) and labels [N]: one
    (pass, chunk) of logits at a time, under remat — the backward replays
    the chunk's matmul instead of keeping [T, N, V]."""
    T, N, H = hiddens.shape
    chunk = min(chunk_tokens, N)
    if N % chunk:
        raise ValueError(
            f"loss_chunk_tokens ({chunk_tokens}) must divide the "
            f"micro-batch's tokens ({N})"
        )

    @jax.checkpoint
    def one(h, y):  # [chunk, H], [chunk] -> [chunk]
        logits = jnp.dot(h, lm_head, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return lse - picked

    h = hiddens.reshape(T * (N // chunk), chunk, H)
    y = jnp.broadcast_to(
        labels.reshape(1, N // chunk, chunk), (T, N // chunk, chunk)
    ).reshape(T * (N // chunk), chunk)
    return jax.lax.map(lambda hy: one(*hy), (h, y)).reshape(T, N)


def apply_with_grad_sinks(model, params, input_ids, grad_sinks,
                          compute_copies=None, **inputs):
    """``model.apply`` on ``params``, with ``grad_sinks`` (None, or the
    subtree of a float32 gradient accumulator that ``routed_grad_sink_mask``
    marks) riding beside them as the collection ``GRAD_SINKS`` and
    ``compute_copies`` (None, or the same subtree of ``params`` in the
    compute dtype) as ``COMPUTE_COPIES``; ``inputs``: the model's further
    keyword inputs (a batch's position streams)."""
    variables = {"params": params}
    if grad_sinks is not None:
        variables[GRAD_SINKS] = grad_sinks
    if compute_copies is not None:
        variables[COMPUTE_COPIES] = compute_copies
    return model.apply(variables, input_ids, **inputs)


def routed_metrics(routing, params, gauges: Dict[str, Callable]):
    """The routing gauges of ``docs/observability.md`` from a stack's
    ``routing`` [layers, ...], a family's own ``gauges`` (name -> function
    of ``params`` and ``routing``) among them, and the micro-batch's routing
    as the step
    itself computed it (``moe.choice`` [L, T, k], ``moe.scores`` [L, T, E]:
    what a check routes its reference by and compares; 8 MB at the published
    sizes, summed by nothing)."""
    load = routing["load"]  # [L, E]
    return {
        "moe.load_max_over_mean": jnp.max(load, axis=1) / jnp.mean(
            load, axis=1
        ),
        "moe.local_slot_share": jnp.mean(routing["local_slot_share"]),
        "moe.bulk_row_share": jnp.mean(routing["bulk_row_share"]),
        **{name: gauge(params, routing) for name, gauge in gauges.items()},
        "moe.dropped_slots": jnp.sum(routing["dropped_slots"]),
        "moe.grad_sink_leaves": jnp.sum(routing["grad_sink_leaves"]),
        "moe.compute_copy_leaves": jnp.sum(routing["compute_copy_leaves"]),
        "moe.choice": routing["choice"],
        "moe.scores": routing["scores"],
    }


def expert_lm_loss(model, params, batch: Dict[str, jnp.ndarray], grad_sinks,
                   head: Callable, gauges: Dict[str, Callable],
                   compute_copies=None):
    """(loss, metrics) of one micro-batch of an expert decoder under the
    plain next-token objective: ``input_ids`` and next-token ``labels``,
    [B, S] each, no padding; the mean cross-entropy under ``head(params)``
    ([H, V], in the compute dtype) a chunk of tokens at a time, beside
    ``routed_metrics``. ``grad_sinks``, ``compute_copies``:
    ``apply_with_grad_sinks``'s; differentiated with respect to the sinks
    too, their cotangent is ``sink + gradient`` of the leaf of that name,
    whose own gradient is then zero."""
    cfg = model.cfg
    hidden, routing = apply_with_grad_sinks(
        model, params, batch["input_ids"], grad_sinks, compute_copies
    )
    ce = chunked_cross_entropy(
        hidden.reshape(1, -1, cfg.hidden_size), head(params),
        batch["labels"].reshape(-1), cfg.loss_chunk_tokens,
    )
    loss = jnp.mean(ce)
    return loss, {"loss": loss, **routed_metrics(routing, params, gauges)}


def _leaves_named(params, names, among: bool):
    return jax.tree_util.tree_map_with_path(
        lambda path, _: (path[-1].key in names) == among, params
    )


def weight_decay_mask(params, exempt: Tuple[str, ...] = ("weight",)):
    """True where weight decay applies: every matrix; not the leaves named
    in ``exempt`` (the RMSNorm ``weight``s, a model's biases)."""
    return _leaves_named(params, exempt, False)


def sign_step_mask(params):
    """True for the leaves stepped by the sign of their (load) cotangent:
    the expert layers' correction biases."""
    return _leaves_named(params, (BIAS,), True)


def routed_grad_sink_mask(params):
    """True for the leaves whose gradient the routed loop can add into an
    accumulator in place: the held experts' matrices."""
    return _leaves_named(params, EXPERT_LEAVES, True)
