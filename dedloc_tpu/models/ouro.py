"""Ouro (ByteDance, arXiv 2510.25741): a looped decoder, in Flax.

A decoder stack of L layers applied ``total_ut_steps`` times with the SAME
weights, an exit gate after every pass. It is ALBERT's idea — depth bought
with compute, not parameters, which is why DeDLOC's wire likes it — in a
2025 block: RMSNorm in sandwich position, RoPE, SwiGLU, no biases, untied
embeddings.

    h⁰ = E[x];  one pass, for each layer:
        a  = h + RMSNorm₂(Attn(RMSNorm₁(h)))
        h' = a + RMSNorm₄(MLP(RMSNorm₃(a)))
    hᵗ = RMSNorm_f(Stack(hᵗ⁻¹))        (the final norm closes EVERY pass)
    logitsᵗ = W_out hᵗ;   λₜ = σ(w_g·hᵗ + b_g)
    p₁ = λ₁, pₜ = λₜ ∏_{j<t}(1−λⱼ), p_T = ∏_{j<T}(1−λⱼ)
    loss = mean over tokens of Σₜ pₜ·CE(logitsᵗ, next token) − β·H(p)

The program's shape is ALBERT's twice over: an ``nn.scan`` over the L
layers (their parameters stacked on axis 0) inside an ``nn.scan`` over the
passes (parameters broadcast), the layer body under a remat policy from
``albert.remat_policy_object``'s table. The model returns the hidden state
of every pass, [T, B, S, H] in bf16 (134 MB at 8,192 tokens); the head and
its cross-entropy are NOT part of the scan: ``ouro_loss`` computes them one
(pass, token chunk) at a time under remat, so at most one chunk of the
[tokens, 49,152] float32 logits lives at a time, forward or backward.

TPU notes: matmuls in bf16 with float32 accumulation; norms, softmax, the
gate and the loss in float32; q/k/v leave their projections as [B, S, H·D]
and go to the flash kernels in that layout, named ``flash_qkv`` (RoPE is
applied before the name, so what a policy stashes is what the kernels
read); RMSNorm is XLA's.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.models.albert import remat_policy_object


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Ouro-2.6B as published (``config.json``); what it does not fix is
    in ``benchmark/configs/ouro_2p6b_s4096.json`` under ``assumed``."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    # the entropy bonus of the exit distribution (the paper's stage I)
    exit_entropy_beta: float = 0.05
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # the layer always runs under remat; the policy is a name from
    # albert.remat_policy_object's table. "kernel_outputs" keeps one layer
    # input per (pass, layer) plus the flash kernel's out + lse (what its
    # backward reads: 17 MB a layer iteration) and replays the rest of the
    # layer in the backward: at 350-400 M parameters the state leaves no
    # room for a q/k/v/FFN stash of 12-16 layer iterations
    remat_policy: str = "kernel_outputs"
    # "flash": the causal mode of ops/flash_attention.py; "dense": XLA's
    # materialized S² scores (tests, tiny models)
    attention_impl: str = "flash"
    attention_block_size: int = 512
    # tokens per chunk of the head + cross-entropy (see ``ouro_loss``)
    loss_chunk_tokens: int = 1024
    mesh: Any = None  # the slice mesh, for the flash kernels' shard_map

    @staticmethod
    def named(model_size: str):
        ctors = {"ouro_2p6b": OuroConfig.ouro_2p6b,
                 "ouro_tiny": OuroConfig.tiny}
        if model_size not in ctors:
            raise ValueError(
                f"unknown model_size {model_size!r} "
                f"(expected one of {sorted(ctors)})"
            )
        return ctors[model_size]

    @staticmethod
    def ouro_2p6b(**overrides) -> "OuroConfig":
        return OuroConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "OuroConfig":
        """Test-sized: every mechanism (two layers, three passes, RoPE, the
        gate, a chunked head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=2, head_dim=16,
            intermediate_size=48, max_position_embeddings=128,
            total_ut_steps=3, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return OuroConfig(**base)


def _dense(features: int, cfg: OuroConfig, name: str) -> nn.Dense:
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name,
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


def rope_tables(seq: int, head_dim: int, theta: float):
    """cos, sin [S, D] of rotate-half RoPE: the D/2 frequencies repeated
    over both halves."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x [B, S, H, D]: x·cos + rotate_half(x)·sin, in float32."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (
        x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]
    ).astype(x.dtype)


def swiglu(cfg, x, width: int):
    """down(silu(gate x) · up x) with ``gate_proj`` / ``up_proj`` /
    ``down_proj`` created in the CALLING module's scope (call it inside a
    compact method)."""
    # named for the remat policies that stash the FFN's matmul outputs
    gate = checkpoint_name(_dense(width, cfg, "gate_proj")(x), "ffn_up")
    up = checkpoint_name(_dense(width, cfg, "up_proj")(x), "ffn_up")
    return _dense(cfg.hidden_size, cfg, "down_proj")(nn.silu(gate) * up)


class SwiGLU(nn.Module):
    """``swiglu`` in a scope of its own (a decoder whose layer has more than
    one: a dense FFN, shared experts)."""

    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        return swiglu(self.cfg, x, self.width)


class OuroAttention(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        B, S, _ = hidden.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        cos, sin = rope
        q = _dense(H * D, cfg, "q_proj")(hidden).reshape(B, S, H, D)
        k = _dense(KV * D, cfg, "k_proj")(hidden).reshape(B, S, KV, D)
        v = _dense(KV * D, cfg, "v_proj")(hidden).reshape(B, S, KV, D)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        if cfg.attention_impl == "flash":
            from dedloc_tpu.ops.flash_attention import flash_attention

            ctx = flash_attention(
                q, k, v, causal=True, block_q=cfg.attention_block_size,
                block_k=cfg.attention_block_size, mesh=cfg.mesh,
            )
        elif cfg.attention_impl == "dense":
            q, k, v = (checkpoint_name(x, "flash_qkv") for x in (q, k, v))
            if KV != H:  # each kv head for its H / KV query heads
                k, v = (jnp.repeat(x, H // KV, axis=2) for x in (k, v))
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) / jnp.sqrt(jnp.float32(D))
            visible = jnp.tril(jnp.ones((S, S), bool))
            logits = jnp.where(visible[None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        else:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r}: an Ouro model takes "
                "'flash' or 'dense'"
            )
        return _dense(cfg.hidden_size, cfg, "o_proj")(
            ctx.reshape(B, S, H * D)
        )


class OuroLayer(nn.Module):
    """One decoder layer, sandwich-normed: a norm before AND after each
    sub-layer, the residual added after the second."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        attn = OuroAttention(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(hidden), rope
        )
        hidden = hidden + RMSNorm(cfg, name="input_layernorm_2")(attn)
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        mlp = swiglu(cfg, x, cfg.intermediate_size)
        return hidden + RMSNorm(cfg, name="post_attention_layernorm_2")(mlp)


class _ScannedLayer(nn.Module):
    """Inner scan body: carry = hidden; rope broadcast; no per-step out."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        layer_cls = nn.remat(
            OuroLayer, policy=remat_policy_object(self.cfg.remat_policy)
        )
        return layer_cls(self.cfg, name="block")(hidden, rope), None


class _Pass(nn.Module):
    """Outer scan body: the whole stack, the final norm, the exit gate.
    carry = hidden; per-step out = (this pass's hidden, its gate logits)."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        stack = nn.scan(
            _ScannedLayer,
            variable_axes={"params": 0},  # L distinct layers, stacked
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=cfg.num_hidden_layers,
        )
        hidden, _ = stack(cfg, name="layers")(hidden, rope)
        hidden = RMSNorm(cfg, name="norm")(hidden)
        gate = nn.Dense(
            1, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="early_exit_gate",
        )(hidden.astype(jnp.float32))[..., 0]
        return hidden, (hidden, gate)


class OuroForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hiddens [T, B, S, H] in the compute
    dtype, gate logits [T, B, S] float32). The head's weight is the
    parameter ``lm_head`` [H, V]; ``ouro_loss`` and ``ouro_logits`` apply
    it (a head inside the module would have to hand back [T, B, S, V])."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param(
            "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        # untied: declared here so the whole model is one parameter tree
        self.param(
            "lm_head", init, (cfg.hidden_size, cfg.vocab_size), jnp.float32
        )
        hidden = jnp.take(embed, input_ids, axis=0).astype(cfg.dtype)
        rope = rope_tables(input_ids.shape[1], cfg.head_dim, cfg.rope_theta)
        passes = nn.scan(
            _Pass,
            variable_broadcast="params",  # the SAME weights every pass
            split_rngs={"params": False},
            in_axes=nn.broadcast,
            length=cfg.total_ut_steps,
        )
        _, (hiddens, gates) = passes(cfg, name="model")(hidden, rope)
        return hiddens, gates


def ouro_logits(params, hiddens, cfg: OuroConfig):
    """[T, B, S, V] float32 logits of every pass, all at once: for tests
    and small models only."""
    return jnp.einsum(
        "tbsh,hv->tbsv", hiddens, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def exit_distribution(gate_logits):
    """[T, ...] gate logits -> (p [T, ...], log p): pₜ = λₜ ∏_{j<t}(1−λⱼ),
    the last pass takes what is left. In logs: log σ(x) and log(1−σ(x)) =
    log σ(−x) are exact where p underflows."""
    log_lam = jax.nn.log_sigmoid(gate_logits)
    log_stay = jax.nn.log_sigmoid(-gate_logits)
    before = jnp.cumsum(log_stay, axis=0) - log_stay  # Σ_{j<t} log(1−λⱼ)
    last = gate_logits.shape[0] - 1
    log_p = jnp.concatenate(
        [(log_lam + before)[:last], before[last:]], axis=0
    )
    return jnp.exp(log_p), log_p


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


def gated_loss(ce, gate_logits, beta: float):
    """The paper's stage-I objective from per-pass CE [T, N] and gate
    logits [T, N]: (loss, metrics with the per-pass means ``lm.loss`` and
    ``lm.exit_prob``, [T] each)."""
    p, log_p = exit_distribution(gate_logits)
    entropy = -jnp.sum(p * log_p, axis=0)  # [N], >= 0
    loss = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
    return loss, {
        "loss": loss,
        "lm.loss": jnp.mean(ce, axis=1),
        "lm.exit_prob": jnp.mean(p, axis=1),
        "exit_entropy": jnp.mean(entropy),
    }


def ouro_loss(model: OuroForCausalLM, params, batch: Dict[str, jnp.ndarray]):
    """(loss, metrics) of one micro-batch: ``input_ids`` and next-token
    ``labels``, [B, S] each, no padding."""
    cfg = model.cfg
    hiddens, gates = model.apply({"params": params}, batch["input_ids"])
    T = hiddens.shape[0]
    ce = chunked_cross_entropy(
        hiddens.reshape(T, -1, cfg.hidden_size),
        params["lm_head"].astype(cfg.dtype),
        batch["labels"].reshape(-1), cfg.loss_chunk_tokens,
    )
    return gated_loss(ce, gates.reshape(T, -1), cfg.exit_entropy_beta)


def ouro_weight_decay_mask(params):
    """True where weight decay applies: every matrix (projections, both
    embedding matrices, the gate's kernel); not the RMSNorm ``weight``s nor
    the gate's ``bias`` (the reference recipe's no_decay = bias + norm
    weights, ``optim.lamb.albert_weight_decay_mask``, in this model's
    names)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key not in ("weight", "bias"), params
    )


def ouro_train_tflops_per_sample(cfg: OuroConfig, seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted, causal attention at its triangle)."""
    h, i, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    width = cfg.num_attention_heads * d
    kv_width = cfg.num_key_value_heads * d
    per_token_layer = (
        2 * 2 * h * (width + kv_width)  # q, o; k, v
        + 2 * 3 * h * i  # gate, up, down
        + 2 * 2 * width * (seq + 1) / 2  # QKᵀ and PV over the triangle
    )
    per_token = cfg.total_ut_steps * (
        cfg.num_hidden_layers * per_token_layer + 2 * h * cfg.vocab_size
    )
    return 3.0 * per_token * seq / 1e12
