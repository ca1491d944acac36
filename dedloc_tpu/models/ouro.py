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
passes (parameters broadcast), the layer body under a remat policy of
``models/remat.py``'s table. The model returns the hidden state of every
pass, [T, B, S, H] in bf16 (134 MB at 8,192 tokens); the head and its
cross-entropy are NOT part of the scan: ``ouro_loss`` computes them one
(pass, token chunk) at a time under remat, so at most one chunk of the
[tokens, 49,152] float32 logits lives at a time, forward or backward. The
blocks (RMSNorm, RoPE, SwiGLU, grouped-query attention, the chunked head +
cross-entropy) and the TPU notes are ``models/decoder.py``'s.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    RMSNorm,
    ScannedBlock,
    Visibility,
    chunked_cross_entropy,
    embed_tokens,
    named_config,
    rope_tables,
    scan_layers,
    swiglu,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer


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
    # the layer always runs under remat; the policy is a name of
    # models/remat.py's table. "kernel_outputs" keeps one layer
    # input per (pass, layer) plus the flash kernel's out + lse (what its
    # backward reads: 17 MB a layer iteration) and replays the rest of the
    # layer in the backward: at 350-400 M parameters the state leaves no
    # room for a q/k/v/FFN stash of 12-16 layer iterations. The kernels'
    # operands are buffers of their own all the same
    # (decoder.GroupedQueryAttention's barrier: 0.14 GB LESS scratch),
    # replayed and not kept: "kernel_operands" would keep them, for 6.2 GB
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
        return named_config(model_size, {
            "ouro_2p6b": OuroConfig.ouro_2p6b,
            "ouro_tiny": OuroConfig.tiny,
        })

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


class OuroLayer(nn.Module):
    """One decoder layer, sandwich-normed: a norm before AND after each
    sub-layer, the residual added after the second. Returns (hidden, None):
    a scan step's carry and its (absent) per-step out."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        attn = GroupedQueryAttention(
            cfg, Visibility(causal=True), name="self_attn"
        )(RMSNorm(cfg, name="input_layernorm")(hidden), rope)
        hidden = hidden + RMSNorm(cfg, name="input_layernorm_2")(attn)
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        mlp = swiglu(cfg, x, cfg.intermediate_size)
        mlp = RMSNorm(cfg, name="post_attention_layernorm_2")(mlp)
        return hidden + mlp, None


class _Pass(nn.Module):
    """Outer scan body: the whole stack, the final norm, the exit gate.
    carry = hidden; per-step out = (this pass's hidden, its gate logits)."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        # L distinct layers, stacked, a layer a step
        hidden, _ = scan_layers(ScannedBlock, cfg.num_hidden_layers)(
            functools.partial(remat_layer, OuroLayer, cfg), name="layers"
        )(hidden, rope)
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
        hidden = embed_tokens(self, input_ids)
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


# decayed: every matrix (projections, both embeddings, the gate's kernel);
# not the RMSNorm ``weight``s nor the gate's ``bias`` — the reference recipe's
# no_decay (``optim.lamb.albert_weight_decay_mask``) in this model's names
ouro_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", "bias")
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
