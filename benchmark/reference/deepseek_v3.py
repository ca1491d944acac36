"""kanana-2-30b-a3b (``model_type: deepseek_v3``, ``q_lora_rank: null``)
training loss in plain ``jax.numpy``, float32: no kernels, no tile loop, no
chunked head.

    x [S, 2048]; pre-norm decoder:  h = x + Attn(RMSNorm(x));
                                    y = h + FFN(RMSNorm(h));  eps 1e-6;
                                    final RMSNorm; untied head
    Attn(u):  q = W_q u            -> [S, 32, 192] = (q_nope 128 | q_rope 64)
              c = W_kva u          -> [S, 512 + 64] = (c_kv 512 | k_rope 64,
                                      ONE head shared by all 32)
              (k_nope | v) = W_kvb RMSNorm(c_kv) -> [S, 32, 128 + 128]
              RoPE(theta 1e6, 64 dims, rope_interleave: pairs (2i, 2i+1)) on
              q_rope and k_rope
              q = (q_nope | q_rope), k = (k_nope | k_rope broadcast over
              heads): 192 wide; v: 128 wide
              out = W_o softmax_causal(q kᵀ / sqrt(192)) v   (no yarn:
              rope_scaling null)
    FFN, layer 0 (first_k_dense_replace 1):
              W_down(silu(W_gate u) * W_up u), width 6144
    FFN, layers 1..: s = sigmoid(W_r u), [128];  choice = top6(s + b)
              (n_group 1, topk_group 1: no group limit)
              w = s[choice] / (sum s[choice] + 1e-20) * 2.448
              FFN(u) = sum_{e in choice} w_e * SwiGLU_e(u)  (width 768)
                       + SwiGLU_shared(u)  (width 2 * 768)
    loss: mean next-token cross-entropy; no auxiliary loss (noaux_tc)
    b (e_score_correction_bias, [128] a layer, starts at 0): after every
              GLOBAL step, b_e <- b_e - gamma * sign(load_e - mean load),
              load_e = share of the step's routed (token, slot) pairs that
              chose e, over all peers' samples; gamma 0.001

It reads the parameter tree the program trains (names as Flax lays them out:
the expert layers' weights stacked on axis 0, the held experts' matrices
stacked on the next) and imports nothing from ``dedloc_tpu``. It is given the
same SHARE the program holds: ``held = (first, count)`` — the sum over the
chosen experts runs over the held ones, what an absent expert would have
added is left out — and the same vocabulary slice (the tree's own rows).

Departures from a textbook forward, each for a stated reason:

- the expert layers are a ``lax.scan`` over the stacked weights; the experts
  a Python loop over the HELD ones, each applied to every token and masked
  by the token's weight for it (dense: no sort, no gather);
- ``choices`` ([L, T, k]): route by THESE choices instead of the reference's
  own top-k. The top-k is discrete: a near-tie between the 6th and 7th score
  flips under bf16 rounding and a flipped slot changes its token's gradient
  wholesale, so a comparison of gradients routes the reference as the
  program routed; the scores (continuous) and the share of agreeing choices
  are compared on their own;
- with ``checkpoint=True`` every layer, every block of 8 heads of the dense
  attention and the head run under ``jax.checkpoint`` — at the published
  widths and S=4,096 one layer's float32 scores are 2.1 GB; values are
  unchanged.

``dtype`` exists to read what a lower precision does (see ``forward``);
``bias_in_choice`` and ``scale`` exist so a test can show that a reference
without the bias in the choice, or without the 2.448, is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"
HEAD_BLOCK = 8  # heads of dense attention computed at a time


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def _rope_interleaved(x, theta):
    """x [B, S, H, D]: pairs (2i, 2i+1) rotated by position · theta^(-2i/D)."""
    _b, s, _h, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = (
        jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    )[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1,
    ).reshape(x.shape)


def _swiglu(x, p):
    return (
        jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
    ) @ p["down_proj"]["kernel"]


def _causal_attention(q, k, v):
    """softmax_causal(q kᵀ / sqrt(D_qk)) v for a block of heads."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention(hidden, p, *, num_heads, nope, rope, rank, eps, theta,
              checkpoint=False):
    b, s, _ = hidden.shape
    q = (hidden @ p["q_proj"]["kernel"]).reshape(b, s, num_heads, nope + rope)
    latent = hidden @ p["kv_a_proj_with_mqa"]["kernel"]
    kv = (
        _rms_norm(latent[..., :rank], p["kv_a_layernorm"], eps)
        @ p["kv_b_proj"]["kernel"]
    ).reshape(b, s, num_heads, -1)
    k_rope = _rope_interleaved(latent[..., rank:][:, :, None, :], theta)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], theta)], axis=-1
    )
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, num_heads, rope))],
        axis=-1,
    )
    v = kv[..., nope:]
    block = jax.checkpoint(_causal_attention) if checkpoint else (
        _causal_attention
    )
    ctx = jnp.concatenate([
        block(q[:, :, h:h + HEAD_BLOCK], k[:, :, h:h + HEAD_BLOCK],
              v[:, :, h:h + HEAD_BLOCK])
        for h in range(0, num_heads, HEAD_BLOCK)
    ], axis=2)
    return ctx.reshape(b, s, -1) @ p["o_proj"]["kernel"]


def route(scores, bias, top_k, scale, bias_in_choice=True, choice=None):
    """(choice [T, k], weights [T, k]) of sigmoid scores [T, E]."""
    if choice is None:
        _, choice = jax.lax.top_k(
            scores + (bias if bias_in_choice else 0.0), top_k
        )
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20
    ) * scale


def moe_ffn(x, p, *, held, top_k, scale, bias_in_choice=True, choice=None):
    """x [T, H] -> dict: ``routed`` (the HELD experts' part of the sum over
    the chosen experts), ``shared``, ``scores`` [T, E], ``choice`` [T, k],
    ``load`` [E] (each expert's share of the T·k routed pairs)."""
    first, count = held
    scores = jax.nn.sigmoid(x @ p["router"])
    choice, weights = route(
        scores, p[BIAS], top_k, scale, bias_in_choice, choice
    )
    routed = jnp.zeros_like(x)
    for i in range(count):
        mine = jnp.sum(jnp.where(choice == first + i, weights, 0.0), axis=-1)
        expert = (
            jax.nn.silu(x @ p["experts_gate"][i]) * (x @ p["experts_up"][i])
        ) @ p["experts_down"][i]
        routed = routed + mine[:, None] * expert
    experts = scores.shape[-1]
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), experts, dtype=jnp.float32), axis=0
    ) / choice.size
    return {"routed": routed, "shared": _swiglu(x, p["shared_experts"]),
            "scores": scores, "choice": choice, "load": load}


def _head(hidden, lm_head, labels):
    log_probs = jax.nn.log_softmax(hidden @ lm_head, axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def forward(params, batch, *, num_heads, nope, rope, rank, eps, theta, top_k,
            scale, held, choices=None, bias_in_choice=True, checkpoint=False,
            dtype=jnp.float32):
    """-> dict: ``loss``, ``ce`` [B, S], ``scores`` [L, T, E], ``choice``
    [L, T, k], ``load_excess`` [L, E] (load − mean load: what the bias rule
    takes the sign of). ``dtype``: float32, the reference; bfloat16 turns
    every weight, activation, accumulation and the softmax into bf16 — the
    reading of what a precision BELOW the cell's (bf16 operands, float32
    accumulation and softmax) does to loss and gradients."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    att = dict(num_heads=num_heads, nope=nope, rope=rope, rank=rank, eps=eps,
               theta=theta, checkpoint=checkpoint)

    def dense_layer(hidden, p):
        hidden = hidden + attention(
            _rms_norm(hidden, p["input_layernorm"], eps), p["self_attn"], **att
        )
        return hidden + _swiglu(
            _rms_norm(hidden, p["post_attention_layernorm"], eps), p["mlp"]
        )

    def expert_layer(hidden, scanned):
        p, choice = scanned
        hidden = hidden + attention(
            _rms_norm(hidden, p["input_layernorm"], eps), p["self_attn"], **att
        )
        b, s, h = hidden.shape
        out = moe_ffn(
            _rms_norm(hidden, p["post_attention_layernorm"], eps).reshape(
                b * s, h
            ),
            p["mlp"], held=held, top_k=top_k, scale=scale,
            bias_in_choice=bias_in_choice, choice=choice,
        )
        hidden = hidden + (out["routed"] + out["shared"]).reshape(b, s, h)
        return hidden, (out["scores"], out["choice"], out["load"])

    head = _head
    if checkpoint:
        dense_layer, expert_layer, head = (
            jax.checkpoint(f) for f in (dense_layer, expert_layer, head)
        )

    hidden = params["embed_tokens"][batch["input_ids"]]
    i = 0
    while f"dense_layer_{i}" in params:
        hidden = dense_layer(hidden, params[f"dense_layer_{i}"])
        i += 1
    stacked = params["layers"]["block"]
    if choices is None:
        layers = jax.tree.leaves(stacked)[0].shape[0]
        # the scan needs an array per layer: -1 = "choose for yourself"
        own = jnp.full((layers, 1, top_k), -1, jnp.int32)
        hidden, (scores, choice, load) = jax.lax.scan(
            lambda h, pc: expert_layer(h, (pc[0], None)), hidden,
            (stacked, own),
        )
    else:
        hidden, (scores, choice, load) = jax.lax.scan(
            expert_layer, hidden, (stacked, choices)
        )
    ce = head(
        _rms_norm(hidden, params["norm"], eps), params["lm_head"],
        batch["labels"],
    )
    return {
        "loss": jnp.mean(ce), "ce": ce, "scores": scores, "choice": choice,
        "load_excess": load - jnp.mean(load, axis=-1, keepdims=True),
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
