"""Ouro (arXiv 2510.25741; ``ByteDance/Ouro-2.6B``) training loss in plain
``jax.numpy``, float32: no kernels, no chunked head, no remat by default.

    h⁰ = E[x]
    one pass, for each of the L layers (their weights stacked on axis 0):
        a  = h + RMSNorm₂(Attn(RMSNorm₁(h)))
        h' = a + RMSNorm₄(MLP(RMSNorm₃(a)))          (sandwich norms)
      Attn: q, k, v, o projections without bias, rotate-half RoPE on q and k,
            causal softmax at scale D^-½;  MLP: W_down(silu(W_gate x) ⊙ W_up x)
    hᵗ = RMSNorm_f(Stack(hᵗ⁻¹))     — the final norm closes EVERY pass and its
                                       output feeds the next
    logitsᵗ = hᵗ W_out (untied);  λₜ = σ(hᵗ·w_g + b_g)
    p₁ = λ₁;  pₜ = λₜ ∏_{j<t}(1−λⱼ), t < T;  p_T = ∏_{j<T}(1−λⱼ)
    loss = mean over tokens of Σₜ pₜ·CE(logitsᵗ, next token) − β·H(p),
           H(p) = −Σₜ pₜ log pₜ

It reads the parameter tree the program trains (names as Flax lays them out:
the L layers' weights stacked on axis 0) and imports nothing from
``dedloc_tpu``. Departures from a textbook forward, each for a stated reason:

- the layers of a pass are a ``lax.scan`` over the stacked weights (the four
  passes are a Python loop), so the compiled reference holds one layer body
  per pass, not L;
- with ``checkpoint=True`` every layer and every pass's head run under
  ``jax.checkpoint`` — at the published widths and S=4,096 the float32
  activations of one row (1 GB of scores per layer and pass, 0.8 GB of logits
  per pass) would otherwise outgrow the chip; values are unchanged.

``passes`` and ``final_norm_inside`` exist so a test can show that a reference
one pass short, or with the final norm outside the loop, is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def _rope(x, theta):
    """x [B, S, H, D] -> x·cos + rotate_half(x)·sin."""
    _b, s, _h, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _layer(hidden, p, num_heads, eps, theta):
    b, s, _ = hidden.shape
    att = p["self_attn"]
    x = _rms_norm(hidden, p["input_layernorm"], eps)
    q, k, v = (
        (x @ att[name]["kernel"]).reshape(b, s, num_heads, -1)
        for name in ("q_proj", "k_proj", "v_proj")
    )
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    attn = ctx.reshape(b, s, -1) @ att["o_proj"]["kernel"]
    hidden = hidden + _rms_norm(attn, p["input_layernorm_2"], eps)
    x = _rms_norm(hidden, p["post_attention_layernorm"], eps)
    mlp = (
        jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
    ) @ p["down_proj"]["kernel"]
    return hidden + _rms_norm(mlp, p["post_attention_layernorm_2"], eps)


def _head(hidden, lm_head, labels):
    """(logits [B, S, V], per-token cross-entropy [B, S])."""
    logits = hidden @ lm_head
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    return logits, -jnp.take_along_axis(
        log_probs, labels[..., None], axis=-1
    )[..., 0]


def forward(params, batch, *, num_heads, eps, theta, passes, beta,
            checkpoint=False, final_norm_inside=True):
    """-> dict: ``loss``, ``logits`` [T, B, S, V], ``ce`` [T, B, S], ``p``
    [T, B, S] (the exit distribution), ``entropy`` [B, S]."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    model = params["model"]
    head = jax.checkpoint(_head) if checkpoint else _head

    def layer(hidden, p):
        return _layer(hidden, p, num_heads, eps, theta), None

    if checkpoint:
        layer = jax.checkpoint(layer)

    hidden = params["embed_tokens"][batch["input_ids"]]
    logits, ce, lam = [], [], []
    for _t in range(passes):
        hidden, _ = jax.lax.scan(layer, hidden, model["layers"]["block"])
        out = _rms_norm(hidden, model["norm"], eps)
        if final_norm_inside:
            hidden = out
        pass_logits, pass_ce = head(out, params["lm_head"], batch["labels"])
        gate = model["early_exit_gate"]
        lam.append(jax.nn.sigmoid(
            (out @ gate["kernel"])[..., 0] + gate["bias"][0]
        ))
        logits.append(pass_logits)
        ce.append(pass_ce)

    p, stay = [], jnp.ones_like(lam[0])
    for t in range(passes):
        p.append(stay * lam[t] if t < passes - 1 else stay)
        stay = stay * (1.0 - lam[t])
    p, ce = jnp.stack(p), jnp.stack(ce)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    loss = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
    return {"loss": loss, "logits": jnp.stack(logits), "ce": ce, "p": p,
            "entropy": entropy}


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
