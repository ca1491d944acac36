"""LFM2-24B-A2B (``model_type: lfm2_moe``) training loss in plain
``jax.numpy``, float32: no kernels, no tile loop, no chunked head.

    x [S, 2048]; eps 1e-5; decoder layer:
              h = x + mixer(operator_norm(x));  out = h + ffn(ffn_norm(h))
              after the stack a final RMSNorm, then the head = the embedding
              transposed (tied)
    mixer ``conv`` (30 of 40 layers):
              (B | C | u) = in_proj(x)         2048 -> 6144, no bias
              z = B * u
              c_t = w0 * z_{t-2} + w1 * z_{t-1} + w2 * z_t   per channel,
                    causal, depthwise, zeros before the row's start
              y = out_proj(C * c)              no activation anywhere
    mixer ``full_attention`` (layers 2, 6, ..., 38):
              q = q_proj(x) [S, 32, 64];  k, v = k_proj(x), v_proj(x)
              [S, 8, 64]; RMSNorm over each head's 64 lanes of q and of k
              (one weight vector for q, one for k), THEN RoPE (theta 1e6,
              rotate-half); causal softmax attention at scale 1/8, each kv
              head serving 4 query heads; out_proj 2048 -> 2048
    ffn, the first ``num_dense_layers`` layers: w2(silu(w1 x) * w3 x), 11776
    ffn, the others:  s = sigmoid(gate(x)), [64];  choice = top4(s + bias)
              w = s[choice] / (sum s[choice] + 1e-6) * 1
              ffn(x) = sum_{e in choice} w_e * SwiGLU_e(x)   (width 1536;
              dropless; no shared expert)
    loss: mean next-token cross-entropy; no auxiliary loss
    bias (starts at 0, [64] a layer): after every GLOBAL step,
              bias_e <- bias_e - 0.001 * sign(load_e - mean load)

It reads the parameter tree the program trains (names as Flax lays them out:
``dense_layer_<i>``, the scanned periods under ``layers`` with one entry
``layer_<k>`` per position in the period and every leaf stacked over the
periods, ``tail_layer_<i>``; a layer is a conv layer if it has ``conv`` and
an attention layer if it has ``self_attn``) and imports nothing from
``dedloc_tpu``. It is given the same SHARE the program holds: ``held =
(first, count)`` — the sum over the chosen experts runs over the held ones,
what an absent expert would have added is left out — and the same vocabulary
slice (the tree's own rows).

Departures from a textbook forward, each for a stated reason:

- the experts are a Python loop over the HELD ones, each applied to every
  token and masked by the token's weight for it (dense: no sort, no gather);
- ``choices`` ([L, T, k], the expert layers in order): route by THESE
  choices instead of the reference's own top-k — the top-k is discrete, a
  near-tie flips under bf16 rounding and a flipped slot changes its token's
  gradient wholesale, so a comparison of gradients routes the reference as
  the program routed; scores and choices are compared on their own;
- with ``checkpoint=True`` every layer, every block of 8 query heads of the
  dense attention and the head run under ``jax.checkpoint`` — at S=4,096 one
  layer's float32 scores are 2.1 GB; values are unchanged.

``dtype`` exists to read what a lower precision does (see ``forward``);
``bias_in_choice``, ``qk_norm``, ``gates_swapped`` and ``causal_conv`` exist so
a test can show that a reference WITHOUT each of these is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"
HEAD_BLOCK = 8  # query heads of dense attention computed at a time


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def _rope(x, theta):
    """x [B, S, H, D]: x * cos + rotate_half(x) * sin, position t and pair
    (i, i + D/2) at angle t * theta^(-2i/D)."""
    _b, s, _h, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (
        x * jnp.cos(angles).astype(x.dtype)
        + rotated * jnp.sin(angles).astype(x.dtype)
    )


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def short_conv(x, p, *, gates_swapped=False, causal=True):
    """out_proj(C * conv3(B * u)) with (B | C | u) = in_proj(x)."""
    b, c, u = jnp.split(x @ p["in_proj"]["kernel"], 3, axis=-1)
    if gates_swapped:
        b, c = c, b
    z = b * u
    w = p["conv"].astype(z.dtype)  # [H, 3]: w[:, 2] at the current position
    seq = z.shape[1]
    if causal:
        pad = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))
    else:  # the same taps centred: position t sees t + 1
        pad = jnp.pad(z, ((0, 0), (1, 1), (0, 0)))
    conv = sum(w[:, k] * pad[:, k:k + seq] for k in range(3))
    return (c * conv) @ p["out_proj"]["kernel"]


def _causal_attention(q, k, v):
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention(x, p, *, num_heads, kv_heads, eps, theta, qk_norm=True,
              checkpoint=False):
    b, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, num_heads, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    if qk_norm:
        q = _rms_norm(q, p["q_layernorm"], eps)
        k = _rms_norm(k, p["k_layernorm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    # each kv head serves num_heads / kv_heads adjacent query heads
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    block = jax.checkpoint(_causal_attention) if checkpoint else (
        _causal_attention
    )
    ctx = jnp.concatenate([
        block(q[:, :, h:h + HEAD_BLOCK], k[:, :, h:h + HEAD_BLOCK],
              v[:, :, h:h + HEAD_BLOCK])
        for h in range(0, num_heads, HEAD_BLOCK)
    ], axis=2)
    return ctx.reshape(b, s, -1) @ p["out_proj"]["kernel"]


def route(scores, bias, top_k, scale, route_eps, bias_in_choice=True,
          choice=None):
    """(choice [T, k], weights [T, k]) of sigmoid scores [T, E]."""
    if choice is None:
        _, choice = jax.lax.top_k(
            scores + (bias if bias_in_choice else 0.0), top_k
        )
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + route_eps
    ) * scale


def moe_ffn(x, p, *, held, top_k, scale, route_eps, bias_in_choice=True,
            choice=None):
    """x [T, H] -> dict: ``routed`` (the HELD experts' part of the sum over
    the chosen experts), ``scores`` [T, E], ``choice`` [T, k], ``load`` [E]
    (each expert's share of the T·k routed pairs)."""
    first, count = held
    scores = jax.nn.sigmoid(x @ p["router"])
    choice, weights = route(
        scores, p[BIAS], top_k, scale, route_eps, bias_in_choice, choice
    )
    routed = jnp.zeros_like(x)
    for i in range(count):
        mine = jnp.sum(jnp.where(choice == first + i, weights, 0.0), axis=-1)
        routed = routed + mine[:, None].astype(x.dtype) * _swiglu(
            x, p["experts_gate"][i], p["experts_up"][i], p["experts_down"][i]
        )
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), scores.shape[-1],
                       dtype=jnp.float32), axis=0,
    ) / choice.size
    return {"routed": routed, "scores": scores, "choice": choice,
            "load": load}


def _head(hidden, embedding, labels):
    log_probs = jax.nn.log_softmax(hidden @ embedding.T, axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def layers_in_order(params):
    """The parameter trees of the layers as the model applies them."""
    out = []
    i = 0
    while f"dense_layer_{i}" in params:
        out.append(params[f"dense_layer_{i}"])
        i += 1
    if "layers" in params:
        period = params["layers"]
        positions = sorted(period, key=lambda name: int(name.split("_")[-1]))
        periods = jax.tree.leaves(period)[0].shape[0]
        for n in range(periods):
            out += [
                jax.tree.map(lambda x: x[n], period[name])
                for name in positions
            ]
    i = 0
    while f"tail_layer_{i}" in params:
        out.append(params[f"tail_layer_{i}"])
        i += 1
    return out


def forward(params, batch, *, num_heads, kv_heads, eps, theta, top_k, scale,
            route_eps, held, choices=None, bias_in_choice=True, qk_norm=True,
            gates_swapped=False, causal_conv=True, checkpoint=False,
            dtype=jnp.float32):
    """-> dict: ``loss``, ``ce`` [B, S], ``scores`` [L, T, E], ``choice``
    [L, T, k], ``load_excess`` [L, E] (load − mean load: what the bias rule
    takes the sign of), ``routed`` (each expert layer's routed output, [L,
    T, H]: what the shares of a deployment add up to). ``dtype``: float32,
    the reference; bfloat16 turns every weight, activation, accumulation
    and the softmax into bf16 — the reading of what a precision BELOW the
    cell's (bf16 operands, float32 accumulation and softmax) does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)

    def layer(hidden, p, choice):
        x = _rms_norm(hidden, p["operator_norm"], eps)
        if "conv" in p:
            hidden = hidden + short_conv(
                x, p["conv"], gates_swapped=gates_swapped, causal=causal_conv
            )
        else:
            hidden = hidden + attention(
                x, p["self_attn"], num_heads=num_heads, kv_heads=kv_heads,
                eps=eps, theta=theta, qk_norm=qk_norm, checkpoint=checkpoint,
            )
        x = _rms_norm(hidden, p["ffn_norm"], eps)
        ffn = p["feed_forward"]
        if "router" not in ffn:
            return hidden + _swiglu(
                x, ffn["gate_proj"]["kernel"], ffn["up_proj"]["kernel"],
                ffn["down_proj"]["kernel"],
            ), None
        b, s, h = hidden.shape
        out = moe_ffn(
            x.reshape(b * s, h), ffn, held=held, top_k=top_k, scale=scale,
            route_eps=route_eps, bias_in_choice=bias_in_choice, choice=choice,
        )
        return hidden + out["routed"].reshape(b, s, h), out

    head = _head
    if checkpoint:
        layer, head = jax.checkpoint(layer), jax.checkpoint(head)

    hidden = params["embed_tokens"][batch["input_ids"]]
    routings = []
    for p in layers_in_order(params):
        sparse = "router" in p["feed_forward"]
        choice = (
            choices[len(routings)] if sparse and choices is not None else None
        )
        hidden, out = layer(hidden, p, choice)
        if sparse:
            routings.append(out)
    ce = head(
        _rms_norm(hidden, params["norm"], eps), params["embed_tokens"],
        batch["labels"],
    )
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    }
    load = stacked["load"]
    return {
        "loss": jnp.mean(ce), "ce": ce, "scores": stacked["scores"],
        "choice": stacked["choice"], "routed": stacked["routed"],
        "load_excess": load - jnp.mean(load, axis=-1, keepdims=True),
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
