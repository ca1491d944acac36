"""Kimi-Linear-48B-A3B (``model_type: kimi_linear``) training loss in plain
``jax.numpy``, float32: no kernels, no chunked rule, no tile loop, no chunked
head.

    x [S, 2304]; eps 1e-5; decoder layer, layers numbered from 1:
              h = x + mixer(input_layernorm(x))
              out = h + ffn(post_attention_layernorm(h))
    mixer ``kda`` (layers 1-3, 5-7, ...; heads of d = 128), u the normed input:
              q~, k~, v~ = W_q u, W_k u, W_v u
              q', k', v = SiLU(conv4(.)): causal, depthwise, 4 taps
                      (taps[:, 3] at the current position), zeros before
              q = q' / sqrt(|q'|² + 1e-6) · d^-1/2,  k = k' / sqrt(|k'|² + 1e-6)
              g = -exp(A_log) · softplus(W_fb (W_fa u) + dt_bias)  [heads, d]
              beta = sigmoid(W_b u)                                [heads]
              per head, S_0 = 0 [d, d] (key x value), TOKEN BY TOKEN:
                  S' = Diag(e^{g_t}) S_{t-1}
                  S_t = S' + beta_t k_t (v_t - S'ᵀ k_t)ᵀ
                  o_t = S_tᵀ q_t
              out = W_o [RMSNorm_d(o) · w_o ⊙ sigmoid(W_gb (W_ga u) + b_g)]
    mixer ``mla`` (layers 4, 8, ...): q = W_q u [S, heads, 128 + 64];
              (c_kv 512 | k_pe 64, ONE head) = W_kva u; (k_nope | v) =
              W_kvb RMSNorm(c_kv); k = (k_nope | k_pe for every head); NO
              rotary embedding; out = W_o softmax_causal(q kᵀ / sqrt(192)) v
    ffn, layer 1: W_down(silu(W_gate u) * W_up u), width 9216
    ffn, the others: s = sigmoid(W_r u) [256]; choice = top8(s + b);
              w = s[choice] / (sum s[choice] + 1e-20) * 2.446
              sum_{e in choice} w_e SwiGLU_e(u) (width 1024) + SwiGLU_shared(u)
    final RMSNorm; untied head; loss: mean next-token cross-entropy
    b: after every GLOBAL step b_e <- b_e - gamma sign(load_e - mean load)

It reads the parameter tree the program trains (names as Flax lays them out:
``dense_layer_<i>``, ``layers`` with one ``layer_<k>`` per position in the
period and every leaf stacked over the periods, ``tail_layer_<i>``; a layer is
a KDA layer if its mixer has ``A_log``) and imports nothing from
``dedloc_tpu``. It is given the same SHARE the program holds: the heads are
the tree's own (a mixer's projections exist for the held heads alone, and its
out-projection gives the mixer's PARTIAL sum, which is what joins the
stream), ``held = (first, count)`` the experts, the vocabulary slice the
tree's rows.

Departures from a textbook forward, each for a stated reason:

- THE RECURRENCE IS THE TOKEN-BY-TOKEN ONE, not the chunked algebra of
  ``ops/kda.py``: it shares nothing with what it checks. With
  ``checkpoint=True`` it runs a block of ``TOKEN_BLOCK`` tokens at a time
  under ``jax.checkpoint`` (8,192 states of 8 x 64 KB are 4.3 GB a layer
  unblocked), every layer and the head under one too, dense attention a
  block of ``HEAD_BLOCK`` query heads at a time; values are unchanged;
- ``choices`` ([L, T, k]): route by THESE choices instead of the
  reference's own top-k (the top-k is discrete: ``deepseek_v3.py``'s
  reason).

``dtype`` exists to read what a lower precision does (bfloat16: every
weight, activation, the log-decays, THE STATE and every accumulation);
``decay`` ("channel" as published, "head": a head's mean over its channels,
"none"), ``beta_sigmoid``, ``causal_conv``, ``gate_sigmoid``, ``k_norm`` and
``mla_rope_theta`` exist so a test can show that a reference without each
is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"
HEAD_BLOCK = 8  # heads of dense attention computed at a time
TOKEN_BLOCK = 128  # tokens of the recurrence under one jax.checkpoint


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def _swiglu(x, p):
    return (
        jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
    ) @ p["down_proj"]["kernel"]


def conv_silu(x, taps, causal=True):
    """SiLU of the depthwise convolution of x [B, S, W] with taps [W, K]:
    ``taps[:, K - 1]`` at the current position, zeros outside the row;
    ``causal=False``: the same taps shifted one position into the future."""
    seq, width = x.shape[1], taps.shape[1]
    before = width - 1 if causal else width - 2
    padded = jnp.pad(x, ((0, 0), (before, width - 1 - before), (0, 0)))
    return jax.nn.silu(sum(
        taps[:, k].astype(x.dtype) * padded[:, k:k + seq] for k in range(width)
    ))


def delta_rule(q, k, v, g, beta, checkpoint=False):
    """o [B, S, H, dv] of the gated delta rule, one token after another;
    q, k, g [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H]; the state
    [B, H, dk, dv] in the operands' dtype."""
    batch, seq, heads, dk = k.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., None] * (
            b_t[..., None] * (v_t - read)
        )[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    size = TOKEN_BLOCK if checkpoint and seq % TOKEN_BLOCK == 0 else seq
    if checkpoint:
        block = jax.checkpoint(block)
    xs = tuple(
        jnp.moveaxis(x, 1, 0).reshape(seq // size, size, *x.shape[:1],
                                      *x.shape[2:])
        for x in (q, k, v, g, beta)
    )
    _state, out = jax.lax.scan(
        block, jnp.zeros((batch, heads, dk, v.shape[-1]), v.dtype), xs
    )
    return jnp.moveaxis(out.reshape(seq, *out.shape[2:]), 0, 1)


def kda_mixer(x, p, *, eps, l2_eps=1e-6, decay="channel", beta_sigmoid=True,
              causal_conv=True, gate_sigmoid=True, k_norm=True,
              checkpoint=False):
    b, s, _ = x.shape
    heads = p["A_log"].shape[0]
    d = p["dt_bias"].shape[0] // heads

    def branch(name):
        return conv_silu(
            x @ p[f"{name}_proj"]["kernel"], p[f"{name}_conv"], causal_conv
        ).reshape(b, s, heads, d)

    def unit(y):
        return y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True)
            + jnp.asarray(l2_eps, y.dtype)
        )

    q, k, v = unit(branch("q")) * d ** -0.5, branch("k"), branch("v")
    if k_norm:
        k = unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        ((x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"]
         + p["dt_bias"]).reshape(b, s, heads, d)
    )
    if decay == "head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    elif decay == "none":
        g = jnp.zeros_like(g)
    beta = x @ p["b_proj"]["kernel"]
    if beta_sigmoid:
        beta = jax.nn.sigmoid(beta)
    out = delta_rule(q, k, v, g, beta, checkpoint)
    gate = (
        (x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"] + p["g_b_bias"]
    ).reshape(b, s, heads, d)
    if gate_sigmoid:
        gate = jax.nn.sigmoid(gate)
    normed = _rms_norm(out, p["o_norm"], eps)
    return (normed * gate).reshape(b, s, heads * d) @ p["o_proj"]["kernel"]


def _rope_interleaved(x, theta):
    """x [B, S, H, D]: pairs (2i, 2i+1) rotated by position · theta^(-2i/D):
    what this model does NOT do (a mutation for the tests)."""
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


def _causal_attention(q, k, v):
    """softmax_causal(q kᵀ / sqrt(D_qk)) v for a block of heads."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def mla_mixer(x, p, *, nope, rope, eps, mla_rope_theta=None,
              checkpoint=False):
    b, s, _ = x.shape
    rank = p["kv_a_layernorm"]["weight"].shape[0]
    heads = p["q_proj"]["kernel"].shape[1] // (nope + rope)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, heads, nope + rope)
    latent = x @ p["kv_a_proj_with_mqa"]["kernel"]
    kv = (
        _rms_norm(latent[..., :rank], p["kv_a_layernorm"], eps)
        @ p["kv_b_proj"]["kernel"]
    ).reshape(b, s, heads, -1)
    k_pe = latent[..., rank:][:, :, None, :]
    if mla_rope_theta is not None:
        k_pe = _rope_interleaved(k_pe, mla_rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], _rope_interleaved(q[..., nope:], mla_rope_theta)],
            axis=-1,
        )
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))], axis=-1
    )
    v = kv[..., nope:]
    block = jax.checkpoint(_causal_attention) if checkpoint else (
        _causal_attention
    )
    ctx = jnp.concatenate([
        block(q[:, :, h:h + HEAD_BLOCK], k[:, :, h:h + HEAD_BLOCK],
              v[:, :, h:h + HEAD_BLOCK])
        for h in range(0, heads, HEAD_BLOCK)
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
        routed = routed + mine[:, None].astype(x.dtype) * expert
    experts = scores.shape[-1]
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), experts, dtype=jnp.float32), axis=0
    ) / choice.size
    return {"routed": routed, "shared": _swiglu(x, p["shared_experts"]),
            "scores": scores, "choice": choice, "load": load}


def _head(hidden, lm_head, labels):
    log_probs = jax.nn.log_softmax(hidden @ lm_head, axis=-1)
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


def forward(params, batch, *, nope, rope, eps, top_k, scale, held,
            choices=None, bias_in_choice=True, checkpoint=False,
            dtype=jnp.float32, mla_rope_theta=None, **kda_mutations):
    """-> dict: ``loss``, ``ce`` [B, S], ``scores`` [L, T, E], ``choice``
    [L, T, k], ``load_excess`` [L, E] (load − mean load: what the bias rule
    takes the sign of), ``mixed`` (every layer's mixer output, [layers, B, S,
    H]: a head share's PARTIAL sum) and ``routed`` (each sparse layer's
    routed output [L, T, H]): what the shares of a deployment add up to.
    ``dtype``: float32, the reference; bfloat16 turns every weight,
    activation, log-decay, the recurrent state, every accumulation and the
    softmax into bf16 — the reading of what a precision BELOW the cell's
    does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)

    def layer(hidden, p, choice):
        x = _rms_norm(hidden, p["input_layernorm"], eps)
        if "A_log" in p["self_attn"]:
            mixed = kda_mixer(
                x, p["self_attn"], eps=eps, checkpoint=checkpoint,
                **kda_mutations,
            )
        else:
            mixed = mla_mixer(
                x, p["self_attn"], nope=nope, rope=rope, eps=eps,
                mla_rope_theta=mla_rope_theta, checkpoint=checkpoint,
            )
        hidden = hidden + mixed
        x = _rms_norm(hidden, p["post_attention_layernorm"], eps)
        if "router" not in p["mlp"]:
            return hidden + _swiglu(x, p["mlp"]), mixed, None
        b, s, h = hidden.shape
        out = moe_ffn(
            x.reshape(b * s, h), p["mlp"], held=held, top_k=top_k,
            scale=scale, bias_in_choice=bias_in_choice, choice=choice,
        )
        return hidden + (out["routed"] + out["shared"]).reshape(
            b, s, h
        ), mixed, out

    head = _head
    if checkpoint:
        layer, head = jax.checkpoint(layer), jax.checkpoint(head)

    hidden = params["embed_tokens"][batch["input_ids"]]
    routings, mixers = [], []
    for p in layers_in_order(params):
        sparse = "router" in p["mlp"]
        choice = (
            choices[len(routings)] if sparse and choices is not None else None
        )
        hidden, mixed, out = layer(hidden, p, choice)
        mixers.append(mixed)
        if sparse:
            routings.append(out)
    ce = head(
        _rms_norm(hidden, params["norm"], eps), params["lm_head"],
        batch["labels"],
    )
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    }
    load = stacked["load"]
    return {
        "loss": jnp.mean(ce), "ce": ce, "scores": stacked["scores"],
        "choice": stacked["choice"], "routed": stacked["routed"],
        "mixed": jnp.stack(mixers),
        "load_excess": load - jnp.mean(load, axis=-1, keepdims=True),
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
