"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) training loss
in plain ``jax.numpy``, float32: no kernels, no chunked scan, no tile loop,
no chunked head.

    x [S, 2688]; eps 1e-5; layer l is ONE sublayer by letter l of
    ``hybrid_override_pattern``:  x <- x + f_l(RMSNorm_l(x)),  u the normed x
    ``M`` (64 heads of P = 64 in 8 groups; state N = 128):
              (z | xBC | dt~) = W_in u      [4096 | 4096 + 2 x 8 x 128 | 64]
              (x | B | C) = SiLU(conv4(xBC) + b_conv): causal, depthwise, 4
                      taps (taps[:, 3] at the current position), zeros before
              dt = softplus(dt~ + dt_bias);  a = dt x (-exp(A_log))   [heads]
              per head h of group g = h // 8, S_0 = 0 [N, P], TOKEN BY TOKEN:
                  S_t = e^{a_t} S_{t-1} + dt_t B_{t,g} x_{t,h}^T
                  y_t = S_t^T C_{t,g} + D_h x_{t,h}
              out = W_out [RMSNorm_group(y * SiLU(z)) x w]: the gate BEFORE
                      the norm, the norm over each group's 512 lanes
    ``*``: q = W_q u [S, 32, 128]; k, v = W_k u, W_v u [S, 2, 128], key head j
              serving 16 ADJACENT query heads; NO rotary embedding;
              out = W_o softmax_causal(q k^T / sqrt(128)) v
    ``E``: s = sigmoid(W_r u) [128]; choice = top6(s + b);
              w = s[choice] / (sum s[choice] + 1e-20) x 2.5
              sum_{e in choice} w_e W_down,e relu(W_up,e u)^2 (width 1856, NO
              gate matrix) + W_down,s relu(W_up,s u)^2 (width 3712)
    final RMSNorm; untied head; loss: mean next-token cross-entropy
    b: after every GLOBAL step b_e <- b_e - gamma sign(load_e - mean load)

It reads the parameter tree the program trains (names as Flax lays them out:
``layer_<i>`` with ``norm`` and ``mixer``; a layer is a Mamba layer if its
mixer has ``A_log``, an expert layer if it has ``router``) and imports
nothing from ``dedloc_tpu``. It is given the same SHARE the program holds:
the heads and groups are the tree's own (a mixer's projections exist for the
held heads alone, and its out-projection gives the mixer's PARTIAL sum,
which is what joins the stream), ``held = (first, count)`` the experts, the
vocabulary slice the tree's rows.

Departures from a textbook forward, each for a stated reason:

- THE RECURRENCE IS THE TOKEN-BY-TOKEN ONE, not the chunked algebra of
  ``ops/ssd.py``: it shares nothing with what it checks. With
  ``checkpoint=True`` it runs a block of ``TOKEN_BLOCK`` tokens at a time
  under ``jax.checkpoint`` (8,192 states of 32 x 32 KB are 8.6 GB a layer
  unblocked), every layer and the head under one too, dense attention a
  block of ``HEAD_BLOCK`` query heads at a time; values are unchanged;
- ``choices`` ([L, T, k]): route by THESE choices instead of the
  reference's own top-k (the top-k is discrete: ``deepseek_v3.py``'s
  reason).

``dtype`` exists to read what a lower precision does (bfloat16: every
weight, activation, the time steps and log-decays, THE STATE and every
accumulation); ``decay``, ``skip``, ``gate_before_norm``, ``norm_groups``,
``causal_conv``, ``conv_bias``, ``activation``, ``expert_gate``,
``rope_theta`` and ``kv_interleaved`` exist so a test can show that a
reference without each is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"
HEAD_BLOCK = 8  # query heads of dense attention computed at a time
TOKEN_BLOCK = 128  # tokens of the recurrence under one jax.checkpoint


def _rms_norm(x, weight, eps, groups=1):
    """RMSNorm over each of ``groups`` equal runs of the last axis."""
    grouped = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        + jnp.asarray(eps, x.dtype)
    )
    return normed.reshape(x.shape) * weight


def conv_silu(x, taps, bias=None, causal=True):
    """SiLU of the depthwise convolution of x [B, S, W] with taps [W, K] (+
    bias [W]): ``taps[:, K - 1]`` at the current position, zeros outside the
    row; ``causal=False``: the same taps shifted one position into the
    future."""
    seq, width = x.shape[1], taps.shape[1]
    before = width - 1 if causal else width - 2
    padded = jnp.pad(x, ((0, 0), (before, width - 1 - before), (0, 0)))
    conv = sum(
        taps[:, k].astype(x.dtype) * padded[:, k:k + seq] for k in range(width)
    )
    return jax.nn.silu(conv if bias is None else conv + bias.astype(x.dtype))


def selective_scan(x, dt, a, B, C, D, checkpoint=False, skip=True):
    """y [B, S, H, P] of Mamba-2's recurrence, one token after another:
    x [B, S, H, P]; dt, a [B, S, H]; B, C [B, S, G, N] (head h reads group
    h // (H / G)); D [H]; the state [B, H, N, P] in the operands' dtype."""
    batch, seq, heads, dim = x.shape
    per_group = heads // B.shape[2]

    def step(state, inputs):
        x_t, dt_t, a_t, b_t, c_t = inputs
        b_t = jnp.repeat(b_t, per_group, axis=1)
        c_t = jnp.repeat(c_t, per_group, axis=1)
        state = state * jnp.exp(a_t)[..., None, None] + (
            (dt_t[..., None] * b_t)[..., None] * x_t[..., None, :]
        )
        y_t = jnp.einsum("bhnp,bhn->bhp", state, c_t)
        return state, (y_t + D[:, None] * x_t) if skip else y_t

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    size = TOKEN_BLOCK if checkpoint and seq % TOKEN_BLOCK == 0 else seq
    if checkpoint:
        block = jax.checkpoint(block)
    xs = tuple(
        jnp.moveaxis(v, 1, 0).reshape(seq // size, size, *v.shape[:1],
                                      *v.shape[2:])
        for v in (x, dt, a, B, C)
    )
    _state, out = jax.lax.scan(
        block, jnp.zeros((batch, heads, B.shape[-1], dim), x.dtype), xs
    )
    return jnp.moveaxis(out.reshape(seq, *out.shape[2:]), 0, 1)


def mamba_mixer(u, p, *, state, eps, decay=True, skip=True,
                gate_before_norm=True, norm_groups=None, causal_conv=True,
                conv_bias=True, checkpoint=False):
    b, s, _ = u.shape
    heads = p["A_log"].shape[0]
    inner = p["out_proj"]["kernel"].shape[0]
    dim = inner // heads
    keys = (p["conv"].shape[0] - inner) // 2
    groups = keys // state
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * keys], axis=-1)
    xbc = conv_silu(
        xbc, p["conv"], p["conv_bias"] if conv_bias else None, causal_conv
    )
    x, bm, cm = jnp.split(xbc, [inner, inner + keys], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"]) * dt
    if not decay:
        a = jnp.zeros_like(a)
    y = selective_scan(
        x.reshape(b, s, heads, dim), dt, a, bm.reshape(b, s, groups, state),
        cm.reshape(b, s, groups, state), p["D"], checkpoint, skip,
    ).reshape(b, s, inner)
    norm_groups = groups if norm_groups is None else norm_groups
    weight = p["norm"]["weight"]
    if gate_before_norm:
        out = _rms_norm(y * jax.nn.silu(z), weight, eps, norm_groups)
    else:
        out = _rms_norm(y, weight, eps, norm_groups) * jax.nn.silu(z)
    return out @ p["out_proj"]["kernel"]


def _rope(x, theta):
    """x [B, S, H, D] under rotate-half RoPE: what this model does NOT do
    (a mutation for the tests)."""
    _b, s, _h, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles).astype(x.dtype) + rotated * jnp.sin(
        angles
    ).astype(x.dtype)


def _causal_attention(q, k, v):
    """softmax_causal(q kᵀ / sqrt(D)) v for a block of query heads and the
    key head of each, under an explicit mask."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention_mixer(u, p, *, head_dim, rope_theta=None, kv_interleaved=False,
                    checkpoint=False):
    b, s, _ = u.shape
    heads = p["q_proj"]["kernel"].shape[1] // head_dim
    kv = p["k_proj"]["kernel"].shape[1] // head_dim
    q = (u @ p["q_proj"]["kernel"]).reshape(b, s, heads, head_dim)
    k = (u @ p["k_proj"]["kernel"]).reshape(b, s, kv, head_dim)
    v = (u @ p["v_proj"]["kernel"]).reshape(b, s, kv, head_dim)
    if rope_theta is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    # the key head of every query head: ADJACENT heads share one
    serves = jnp.arange(heads) % kv if kv_interleaved else (
        jnp.arange(heads) // (heads // kv)
    )
    k, v = k[:, :, serves], v[:, :, serves]
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


def _plain_mlp(x, up, down, activation="relu2", expert_gate=False):
    h = x @ up
    act = jnp.square(jax.nn.relu(h)) if activation == "relu2" else (
        jax.nn.relu(h)
    )
    if expert_gate:  # a gate this model does NOT have: W_up standing in
        act = jax.nn.silu(h) * act
    return act @ down


def moe_ffn(x, p, *, held, top_k, scale, bias_in_choice=True, choice=None,
            activation="relu2", expert_gate=False):
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
        routed = routed + mine[:, None].astype(x.dtype) * _plain_mlp(
            x, p["experts_up"][i], p["experts_down"][i], activation,
            expert_gate,
        )
    experts = scores.shape[-1]
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), experts, dtype=jnp.float32), axis=0
    ) / choice.size
    shared = _plain_mlp(
        x, p["shared_experts"]["up_proj"]["kernel"],
        p["shared_experts"]["down_proj"]["kernel"], activation, expert_gate,
    )
    return {"routed": routed, "shared": shared, "scores": scores,
            "choice": choice, "load": load}


def _head(hidden, lm_head, labels):
    log_probs = jax.nn.log_softmax(hidden @ lm_head, axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def layers_in_order(params):
    """The parameter trees of the layers as the model applies them."""
    out = []
    while f"layer_{len(out)}" in params:
        out.append(params[f"layer_{len(out)}"])
    return out


def kind_of(layer) -> str:
    mixer = layer["mixer"]
    return "M" if "A_log" in mixer else "E" if "router" in mixer else "*"


def forward(params, batch, *, state, head_dim, eps, top_k, scale, held,
            choices=None, bias_in_choice=True, checkpoint=False,
            dtype=jnp.float32, rope_theta=None, kv_interleaved=False,
            activation="relu2", expert_gate=False, **mamba_mutations):
    """-> dict: ``loss``, ``ce`` [B, S], ``scores`` [L, T, E], ``choice``
    [L, T, k], ``load_excess`` [L, E] (load − mean load: what the bias rule
    takes the sign of), ``mixed`` (every layer's sublayer output, [layers,
    B, S, H]: a head share's PARTIAL sum in an ``M`` or ``*`` layer) and
    ``routed`` / ``shared`` (each expert layer's two parts [L, T, H]): what
    the shares of a deployment add up to. ``dtype``: float32, the
    reference; bfloat16 turns every weight, activation, time step and
    log-decay, the recurrent state, every accumulation and the softmax into
    bf16 — the reading of what a precision BELOW the cell's does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)

    def layer(hidden, p, choice):
        u = _rms_norm(hidden, p["norm"]["weight"], eps)
        kind = kind_of(p)
        if kind == "M":
            mixed = mamba_mixer(
                u, p["mixer"], state=state, eps=eps, checkpoint=checkpoint,
                **mamba_mutations,
            )
            return hidden + mixed, mixed, None
        if kind == "*":
            mixed = attention_mixer(
                u, p["mixer"], head_dim=head_dim, rope_theta=rope_theta,
                kv_interleaved=kv_interleaved, checkpoint=checkpoint,
            )
            return hidden + mixed, mixed, None
        b, s, h = hidden.shape
        out = moe_ffn(
            u.reshape(b * s, h), p["mixer"], held=held, top_k=top_k,
            scale=scale, bias_in_choice=bias_in_choice, choice=choice,
            activation=activation, expert_gate=expert_gate,
        )
        mixed = (out["routed"] + out["shared"]).reshape(b, s, h)
        return hidden + mixed, mixed, out

    head = _head
    if checkpoint:
        layer, head = jax.checkpoint(layer), jax.checkpoint(head)

    hidden = params["embed_tokens"][batch["input_ids"]]
    routings, mixers = [], []
    for p in layers_in_order(params):
        sparse = kind_of(p) == "E"
        choice = (
            choices[len(routings)] if sparse and choices is not None else None
        )
        hidden, mixed, out = layer(hidden, p, choice)
        mixers.append(mixed)
        if sparse:
            routings.append(out)
    ce = head(
        _rms_norm(hidden, params["norm"]["weight"], eps), params["lm_head"],
        batch["labels"],
    )
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    }
    load = stacked["load"]
    return {
        "loss": jnp.mean(ce), "ce": ce, "scores": stacked["scores"],
        "choice": stacked["choice"], "routed": stacked["routed"],
        "shared": stacked["shared"], "mixed": jnp.stack(mixers),
        "load_excess": load - jnp.mean(load, axis=-1, keepdims=True),
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
