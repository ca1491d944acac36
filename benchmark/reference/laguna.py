"""Laguna-XS.2 (``model_type: laguna``; poolside, 33B-A3B) training loss in
plain ``jax.numpy``, float32: no kernels, no tile loop, no chunked head.

    x [S, 2048], no bias anywhere, RMSNorm eps 1e-6; decoder layer l:
        n   = RMSNorm_in(x)
        H_l = num_attention_heads_per_layer[l]: 48 where layer_types[l] is
              full_attention, 64 where sliding_attention; 8 kv heads of 128;
              kv head j serves the H_l / 8 ADJACENT query heads
        q = W_q n [H_l x 128];  k = W_k n, v = W_v n [8 x 128]
        g = sigmoid(W_g n) [H_l]                    one gate a head
        full_attention:    rotate-half RoPE over lanes 0..63 of each head of
            q and k, lanes 64..127 pass (partial_rotary_factor 0.5); 32
            inverse frequencies by YaRN over dim 64:
              f_i = 500000^(-2i/64)
              c(b) = 64 ln(4096 / (2 pi b)) / (2 ln 500000)
              low = floor(c(64)), high = ceil(c(1)), held to [0, 63]
              r_i = clip((i - low) / (high - low), 0, 1)
              inv_freq_i = (f_i / 64) r_i + f_i (1 - r_i)
            cos and sin x 1.4158883083359672
        sliding_attention: rotate-half RoPE over the whole 128 lanes, theta
            10000, no scaling
        a_h = softmax(q_h k_j^T / sqrt(128) + mask_l) v_j
              mask_l: key <= query, and where sliding_attention also
              query - key < 512 (the window counts the query's own position)
        h   = x + W_o concat_h(g_h a_h)
        m   = RMSNorm_post(h)
        mlp_layer_types[l] dense (layer 0):
              y = h + W_down(silu(W_gate m) * W_up m)       width 8192
        sparse: s = sigmoid(W_r m) [256]
              C = top-8(s);  w_e = 2.5 s_e / sum_{c in C} s_c, on the
              experts' OUTPUTS
              y = h + sum_{e in C} w_e Expert_e(m) + Shared(m)
              Expert_e, Shared: SwiGLU 2048 -> 512 -> 2048
    after the stack a final RMSNorm, then the untied head
    loss: mean next-token cross-entropy; no auxiliary term, no selection bias

Written from these equations. It reads the parameter tree the program trains
(names as Flax lays them out: ``dense_layer_<i>``, then the scanned periods
under ``layers`` with one entry ``layer_<k>`` per position in the period and
every leaf stacked over the periods, then ``tail_layer_<i>``; a layer's gate
is ``g_proj`` beside ``self_attn``) and imports nothing from ``dedloc_tpu``.
It is given the same SHARE the program holds: ``held = (first, count)`` —
the sum over the chosen experts runs over the held ones, what an absent
expert would have added is left out; the shared expert is whole — and the
same vocabulary slice (the tree's own rows).

Departures from a textbook forward, each for a stated reason:

- the experts are a Python loop over the HELD ones, each applied to every
  token and masked by the token's weight for it (dense: no sort, no gather);
- ``choices`` ([L, T, k], the sparse layers in order): route by THESE
  choices instead of the reference's own top-k — the top-k is discrete, a
  near-tie flips under bf16 rounding and a flipped slot changes its token's
  gradient wholesale, so a comparison of gradients routes the reference as
  the program routed; scores and choices are compared on their own;
- attention contracts each kv head against ITS group of query heads (q as
  [.., 8, H_l / 8, 128]) instead of repeating k and v H_l / 8 times: the
  same sums;
- the dense attention runs a block of ``ROW_BLOCK`` query rows at a time
  against every key under its rows of the explicit [S, S] mask (``lax.map``:
  one block's scores live at once), and with ``checkpoint=True`` every
  layer, every such block and the head run under ``jax.checkpoint`` — at
  S=8,192 one block's float32 scores over 64 heads are 1.07 GB; values are
  unchanged.

``dtype`` exists to read what a lower precision does (see ``forward``);
``gate``, ``rotary``, ``yarn``, ``router`` and ``band`` exist so a test can
show that a reference under another reading of what the config leaves open
— no gate or a softplus one, the LAST lanes rotated or all of them, plain
frequencies, a softmax router, the window off — is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS = ("experts_gate", "experts_up", "experts_down")
ROW_BLOCK = 512  # query rows of dense attention computed at a time
FULL, SLIDING = "full_attention", "sliding_attention"


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def yarn_frequencies(dim, theta, factor, original, beta_fast, beta_slow):
    """The dim / 2 inverse frequencies of the equations above, float64."""
    f = np.array([theta ** (-2.0 * i / dim) for i in range(dim // 2)])

    def c(beta):
        return dim * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta)
        )

    low = min(max(math.floor(c(beta_fast)), 0), dim - 1)
    high = min(max(math.ceil(c(beta_slow)), 0), dim - 1)
    r = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return (f / factor) * r + f * (1.0 - r)


def rope_tables(seq, kind, rope):
    """(cos, sin) [S, lanes rotated] of one kind of layer; ``rope``: the
    config's ``rope_parameters`` with ``head_dim``."""
    p = rope[kind]
    lanes = int(rope["head_dim"] * p.get("partial_rotary_factor", 1))
    if p.get("rope_type", "default") == "yarn":
        inv_freq = yarn_frequencies(
            lanes, p["rope_theta"], p["factor"],
            p["original_max_position_embeddings"], p["beta_fast"],
            p["beta_slow"],
        )
        scale = p["attention_factor"]
    else:
        inv_freq = np.array([
            p["rope_theta"] ** (-2.0 * i / lanes) for i in range(lanes // 2)
        ])
        scale = 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32
    )[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rope(x, cos, sin, rotary="first"):
    """x [B, S, H, D]: the lanes the tables cover — the head's first (or,
    ``rotary="last"``, its last) — become x cos + rotate_half(x) sin, pair
    (i, i + lanes / 2) at one angle; the other lanes pass."""
    lanes = cos.shape[-1]
    if rotary == "last":
        keep, turn = x[..., :-lanes], x[..., -lanes:]
    else:
        turn, keep = x[..., :lanes], x[..., lanes:]
    half = lanes // 2
    rotated = jnp.concatenate([-turn[..., half:], turn[..., :half]], axis=-1)
    turned = (
        turn * cos[None, :, None, :].astype(x.dtype)
        + rotated * sin[None, :, None, :].astype(x.dtype)
    )
    return jnp.concatenate(
        [keep, turned] if rotary == "last" else [turned, keep], axis=-1
    )


def visible(seq, window):
    """The explicit [S, S] mask: key <= query, and (``window``) query - key
    < window."""
    i = jnp.arange(seq)
    seen = i[None, :] <= i[:, None]
    if window is not None:
        seen &= i[:, None] - i[None, :] < window
    return seen


def _masked_attention(q, k, v, seen):
    """q [B, R, KV, G, D] against every key [B, S, KV, D]; ``seen`` [R, S]."""
    scores = jnp.einsum("bqcgd,bkcd->bcgqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum(
        "bcgqk,bkcd->bqcgd", jax.nn.softmax(scores, axis=-1), v
    )


def attention(x, p, gate_kernel, *, heads, kv_heads, tables, window,
              gate="sigmoid", rotary="first", checkpoint=False):
    """W_o concat_h(g_h a_h) of the normalised input x [B, S, hidden]."""
    b, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, heads, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    q, k = _rope(q, *tables, rotary), _rope(k, *tables, rotary)
    seen = visible(s, window)
    block = jax.checkpoint(_masked_attention) if checkpoint else (
        _masked_attention
    )
    # a block of query rows at a time, one after the other (``lax.map`` is
    # a loop on the device: an unrolled Python loop lets the compiler hold
    # every block's scores at once)
    rows = min(ROW_BLOCK, s)
    grouped = q.reshape(b, s // rows, rows, kv_heads, heads // kv_heads, -1)
    ctx = jax.lax.map(
        lambda block_of: block(block_of[0], k, v, block_of[1]),
        (jnp.moveaxis(grouped, 1, 0), seen.reshape(s // rows, rows, s)),
    )  # [blocks, B, rows, KV, G, D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, heads, -1)
    logits = x @ gate_kernel  # [B, S, heads]
    g = {
        "sigmoid": jax.nn.sigmoid, "softplus": jax.nn.softplus,
        "none": jnp.ones_like,
    }[gate](logits)
    ctx = ctx * g[..., None]
    return ctx.reshape(b, s, -1) @ p["o_proj"]["kernel"], g


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(scores, top_k, scale, choice=None):
    """(choice [T, k], weights [T, k]): the top k of the scores, each over
    the sum of the chosen, times ``scale``."""
    if choice is None:
        _, choice = jax.lax.top_k(scores, top_k)
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_ffn(x, p, *, held, top_k, scale, router="sigmoid", choice=None):
    """x [T, H] -> dict: ``routed`` (the HELD experts' part of the sum over
    the chosen experts), ``shared`` (the shared expert, which every chip
    computes alike), ``scores`` [T, E], ``choice`` [T, k], ``load`` [E]."""
    first, count = held
    logits = x @ p["router"]
    scores = {
        "sigmoid": jax.nn.sigmoid,
        "softmax": lambda z: jax.nn.softmax(z, axis=-1),
    }[router](logits)
    choice, weights = route(scores, top_k, scale, choice)
    gate, up, down = (p[name] for name in EXPERTS)
    routed = jnp.zeros_like(x)
    for i in range(count):
        mine = jnp.sum(jnp.where(choice == first + i, weights, 0.0), axis=-1)
        routed = routed + mine[:, None].astype(x.dtype) * _swiglu(
            x, gate[i], up[i], down[i]
        )
    shared = _swiglu(x, *(
        p["shared_experts"][name]["kernel"]
        for name in ("gate_proj", "up_proj", "down_proj")
    ))
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), scores.shape[-1],
                       dtype=jnp.float32), axis=0,
    ) / choice.size
    return {"routed": routed, "shared": shared, "scores": scores,
            "choice": choice, "load": load}


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
    period = params.get("layers", {})
    positions = sorted(period, key=lambda name: int(name.split("_")[-1]))
    periods = jax.tree.leaves(period)[0].shape[0] if period else 0
    out += [
        jax.tree.map(lambda x: x[n], period[name])
        for n in range(periods) for name in positions
    ]
    i = 0
    while f"tail_layer_{i}" in params:
        out.append(params[f"tail_layer_{i}"])
        i += 1
    return out


def forward(params, batch, *, layer_types, heads_per_layer, mlp_layer_types,
            kv_heads, eps, window, rope, top_k, scale, held, choices=None,
            gate="sigmoid", rotary="first", yarn=True, router="sigmoid",
            band=True, checkpoint=False, dtype=jnp.float32):
    """-> dict: ``loss``, ``ce`` [B, S], ``gate_mean`` [layers], and over
    the SPARSE layers in order ``scores`` [L, T, E], ``choice`` [L, T, k],
    ``load`` [L, E], ``routed`` / ``shared`` [L, T, H] (the held experts'
    part and the shared expert's: what the shares of a deployment add up
    to). The three per-layer lists name the layers run, in order. ``rope``:
    the config's ``rope_parameters`` (a group a kind) with ``head_dim``.
    ``dtype``: float32, the reference; bfloat16 turns every weight,
    activation, accumulation and the softmax into bf16 — the reading of what
    a precision BELOW the cell's (bf16 operands, float32 accumulation and
    softmax) does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    if not yarn:
        rope = dict(rope, **{FULL: dict(rope[FULL], rope_type="default")})
    seq = batch["input_ids"].shape[1]
    tables = {
        kind: tuple(t.astype(dtype) for t in rope_tables(seq, kind, rope))
        for kind in set(layer_types)
    }

    def layer(hidden, p, choice, kind, heads, sparse):
        n = _rms_norm(hidden, p["input_layernorm"], eps)
        mixed, g = attention(
            n, p["self_attn"], p["g_proj"]["kernel"], heads=heads,
            kv_heads=kv_heads, tables=tables[kind],
            window=window if kind == SLIDING and band else None,
            gate=gate, rotary=rotary, checkpoint=checkpoint,
        )
        hidden = hidden + mixed
        m = _rms_norm(hidden, p["post_attention_layernorm"], eps)
        if not sparse:
            return hidden + _swiglu(m, *(
                p["mlp"][name]["kernel"]
                for name in ("gate_proj", "up_proj", "down_proj")
            )), {"gate_mean": jnp.mean(g.astype(jnp.float32))}
        b, s, h = hidden.shape
        out = moe_ffn(
            m.reshape(b * s, h), p["mlp"], held=held, top_k=top_k,
            scale=scale, router=router, choice=choice,
        )
        y = hidden + (out["routed"] + out["shared"]).reshape(b, s, h)
        return y, dict(out, gate_mean=jnp.mean(g.astype(jnp.float32)))

    head = _head
    if checkpoint:
        layer = jax.checkpoint(layer, static_argnums=(3, 4, 5))
        head = jax.checkpoint(head)

    hidden = params["embed_tokens"][batch["input_ids"]]
    routings, gates = [], []
    for p, kind, heads, ffn in zip(
        layers_in_order(params), layer_types, heads_per_layer,
        mlp_layer_types,
    ):
        sparse = ffn == "sparse"
        choice = None
        if sparse and choices is not None:
            choice = choices[len(routings)]
        hidden, out = layer(hidden, p, choice, kind, int(heads), sparse)
        gates.append(out.pop("gate_mean"))
        if sparse:
            routings.append(out)
    ce = head(
        _rms_norm(hidden, params["norm"], eps), params["lm_head"],
        batch["labels"],
    )
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    } if routings else {}
    return {"loss": jnp.mean(ce), "ce": ce, "gate_mean": jnp.stack(gates),
            **stacked}


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
