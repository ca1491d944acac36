"""SmallThinker-21BA3B-Instruct (``model_name: smallthinker_21b_instruct``)
training loss in plain ``jax.numpy``, float32: no kernels, no tile loop, no
chunked head.

    x [S, 2560]; RMSNorm eps 1e-6, no bias anywhere; decoder layer l:
        n  = RMSNorm_in(x)
        r  = W_r n                            [64] logits: the router reads
                                              n, BEFORE attention
        q, k, v = W_q n, W_k n, W_v n         28 / 4 / 4 heads of 128
        if rope_layout[l] == 1: q, k = RoPE(q, k)   theta 1.5e6, rotate-half
                                              over the whole 128-wide head;
                                              layout 0: no positions at all
        a  = softmax(q k^T / sqrt(128) + mask_l) v   kv head j serves query
                                              heads 7j .. 7j+6
             mask_l: key <= query, and where sliding_window_layout[l] == 1
             also query - key < 4096
        h  = x + W_o a
        m  = RMSNorm_post(h)
        C  = top-6 of r;  w = softmax(r[C])   sums to 1; no bias, no scale
        y  = h + sum_{e in C} w_e W_down,e(relu(W_gate,e m) * W_up,e m)
    after the stack a final RMSNorm, then the untied head
    loss: mean next-token cross-entropy; no auxiliary or balancing loss
    layouts 0,1,1,1 repeating: layer 4i global without positions, layers
    4i+1 .. 4i+3 banded with RoPE

It reads the parameter tree the program trains (names as Flax lays them out:
the scanned periods under ``layers`` with one entry ``layer_<k>`` per
position in the period and every leaf stacked over the periods, then
``tail_layer_<i>``) and imports nothing from ``dedloc_tpu``. It is given the
same SHARE the program holds: ``held = (first, count)`` — the sum over the
chosen experts runs over the held ones, what an absent expert would have
added is left out — and the same vocabulary slice (the tree's own rows).

Departures from a textbook forward, each for a stated reason:

- the experts are a Python loop over the HELD ones, each applied to every
  token and masked by the token's weight for it (dense: no sort, no gather);
- ``choices`` ([L, T, k], the layers in order): route by THESE choices
  instead of the reference's own top-k — the top-k is discrete, a near-tie
  flips under bf16 rounding and a flipped slot changes its token's gradient
  wholesale, so a comparison of gradients routes the reference as the
  program routed; logits and choices are compared on their own;
- the dense attention runs a block of ``ROW_BLOCK`` query rows at a time
  (``lax.map``: one block's scores live at once), and with
  ``checkpoint=True`` every layer, every such block and the head run under
  ``jax.checkpoint`` — at S=16,384 one head's float32 scores are 1.07 GB;
  values are unchanged.

``dtype`` exists to read what a lower precision does (see ``forward``);
``rope_on_global``, ``router_after_attention``, ``activation`` and ``band``
exist so a test can show that a reference with RoPE on the global layers,
with the router fed after attention, with SiLU for ReLU or with the band off
is far off.

Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EXPERTS = ("experts_gate", "experts_up", "experts_down")
ROW_BLOCK = 512  # query rows of dense attention computed at a time


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


def visible(seq, window):
    """The explicit [S, S] mask: key <= query, and (``window``) query - key
    < window."""
    i = jnp.arange(seq)
    seen = i[None, :] <= i[:, None]
    if window is not None:
        seen &= i[:, None] - i[None, :] < window
    return seen


def _masked_attention(q, k, v, seen):
    """q [B, R, H, D] against every key; ``seen`` [R, S]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention(x, p, *, num_heads, kv_heads, theta, rotated, window,
              checkpoint=False):
    b, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, num_heads, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    if rotated:
        q, k = _rope(q, theta), _rope(k, theta)
    # each kv head serves num_heads / kv_heads adjacent query heads
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    seen = visible(s, window)
    block = jax.checkpoint(_masked_attention) if checkpoint else (
        _masked_attention
    )
    # a block of query rows at a time, one after the other (``lax.map`` is
    # a loop on the device: an unrolled Python loop lets the compiler hold
    # every block's scores at once)
    rows = min(ROW_BLOCK, s)
    ctx = jax.lax.map(
        lambda block_of: block(block_of[0], k, v, block_of[1]),
        (jnp.moveaxis(q.reshape(b, s // rows, rows, num_heads, -1), 1, 0),
         seen.reshape(s // rows, rows, s)),
    )  # [blocks, B, rows, H, D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, num_heads, -1)
    return ctx.reshape(b, s, -1) @ p["o_proj"]["kernel"]


def route(logits, top_k, choice=None):
    """(choice [T, k], weights [T, k]): the top k of the logits, a softmax
    over the chosen ones."""
    if choice is None:
        _, choice = jax.lax.top_k(logits, top_k)
    picked = jnp.take_along_axis(logits, choice, axis=-1)
    return choice, jax.nn.softmax(picked, axis=-1)


def moe_ffn(x, router_input, p, *, held, top_k, activation="relu",
            choice=None):
    """x [T, H], routed by ``router_input`` [T, H] -> dict: ``routed`` (the
    HELD experts' part of the sum over the chosen experts), ``scores`` [T,
    E] (the router's logits), ``choice`` [T, k], ``load`` [E]."""
    first, count = held
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    logits = router_input @ p["router"]
    choice, weights = route(logits, top_k, choice)
    gate, up, down = (p[name] for name in EXPERTS)
    routed = jnp.zeros_like(x)
    for i in range(count):
        mine = jnp.sum(jnp.where(choice == first + i, weights, 0.0), axis=-1)
        routed = routed + mine[:, None].astype(x.dtype) * (
            (act(x @ gate[i]) * (x @ up[i])) @ down[i]
        )
    load = jnp.sum(
        jax.nn.one_hot(choice.reshape(-1), logits.shape[-1],
                       dtype=jnp.float32), axis=0,
    ) / choice.size
    return {"routed": routed, "scores": logits, "choice": choice,
            "load": load}


def _head(hidden, lm_head, labels):
    log_probs = jax.nn.log_softmax(hidden @ lm_head, axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def layers_in_order(params):
    """The parameter trees of the layers as the model applies them."""
    period = params["layers"]
    positions = sorted(period, key=lambda name: int(name.split("_")[-1]))
    periods = jax.tree.leaves(period)[0].shape[0]
    out = [
        jax.tree.map(lambda x: x[n], period[name])
        for n in range(periods) for name in positions
    ]
    i = 0
    while f"tail_layer_{i}" in params:
        out.append(params[f"tail_layer_{i}"])
        i += 1
    return out


def forward(params, batch, *, num_heads, kv_heads, eps, theta, top_k,
            window, rope_layout, window_layout, held, choices=None,
            rope_on_global=False, router_after_attention=False,
            activation="relu", band=True, checkpoint=False,
            dtype=jnp.float32):
    """-> dict: ``loss``, ``ce`` [B, S], ``scores`` [L, T, E] (router
    logits), ``choice`` [L, T, k], ``load`` [L, E], ``routed`` (each
    layer's routed output, [L, T, H]: what the shares of a deployment add up
    to). ``rope_layout`` / ``window_layout``: 0 / 1 per layer run, in
    order. ``dtype``: float32, the reference; bfloat16 turns every weight,
    activation, accumulation and the softmax into bf16 — the reading of
    what a precision BELOW the cell's (bf16 operands, float32 accumulation
    and softmax) does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)

    def layer(hidden, p, choice, rotated, banded):
        n = _rms_norm(hidden, p["input_layernorm"], eps)
        hidden = hidden + attention(
            n, p["self_attn"], num_heads=num_heads, kv_heads=kv_heads,
            theta=theta, rotated=rotated or rope_on_global,
            window=window if banded and band else None,
            checkpoint=checkpoint,
        )
        m = _rms_norm(hidden, p["post_attention_layernorm"], eps)
        b, s, h = hidden.shape
        out = moe_ffn(
            m.reshape(b * s, h),
            (m if router_after_attention else n).reshape(b * s, h),
            p["block_sparse_moe"], held=held, top_k=top_k,
            activation=activation, choice=choice,
        )
        return hidden + out["routed"].reshape(b, s, h), out

    head = _head
    if checkpoint:
        layer = jax.checkpoint(layer, static_argnums=(3, 4))
        head = jax.checkpoint(head)

    hidden = params["embed_tokens"][batch["input_ids"]]
    routings = []
    for i, p in enumerate(layers_in_order(params)):
        hidden, out = layer(
            hidden, p, None if choices is None else choices[i],
            bool(rope_layout[i]), bool(window_layout[i]),
        )
        routings.append(out)
    ce = head(
        _rms_norm(hidden, params["norm"], eps), params["lm_head"],
        batch["labels"],
    )
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    }
    return {"loss": jnp.mean(ce), "ce": ce, **stacked}


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
