"""SDAR-30B-A3B-Chat (``model_type: sdar_moe``) block-diffusion training loss
in plain ``jax.numpy``, float32: no kernels, no scan, no tile loop, no
chunked head.

    a row: x [L] clean ids, x~ [L] its noisy copy (each id of a block of B
    replaced by the mask id with the block's probability t), w [L] the
    weights (1 / t at a masked position, else 0): the batch's ``labels``,
    ``input_ids`` and ``loss_weights``
    the stack's input: [x~ ; x], 2L positions; position ids 0..L-1 TWICE
    block of a position p of either stream: b(p) = p // B
    query i sees key j iff
        i clean:  j clean and b(j) <= b(i)
        i noisy: (j noisy and b(j) == b(i)) or (j clean and b(j) < b(i))
    RMSNorm eps 1e-6, no bias anywhere; decoder layer:
        n  = RMSNorm_in(h)
        q, k, v = W_q n, W_k n, W_v n         32 / 4 / 4 heads of 128
        q, k = RoPE(RMSNorm_q(q)), RoPE(RMSNorm_k(k))   the norms over a
                                              head's 128 lanes, one weight
                                              per lane shared by the heads;
                                              rotate-half, theta 1e6
        a  = softmax(q k^T / sqrt(128) + visibility) v  kv head j serves
                                              query heads 8j .. 8j+7
        h' = h + W_o a;  m = RMSNorm_post(h')
        r  = W_r m                            [128] logits
        C  = top-8 of r;  g = softmax(r)[C] / sum softmax(r)[C]
        h''= h' + sum_{e in C} g_e W_down,e(silu(W_gate,e m) * W_up,e m)
    after the stack, over the NOISY stream's L positions: a final RMSNorm,
    the untied head, and
    loss = (1 / L) sum_p w_p * (-log softmax(W_head h_p)[x_p])   (no shift),
    the mean over the rows; no auxiliary or balancing loss

It reads the parameter tree the program trains (names as Flax lays them out:
the scanned periods' layers under ``layers/layer_<i>``, every leaf stacked
over the periods, then ``tail_layer_<i>``) and imports nothing from ``dedloc_tpu``. It is given the same SHARE
the program holds: ``held = (first, count)`` — the sum over the chosen
experts runs over the held ones, what an absent expert would have added is
left out — and the same vocabulary slice (the tree's own rows).

Departures from a textbook forward, each for a stated reason:

- the experts are a Python loop over the HELD ones, each applied to every
  token and masked by the token's weight for it (dense: no sort, no gather);
- ``choices`` ([layers, T, k]): route by THESE choices instead of the
  reference's own top-k — the top-k is discrete, a near-tie flips under bf16
  rounding and a flipped slot changes its token's gradient wholesale, so a
  comparison of gradients routes the reference as the program routed; logits
  and choices are compared on their own;
- the dense attention builds the explicit [2L, 2L] mask from the three
  sentences above and runs a block of ``ROW_BLOCK`` query rows at a time
  (``lax.map``: one block's scores live at once), and with
  ``checkpoint=True`` every layer, every such block and the head run under
  ``jax.checkpoint`` — at 2L = 8,192 the 32 heads' float32 scores are 8.6
  GB; values are unchanged.

``dtype`` exists to read what a lower precision does (see ``forward``);
``rule``, ``positions``, ``qk_norm`` and ``shift`` exist so a test can show
that a reference with a plain causal mask over the 2L positions, with a
noisy query seeing the clean copy of its OWN block, with positions 0..2L-1,
without the q / k norm or with a shifted target is far off.

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


def _rope(x, positions, theta):
    """x [B, S, H, D]: x * cos + rotate_half(x) * sin, the token at
    position id t and pair (i, i + D/2) at angle t * theta^(-2i/D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (
        x * jnp.cos(angles).astype(x.dtype)
        + rotated * jnp.sin(angles).astype(x.dtype)
    )


def visible(length, block, rule="block_diffusion"):
    """The explicit [2L, 2L] mask over [noisy ; clean], rows queries.
    ``rule``: the model's, or one of the wrong ones a test names."""
    i = jnp.arange(2 * length)
    if rule == "causal":  # a plain decoder's mask over the 2L positions
        return i[None, :] <= i[:, None]
    clean, blk = i >= length, (i % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    clean_sees = k_clean & (k_blk <= q_blk)
    noisy_sees_noisy = ~k_clean & (k_blk == q_blk)
    if rule == "block_diffusion":
        noisy_sees_clean = k_clean & (k_blk < q_blk)
    elif rule == "own_block_clean":  # ... which holds the answer
        noisy_sees_clean = k_clean & (k_blk <= q_blk)
    else:
        raise ValueError(rule)
    return jnp.where(q_clean, clean_sees, noisy_sees_noisy | noisy_sees_clean)


def _masked_attention(q, k, v, seen):
    """q [B, R, H, D] against every key; ``seen`` [R, S]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def attention(x, p, *, num_heads, kv_heads, eps, theta, block, rule,
              positions, qk_norm=True, checkpoint=False):
    b, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, num_heads, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    if qk_norm:  # over each head's own lanes
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    # each kv head serves num_heads / kv_heads adjacent query heads
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    seen = visible(s // 2, block, rule)
    one = jax.checkpoint(_masked_attention) if checkpoint else (
        _masked_attention
    )
    # a block of query rows at a time, one after the other (``lax.map`` is
    # a loop on the device: an unrolled Python loop lets the compiler hold
    # every block's scores at once)
    rows = min(ROW_BLOCK, s)
    ctx = jax.lax.map(
        lambda block_of: one(block_of[0], k, v, block_of[1]),
        (jnp.moveaxis(q.reshape(b, s // rows, rows, num_heads, -1), 1, 0),
         seen.reshape(s // rows, rows, s)),
    )  # [blocks, B, rows, H, D]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, num_heads, -1)
    return ctx.reshape(b, s, -1) @ p["o_proj"]["kernel"]


def route(logits, top_k, choice=None):
    """(choice [T, k], weights [T, k]): the top k of softmax(logits),
    renormalised over the chosen (``norm_topk_prob``)."""
    probs = jax.nn.softmax(logits, axis=-1)
    if choice is None:
        _, choice = jax.lax.top_k(probs, top_k)
    picked = jnp.take_along_axis(probs, choice, axis=-1)
    return choice, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_ffn(x, p, *, held, top_k, choice=None):
    """x [T, H] -> dict: ``routed`` (the HELD experts' part of the sum over
    the chosen experts), ``scores`` [T, E] (the router's logits), ``choice``
    [T, k], ``load`` [E]."""
    first, count = held
    logits = x @ p["router"]
    choice, weights = route(logits, top_k, choice)
    gate, up, down = (p[name] for name in EXPERTS)
    routed = jnp.zeros_like(x)
    for i in range(count):
        mine = jnp.sum(jnp.where(choice == first + i, weights, 0.0), axis=-1)
        routed = routed + mine[:, None].astype(x.dtype) * (
            (jax.nn.silu(x @ gate[i]) * (x @ up[i])) @ down[i]
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
    """The parameter trees of the layers as the model applies them: the
    scanned periods' positions (``layers/layer_<i>``, every leaf stacked
    over the periods), then the layers after the last whole period."""
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


def forward(params, batch, *, num_heads, kv_heads, eps, theta, top_k, block,
            held, choices=None, rule="block_diffusion",
            positions="per_stream", qk_norm=True, shift=False,
            checkpoint=False, dtype=jnp.float32):
    """-> dict: ``loss``, ``ce`` [B, L], ``hidden`` [B, 2L, H] (after the
    final norm, both streams), ``logits`` [B, L, V] (the noisy stream's),
    ``scores`` [layers, T, E] (router logits, T = B x 2L), ``choice``
    [layers, T, k], ``load`` [layers, E], ``routed`` (each layer's routed
    output, [layers, T, H]: what the shares of a deployment add up to).
    ``dtype``: float32, the reference; bfloat16 turns every weight,
    activation, accumulation and the softmax into bf16 — the reading of
    what a precision BELOW the cell's (bf16 operands, float32 accumulation
    and softmax) does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    clean, weights = batch["labels"], batch["loss_weights"]
    length = clean.shape[1]
    ids = jnp.concatenate([batch["input_ids"], clean], axis=1)
    position_ids = {
        "per_stream": jnp.arange(2 * length) % length,
        "running": jnp.arange(2 * length),  # wrong: 0 .. 2L-1
    }[positions]

    def layer(hidden, p, choice):
        hidden = hidden + attention(
            _rms_norm(hidden, p["input_layernorm"], eps), p["self_attn"],
            num_heads=num_heads, kv_heads=kv_heads, eps=eps, theta=theta,
            block=block, rule=rule, positions=position_ids, qk_norm=qk_norm,
            checkpoint=checkpoint,
        )
        m = _rms_norm(hidden, p["post_attention_layernorm"], eps)
        b, s, h = hidden.shape
        out = moe_ffn(
            m.reshape(b * s, h), p["mlp"], held=held, top_k=top_k,
            choice=choice,
        )
        return hidden + out["routed"].reshape(b, s, h), out

    head = _head
    if checkpoint:
        layer, head = jax.checkpoint(layer), jax.checkpoint(head)

    hidden = params["embed_tokens"][ids]
    routings = []
    for i, p in enumerate(layers_in_order(params)):
        hidden, out = layer(
            hidden, p, None if choices is None else choices[i]
        )
        routings.append(out)
    hidden = _rms_norm(hidden, params["norm"], eps)
    noisy = hidden[:, :length]
    if shift:  # wrong: position p's hidden asked for x_{p+1}
        clean = jnp.roll(clean, -1, axis=1)
        weights = jnp.roll(weights, -1, axis=1).at[:, -1].set(0.0)
    ce = head(noisy, params["lm_head"], clean)
    stacked = {
        key: jnp.stack([r[key] for r in routings]) for key in routings[0]
    }
    return {
        "loss": jnp.sum(ce * weights.astype(ce.dtype)) / clean.size,
        "ce": ce, "hidden": hidden, "logits": noisy @ params["lm_head"],
        **stacked,
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
