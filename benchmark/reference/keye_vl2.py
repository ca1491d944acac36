"""Keye-VL-2.0-30B-A3B's language model (``model_type: KeyeVL2``): its
training loss in plain ``jax.numpy``, float32 — no kernels, no scan, no tile
loop, no chunked head, no threshold search. Written from the equations:

    x [S, 2048], no bias in any projection, RMSNorm eps 1e-6, every layer
    alike, positions p = (p_t, p_h, p_w) [3, S] (the batch's
    ``position_ids``; three aranges where it has none):
    n = RMSNorm_in(x)
    q = W_q n [32 x 128];  k = W_k n, v = W_v n [4 x 128];  kv head j serves
    the 8 ADJACENT query heads
    q_h <- RMSNorm_q(q_h), k_j <- RMSNorm_k(k_j)    over a head's 128 lanes
    M-RoPE, rotate-half over the whole head: f_i = theta^(-2i/128), pair i
        turns by f_i x p_t[s] for i in 0..15, f_i x p_h[s] for i in 16..39,
        f_i x p_w[s] for i in 40..63 (sections [16, 24, 24], contiguous)
    indexer, from nd = stop_gradient(n):
        qI = W_qI nd [16 x 64];  kI = LayerNorm(W_kI nd) [64] (weight and
        bias, eps 1e-6);  w = W_wI nd [16];  qI and kI rotate-half over
        their 64 lanes, f_i = theta^(-2i/64), by p_t
        I[t, s] = sum_j w[t, j] relu(qI[t, j] · kI[s]) 64^-0.5 16^-0.5,
        s <= t
        S_t = the top-k keys s <= t by I[t, s] (all where t < k); ties to
        the lower s
    a_h[t] = softmax over s in S_t of (q_h[t] · k_j[s] / sqrt(128)) v_j[s]
    h = x + W_o concat_h(a_h);   m = RMSNorm_post(h)
    r = softmax(W_r m);  C = top-8(r);  w_e = r_e / sum_{c in C} r_c
    y = h + sum_{e in C, held} w_e W_down,e(silu(W_gate,e m) * W_up,e m)
    after the stack a final RMSNorm and the untied head.
    L_LM = sum_t u_t CE(logits_t, label_t) / sum_t u_t  (``loss_weights``)
    L_I  = mean over layers and queries of KL(pbar_t || softmax_{S_t} I[t]),
           pbar_t = stop_gradient(sum_h P_h[t]) / 32 over S_t
    L = L_LM + L_I

It reads the parameter tree the program trains (names as Flax lays them out:
``layers/layer_<i>`` stacked over the scanned periods, then
``tail_layer_<i>``) and imports nothing from ``dedloc_tpu``; the same SHARE
the program holds (``held``; the tree's own vocabulary rows).

Departures from a textbook forward, each for a stated reason:

- the experts are a Python loop over the HELD ones (dense: no sort);
- ``choices`` ([layers, T, k]) and ``selections`` ([layers, B, S, S], not 0
  where selected): route, and attend, by THESE instead of the reference's
  own top-8 / top-k. Both are discrete: a near-tie flips under bf16
  rounding and a flipped key or slot changes its query's gradient wholesale,
  so a comparison of losses and gradients gives the reference the program's
  choices. The reference's OWN selection — ``lax.top_k`` of its own I, given
  the same upstream — is always returned (``selection``), to be compared
  apart;
- the whole mixer of a layer a block of ``ROW_BLOCK`` query rows at a time
  (``lax.map``): the block's index scores, its top-k, its [32, rows, S]
  masked scores, its rows of pbar and its KL terms live at once, nothing
  [S, S] in float32 ever (four layers of those are 22 GB of scratch at S =
  16,384: read offline, PR 51); with ``checkpoint=True`` every layer, every
  block and the head run under ``jax.checkpoint`` (values unchanged).

``dtype`` exists to read what a lower precision does: bfloat16 turns every
weight, activation, accumulation, softmax and index score into bf16.
Callers run it under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EXPERTS = ("experts_gate", "experts_up", "experts_down")
ROW_BLOCK = 128  # query rows of a layer's mixer computed at a time


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * p["weight"]


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def mrope_angles(position_ids, width, theta, sections):
    """[B, S, width]: pair i (of width / 2, repeated over both halves) at
    f_i x the position of the stream its section names. ``position_ids``
    [3, B, S]."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, width, 2, dtype=jnp.float32) / width
    )
    stream = np.repeat(np.arange(3), sections)
    position = jnp.stack(
        [position_ids[int(s), :, :] for s in stream], axis=-1
    ).astype(jnp.float32)  # [B, S, width / 2]
    angles = position * inv_freq
    return jnp.concatenate([angles, angles], axis=-1)


def _rotate(x, angles):
    """x [B, S, H, D], angles [B, S, D]: x cos + rotate_half(x) sin."""
    d = x.shape[-1]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return x * cos + rotated * sin


def indexer(nd, p, angles, *, heads, eps):
    """(qI [B, S, heads, D], kI [B, S, D], w [B, S, heads]) of the detached
    normalised input ``nd``: the key LayerNorm'd, qI and kI rotated."""
    b, s, _ = nd.shape
    q = (nd @ p["wq"]["kernel"]).reshape(b, s, heads, -1)
    k = _layer_norm(nd @ p["wk"]["kernel"], p["k_norm_weight"],
                    p["k_norm_bias"], eps)
    w = nd @ p["weights_proj"]["kernel"]
    return _rotate(q, angles), _rotate(k[:, :, None, :], angles)[:, :, 0], w


def top_k_selection(scores, causal, top_k):
    """[B, R, S] bool: each row's ``top_k`` keys among ``causal`` [R, S]
    with the largest score (all of them where a row has fewer), ties to the
    lower s: ``lax.top_k``."""
    b, r, s = scores.shape
    masked = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    _, chosen = jax.lax.top_k(masked, min(top_k, s))
    picked = jnp.zeros((b, r, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None], chosen
    ].set(True)
    return picked & causal


def _attend_rows(q, q_index, w_index, given, first, k, v, k_index, *,
                 top_k):
    """A block of R query rows, the first at position ``first``, against
    every key: q [B, R, H, D], q_index [B, R, heads, Di], w_index [B, R,
    heads]; k, v [B, S, H, D], k_index [B, S, Di]; ``given`` [B, R, S] (not
    0: attend these) or None (attend the block's own top-k). -> (context
    [B, R, H, D], the block's OWN selection [B, R, S] bool, the sum over its
    queries of KL(pbar || softmax over the attended of I), the sum of the
    index peaks)."""
    heads, rows = q_index.shape[2], q.shape[1]
    dots = jnp.einsum("brjd,bsd->brjs", q_index, k_index)
    index = jnp.sum(w_index[..., None] * jax.nn.relu(dots), axis=2) * (
        q_index.shape[-1] ** -0.5
    ) * (heads ** -0.5)
    s = k.shape[1]
    causal = jnp.arange(s)[None, :] <= first + jnp.arange(rows)[:, None]
    own = top_k_selection(jax.lax.stop_gradient(index), causal, top_k)
    seen = own if given is None else (given != 0) & causal
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype)
    )
    probs = jax.nn.softmax(
        jnp.where(seen[:, None], scores, -jnp.inf), axis=-1
    )
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    target = jax.lax.stop_gradient(
        jnp.sum(probs, axis=1).astype(jnp.float32) / q.shape[2]
    )
    index = index.astype(jnp.float32)
    log_q = jax.nn.log_softmax(jnp.where(seen, index, -jnp.inf), axis=-1)
    kl = jnp.where(
        target > 0,
        target * (jnp.log(jnp.where(target > 0, target, 1.0))
                  - jnp.where(seen, log_q, 0.0)),
        0.0,
    )  # 0 log 0 = 0
    peak = jnp.sum(seen, axis=-1) * jnp.exp(jnp.max(log_q, axis=-1))
    return ctx, own, jnp.sum(kl), jax.lax.stop_gradient(jnp.sum(peak))


def attention(x, p, p_index, given, angles, index_angles, *, num_heads,
              kv_heads, index_heads, index_top_k, eps, checkpoint=False):
    """-> (W_o concat_h(a_h) [B, S, H], the layer's OWN selection [B, S, S]
    bool, its L_I, its index peak), a block of ``ROW_BLOCK`` query rows at a
    time: index scores, top-k, masked softmax, pbar and the KL of a block
    live at once, nothing [S, S] in float32."""
    b, s, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, num_heads, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, s, kv_heads, -1)
    q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    q, k = _rotate(q, angles), _rotate(k, angles)
    # each kv head serves num_heads / kv_heads adjacent query heads
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    q_index, k_index, w_index = indexer(
        jax.lax.stop_gradient(x), p_index, index_angles, heads=index_heads,
        eps=eps,
    )
    rows = min(ROW_BLOCK, s)

    def one(q, q_index, w_index, given, first, k, v, k_index):
        return _attend_rows(q, q_index, w_index, given, first, k, v,
                            k_index, top_k=index_top_k)

    if checkpoint:
        one = jax.checkpoint(one)

    def blocks(t):  # [B, S, ...] -> [blocks, B, rows, ...]
        return jnp.moveaxis(
            t.reshape((b, s // rows, rows) + t.shape[2:]), 1, 0
        )

    ctx, own, kl, peak = jax.lax.map(
        lambda block: one(*block, k, v, k_index),
        (blocks(q), blocks(q_index), blocks(w_index),
         None if given is None else blocks(given),
         jnp.arange(0, s, rows)),
    )
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, -1)
    own = jnp.moveaxis(own, 0, 1).reshape(b, s, s)
    return (ctx @ p["o_proj"]["kernel"], own, jnp.sum(kl) / (b * s),
            jnp.sum(peak) / (b * s))


def route(logits, top_k, choice=None):
    """(choice [T, k], weights [T, k]): the top k of softmax(logits),
    renormalised over the chosen (``norm_topk_prob``)."""
    probs = jax.nn.softmax(logits, axis=-1)
    if choice is None:
        _, choice = jax.lax.top_k(probs, top_k)
    picked = jnp.take_along_axis(probs, choice, axis=-1)
    return choice, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_ffn(x, p, *, held, top_k, choice=None):
    """x [T, H] -> (the HELD experts' part of the sum over the chosen
    experts, the router's logits [T, E], choice [T, k])."""
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
    return routed, logits, choice


def _head(hidden, lm_head, labels):
    log_probs = jax.nn.log_softmax(hidden @ lm_head, axis=-1)
    return -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]


def layers_in_order(params):
    """The parameter trees of the layers as the model applies them: the
    scanned periods' positions (``layers/layer_<i>``, every leaf stacked
    over the periods), then the layers after the last whole period."""
    period = params.get("layers", {})
    positions = sorted(period, key=lambda name: int(name.split("_")[-1]))
    periods = jax.tree.leaves(period)[0].shape[0] if period else 0
    out = [
        jax.tree.map(lambda x: x[n], period[name])
        for n in range(periods) for name in positions
    ]
    i = 0
    while f"tail_layer_{i}" in params:
        out.append(params[f"tail_layer_{i}"])
        i += 1
    return out


def forward(params, batch, *, num_heads, kv_heads, eps, theta, sections,
            index_heads, index_top_k, top_k, held, choices=None,
            selections=None, checkpoint=False, dtype=jnp.float32):
    """-> dict: ``loss`` (L_LM + L_I), ``lm``, ``index_kl``, ``ce`` [B, S],
    ``hidden`` [B, S, H] (after the final norm), ``scores`` [layers, T, E]
    (router logits), ``choice`` [layers, T, k], ``routed`` [layers, T, H]
    (each layer's routed output: what the shares of a deployment add up
    to), ``selection`` [layers, B, S, S] bool (the reference's OWN top-k of
    its own index scores, whatever it was told to attend by),
    ``select_missed`` [layers] (pairs of the GIVEN selection that are not in
    that own top-k: a check reads this and never holds four [S, S] masks at
    once), ``index_peak`` [layers]. ``dtype``: float32, the reference; bfloat16 is the reading of
    what a precision BELOW the cell's does."""
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    ids, labels = batch["input_ids"], batch["labels"]
    b, s = ids.shape
    position_ids = batch.get("position_ids")
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(s), (3, b, s))
    weights = batch.get("loss_weights")
    if weights is None:
        weights = jnp.ones((b, s), jnp.float32)
    head_dim = layers_in_order(params)[0]["self_attn"]["q_norm"][
        "weight"
    ].shape[-1]
    angles = mrope_angles(position_ids, head_dim, theta, sections)

    def layer(hidden, p, choice, given):
        n = _rms_norm(hidden, p["input_layernorm"], eps)
        index_dim = p["indexer"]["k_norm_weight"].shape[-1]
        mixed, own, kl, peak = attention(
            n, p["self_attn"], p["indexer"], given, angles,
            mrope_angles(position_ids, index_dim, theta,
                         (index_dim // 2, 0, 0)),
            num_heads=num_heads, kv_heads=kv_heads, index_heads=index_heads,
            index_top_k=index_top_k, eps=eps, checkpoint=checkpoint,
        )
        hidden = hidden + mixed
        m = _rms_norm(hidden, p["post_attention_layernorm"], eps)
        routed, logits, choice = moe_ffn(
            m.reshape(b * s, -1), p["mlp"], held=held, top_k=top_k,
            choice=choice,
        )
        # of the GIVEN selection's pairs, those the layer's own top-k lacks
        missed = jnp.zeros([], jnp.int32) if given is None else jnp.sum(
            (given != 0) & ~own, dtype=jnp.int32
        )
        return hidden + routed.reshape(hidden.shape), {
            "scores": logits, "choice": choice, "routed": routed,
            "selection": own, "index_kl": kl, "index_peak": peak,
            "select_missed": missed,
        }

    head = _head
    if checkpoint:
        layer, head = jax.checkpoint(layer), jax.checkpoint(head)

    hidden = params["embed_tokens"][ids]
    outs = []
    for i, p in enumerate(layers_in_order(params)):
        hidden, out = layer(
            hidden, p, None if choices is None else choices[i],
            None if selections is None else selections[i],
        )
        outs.append(out)
    hidden = _rms_norm(hidden, params["norm"], eps)
    ce = head(hidden, params["lm_head"], labels)
    stacked = {key: jnp.stack([o[key] for o in outs]) for key in outs[0]}
    lm = jnp.sum(ce * weights.astype(ce.dtype)) / jnp.maximum(
        jnp.sum(weights), 1.0
    ).astype(ce.dtype)
    kl = jnp.mean(stacked["index_kl"])
    # ``index_kl``: the mean over the layers (``stacked`` holds each one's)
    return {
        **stacked, "loss": lm.astype(jnp.float32) + kl, "lm": lm,
        "index_kl": kl, "ce": ce, "hidden": hidden,
    }


def loss_fn(params, batch, **kwargs):
    return forward(params, batch, **kwargs)["loss"]
