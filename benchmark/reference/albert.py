"""ALBERT (Lan et al., 2019) pre-training loss in plain ``jax.numpy``, float32.

Forward: factorised embeddings (word + position + type → LayerNorm → project
to hidden), ONE transformer block applied ``num_hidden_layers`` times
(post-LN: self-attention + residual + LN, tanh-GELU FFN + residual + LN), a
tanh pooler on token 0; heads: MLM on the gathered masked positions (dense →
GELU → LN → the tied word-embedding table + bias) and sentence-order
prediction on the pooled vector. Loss: weighted-mean MLM cross-entropy + mean
SOP cross-entropy. Gradients come from ``jax.grad`` of this function.

It reads the same parameter tree the program trains (names as Flax lays them
out) and imports nothing from ``dedloc_tpu``. Departures from a textbook
forward, each for a stated reason:

- the layer loop is a ``lax.scan`` whose body is wrapped in
  ``jax.checkpoint``: the 24 iterations share one block, and without
  recomputation the float32 activations of even two rows would outgrow the
  cell under test and report ITS peak memory; values are unchanged;
- the additive attention mask uses -1e9 like the program (all-ones masks in
  the benchmark's batches, so it never binds).

Callers run it under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3))
    )


def _block(hidden, p, bias, num_heads, eps):
    b, s, h = hidden.shape
    d = h // num_heads
    att = p["attention"]

    def heads(x):
        return x.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

    q, k, v = (heads(_dense(hidden, att[n])) for n in ("query", "key", "value"))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(d)) + bias
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    hidden = _layer_norm(_dense(ctx, att["dense"]) + hidden,
                         att["layernorm"], eps)
    ffn = _dense(_gelu(_dense(hidden, p["ffn"])), p["ffn_output"])
    return _layer_norm(ffn + hidden, p["layernorm"], eps)


def loss_fn(params, batch, num_hidden_layers, num_attention_heads,
            layer_norm_eps=1e-12):
    """MLM + SOP loss of ``batch`` (the program's gathered layout:
    ``mlm_positions`` / ``mlm_label_ids`` / ``mlm_weights``)."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    a = params["albert"]
    ids = batch["input_ids"]
    s = ids.shape[1]
    emb = (
        a["word_embeddings"]["embedding"][ids]
        + a["position_embeddings"]["embedding"][jnp.arange(s)][None]
        + a["token_type_embeddings"]["embedding"][batch["token_type_ids"]]
    )
    emb = _layer_norm(emb, a["embeddings_layernorm"], layer_norm_eps)
    hidden = _dense(emb, a["embedding_projection"])
    bias = jnp.where(
        batch["attention_mask"][:, None, None, :] > 0, 0.0, -1e9
    ).astype(jnp.float32)
    block = a["encoder"]["layer"]["block"]

    @jax.checkpoint
    def body(h, _):
        return _block(h, block, bias, num_attention_heads, layer_norm_eps), None

    hidden, _ = jax.lax.scan(body, hidden, None, length=num_hidden_layers)
    pooled = jnp.tanh(_dense(hidden[:, 0], a["pooler"]))

    picked = jnp.take_along_axis(
        hidden, batch["mlm_positions"][..., None].astype(jnp.int32), axis=1
    )
    x = _layer_norm(
        _gelu(_dense(picked, params["mlm_dense"])),
        params["mlm_layernorm"], layer_norm_eps,
    )
    logits = x @ a["word_embeddings"]["embedding"].T + params["mlm_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, batch["mlm_label_ids"][..., None], axis=-1
    )[..., 0]
    w = batch["mlm_weights"].astype(jnp.float32)
    mlm = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    sop_logp = jax.nn.log_softmax(
        _dense(pooled, params["sop_classifier"]), axis=-1
    )
    sop = -jnp.mean(
        jnp.take_along_axis(sop_logp, batch["sop_labels"][:, None], axis=-1)
    )
    return mlm + sop
