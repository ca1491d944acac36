"""Long-context attention: blockwise (memory-efficient) and ring (sequence-
parallel) variants.

The reference has NO long-context support (SURVEY.md §5: seq fixed at 512,
no ring/blockwise/Ulysses anywhere) — this module is a TPU-native extension
that makes sequence length a first-class scaling axis:

- ``blockwise_attention``: online-softmax attention computed in KV blocks
  under ``lax.scan`` — activation memory O(S·block) instead of O(S²), the
  single-device long-context workhorse (same math as FlashAttention).
- ``ring_attention``: shard the sequence over a mesh axis; each device holds
  S/n of Q, K, V and rotates its KV shard around the ring with
  ``lax.ppermute`` while accumulating online-softmax partials for its local
  queries. Peak memory O((S/n)²) per device and the KV transfer overlaps
  compute steps; collectives ride ICI. Exact (bitwise-stable softmax
  rescaling), not an approximation.

Both are bidirectional (ALBERT-style); an additive bias [B, S_kv] travels
with the KV shards.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_update(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Skv, H, D]
    v: jnp.ndarray,  # [B, Skv, H, D]
    bias: Optional[jnp.ndarray],  # [B, Skv] additive (0 keep / -inf drop)
    acc: jnp.ndarray,  # [B, Sq, H, D] fp32 running numerator
    row_max: jnp.ndarray,  # [B, Sq, H] fp32 running max
    row_sum: jnp.ndarray,  # [B, Sq, H] fp32 running denominator
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One online-softmax accumulation step against a KV block."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    block_max = jnp.max(s, axis=-1)  # [B, H, Sq]
    new_max = jnp.maximum(row_max, block_max.transpose(0, 2, 1))
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(s - new_max.transpose(0, 2, 1)[..., None])  # [B, H, Sq, K]
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    acc = acc * correction[..., None] + pv
    row_sum = row_sum * correction + jnp.sum(p, axis=-1).transpose(0, 2, 1)
    return acc, new_max, row_sum


def blockwise_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,  # [B, S] additive kv-position bias
    block_size: int = 512,
) -> jnp.ndarray:
    """Exact attention with KV processed in blocks via lax.scan."""
    b, s, h, d = q.shape
    num_blocks = max(1, s // block_size)
    assert s % num_blocks == 0, "seq length must divide block size grid"
    bs = s // num_blocks
    k_blocks = k.reshape(b, num_blocks, bs, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, num_blocks, bs, h, d).transpose(1, 0, 2, 3, 4)
    bias_blocks = (
        bias.reshape(b, num_blocks, bs).transpose(1, 0, 2)
        if bias is not None
        else None
    )

    acc = jnp.zeros((b, s, h, d), jnp.float32)
    row_max = jnp.full((b, s, h), NEG_INF, jnp.float32)
    row_sum = jnp.zeros((b, s, h), jnp.float32)

    def body(carry, blocks):
        acc, row_max, row_sum = carry
        if bias_blocks is not None:
            kb, vb, bb = blocks
        else:
            kb, vb = blocks
            bb = None
        acc, row_max, row_sum = _block_update(q, kb, vb, bb, acc, row_max, row_sum)
        return (acc, row_max, row_sum), None

    xs = (k_blocks, v_blocks, bias_blocks) if bias is not None else (k_blocks, v_blocks)
    (acc, row_max, row_sum), _ = jax.lax.scan(body, (acc, row_max, row_sum), xs)
    return (acc / row_sum[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S GLOBAL; sharded over ``axis`` by caller
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,  # [B, S]
    *,
    mesh: Mesh,
    axis: str = "seq",
) -> jnp.ndarray:
    """Sequence-parallel exact attention over a ring of devices.

    Inputs/outputs are GLOBAL arrays; shard them over ``axis`` on the S
    dimension (``P(None, axis)``...) before calling for zero relayout. Inside
    shard_map each device starts with its local KV shard and passes it to the
    next ring neighbour each step (lax.ppermute over ICI), accumulating
    online-softmax partials for its resident queries.
    """
    n = mesh.shape[axis]

    def local(q_l, k_l, v_l, bias_l):
        b, s_l, h, d = q_l.shape
        acc = jnp.zeros((b, s_l, h, d), jnp.float32)
        row_max = jnp.full((b, s_l, h), NEG_INF, jnp.float32)
        row_sum = jnp.zeros((b, s_l, h), jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(i, carry):
            acc, row_max, row_sum, k_cur, v_cur, bias_cur = carry
            acc, row_max, row_sum = _block_update(
                q_l, k_cur, v_cur, bias_cur, acc, row_max, row_sum
            )
            # rotate the KV shard to the next neighbour (skip after last use)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            bias_nxt = (
                jax.lax.ppermute(bias_cur, axis, perm)
                if bias_cur is not None
                else None
            )
            return acc, row_max, row_sum, k_nxt, v_nxt, bias_nxt

        carry = (acc, row_max, row_sum, k_l, v_l, bias_l)
        for i in range(n):  # static unroll: n is a mesh constant
            carry = body(i, carry)
        acc, row_max, row_sum = carry[:3]
        return (acc / row_sum[..., None]).astype(q_l.dtype)

    # the batch dim rides the mesh's data axis when one exists (dp x sp
    # composition: the trainer shards batches P("data", "seq")); a pure-sp
    # mesh replicates B
    b_axis = "data" if "data" in dict(mesh.shape) else None
    qkv_spec = P(b_axis, axis, None, None)
    bias_spec = P(b_axis, axis)
    if bias is None:
        fn = shard_map(
            lambda a, b_, c: local(a, b_, c, None),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
        )
        return fn(q, k, v)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, bias)


def dense_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference O(S²) attention for testing equivalence."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
