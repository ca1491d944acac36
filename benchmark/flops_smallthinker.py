"""Operations and bytes of the band-and-global expert decoder
(SmallThinker-21BA3B: sliding-window RoPE attention in three layers of four
beside a global NoPE layer, every layer routed) and of the band flash
kernels, computed from shapes — beside ``flops_lfm2.py`` (whose grouped
causal kernel cost the global layer's kernels are held to).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, by KIND of layer: both kinds project q over ``heads`` and k / v over
``kv_heads`` and route the same experts (the HELD ones at the expected share
of slots); they differ in the (query, key) pairs attention computes — the
triangle for a global layer, ``min(i + 1, band)`` keys for query i of a band
layer — and the untied head runs over the held vocabulary rows.

A band kernel's cost counts the (query tile, key tile) pairs that hold at
least one visible pair — whole tiles, the two crossed edges too — for every
QUERY head, with q-side tensors ``heads`` wide and k / v and their gradients
``kv_heads`` wide, read once, written once. The tile arithmetic is the mask
description of ``dedloc_tpu/ops/flash_attention.py`` re-stated here (query i
sees keys i - band + 1 .. i), not imported: the yardstick counts what the
mask IS, whatever the kernels visit.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lfm2 import gqa_kernel_cost
from benchmark.flops_lm import causal_tiles


def band_pairs(seq: int, band: int) -> int:
    """(query, key) pairs inside a band: min(i + 1, band) keys for query i."""
    band = min(band, seq)
    return band * (band + 1) // 2 + (seq - band) * band


def band_tiles(seq: int, block_q: int, block_k: int, band: int) -> int:
    """(query tile, key tile) pairs that hold a visible pair: for query
    tile j the key tiles from the one holding key j·Bq - band + 1 to the
    one holding key j·Bq + Bq - 1. 252 at 16,384 / 512 / 4,096 (528 under
    the causal mask alone, which is ``band >= seq``)."""
    total = 0
    for j in range(seq // block_q):
        first = max(j * block_q - band + 1, 0) // block_k
        last = (j * block_q + block_q - 1) // block_k
        total += last - first + 1
    return total


def smallthinker_parts_flops_per_token(sizes: Dict[str, float],
                                       seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of ONE layer of each kind and of the
    head; ``sizes``: the configuration file's ``sizes``."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    projections = 2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
    routed = (
        2 * h * sizes["num_experts"]  # the router's 64 outputs
        + 2 * 3 * h * sizes["moe_intermediate_size"]
        * sizes["num_experts_per_tok"]
        * sizes["held_experts"] / sizes["num_experts"]
    )
    pair = 2 * 2 * heads * d  # QK^T and PV, a (query, key) pair
    return {
        "global_nope": (
            projections + routed + pair * band_pairs(seq, seq) / seq
        ),
        "band_rope": (
            projections + routed
            + pair * band_pairs(seq, sizes["sliding_window_size"]) / seq
        ),
        "head": 2 * h * sizes["vocab_size"],
    }


def smallthinker_train_flops_per_sample(sizes: Dict[str, float],
                                        seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens: each
    kind's layer times the layers of that kind the cut runs
    (``sizes['global_layers']``, ``sizes['band_layers']``)."""
    part = smallthinker_parts_flops_per_token(sizes, seq)
    per_token = (
        sizes["global_layers"] * part["global_nope"]
        + sizes["band_layers"] * part["band_rope"] + part["head"]
    )
    return 3.0 * per_token * seq


def smallthinker_parameters(sizes: Dict[str, float]) -> int:
    """Parameters held: per layer the four attention projections, the
    router, two norms and the held experts; embedding, untied head and the
    final norm."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layer = (
        h * (heads + 2 * kv) * d + heads * d * h + h * sizes["num_experts"]
        + 2 * h
        + sizes["held_experts"] * 3 * h * sizes["moe_intermediate_size"]
    )
    layers = sizes["global_layers"] + sizes["band_layers"]
    return int(layers * layer + 2 * sizes["vocab_size"] * h + h)


def band_kernel_cost(
    kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
    head_dim: int, block_q: int, block_k: int, band: int,
    dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` (``flash_band_fwd`` /
    ``_bwd_dq`` / ``_bwd_dkv`` / ``_bwd_tiled``) on ``batch`` rows: its
    grouped causal twin's matmuls a tile and tensors
    (``flops_lfm2.gqa_kernel_cost``), over the band's tiles in place of the
    triangle's."""
    if not kernel.startswith("flash_band_"):
        raise KeyError(f"no cost function for kernel {kernel!r}")
    flops, bytes_ = gqa_kernel_cost(
        kernel.replace("flash_band_", "flash_gqa_"), batch, heads, kv_heads,
        seq, head_dim, block_q, block_k, dtype_bytes,
    )
    tiles = band_tiles(seq, block_q, block_k, band)
    return flops * tiles / causal_tiles(seq, block_q, block_k), bytes_
