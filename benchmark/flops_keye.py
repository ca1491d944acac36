"""Operations and bytes of Keye-VL-2.0's language model (``model_type:
KeyeVL2``: grouped-query attention over the keys a learned indexer selects,
every layer routed) and of the selected flash kernels, computed from shapes
— beside ``flops_lfm2.py`` (whose per-tile matmul and tensor counts of a
grouped kernel the selected kernels share).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, by part. ATTENTION IS COUNTED AT ITS SELECTED PAIRS: query t
attends min(t + 1, top-k) keys, so a row of S has

    k (k + 1) / 2 + (S - k) k          selected (query, key) pairs

(31,458,304 at S = 16,384, top-k 2,048) of the S (S + 1) / 2 a causal layer
has (134,225,920) — what the mask IS, whatever tiles the kernels visit to
apply it. The index scores are counted over the whole triangle (every pair
s <= t is scored once: 2 x 16 x 64 FLOPs), the indexer's projections beside
the main ones, the routed experts for the HELD ones at the expected share of
slots, the untied head over the held vocabulary rows. NOT counted: the
indexer's loss (it scores the triangle a second time and takes the main
attention's scores of the selected pairs again for its target), as remat's
replays are not.

A kernel's cost counts the (query tile, key tile) pairs it computes — the
tiles on and under the diagonal that hold a selected pair: ``tile_share`` of
the triangle's, the program's own gauge ``attn.select_tile_share``, 1 where
it is not given — for every QUERY head, q-side tensors ``heads`` wide, k / v
and their gradients ``kv_heads`` wide, and the int8 selection's computed
tiles, each read once.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lfm2 import _GQA


def selected_pairs(seq: int, top_k: int) -> int:
    """sum_t min(t + 1, top_k) over a row's ``seq`` queries."""
    k = min(top_k, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def triangle_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def triangle_tiles(seq: int, block_q: int, block_k: int) -> int:
    """(query tile, key tile) pairs on or under the diagonal: 528 at S =
    16,384 and 512 x 512 tiles."""
    return sum(
        (q0 + block_q - 1) // block_k + 1 for q0 in range(0, seq, block_q)
    )


def keye_parts_flops_per_row(sizes: Dict[str, float],
                             seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs of one row of ``seq`` tokens, by part;
    ``sizes``: the configuration file's ``sizes``."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    layers = sizes["num_hidden_layers"]
    return {
        "projections": layers * seq * (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
        ),
        "indexer_projections": layers * seq * 2 * h * (j * di + di + j),
        # QK^T and PV, a SELECTED (query, key) pair
        "attention": layers * 2 * 2 * heads * d * selected_pairs(
            seq, sizes["index_topk"]
        ),
        # every pair s <= t scored once: 16 dots of 64
        "index_scores": layers * 2 * j * di * triangle_pairs(seq),
        "router": layers * seq * 2 * h * sizes["num_experts"],
        "routed": layers * seq * (
            2 * 3 * h * sizes["moe_intermediate_size"]
            * sizes["num_experts_per_tok"]
            * sizes["held_experts"] / sizes["num_experts"]
        ),
        "head": seq * 2 * h * sizes["vocab_size"],
    }


def keye_train_flops_per_sample(sizes: Dict[str, float], seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens."""
    return 3.0 * sum(keye_parts_flops_per_row(sizes, seq).values())


def keye_parameters(sizes: Dict[str, float]) -> int:
    """Parameters held: per layer the four attention projections, the q and
    k norms, the indexer (W_qI, W_kI, W_wI, the key's LayerNorm), the
    router, two norms and the held experts; embedding, untied head and the
    final norm."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    j, di = sizes["index_n_heads"], sizes["index_head_dim"]
    layer = (
        h * (heads + 2 * kv) * d + heads * d * h + 2 * d
        + h * (j * di + di + j) + 2 * di
        + h * sizes["num_experts"] + 2 * h
        + sizes["held_experts"] * 3 * h * sizes["moe_intermediate_size"]
    )
    return int(
        sizes["num_hidden_layers"] * layer + 2 * sizes["vocab_size"] * h + h
    )


def sel_kernel_cost(
    kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
    head_dim: int, block_q: int, block_k: int, tile_share: float = 1.0,
    dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` (``flash_sel_fwd`` /
    ``_bwd_dq`` / ``_bwd_dkv`` / ``_bwd_tiled``) on ``batch`` rows of
    ``seq``: its grouped causal twin's matmuls a tile and tensors
    (``flops_lfm2._GQA``) over ``tile_share`` of the triangle's tiles, and
    those tiles of the int8 selection."""
    if not kernel.startswith("flash_sel_"):
        raise KeyError(f"no cost function for kernel {kernel!r}")
    k = _GQA[kernel.replace("flash_sel_", "flash_gqa_")]
    tiles = tile_share * triangle_tiles(seq, block_q, block_k)
    flops = (
        2.0 * block_q * block_k * head_dim * k["matmuls"] * tiles * batch
        * heads
    )
    tensors = batch * seq * head_dim * dtype_bytes * (
        k["q_tensors"] * heads + k["kv_tensors"] * kv_heads
    )
    rows = (batch * heads + batch) * seq * 4  # lse a head, bias a row
    selection = batch * tiles * block_q * block_k  # int8, once
    return flops, float(tensors + rows + selection)
