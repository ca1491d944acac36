"""Operations and bytes of the block-diffusion expert decoder (``model_type:
sdar_moe``: SDAR-30B-A3B-Chat — a noisy and a clean copy of every row
through one stack of grouped-query, q / k-normed, fully routed layers) and of
the block-diffusion flash kernels, computed from shapes — beside
``flops_lfm2.py`` (whose per-tile matmul and tensor counts of a grouped
kernel the block-diffusion kernels share).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, by part: the projections, the router and the routed experts (the
HELD ones at the expected share of slots) run over BOTH streams' 2L
positions, attention over its VISIBLE (query, key) pairs, the untied head
over the noisy stream's L positions and the held vocabulary rows. A SAMPLE
is one row of L clean tokens.

The visibility rule, re-stated here and not imported (the yardstick counts
what the mask IS, whatever the kernels visit): the 2L positions are a NOISY
stream then a CLEAN one, each in blocks of B positions, b(p) = p // B in its
stream; query i sees key j iff

    i clean:  j clean and b(j) <= b(i)
    i noisy: (j noisy and b(j) == b(i)) or (j clean and b(j) < b(i))

A kernel's cost counts the (query tile, key tile) pairs that hold at least
one visible pair — whole tiles, the crossed diagonal ones too — for every
QUERY head, with q-side tensors ``heads`` wide and k / v and their gradients
``kv_heads`` wide over the 2L positions, read once, written once.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lfm2 import _GQA


def bd_pairs(length: int, block: int) -> int:
    """Visible (query, key) pairs of one row: clean x clean L(L + B) / 2,
    noisy x clean L(L - B) / 2, noisy x noisy L·B."""
    return length * length + length * block


def bd_tiles(length: int, block_q: int, block_k: int, block: int) -> int:
    """(query tile, key tile) pairs that hold a visible pair; tiles are
    tiles of ONE stream. 80 at L = 4,096, 512 x 512 tiles and blocks of 4:
    clean x clean 36, noisy x clean 36, the 8 noisy diagonal tiles."""
    total = 0
    for q0 in range(0, length, block_q):
        q_first, q_last = q0 // block, (q0 + block_q - 1) // block
        for k0 in range(0, length, block_k):
            k_first, k_last = k0 // block, (k0 + block_k - 1) // block
            total += k_first <= q_last  # clean query, clean key
            total += k_first < q_last  # noisy query, clean key
            total += k_first <= q_last and q_first <= k_last  # both noisy
    return total


def sdar_parts_flops_per_row(sizes: Dict[str, float],
                             length: int) -> Dict[str, float]:
    """Forward matmul FLOPs of one row of ``length`` clean tokens, by part;
    ``sizes``: the configuration file's ``sizes``."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers, positions = sizes["num_hidden_layers"], 2 * length
    return {
        "projections": layers * positions * (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
        ),
        # QK^T and PV, a visible (query, key) pair
        "attention": layers * 2 * 2 * heads * d * bd_pairs(
            length, sizes["block_length"]
        ),
        "router": layers * positions * 2 * h * sizes["num_experts"],
        "routed": layers * positions * (
            2 * 3 * h * sizes["moe_intermediate_size"]
            * sizes["num_experts_per_tok"]
            * sizes["held_experts"] / sizes["num_experts"]
        ),
        "head": length * 2 * h * sizes["vocab_size"],
    }


def sdar_train_flops_per_sample(sizes: Dict[str, float],
                                length: int) -> float:
    """Model FLOPs of one forward + backward row of ``length`` clean
    tokens (the stack's 2 x ``length`` positions are inside)."""
    return 3.0 * sum(sdar_parts_flops_per_row(sizes, length).values())


def sdar_parameters(sizes: Dict[str, float]) -> int:
    """Parameters held: per layer the four attention projections, the q and
    k norms (a weight per lane), the router, two norms and the held
    experts; embedding, untied head and the final norm."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layer = (
        h * (heads + 2 * kv) * d + heads * d * h + 2 * d
        + h * sizes["num_experts"] + 2 * h
        + sizes["held_experts"] * 3 * h * sizes["moe_intermediate_size"]
    )
    return int(
        sizes["num_hidden_layers"] * layer + 2 * sizes["vocab_size"] * h + h
    )


def bd_kernel_cost(
    kernel: str, batch: int, heads: int, kv_heads: int, length: int,
    head_dim: int, block_q: int, block_k: int, block: int,
    dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` (``flash_bd_fwd`` /
    ``_bwd_dq`` / ``_bwd_dkv`` / ``_bwd_tiled``) on ``batch`` rows of
    ``length`` clean tokens (2 x ``length`` positions): its grouped causal
    twin's matmuls a tile and tensors (``flops_lfm2._GQA``), over the rule's
    tiles and both streams' positions."""
    if not kernel.startswith("flash_bd_"):
        raise KeyError(f"no cost function for kernel {kernel!r}")
    k = _GQA[kernel.replace("flash_bd_", "flash_gqa_")]
    positions = 2 * length
    flops = (
        2.0 * block_q * block_k * head_dim * k["matmuls"]
        * bd_tiles(length, block_q, block_k, block) * batch * heads
    )
    tensors = batch * positions * head_dim * dtype_bytes * (
        k["q_tensors"] * heads + k["kv_tensors"] * kv_heads
    )
    rows = (batch * heads + batch) * positions * 4  # lse a head, bias a row
    return flops, float(tensors + rows)
