"""Operations and bytes of the looped decoder (Ouro) and of the causal flash
kernels, computed from shapes — the causal-LM twin of ``flops.py``.

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted (recomputation shows as lower utilisation), causal attention at its
triangle: a token attends to (S+1)/2 keys on average. The kernel costs count
what each Pallas call must do for its shapes as the kernels are tiled: the
(query tile, key tile) pairs on and under the diagonal, each a whole tile
(a tile the diagonal crosses is computed whole and masked); bytes are every
operand read once and every result written once, the least any schedule
could move.
"""
from __future__ import annotations

from typing import Tuple


def ouro_train_flops_per_sample(
    hidden_size: int, intermediate_size: int, num_attention_heads: int,
    head_dim: int, vocab_size: int, num_hidden_layers: int,
    total_ut_steps: int, seq: int,
) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens."""
    width = num_attention_heads * head_dim
    per_token_layer = (
        2 * 4 * hidden_size * width  # q, k, v, o projections
        + 2 * 3 * hidden_size * intermediate_size  # gate, up, down
        + 2 * 2 * width * (seq + 1) / 2  # QK^T and PV over the triangle
    )
    per_token = total_ut_steps * (
        num_hidden_layers * per_token_layer
        + 2 * hidden_size * vocab_size  # the untied head, once a pass
    )
    return 3.0 * per_token * seq


def causal_tiles(seq: int, block_q: int, block_k: int) -> int:
    """(query tile, key tile) pairs a causal kernel visits: for query tile j
    the key tiles up to the one holding its last position."""
    return sum(
        (j * block_q + block_q - 1) // block_k + 1
        for j in range(seq // block_q)
    )


# matmuls of 2·Bq·Bk·D per visited tile and head; tensors of B·S·H·D read
# and written; float32 rows of B·H·S (bias is B·S) read and written
_KERNELS = {
    # QK^T, PV; reads q k v, writes o; reads bias, writes lse
    "flash_causal_fwd": (2, 3, 1),
    # QK^T, dP = dO·V^T, dQ = dS·K; reads q k v dO O, writes dq
    "flash_causal_bwd_dq": (3, 5, 1),
    # QK^T, dP, dV = P^T·dO, dK = dS^T·Q; reads q k v dO O, writes dk dv
    "flash_causal_bwd_dkv": (4, 5, 2),
    # the one-sweep backward: QK^T, dP, dV, dK, dQ from ONE score tile;
    # reads q k v dO O, writes dq dk dv
    "flash_causal_bwd_tiled": (5, 5, 3),
}


def causal_kernel_cost(
    kernel: str, batch: int, heads: int, seq: int, head_dim: int,
    block_q: int, block_k: int, dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` on a micro-batch of
    ``batch`` rows."""
    if kernel not in _KERNELS:
        raise KeyError(f"no cost function for kernel {kernel!r}")
    matmuls, reads, writes = _KERNELS[kernel]
    bh = batch * heads
    flops = (
        matmuls * 2.0 * block_q * block_k * head_dim
        * causal_tiles(seq, block_q, block_k) * bh
    )
    tensor = bh * seq * head_dim * dtype_bytes
    rows = (bh + batch) * seq * 4  # lse per head, bias per row
    return flops, float((reads + writes) * tensor + rows)
