"""Operations and bytes the algorithms need, computed from shapes.

Copied arithmetic, not imported: ``albert_train_flops_per_sample`` is
``bench.py``'s formula (matmuls only; recomputed operations of the remat
replay do not count, so recomputation shows as lower utilisation). The kernel
costs count what each Pallas call must do and must move for its shapes:
matmul FLOPs as 2·m·n·k, bytes as every operand read once and every result
written once (the least any schedule could move).
"""
from __future__ import annotations

from typing import Tuple


def albert_train_flops_per_sample(
    hidden_size: int, intermediate_size: int, embedding_size: int,
    vocab_size: int, num_hidden_layers: int, seq: int, max_predictions: int,
) -> float:
    """Model FLOPs of one forward + backward sample (backward = 2x forward)."""
    h, i, s = hidden_size, intermediate_size, seq
    e, v = embedding_size, vocab_size
    per_token_layer = (
        8 * h * h  # Q, K, V and attention-output projections
        + 4 * h * s  # QK^T scores + attention-weighted values
        + 4 * h * i  # FFN in + out
    )
    fwd = num_hidden_layers * per_token_layer * s
    fwd += 2 * e * h * s  # factorised embedding projection
    fwd += max_predictions * 2 * (h * e + e * v)  # gathered MLM head
    fwd += 2 * h * 2  # SOP head
    return 3.0 * fwd


def max_predictions_for(seq: int, mlm_probability: float = 0.15) -> int:
    """Width of the gathered MLM head per sequence (the program's rule,
    data/mlm.py: the expected masked count plus 4 of slack)."""
    return int(seq * mlm_probability) + 4


def flash_fwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one ``flash_fwd`` call: QK^T and PV, 2 matmuls of
    2·S·S·D per head; reads q, k, v and the [B·H, S] f32 bias, writes o and
    the f32 log-sum-exp."""
    bh = batch * heads
    flops = 2 * 2.0 * bh * seq * seq * head_dim
    tensor = bh * seq * head_dim * dtype_bytes
    bytes_ = 4 * tensor + 2 * bh * seq * 4
    return flops, float(bytes_)


def flash_bwd_fused_cost(batch: int, heads: int, seq: int, head_dim: int,
                         dtype_bytes: int = 2) -> Tuple[float, float]:
    """One ``flash_bwd_fused`` call: recompute QK^T, then dV, dP, dQ, dK —
    5 matmuls; reads q, k, v, do and three f32 [B·H, S] rows (bias, lse,
    delta), writes dq, dk, dv."""
    bh = batch * heads
    flops = 5 * 2.0 * bh * seq * seq * head_dim
    tensor = bh * seq * head_dim * dtype_bytes
    bytes_ = 7 * tensor + 3 * bh * seq * 4
    return flops, float(bytes_)


def kernel_cost(kernel: str, batch: int, heads: int, seq: int,
                head_dim: int) -> Tuple[float, float]:
    """Cost of one call of ``kernel`` for a micro-batch of ``batch`` rows."""
    if kernel == "flash_fwd":
        return flash_fwd_cost(batch, heads, seq, head_dim)
    if kernel == "flash_bwd_fused":
        return flash_bwd_fused_cost(batch, heads, seq, head_dim)
    raise KeyError(f"no cost function for kernel {kernel!r}")


def roofline_seconds(flops: float, bytes_: float, peaks) -> Tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    compute = flops / peaks["flops_per_s"]
    memory = bytes_ / peaks["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
