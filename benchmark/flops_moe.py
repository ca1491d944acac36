"""Operations and bytes of the latent-attention expert decoder
(``model_type: deepseek_v3``) and of the two-width causal flash kernels,
computed from shapes — beside ``flops_lm.py`` (one head width) and
``flops.py`` (ALBERT).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, causal attention at its triangle ((S+1)/2 keys a token on average)
with q and k ``qk`` wide and v and out ``v`` wide; the routed experts are
counted for the HELD experts only, at the EXPECTED share of slots (held /
routed experts of the top-k: what a balanced router sends here). The kernel
costs count the (query tile, key tile) pairs on and under the diagonal, each
a whole tile, with the real widths — nothing is padded to make them equal —
and every operand read once, every result written once.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lm import causal_tiles


def moe_lm_train_flops_per_sample(sizes: Dict[str, float], seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens;
    ``sizes``: the configuration file's ``sizes``."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    rank, f = sizes["kv_lora_rank"], sizes["moe_intermediate_size"]
    attention = (
        2 * h * heads * (nope + rope)  # W_q
        + 2 * h * (rank + rope)  # W_kva: the latent and the one rotary key
        + 2 * rank * heads * (nope + v)  # W_kvb
        + 2 * heads * v * h  # W_o
        # QK^T over 192, PV over 128, over the triangle
        + 2 * heads * (nope + rope + v) * (seq + 1) / 2
    )
    dense_layers = sizes["first_k_dense_replace"]
    expert_layers = sizes["num_hidden_layers"] - dense_layers
    sparse = (
        2 * h * sizes["n_routed_experts"]  # the router's 128 outputs
        + 2 * 3 * h * f * sizes["n_shared_experts"]
        + 2 * 3 * h * f * sizes["num_experts_per_tok"]
        * sizes["held_experts"] / sizes["n_routed_experts"]
    )
    per_token = (
        sizes["num_hidden_layers"] * attention
        + dense_layers * 2 * 3 * h * sizes["intermediate_size"]
        + expert_layers * sparse
        + 2 * h * sizes["vocab_size"]  # the untied head over the slice
    )
    return 3.0 * per_token * seq


# per visited tile and head: matmuls contracting or producing the q/k width,
# those of the v width; tensors read and written at each width; float32 rows
_KERNELS = {
    # QK^T | PV; reads q k | v, writes o; reads bias, writes lse
    "flash_mla_fwd": dict(qk=1, v=1, qk_tensors=2, v_tensors=2),
    # QK^T, dQ = dS·K | dP = dO·V^T; reads q k, writes dq | reads v dO O
    "flash_mla_bwd_dq": dict(qk=2, v=1, qk_tensors=3, v_tensors=3),
    # QK^T, dK = dS^T·Q | dP, dV = P^T·dO; reads q k, writes dk | reads
    # v dO O, writes dv
    "flash_mla_bwd_dkv": dict(qk=2, v=2, qk_tensors=3, v_tensors=4),
    # the one-sweep backward: QK^T, dK, dQ | dP, dV from ONE score tile;
    # reads q k, writes dq dk | reads v dO O, writes dv
    "flash_mla_bwd_tiled": dict(qk=3, v=2, qk_tensors=4, v_tensors=4),
}


def mla_kernel_cost(
    kernel: str, batch: int, heads: int, seq: int, qk_dim: int, v_dim: int,
    block_q: int, block_k: int, dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` on ``batch`` rows."""
    if kernel not in _KERNELS:
        raise KeyError(f"no cost function for kernel {kernel!r}")
    k = _KERNELS[kernel]
    bh = batch * heads
    flops = (
        2.0 * block_q * block_k * (k["qk"] * qk_dim + k["v"] * v_dim)
        * causal_tiles(seq, block_q, block_k) * bh
    )
    tensors = bh * seq * dtype_bytes * (
        k["qk_tensors"] * qk_dim + k["v_tensors"] * v_dim
    )
    rows = (bh + batch) * seq * 4  # lse per head, bias per row
    return flops, float(tensors + rows)
