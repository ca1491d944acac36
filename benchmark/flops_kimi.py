"""Operations and bytes of Kimi Linear (``model_type: kimi_linear``: Kimi
Delta Attention in three layers of four beside NoPE latent attention, one
leading dense layer, then sigmoid top-8 of 256 experts beside a shared one)
at ONE CHIP'S SHARE — ``held_heads`` of every mixer's heads, ``held_experts``
of every sparse layer's experts, the held vocabulary rows — and of the KDA
kernel pair, computed from shapes; beside ``flops_moe.py`` (the two-width
latent-attention kernels, counted here AT THE HELD HEADS).

Model FLOPs are matmuls, backward = 2x forward, the remat replay not
counted, by part. The KDA mixer's projections are q / k / v, the two
low-rank gates (H -> rank -> heads x 128, twice), the write strength (H ->
heads) and the out-projection; its convolution, norms and gates are
element-wise and count nothing. The chunked delta rule itself IS counted
(``kda_chunk_flops``): per head and chunk of C tokens the products of its
algebra — Akk and Aqk (2·C·C·d each), the unit-triangular solve (2·C³/3),
U and W (2·C·C·d each), W S, (Q ⊙ e^G) S and the state's update (2·C·d·d
each) and Aqk V' (2·C·C·d): 11.71 MFLOP at C = 64, d = 128 — what any
chunked schedule of the rule does, not what this tree's kernel spends on
its levels and split operands. Latent attention is counted at its triangle
((S+1)/2 keys a token, q / k 192 wide beside v 128); the routed experts for
the HELD ones at the expected share of slots; the untied head over the slice.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_moe import mla_kernel_cost


def kda_chunk_flops(chunk: int, dk: int, dv: int) -> Dict[str, float]:
    """The chunked rule's products of one head's chunk, forward and backward
    (the backward recomputes the forward's but O, then one product a
    cotangent)."""
    cc_k, cc_v = 2.0 * chunk * chunk * dk, 2.0 * chunk * chunk * dv
    state = 2.0 * chunk * dk * dv
    solve = 2.0 * chunk ** 3 / 3
    forward = (
        2 * cc_k  # Akk, Aqk
        + solve
        + cc_v + cc_k  # U, W
        + 3 * state  # W S, (Q e^G) S, the state's update
        + cc_v  # Aqk V'
    )
    backward = (
        forward - state - cc_v  # recomputed: everything but the two of O
        + cc_v + state  # dV' = Aqkᵀ dO + (K e^{G_C - G}) dS
        + cc_v  # dAqk = dO V'ᵀ
        + 4 * state  # d(Q e^G), d(K e^{G_C - G}), dW, and dS's Qᵀ dO
        + state  # dS's Wᵀ dV'
        + cc_v + cc_k  # Xᵀ dU, Xᵀ dW
        + cc_v + cc_k  # dA = -(dRV Uᵀ + dRK Wᵀ)
        + 4 * cc_k  # dAkk and dAqk into q and k, a row and a column role
    )
    return {"kda_fwd": forward, "kda_bwd": backward}


def kda_kernel_cost(kernel: str, batch: int, heads: int, seq: int, dk: int,
                    dv: int, chunk: int,
                    dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` (``kda_fwd`` / ``kda_bwd``)
    on ``batch`` rows of ``seq`` tokens and the ``heads`` the call HAS:
    every operand read once and every result written once — q, k, v (and dO,
    o, dq, dk, dv) in the compute dtype, g and dg in float32, beta and dbeta
    a float32 a token-head, and the float32 [dk, dv] state entering every
    chunk, which the forward writes and the backward reads."""
    chunks = batch * heads * (seq // chunk)
    tokens = batch * heads * seq
    flops = kda_chunk_flops(chunk, dk, dv)[kernel] * chunks
    narrow, wide = tokens * dtype_bytes, tokens * 4
    states = chunks * dk * dv * 4
    if kernel == "kda_fwd":
        bytes_ = narrow * (2 * dk + 2 * dv) + wide * (dk + 1) + states
    else:
        bytes_ = (
            narrow * (4 * dk + 3 * dv)  # q k dq dk | v dO dv
            + wide * 2 * (dk + 1) + states
        )
    return flops, float(bytes_)


def held_mla_kernel_cost(kernel: str, batch: int, sizes: Dict[str, float],
                         seq: int) -> Tuple[float, float]:
    """``flops_moe.mla_kernel_cost`` of a two-width causal kernel at the
    heads the call HAS: ``sizes['held_heads']``, not the published 32."""
    block = min(sizes["attention_block_size"], seq)
    return mla_kernel_cost(
        kernel, batch, sizes["held_heads"], seq,
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        sizes["v_head_dim"], block, block,
    )


def kimi_parts_flops_per_token(sizes: Dict[str, float],
                               seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of every part, summed over the layers that run
    it; ``sizes``: the configuration file's ``sizes``."""
    h, heads = sizes["hidden_size"], sizes["held_heads"]
    d, rank = sizes["kda_head_dim"], sizes["kda_gate_rank"]
    nope, rope, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    chunk = sizes["kda_chunk"]
    f = sizes["moe_intermediate_size"]
    return {
        "kda_projections": sizes["kda_layers"] * (
            3 * 2 * h * heads * d  # W_q, W_k, W_v
            + 2 * (2 * h * rank + 2 * rank * heads * d)  # the two gates
            + 2 * h * heads  # W_b
            + 2 * heads * d * h  # W_o
        ),
        "kda_rule": sizes["kda_layers"] * heads * (
            kda_chunk_flops(chunk, d, d)["kda_fwd"] / chunk
        ),
        "mla": sizes["mla_layers"] * (
            2 * h * heads * (nope + rope)  # W_q
            + 2 * h * (sizes["kv_lora_rank"] + rope)  # W_kva
            + 2 * sizes["kv_lora_rank"] * heads * (nope + v)  # W_kvb
            + 2 * heads * v * h  # W_o
            + 2 * heads * (nope + rope + v) * (seq + 1) / 2  # the triangle
        ),
        "dense_ffn": sizes["dense_ffn_layers"] * 2 * 3 * h * sizes[
            "intermediate_size"
        ],
        "routed_ffn": sizes["routed_ffn_layers"] * (
            2 * h * sizes["num_experts"]  # the router's 256 outputs
            + 2 * 3 * h * f * sizes["num_shared_experts"]
            + 2 * 3 * h * f * sizes["num_experts_per_token"]
            * sizes["held_experts"] / sizes["num_experts"]
        ),
        "head": 2 * h * sizes["vocab_size"],
    }


def kimi_train_flops_per_sample(sizes: Dict[str, float], seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens."""
    return 3.0 * seq * sum(kimi_parts_flops_per_token(sizes, seq).values())


def kimi_parameters(sizes: Dict[str, float]) -> Dict[str, int]:
    """Parameters held, by part (``total`` their sum over the layers run)."""
    h, heads = sizes["hidden_size"], sizes["held_heads"]
    d, rank = sizes["kda_head_dim"], sizes["kda_gate_rank"]
    nope, rope, v = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    latent, f = sizes["kv_lora_rank"], sizes["moe_intermediate_size"]
    wide = heads * d
    kda = (
        3 * h * wide + 3 * wide * sizes["short_conv_kernel_size"]  # q k v, taps
        + heads  # A_log
        + h * rank + rank * wide + wide  # W_fa, W_fb, dt_bias
        + h * heads  # W_b
        + h * rank + rank * wide + wide  # W_ga, W_gb and its bias
        + d  # the gated norm's weight
        + wide * h  # W_o
    )
    mla = (
        h * heads * (nope + rope) + h * (latent + rope) + latent
        + latent * heads * (nope + v) + heads * v * h
    )
    dense_ffn = 3 * h * sizes["intermediate_size"]
    experts = sizes["held_experts"] * 3 * h * f
    routed_ffn = (
        experts + sizes["num_shared_experts"] * 3 * h * f
        + h * sizes["num_experts"] + sizes["num_experts"]
    )
    norms = 2 * h
    ends = 2 * sizes["vocab_size"] * h + h
    total = (
        sizes["kda_layers"] * kda + sizes["mla_layers"] * mla
        + sizes["dense_ffn_layers"] * dense_ffn
        + sizes["routed_ffn_layers"] * routed_ffn
        + sizes["num_hidden_layers"] * norms + ends
    )
    return {
        "kda_mixer": int(kda), "mla_mixer": int(mla),
        "dense_ffn": int(dense_ffn), "routed_ffn": int(routed_ffn),
        "held_experts": int(sizes["routed_ffn_layers"] * experts),
        "norms_a_layer": int(norms), "ends": int(ends), "total": int(total),
    }
