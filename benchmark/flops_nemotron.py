"""Operations and bytes of Nemotron-H (``model_type: nemotron_h``: layers that
are ONE sublayer each — a Mamba-2 mixer, NoPE grouped attention or sigmoid
top-6 of 128 un-gated relu² experts beside a shared expert twice their
width) at ONE CHIP'S SHARE — ``held_mamba_heads`` / ``held_groups`` of every
Mamba mixer, ``held_heads`` / ``held_kv_heads`` of attention, ``held_experts``
of every expert layer, the held vocabulary rows — and of the state-space
scan's kernel pair, computed from shapes; beside ``flops_lfm2.py`` (the
grouped-query causal kernels, counted here AT THE HEADS THE CALL HAS).

Model FLOPs are matmuls, backward = 2x forward, the remat replay not
counted, by part. The Mamba mixer's projections are the fused in-projection
(H -> z | x | B | C | dt at the held heads and groups) and the
out-projection; its convolution, SiLU, softplus, gate and group norm are
element-wise and count nothing. The chunked scan itself IS counted
(``ssd_chunk_flops``): per group and chunk of Q tokens C Bᵀ (2·Q·Q·N), the
masked intra-chunk product of each head (2·Q·Q·P), the read of the entering
state and the state's update (2·Q·N·P a head each): 54.5 MFLOP at Q = 128,
N = 128, eight heads of P = 64 — what any chunked schedule of the scan
does, not what this tree's kernel spends on heads side by side in a matmul
or on split operands. Attention is counted at its triangle ((S+1)/2 keys a
token); an expert is TWO matrices (up and down, no gate); the routed experts
for the HELD ones at the expected share of slots; the untied head over the
slice.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lfm2 import gqa_kernel_cost


def ssd_chunk_flops(chunk: int, heads: int, dim: int,
                    state: int) -> Dict[str, float]:
    """The chunked scan's products of one GROUP's chunk (``heads`` heads of
    ``dim`` sharing one B and C of ``state``), forward and backward (the
    backward recomputes the forward's but the state's update, then one
    product a cotangent)."""
    cb = 2.0 * chunk * chunk * state
    intra = heads * 2.0 * chunk * chunk * dim
    against_state = heads * 2.0 * chunk * state * dim
    forward = cb + intra + 2 * against_state  # C S, and Bᵀ(.) into S
    backward = (
        cb + intra + against_state  # recomputed: C Bᵀ, the intra product, C S
        + 2 * intra  # dM = dY (dt X)ᵀ, Mᵀ dY
        + 4 * against_state  # dC and dS from the read; B dS, dB from the write
        + 2 * cb  # dCB into B and C
    )
    return {"ssd_fwd": forward, "ssd_bwd": backward}


def ssd_kernel_cost(kernel: str, batch: int, heads: int, groups: int,
                    seq: int, dim: int, state: int, chunk: int,
                    dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` (``ssd_fwd`` / ``ssd_bwd``)
    on ``batch`` rows of ``seq`` tokens and the ``heads`` / ``groups`` the
    call HAS: every operand read once and every result written once — x and
    y (dy, dx) in the compute dtype, B and C (dB, dC) a group, dt and the
    log-decays (ddt, da) a float32 a token-head, and the float32 [state,
    dim] state a head entering every chunk, which the forward writes and the
    backward reads: the largest term."""
    chunks = batch * (seq // chunk)
    flops = ssd_chunk_flops(chunk, heads // groups, dim, state)[kernel] * (
        chunks * groups
    )
    tokens = batch * seq
    wide = tokens * heads * dim * dtype_bytes  # x, y, dy, dx
    keys = tokens * groups * state * dtype_bytes  # B, C, dB, dC
    scalars = tokens * heads * 4  # dt, a, ddt, da
    states = chunks * heads * state * dim * 4
    if kernel == "ssd_fwd":
        bytes_ = 2 * wide + 2 * keys + 2 * scalars + states
    else:
        bytes_ = 3 * wide + 4 * keys + 4 * scalars + states
    return flops, float(bytes_)


def held_gqa_kernel_cost(kernel: str, batch: int, sizes: Dict[str, float],
                         seq: int, dtype_bytes: int = 2) -> Tuple[float, float]:
    """``flops_lfm2.gqa_kernel_cost`` of a grouped causal kernel
    (``flash_gqa_fwd``, or ``flash_gqa_bwd_tiled``, the one-sweep backward)
    at the heads the call HAS: ``sizes['held_heads']`` over
    ``sizes['held_kv_heads']``, not the published 32 / 2."""
    block = min(sizes["attention_block_size"], seq)
    return gqa_kernel_cost(
        kernel, batch, sizes["held_heads"], sizes["held_kv_heads"], seq,
        sizes["head_dim"], block, block, dtype_bytes,
    )


def _mamba_widths(sizes: Dict[str, float]) -> Tuple[int, int, int]:
    """(inner width, the convolution's width, the in-projection's) at the
    held heads and groups."""
    inner = sizes["held_mamba_heads"] * sizes["mamba_head_dim"]
    conv = inner + 2 * sizes["held_groups"] * sizes["ssm_state_size"]
    return inner, conv, inner + conv + sizes["held_mamba_heads"]


def nemotron_parts_flops_per_token(sizes: Dict[str, float],
                                   seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of every part, summed over the layers that run
    it; ``sizes``: the configuration file's ``sizes``."""
    h = sizes["hidden_size"]
    inner, _conv, in_proj = _mamba_widths(sizes)
    heads, kv, d = sizes["held_heads"], sizes["held_kv_heads"], sizes["head_dim"]
    chunk = sizes["ssd_chunk"]
    per_group = sizes["held_mamba_heads"] // sizes["held_groups"]
    f = sizes["moe_intermediate_size"]
    return {
        "mamba_projections": sizes["mamba_layers"] * (
            2 * h * in_proj + 2 * inner * h
        ),
        "mamba_scan": sizes["mamba_layers"] * sizes["held_groups"] * (
            ssd_chunk_flops(
                chunk, per_group, sizes["mamba_head_dim"],
                sizes["ssm_state_size"],
            )["ssd_fwd"] / chunk
        ),
        "attention_projections": sizes["attention_layers"] * (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
        ),
        "attention_triangle": sizes["attention_layers"] * (
            2 * 2 * heads * d * (seq + 1) / 2
        ),
        "router": sizes["routed_ffn_layers"] * 2 * h * sizes["n_routed_experts"],
        "shared_expert": sizes["routed_ffn_layers"] * (
            2 * 2 * h * sizes["moe_shared_expert_intermediate_size"]
        ),
        "routed_experts": sizes["routed_ffn_layers"] * (
            2 * 2 * h * f * sizes["num_experts_per_tok"]
            * sizes["held_experts"] / sizes["n_routed_experts"]
        ),
        "head": 2 * h * sizes["vocab_size"],
    }


def nemotron_train_flops_per_sample(sizes: Dict[str, float],
                                    seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens."""
    return 3.0 * seq * sum(
        nemotron_parts_flops_per_token(sizes, seq).values()
    )


def nemotron_parameters(sizes: Dict[str, float]) -> Dict[str, int]:
    """Parameters held, by part (``total`` their sum over the layers run)."""
    h = sizes["hidden_size"]
    inner, conv, in_proj = _mamba_widths(sizes)
    heads, kv, d = sizes["held_heads"], sizes["held_kv_heads"], sizes["head_dim"]
    mamba = (
        h * in_proj + conv * sizes["conv_kernel"] + conv  # W_in, taps, bias
        + 3 * sizes["held_mamba_heads"]  # A_log, D, dt_bias
        + inner + inner * h  # the group norm's weight, W_out
    )
    attention = h * (heads + 2 * kv) * d + heads * d * h
    experts = sizes["held_experts"] * 2 * h * sizes["moe_intermediate_size"]
    routed_ffn = (
        h * sizes["n_routed_experts"] + sizes["n_routed_experts"]
        + 2 * h * sizes["moe_shared_expert_intermediate_size"] + experts
    )
    ends = 2 * sizes["vocab_size"] * h + h
    total = (
        sizes["mamba_layers"] * mamba + sizes["attention_layers"] * attention
        + sizes["routed_ffn_layers"] * routed_ffn
        + sizes["num_hidden_layers"] * h + ends
    )
    return {
        "mamba_mixer": int(mamba), "attention_mixer": int(attention),
        "routed_ffn": int(routed_ffn),
        "held_experts": int(sizes["routed_ffn_layers"] * experts),
        "norm_a_layer": int(h), "ends": int(ends), "total": int(total),
    }
