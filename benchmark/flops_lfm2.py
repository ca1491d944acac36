"""Operations and bytes of the conv-hybrid expert decoder (``model_type:
lfm2_moe``), of the grouped-query causal flash kernels and of the short
convolution kernels, computed from shapes — beside ``flops_moe.py`` (latent
attention), ``flops_lm.py`` (one head count) and ``flops.py`` (ALBERT).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, by KIND of layer part: the conv mixer (``in_proj`` H -> 3H and
``out_proj``; its element-wise convolution is not a matmul and counts
nothing), the attention mixer (q over ``heads``, k and v over ``kv_heads``,
the triangle at (S+1)/2 keys a token), the dense and the routed FFN (the
HELD experts at the expected share of slots), the tied head over the held
vocabulary rows. The kernel costs count the (query tile, key tile) pairs on
and under the diagonal for every QUERY head, with q-side tensors ``heads``
wide and k / v and their gradients ``kv_heads`` wide — read once, written
once, nothing expanded; the convolution's are its HBM bytes (it is
memory-bound by two orders of magnitude) and its multiply-adds, with the
tensors the program keeps in on-chip memory for a call left out.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lm import causal_tiles


def lfm2_parts_flops_per_token(sizes: Dict[str, float],
                               seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of ONE layer part of each kind and of
    the head; ``sizes``: the configuration file's ``sizes``."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return {
        "conv": 2 * h * 3 * h + 2 * h * h,  # in_proj, out_proj
        "attention": (
            2 * h * (heads + 2 * kv) * d  # W_q, W_k, W_v
            + 2 * heads * d * h  # W_o
            + 2 * 2 * heads * d * (seq + 1) / 2  # QK^T, PV over the triangle
        ),
        "dense_ffn": 2 * 3 * h * sizes["intermediate_size"],
        "routed_ffn": (
            2 * h * sizes["num_experts"]  # the router's 64 outputs
            + 2 * 3 * h * sizes["moe_intermediate_size"]
            * sizes["num_experts_per_tok"]
            * sizes["held_experts"] / sizes["num_experts"]
        ),
        "head": 2 * h * sizes["vocab_size"],
    }


def lfm2_train_flops_per_sample(sizes: Dict[str, float], seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens: each
    kind's part times the layers of that kind the cut runs
    (``sizes['conv_layers']`` ...)."""
    part = lfm2_parts_flops_per_token(sizes, seq)
    per_token = (
        sizes["conv_layers"] * part["conv"]
        + sizes["attention_layers"] * part["attention"]
        + sizes["dense_ffn_layers"] * part["dense_ffn"]
        + sizes["routed_ffn_layers"] * part["routed_ffn"]
        + part["head"]
    )
    return 3.0 * per_token * seq


# per visited tile and QUERY head: matmuls of the head width; tensors read
# and written at the query width (q, dO, O, dq, out) and at the kv width
_GQA = {
    # QK^T, PV; reads q | k v, writes o
    "flash_gqa_fwd": dict(matmuls=2, q_tensors=2, kv_tensors=2),
    # QK^T, dP, dQ; reads q dO O, writes dq | reads k v
    "flash_gqa_bwd_dq": dict(matmuls=3, q_tensors=4, kv_tensors=2),
    # QK^T, dP, dK, dV; reads q dO O | reads k v, writes dk dv
    "flash_gqa_bwd_dkv": dict(matmuls=4, q_tensors=3, kv_tensors=4),
    # the one-sweep backward: QK^T, dP, dV, dK, dQ from ONE score tile;
    # reads q dO O, writes dq | reads k v, writes dk dv
    "flash_gqa_bwd_tiled": dict(matmuls=5, q_tensors=4, kv_tensors=4),
}


def gqa_kernel_cost(
    kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
    head_dim: int, block_q: int, block_k: int, dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` on ``batch`` rows."""
    if kernel not in _GQA:
        raise KeyError(f"no cost function for kernel {kernel!r}")
    k = _GQA[kernel]
    flops = (
        2.0 * block_q * block_k * head_dim * k["matmuls"]
        * causal_tiles(seq, block_q, block_k) * batch * heads
    )
    tensors = batch * seq * head_dim * dtype_bytes * (
        k["q_tensors"] * heads + k["kv_tensors"] * kv_heads
    )
    rows = (batch * heads + batch) * seq * 4  # lse per head, bias per row
    return flops, float(tensors + rows)


# a kernel's tensors: H-wide parts a token, and where the call holds each
# (operand or result index of its custom call); multiply-adds an element
_CONV = {
    # reads B C u (one operand, three parts), writes y; z, three taps, gate
    "short_conv_fwd": dict(
        tensors={"bcu": (3, "operand", 0), "y": (1, "result", 0)}, flops=7,
    ),
    # reads B C u and dy, writes dB dC du (one result); z and the conv
    # again, g, dz's three taps, the three products, dw's three sums
    "short_conv_bwd": dict(
        tensors={"bcu": (3, "operand", 0), "dy": (1, "operand", 3),
                 "d_bcu": (3, "result", 0)},
        flops=22,
    ),
}


def conv_kernel_tensors(kernel: str) -> Dict[str, Tuple[int, str, int]]:
    """name -> (H-wide parts, 'operand' | 'result', index in the call)."""
    if kernel not in _CONV:
        raise KeyError(f"no cost function for kernel {kernel!r}")
    return _CONV[kernel]["tensors"]


def conv_kernel_cost(kernel: str, batch: int, seq: int, hidden: int,
                     dtype_bytes: int = 2,
                     on_chip=frozenset()) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of one call of ``kernel`` on ``batch`` rows: every
    H-wide operand read once and every result written once (halo rows and
    the [H, 3] taps, < 7 % and < 0.1 %, not counted: the least the op
    needs). ``on_chip``: the tensors (names of ``conv_kernel_tensors``) the
    program holds in on-chip memory for this call — XLA's fast-memory
    assignment, ``S(1)`` in the call's HLO text — which cross no HBM."""
    elements = batch * seq * hidden
    parts = sum(
        n for name, (n, _side, _i) in conv_kernel_tensors(kernel).items()
        if name not in on_chip
    )
    return float(_CONV[kernel]["flops"] * elements), float(
        parts * elements * dtype_bytes
    )
