"""Operations and bytes of Laguna-XS.2 at one chip's share (full and
window-512 attention with a head count of their own over the same kv heads,
a gate a head, one leading dense layer, then sigmoid top-8 of 256 experts
beside a shared expert) and of its flash kernels, computed from shapes —
beside ``flops_smallthinker.py`` (whose band arithmetic and band kernel cost
the sliding layers are held to, at THIS configuration's 64 / 8 heads and a
band equal to the tile) and ``flops_lfm2.py`` (whose grouped causal kernel
cost the full layers' kernels are held to, at 48 / 8).

Model FLOPs are matmuls only, backward = 2x forward, the remat replay not
counted, by KIND of layer part: a mixer projects q, the gate and the output
over ITS kind's heads and k / v over ``kv_heads``, and computes the
(query, key) pairs of its mask — the triangle for a full layer, ``min(i + 1,
window)`` keys for query i of a sliding one; the dense FFN; a sparse FFN's
router, its HELD experts at the expected share of slots and its shared
expert, whole; the untied head over the held vocabulary rows.

A kernel's cost counts the (query tile, key tile) pairs that hold at least
one visible pair — whole tiles, the crossed ones too: under a band equal to
the tile BOTH tiles a query tile visits are crossed, and half their pairs
are masked — for every QUERY head, with q-side tensors ``heads`` wide and
k / v and their gradients ``kv_heads`` wide, read once, written once.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.flops_lfm2 import gqa_kernel_cost
from benchmark.flops_smallthinker import (
    band_kernel_cost,
    band_pairs,
    band_tiles,
)

FULL, SLIDING = "full_attention", "sliding_attention"


def heads_of(sizes: Dict[str, float], kind: str) -> int:
    """Query heads of a layer of ``kind`` (``sizes``: the configuration
    file's ``sizes``)."""
    return int(sizes[f"{kind}_heads"])


def laguna_parts_flops_per_token(sizes: Dict[str, float],
                                 seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of ONE layer part of each kind, split
    so that a share can be read: ``<kind>.projections`` / ``<kind>.pairs``
    of the two mixers, ``dense_ffn``, a sparse layer's ``router``,
    ``held_experts`` and ``shared_expert``, and the ``head``."""
    h, d, kv = sizes["hidden_size"], sizes["head_dim"], sizes[
        "num_key_value_heads"
    ]
    pairs = {FULL: band_pairs(seq, seq),
             SLIDING: band_pairs(seq, sizes["sliding_window"])}
    parts = {}
    for kind in (FULL, SLIDING):
        n = heads_of(sizes, kind)
        parts[f"{kind}.projections"] = (
            2 * h * (n + 2 * kv) * d + 2 * n * d * h + 2 * h * n
        )  # q k v, out, the gate
        parts[f"{kind}.pairs"] = 2 * 2 * n * d * pairs[kind] / seq
    f = sizes["moe_intermediate_size"]
    parts.update({
        "dense_ffn": 2 * 3 * h * sizes["intermediate_size"],
        "router": 2 * h * sizes["num_experts"],  # 256 outputs
        "held_experts": (
            2 * 3 * h * f * sizes["num_experts_per_tok"]
            * sizes["held_experts"] / sizes["num_experts"]
        ),
        "shared_expert": 2 * 3 * h * sizes["shared_expert_intermediate_size"],
        "head": 2 * h * sizes["vocab_size"],
    })
    return parts


def laguna_flops_per_token_by_part(sizes: Dict[str, float],
                                   seq: int) -> Dict[str, float]:
    """The parts above times the layers of each kind the cut runs
    (``sizes['full_attention_layers']``, ``['sliding_attention_layers']``,
    ``['dense_layers']``, ``['sparse_layers']``)."""
    part = laguna_parts_flops_per_token(sizes, seq)
    count = {
        **{f"{kind}.{piece}": sizes[f"{kind}_layers"]
           for kind in (FULL, SLIDING) for piece in ("projections", "pairs")},
        "dense_ffn": sizes["dense_layers"],
        "router": sizes["sparse_layers"],
        "held_experts": sizes["sparse_layers"],
        "shared_expert": sizes["sparse_layers"], "head": 1,
    }
    return {name: count[name] * value for name, value in part.items()}


def laguna_train_flops_per_sample(sizes: Dict[str, float], seq: int) -> float:
    """Model FLOPs of one forward + backward row of ``seq`` tokens."""
    return 3.0 * seq * sum(
        laguna_flops_per_token_by_part(sizes, seq).values()
    )


def laguna_parameters(sizes: Dict[str, float]) -> int:
    """Parameters held: a mixer's four projections and its gate at its
    kind's heads, two norms a layer, the dense FFN, a sparse layer's router,
    held experts and shared expert; embedding, untied head and the final
    norm."""
    h, d, kv = sizes["hidden_size"], sizes["head_dim"], sizes[
        "num_key_value_heads"
    ]
    mixers = sum(
        sizes[f"{kind}_layers"] * (
            h * (heads_of(sizes, kind) + 2 * kv) * d
            + heads_of(sizes, kind) * d * h + h * heads_of(sizes, kind)
        ) for kind in (FULL, SLIDING)
    )
    layers = sizes["dense_layers"] + sizes["sparse_layers"]
    sparse = (
        h * sizes["num_experts"]
        + sizes["held_experts"] * 3 * h * sizes["moe_intermediate_size"]
        + 3 * h * sizes["shared_expert_intermediate_size"]
    )
    return int(
        mixers + layers * 2 * h
        + sizes["dense_layers"] * 3 * h * sizes["intermediate_size"]
        + sizes["sparse_layers"] * sparse + 2 * sizes["vocab_size"] * h + h
    )


def kernel_cost(
    kernel: str, batch: int, sizes: Dict[str, float], seq: int,
    dtype_bytes: int = 2,
) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of ``kernel`` on ``batch`` rows at this
    configuration's shapes: ``flash_band_*`` are the sliding layers' (64 / 8
    heads, the band's tiles), ``flash_gqa_*`` the full layers' (48 / 8, the
    triangle's)."""
    block = min(int(sizes["attention_block_size"]), seq)
    kv, d = int(sizes["num_key_value_heads"]), int(sizes["head_dim"])
    if kernel.startswith("flash_band_"):
        return band_kernel_cost(
            kernel, batch, heads_of(sizes, SLIDING), kv, seq, d, block, block,
            int(sizes["sliding_window"]), dtype_bytes,
        )
    return gqa_kernel_cost(
        kernel, batch, heads_of(sizes, FULL), kv, seq, d, block, block,
        dtype_bytes,
    )


def band_visible_share(sizes: Dict[str, float], seq: int) -> float:
    """Visible pairs of a sliding layer over the pairs of the tiles its
    kernels visit: 4,063,488 / (31 x 512²) = 0.50 at S=8,192."""
    block = min(int(sizes["attention_block_size"]), seq)
    band = int(sizes["sliding_window"])
    return band_pairs(seq, band) / (
        band_tiles(seq, block, block, band) * block * block
    )
