"""The one-sweep backward's rows (``flash_<mode>_bwd_tiled``) in the three
cost tables every mode shares: its five products are the accepted pair's
seven less the score tile the pair computed twice (``bwd_dq`` + ``bwd_dkv``
- ``fwd``, at the same shapes), its bytes the eight tensors once and the
float32 rows, and two calls counted by hand."""
import json
import os

import pytest

from benchmark import (
    flops_keye,
    flops_kimi,
    flops_laguna,
    flops_lfm2,
    flops_lm,
    flops_moe,
    flops_nemotron,
    flops_sdar,
    flops_smallthinker,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["sizes"]


def _causal(kernel):
    s = _sizes("ouro_2p6b_s4096")
    heads, d = s["num_attention_heads"], s["head_dim"]
    cost = flops_lm.causal_kernel_cost(
        f"flash_causal_{kernel}", 1, heads, 4096, d, 512, 512
    )
    tensor = heads * 4096 * d * 2
    return cost, 8 * tensor + (heads + 1) * 4096 * 4


def _mla(kernel):
    s = _sizes("kanana2_30b_a3b_s4096")
    heads = s["num_attention_heads"]
    qk, v = s["qk_nope_head_dim"] + s["qk_rope_head_dim"], s["v_head_dim"]
    cost = flops_moe.mla_kernel_cost(
        f"flash_mla_{kernel}", 1, heads, 4096, qk, v, 512, 512
    )
    return cost, heads * 4096 * 2 * (4 * qk + 4 * v) + (heads + 1) * 4096 * 4


def _grouped(heads, kv, seq, d):
    """q dO O dq at the query heads' width, k v dk dv at the kv heads', the
    float32 lse a head and bias a row."""
    return seq * d * 2 * (4 * heads + 4 * kv) + (heads + 1) * seq * 4


def _gqa(kernel):
    s = _sizes("lfm2_24b_a2b_s4096")
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    cost = flops_lfm2.gqa_kernel_cost(
        f"flash_gqa_{kernel}", 1, heads, kv, 4096, d, 512, 512
    )
    return cost, _grouped(heads, kv, 4096, d)


def _band(kernel):
    s = _sizes("smallthinker_21b_a3b_s16384")
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    cost = flops_smallthinker.band_kernel_cost(
        f"flash_band_{kernel}", 1, heads, kv, 16384, d, 512, 512,
        s["sliding_window_size"],
    )
    return cost, _grouped(heads, kv, 16384, d)


def _bd(kernel):
    s = _sizes("sdar_30b_a3b_s4096")
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    cost = flops_sdar.bd_kernel_cost(
        f"flash_bd_{kernel}", 1, heads, kv, 4096, d, 512, 512,
        s["block_length"],
    )
    return cost, _grouped(heads, kv, 2 * 4096, d)  # both streams' positions


def _sel(kernel):
    s = _sizes("keye_vl2_30b_a3b_s16384")
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    cost = flops_keye.sel_kernel_cost(
        f"flash_sel_{kernel}", 1, heads, kv, 16384, d, 512, 512, 0.75
    )
    selection = 0.75 * flops_keye.triangle_tiles(16384, 512, 512) * 512 * 512
    return cost, _grouped(heads, kv, 16384, d) + selection


@pytest.mark.parametrize(
    "mode", [_causal, _mla, _gqa, _band, _bd, _sel],
    ids=["causal", "mla", "gqa", "band", "bd", "sel"],
)
def test_the_sweep_is_the_pair_less_one_score_tile(mode):
    (flops, bytes_), by_hand = mode("bwd_tiled")
    # the identity tools/chip_gqa_check.kernel_cost derived the row from
    assert flops == pytest.approx(
        mode("bwd_dq")[0][0] + mode("bwd_dkv")[0][0] - mode("fwd")[0][0],
        rel=1e-12,
    )
    assert flops / mode("fwd")[0][0] == pytest.approx(
        (3 * 192 + 2 * 128) / 320 if mode is _mla else 2.5
    )
    # q k v dO O dq dk dv once each, the float32 lse and bias rows (and the
    # selected tiles of Keye's int8 selection): nothing read twice
    assert bytes_ == by_hand
    assert bytes_ < mode("bwd_dq")[0][1] + mode("bwd_dkv")[0][1]


def test_two_calls_by_hand():
    # LFM2: one row of 4,096, 32 / 8 heads of 64, 36 tiles of 512 x 512 a
    # head: five products of 2 x 512 x 512 x 64 a tile
    flops, bytes_ = _gqa("bwd_tiled")[0]
    assert flops == 5 * 2 * 512 * 512 * 64 * 36 * 32
    assert flops == pytest.approx(193.3e9, rel=5e-4)
    assert bytes_ == pytest.approx(84.4e6, rel=1e-3)
    # kanana-2: 32 heads, q / k 192 wide (QK^T, dK, dQ), v 128 (dP, dV)
    flops, bytes_ = _mla("bwd_tiled")[0]
    assert flops == 2 * 512 * 512 * (3 * 192 + 2 * 128) * 36 * 32
    assert flops == pytest.approx(502.5e9, rel=5e-4)
    # Ouro's is LFM2's FLOPs at half the heads of twice the width
    assert _causal("bwd_tiled")[0][0] == _gqa("bwd_tiled")[0][0]


def test_the_cells_that_share_a_table_read_its_row():
    # Nemotron-H and Kimi Linear at the heads the call HAS; Laguna's two
    # kinds of layer at their own head counts
    s = _sizes("nemotron3_nano_30b_a3b_s8192")
    assert flops_nemotron.held_gqa_kernel_cost(
        "flash_gqa_bwd_tiled", 1, s, 8192
    ) == flops_lfm2.gqa_kernel_cost(
        "flash_gqa_bwd_tiled", 1, s["held_heads"], s["held_kv_heads"], 8192,
        s["head_dim"], 512, 512,
    )
    # what the private copy of the formula read until PR 61: 730 GFLOP
    assert flops_nemotron.held_gqa_kernel_cost(
        "flash_gqa_bwd_tiled", 1, s, 8192
    )[0] == pytest.approx(730.1e9, rel=5e-4)
    s = _sizes("kimi_linear_48b_a3b_s8192")
    assert flops_kimi.held_mla_kernel_cost(
        "flash_mla_bwd_tiled", 1, s, 8192
    )[0] == 2 * 512 * 512 * (3 * 192 + 2 * 128) * 136 * s["held_heads"]
    s = _sizes("laguna_xs2_33b_a3b_s8192")
    full = flops_laguna.kernel_cost("flash_gqa_bwd_tiled", 1, s, 8192)[0]
    band = flops_laguna.kernel_cost("flash_band_bwd_tiled", 1, s, 8192)[0]
    assert full == 5 * 2 * 512 * 512 * 128 * 136 * 48
    assert band == 5 * 2 * 512 * 512 * 128 * 31 * 64
    for table, cost in (
        (flops_lfm2.gqa_kernel_cost, (1, 32, 8, 4096, 64, 512, 512)),
        (flops_lm.causal_kernel_cost, (1, 16, 4096, 128, 512, 512)),
        (flops_moe.mla_kernel_cost, (1, 32, 4096, 192, 128, 512, 512)),
    ):
        with pytest.raises(KeyError):
            table("flash_bwd_tiled", *cost)
