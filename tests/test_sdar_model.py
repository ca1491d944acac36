"""``sdar_tiny`` through the model against the plain reference
(``benchmark/reference/sdar_moe.py``: float32, matmul precision 'highest',
dense attention with k / v repeated per group and an explicit [2L, 2L] mask
built from the rule's three sentences, a loop over experts, whole logits) on
seeded random weights: loss, every gradient leaf, the noisy stream's logits,
the router's logits, the choices exactly; NO LEAK across the rule's edges; a
reference with a plain causal mask over 2L, with a noisy query seeing the
clean copy of its own block, with positions 0..2L-1, without the q / k norm
or with a shifted target failing; the same model through the block-diffusion
kernels (interpreter mode); and THE SHARE TEST: the 8 shares' routed parts
add up to the uncut reference's layer output."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import sdar_moe as reference
from dedloc_tpu.data.block_diffusion import block_diffusion_batches
from dedloc_tpu.models import decoder
from dedloc_tpu.models.decoder import RoutedGLU, Visibility
from dedloc_tpu.models.sdar_moe import (
    SdarMoeConfig,
    SdarMoeForDiffusionLM,
    bd_tile_share,
    sdar_moe_flops_per_row,
    sdar_moe_loss,
    sdar_moe_train_tflops_per_sample,
)
from dedloc_tpu.roles.common import SDAR_MOE

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 1e-5, 1e-4


def _batch(cfg, seq):
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size - 1, (2, seq)
    ).astype(np.int32)
    return jax.tree.map(jnp.asarray, next(block_diffusion_batches(
        [rows], cfg.block_length, cfg.mask_token_id, seed=3
    )))


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        top_k=cfg.num_experts_per_tok, block=cfg.block_length,
        held=cfg.held_experts,
    )
    kwargs.update(changes)
    return kwargs


SDAR = cases.Family(
    tiny=SdarMoeConfig.tiny, module=SdarMoeForDiffusionLM, loss=sdar_moe_loss,
    reference=reference, reference_kwargs=_reference_kwargs, batch=_batch,
    loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL,
)
# two layers through the block-diffusion kernels: heads of 128, tiles of 16
FLASH = dict(head_dim=128, num_hidden_layers=2, attention_impl="flash",
             attention_block_size=16)


@pytest.mark.parametrize(
    "overrides", [dict(), dict(expert_shard=(1, 4)),
                  dict(num_hidden_layers=2, block_length=8),
                  dict(num_hidden_layers=5)],
    ids=["whole", "share_1_of_4", "two_layers_blocks_of_8",
         "a_period_and_a_tail_layer"],
)
def test_model_matches_reference(overrides):
    cfg, metrics, _grads, ref, _ref_grads = (
        cases.check_model_matches_reference(SDAR, **overrides)
    )
    _cfg, model, params, batch = cases.case(SDAR, **overrides)
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    hidden, _routing = model.apply(
        {"params": params},
        jnp.concatenate([batch["input_ids"], batch["labels"]], axis=1),
    )
    np.testing.assert_allclose(
        hidden @ params["lm_head"], ref["logits"], atol=2e-4, rtol=2e-4
    )
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0  # none handed
    assert metrics["moe.load_max_over_mean"].shape == (cfg.num_hidden_layers,)
    weights = np.asarray(batch["loss_weights"])
    assert float(metrics["diffusion.masked_tokens"]) == (weights > 0).sum()
    assert float(metrics["diffusion.masked_share"]) == pytest.approx(
        (weights > 0).mean()
    )


WRONG = {
    "a_plain_causal_mask_over_2L": dict(rule="causal"),
    "a_noisy_query_sees_its_own_clean_block": dict(rule="own_block_clean"),
    "positions_0_to_2L": dict(positions="running"),
    "no_qk_norm": dict(qk_norm=False),
    "a_shifted_target": dict(shift=True),
}


@pytest.mark.parametrize("changes", WRONG.values(), ids=WRONG.keys())
def test_a_different_function_fails(changes):
    cases.check_a_different_function_fails(SDAR, changes)


def test_reference_routed_by_given_choices():
    cases.check_reference_routed_by_given_choices(SDAR)


def _both_streams(model, params, noisy, clean):
    hidden, _routing = model.apply(
        {"params": params}, jnp.concatenate([noisy, clean], axis=1),
        both_streams=True,
    )
    return np.asarray(hidden)


def _no_leak(impl, same):
    """``test_no_leak``'s three changes; ``same(got, want)`` holds the
    positions that may not move."""
    cfg, model, params, batch = cases.case(
        SDAR, 32, **(FLASH if impl == "flash" else dict(num_hidden_layers=2))
    )
    noisy, clean = batch["input_ids"], batch["labels"]
    length, blk, b = 32, cfg.block_length, 3
    lo, hi = b * blk, (b + 1) * blk
    base = _both_streams(model, params, noisy, clean)
    other = (clean + 7) % (cfg.vocab_size - 1)

    # clean tokens of blocks >= b change
    moved = _both_streams(
        model, params, noisy, clean.at[:, lo:].set(other[:, lo:])
    )
    same(moved[:, :hi], base[:, :hi])  # noisy <= b
    same(
        moved[:, length:length + lo], base[:, length:length + lo]
    )  # clean < b
    assert np.abs(moved[:, hi:length] - base[:, hi:length]).max() > 1e-3
    assert np.abs(moved[:, length + lo:] - base[:, length + lo:]).max() > 1e-3

    # every noisy token changes: no clean position moves; each noisy does
    moved = _both_streams(model, params, (noisy + 5) % cfg.vocab_size, clean)
    same(moved[:, length:], base[:, length:])
    assert np.abs(moved[:, :length] - base[:, :length]).max() > 1e-3

    # the noisy tokens of block b change: only block b's noisy positions move
    moved = _both_streams(
        model, params,
        noisy.at[:, lo:hi].set((noisy[:, lo:hi] + 5) % cfg.vocab_size), clean,
    )
    same(moved[:, :lo], base[:, :lo])
    same(moved[:, hi:], base[:, hi:])
    # ... the block's FIRST position included: it sees the keys after it
    assert np.abs(moved[:, lo] - base[:, lo]).max() > 1e-3


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_no_leak(impl, monkeypatch):
    """What a position may not see does not move it, to the bit: the noisy
    stream's hidden (so its logits) of block b under a change of clean
    tokens of blocks >= b or of noisy tokens of other blocks; the clean
    stream's hidden of block b under a change of ANY noisy token or of a
    clean token of a later block. And what it may see does. This is about
    what ATTENTION shows a position, so the experts walk their rows one
    tile an iteration (``run_tiles=1``), where a row's value is the same
    bits whatever the other rows do; the walk that ships is the next test."""
    monkeypatch.setattr(decoder, "routed_experts", functools.partial(
        decoder.routed_experts, run_tiles=1
    ))
    _no_leak(impl, np.testing.assert_array_equal)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_no_leak_under_the_run_length_walk(impl):
    """The same under the walk that ships (``parallel/moe.RUN_TILES``):
    WHICH rows share an iteration, and so the blocking of their matmuls,
    follows the other positions' routing, and a position that sees nothing
    of a change can move in float32's last digits — 6e-7 read here, held to
    4e-6, where a leak moves a position by more than 1e-3."""
    _no_leak(impl, functools.partial(
        np.testing.assert_allclose, rtol=0, atol=4e-6
    ))


def test_the_block_diffusion_kernels_inside_the_model():
    """``attention_impl="flash"``: the grouped kernels (8 / 1 x 128, a group
    of eight) under the block rule, tiles of 16 = four blocks, in
    interpreter mode, against the reference."""
    metrics = cases.check_the_model_under_overrides(SDAR, **FLASH)
    # 4 tiles a stream: 10 + 10 + 4 of the 36 a causal call over 2L visits
    assert float(metrics["attn.bd_tile_share"]) == pytest.approx(24 / 36)
    assert bd_tile_share(SdarMoeConfig(), 4096) == 80 / 136
    np.testing.assert_array_equal(
        Visibility(block_diffusion=4).matrix(16),
        reference.visible(8, 4),
    )


def test_the_shares_add_up_to_the_uncut_layer():
    """One layer's FFN: the routed parts that the 8 shares compute (each
    told its share, holding 2 of the 16 experts) are the uncut reference's
    layer output — there is no shared expert, so nothing is computed alike
    on every chip but the router, whose choices agree."""
    cfg, _model, params, _batch = cases.case(SDAR)
    layer = jax.tree.map(lambda x: x[0], params["layers"]["layer_1"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), layer, held=(0, 16),
            top_k=cfg.num_experts_per_tok,
        )
    cases.check_the_routed_shares_add_up(
        SDAR, layer, lambda share: RoutedGLU(share, activation="silu"),
        (x, x), whole, reference.EXPERTS,
    )


def test_masks_and_flops():
    params = cases.case(SDAR).params
    decay = SDAR_MOE.weight_decay_mask(params)
    assert decay["norm"]["weight"] is False and decay["lm_head"] is True
    attn = decay["layers"]["layer_0"]["self_attn"]
    assert attn["q_norm"]["weight"] is False and attn["q_proj"]["kernel"]
    assert decay["layers"]["layer_2"]["mlp"]["router"] is True
    sinks = SDAR_MOE.grad_sink_mask(params)["layers"]["layer_1"]["mlp"]
    assert sinks["experts_down"] is True and sinks["router"] is False
    assert SDAR_MOE.sign_step_mask is None
    # the cell's cut: 456.3 M parameters, one scan step of four layers,
    # each with leaves of its own (the tile loop's gradient sinks)
    cut = dict(num_hidden_layers=4, vocab_size=18992)
    held = SdarMoeConfig(expert_shard=(0, 8), **cut)
    shapes = jax.eval_shape(
        lambda: SdarMoeForDiffusionLM(held).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 456_346_624
    assert sorted(shapes["layers"]) == [f"layer_{i}" for i in range(4)]
    assert shapes["layers"]["layer_3"]["mlp"]["experts_gate"].shape == (
        1, 16, 2048, 768
    )
    assert shapes["layers"]["layer_0"]["self_attn"]["q_norm"][
        "weight"
    ].shape == (1, 128)
    # a depth that is no whole number of periods: the rest after the scan
    six = jax.eval_shape(
        lambda: SdarMoeForDiffusionLM(
            SdarMoeConfig.tiny(num_hidden_layers=6)
        ).init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    )
    assert sorted(k for k in six if "layer" in k) == [
        "layers", "tail_layer_0", "tail_layer_1"
    ]
    assert held.mask_token_id == 18991
    part = sdar_moe_flops_per_row(held, 4096)
    assert part["attention"] == 4 * 2 * 2 * 32 * 128 * 16_793_600
    assert part["head"] == 4096 * 2 * 2048 * 18992
    assert part["routed"] == 4 * 8192 * 2 * 3 * 2048 * 768 * 8 / 8
    total = sdar_moe_train_tflops_per_sample(held, 4096)
    assert total == pytest.approx(8.95, abs=0.01)
    assert 3 * part["attention"] / 1e12 / total == pytest.approx(
        0.369, abs=0.001
    )
    # all 128 held: eight times the routed work, nothing else
    assert sdar_moe_train_tflops_per_sample(
        SdarMoeConfig(**cut), 4096
    ) - total == pytest.approx(3 * part["routed"] * 7 / 1e12, rel=1e-9)
