"""The band (a sliding window: query i sees keys i-w+1 .. i) inside the
flash kernels, in interpreter mode against a dense masked float32
attention: forward and all three gradients, at one head count and grouped
(a group of seven where a column block is one head), with the band shorter
than a block, whole blocks long, crossing a block, and as long as the
sequence (which IS the causal call)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import flash_attention, visited_tiles

fa = importlib.import_module("dedloc_tpu.ops.flash_attention")

S, BLOCK = 96, 32
SHAPES = [(4, 4, 64), (4, 4, 128), (8, 2, 64), (8, 2, 128), (7, 1, 128),
          (28, 4, 128)]
BANDS = {"under_a_block": 8, "two_blocks": 64, "crosses_a_block": 50,
         "the_sequence": S}


def _dense(q, k, v, band):
    """softmax(q kᵀ / sqrt(D) + mask) v, k / v repeated per group."""
    h, kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])
    seen = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < band)
    probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _operands(h, kv, d, seed=0, seq=S):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((1, seq, n, d)), jnp.float32)
        for n in (h, kv, kv)
    ) + (jnp.asarray(rng.standard_normal((1, seq, h, d)), jnp.float32),)


@pytest.mark.parametrize("band", BANDS.values(), ids=BANDS.keys())
@pytest.mark.parametrize(
    "h,kv,d", SHAPES, ids=[f"{h}_{kv}x{d}" for h, kv, d in SHAPES]
)
def test_band_against_dense(h, kv, d, band):
    q, k, v, do = _operands(h, kv, d)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, band=band,
                               block_q=BLOCK, block_k=BLOCK)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda *x: _dense(*x, band), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=name)
    if band >= S:  # ... and the causal call's very bits
        causal, causal_vjp = jax.vjp(
            lambda *x: flash_attention(*x, causal=True, block_q=BLOCK,
                                       block_k=BLOCK), q, k, v,
        )
        np.testing.assert_array_equal(out, causal)
        for got, ref in zip(vjp(do), causal_vjp(do)):
            np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32)])
def test_band_with_unequal_blocks(block_q, block_k):
    q, k, v, do = _operands(8, 2, 128, seed=1)
    out, vjp = jax.vjp(
        lambda *x: flash_attention(*x, causal=True, band=40, block_q=block_q,
                                   block_k=block_k), q, k, v,
    )
    want, want_vjp = jax.vjp(lambda *x: _dense(*x, 40), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_band_in_one_tile():
    """One tile over the sequence: the one-tile forms carry the band too."""
    q, k, v, do = _operands(4, 4, 64, seed=2, seq=32)
    out, vjp = jax.vjp(
        lambda *x: flash_attention(*x, causal=True, band=5), q, k, v
    )
    want, want_vjp = jax.vjp(lambda *x: _dense(*x, 5), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def _kernels(h, kv, d, band, seq=S):
    """{kernel name: (grid, metadata)} of a call's forward and backward."""
    q, k, v, _do = _operands(h, kv, d, seq=seq)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *x: jnp.sum(flash_attention(
        *x, causal=True, band=band, block_q=BLOCK, block_k=BLOCK
    )), argnums=(0, 1, 2)))(q, k, v)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = (
                    eqn.params["grid_mapping"].grid,
                    dict(eqn.params["metadata"] or {}),
                )
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_band_calls_keep_their_own_names_and_a_short_sweep():
    """``flash_band_*`` in a device trace, the band and the head counts in
    the metadata; the inner grid axis is the band's tiles (2 of 4 at a band
    of one block), not the sequence's; a group of seven is one program."""
    found = _kernels(28, 4, 128, 32, seq=128)
    assert sorted(found) == ["flash_band_bwd_tiled", "flash_band_fwd"]
    grid, metadata = found["flash_band_fwd"]
    assert grid == (1, 4, 4, 2)  # 4 groups of 7 heads, 4 query tiles, 2 keys
    assert metadata == {"heads": 28, "kv_heads": 4, "band": 32}
    # ONE backward kernel on the forward's walk: (B, kv blocks, the block's
    # query programs, query tiles, key steps)
    assert found["flash_band_bwd_tiled"][0] == (1, 4, 1, 4, 2)
    # a band the sequence is no longer than: the causal kernels, as they were
    causal = _kernels(28, 4, 128, 128, seq=128)
    assert sorted(causal) == ["flash_gqa_bwd_tiled", "flash_gqa_fwd"]
    assert causal["flash_gqa_fwd"] == (
        (1, 4, 4, 4), {"heads": 28, "kv_heads": 4}
    )
    assert sorted(_kernels(4, 4, 128, 8)) == [
        "flash_band_bwd_tiled", "flash_band_fwd"
    ]


def test_visited_tiles():
    """The count every reader of the mask description agrees on."""
    assert visited_tiles(16384, 512, 512, True) == 528
    assert visited_tiles(16384, 512, 512, True, band=4096) == 252
    assert visited_tiles(16384, 512, 512, True, band=16384) == 528
    assert visited_tiles(4096, 512, 512, True, band=4096) == 36
    assert visited_tiles(8192, 512, 512, False) == 256
    assert visited_tiles(128, 32, 32, True, band=8) == 7
    mask = fa._Mask(True, 4096)
    assert fa._sweep(mask, 32, 32, 512, 512) == 9
    assert fa._first_k_tile(mask, 9, 512, 512) == 1
    # the backward's sweep is the forward's: query tile 30 starts at key
    # tile 22 and a step past its last names tile 30 again
    assert [int(fa._k_tile(mask, 30, step, 512, 512)) for step in (0, 8)] == [
        22, 30
    ]
    assert int(fa._k_tile(mask, 3, 8, 512, 512)) == 3


@pytest.mark.parametrize("kwargs", [dict(causal=False, band=8),
                                    dict(causal=True, band=0)])
def test_a_band_is_a_causal_masks_second_edge(kwargs):
    q, k, v, _do = _operands(4, 4, 64, seq=32)
    with pytest.raises(ValueError, match="band"):
        flash_attention(q, k, v, **kwargs)


def test_a_group_of_seven_needs_one_head_a_column_block():
    q = jnp.zeros((1, 64, 14, 64), jnp.float32)
    kv = jnp.zeros((1, 64, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="grouped-query"):
        flash_attention(q, kv, kv, causal=True, block_q=32, block_k=32)


# Laguna's shapes: a band EQUAL to the tile (every query tile but the first
# visits two key tiles and BOTH are crossed), under a group of eight (two
# programs of four heads share a kv head) and — the full layers' call — a
# group of SIX, a whole group a program
GROUPS = [(6, 1, 128), (12, 2, 128), (8, 1, 128), (16, 2, 128)]


@pytest.mark.parametrize("band", [BLOCK, None], ids=["band_is_the_tile",
                                                     "causal"])
@pytest.mark.parametrize(
    "h,kv,d", GROUPS, ids=[f"{h}_{kv}x{d}" for h, kv, d in GROUPS]
)
def test_groups_of_six_and_eight_at_a_band_equal_to_the_tile(h, kv, d, band):
    q, k, v, do = _operands(h, kv, d, seed=3)
    out, vjp = jax.vjp(
        lambda *x: flash_attention(*x, causal=True, band=band, block_q=BLOCK,
                                   block_k=BLOCK), q, k, v,
    )
    want, want_vjp = jax.vjp(lambda *x: _dense(*x, band or S), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


def test_a_band_equal_to_the_tile_visits_two_crossed_tiles():
    """31 of the triangle's 136 at S=8,192 and 512 x 512 tiles; the sweep
    is two tiles long on both axes, and neither tile of a pair is plain:
    the first holds one visible pair a row short of the diagonal's, the
    second the diagonal."""
    assert visited_tiles(8192, 512, 512, True, band=512) == 31
    assert visited_tiles(8192, 512, 512, True) == 136
    mask = fa._Mask(True, 512)
    assert fa._sweep(mask, 16, 16, 512, 512) == 2
    assert [fa._first_k_tile(mask, qi, 512, 512) for qi in (0, 1, 9)] == [
        0, 0, 8
    ]
    for qi, ki in ((9, 8), (9, 9)):  # both crossed: some pair masked
        seen = np.asarray(fa._tile_mask(mask, qi, ki, 512, 512))
        assert seen.any() and not seen.all()
    assert int(np.asarray(fa._tile_mask(mask, 9, 8, 512, 512)).sum()) + int(
        np.asarray(fa._tile_mask(mask, 9, 9, 512, 512)).sum()
    ) == 512 * 512  # a query's 512 keys, over the two tiles
    # a tile narrower than the band: four key tiles of 128 a query tile + 1
    assert visited_tiles(8192, 512, 128, True, band=512) == 4 + 15 * 8
    assert visited_tiles(8192, 512, 256, True, band=512) == 2 + 15 * 4
    found = _kernels(12, 2, 128, BLOCK, seq=128)
    # 2 groups of six heads (a whole group a program), 4 query tiles, 2 keys
    assert found["flash_band_fwd"] == (
        (1, 2, 4, 2), {"heads": 12, "kv_heads": 2, "band": 32}
    )
    eight = _kernels(16, 2, 128, BLOCK, seq=128)
    assert eight["flash_band_fwd"][0][2:] == (4, 2)
