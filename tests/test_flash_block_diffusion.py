"""The block-diffusion rule (two streams [noisy ; clean] of L positions in
blocks of B: a clean query sees the clean blocks up to its own, a noisy one
the clean blocks BEFORE its own and the noisy keys OF its own) inside the
flash kernels, in interpreter mode against a dense masked float32 attention:
forward and all three gradients, at one head count and grouped (a group of
eight, of seven), with a tile that is one block, that holds many, with
unequal tiles; the count of visited tiles against a brute-force count; the
other masks' answers as they were."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import flash_attention, visited_tiles

# (L, block length, query tile, key tile)
GEOMETRY = {
    "many_blocks_a_tile": (64, 4, 32, 32),
    "a_block_a_tile": (64, 32, 32, 32),
    "half_a_tile": (96, 16, 32, 32),
    "wide_key_tiles": (128, 4, 32, 64),
    "wide_query_tiles": (128, 8, 64, 32),
    "a_block_of_three": (96, 3, 48, 24),
    "one_tile_a_stream": (32, 4, 32, 32),
}
SHAPES = [(4, 4, 64), (4, 4, 128), (8, 2, 64), (8, 1, 128), (32, 4, 128),
          (7, 1, 128)]


def visible(length: int, block: int) -> np.ndarray:
    """[2L, 2L] bool, the rule's three sentences, rows queries."""
    i = np.arange(2 * length)
    clean, blk = i >= length, (i % length) // block
    qc, kc = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return np.where(
        qc, kc & (kb <= qb), (~kc & (kb == qb)) | (kc & (kb < qb))
    )


def _dense(q, k, v, seen):
    """softmax(q kᵀ / sqrt(D) + mask) v, k / v repeated per group."""
    h, kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _operands(h, kv, d, seq, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((batch, seq, n, d)), jnp.float32)
        for n in (h, kv, kv, h)
    )


def _check(h, kv, d, length, block, block_q, block_k, seed=0, batch=1):
    q, k, v, do = _operands(h, kv, d, 2 * length, seed, batch)
    out, vjp = jax.vjp(
        lambda *x: flash_attention(
            *x, block_diffusion=block, block_q=block_q, block_k=block_k
        ), q, k, v,
    )
    seen = jnp.asarray(visible(length, block))
    want, want_vjp = jax.vjp(lambda *x: _dense(*x, seen), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("geometry", GEOMETRY.values(), ids=GEOMETRY.keys())
def test_geometries_against_dense(geometry):
    """A group of eight on one kv head of 128, the cell's kind of call."""
    _check(8, 1, 128, *geometry)


@pytest.mark.parametrize(
    "h,kv,d", SHAPES, ids=[f"{h}_{kv}x{d}" for h, kv, d in SHAPES]
)
def test_head_layouts_against_dense(h, kv, d):
    _check(h, kv, d, 64, 4, 32, 32, seed=1)


def test_a_batch_of_rows_and_a_kv_bias():
    """Batch is a grid axis; the KV bias still applies on top."""
    length, block = 64, 8
    q, k, v, _do = _operands(4, 2, 64, 2 * length, seed=2, batch=2)
    keep = np.ones((2, 2 * length), bool)
    keep[1, 5:9] = False  # dropped keys of the noisy stream's first blocks
    bias = jnp.where(jnp.asarray(keep), 0.0, -1e30)
    out = flash_attention(q, k, v, bias, block_diffusion=block, block_q=32,
                          block_k=32)
    seen = jnp.asarray(visible(length, block))[None] & keep[:, None, :]
    want = _dense(q, k, v, seen[:, None])
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def _brute_force_tiles(length, block, bq, bk):
    seen = visible(length, block)
    return sum(
        bool(seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any())
        for qi in range(2 * length // bq) for ki in range(2 * length // bk)
    )


@pytest.mark.parametrize("geometry", GEOMETRY.values(), ids=GEOMETRY.keys())
def test_visited_tiles_is_a_brute_force_count(geometry):
    length, block, bq, bk = geometry
    assert visited_tiles(
        2 * length, bq, bk, False, block_diffusion=block
    ) == _brute_force_tiles(length, block, bq, bk)


def test_visited_tiles_at_the_cells_shapes():
    """80 = clean x clean 36 + noisy x clean 36 + the 8 noisy diagonal
    tiles, of the 256 a dense call and the 136 a causal call over 2L
    visit; the other masks' answers as they were."""
    assert visited_tiles(8192, 512, 512, False, block_diffusion=4) == 80
    assert _brute_force_tiles(4096, 4, 512, 512) == 80
    assert visited_tiles(8192, 512, 512, False) == 256
    assert visited_tiles(8192, 512, 512, True) == 136
    assert visited_tiles(16384, 512, 512, True, band=4096) == 252


def _kernels(h, kv, d, length, block, tile):
    """{kernel name: (grid, metadata)} of a call's forward and backward."""
    q, k, v, _do = _operands(h, kv, d, 2 * length)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *x: jnp.sum(flash_attention(
        *x, block_diffusion=block, block_q=tile, block_k=tile
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


def test_block_diffusion_calls_keep_their_own_names_and_sweeps():
    """``flash_bd_*`` in a device trace, the blocks and the head counts in
    the metadata; a query tile's sweep is its stream's tiles + its noisy
    diagonal one (n + 1), forward and backward: the ONE backward kernel
    walks the forward's grid under one more axis, a kv block's programs."""
    found = _kernels(32, 4, 128, 128, 4, 32)
    assert sorted(found) == ["flash_bd_bwd_tiled", "flash_bd_fwd"]
    grid, metadata = found["flash_bd_fwd"]
    assert grid == (1, 4, 8, 5)  # 4 programs of 8 heads, 8 query tiles
    assert metadata == {"heads": 32, "kv_heads": 4, "block": 4,
                        "stream": 128}
    assert found["flash_bd_bwd_tiled"][0] == (1, 4, 1, 8, 5)
    assert sorted(_kernels(4, 4, 128, 64, 4, 32)) == [
        "flash_bd_bwd_tiled", "flash_bd_fwd"
    ]


@pytest.mark.parametrize("kwargs,match", [
    (dict(causal=True, block_diffusion=4), "block rule"),
    (dict(block_diffusion=5), "block rule"),  # 64 is not whole blocks of 5
    (dict(block_diffusion=4, block_q=2, block_k=2), "whole blocks"),
])
def test_the_block_rule_is_a_mask_of_its_own(kwargs, match):
    q, k, v, _do = _operands(4, 4, 64, 64)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v, **kwargs)
