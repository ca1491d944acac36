"""Pallas flash attention vs the dense reference (interpret mode on CPU —
identical kernel code to the compiled TPU path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import flash_attention
from dedloc_tpu.parallel.ring_attention import dense_attention


def _qkv(rng, b=2, s=128, h=2, d=32, dtype=jnp.float32):
    shape = (b, s, h, d)
    q = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return q, k, v


def test_forward_matches_dense(rng):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, block_q=64, block_k=32)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_with_mask_bias(rng):
    q, k, v = _qkv(rng)
    mask = jnp.asarray(rng.random((2, 128)) > 0.3, jnp.int32)
    mask = mask.at[:, 0].set(1)  # never fully masked
    bias = jnp.where(mask > 0, 0.0, -1e9).astype(jnp.float32)
    out = flash_attention(q, k, v, bias, block_q=64, block_k=32)
    ref = dense_attention(q, k, v, bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # masked KV positions must receive zero weight: perturbing them is a no-op
    v2 = v + jnp.where(mask[:, :, None, None] > 0, 0.0, 7.0)
    out2 = flash_attention(q, k, v2, bias, block_q=64, block_k=32)
    np.testing.assert_allclose(out, out2, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (64, 64)])
def test_gradients_match_dense(rng, block_q, block_k):
    # (64, 64) covers the whole sequence per tile -> the FUSED single-kernel
    # backward (_dqkv_fused_kernel), the path production seq-512 training
    # takes with the default block sizes; (32, 16) covers the tiled (one-sweep) path
    q, k, v = _qkv(rng, b=1, s=64, h=2, d=16)
    bias = jnp.zeros((1, 64))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, bias, block_q=block_q, block_k=block_k)
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, bias) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_gradients_with_mask(rng):
    q, k, v = _qkv(rng, b=1, s=64, h=1, d=16)
    mask = np.ones((1, 64), np.float32)
    mask[:, 40:] = 0.0
    bias = jnp.where(jnp.asarray(mask) > 0, 0.0, -1e9)

    gf = jax.grad(
        lambda q: jnp.sum(flash_attention(q, k, v, bias, block_q=32, block_k=32))
    )(q)
    gd = jax.grad(
        lambda q: jnp.sum(dense_attention(q, k, v, bias))
    )(q)
    np.testing.assert_allclose(gf, gd, atol=5e-4, rtol=5e-4)


def test_odd_sequence_blocks(rng):
    # s=96: block sizes must shrink to divide (96 -> 32/24-ish powers)
    q, k, v = _qkv(rng, b=1, s=96, h=1, d=16)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [64, 128], ids=["tiles", "one_tile"])
def test_bfloat16_path(rng, block):
    q, k, v = _qkv(rng, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=block, block_k=block)
    ref = dense_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


def test_albert_flash_impl_matches_dense(rng):
    from dedloc_tpu.models.albert import AlbertConfig, AlbertForPreTraining

    ids = jnp.asarray(rng.integers(5, 500, (2, 64)), jnp.int32)
    outs = {}
    for impl in ("dense", "flash"):
        cfg = AlbertConfig.tiny(attention_impl=impl, dtype=jnp.float32)
        model = AlbertForPreTraining(cfg)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        outs[impl] = model.apply({"params": params}, ids)
    np.testing.assert_allclose(
        outs["dense"][0], outs["flash"][0], atol=1e-4, rtol=1e-4
    )


def test_flash_rejects_attention_dropout_in_training_only(rng):
    from dedloc_tpu.models.albert import AlbertConfig, AlbertForPreTraining

    cfg = AlbertConfig.tiny(attention_impl="flash", attention_dropout_prob=0.1)
    model = AlbertForPreTraining(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    # deterministic (eval/serving): dropout inactive — must work, so a
    # dense-trained model can be served with the fused impl
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    model.apply({"params": params}, ids, deterministic=True)
    # training mode: fused impls cannot apply attention dropout — fail loudly
    with pytest.raises(ValueError, match="attention dropout"):
        model.apply(
            {"params": params}, ids, deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(1)},
        )


@pytest.mark.parametrize("masked", [False, True], ids=["nobias", "mask"])
@pytest.mark.parametrize("block", [64, 32], ids=["one_tile", "tiles"])
@pytest.mark.parametrize("h,d", [(16, 64), (4, 16), (2, 128), (3, 64)])
def test_model_layout_matches_dense(rng, h, d, block, masked):
    """The kernels index [B, S, H·D] directly, a column block of adjacent
    heads at a time: 2 heads per 128 lanes at (16, 64), one at (2, 128), the
    whole width where the heads do not fill or divide into 128-lane blocks
    ((4, 16): 64 lanes; (3, 64): 192). Forward and all three gradients
    against dense attention; block 64 covers S (the fused backward), 32
    takes the tiled one."""
    b, s = 2, 64
    q, k, v = _qkv(rng, b=b, s=s, h=h, d=d)
    bias = None
    if masked:
        mask = np.ones((b, s), np.float32)
        mask[0, 40:] = 0.0
        mask[1, 5:9] = 0.0
        bias = jnp.where(jnp.asarray(mask) > 0, 0.0, -1e9)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    flash = lambda q, k, v: flash_attention(
        q, k, v, bias, block_q=block, block_k=block
    )
    dense = lambda q, k, v: dense_attention(q, k, v, bias)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masked", [False, True], ids=["nobias", "mask"])
@pytest.mark.parametrize(
    "h,d,dv", [(16, 64, 64), (4, 16, 16), (2, 128, 128), (3, 64, 64),
               (4, 24, 16)],
    ids=["16-64", "4-16", "2-128", "3-64", "4-24-16"],
)
def test_one_tile_forward_is_the_tiled_kernel_at_one_tile(rng, h, d, dv,
                                                          masked, causal):
    """Where one tile covers the sequence ``_fwd`` takes a kernel with no
    online-softmax state (as ``_bwd`` takes the fused backward). It is the
    same arithmetic in the same order — at one tile the tiled kernel's
    correction is exactly 0 and its accumulator exactly p·v — so ``out`` and
    the ``lse`` the backward reads are the same BITS, in bfloat16 and in
    float32, with a sample whose every key is masked (-1e30: the row's
    softmax is uniform) and one whose bias is -inf (the row comes out 0 and
    finite from both)."""
    from dedloc_tpu.ops.flash_attention import (
        _fwd_one_tile,
        _fwd_tiled,
        _Mask,
    )

    b, s = 3, 64
    bias = np.zeros((b, 1, s), np.float32)
    if masked:
        bias[0, 0, 40:] = -1e9
        bias[1] = -1e30
        bias[2] = -np.inf
    bias = jnp.asarray(bias)
    for dtype in (jnp.bfloat16, jnp.float32):
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, s, h * width)), dtype)
            for width in (d, d, dv)
        )
        out, lse = _fwd_one_tile(q, k, v, bias, d, dv, _Mask(causal), True)
        want_out, want_lse = _fwd_tiled(
            q, k, v, bias, d, dv, s, s, _Mask(causal), True
        )
        assert out.dtype == dtype and lse.dtype == jnp.float32
        np.testing.assert_array_equal(
            out.astype(jnp.float32), want_out.astype(jnp.float32)
        )
        np.testing.assert_array_equal(lse, want_lse)
        assert np.isfinite(np.asarray(out, np.float32)).all()


def test_forward_form_follows_the_shapes(rng):
    """The forward's form is chosen as the backward's is, from the shapes
    alone: one tile -> the one-tile forward beside the fused backward (a
    lowering carries the form in the kernel's metadata, which
    tools/tpu_aot.py counts); several tiles -> the online-softmax kernel
    beside the one-sweep backward. Both forms keep the kernel's name."""
    q, k, v = _qkv(rng, b=1, s=64, h=2, d=64)

    def traced(block):
        return str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=False
        ))))(q))

    one_tile, tiles = traced(64), traced(32)
    assert "flash_fwd" in one_tile and "flash_fwd" in tiles
    assert "one_tile" in one_tile and "flash_bwd_fused" in one_tile
    assert "one_tile" not in tiles and "flash_bwd_tiled" in tiles
    assert "bwd_dq" not in tiles and "bwd_dkv" not in tiles


def test_under_a_mesh_matches_one_device(rng):
    """On a multi-device mesh the op runs per shard under shard_map (batch
    over "data", heads over "model"): values and gradients must equal the
    unsharded call — this is the path every slice peer trains through."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    q, k, v = _qkv(rng, b=2, s=32, h=2, d=16)
    mask = np.ones((2, 32), np.float32)
    mask[1, 20:] = 0.0
    bias = jnp.where(jnp.asarray(mask) > 0, 0.0, -1e9)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss(mesh):
        return lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, bias, block_q=16, block_k=16, mesh=mesh)
            * w
        )

    qkv_sharding = NamedSharding(mesh, P("data", None, "model"))
    sharded = jax.jit(
        jax.value_and_grad(loss(mesh), argnums=(0, 1, 2)),
        in_shardings=(qkv_sharding,) * 3,
    )(q, k, v)
    local = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(sharded[0], local[0], rtol=1e-5)
    for a, b, name in zip(sharded[1], local[1], "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5,
                                   err_msg=f"d{name} mismatch")


# the four geometries the decoder cells run, in miniature:
# (query heads, kv heads, q/k width, v width, band)
_STATE_GEOMETRIES = {
    "d128_one_head_a_block": (2, 2, 128, 128, None),
    "d128_group_of_three_full": (6, 2, 128, 128, None),
    "d128_group_of_three_band": (6, 2, 128, 128, 80),
    "qk192_v128": (2, 2, 192, 128, None),
    "d64_two_heads_a_tile_grouped": (4, 2, 64, 64, None),
}


@pytest.mark.parametrize(
    "h,kv,d,dv,band", _STATE_GEOMETRIES.values(), ids=_STATE_GEOMETRIES.keys()
)
def test_tiled_forward_carries_its_state(rng, h, kv, d, dv, band):
    """The online-softmax state over a sweep of three and four key tiles,
    at each layout the state tiles take: a query tile whose every row's max
    is set in its FIRST key tile and never rises (the correction is the
    identity from then on), one whose max rises in its LAST, and a sample
    whose every score is -inf in every tile (it stays finite). ``out`` and ``lse`` against dense float32 attention; and where the
    shapes have a one-tile form, its bits from the tiled kernel at one
    tile, on the same operands."""
    from dedloc_tpu.ops.flash_attention import (
        _fwd_one_tile,
        _fwd_tiled,
        _mask_of,
    )

    s, block, big = 128, 32, 16.0
    q, k, v = (
        0.1 * rng.standard_normal((2, s, n, width)).astype(np.float32)
        for n, width in ((h, d), (kv, d), (kv, dv))
    )
    amp = np.sqrt(big * np.sqrt(d))  # a score of ``big`` where both carry it
    # query tile 2 (rows 64-95): key 20, in the first tile every row of it
    # visits (band or not), outscores all that follow
    q[0, 64:96, :, 0] += amp
    k[0, 20, :, 0] += amp
    # query tile 3 (rows 96-127): every row's own key, in the LAST tile it
    # visits, outscores the keys before it
    q[0, 96:, :, 1] += amp
    k[0, 96:, :, 1] += amp * (np.arange(32)[:, None] + 1) / 32
    bias = np.zeros((2, 1, s), np.float32)
    bias[1] = -np.inf
    mask = _mask_of(True, band, s)
    flat = [jnp.asarray(x.reshape(2, s, -1)) for x in (q, k, v)]

    out, lse = _fwd_tiled(*flat, jnp.asarray(bias), d, dv, block, block,
                          mask, True)
    out = np.asarray(out).reshape(2, s, h, dv)
    lse = np.asarray(lse).reshape(2, h, s)
    assert np.isfinite(out).all() and np.isfinite(lse).all()

    i = np.arange(s)
    seen = i[None, :] <= i[:, None]
    if band is not None:
        seen &= i[:, None] - i[None, :] < band
    group = h // kv
    for head in range(h):
        x = q[0, :, head] @ k[0, :, head // group].T / np.sqrt(np.float32(d))
        x = np.where(seen, x, -np.inf).astype(np.float32)
        # the cases are what they claim: which key tiles raise a row's max
        rises = [
            [bool((x[rows, t * block:(t + 1) * block].max(-1)
                   > x[rows, :t * block].max(-1, initial=-np.inf)).any())
             for t in range(4)]
            for rows in (slice(64, 96), slice(96, 128))
        ]
        assert rises[0] == [True, False, False, False], rises
        assert rises[1][-1] and sum(rises[1]) >= 2, rises
        top = x.max(-1, keepdims=True)
        p = np.exp(x - top)
        want_lse = (top + np.log(p.sum(-1, keepdims=True)))[:, 0]
        want = (p / p.sum(-1, keepdims=True)) @ v[0, :, head // group]
        np.testing.assert_allclose(out[0, :, head], want, atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_allclose(lse[0, head], want_lse, atol=2e-5,
                                   rtol=2e-5)

    if kv == h:  # a one-tile form exists: the same bits at one tile
        one, one_lse = _fwd_one_tile(*flat, jnp.asarray(bias), d, dv, mask,
                                     True)
        tiled, tiled_lse = _fwd_tiled(*flat, jnp.asarray(bias), d, dv, s, s,
                                      mask, True)
        np.testing.assert_array_equal(one, tiled)
        np.testing.assert_array_equal(one_lse, tiled_lse)


@pytest.mark.parametrize(
    "hp,kvb,d,dv,asked_mb",
    [(2, 2, 128, 128, None), (8, 8, 128, 128, 30.0), (8, 8, 192, 128, 32.0),
     (8, 2, 64, 64, 23.5), (7, 1, 128, 128, 23.75), (8, 1, 128, 128, 26.5)],
    ids=["two_heads", "ouro", "kanana2", "lfm2",
         "smallthinker_group_of_seven", "a_group_of_eight"],
)
def test_a_program_of_many_heads_asks_for_its_vmem(hp, kvb, d, dv, asked_mb):
    """The tiled forward's heads overlap, so the compiler holds a score
    tile a head on its stack: two heads at 512 x 512 stay inside its
    default limit and their call carries no compiler parameters; the eight
    heads (a whole group of seven) a cell's program takes since PR 58 ask
    for what they need (seven: 16.48 MB by the v5e compiler's own count, PR
    37, 23.75 MiB asked) — a fraction of what the backward holds."""
    from dedloc_tpu.ops.flash_attention import _fwd_vmem

    q = jax.ShapeDtypeStruct((1, 4096, hp * d), jnp.bfloat16)
    params = _fwd_vmem(q, 512, 512, hp, kvb, d, dv)
    if asked_mb is None:
        assert params is None
    else:
        assert params.vmem_limit_bytes == asked_mb * 2**20
