"""The grouped-query flash kernels (fewer kv heads than query heads, k / v
read at their own width, dk / dv summed over a group inside the kernel) in
interpret mode against dense float32 attention with k / v repeated per
group: forward and the three gradients, groups of 1, 2 and 4, D=64 and 128,
one tile and several, bf16 through the packed lane rotation; group 1 staying
on the kernels, and the bits, it had; shapes the kernels do not take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import (
    _bwd_geometry,
    _fwd_geometry,
    flash_attention,
)
from tests.test_flash_mla import (
    PARENT_DIGESTS,
    _arithmetic_canary,
    _digest,
)


def _dense(q, k, v, causal=True):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _operands(rng, b, s, h, kv, d, dtype=jnp.float32):
    shapes = [(b, s, h, d), (b, s, kv, d), (b, s, kv, d), (b, s, h, d)]
    return [jnp.asarray(rng.standard_normal(x), dtype) for x in shapes]


# (heads, kv heads, D, S, block): groups of 1, 2, 4 at D=64 (two heads a
# lane tile: the kv head rotated into both halves) and D=128 (a kv head is
# a lane tile), one tile and tiled; 32 / 8 x 64 — the published counts —
# where a program's 4 query heads share one kv head and two programs a kv
# block; 16 / 2 x 64 with a group of 8 (the slot follows the program)
SHAPES = [
    pytest.param(8, 8, 64, 128, 64, id="group1_d64_tiles"),
    pytest.param(8, 4, 64, 128, 128, id="group2_d64_one_tile"),
    pytest.param(8, 4, 64, 256, 64, id="group2_d64_tiles"),
    pytest.param(8, 2, 64, 128, 128, id="group4_d64_one_tile"),
    pytest.param(8, 2, 64, 256, 64, id="group4_d64_tiles"),
    pytest.param(4, 2, 128, 128, 128, id="group2_d128_one_tile"),
    pytest.param(4, 1, 128, 256, 64, id="group4_d128_tiles"),
    pytest.param(32, 8, 64, 128, 64, id="published_32_over_8"),
    pytest.param(16, 2, 64, 128, 64, id="group8_d64_tiles"),
]


@pytest.mark.parametrize("h,kv,d,s,block", SHAPES)
def test_forward_and_gradients_match_dense(rng, h, kv, d, s, block):
    q, k, v, w = _operands(rng, 2, s, h, kv, d)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * w)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block)

    out = flash(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, _dense(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(_dense), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_bf16_and_a_masked_sample(rng, causal):
    """bf16 operands (the lane rotation then runs on rows packed in pairs)
    and the kv bias on top of the causal mask."""
    q, k, v, _w = _operands(rng, 2, 128, 8, 2, 64, jnp.bfloat16)
    bias = np.zeros((2, 128), np.float32)
    bias[1, 100:] = -1e9
    out = flash_attention(q, k, v, jnp.asarray(bias), causal=causal,
                          block_q=64, block_k=64)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    group = 4
    kk, vv = (jnp.repeat(x, group, axis=2) for x in f32[1:])
    s = jnp.einsum("bqhd,bkhd->bhqk", f32[0], kk) / 8.0 + bias[:, None, None]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv)
    err = float(jnp.linalg.norm(out.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 0.01, err


@pytest.mark.parametrize(
    "h,d,block,causal,dtype",
    [key for key in PARENT_DIGESTS if key[2] == 64],
    ids=["-".join(map(str, key)) for key in PARENT_DIGESTS if key[2] == 64],
)
def test_group_of_one_keeps_the_existing_kernels_bits(h, d, block, causal,
                                                      dtype):
    """With as many kv heads as heads a call is not grouped: the tiled
    kernels' out and three gradients are the digests recorded before the
    kernels took groups (``tests/test_flash_mla.PARENT_DIGESTS``)."""
    if _arithmetic_canary() != "d1640a6c75f66c81":
        pytest.skip("this CPU's XLA rounds differently from the one the "
                    "parent's digests were recorded on")
    rng = np.random.default_rng(30)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((2, 128, h, d)), dtype)
        for _ in range(4)
    )
    bias = np.zeros((2, 128), np.float32)
    bias[1, 100:] = -1e9

    def loss(q, k, v):
        out = flash_attention(q, k, v, jnp.asarray(bias), causal=causal,
                              block_q=block, block_k=block)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q, k, v
    )
    assert _digest(out, *grads) == PARENT_DIGESTS[h, d, block, causal, dtype]


def _names(h, kv, causal=True):
    q = jnp.zeros((1, 128, h, 64), jnp.float32)
    k = jnp.zeros((1, 128, kv, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64, interpret=False,
        ))

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, k))
    return {
        name for name in (
            "flash_causal_fwd", "flash_causal_bwd_tiled",
            "flash_gqa_fwd", "flash_gqa_bwd_tiled", "flash_gqa_full_fwd",
            "bwd_dq", "bwd_dkv",
        ) if name in text
    }, text


def test_kernel_names_and_metadata_follow_the_head_counts():
    """Grouped calls get names of their own (``flash_gqa_*``: what the new
    roofline metrics read) and carry their head counts; a call with as many
    kv heads as heads stays ``flash_causal_*`` and carries nothing."""
    names, text = _names(8, 2)
    # ONE backward kernel a tiled call: no dq / dkv pair under any name
    assert names == {"flash_gqa_fwd", "flash_gqa_bwd_tiled"}
    assert "kv_heads" in text
    names, text = _names(8, 8)
    assert names == {"flash_causal_fwd", "flash_causal_bwd_tiled"}
    assert "kv_heads" not in text
    assert "flash_gqa_full_fwd" in _names(8, 2, causal=False)[0]


def test_the_kv_block_plan():
    """(query heads a program, group, kv heads a kv block) of a call at the
    cells' tiles, forward | backward: a program's kv block is whole column
    blocks; a program takes a whole kv block's query heads where eight
    heads hold them (and the VMEM it asks for holds THEM), and shares the
    block with other programs where they do not."""
    def plan(h, kv, d, seq=4096):
        q = jax.ShapeDtypeStruct((1, seq, h * d), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, seq, kv * d), jnp.bfloat16)
        return tuple(
            geometry(q, k, d, d, 512, 512)[i]
            for geometry in (_fwd_geometry, _bwd_geometry) for i in (4, 7, 8)
        )

    assert plan(32, 32, 64) == (8, 1, 8) * 2
    assert plan(32, 8, 64) == (8, 4, 2) * 2  # one program a kv block
    assert plan(32, 16, 64) == (8, 2, 4) * 2  # one program, four kv heads
    assert plan(16, 4, 128) == (8, 4, 2) * 2  # two whole groups of four
    assert plan(16, 1, 128) == (8, 16, 1) * 2  # two programs a kv block
    # dk / dv of eight kv heads of 128 do not fit at S=16,384 beside eight
    # heads' transients: four heads over their two kv heads
    assert plan(16, 8, 128, 16384) == (8, 2, 4, 4, 2, 2)


@pytest.mark.parametrize(
    "h,kv,d,dv", [(4, 2, 16, 16), (8, 4, 192, 128), (6, 4, 64, 64),
                  (8, 1, 64, 64), (6, 2, 64, 64)],
    ids=["narrow_heads", "two_widths", "not_a_divisor", "one_kv_head_d64",
         "group_of_3_d64"],
)
def test_shapes_the_grouped_kernels_do_not_take(h, kv, d, dv):
    q = jnp.zeros((1, 64, h, d), jnp.float32)
    k = jnp.zeros((1, 64, kv, d), jnp.float32)
    v = jnp.zeros((1, 64, kv, dv), jnp.float32)
    with pytest.raises(ValueError, match="grouped-query"):
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32)


def test_a_row_of_32768_still_compiles_at_four_heads_a_program():
    """The degrade path through Mosaic: at twice the cells' longest row dk /
    dv of a kv block leave no room for a group of eight's transients, and
    the backward compiles for a v5e at FOUR heads a program inside the
    112 MiB a core can be asked for; the forward keeps the eight."""
    from tests.tpu_aot_rows import tpu_aot

    row = tpu_aot("long_row_kernels")["long_row_kernels"]
    assert row["flash_heads"] == {
        "flash_gqa_fwd": 8, "flash_gqa_bwd_tiled": 4,
    }
    assert row["flash_vmem_mb"] == {
        "flash_gqa_fwd": 26.5, "flash_gqa_bwd_tiled": 91.5,
    }
