"""The two-width flash kernels (latent attention: q and k wider than v and
the result) in interpret mode against dense float32 causal attention:
forward and the three gradients, one tile and several; the window rule (a
head's products contract over, and land in, its own 128-lane tiles); and
equal widths staying on the programs, and the bits, they had."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import (
    _lanes,
    _segments,
    _window,
    flash_attention,
)


def _dense(q, k, v, causal=True):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _operands(rng, b, s, h, d, dv):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, dv)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, s, h, dv)), jnp.float32)
    return q, k, v, w


# (S, heads, q/k width, v width, block): one tile; several tiles with the
# whole width as the column block; at the published widths (two heads a
# column block of 384 and 256 lanes, two column blocks a program: each
# head's 256-lane q/k window and its own v tile) several tiles, and one
# (the one-tile forward and the fused backward on the windowed path)
SHAPES = [
    pytest.param(64, 4, 24, 16, 64, id="one_tile"),
    pytest.param(128, 4, 24, 16, 32, id="tiles"),
    pytest.param(256, 4, 192, 128, 128, id="tiles_192_128"),
    pytest.param(128, 4, 192, 128, 128, id="one_tile_192_128"),
]


@pytest.mark.parametrize("s,h,d,dv,block", SHAPES)
def test_forward_matches_dense(rng, s, h, d, dv, block):
    q, k, v, _w = _operands(rng, 2, s, h, d, dv)
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, _dense(q, k, v), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,h,d,dv,block", SHAPES)
def test_gradients_match_dense(rng, s, h, d, dv, block):
    q, k, v, w = _operands(rng, 1, s, h, d, dv)

    def flash_loss(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block
        ))

    def dense_loss(q, k, v):
        return jnp.sum(w * _dense(q, k, v))

    got = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("width,g,windows,segments", [
    # two heads share one lane tile: the window is the block
    pytest.param(64, 2, [(0, 128), (0, 128)], [(0, 128)], id="64x2"),
    pytest.param(128, 1, [(0, 128)], [(0, 128)], id="128x1"),
    # 384 lanes: head 0 in tiles 0-1, head 1 in tiles 1-2
    pytest.param(192, 2, [(0, 256), (128, 384)],
                 [(0, 128), (128, 256), (256, 384)], id="192x2"),
    # v beside a 192-wide q/k: the head's own tile
    pytest.param(128, 2, [(0, 128), (128, 256)], [(0, 128), (128, 256)],
                 id="128x2"),
    # not whole lane tiles (a tiny model's whole width): the block
    pytest.param(24, 4, [(0, 96)] * 4, [(0, 96)], id="24x4_whole_block"),
])
def test_window_is_the_heads_own_lane_tiles(width, g, windows, segments):
    got = [_window(i, width, g) for i in range(g)]
    assert [(w.start, w.stop) for w in got] == windows
    assert [(s.start, s.stop) for s in _segments(width, g)] == segments
    for i, w in enumerate(got):  # a window holds its head, whole
        assert w.start <= i * width and (i + 1) * width <= w.stop


def test_kernels_carry_their_windows():
    """What ``tools/tpu_aot.py`` prints as ``flash_windows``."""
    assert _lanes(192, 128, 2) == {
        "qk_window": 256, "qk_block": 384, "v_window": 128, "v_block": 256,
    }
    # window == block: no metadata, so the equal-width callers' programs
    # (XLA schedules around a call by its metadata too) stay what they were
    assert _lanes(64, 64, 2) is None and _lanes(128, 128, 1) is None

    def traced(d, dv):
        return str(jax.make_jaxpr(lambda q, v: flash_attention(
            q, q, v, causal=True, block_q=128, block_k=128, interpret=False
        ))(jnp.zeros((1, 256, 2, d)), jnp.zeros((1, 256, 2, dv))))

    assert "qk_window" in traced(192, 128) and "v_window" in traced(192, 128)
    assert "window" not in traced(128, 128)


def _digest(*arrays):
    sha = hashlib.sha256()
    for a in arrays:
        sha.update(np.asarray(a, np.float32).tobytes())
    return sha.hexdigest()[:16]


def _arithmetic_canary():
    """A matmul, an exp and two reductions through XLA:CPU, digested: the
    digests below were recorded where this reads ``d1640a6c75f66c81``."""
    rng = np.random.default_rng(30)
    a, b = (jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
            for _ in range(2))
    s = a @ b.T
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return _digest(p @ a, jnp.sum(p, axis=-1))


# out + dq + dk + dv of the PARENT's kernels (PR 29, d2d8a9f), interpret
# mode on the CPU: (heads, D, block of S=128, causal, dtype) -> digest
PARENT_DIGESTS = {
    (4, 64, 128, False, "float32"): "96486174ffddeb2b",
    (4, 64, 128, False, "bfloat16"): "9b2e692f5aa1b27a",
    (4, 64, 64, False, "float32"): "a501678d5824ab5d",
    (4, 64, 64, False, "bfloat16"): "ff64b0cda758b396",
    (2, 128, 128, True, "float32"): "85ca93aef8b9189f",
    (2, 128, 128, True, "bfloat16"): "ecbc1eea872a71c7",
    (2, 128, 64, True, "float32"): "97f1a99f3dc08428",
    (2, 128, 64, True, "bfloat16"): "44ca38bbdb68cac9",
}


@pytest.mark.parametrize(
    "h,d,block,causal,dtype", list(PARENT_DIGESTS),
    ids=["-".join(map(str, key)) for key in PARENT_DIGESTS],
)
def test_equal_widths_keep_their_bits(h, d, block, causal, dtype):
    """At D=64 (two heads a lane tile) and D=128 the window IS the column
    block, so the window rule leaves the equal-width kernels the arithmetic
    they had, in the order they had it: one tile and tiled, with a masked
    sample, the bits of the parent's out and three gradients."""
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


def test_not_causal_two_widths(rng):
    q, k, v, _w = _operands(rng, 1, 64, 2, 24, 16)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(
        out, _dense(q, k, v, causal=False), atol=2e-5, rtol=2e-5
    )


def _kernel_names(d, dv, s, block):
    q = jnp.zeros((1, s, 2, d), jnp.float32)
    v = jnp.zeros((1, s, 2, dv), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=False,
        ))

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, v))
    return {
        name for name in (
            "flash_causal_fwd", "flash_causal_bwd_tiled",
            "flash_causal_bwd_fused", "flash_mla_fwd", "flash_mla_bwd_tiled",
            "flash_mla_bwd_fused", "bwd_dq", "bwd_dkv",
        ) if name in text
    }


def test_kernel_names_follow_the_widths():
    """Equal widths stay the programs they were (``flash_causal_*``: what
    the accepted roofline metrics read); two widths get names of their own
    (``flash_mla_*``)."""
    assert _kernel_names(16, 16, 64, 32) == {
        "flash_causal_fwd", "flash_causal_bwd_tiled"
    }
    assert _kernel_names(16, 16, 64, 64) == {
        "flash_causal_fwd", "flash_causal_bwd_fused"
    }
    assert _kernel_names(24, 16, 64, 32) == {
        "flash_mla_fwd", "flash_mla_bwd_tiled"
    }
