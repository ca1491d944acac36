"""The doubly gated short convolution kernels in interpret mode against
``jax.grad`` of the shifted-sum form: forward, ``d_bcu`` (ONE array) and
``dw``; row-block edges (the halo rows on both sides), the first two
positions (zeros before the row's start), several column blocks; bf16; a
mesh; shapes the kernels do not take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops import short_conv as sc
from dedloc_tpu.ops.short_conv import short_conv, short_conv_reference


def _operands(rng, b, s, h, dtype=jnp.float32):
    bcu = jnp.asarray(rng.standard_normal((b, s, 3 * h)), dtype)
    w = jnp.asarray(rng.standard_normal((h, 3)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32)
    return bcu, w, t


# (rows, S, H, row tile, lane tile): one row block; several (the halo on
# both sides); several column blocks; the least rows a block takes
SHAPES = [
    pytest.param(2, 64, 32, 256, 512, id="one_block"),
    pytest.param(2, 64, 32, 16, 512, id="row_blocks_of_16"),
    pytest.param(1, 128, 256, 32, 128, id="row_and_column_blocks"),
    pytest.param(3, 16, 128, 256, 128, id="least_rows"),
]


@pytest.mark.parametrize("b,s,h,rows,lanes", SHAPES)
def test_forward_and_gradients_match_the_shifted_sum(rng, monkeypatch, b, s,
                                                     h, rows, lanes):
    monkeypatch.setattr(sc, "ROWS", rows)
    monkeypatch.setattr(sc, "LANES", lanes)
    bcu, w, t = _operands(rng, b, s, h)
    y = short_conv(bcu, w)
    assert y.shape == (b, s, h)
    np.testing.assert_allclose(
        y, short_conv_reference(bcu, w), atol=1e-5, rtol=1e-5
    )
    d_bcu, dw = jax.grad(
        lambda bcu, w: jnp.sum(short_conv(bcu, w) * t), (0, 1)
    )(bcu, w)
    want_bcu, want_w = jax.grad(
        lambda bcu, w: jnp.sum(short_conv_reference(bcu, w) * t), (0, 1)
    )(bcu, w)
    assert d_bcu.shape == bcu.shape and dw.shape == (h, 3)
    np.testing.assert_allclose(d_bcu, want_bcu, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(dw, want_w, atol=1e-3, rtol=1e-4)


def test_the_first_two_positions_and_causality(rng, monkeypatch):
    """y_0 = C_0 w2 z_0 and y_1 = C_1 (w1 z_0 + w2 z_1): zeros before the
    row's start, in every row of the batch; and position t sees nothing
    after t, across a row-block edge."""
    monkeypatch.setattr(sc, "ROWS", 16)
    bcu, w, _t = _operands(rng, 2, 48, 32)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    z = b * u
    y = short_conv(bcu, w)
    np.testing.assert_allclose(y[:, 0], c[:, 0] * w[:, 2] * z[:, 0],
                               atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(
        y[:, 1], c[:, 1] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1]),
        atol=1e-6, rtol=1e-5,
    )
    later = bcu.at[:, 17:].set(0.0)  # everything after position 16 changed
    np.testing.assert_array_equal(short_conv(later, w)[:, :17], y[:, :17])
    # and the gradient of position 15's output reaches back two positions,
    # across the block edge at 16, and no further
    grad = jax.grad(lambda x: jnp.sum(short_conv(x, w)[:, 17]))(bcu)
    reach = np.flatnonzero(np.abs(np.asarray(grad)).sum(axis=(0, 2)))
    assert list(reach) == [15, 16, 17]


def test_bf16_storage_float32_products(rng):
    bcu, w, t = _operands(rng, 1, 64, 128, jnp.bfloat16)
    f32 = bcu.astype(jnp.float32)
    y = short_conv(bcu, w)
    assert y.dtype == jnp.bfloat16
    want = short_conv_reference(f32, w)
    err = float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 4e-3, err  # one rounding of the result
    d_bcu, dw = jax.grad(
        lambda bcu, w: jnp.sum(short_conv(bcu, w).astype(jnp.float32) * t),
        (0, 1),
    )(bcu, w)
    want_bcu, want_w = jax.grad(
        lambda bcu, w: jnp.sum(short_conv_reference(bcu, w) * t), (0, 1)
    )(f32, w)
    assert d_bcu.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert float(jnp.linalg.norm(d_bcu.astype(jnp.float32) - want_bcu)
                 / jnp.linalg.norm(want_bcu)) < 6e-3
    # dw sums float32 products of the bf16 operands: no rounding of its own
    # beyond dy's
    assert float(jnp.linalg.norm(dw - want_w) / jnp.linalg.norm(want_w)) < 6e-3


def test_under_a_mesh(rng):
    from dedloc_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2)
    bcu, w, t = _operands(rng, 2, 32, 32)

    def loss(bcu, w):
        return jnp.sum(short_conv(bcu, w, mesh=mesh) * t)

    got = jax.jit(jax.grad(loss, (0, 1)))(bcu, w)
    want = jax.grad(
        lambda bcu, w: jnp.sum(short_conv_reference(bcu, w) * t), (0, 1)
    )(bcu, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "s,h", [(24, 32), (8, 128)], ids=["rows_not_in_16s", "fewer_than_16"]
)
def test_shapes_the_kernels_do_not_take(s, h):
    with pytest.raises(ValueError, match="whole tiles of 16"):
        short_conv(jnp.zeros((1, s, 3 * h)), jnp.zeros((h, 3)))
