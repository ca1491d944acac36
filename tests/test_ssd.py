"""Mamba-2's scan as a kernel pair (ops/ssd.py) under ``interpret=True``
against the float32 token-by-token recurrence: the output and all six
gradients (x, dt, a, B, C, D) at two chunk counts, two groups and a batch,
in float32 (the algebra, tight) and in bf16 (the rounding, loose); a decay
of e^-30 a step (only differences are exponentiated); dt = 0 leaves the
state untouched and y = D x; a = 0 under a repeated unit B is a running sum
of dt x (plain linear attention); the state crosses a chunk's edge; the
state that leaves the row; B and C are a GROUP's; a ragged row; a head of a
whole lane tile; a group that is no whole tile is refused."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.ssd import ssd, ssd_recurrence

CHUNK = 32  # the tests' chunk: the model's is 128
NAMES = "x dt a B C D".split()


def _operands(batch, seq, heads, dim, groups, state, dtype=jnp.float32,
              seed=0, decay=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (batch, seq, heads, dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)) - 2)
    # a rate per head from e^-1 to e^2 a unit of dt, times ``decay``
    rate = jnp.exp(jax.random.uniform(keys[2], (heads,), minval=-1, maxval=2))
    a = -decay * rate * dt
    B = jax.random.normal(keys[3], (batch, seq, groups, state)) * state ** -0.5
    C = jax.random.normal(keys[4], (batch, seq, groups, state))
    D = 1.0 + 0.1 * jax.random.normal(keys[5], (heads,))
    weight = jax.random.normal(keys[6], (batch, seq, heads, dim))
    return (x.astype(dtype), dt, a, B.astype(dtype), C.astype(dtype), D), weight


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _value_and_grads(fn, operands, weight):
    def loss(*xs):
        out = fn(*xs)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True
    )(*operands)
    return out, grads


def _kernel(*xs, **kw):
    return ssd(*xs, chunk=CHUNK, **kw)


@pytest.mark.parametrize(
    "batch,seq,heads,dim,groups,state,dtype,tolerance",
    [(2, 2 * CHUNK, 8, 16, 2, 16, jnp.float32, 2e-5),
     (1, 4 * CHUNK, 4, 32, 1, 32, jnp.float32, 2e-5),
     (2, 2 * CHUNK, 4, 16, 2, 16, jnp.bfloat16, 2e-2)],
    ids=["float32_two_chunks_two_groups_batch", "float32_four_chunks",
         "bf16_two_chunks"],
)
def test_kernels_match_the_recurrence(batch, seq, heads, dim, groups, state,
                                      dtype, tolerance):
    operands, weight = _operands(batch, seq, heads, dim, groups, state, dtype)
    out, grads = _value_and_grads(_kernel, operands, weight)
    rounded = tuple(v.astype(jnp.float32) for v in operands)
    ref_out, ref_grads = _value_and_grads(ssd_recurrence, rounded, weight)
    assert out.dtype == dtype and _rel(out, ref_out) < tolerance
    for name, grad, ref in zip(NAMES, grads, ref_grads):
        assert grad.shape == ref.shape, name
        assert _rel(grad, ref) < 3 * tolerance, name


def test_a_decay_of_e_minus_30_a_step_stays_finite_and_exact():
    """a down to -30 a step: a chunk of it is e^-960 and ``e^{-G}`` is
    infinite in any float; only non-positive differences are exponentiated."""
    operands, weight = _operands(1, 2 * CHUNK, 4, 16, 2, 16)
    x, dt, _a, B, C, D = operands
    a = -30.0 - jnp.abs(_a)
    operands = (x, dt, a, B, C, D)
    out, grads = _value_and_grads(_kernel, operands, weight)
    ref_out, ref_grads = _value_and_grads(ssd_recurrence, operands, weight)
    for name, got, ref in zip(["y"] + NAMES, (out,) + grads,
                              (ref_out,) + ref_grads):
        assert bool(jnp.all(jnp.isfinite(got))), name
        assert _rel(got, ref) < 1e-4 or float(jnp.max(jnp.abs(ref))) < 1e-9, name


def test_a_time_step_of_zero_writes_nothing():
    """dt = 0 (and so a = 0): the state stays zero and y = D x."""
    (x, dt, a, B, C, D), _ = _operands(1, 2 * CHUNK, 4, 16, 2, 16)
    y, state = _kernel(x, 0 * dt, 0 * a, B, C, D, return_state=True)
    np.testing.assert_allclose(y, D[:, None] * x, rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(state))) == 0.0


def test_no_decay_and_a_unit_key_is_a_running_sum():
    """a = 0, B = C = e_0, D = 0: y_t = Σ_{s<=t} dt_s x_s — plain linear
    attention, over the chunk's edge too."""
    (x, dt, a, B, C, D), _ = _operands(1, 3 * CHUNK, 2, 16, 1, 16)
    unit = jnp.zeros_like(B).at[..., 0].set(1.0)
    y = _kernel(x, dt, 0 * a, unit, unit, 0 * D)
    want = jnp.cumsum(dt[..., None] * x, axis=1)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)


def test_the_state_crosses_a_chunk_edge_and_leaves_the_row():
    """A token of the first chunk is read in the second; the state the
    kernel reports is the recurrence's."""
    (x, dt, a, B, C, D), _ = _operands(2, 2 * CHUNK, 4, 16, 2, 16, seed=3)
    a = 0.05 * a  # slow decays: the first chunk is still there
    y, state = _kernel(x, dt, a, B, C, D, return_state=True)
    ref, ref_state = ssd_recurrence(x, dt, a, B, C, D, return_state=True)
    assert _rel(y, ref) < 2e-5 and _rel(state, ref_state) < 2e-5
    silenced = _kernel(x.at[:, :CHUNK].set(0.0), dt, a, B, C, D)
    assert _rel(silenced[:, CHUNK:], ref[:, CHUNK:]) > 1e-2


def test_keys_and_queries_are_a_groups():
    """The cotangent of ONE head alone lands on its group's B and C (and
    on nobody else's); the other heads of the group read the same B."""
    (x, dt, a, B, C, D), weight = _operands(1, 2 * CHUNK, 8, 16, 2, 16)
    only = jnp.zeros_like(weight).at[:, :, 5].set(weight[:, :, 5])  # group 1
    _out, grads = _value_and_grads(_kernel, (x, dt, a, B, C, D), only)
    _ref, ref_grads = _value_and_grads(ssd_recurrence, (x, dt, a, B, C, D), only)
    for name, got, ref in ((n, grads[i], ref_grads[i]) for i, n in
                           ((3, "B"), (4, "C"))):
        assert float(jnp.max(jnp.abs(got[:, :, 0]))) == 0.0, name
        assert float(jnp.max(jnp.abs(got[:, :, 1]))) > 0.0, name
        assert _rel(got, ref) < 1e-4, name
    # head 5's x alone moves under that cotangent
    assert float(jnp.max(jnp.abs(grads[0][:, :, 4]))) == 0.0
    assert float(jnp.max(jnp.abs(grads[0][:, :, 5]))) > 0.0


def test_a_ragged_row_is_padded_with_tokens_that_write_nothing():
    operands, weight = _operands(1, 2 * CHUNK - 5, 4, 16, 2, 16)
    out, grads = _value_and_grads(_kernel, operands, weight)
    ref_out, ref_grads = _value_and_grads(ssd_recurrence, operands, weight)
    assert out.shape == ref_out.shape and _rel(out, ref_out) < 2e-5
    for name, grad, ref in zip(NAMES, grads, ref_grads):
        assert _rel(grad, ref) < 6e-5, name


def test_a_head_of_a_whole_lane_tile_and_two_tiles_a_group():
    """dim 128: one head a tile (no heads side by side in a matmul); dim 64
    at four heads a group: two tiles of two."""
    for heads, dim, groups in ((2, 128, 2), (4, 64, 1)):
        operands, weight = _operands(1, CHUNK, heads, dim, groups, 16)
        out, grads = _value_and_grads(_kernel, operands, weight)
        ref_out, ref_grads = _value_and_grads(ssd_recurrence, operands, weight)
        assert _rel(out, ref_out) < 2e-5
        for name, grad, ref in zip(NAMES, grads, ref_grads):
            assert _rel(grad, ref) < 6e-5, (dim, name)


def test_a_group_that_is_no_whole_tile_is_refused():
    (x, dt, a, B, C, D), _ = _operands(1, CHUNK, 3, 64, 1, 16)
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        ssd(x, dt, a, B, C, D, chunk=CHUNK)
